"""Roofline share of the configuration's Mamba-2 state-space scan,
whichever leg ran it: the least time the chip could take for the calls of
the traced window (``benchmark/kernels/ssd.py``: the larger of FLOPs over
the bf16 peak and of the bytes of x, dt, B, C in and y out, or their
gradients, over the HBM peak) over the device time of the scan's OWN
events (the configuration's ``ssd_scan_match``: the carry's ``while`` and
every event that reads or writes a chunked shape, merged so that an
event inside another counts once).  The mixer's convolution, its bias and
SiLU and the gated group norm are in ``ssd_match``, which the share
reads, and not here: the least time counts none of their bytes, and a
scan at its roofline has to be able to read 100.

Calls are counted from the trace, so a forward computed again under
``nn.Remat`` counts as the call it is.  ``ssd_kernels`` names the events
to count, by ``counts``: ``"fwd"`` and ``"bwd"`` match once a forward or
a backward call (a leg that is one custom call a direction); ``"scan"``
matches once a call of either direction and ``"scan_bwd"`` once a
backward call among those (the XLA leg's scan over the chunks: forward
calls are the scans that are left).  Only calls whole inside the window
are counted while the time is clipped to it, so the share can read low by
the step the window cuts, never high.  A configuration without the keys,
a program whose trace holds no such event (the parent of the PR that
brought the scan), or a window in which nothing matched, reports
nothing."""

from benchmark import trace
from benchmark.kernels import ssd
from benchmark.readers import attention_roofline, mfu


def read(ctx):
    conf = ctx["cell"]["config"]
    if not conf.get("ssd_kernels") or not conf.get("ssd_scan_match"):
        return None
    counted = dict.fromkeys(("fwd", "bwd", "scan", "scan_bwd"), 0.0)
    for k in conf["ssd_kernels"]:
        counted[k["counts"]] += attention_roofline.calls(ctx, k["match"])[0]
    calls = {"fwd": counted["fwd"]
             + max(counted["scan"] - counted["scan_bwd"], 0.0),
             "bwd": counted["bwd"] + counted["scan_bwd"]}
    took = trace.matching_seconds(ctx["trace"], ctx["lo"], ctx["hi"],
                                  conf["ssd_scan_match"])
    least = sum(n * ssd.least_seconds(
        direction, mfu.peak(ctx, "bf16_flops_per_s"),
        mfu.peak(ctx, "hbm_bytes_per_s"), **conf["ssd_kernel_args"])
        for direction, n in calls.items())
    return 100.0 * least / took if took > 0 and least > 0 else None
