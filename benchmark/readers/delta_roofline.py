"""Roofline share of the configuration's gated delta rule, whichever leg
ran it: the least time the chip could take for the calls of the traced
window (``benchmark/kernels/delta_rule.py``: the larger of FLOPs over the
bf16 peak and of the bytes of q, k, v, g, beta in and o out, or their
gradients, over the HBM peak) over the device time of the rule's events
(the configuration's ``delta_match``, merged so that an event inside
another counts once).

Calls are counted from the trace, so a forward computed again under
``nn.Remat`` counts as the call it is.  ``delta_kernels`` names the
events to count, by ``counts``: ``"fwd"`` and ``"bwd"`` match once a
forward or a backward call (a leg that is one custom call a direction);
``"scan"`` matches once a call of either direction and ``"scan_bwd"``
once a backward call among those (the XLA leg's scan over the chunks:
forward calls are the scans that are left).  Only calls whole inside
the window are counted while the time is clipped to it, so the share can
read low by the step the window cuts, never high.  A configuration without the keys, or a window in which
nothing matched, reports nothing."""

from benchmark import trace
from benchmark.kernels import delta_rule
from benchmark.readers import attention_roofline, mfu


def read(ctx):
    conf = ctx["cell"]["config"]
    if not conf.get("delta_kernels") or not conf.get("delta_match"):
        return None
    counted = dict.fromkeys(("fwd", "bwd", "scan", "scan_bwd"), 0.0)
    for k in conf["delta_kernels"]:
        counted[k["counts"]] += attention_roofline.calls(ctx, k["match"])[0]
    calls = {"fwd": counted["fwd"]
             + max(counted["scan"] - counted["scan_bwd"], 0.0),
             "bwd": counted["bwd"] + counted["scan_bwd"]}
    took = trace.matching_seconds(ctx["trace"], ctx["lo"], ctx["hi"],
                                  conf["delta_match"])
    least = sum(n * delta_rule.least_seconds(
        direction, mfu.peak(ctx, "bf16_flops_per_s"),
        mfu.peak(ctx, "hbm_bytes_per_s"), **conf["delta_kernel_args"])
        for direction, n in calls.items())
    return 100.0 * least / took if took > 0 and least > 0 else None
