"""Roofline share of the configuration's multi-stream residual path,
whatever implements it: the least time the chip could take to move the
bytes the passes of the traced window need (``benchmark/kernels/mhc.py``:
a token's streams read once and written once a pass, bytes only, over the
HBM peak) over the device time of the path's own events (the
configuration's ``mhc_match``, merged so that an event inside another
counts once).

Passes are counted from the trace (``mhc_kernels``: one pattern a
``direction`` that matches once a sub-layer's pass, by an event only that
pass has: the projection's product a forward, its transpose a backward),
so a forward computed again under ``nn.Remat`` counts as the pass it is.
Only events whole inside the window are counted while the time is clipped
to it, so the share can read low by the step the window cuts, never high.
A configuration without the keys, a program whose trace holds no such
event (the parent of the PR that brought the path), or a window in which
nothing matched, reports nothing."""

from benchmark import trace
from benchmark.kernels import mhc
from benchmark.readers import attention_roofline, mfu


def read(ctx):
    conf = ctx["cell"]["config"]
    kernels, match = conf.get("mhc_kernels"), conf.get("mhc_match")
    if not kernels or not match:
        return None
    peak = mfu.peak(ctx, "hbm_bytes_per_s")
    least = sum(
        attention_roofline.calls(ctx, k["match"])[0]
        * mhc.least_seconds(k["direction"], peak, **conf["mhc_kernel_args"])
        for k in kernels)
    took = trace.matching_seconds(ctx["trace"], ctx["lo"], ctx["hi"], match)
    return 100.0 * least / took if took > 0 and least > 0 else None
