"""Roofline share of the configuration's gated short convolution,
whatever implements it: the least time the chip could take to move the
bytes the calls of the traced window need
(``benchmark/kernels/short_conv.py``, bytes only, over the HBM peak)
over the device time of the convolution's own events (the
configuration's ``shortconv_match``, merged so that an event inside
another counts once).

Calls are counted from the trace (``shortconv_kernels``: one pattern a
``direction`` that matches once a call), so a forward computed again
under ``nn.Remat`` counts as the call it is.  Only calls whole inside the
window are counted while the time is clipped to it, so the share can
read low by the step the window cuts, never high.  A configuration
without the keys, or a window in which nothing matched, reports
nothing."""

from benchmark import trace
from benchmark.kernels import short_conv
from benchmark.readers import attention_roofline, mfu


def read(ctx):
    conf = ctx["cell"]["config"]
    kernels, match = conf.get("shortconv_kernels"), conf.get("shortconv_match")
    if not kernels or not match:
        return None
    peak = mfu.peak(ctx, "hbm_bytes_per_s")
    least = sum(
        attention_roofline.calls(ctx, k["match"])[0]
        * short_conv.least_seconds(k["direction"], peak,
                                   **conf["shortconv_kernel_args"])
        for k in kernels)
    took = trace.matching_seconds(ctx["trace"], ctx["lo"], ctx["hi"], match)
    return 100.0 * least / took if took > 0 and least > 0 else None
