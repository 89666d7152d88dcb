"""Roofline share of one family of the configuration's attention
kernels (``attention_kernels`` entries of that ``family``: forward, dq,
dkv): for every call that ran whole inside the traced window, the least
time the chip could take (``benchmark/kernels/attention.py``: the larger
of FLOPs over the bf16 peak and bytes over the HBM peak, score elements
counted exactly under the masks), summed, over the device time those
calls took.  Calls are counted from the trace, so recomputed forwards
count as the calls they are."""

import re

from benchmark.kernels import attention
from benchmark.readers import mfu


def calls(ctx, match: str):
    """``(count, device seconds)`` of the events that match and lie
    whole inside the window, averaged over the devices."""
    rx = re.compile(match)
    n = secs = 0.0
    for d in ctx["trace"].devices:
        for name, s, e in d.ops:
            if ctx["lo"] <= s and e <= ctx["hi"] and rx.search(name):
                n += 1
                secs += e - s
    k = max(len(ctx["trace"].devices), 1)
    return n / k, secs / k


def read(ctx, family: str):
    conf = ctx["cell"]["config"]
    kernels = [k for k in conf.get("attention_kernels", [])
               if k["family"] == family]
    if not kernels:
        return None
    shape = conf["attention_kernel_args"][family]
    least = took = 0.0
    for k in kernels:
        n, secs = calls(ctx, k["match"])
        least += n * attention.least_seconds(
            k["direction"], mfu.peak(ctx, "bf16_flops_per_s"),
            mfu.peak(ctx, "hbm_bytes_per_s"), **shape)
        took += secs
    return 100.0 * least / took if took > 0 else None
