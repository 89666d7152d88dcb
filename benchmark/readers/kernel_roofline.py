"""Roofline share of the configuration's Pallas kernels, all of them
bound by memory: the least time the chip could take to move the bytes
each call must move (``benchmark/kernels/<kernel>.py``) at the peak of
the benchmark's own table, summed over the calls of the window, over
the device time those calls took."""

import importlib

from benchmark.readers import kernel_share, mfu


def read(ctx):
    secs = kernel_share.kernel_seconds(ctx)
    if not secs or not ctx["steps"]:
        return None
    bw = mfu.peak(ctx, "hbm_bytes_per_s")
    per_chip = ctx["batch"] // ctx["chips"]
    least = 0.0
    for k in ctx["cell"]["config"]["pallas_kernels"]:
        if k["name"] not in secs:
            continue
        fn = importlib.import_module("benchmark.kernels." + k["kernel"])
        calls = k.get("calls_per_step", 1) * ctx["steps"]
        least += calls * fn.least_bytes(per_chip, **k["args"]) / bw
    return 100.0 * least / sum(secs.values())
