"""Model FLOP/s utilisation of the compiled step while it runs: the
configuration's ``flops_per_record`` (forward + backward, fixed as data,
recomputation not counted) x the global batch, over device busy time per
step x chips x the peak of the benchmark's own table."""

from benchmark.readers import device_step


def peak(ctx, key: str) -> float:
    kinds = ctx["peaks"]["device_kinds"]
    if ctx["device_kind"] not in kinds:
        raise KeyError(f"no peaks for device kind {ctx['device_kind']!r}: "
                       f"add it to benchmark/peaks.json with its source")
    return kinds[ctx["device_kind"]][key]


def read(ctx):
    ms = device_step.read(ctx)
    if ms is None:
        return None
    flops = ctx["cell"]["config"]["flops_per_record"] * ctx["batch"]
    return 100.0 * flops / (ms * 1e-3 * ctx["chips"]
                            * peak(ctx, "bf16_flops_per_s"))
