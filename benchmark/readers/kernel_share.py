"""Share of device busy time spent in the configuration's Pallas
kernels: device time of the events whose name matches a kernel's
``match`` pattern, over device busy time."""

from benchmark import trace


def kernel_seconds(ctx):
    """{kernel entry name: device seconds}; empty when the configuration
    names no kernel or none ran (every op on its XLA leg)."""
    out = {}
    for k in ctx["cell"]["config"].get("pallas_kernels", []):
        s = trace.matching_seconds(ctx["trace"], ctx["lo"], ctx["hi"],
                                   k["match"])
        if s > 0:
            out[k["name"]] = s
    return out


def read(ctx):
    busy = trace.busy_seconds(ctx["trace"], ctx["lo"], ctx["hi"])
    secs = kernel_seconds(ctx)
    if busy <= 0 or not secs:
        return None
    pattern = "|".join(f"(?:{k['match']})"
                       for k in ctx["cell"]["config"]["pallas_kernels"])
    return 100.0 * trace.matching_seconds(
        ctx["trace"], ctx["lo"], ctx["hi"], pattern) / busy
