"""Per step, the time in which a collective ran on a device and no other
operation did (averaged over the chips)."""

from benchmark import trace


def read(ctx):
    if ctx["chips"] < 2 or not ctx["steps"]:
        return None
    s = trace.exposed_collective_seconds(ctx["trace"], ctx["lo"], ctx["hi"])
    return 1e3 * s / ctx["steps"]
