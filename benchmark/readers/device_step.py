"""Device busy time per step: the union of the intervals in which an
operation ran on a device inside the traced window, averaged over the
chips, over the steps that completed in it."""

from benchmark import trace


def read(ctx):
    if not ctx["steps"]:
        return None
    busy = trace.busy_seconds(ctx["trace"], ctx["lo"], ctx["hi"])
    return 1e3 * busy / ctx["steps"] if busy > 0 else None
