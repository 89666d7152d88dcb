"""Share of device busy time in the events whose name matches patterns
the configuration gives: ``key`` names either one regular expression or
a list of entries with a ``match`` each (merged per device, so nesting
does not count twice).  A configuration without the key, or a window in
which nothing matched, reports nothing."""

from benchmark import trace


def read(ctx, key: str):
    spec = ctx["cell"]["config"].get(key)
    if not spec:
        return None
    if not isinstance(spec, str):
        spec = "|".join(f"(?:{k['match']})" for k in spec)
    busy = trace.busy_seconds(ctx["trace"], ctx["lo"], ctx["hi"])
    secs = trace.matching_seconds(ctx["trace"], ctx["lo"], ctx["hi"], spec)
    return 100.0 * secs / busy if busy > 0 and secs > 0 else None
