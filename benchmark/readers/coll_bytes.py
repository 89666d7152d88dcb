"""Bytes the step's collectives move per step, on one chip: each
collective event of the device trace is named by its whole HLO
instruction, whose result shapes say how many bytes it reduces or
gathers.  A ``-done`` half of an asynchronous pair is not counted
again."""

import re

from benchmark import trace

_SHAPE = re.compile(r"\b(pred|[suf]\d+|bf16|f8\w*)\[([\d,]*)\]")
_BITS = {"pred": 8, "bf16": 16}


def result_bytes(name: str) -> int:
    """Bytes of the result of the HLO instruction ``name``."""
    m = trace.HLO.match(name)
    if not m:
        return 0
    total = 0
    for dtype, dims in _SHAPE.findall(trace.LAYOUT.sub("", m.group(2))):
        bits = _BITS.get(dtype) or (8 if dtype.startswith("f8")
                                    else int(dtype[1:]))
        n = 1
        for d in filter(None, dims.split(",")):
            n *= int(d)
        total += n * bits // 8
    return total


def read(ctx):
    if ctx["chips"] < 2 or not ctx["steps"] or not ctx["trace"].devices:
        return None
    total = 0
    for name, s, e in ctx["trace"].devices[0].ops:
        m = trace.HLO.match(name)
        if (m and trace.COLLECTIVE.search(m.group(3))
                and not m.group(3).endswith("-done")
                and ctx["lo"] <= s < ctx["hi"]):
            total += result_bytes(name)
    return total / ctx["steps"] / 1e6 if total else None
