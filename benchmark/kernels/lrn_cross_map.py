"""Bytes cross-map LRN must move for ``[N, C, H, W]``: forward reads the
input and writes the output (the saved denominator is the kernel's own
choice, not counted); backward reads input, denominator or output, and
the incoming gradient, and writes the input's gradient."""


def least_bytes(batch: int, channels: int, hw: int, itemsize: int,
                direction: str) -> int:
    tensor = batch * channels * hw * hw * itemsize
    return {"fwd": 2, "bwd": 4}[direction] * tensor
