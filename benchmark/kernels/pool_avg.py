"""Bytes the 7x7/s1 average pool must move for ``[N, C, H, W]``: forward
reads every plane and writes one value for each; backward reads one
value for each plane and writes the plane."""


def least_bytes(batch: int, channels: int, hw: int, itemsize: int,
                direction: str) -> int:
    planes = batch * channels
    return planes * (hw * hw + 1) * itemsize
