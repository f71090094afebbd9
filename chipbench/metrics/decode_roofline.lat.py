"""Kernels: the decode-slice program's least HBM time over its device time (HBM bounds it); moves ttft_p95_ms."""
from chipbench.readers import decode_roofline as read  # noqa: F401
