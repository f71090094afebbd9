"""Model step: model FLOPs of the traced window's slices over the window times the chip's bf16 peak; moves ttft_p95_ms."""
from chipbench.readers import mfu as read  # noqa: F401
