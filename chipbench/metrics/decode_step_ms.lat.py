"""Engine: decode milliseconds per step over the traced window's slices (slice time less prefill time, over steps); moves ttft_p95_ms."""
from chipbench.readers import decode_step_ms as read  # noqa: F401
