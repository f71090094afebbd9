"""Scheduler: median wait from a request's due time to its first dispatch, over requests due in the traced window; moves ttft_p95_ms."""
from chipbench.readers import queue_wait_p50_ms as read  # noqa: F401
