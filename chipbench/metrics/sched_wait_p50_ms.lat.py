"""Scheduler: median time from the end of a request's scls.submit to the start of its first scls.slice, over the first dispatches made in the traced window; moves ttft_p95_ms."""
from chipbench.program_spans import sched_wait_p50_ms as read  # noqa: F401
