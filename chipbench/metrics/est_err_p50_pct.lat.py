"""Scheduler: median absolute error of the Eq. 3/4 est_time of each scls.slice in the traced window, as a share of the span's measured time; moves ttft_p95_ms."""
from chipbench.program_spans import est_err_p50_pct as read  # noqa: F401
