"""Front end: median time from a request's due time to the start of its scls.submit program span, over the submits made in the traced window; moves ttft_p95_ms."""
from chipbench.program_spans import submit_lag_p50_ms as read  # noqa: F401
