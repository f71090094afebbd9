"""Engine: share of the traced window's scls.slice time outside its prefill, prefill_tail and decode stages (host bookkeeping inside a slice); moves ttft_p95_ms."""
from chipbench.program_spans import slice_host_share as read  # noqa: F401
