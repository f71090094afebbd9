"""Device: share of the traced window with no operation on the device; moves ttft_p95_ms."""
from chipbench.readers import idle_share as read  # noqa: F401
