"""95th percentile, over every request due in the window, of due time to first token event on the client's clock."""
from benchmark.harness.readers import ttft_p95_ms as read  # noqa: F401
