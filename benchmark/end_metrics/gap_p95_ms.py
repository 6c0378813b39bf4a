"""95th percentile of every gap between consecutive tokens of every request of the window, pooled."""
from benchmark.harness.readers import gap_p95_ms as read  # noqa: F401
