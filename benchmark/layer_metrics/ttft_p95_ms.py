"""95th percentile, over every request due in the window, of due time to first token event on the client's clock (per layer since PR 32: its runs spread past any bound an end-to-end metric may have)."""
from benchmark.harness.readers import ttft_p95_ms as read  # noqa: F401
