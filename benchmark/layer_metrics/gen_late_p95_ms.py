"""How late the generator sent: 95th percentile of sent - due."""
from benchmark.harness.readers import gen_late_p95_ms as read  # noqa: F401
