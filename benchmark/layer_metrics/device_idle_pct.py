"""Share of the traced window in which no operation ran on the busiest device."""
from benchmark.harness.readers import device_idle_pct as read  # noqa: F401
