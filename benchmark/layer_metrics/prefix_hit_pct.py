"""Prompt pages served from the prefix cache over pages looked up, in the window."""
from benchmark.harness.readers import prefix_hit_pct as read  # noqa: F401
