"""Matmul and attention FLOPs that the traced window's work requires, over what the chips could do in the time they were busy."""
from benchmark.harness.readers import mfu_pct as read  # noqa: F401
