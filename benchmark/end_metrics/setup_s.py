"""Process start to the window's first timed operation."""
from benchmark.harness.readers import setup_s as read  # noqa: F401
