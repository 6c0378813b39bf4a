"""Median device time of the step program that carries a prefill chunk beside the decode lanes (the slower of the two classes)."""
from benchmark.harness.readers import step_chunk_ms as read  # noqa: F401
