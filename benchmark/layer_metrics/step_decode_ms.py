"""Median device time of the decode-only step program in the traced window (the faster of the engine's two step classes)."""
from benchmark.harness.readers import step_decode_ms as read  # noqa: F401
