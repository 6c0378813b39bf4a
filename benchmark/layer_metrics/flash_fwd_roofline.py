"""Least time of the flash forward calls of the traced steps over their summed device time."""
from benchmark.harness.readers import flash_fwd_roofline as read  # noqa: F401
