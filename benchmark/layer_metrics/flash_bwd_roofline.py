"""Least time of the flash backward (dq, dk/dv) of the traced steps over the kernels' summed device time."""
from benchmark.harness.readers import flash_bwd_roofline as read  # noqa: F401
