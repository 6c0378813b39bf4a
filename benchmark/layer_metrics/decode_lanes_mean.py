"""Mean number of lanes that decoded in a step of the window: the engine's batch_size histogram, sum over count."""
from benchmark.harness.readers import decode_lanes_mean as read  # noqa: F401
