"""Mean device time of one step: summed time of the step program's executions in the traced window over the steps they made."""
from benchmark.harness.readers import step_ms as read  # noqa: F401
