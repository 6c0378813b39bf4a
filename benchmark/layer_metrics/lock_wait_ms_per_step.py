"""The engine loop's wait for the front-end's lock, a step: the seconds of
the ``serving.lock_wait`` spans that began in the window, in ms over the
window's ``serving.step`` spans. ``None`` where the program keeps no such
spans or the window holds no step."""
from benchmark.layer_metrics.host_ms_per_step import per_step_ms


def read(run):
    return per_step_ms(run, ("serving.lock_wait",))
