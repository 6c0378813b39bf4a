"""Host time a step that the device waits for: the engine loop's phases
``serving.schedule``, ``.pack``, ``.dispatch``, ``.emit``, ``.upkeep``
and the loop's ``serving.lock_wait``, summed over the spans that began in
the window, in ms over the window's ``serving.step`` spans. Leaves out
``serving.fetch`` (the device's own time) and ``serving.overlap`` (hidden
behind the device's step). Read from ``paddle_tpu.profiler.span_totals``;
``None`` where the program keeps no such spans or the window holds no
step."""

HOST_PHASES = ("serving.schedule", "serving.pack", "serving.dispatch",
               "serving.emit", "serving.upkeep", "serving.lock_wait")


def per_step_ms(run, names):
    """Summed seconds of the spans ``names`` that began in the run's
    window, in ms a ``serving.step`` begun in it."""
    try:
        from paddle_tpu.profiler import span_totals
    except ImportError:          # a program without the span store
        return None
    spans = span_totals(run.t0, run.t1)
    steps = spans.get("serving.step", (0, 0.0))[0]
    if not steps:
        return None
    return 1e3 * sum(spans.get(n, (0, 0.0))[1] for n in names) / steps


def read(run):
    return per_step_ms(run, HOST_PHASES)
