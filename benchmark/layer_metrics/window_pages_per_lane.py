"""Pages of a window layer's pool that a live lane held, a window layer a step, over the window: the engine's window_pages_held over window_layer_steps (each counted a live lane). At most ceil((window + prefill_chunk) / page) + 1 where the allocator releases the pages behind the window."""


def read(run):
    steps = run.counters.get("window_layer_steps")
    return run.counters.get("window_pages_held", 0.0) / steps if steps else None
