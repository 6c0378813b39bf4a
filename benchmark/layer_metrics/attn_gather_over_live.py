"""Page-table entries the step's attention gathered over the pages its live lanes held, in the window: the engine's attn_pages_gathered over attn_pages_live. What padded tables, and several layers that each gather one pool, cost."""


def read(run):
    live = run.counters.get("attn_pages_live")
    return run.counters.get("attn_pages_gathered", 0.0) / live if live else None
