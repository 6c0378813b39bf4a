"""Blocks the sparse layers attended over the blocks live, in the window: the engine's sparse_blocks_read over sparse_blocks_live, each summed over the sparse layers, the live rows and the KV heads. What the block selection saves over dense attention; None where the program counts no selection."""


def read(run):
    live = run.counters.get("sparse_blocks_live")
    return run.counters.get("sparse_blocks_read", 0.0) / live if live else None
