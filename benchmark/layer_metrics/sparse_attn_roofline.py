"""Least time of the sparse layers' attention and selection in the traced window (the keys and values of the pages each lane selected and the compressed keys its lanes hold, read once at 819 GB/s, or the selected blocks' and the scoring's FLOPs at peak: harness/flops_sala.py::sparse_cost) over the summed device time of the operations the mix's sparse_op_match names."""
from benchmark.harness import flops_sala


def read(run):
    if run.trace is None or run.peaks is None:
        return None
    pattern = run.mix.get("sparse_op_match")
    c = run.counters
    if not pattern or not c.get("sparse_blocks_read"):
        return None
    secs = run.trace.op_seconds(pattern)
    if not secs:
        return None
    import jax.numpy as jnp
    fl, by = flops_sala.sparse_cost(
        run.cfg, c, jnp.dtype(run.cfg["torch_dtype"]).itemsize)
    least, _ = flops_sala.roofline_seconds(fl, by, run.peaks)
    return 100.0 * least / secs
