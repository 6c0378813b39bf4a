"""Least time of the lightning layers in the traced window (each live lane's float32 state read and written once a layer-step at 819 GB/s, or the recurrence's and the chunkwise form's FLOPs at peak: harness/flops_sala.py::lightning_cost) over the summed device time of the operations the mix's lightning_op_match names."""
from benchmark.harness import flops_sala


def read(run):
    if run.trace is None or run.peaks is None:
        return None
    pattern = run.mix.get("lightning_op_match")
    c = run.counters
    if not pattern or not c.get("lightning_layer_steps"):
        return None
    secs = run.trace.op_seconds(pattern)
    if not secs:
        return None
    fl, by = flops_sala.lightning_cost(run.cfg, c)
    least, _ = flops_sala.roofline_seconds(fl, by, run.peaks)
    return 100.0 * least / secs
