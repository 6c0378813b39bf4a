"""Least time of the delta rule of the traced window (each live lane's float32 state read and written once a KDA layer-step at 819 GB/s, or the recurrence's and the chunkwise form's FLOPs at peak: harness/flops_kda_moe.py::delta_cost) over the summed device time of the operations the mix's kda_op_match names (the chunk form's loops and the operations on state-shaped arrays)."""
from benchmark.harness import flops_kda_moe


def read(run):
    if run.trace is None or run.peaks is None:
        return None
    pattern = run.mix.get("kda_op_match")
    c = run.counters
    if not pattern or not c.get("delta_layer_steps"):
        return None
    secs = run.trace.op_seconds(pattern)
    if not secs:
        return None
    fl, by = flops_kda_moe.delta_cost(run.cfg, c["delta_lane_steps"],
                                      c.get("delta_rows_chunked", 0.0))
    least, _ = flops_kda_moe.roofline_seconds(fl, by, run.peaks)
    return 100.0 * least / secs
