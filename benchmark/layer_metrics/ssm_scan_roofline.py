"""Least time of the selective scans of the traced window (state, inputs and outputs moved once at 819 GB/s, or their FLOPs at peak, a call) over the summed device time of the operations the mix's ssm_op_match names (the scan's named scope)."""
from benchmark.harness import flops_sambay


def read(run):
    if run.trace is None or run.peaks is None:
        return None
    pattern = run.mix.get("ssm_op_match")
    c = run.counters
    if not pattern or not c.get("ssm_layer_steps"):
        return None
    secs = run.trace.op_seconds(pattern)
    if not secs:
        return None
    least = flops_sambay.scan_least_seconds(
        run.cfg, c["ssm_rows_scanned"], c["ssm_lane_scans"], run.peaks)
    return 100.0 * least / secs
