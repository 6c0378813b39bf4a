"""As mfu_pct.rate for the state-space / differential-attention hybrid: the FLOPs that the traced window's work requires (harness/flops_sambay.py: matmuls, both softmaxes' products, the scan's multiply-adds, the head) over what the chip could do in the time it was busy."""
from benchmark.harness import flops_sambay


def read(run):
    if run.trace is None or run.peaks is None:
        return None
    need, busy = flops_sambay.serve_window_flops(run), run.trace.busy_s
    if not need or not busy:
        return None
    return 100.0 * need / (busy * run.chips * run.peaks["bf16_flops"])
