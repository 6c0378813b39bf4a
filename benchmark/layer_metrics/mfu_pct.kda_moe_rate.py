"""As mfu_pct.rate for the Kimi-delta-attention / latent-attention hybrid with a share of group-routed experts: the FLOPs that the traced window's work requires (harness/flops_kda_moe.py: matmuls, the held experts at their share, the MLA layer's attention, the delta rule, the head) over what the chip could do in the time it was busy."""
from benchmark.harness import flops_kda_moe


def read(run):
    if run.trace is None or run.peaks is None:
        return None
    need, busy = flops_kda_moe.serve_window_flops(run), run.trace.busy_s
    if not need or not busy:
        return None
    return 100.0 * need / (busy * run.chips * run.peaks["bf16_flops"])
