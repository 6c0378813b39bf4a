"""As mfu_pct.rate for the latent-attention, sparse-expert family: the FLOPs that the traced window's work requires (harness/flops_latent_moe.py) over what the chip could do in the time it was busy."""
from benchmark.harness import flops_latent_moe


def read(run):
    if run.trace is None or run.peaks is None:
        return None
    need, busy = flops_latent_moe.serve_window_flops(run), run.trace.busy_s
    if not need or not busy:
        return None
    return 100.0 * need / (busy * run.chips * run.peaks["bf16_flops"])
