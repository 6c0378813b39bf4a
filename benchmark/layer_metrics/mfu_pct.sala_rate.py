"""As mfu_pct.rate for MiniCPM-SALA: the FLOPs that the traced window's work requires (harness/flops_sala.py::window_flops, from the engine's counters: every decode and chunk row through the matmuls, the head once a token served, the sparse layers by the blocks they selected, never dense, and the lightning layers) over what the chip could do in the time it was busy."""
from benchmark.harness import flops_sala


def read(run):
    if run.trace is None or run.peaks is None:
        return None
    need, busy = flops_sala.window_flops(run.cfg, run.counters), \
        run.trace.busy_s
    if not need or not busy:
        return None
    return 100.0 * need / (busy * run.chips * run.peaks["bf16_flops"])
