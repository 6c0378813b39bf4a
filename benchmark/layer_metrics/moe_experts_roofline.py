"""Least time of the routed experts' products of the traced window (weights of the experts hit read once at 819 GB/s, or their FLOPs at peak) over the summed device time of the operations the mix's moe_op_match names."""
from benchmark.harness import flops_latent_moe


def read(run):
    if run.trace is None or run.peaks is None:
        return None
    pattern = run.mix.get("moe_op_match")
    c = run.counters
    if not pattern or not c.get("moe_experts_hit"):
        return None
    secs = run.trace.op_seconds(pattern)
    if not secs:
        return None
    import jax.numpy as jnp
    fl, by = flops_latent_moe.expert_stack_cost(
        run.cfg, c["moe_assignments"], c["moe_experts_hit"],
        jnp.dtype(run.cfg["torch_dtype"]).itemsize)
    least, _ = flops_latent_moe.roofline_seconds(fl, by, run.peaks)
    return 100.0 * least / secs
