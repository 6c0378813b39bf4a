"""Distinct routed experts (of the layer's 256) that had a token, a layer a step, over the window: the engine's moe_experts_hit over moe_layer_steps."""


def read(run):
    steps = run.counters.get("moe_layer_steps")
    return run.counters.get("moe_experts_hit", 0.0) / steps if steps else None
