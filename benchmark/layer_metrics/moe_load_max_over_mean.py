"""The fullest expert's tokens (mean over layer-steps) over the mean tokens of an expert hit, over the window: (moe_expert_load_max / moe_layer_steps) / (moe_assignments / moe_experts_hit)."""


def read(run):
    c = run.counters
    hit, assigned, steps = (c.get("moe_experts_hit"),
                            c.get("moe_assignments"),
                            c.get("moe_layer_steps"))
    if not hit or not assigned or not steps:
        return None
    return (c.get("moe_expert_load_max", 0.0) / steps) * hit / assigned
