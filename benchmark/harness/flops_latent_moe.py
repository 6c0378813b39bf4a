"""Operations and bytes that the work of the latent-attention,
sparse-expert family requires, counted from shapes (``flops.py`` counts
the Llama shape). ``cfg`` is a configuration file's dict.

Only matrix multiplications and attention count (2 FLOPs a multiply-add).
Attention is counted in the *published* (expanded) form whichever form
the program runs: a query-key pair costs ``2 * heads * (nope + rope + v)``
and a token's share of ``W_kvb`` is counted once, where it is cached.
A token meets ``num_experts_per_tok`` routed experts and the shared ones,
never the rest.
"""
from __future__ import annotations

from .flops import roofline_seconds, span_keys  # noqa: F401


def attention_params(cfg) -> int:
    """The five projections of one layer's latent attention."""
    h, nh = cfg["hidden_size"], cfg["num_attention_heads"]
    rq, rkv = cfg["q_lora_rank"], cfg["kv_lora_rank"]
    dn, dr, dv = (cfg["qk_nope_head_dim"], cfg["qk_rope_head_dim"],
                  cfg["v_head_dim"])
    return (h * rq + rq * nh * (dn + dr) + h * (rkv + dr)
            + rkv * nh * (dn + dv) + nh * dv * h)


def expert_params(cfg) -> int:
    """One routed (or shared) expert: three matrices."""
    return 3 * cfg["hidden_size"] * cfg["moe_intermediate_size"]


def layer_token_params(cfg, layer: int) -> int:
    """Weights one token is multiplied with in layer ``layer``."""
    h = cfg["hidden_size"]
    if layer < cfg["first_k_dense_replace"]:
        ffn = 3 * h * cfg["intermediate_size"]
    else:
        ffn = h * cfg["n_routed_experts"] + expert_params(cfg) * (
            cfg["num_experts_per_tok"] + cfg["n_shared_experts"])
    return attention_params(cfg) + ffn


def token_matmul_flops(cfg, head: bool = True) -> int:
    """Forward matmul FLOPs of one token."""
    n = sum(layer_token_params(cfg, i)
            for i in range(cfg["num_hidden_layers"]))
    return 2 * (n + (cfg["hidden_size"] * cfg["vocab_size"] if head else 0))


def pair_flops(cfg) -> int:
    """One query-key pair in one layer: the score and the weighted value,
    every head."""
    return 2 * cfg["num_attention_heads"] * (
        cfg["qk_nope_head_dim"] + cfg["qk_rope_head_dim"]
        + cfg["v_head_dim"])


def span_forward_flops(cfg, start: int, end: int, head_tokens: int) -> int:
    """Forward FLOPs of processing positions start..end-1 of one sequence
    (keys before ``start`` come from a cache), the head applied to
    ``head_tokens`` of them."""
    return ((end - start) * token_matmul_flops(cfg, head=False)
            + 2 * cfg["hidden_size"] * cfg["vocab_size"] * head_tokens
            + pair_flops(cfg) * cfg["num_hidden_layers"]
            * span_keys(start, end, None))


def serve_window_flops(run) -> float:
    """As ``readers.serve_window_flops``: every token event stamped in
    the window is one decode position; a request whose first token
    arrived in it had its prompt prefilled (the head once)."""
    cfg, t0, t1 = run.cfg, run.t0, run.t1
    total = 0.0
    for r in run.records:
        plen = len(r.prompt)
        for j, s in enumerate(r.stamps):
            if not t0 <= s < t1:
                continue
            if j == 0:
                total += span_forward_flops(cfg, 0, plen, 1)
            else:
                pos = plen + j - 1
                total += span_forward_flops(cfg, pos, pos + 1, 1)
    return total


def expert_stack_cost(cfg, assignments: float, experts_hit: float,
                      itemsize: int = 2):
    """(FLOPs, bytes) of the routed experts' products over some
    layer-steps: ``assignments`` (token, expert) pairs, each three
    matrices; the weights of the ``experts_hit`` distinct experts that had
    a token read once, the tokens' activations read and written once."""
    h = cfg["hidden_size"]
    tokens = assignments / cfg["num_experts_per_tok"]
    flops = 2 * expert_params(cfg) * assignments
    byts = itemsize * (expert_params(cfg) * experts_hit + 2 * h * tokens)
    return flops, byts
