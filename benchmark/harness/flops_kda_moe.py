"""Operations and bytes that the work of the Kimi-delta-attention /
latent-attention hybrid with group-routed experts requires, counted from
shapes. ``cfg`` is a configuration file's dict.

Matrix multiplications (2 FLOPs a multiply-add), the MLA layer's
attention in the published expanded form (a query-key pair costs ``2 *
heads * (nope + rope + v)``), and the delta rule count. A token meets
``num_experts_per_tok`` of the router's experts and the shared one; of
the routed, this chip computes those it holds: ``top_k * held / total``
a token on average (the router is a function of the seeded weights and
routes near uniformly over the groups; ``moe_assignments`` counts the
window's own). The short convolution's taps count as a multiply-add a
channel and tap.

The delta rule a KDA layer, ``H`` heads of ``d``:

- a row through the one-row recurrence (``ops/delta_rule.py::
  delta_step``): the decay, ``S'^T k``, the rank-one write and ``S^T q``,
  four passes over a ``d x d`` state a head: ``8 H d^2`` FLOPs;
- a row through the chunkwise form in sub-chunks of ``C = 16``
  (``delta_chunk``): per sub-chunk and head ``A``, ``W``, ``U~`` and
  ``P`` (``4 C^2 d`` multiply-adds), the inverse of ``I + A`` (``C^3``
  a product; three squarings and three products at C = 16: ``6 C^3``),
  and against the state ``W S``, ``Q~ S`` and the write (``3 C d^2``)
  with ``P U`` (``C^2 d``): ``2 H (5 C d + 3 d^2 + 6 C^2)`` FLOPs a row.

The least time of the delta rule (``kda_scan_roofline``) is each lane's
state read and written once a layer-step in float32, or those FLOPs at
peak, whichever is longer.
"""
from __future__ import annotations

from .flops import roofline_seconds, span_keys  # noqa: F401
from .flops_latent_moe import expert_params
from .weights_kda_moe import kind, routed

SUB = 16            # the chunkwise form's sub-chunk (ops/delta_rule.py)


def dims(cfg) -> dict:
    nh, d = cfg["num_attention_heads"], cfg["head_dim"]
    return {"h": cfg["hidden_size"], "nh": nh, "d": d, "hd": nh * d,
            "k": cfg["short_conv_kernel_size"], "rkv": cfg["kv_lora_rank"],
            "dn": cfg["qk_nope_head_dim"], "dr": cfg["qk_rope_head_dim"],
            "dv": cfg["v_head_dim"]}


def mixer_params(cfg, i: int) -> int:
    """Weights one token is multiplied with in layer ``i``'s mixer."""
    z = dims(cfg)
    h, nh, hd = z["h"], z["nh"], z["hd"]
    if kind(cfg, i) == "kda":
        return 4 * h * hd + 2 * h * nh + hd * h + z["k"] * 3 * hd
    return (h * nh * (z["dn"] + z["dr"]) + h * (z["rkv"] + z["dr"])
            + z["rkv"] * nh * (z["dn"] + z["dv"]) + nh * z["dv"] * h)


def routed_per_token(cfg) -> float:
    """Routed experts held here that a token meets, on average."""
    total, _, count = routed(cfg)
    return cfg["num_experts_per_tok"] * count / total


def layer_token_params(cfg, i: int) -> float:
    """Weights one token is multiplied with in layer ``i`` (the routed
    experts held here at their average share)."""
    h = cfg["hidden_size"]
    if i < cfg["first_k_dense_replace"]:
        ffn = 3 * h * cfg["intermediate_size"]
    else:
        ffn = (h * routed(cfg)[0]
               + 3 * h * cfg["moe_shared_expert_intermediate_size"]
               + expert_params(cfg) * routed_per_token(cfg))
    return mixer_params(cfg, i) + ffn


def token_matmul_flops(cfg, head: bool = True) -> float:
    n = sum(layer_token_params(cfg, i)
            for i in range(cfg["num_hidden_layers"]))
    return 2 * (n + (cfg["hidden_size"] * cfg["vocab_size"] if head else 0))


def pair_flops(cfg) -> int:
    """One query-key pair in the MLA layer."""
    z = dims(cfg)
    return 2 * z["nh"] * (z["dn"] + z["dr"] + z["dv"])


def step_row_flops(cfg) -> int:
    """One row through one KDA layer's one-row recurrence."""
    z = dims(cfg)
    return 8 * z["nh"] * z["d"] ** 2


def chunk_row_flops(cfg) -> int:
    """One row through one KDA layer's chunkwise form."""
    z = dims(cfg)
    d, c = z["d"], SUB
    return 2 * z["nh"] * (5 * c * d + 3 * d * d + 6 * c * c)


def state_bytes(cfg) -> int:
    """A lane's delta-rule state in one KDA layer (float32)."""
    z = dims(cfg)
    return 4 * z["nh"] * z["d"] ** 2


def layer_counts(cfg) -> dict:
    out = {"kda": 0, "mla": 0}
    for i in range(cfg["num_hidden_layers"]):
        out[kind(cfg, i)] += 1
    return out


def span_forward_flops(cfg, start: int, end: int, head_tokens: int) -> float:
    """Forward FLOPs of processing positions start..end-1 of one sequence
    (state and keys before ``start`` come from the cache), the head
    applied to ``head_tokens`` of them: a span of one row takes the
    recurrence, a longer one the chunkwise form."""
    n, rows = layer_counts(cfg), end - start
    delta = step_row_flops(cfg) if rows == 1 else chunk_row_flops(cfg)
    return (rows * (token_matmul_flops(cfg, head=False) + n["kda"] * delta)
            + 2 * cfg["hidden_size"] * cfg["vocab_size"] * head_tokens
            + pair_flops(cfg) * n["mla"] * span_keys(start, end, None))


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


def delta_cost(cfg, lane_steps: float, rows_chunked: float):
    """(FLOPs, bytes) of the delta rule over some layer-steps: every live
    lane's state read and written once (``lane_steps``), a row a lane
    through the recurrence, and ``rows_chunked`` through the chunkwise
    form. (The chunk's lane counts one recurrence row too: at most one
    lane in 65 a step, and the bytes bound either way.)"""
    return (lane_steps * step_row_flops(cfg)
            + rows_chunked * chunk_row_flops(cfg),
            lane_steps * 2 * state_bytes(cfg))
