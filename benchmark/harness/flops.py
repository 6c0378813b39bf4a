"""Operations and bytes that the work requires, counted from shapes.

Only matrix multiplications and attention count (2 FLOPs a multiply-add).
The embedding is a gather and counts nothing; causal and windowed
attention count only the keys a query may see; nothing recomputed counts.
``cfg`` is a configuration file's dict (the published ``config.json`` keys).
"""
from __future__ import annotations


def _dims(cfg):
    h = cfg["hidden_size"]
    nh = cfg["num_attention_heads"]
    nkv = cfg.get("num_key_value_heads") or nh
    return h, nh, nkv, h // nh, cfg["intermediate_size"]


def matmul_params(cfg, head: bool = True) -> int:
    """Weights that take part in a matrix multiplication, for one token's
    forward pass (embedding table excluded, head included on request)."""
    h, nh, nkv, hd, ffn = _dims(cfg)
    layer = h * nh * hd + 2 * h * nkv * hd + nh * hd * h + 3 * h * ffn
    return cfg["num_hidden_layers"] * layer + (
        h * cfg["vocab_size"] if head else 0)


def token_matmul_flops(cfg, head: bool = True) -> int:
    """Forward matmul FLOPs of one token."""
    return 2 * matmul_params(cfg, head)


def visible_keys(pos: int, window) -> int:
    """Keys the query at 0-based position ``pos`` sees: itself and what
    precedes it, at most ``window`` in all."""
    n = pos + 1
    return min(n, int(window)) if window else n


def span_keys(start: int, end: int, window) -> int:
    """Sum of visible keys over query positions start..end-1."""
    if not window or end <= window:
        return (end * (end + 1) - start * (start + 1)) // 2
    return sum(visible_keys(p, window) for p in range(start, end))


def span_forward_flops(cfg, start: int, end: int, head_tokens: int) -> int:
    """Forward FLOPs of processing positions start..end-1 of one
    sequence (keys before ``start`` come from a cache), with the head
    applied to ``head_tokens`` of them."""
    h, nh, nkv, hd, ffn = _dims(cfg)
    n = end - start
    return (n * token_matmul_flops(cfg, head=False)
            + 2 * h * cfg["vocab_size"] * head_tokens
            + 4 * nh * hd * cfg["num_hidden_layers"]
            * span_keys(start, end, cfg.get("sliding_window")))


def train_step_flops(cfg, batch: int, seq: int) -> int:
    """Forward and backward of one optimizer step: the backward pass is
    twice the forward; the head sees every position (the loss drops the
    last one, the matmul does not)."""
    return 3 * batch * span_forward_flops(cfg, 0, seq, head_tokens=seq)


def flash_fwd_cost(cfg, batch: int, seq: int, itemsize: int = 2):
    """(FLOPs, bytes) of one layer's flash-attention forward call: two
    matmuls over the visible keys; q, k, v read and o written once, and
    the f32 log-sum-exp written."""
    h, nh, nkv, hd, ffn = _dims(cfg)
    keys = span_keys(0, seq, cfg.get("sliding_window"))
    flops = 4 * batch * nh * hd * keys
    byts = batch * seq * hd * itemsize * (2 * nh + 2 * nkv) \
        + batch * seq * nh * 4
    return flops, byts


def flash_bwd_cost(cfg, batch: int, seq: int, itemsize: int = 2):
    """(FLOPs, bytes) of one layer's flash-attention backward (dq and
    dk/dv together): five matmuls over the visible keys -- the scores
    again, dP, dV, dK, dQ -- however many kernels they are split over;
    q, k, v, o, do, lse read once and dq, dk, dv written once."""
    h, nh, nkv, hd, ffn = _dims(cfg)
    keys = span_keys(0, seq, cfg.get("sliding_window"))
    flops = 10 * batch * nh * hd * keys
    byts = batch * seq * hd * itemsize * (4 * nh + 4 * nkv) \
        + batch * seq * nh * 4
    return flops, byts


def roofline_seconds(flops: float, byts: float, peaks: dict):
    """(least seconds, which bound holds)."""
    t_c = flops / peaks["bf16_flops"]
    t_m = byts / peaks["hbm_bytes_per_s"]
    return (t_c, "compute") if t_c >= t_m else (t_m, "memory")
