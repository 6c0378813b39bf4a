"""Operations and bytes that the work of the SambaY family requires
(Mamba-1 mixers, window and full differential attention, a cross-decoder
over one layer's keys and values, Gated Memory Units), counted from
shapes. ``cfg`` is a configuration file's dict; the sizes no published
key gives are ``weights_sambay.dims``'s.

Matrix multiplications, both softmaxes' products of a differential
attention, and the scan's multiply-adds count (2 FLOPs a multiply-add).
A query-key pair of one layer costs ``2 * heads * d`` for the scores (each
of the ``heads`` query heads against its one key head) and ``2 * heads *
2d`` for the values (each against its pair's joined values): ``6 * heads
* d``. A row at position ``p`` sees ``min(p + 1, window)`` keys in a
window layer and ``p + 1`` in the full layer and in every cross layer.
A scanned row costs ``3 * d_inner * d_state`` multiply-adds (the decay
times the state plus the input; the input's outer product; the output's
contraction) and ``d_inner`` more for the skip.
"""
from __future__ import annotations

from .flops import roofline_seconds, span_keys  # noqa: F401
from .weights_sambay import dims, kind


def mixer_params(cfg, i: int) -> int:
    """Weights one token is multiplied with in layer ``i``'s mixer (the
    depthwise convolution's taps among them)."""
    z = dims(cfg)
    h, di, n, r, kw = z["h"], z["di"], z["n"], z["r"], z["nkv"] * z["d"]
    what = kind(cfg, i)
    if what == "mamba":
        return (h * 2 * di + z["k"] * di + di * (r + 2 * n) + r * di
                + di * h)
    if what in ("window", "full"):
        return h * (h + 2 * kw) + h * h
    if what == "cross":
        return 2 * h * h
    return 2 * h * di                                          # gmu


def layer_token_params(cfg, i: int) -> int:
    z = dims(cfg)
    return mixer_params(cfg, i) + 3 * z["h"] * z["ffn"]


def token_matmul_flops(cfg, head: bool = True) -> int:
    """Forward matmul FLOPs of one token."""
    n = sum(layer_token_params(cfg, i)
            for i in range(cfg["num_hidden_layers"]))
    return 2 * (n + (cfg["hidden_size"] * cfg["vocab_size"] if head else 0))


def pair_flops(cfg) -> int:
    """One query-key pair in one attending layer: both softmaxes' score
    and value products."""
    z = dims(cfg)
    return 6 * z["nh"] * z["d"]


def scan_row_flops(cfg) -> int:
    """One row through one layer's selective scan."""
    z = dims(cfg)
    return 2 * (3 * z["di"] * z["n"] + z["di"])


def layer_counts(cfg) -> dict:
    """How many layers of each kind."""
    out = dict.fromkeys(("mamba", "window", "full", "gmu", "cross"), 0)
    for i in range(cfg["num_hidden_layers"]):
        out[kind(cfg, i)] += 1
    return out


def span_forward_flops(cfg, start: int, end: int, head_tokens: int) -> int:
    """Forward FLOPs of processing positions start..end-1 of one sequence
    (keys and state before ``start`` come from a cache), the head applied
    to ``head_tokens`` of them."""
    n = layer_counts(cfg)
    keys = (n["window"] * span_keys(start, end, cfg["sliding_window"])
            + (n["full"] + n["cross"]) * span_keys(start, end, None))
    return ((end - start) * (token_matmul_flops(cfg, head=False)
                             + n["mamba"] * scan_row_flops(cfg))
            + 2 * cfg["hidden_size"] * cfg["vocab_size"] * head_tokens
            + pair_flops(cfg) * keys)


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


def scan_cost(cfg, rows: float, lane_scans: float, itemsize: int = 2):
    """(FLOPs, bytes) of the selective scans over some layer-steps:
    ``rows`` live rows scanned (each: ``x`` and ``y`` in the served type,
    ``dt`` in float32, ``B`` and ``C``), ``lane_scans`` times a lane's
    float32 state read and written once."""
    z = dims(cfg)
    di, n = z["di"], z["n"]
    byts = (rows * (di * (2 * itemsize + 4) + 2 * n * itemsize)
            + lane_scans * 2 * di * n * 4)
    return rows * scan_row_flops(cfg), byts


def scan_least_seconds(cfg, rows: float, lane_scans: float, peaks) -> float:
    import jax.numpy as jnp
    fl, by = scan_cost(cfg, rows, lane_scans,
                       jnp.dtype(cfg["torch_dtype"]).itemsize)
    return roofline_seconds(fl, by, peaks)[0]
