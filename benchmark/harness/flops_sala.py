"""Operations and bytes that the work of MiniCPM-SALA requires, counted
from shapes and from the engine's counters. ``cfg`` is a configuration
file's dict.

Matrix multiplications count 2 FLOPs a multiply-add. A sparse layer's
attention counts the blocks each (row, KV head) selected, never the
dense context: a selected block is ``B`` keys, each met by the group's
``G`` query heads in ``q . k`` and in ``p v`` (``4 G B d`` FLOPs); its
selection scores each existing compressed key with every query head
(``2 H d`` FLOPs a key and row). Its least bytes are the pages it
selected, keys and values of one KV head a page, each once a lane
(a decode row's own selection, the union of a chunk's rows':
``sparse_pages_selected``, counted on the device), and the compressed
keys its lanes hold (``sparse_kernels_read``). What the program streams
past that (a decode row's padded table, the chunk's every page:
``sparse_pages_streamed``) is its over-read, not required work.

Lightning attention a layer, ``H`` heads of ``d``:

- a row through the one-row recurrence (``ops/lightning_attn.py``): the
  decay, the write ``k v^T`` and ``S^T q``: ``5 H d^2`` FLOPs;
- a row through the chunkwise form in sub-chunks of ``C = 64``: ``q k^T``
  and ``P v`` within the sub-chunk (``2 C d``), ``Q S`` and the state's
  write (``2 d^2``): ``2 H (2 C d + 2 d^2)`` FLOPs.

Its least bytes are each live lane's float32 state read and written once
a layer-step.
"""
from __future__ import annotations

from .flops import roofline_seconds  # noqa: F401
from .weights_sala import SPARSE, kind

SUB = 64            # the chunkwise form's sub-chunk (ops/lightning_attn.py)


def mixer_params(cfg, i: int) -> int:
    """Weights one token is multiplied with in layer ``i``'s mixer."""
    h = cfg["hidden_size"]
    if kind(cfg, i) == SPARSE:
        nh, nkv, d = (cfg["num_attention_heads"],
                      cfg["num_key_value_heads"], cfg["head_dim"])
        return 3 * h * nh * d + 2 * h * nkv * d
    return 5 * h * cfg["lightning_nh"] * cfg["lightning_head_dim"]


def token_matmul_flops(cfg, head: bool = True) -> float:
    h = cfg["hidden_size"]
    n = sum(mixer_params(cfg, i) + 3 * h * cfg["intermediate_size"]
            for i in range(cfg["num_hidden_layers"]))
    return 2 * (n + (h * cfg["vocab_size"] if head else 0))


def layer_counts(cfg) -> dict:
    out = {"sparse": 0, "lightning": 0}
    for i in range(cfg["num_hidden_layers"]):
        out["sparse" if kind(cfg, i) == SPARSE else "lightning"] += 1
    return out


def block_flops(cfg) -> int:
    """One selected block of one (row, KV head)."""
    g = cfg["num_attention_heads"] // cfg["num_key_value_heads"]
    return 4 * g * cfg["sparse_config"]["block_size"] * cfg["head_dim"]


def score_flops(cfg) -> int:
    """One compressed key scored by one row."""
    return 2 * cfg["num_attention_heads"] * cfg["head_dim"]


def page_head_bytes(cfg, itemsize: int = 2) -> int:
    """Keys and values of one KV head in one page."""
    return 2 * cfg["sparse_config"]["block_size"] * cfg["head_dim"] \
        * itemsize


def sparse_cost(cfg, c: dict, itemsize: int = 2):
    """(FLOPs, bytes) of the sparse layers' attention and selection."""
    return (c.get("sparse_blocks_read", 0.0) * block_flops(cfg)
            + c.get("sparse_kernels_scored", 0.0) * score_flops(cfg),
            c.get("sparse_pages_selected", 0.0) * page_head_bytes(
                cfg, itemsize)
            + c.get("sparse_kernels_read", 0.0) * cfg["head_dim"]
            * itemsize)


def step_row_flops(cfg) -> int:
    return 5 * cfg["lightning_nh"] * cfg["lightning_head_dim"] ** 2


def chunk_row_flops(cfg) -> int:
    d = cfg["lightning_head_dim"]
    return 2 * cfg["lightning_nh"] * (2 * SUB * d + 2 * d * d)


def state_bytes(cfg) -> int:
    return 4 * cfg["lightning_nh"] * cfg["lightning_head_dim"] ** 2


def lightning_cost(cfg, c: dict):
    """(FLOPs, bytes): every live lane's state read and written once a
    layer-step, a row a lane through the recurrence, and the chunk's rows
    through the chunkwise form (the chunk's lane counts one recurrence
    row too: one lane in 13 a step, and the bytes bound either way)."""
    lanes = c.get("lightning_lane_steps", 0.0)
    return (lanes * step_row_flops(cfg)
            + c.get("lightning_rows_chunked", 0.0) * chunk_row_flops(cfg),
            lanes * 2 * state_bytes(cfg))


def window_flops(cfg, c: dict) -> float:
    """The FLOPs the window's work requires, from the engine's counters:
    its decode rows (``batch_size_sum``) and chunk rows
    (``lightning_rows_chunked`` over the lightning layers) through every
    matmul, the head once a token served, the sparse layers by what they
    selected, the lightning layers by their routes."""
    n = layer_counts(cfg)
    if not n["lightning"]:
        return 0.0
    rows = c.get("batch_size_sum", 0.0) + c.get(
        "lightning_rows_chunked", 0.0) / n["lightning"]
    return (rows * token_matmul_flops(cfg, head=False)
            + c.get("tokens_generated", 0.0) * 2 * cfg["hidden_size"]
            * cfg["vocab_size"]
            + sparse_cost(cfg, c)[0] + lightning_cost(cfg, c)[0])
