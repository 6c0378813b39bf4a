"""Paged attention: attend a query block over K/V read through a page
table (PAPERS.md "Ragged Paged Attention" — the TPU serving kernel
shape; reference capability: vLLM PagedAttention).

Two paths, selected by ``PADDLE_TPU_PAGED_KERNEL``:

- default — a pure jax/lax GATHER reference: pages are gathered into a
  contiguous [B, P·page_size, KV, D] view and attention runs exactly
  like models/generation.py::cached_attention (same einsums, same f32
  accumulation, same absolute-position mask), so it is CPU-testable and
  oracle-comparable against the contiguous static-cache path to 1e-5.
- ``PADDLE_TPU_PAGED_KERNEL=1`` — ONE unified ragged Pallas kernel
  (round 18, replacing the decode-only S=1 stub): the grid streams over
  packed query TOKENS, each grid cell handed its own lane's
  (page_table row, context_len, absolute position), so decode lanes
  (q=1), prefill chunks, and speculative-verify bursts (q=k+1) all run
  through the same program. INTERPRET MODE, CPU ONLY: the knob raises on
  any other backend. The chip's compiler refuses the kernel as written
  ("the last two dimensions of your block shape are divisible by 8 and
  128" — the (1, P) page-table block; tests/test_aot_tpu_compile.py
  keeps the refusal as a strict xfail) and it reads the whole page pool
  per grid cell, which a Mosaic build must replace with per-page DMA to
  respect the O(block) VMEM invariant (ROADMAP S4).

:func:`ragged_paged_attention` is the token-packed entry point
(PAPERS.md "Ragged Paged Attention"): ``q [T, H, D]`` carries the query
tokens of L lanes in two STATIC regions — a rectangle of ``k1`` rows a
lane for the decode/verify lanes, then the prefill chunk's rows for the
last lane — so a row's place says its lane and each lane's padded page
table is gathered once a call, not once a token (PR 30: a 72-token step
gathered 72 tables where 9 lanes own 9). Rows past a lane's query_len
are padding: their output is zero, and the caller discards it.
:func:`paged_attention` keeps the rectangular [B, S] surface and, under
the kernel gate, routes through the SAME ragged kernel (row b = one
lane of query_len S) — one gated kernel, not two.

Both paths accept GQA natively (query heads grouped over KV heads, no
materialized head repeat) and a Mistral-style sliding ``window``.

int8 quantized cache (round 15): ``k_pages``/``v_pages`` may each be a
``(codes int8 [NP, PS, KV, D], scales f32 [NP, PS, KV])`` tuple — the
:class:`~.kv_cache.PagedKVCache` ``dtype="int8"`` layout. Dequant is
inline, the generation-path recipe (``cached_attention``): the score
einsum reads the CODES and the per-slot scales fold in post-dot
(``s_t·(codes_t·q) == (s_t·codes_t)·q``), V scales fold into the
softmax probabilities — no dequantized f32 copy of the pool is ever
materialized, so the per-step HBM stream is the code bytes.
"""
from __future__ import annotations

import os

import jax
import jax.numpy as jnp
import numpy as np

__all__ = ["paged_attention", "paged_attention_ref",
           "ragged_paged_attention", "quantize_q8", "PagedStep"]


def quantize_q8(x):
    """Per-(slot, kv-head) absmax int8 quantization for the paged
    cache's append path: ``[..., KV, D]`` → ``(codes int8 [..., KV, D],
    scales f32 [..., KV])``. Deterministic (pure rounding), so
    preemption recompute and failover re-prefill regenerate
    bit-identical pages — the same recipe generation.py proved at
    delta-NLL ~1e-3 (BENCH_kv8_quality.json)."""
    xf = x.astype(jnp.float32)
    amax = jnp.max(jnp.abs(xf), axis=-1)
    s = jnp.maximum(amax / 127.0, 1e-8)
    codes = jnp.clip(jnp.round(xf / s[..., None]), -127,
                     127).astype(jnp.int8)
    return codes, s


def _kernel_requested() -> bool:
    """True when ``PADDLE_TPU_PAGED_KERNEL=1`` selects the interpret-mode
    kernel. It exists to test the kernel's structure on the CPU; on a
    chip it would quietly run interpreted, so there the knob raises."""
    if os.environ.get("PADDLE_TPU_PAGED_KERNEL") != "1":
        return False
    backend = jax.default_backend()
    if backend != "cpu":
        raise RuntimeError(
            "PADDLE_TPU_PAGED_KERNEL=1 selects an interpret-mode Pallas "
            f"kernel that only runs on the cpu backend (this is "
            f"{backend!r}); unset it — the gather path is the "
            "accelerator path until the kernel compiles under Mosaic")
    return True


def paged_attention(q, k_pages, v_pages, page_table, context_lens,
                    q_offsets, *, scale, window=None, spmd=False):
    """q [B,S,H,D]; k_pages/v_pages [NP, page_size, KV, D];
    page_table [B,P] int32 (pad = scratch page 0); context_lens [B]
    int32 — valid K tokens per row INCLUDING any just scattered;
    q_offsets [B] int32 — absolute position of each row's first query.
    Returns [B,S,H,D] in q.dtype.  ``spmd=True`` (the tensor-parallel
    step) forces the jnp gather path regardless of
    ``PADDLE_TPU_PAGED_KERNEL`` — ``pallas_call`` has no GSPMD
    partitioning rule, so tracing the kernel into a mesh program
    would be silent wrongness; the engine logs + counts the fallback.
    """
    if not spmd and _kernel_requested():
        # rectangular [B, S] is the degenerate ragged batch: row b is a
        # lane of query_len S — expand per token and run the ONE kernel
        b, s, nh, d = q.shape
        pt_tok = jnp.repeat(page_table, s, axis=0)
        cl_tok = jnp.repeat(context_lens, s)
        pos_tok = (q_offsets[:, None].astype(jnp.int32)
                   + jnp.arange(s, dtype=jnp.int32)[None, :]).reshape(-1)
        out = _ragged_attention_kernel(
            q.reshape(b * s, nh, d), k_pages, v_pages, pt_tok, cl_tok,
            pos_tok, scale=scale, window=window)
        return out.reshape(b, s, nh, d)
    return paged_attention_ref(q, k_pages, v_pages, page_table,
                               context_lens, q_offsets, scale=scale,
                               window=window)


def _regions(lanes, t, k1=1):
    """``(n_rect, r)`` of the step's static layout: the first ``n_rect
    = lanes - 1`` lanes own ``k1`` rows each, ``r = n_rect * k1`` rows
    in all (the rectangle); the last lane owns rows ``[r, t)`` (the
    chunk; none in the decode-only class)."""
    n_rect = lanes - 1
    r = n_rect * k1
    if t < r:
        raise ValueError(f"{t} packed rows cannot hold {n_rect} lanes "
                         f"of {k1} rows (the chunk's rows come behind)")
    return n_rect, r


def tables_gathered(lanes, t, k1=1):
    """Page tables one :func:`ragged_paged_attention` call over ``t``
    packed rows gathers: one a lane of the rectangle, one more where
    the chunk has rows. What the engine's ``attn_pages_gathered``
    counts, kept beside the code that gathers."""
    n_rect, r = _regions(lanes, t, k1)
    return n_rect + (t > r)


def _rows_of_lanes(x, t, k1=1):
    """``x [L, ...]`` per lane -> ``[t, ...]`` per packed row, by the
    step's static layout (:func:`_regions`). A row's lane is its
    place, so this is a repeat and a broadcast, never a gather."""
    n_rect, r = _regions(x.shape[0], t, k1)
    rect = jnp.repeat(x[:n_rect], k1, axis=0) if k1 > 1 else x[:n_rect]
    chunk = jnp.broadcast_to(x[n_rect], (t - r,) + x.shape[1:])
    return jnp.concatenate([rect, chunk])


def _row_index(lanes, t, k1=1):
    """``[t]`` int32 (static): a packed row's index within its lane."""
    n_rect, r = _regions(lanes, t, k1)
    return np.concatenate([np.tile(np.arange(k1), n_rect),
                           np.arange(t - r)]).astype(np.int32)


def _rows_live(query_lens, t, k1=1):
    """``[t]`` bool: a packed row is live if it lies inside its lane's
    ``query_len``; every other row is padding, whose output
    :func:`ragged_paged_attention` zeroes and the caller discards."""
    return (_row_index(query_lens.shape[0], t, k1)
            < _rows_of_lanes(query_lens.astype(jnp.int32), t, k1))


def _row_positions(query_lens, q_offsets, t, k1=1):
    """``[t]`` int32: a live row's absolute position (its lane's
    offset + its index in the lane), 0 for a padding row: what the
    per-row kernel attends from (every lane keeps context_len >= 1 by
    the engine's padding contract, so key 0 is visible to it)."""
    return jnp.where(
        _rows_live(query_lens, t, k1),
        _rows_of_lanes(q_offsets.astype(jnp.int32), t, k1)
        + _row_index(query_lens.shape[0], t, k1), 0)


class PagedStep:
    """What the packed step hands a layer that brings its own
    ``paged_forward(x, step)``: ONE protocol for every such layer. A
    layer may own a pool (``pools[i]``, one array of one entry a token:
    read it, write this step's rows, put the new array back), read another layer's pool and write none
    (``pools[j]``, after layer ``j`` ran), own a window pool
    (``window_pools[i]``, its own short table) or a lane state
    (``states[i]``: arrays by lane slot), and hand an activation on to
    later layers (``carried``).

    Per LANE (``L`` lanes; lane ``i < L - 1`` owns rows ``[i * k1,
    (i + 1) * k1)``, the last lane -- the chunk -- the rows behind):
    ``pt [L, P]`` the full pools' page table, ``cl [L]`` the keys a lane
    may see, ``ql [L]`` its live rows, ``qoff [L]`` its first row's
    position (0: the row starts a sequence, whose lane state is nought),
    ``lane_slot [L]`` its slot in the state arrays (0: scratch),
    ``wpt [L, W]`` / ``wbase [L]`` its window-pool pages, oldest first,
    and the position of their first slot. Per packed ROW: ``positions
    [1, T]``, ``slots [T]`` / ``wslots [T]`` the flat slot its entry is
    written to in a full / window pool, and, built on first use,
    ``per_token`` = ``(pt_tok [T, P], cl_tok [T], valid [T])``.
    ``stats``, a list, receives sparse-expert layers' routing counts."""

    def __init__(self, positions, slots, pt, cl, ql, qoff, k1, pools,
                 extra=None, stats=None):
        self.positions, self.slots = positions, slots
        self.pt, self.cl = pt, cl.astype(jnp.int32)
        self.ql, self.qoff = ql.astype(jnp.int32), qoff.astype(jnp.int32)
        self.k1 = k1
        self.t = int(slots.shape[0])
        self.pools = dict(pools)
        extra = extra or {}
        self.window_pools = dict(extra.get("window_pools", {}))
        self.states = dict(extra.get("states", {}))
        self.lane_slot = extra.get("lane_slot")
        self.wslots = extra.get("wslots")
        self.wpt, self.wbase = extra.get("wpt"), extra.get("wbase")
        self.carried = {}
        self.stats = stats
        self._per_token = self._valid = None

    @property
    def per_token(self):
        if self._per_token is None:
            self._per_token = (
                _rows_of_lanes(self.pt, self.t, self.k1),
                _rows_of_lanes(self.cl, self.t, self.k1),
                self.valid)
        return self._per_token

    @property
    def valid(self):
        """``[T]`` bool: the rows that are not padding."""
        if self._valid is None:
            self._valid = _rows_live(self.ql, self.t, self.k1)
        return self._valid

    def regions(self):
        """The step's static regions as ``(rows, lanes, n, s)``: slices
        of the packed rows and of the lanes, ``n`` lanes of ``s`` rows
        each -- the rectangle, then the chunk where the class has one."""
        n_rect, r = _regions(self.pt.shape[0], self.t, self.k1)
        out = [(slice(0, r), slice(0, n_rect), n_rect, self.k1)] if r \
            else []
        if self.t > r:
            out.append((slice(r, self.t), slice(n_rect, n_rect + 1), 1,
                        self.t - r))
        return out


def ragged_paged_attention(q, k_pages, v_pages, page_table,
                           context_lens, query_lens, q_offsets, *,
                           scale, window=None, spmd=False, k1=1):
    """Token-packed mixed-batch paged attention (one program for
    decode + prefill + verify lanes).

    q [T, H, D] — two static regions (:func:`_rows_of_lanes`): lane
    ``i < L - 1`` owns rows ``[i*k1, (i+1)*k1)`` (a decode lane fills
    one, a verify lane up to ``k1 = speculative_k + 1``), the last lane
    — the prefill chunk — rows ``[(L-1)*k1, T)``; ``T == (L-1)*k1`` is
    the decode-only class, with no chunk rows. Rows past a lane's
    ``query_lens`` are padding;
    page_table [L, P] int32 per LANE (pad = scratch page 0);
    context_lens [L] int32 — valid K tokens per lane INCLUDING any just
    scattered (>= 1 even for padded lanes); query_lens [L] int32 (0 for
    padded lanes); q_offsets [L] int32 — absolute position of each
    lane's first query token. Returns [T, H, D] in q.dtype; padding
    rows are zero.

    A row's place says its lane, so the default path gathers each
    lane's padded page table ONCE: two rectangular calls of
    :func:`paged_attention_ref` (the oracle — identical einsums/mask,
    so GQA, sliding window, and the int8 (codes, scales) tuple layout
    are inherited), ``[L-1, k1]`` over the rectangle and ``[1, T -
    (L-1)*k1]`` over the chunk. ``PADDLE_TPU_PAGED_KERNEL=1`` runs the
    unified interpret-mode Pallas kernel on the per-token expansion of
    the same layout; ``spmd=True`` (tensor-parallel step) overrides the
    knob and stays on the ref path — no Pallas under GSPMD.
    """
    t, nh, d = q.shape
    n_rect, r = _regions(page_table.shape[0], t, k1)
    cl = context_lens.astype(jnp.int32)
    qoff = q_offsets.astype(jnp.int32)
    if not spmd and _kernel_requested():
        out = _ragged_attention_kernel(
            q, k_pages, v_pages, _rows_of_lanes(page_table, t, k1),
            _rows_of_lanes(cl, t, k1),
            _row_positions(query_lens, q_offsets, t, k1),
            scale=scale, window=window)
    else:
        parts = []
        if r:
            parts.append(paged_attention_ref(
                q[:r].reshape(n_rect, k1, nh, d), k_pages, v_pages,
                page_table[:n_rect], cl[:n_rect], qoff[:n_rect],
                scale=scale, window=window).reshape(r, nh, d))
        if t > r:
            parts.append(paged_attention_ref(
                q[r:][None], k_pages, v_pages, page_table[n_rect:],
                cl[n_rect:], qoff[n_rect:], scale=scale,
                window=window)[0])
        out = parts[0] if len(parts) == 1 else jnp.concatenate(parts)
    # the one rule for padding rows: their output is zero. (In the
    # rectangular calls such a row sits at its lane's offset + index,
    # where a sliding window can leave it no visible key: an all-masked
    # softmax, which the select discards.)
    return jnp.where(_rows_live(query_lens, t, k1)[:, None, None],
                     out, 0)


def paged_attention_ref(q, k_pages, v_pages, page_table, context_lens,
                        q_offsets, *, scale, window=None):
    """Gather-based reference path (see module docstring)."""
    b, s, nh, d = q.shape
    k_quant = isinstance(k_pages, tuple)
    kp = k_pages[0] if k_quant else k_pages
    _, ps, nkv, _ = kp.shape
    p = page_table.shape[1]
    t = p * ps
    g = nh // nkv
    qg = q.reshape(b, s, nkv, g, d).astype(jnp.float32)
    if k_quant:
        # int8 pages: gather the codes, score in int8-as-f32, fold the
        # K scales in post-dot on the [T] axis and the V scales into
        # the probabilities — cached_attention's algebra over a page
        # table
        kq, ks = k_pages
        vq, vs = v_pages
        kg = kq[page_table].reshape(b, t, nkv, d)
        ksg = ks[page_table].reshape(b, t, nkv)            # [B,T,KV]
        sc = jnp.einsum("bskgd,btkd->bkgst", qg,
                        kg.astype(jnp.float32)) * scale
        sc = sc * jnp.transpose(ksg, (0, 2, 1))[:, :, None, None, :]
    else:
        # [B,P] pages -> contiguous [B,T,KV,D] logical view
        kg = k_pages[page_table].reshape(b, t, nkv, d)
        vg = v_pages[page_table].reshape(b, t, nkv, d)
        sc = jnp.einsum("bskgd,btkd->bkgst", qg,
                        kg.astype(jnp.float32)) * scale
    qpos = q_offsets[:, None] + jnp.arange(s, dtype=jnp.int32)[None, :]
    kpos = jnp.arange(t, dtype=jnp.int32)
    mask = kpos[None, None, :] <= qpos[:, :, None]            # [B,S,T]
    mask = mask & (kpos[None, None, :] < context_lens[:, None, None])
    if window:  # 0/None both disable (all-False band would NaN softmax)
        mask = mask & (kpos[None, None, :] > qpos[:, :, None]
                       - int(window))
    sc = jnp.where(mask[:, None, None], sc, -jnp.inf)
    pr = jax.nn.softmax(sc, axis=-1)
    if k_quant:
        vsg = vs[page_table].reshape(b, t, nkv)
        pr = pr * jnp.transpose(vsg, (0, 2, 1))[:, :, None, None, :]
        out = jnp.einsum("bkgst,btkd->bskgd", pr,
                         vq[page_table].reshape(b, t, nkv, d)
                         .astype(jnp.float32))
    else:
        out = jnp.einsum("bkgst,btkd->bskgd", pr,
                         vg.astype(jnp.float32))
    return out.reshape(b, s, nh, d).astype(q.dtype)


def _ragged_attention_kernel(q, k_pages, v_pages, pt_tok, cl_tok,
                             pos_tok, *, scale, window=None):
    """Unified ragged Pallas kernel, interpret mode only (see module
    docstring). q [T, H, D] packed tokens; pt_tok [T, P] / cl_tok [T] /
    pos_tok [T] are the PER-TOKEN lane rows (gathered by the caller, so
    the grid cell's BlockSpecs stay O(1)-indexed). Grid over tokens —
    decode, prefill-chunk, and verify tokens are indistinguishable
    cells; one online-softmax pass over the page list per cell. int8
    caches add the scale pools as two extra operands; dequant happens
    per page inside the streaming loop (the codes and the scale row of
    ONE page at a time — O(page) VMEM, the shape a Mosaic build
    keeps)."""
    from jax.experimental import pallas as pl

    t, nh, d = q.shape
    quant = isinstance(k_pages, tuple)
    if quant:
        (k_pages, k_scales), (v_pages, v_scales) = k_pages, v_pages
    np_, ps, nkv, _ = k_pages.shape
    p = pt_tok.shape[1]
    g = nh // nkv
    win = int(window) if window else 0

    def kernel(pt_ref, cl_ref, qo_ref, q_ref, k_ref, v_ref, *rest):
        if quant:
            ks_ref, vs_ref, o_ref = rest
        else:
            (o_ref,) = rest
        pt = pt_ref[...][0]                       # [P]
        cl = cl_ref[...][0]
        qpos = qo_ref[...][0]
        qh = q_ref[...][0].astype(jnp.float32).reshape(nkv, g, d)
        # interpret-mode full read; a Mosaic build must DMA per page
        k_all = k_ref[...]
        v_all = v_ref[...]

        def body(i, carry):
            m, l, acc = carry
            page = pt[i]
            kb = jax.lax.dynamic_index_in_dim(
                k_all, page, 0, keepdims=False).astype(jnp.float32)
            vb = jax.lax.dynamic_index_in_dim(
                v_all, page, 0, keepdims=False).astype(jnp.float32)
            if quant:
                ksb = jax.lax.dynamic_index_in_dim(
                    ks_ref[...], page, 0, keepdims=False)    # [PS,KV]
                vsb = jax.lax.dynamic_index_in_dim(
                    vs_ref[...], page, 0, keepdims=False)
                kb = kb * ksb[..., None]
                vb = vb * vsb[..., None]
            sc = jnp.einsum("kgd,tkd->kgt", qh, kb) * scale  # [KV,g,PS]
            tpos = i * ps + jnp.arange(ps, dtype=jnp.int32)
            ok = (tpos <= qpos) & (tpos < cl)
            if win:
                ok = ok & (tpos > qpos - win)
            sc = jnp.where(ok[None, None, :], sc, -jnp.inf)
            m2 = jnp.maximum(m, sc.max(-1))
            # dead blocks (all masked) keep the accumulator untouched:
            # exp guards avoid -inf minus -inf NaNs
            alive = jnp.isfinite(m2)
            alpha = jnp.where(alive, jnp.exp(m - m2), 1.0)
            pexp = jnp.where(alive[..., None],
                             jnp.exp(sc - m2[..., None]), 0.0)
            l2 = l * alpha + pexp.sum(-1)
            acc2 = acc * alpha[..., None] + \
                jnp.einsum("kgt,tkd->kgd", pexp, vb)
            return m2, l2, acc2

        m0 = jnp.full((nkv, g), -jnp.inf, jnp.float32)
        l0 = jnp.zeros((nkv, g), jnp.float32)
        a0 = jnp.zeros((nkv, g, d), jnp.float32)
        m, l, acc = jax.lax.fori_loop(0, p, body, (m0, l0, a0))
        out = acc / jnp.maximum(l, 1e-20)[..., None]
        o_ref[...] = out.reshape(1, nh, d).astype(o_ref.dtype)

    full_k = pl.BlockSpec(k_pages.shape, lambda i: (0, 0, 0, 0))
    in_specs = [pl.BlockSpec((1, p), lambda i: (i, 0)),
                pl.BlockSpec((1,), lambda i: (i,)),
                pl.BlockSpec((1,), lambda i: (i,)),
                pl.BlockSpec((1, nh, d), lambda i: (i, 0, 0)),
                full_k, full_k]
    operands = [pt_tok, cl_tok, pos_tok, q, k_pages, v_pages]
    if quant:
        full_s = pl.BlockSpec(k_scales.shape, lambda i: (0, 0, 0))
        in_specs += [full_s, full_s]
        operands += [k_scales, v_scales]
    out = pl.pallas_call(
        kernel,
        grid=(t,),
        in_specs=in_specs,
        out_specs=pl.BlockSpec((1, nh, d), lambda i: (i, 0, 0)),
        out_shape=jax.ShapeDtypeStruct((t, nh, d), q.dtype),
        interpret=True,
    )(*operands)
    return out
