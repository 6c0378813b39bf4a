"""Flash attention for TPU.

Reference parity: the flash_attn kernel family (upstream
paddle/phi/kernels/fusion/gpu + third_party/flashattn — unverified, see
SURVEY.md §2.1) exposed via paddle.nn.functional.flash_attention with
[batch, seqlen, num_heads, head_dim] layout.

TPU-native design: a Pallas kernel (paddle_tpu/ops/pallas/_fa_kernel.py)
tiled for the MXU (block sizes multiple of 128 on the lane dim) with the
standard online-softmax streaming algorithm; `jax.custom_vjp` wires the
Pallas backward. The kernel natively handles **GQA** (KV heads indexed
in the BlockSpec maps — never repeated through HBM), **packed/varlen
segments** (block-diagonal masking with dead-block skip), and
**additive masks** (per-block mask slabs) — round-3, VERDICT r2 item 2.

Fallback discipline (round-3, VERDICT r2 item 3): every Pallas→XLA
fallback is COUNTED (`dispatch_stats()`), warned once per site, and
raises under `PADDLE_TPU_REQUIRE_PALLAS=1`. A silent fallback cost
round 2 ~24 MFU points before it was root-caused (PERF.md); it cannot
happen quietly again. Off-TPU (CPU tests) the reference path is the
EXPECTED backend and is not counted as a fallback.

Multi-device meshes: a `pallas_call` has no GSPMD partitioning rule
(Mosaic: "kernels cannot be automatically partitioned"), so when a fleet
stepper traces its step for a mesh of more than one device
(`distributed/_axis.py::mesh_env`) every kernel call here runs per shard
inside `jax.shard_map` — batch over the data axes (dp × sharding), heads
over `mp`, the fleet steppers' own layout — and each chip runs the kernel
on its own shard (`_kernel_shard_plan`, `_fa_fwd`, `_fa_bwd`). A shape
the mesh does not divide takes the counted fallback like any other.

The public entry is `flash_attention_bshd(q, k, v, ...)` on framework
Tensors; `_attention_ref` is the jax-level oracle shared by tests.
"""
from __future__ import annotations

import functools
import math
import os
import warnings

import numpy as np

import jax
import jax.numpy as jnp

from ...core.autograd import apply
from ...core.random import next_key

# ---------------------------------------------------------------------------
# dispatch accounting: Pallas engagement is observable, fallbacks are loud

_DISPATCH = {"pallas": 0, "fallback": 0, "resident": 0, "streamed": 0,
             "window_as_causal": 0}
_WARNED: set = set()


def dispatch_stats():
    """Counted at TRACE time: how many attention calls engaged the
    kernel ('pallas') vs fell back while on TPU ('fallback'); of the
    kernel calls, how many took each forward family ('resident': full
    K/V in VMEM, two-axis grid; 'streamed': three-axis grid, masks and
    cross-length); and how many windows that could not bind were sent
    to the plain causal call ('window_as_causal')."""
    return dict(_DISPATCH)


def reset_dispatch_stats():
    for key in _DISPATCH:
        _DISPATCH[key] = 0
    _WARNED.clear()


def _fallback(site, err=None):
    """Record a Pallas→XLA fallback ON TPU: warn once per site; raise
    under PADDLE_TPU_REQUIRE_PALLAS=1 (strict mode)."""
    _DISPATCH["fallback"] += 1
    msg = (f"paddle_tpu flash attention: Pallas kernel fell back to the "
           f"XLA reference [{site}]")
    if err is not None:
        msg += f": {type(err).__name__}: {err}"
    if os.environ.get("PADDLE_TPU_REQUIRE_PALLAS") == "1":
        raise RuntimeError(msg) from err
    if site not in _WARNED:
        _WARNED.add(site)
        warnings.warn(msg + " (warning once per site; set "
                      "PADDLE_TPU_REQUIRE_PALLAS=1 to make this an error)")


def _attention_ref(q, k, v, mask=None, causal=False, scale=None,
                   dropout_p=0.0, dropout_key=None, return_probs=False):
    """XLA reference attention. q: [B, S, H, D]; k/v may carry fewer
    (GQA) heads — repeated here (the kernel never repeats).

    `dropout_p` > 0 applies dropout to the softmax **probabilities**
    (each attention link kept with prob 1-p and rescaled by 1/(1-p)) —
    the reference flash_attn semantics (upstream
    paddle/phi/kernels/fusion — unverified, SURVEY §2.1): dropping
    attention LINKS, not output features (VERDICT r4 missing #3).
    `return_probs` returns (out, probs) with probs AFTER dropout — the
    reference's `return_softmax` payload."""
    d = q.shape[-1]
    h, hkv = q.shape[2], k.shape[2]
    if hkv != h:
        k = jnp.repeat(k, h // hkv, axis=2)
        v = jnp.repeat(v, h // hkv, axis=2)
    s = scale if scale is not None else 1.0 / (d ** 0.5)
    # [B,H,Sq,Sk]
    logits = jnp.einsum("bqhd,bkhd->bhqk", q, k,
                        preferred_element_type=jnp.float32) * s
    if causal:
        sq, sk = logits.shape[-2], logits.shape[-1]
        cm = jnp.tril(jnp.ones((sq, sk), bool), k=sk - sq)
        logits = jnp.where(cm, logits, -jnp.inf)
    if mask is not None:
        if mask.dtype == jnp.bool_:
            logits = jnp.where(mask, logits, -jnp.inf)
        else:
            logits = logits + mask.astype(logits.dtype)
    probs = jax.nn.softmax(logits, axis=-1)
    probs = jnp.where(jnp.isnan(probs), 0.0, probs)
    if dropout_p > 0.0:
        probs = prob_dropout(probs, dropout_key, dropout_p)
    probs = probs.astype(q.dtype)
    out = jnp.einsum("bhqk,bkhd->bqhd", probs, v)
    return (out, probs) if return_probs else out


def prob_dropout(probs, key, p):
    """The one definition of attention-probability dropout (keep each
    link with prob 1-p, rescale 1/(1-p)) — shared by every reference
    attention body so the semantics can't silently diverge."""
    keep = jax.random.bernoulli(key, 1.0 - p, probs.shape)
    return jnp.where(keep, probs / (1.0 - p), 0.0)


def _seg_additive_mask(q_seg, kv_seg):
    """[B, 1, Sq, Sk] additive: 0 where segments match, -inf elsewhere."""
    eq = q_seg[:, None, :, None] == kv_seg[:, None, None, :]
    return jnp.where(eq, 0.0, -jnp.inf).astype(jnp.float32)


def _ref_ext(q, k, v, mask, q_seg, kv_seg, causal, scale,
             dropout_p=0.0, dropout_key=None, return_probs=False):
    if q_seg is not None:
        seg_m = _seg_additive_mask(q_seg, kv_seg)
        if mask is not None and mask.dtype == jnp.bool_:
            mask = jnp.where(mask, 0.0, -jnp.inf).astype(jnp.float32)
        mask = seg_m if mask is None else mask + seg_m
    return _attention_ref(q, k, v, mask=mask, causal=causal, scale=scale,
                          dropout_p=dropout_p, dropout_key=dropout_key,
                          return_probs=return_probs)


# Tests set this True to run the Pallas kernels in interpret mode off-TPU
# (exercises the exact kernel code paths without hardware).
_FORCE_INTERPRET = False


def _on_tpu() -> bool:
    try:
        return jax.default_backend() == "tpu"
    except Exception:
        return False


def _streamed_kernels_enabled() -> bool:
    """Kill-switch for the round-4 STREAMED kernel family (masked
    forward, cross-length sq != sk, FlashMask): `PADDLE_TPU_FA_STREAMED=0`
    restores the round-3 envelope — those paths take the loud counted XLA
    fallback instead of the kernel. These kernels compile under Mosaic
    for a described v5e (tests/test_aot_tpu_compile.py) and the masked,
    packed-segment and cross-length forwards run in chip_smoke.py; the
    switch is queue-3 debt (ROADMAP D6)."""
    return os.environ.get("PADDLE_TPU_FA_STREAMED", "1") != "0"


def _shape_reason(q_shape, k_shape) -> str | None:
    """None if the kernel supports this shape, else the reason it can't.
    Cross-length (sq != sk) is kernel-native (round-4): the streamed
    forward/backward shift the causal diagonal by sk - sq, matching the
    reference's tril(k=sk-sq) semantics."""
    b, sq, h, d = q_shape
    sk, kv_heads = k_shape[1], k_shape[2]
    if d not in (64, 128, 256):
        return f"head_dim {d} not in (64, 128, 256)"
    if sq % 128 != 0 or sq < 128:
        return f"q seq_len {sq} not a multiple of 128"
    if sk % 128 != 0 or sk < 128:
        return f"kv seq_len {sk} not a multiple of 128"
    if kv_heads == 0 or h % kv_heads != 0:
        return f"num_heads {h} not divisible by kv_heads {kv_heads}"
    if sq != sk and not _streamed_kernels_enabled():
        return "cross-length (sq != sk) disabled: PADDLE_TPU_FA_STREAMED=0"
    return None


def _want_pallas() -> bool:
    return _FORCE_INTERPRET or _on_tpu()


def _mask_reason(mask, b, h, sq, sk) -> str | None:
    """None if the kernel can stream this mask, else the reason it
    can't (incl. the kill-switch — naming the env var, not a misleading
    shape complaint). Kernel takes additive [B|1, H|1, Sq, Sk] f32;
    both forward and backward stream it as (block_q, block_k) slabs, so
    there is no sequence-length cap (the round-3 `_MASK_FWD_MAX_S=4096`
    forward slab is gone — VERDICT r3 item 3)."""
    if mask is None:
        return None
    if not _streamed_kernels_enabled():
        return "masked kernel disabled: PADDLE_TPU_FA_STREAMED=0"
    if (mask.ndim == 4 and mask.shape[0] in (1, b) and
            mask.shape[1] in (1, h) and mask.shape[2] == sq and
            mask.shape[3] == sk):
        return None
    return "unsupported mask shape"


# ---------------------------------------------------------------------------
# kernel calls, per shard when the step is traced for a multi-device mesh


def _kernel_shard_plan(q_shape, hkv):
    """``(plan, reason)``. plan None = call the kernel directly (no
    stepper mesh, or one device); else how to shard_map it: ``(mesh,
    manual_axes, batch_axes, head_axis)``. A reason means the mesh
    cannot take this shape — the caller records the counted fallback."""
    from ...distributed._axis import current_axis_env, current_mesh_env
    mesh = current_mesh_env()
    if mesh is None:
        return None, None
    sizes = dict(zip(mesh.axis_names, mesh.devices.shape))
    # axes an enclosing shard_map already bound (sep, pp) stay its own
    free = tuple(a for a in mesh.axis_names
                 if a not in current_axis_env())
    if all(sizes[a] == 1 for a in free):
        return None, None
    batch_axes = tuple(a for a in ("dp", "sharding")
                       if a in free and sizes[a] > 1)
    head_axis = "mp" if "mp" in free and sizes["mp"] > 1 else None
    b, _, h, _ = q_shape
    nb = math.prod(sizes[a] for a in batch_axes)
    if b % nb:
        return None, (f"batch {b} not divisible by mesh axes "
                      f"{batch_axes} (={nb})")
    if head_axis and (h % sizes["mp"] or hkv % sizes["mp"]):
        return None, (f"heads {h}/kv {hkv} not divisible by mp="
                      f"{sizes['mp']}")
    # nested in a stepper's own shard_map the context mesh is the one
    # to shard over (jax rejects a concrete mesh there)
    return (mesh if len(free) == len(mesh.axis_names) else None,
            free, batch_axes or None, head_axis), None


def _per_shard(plan, fn, args, in_roles, out_roles, b, h):
    """Run ``fn(*args)`` directly (plan None) or per shard under
    shard_map. Roles name each array's layout: "bshd" [B,S,H,D];
    "bh.." a merged leading B*H dim (kernel residuals — consistent
    between forward and backward, never read globally); "bhs" [B,H,S];
    "mask" [B|1,H|1,Sq,Sk]; "fm" [B|1,H|1,Sk]; "seg" [B,S]; "rep"
    replicated. None args pass through untouched."""
    if plan is None:
        return fn(*args)
    from jax.sharding import PartitionSpec as Ps
    mesh, free, B, H = plan
    # trailing dims a spec does not name are replicated
    fixed = {"bshd": Ps(B, None, H), "bhs": Ps(B, H), "seg": Ps(B),
             "bh..": Ps(tuple(B or ()) + ((H,) if H else ()) or None),
             "rep": Ps()}

    def spec(role, shape=None):
        if role in ("mask", "fm"):   # [B|1, H|1, ...]
            return Ps(B if shape[0] == b else None,
                      H if shape[1] == h else None)
        return fixed[role]

    live = [i for i, a in enumerate(args) if a is not None]

    def body(*present):
        full = [None] * len(args)
        for i, a in zip(live, present):
            full[i] = a
        return fn(*full)

    out_specs = tuple(spec(r) for r in out_roles)
    return jax.shard_map(
        body, mesh=mesh,
        in_specs=tuple(spec(in_roles[i], args[i].shape) for i in live),
        out_specs=out_specs if len(out_specs) > 1 else out_specs[0],
        axis_names=frozenset(free), check_vma=False,
    )(*[args[i] for i in live])


@jax.tree_util.register_static
class _PlanRes:
    """The forward's shard plan as a leafless residual: the backward
    rule runs after the forward's axis_env has been left (the enclosing
    shard_map is transposed later), so it must not re-derive it."""

    def __init__(self, plan):
        self.plan = plan

    def __hash__(self):
        return hash(self.plan)

    def __eq__(self, other):
        return isinstance(other, _PlanRes) and self.plan == other.plan


def _kernel_plan(site, q_shape, k_shape, *more_reasons):
    """(ok, plan): the one gate in front of every kernel call — shape
    support, caller-specific reasons, then the mesh. Not ok = the
    counted fallback has been recorded (or Pallas is not wanted) and
    the caller takes the XLA reference."""
    if not _want_pallas():
        return False, None
    reason = _shape_reason(q_shape, k_shape) or \
        next((r for r in more_reasons if r), None)
    if reason is None:
        plan, reason = _kernel_shard_plan(q_shape, k_shape[2])
        if reason is None:
            return True, plan
    _fallback(f"{site}: {reason}")
    return False, None


def _fa_fwd(plan, q, k, v, *, causal, scale, return_lse=False,
            lse_bhs=False, mask=None, q_seg=None, kv_seg=None,
            fm=(None, None, None, None), dropout_p=0.0,
            dropout_seed=None):
    """fa_forward under ``plan``, counted as a kernel call of its
    family once it has traced. Returns out, or (out, lse_l) with
    return_lse, or (out, lse_l, lse[B,H,S]) with lse_bhs too."""
    from ._fa_kernel import fa_forward, forward_is_streamed
    b, _, h, _ = q.shape

    def fn(q, k, v, mask, q_seg, kv_seg, f0, f1, f2, f3, seed):
        res = fa_forward(q, k, v, causal=causal, scale=scale,
                         return_lse=return_lse,
                         interpret=_FORCE_INTERPRET, mask=mask,
                         q_seg=q_seg, kv_seg=kv_seg, fm_start=f0,
                         fm_end=f1, fm_start2=f2, fm_end2=f3,
                         dropout_p=dropout_p, dropout_seed=seed)
        if lse_bhs:
            out, lse_l = res
            return out, lse_l, lse_l[:, :, 0].reshape(
                q.shape[0], q.shape[2], q.shape[1])
        return res

    out_roles = ["bshd"] + ["bh.."] * return_lse + ["bhs"] * lse_bhs
    res = _per_shard(
        plan, fn, (q, k, v, mask, q_seg, kv_seg, *fm, dropout_seed),
        ("bshd", "bshd", "bshd", "mask", "seg", "seg", "fm", "fm", "fm",
         "fm", "rep"), out_roles, b, h)
    _DISPATCH["pallas"] += 1
    streamed = forward_is_streamed(
        q.shape[1], k.shape[1], mask is not None,
        any(f is not None for f in fm))
    _DISPATCH["streamed" if streamed else "resident"] += 1
    return res


def _fa_bwd(plan, q, k, v, out, lse_l, g, *, causal, scale, g_lse=None,
            mask=None, q_seg=None, kv_seg=None,
            fm=(None, None, None, None), dropout_p=0.0,
            dropout_seed=None):
    """fa_backward under ``plan``; g_lse is the [B,H,S] lse cotangent."""
    from ._fa_kernel import fa_backward
    b, _, h, _ = q.shape

    def fn(q, k, v, out, lse_l, g, g_lse, mask, q_seg, kv_seg, f0, f1,
           f2, f3, seed):
        dlse = None if g_lse is None else g_lse.reshape(
            q.shape[0] * q.shape[2], q.shape[1])
        return fa_backward(q, k, v, out, lse_l, g, causal=causal,
                           scale=scale, interpret=_FORCE_INTERPRET,
                           dlse=dlse, mask=mask, q_seg=q_seg,
                           kv_seg=kv_seg, fm_start=f0, fm_end=f1,
                           fm_start2=f2, fm_end2=f3,
                           dropout_p=dropout_p, dropout_seed=seed)

    return _per_shard(
        plan, fn,
        (q, k, v, out, lse_l, g, g_lse, mask, q_seg, kv_seg, *fm,
         dropout_seed),
        ("bshd", "bshd", "bshd", "bshd", "bh..", "bshd", "bhs", "mask",
         "seg", "seg", "fm", "fm", "fm", "fm", "rep"),
        ["bshd"] * 3, b, h)


# ---------------------------------------------------------------------------
# the differentiable core: q, k, v diff; mask (additive f32) carried with
# zero cotangent; segment ids are ints (float0 cotangent)


@functools.partial(jax.custom_vjp, nondiff_argnums=(6, 7))
def _flash_core_ext(q, k, v, mask, q_seg, kv_seg, causal, scale):
    # Primal (no-grad) body: do NOT request the lse output — pallas_call
    # is opaque to XLA DCE, so asking for lse here would write a dead
    # [B*H, S, 128] f32 buffer on every inference forward.
    ok, plan = _kernel_plan(
        "fa_forward", q.shape, k.shape,
        _mask_reason(mask, q.shape[0], q.shape[2], q.shape[1],
                     k.shape[1]))
    if ok:
        try:
            return _fa_fwd(plan, q, k, v, causal=causal, scale=scale,
                           mask=mask, q_seg=q_seg, kv_seg=kv_seg)
        except Exception as e:
            _fallback("fa_forward", e)
    return _ref_ext(q, k, v, mask, q_seg, kv_seg, causal, scale)


def _ext_fwd(q, k, v, mask, q_seg, kv_seg, causal, scale):
    ok, plan = _kernel_plan(
        "fa_forward(train)", q.shape, k.shape,
        _mask_reason(mask, q.shape[0], q.shape[2], q.shape[1],
                     k.shape[1]))
    if ok:
        try:
            out, lse_l = _fa_fwd(plan, q, k, v, causal=causal,
                                 scale=scale, return_lse=True, mask=mask,
                                 q_seg=q_seg, kv_seg=kv_seg)
            return out, (q, k, v, out, lse_l, mask, q_seg, kv_seg,
                         _PlanRes(plan))
        except Exception as e:
            _fallback("fa_forward(train)", e)
    out = _ref_ext(q, k, v, mask, q_seg, kv_seg, causal, scale)
    return out, (q, k, v, None, None, mask, q_seg, kv_seg, None)


def _int_zero(x):
    return np.zeros(x.shape, jax.dtypes.float0) if x is not None else None


def _ext_bwd(causal, scale, res, g):
    q, k, v, out, lse_l, mask, q_seg, kv_seg, pres = res
    if lse_l is not None:
        dq, dk, dv = _fa_bwd(pres.plan, q, k, v, out, lse_l, g,
                             causal=causal, scale=scale, mask=mask,
                             q_seg=q_seg, kv_seg=kv_seg)
    else:
        _, vjp_fn = jax.vjp(
            lambda q_, k_, v_: _ref_ext(q_, k_, v_, mask, q_seg, kv_seg,
                                        causal, scale), q, k, v)
        dq, dk, dv = vjp_fn(g)
    dmask = jnp.zeros_like(mask) if mask is not None else None
    return (dq, dk, dv, dmask, _int_zero(q_seg), _int_zero(kv_seg))


_flash_core_ext.defvjp(_ext_fwd, _ext_bwd)


# ---------------------------------------------------------------------------
# in-kernel probability dropout (round 5): the resident kernel generates
# the keep mask with a counter-based hash (_fa_kernel._keep_scale) that
# forward and backward regenerate bit-identically — flash perf for
# dropout>0 training (BERT-class models) instead of the O(S²) XLA
# reference. OPT-IN until Mosaic-validated on-chip:
# PADDLE_TPU_FA_KERNEL_DROPOUT=1 (the chip capture list carries the
# validation smoke; interpret-mode numerics are exact vs the
# reconstructed-mask oracle, tests/test_attn_dropout.py).


def _kernel_dropout_enabled() -> bool:
    return os.environ.get("PADDLE_TPU_FA_KERNEL_DROPOUT", "0") == "1"


def _attention_ref_hash_dropout(q, k, v, seed, p, causal=True,
                                q_seg=None, kv_seg=None):
    """THE parity definition for in-kernel counter-hash dropout: XLA
    attention with the keep mask reconstructed from `_keep_scale` (a
    pure function of (seed, bh, row, col)). Single source of truth for
    the interpret-mode tests AND the on-chip smoke — two hand-
    maintained copies could drift and green-light a divergent kernel."""
    from ._fa_kernel import _keep_scale
    b, sq, h, dh = q.shape
    sk, hkv = k.shape[1], k.shape[2]
    kr, vr = k, v
    if hkv != h:
        kr = jnp.repeat(kr, h // hkv, axis=2)
        vr = jnp.repeat(vr, h // hkv, axis=2)
    logits = jnp.einsum("bqhd,bkhd->bhqk", q, kr,
                        preferred_element_type=jnp.float32) / (dh ** 0.5)
    if causal:
        cm = jnp.tril(jnp.ones((sq, sk), bool), k=sk - sq)
        logits = jnp.where(cm, logits, -jnp.inf)
    if q_seg is not None:
        eq = (q_seg[:, None, :, None] == kv_seg[:, None, None, :]) & \
             (q_seg[:, None, :, None] >= 0) & \
             (kv_seg[:, None, None, :] >= 0)
        logits = jnp.where(eq, logits, -jnp.inf)
    probs = jax.nn.softmax(logits, -1)
    probs = jnp.where(jnp.isnan(probs), 0.0, probs)
    seed_s = jnp.asarray(seed).reshape(-1)[0]
    ks = jnp.stack([
        jnp.stack([_keep_scale(seed_s, bi * h + hi, 0, 0, sq, sk, p)
                   for hi in range(h)]) for bi in range(b)])
    return jnp.einsum("bhqk,bkhd->bqhd", probs * ks,
                      vr.astype(jnp.float32)).astype(q.dtype)


@functools.partial(jax.custom_vjp, nondiff_argnums=(6, 7, 8))
def _flash_core_drop(q, k, v, seed, q_seg, kv_seg, causal, scale,
                     dropout_p):
    # dropout>0 implies training, so the lse write the fwd pays is
    # never a dead inference buffer
    out, _ = _drop_fwd(q, k, v, seed, q_seg, kv_seg, causal, scale,
                       dropout_p)
    return out


def _drop_fwd(q, k, v, seed, q_seg, kv_seg, causal, scale, dropout_p):
    ok, plan = _kernel_plan("fa_forward(kernel-dropout)", q.shape,
                            k.shape)
    if ok and plan is not None:
        # per-shard calls would hash the same (seed, local bh) on every
        # shard — correlated keep masks; the reference draws one sample
        _fallback("fa_forward(kernel-dropout): not sharded over a "
                  "multi-device mesh")
        ok = False
    if ok:
        try:
            out, lse_l = _fa_fwd(None, q, k, v, causal=causal,
                                 scale=scale, return_lse=True,
                                 q_seg=q_seg, kv_seg=kv_seg,
                                 dropout_p=dropout_p, dropout_seed=seed)
            return out, (q, k, v, out, lse_l, seed, q_seg, kv_seg)
        except Exception as e:
            _fallback("fa_forward(kernel-dropout)", e)
    # reference prob-dropout with a bernoulli key derived from the seed
    # (a different — equally valid — dropout sample; residual lse None
    # keeps backward on the same path)
    key = jax.random.PRNGKey(jnp.asarray(seed).reshape(-1)[0])
    out = _ref_ext(q, k, v, None, q_seg, kv_seg, causal, scale,
                   dropout_p=dropout_p, dropout_key=key)
    return out, (q, k, v, None, None, seed, q_seg, kv_seg)


def _drop_bwd(causal, scale, dropout_p, res, g):
    q, k, v, out, lse_l, seed, q_seg, kv_seg = res
    if lse_l is not None:
        dq, dk, dv = _fa_bwd(None, q, k, v, out, lse_l, g, causal=causal,
                             scale=scale, q_seg=q_seg, kv_seg=kv_seg,
                             dropout_p=dropout_p, dropout_seed=seed)
    else:
        key = jax.random.PRNGKey(jnp.asarray(seed).reshape(-1)[0])
        _, vjp_fn = jax.vjp(
            lambda q_, k_, v_: _ref_ext(
                q_, k_, v_, None, q_seg, kv_seg, causal, scale,
                dropout_p=dropout_p, dropout_key=key), q, k, v)
        dq, dk, dv = vjp_fn(g)
    return (dq, dk, dv, _int_zero(seed), _int_zero(q_seg),
            _int_zero(kv_seg))


_flash_core_drop.defvjp(_drop_fwd, _drop_bwd)


def _flash_core(q, k, v, causal, scale):
    """Mask/segment-free core (kept as the name the rest of the framework
    dispatches through)."""
    return _flash_core_ext(q, k, v, None, None, None, causal, scale)


# ---------------------------------------------------------------------------
# flash attention that also returns the per-row logsumexp — the primitive
# ring attention (fleet/long_context.py) builds its streaming combine on.


def _attention_ref_lse(q, k, v, causal=False, scale=None, mask=None):
    """XLA reference returning (out, lse[B,H,S] f32). Accepts the same
    GQA head layout as the kernel (repeat here, never in-kernel).
    `mask` is an optional additive [B|1, H|1, Sq, Sk] slab (fully-dead
    rows emit lse=-inf and zero output)."""
    d = q.shape[-1]
    h, hkv = q.shape[2], k.shape[2]
    if hkv != h:
        k = jnp.repeat(k, h // hkv, axis=2)
        v = jnp.repeat(v, h // hkv, axis=2)
    s = scale if scale is not None else 1.0 / (d ** 0.5)
    logits = jnp.einsum("bqhd,bkhd->bhqk", q, k,
                        preferred_element_type=jnp.float32) * s
    if causal:
        sq, sk = logits.shape[-2], logits.shape[-1]
        cm = jnp.tril(jnp.ones((sq, sk), bool), k=sk - sq)
        logits = jnp.where(cm, logits, -jnp.inf)
    if mask is not None:
        logits = logits + mask.astype(logits.dtype)
    lse = jax.scipy.special.logsumexp(logits, axis=-1)    # [B,H,Sq]
    probs = jnp.exp(logits - jnp.where(jnp.isfinite(lse), lse,
                                       0.0)[..., None])
    probs = jnp.where(jnp.isnan(probs), 0.0, probs).astype(q.dtype)
    return jnp.einsum("bhqk,bkhd->bqhd", probs, v), lse


@functools.partial(jax.custom_vjp, nondiff_argnums=(3, 4))
def flash_core_lse(q, k, v, causal, scale):
    (out, lse), _ = _flash_lse_fwd(q, k, v, causal, scale)
    return out, lse


def _flash_lse_fwd(q, k, v, causal, scale):
    ok, plan = _kernel_plan("flash_core_lse", q.shape, k.shape)
    if ok:
        try:
            out, lse_l, lse = _fa_fwd(plan, q, k, v, causal=causal,
                                      scale=scale, return_lse=True,
                                      lse_bhs=True)
            return (out, lse), (q, k, v, out, lse_l, _PlanRes(plan))
        except Exception as e:
            _fallback("flash_core_lse", e)
    out, lse = _attention_ref_lse(q, k, v, causal=causal, scale=scale)
    return (out, lse), (q, k, v, None, None, None)


def _flash_lse_bwd(causal, scale, res, gs):
    g_out, g_lse = gs
    q, k, v, out, lse_l, pres = res
    b, s, h, d = q.shape
    if lse_l is not None:
        return _fa_bwd(pres.plan, q, k, v, out, lse_l, g_out,
                       causal=causal, scale=scale, g_lse=g_lse)
    if g_lse is None:
        g_lse = jnp.zeros((b, h, s), jnp.float32)
    _, vjp_fn = jax.vjp(
        lambda q_, k_, v_: _attention_ref_lse(q_, k_, v_, causal=causal,
                                              scale=scale), q, k, v)
    return vjp_fn((g_out, g_lse))


flash_core_lse.defvjp(_flash_lse_fwd, _flash_lse_bwd)


def _normalize_mask(marr, b, h, sq, sk):
    """Full masks → additive f32 [B|1, H|1, Sq, Sk] for the kernel's
    block streaming. Broadcast Sq/Sk dims are NOT materialized (a
    [B,1,1,Sk] padding mask densified to O(S²) f32 would cost the HBM
    the flash kernel exists to save) — those return None and ride the
    segment encoding or the lazily-broadcasting reference instead."""
    m = marr
    if m.ndim == 2:
        m = m[None, None]
    elif m.ndim == 3:
        m = m[:, None]
    if m.ndim != 4:
        return None
    if m.shape[2] != sq or m.shape[3] != sk or             m.shape[0] not in (1, b) or m.shape[1] not in (1, h):
        return None
    if m.dtype == jnp.bool_:
        return jnp.where(m, 0.0, -jnp.inf).astype(jnp.float32)
    return m.astype(jnp.float32)


_BIG_MASK_WARNED = False


def _warn_big_dense_mask(m):
    """ADVICE r4 #3: the kernel streams the mask in O(block) VMEM, but
    the dense [Sq, Sk] f32 operand itself is an O(Sq·Sk) HBM array built
    by the CALLER — at s=8192 that is 256 MB per head-row and dominates
    HBM before the kernel sees it. Warn once and point at the O(Sk)
    encodings."""
    global _BIG_MASK_WARNED
    if m is None or _BIG_MASK_WARNED:
        return
    if m.size * 4 >= 64 * 1024 * 1024:
        _BIG_MASK_WARNED = True
        warnings.warn(
            f"dense additive attention mask of shape {tuple(m.shape)} "
            f"costs {m.size * 4 / 2**20:.0f} MB of HBM before the flash "
            "kernel runs; for long sequences prefer the O(Sk) encodings: "
            "flashmask_attention(startend_row_indices=...) for column-"
            "band masks or q_seg/kv_seg segment ids for padding/packing")


def flash_attention_bshd(q, k, v, mask=None, causal=False, dropout_p=0.0,
                         scale=None, q_seg=None, kv_seg=None,
                         return_probs=False):
    """Framework-level entry on Tensors; [B, S, H, D] layout (k/v may
    carry fewer heads — GQA runs natively in the kernel). `mask` is
    bool (True = keep) or additive; q_seg/kv_seg are int32 [B, S] packed
    segment ids (varlen).

    `dropout_p` > 0 applies reference-semantics dropout to the softmax
    PROBABILITIES (attention links), not the output (VERDICT r4 missing
    #3); the Pallas kernels carry no PRNG path, so dropout>0 training
    runs the XLA reference with exact prob-dropout — a loud counted
    fallback on TPU. `return_probs` additionally returns the (post-
    dropout) probabilities."""
    b, sq, h, _ = q.shape
    sk = k.shape[1]
    marr = None       # kernel-streamable additive [B|1, H|1, Sq, Sk]
    marr_raw = None   # reference-only additive (lazy broadcast shapes)
    qsa = q_seg._data if q_seg is not None and hasattr(q_seg, "_data") \
        else q_seg
    ksa = kv_seg._data if kv_seg is not None and hasattr(kv_seg, "_data") \
        else kv_seg
    if mask is not None:
        raw = mask._data
        if (raw.ndim == 4 and raw.shape[1] == 1 and raw.shape[2] == 1 and
                raw.dtype == jnp.bool_ and qsa is None):
            # bool key-padding mask → segment encoding: O(S) memory and
            # dead-block skipping instead of an O(Sq·Sk) dense mask
            # (cross-length too — segments are rectangular-native)
            keep = jnp.broadcast_to(raw[:, 0, 0, :], (b, sk))
            ksa = jnp.where(keep, 0, -2).astype(jnp.int32)
            qsa = jnp.zeros((b, sq), jnp.int32)
        else:
            marr = _normalize_mask(raw, b, h, sq, sk)
            if marr is None:
                marr_raw = raw if raw.dtype != jnp.bool_ else \
                    jnp.where(raw, 0.0, -jnp.inf).astype(jnp.float32)

    if dropout_p > 0.0 or return_probs:
        if (0.0 < dropout_p < 1.0 and not return_probs and
                _kernel_dropout_enabled() and _want_pallas() and
                marr is None and marr_raw is None and sq == sk and
                _shape_reason(q.shape, k.shape) is None):
            # in-kernel counter-hash dropout (opt-in): flash perf for
            # dropout>0 training; RNG still rides next_key() so seed
            # capture / recompute replay hold
            seed = jax.random.randint(next_key(), (1,), 0, 2 ** 31 - 1,
                                      dtype=jnp.int32)

            def f_kd(qa, ka, va):
                return _flash_core_drop(qa, ka, va, seed, qsa, ksa,
                                        causal, scale, float(dropout_p))
            return apply(f_kd, q, k, v, name="attention")
        # probability-dropout / returned-softmax: XLA reference path
        # (exact semantics; differentiable through jax AD; RNG rides
        # next_key() so recompute replay + seed capture apply).
        dkey = next_key() if dropout_p > 0.0 else None
        m_use = marr if marr is not None else marr_raw
        if _want_pallas():
            _fallback("prob-dropout/return_softmax: XLA reference "
                      "(no in-kernel PRNG path; set "
                      "PADDLE_TPU_FA_KERNEL_DROPOUT=1 for the "
                      "counter-hash kernel once chip-validated)")

        def f_pd(qa, ka, va):
            return _ref_ext(qa, ka, va, m_use, qsa, ksa, causal, scale,
                            dropout_p=dropout_p, dropout_key=dkey,
                            return_probs=return_probs)
        return apply(f_pd, q, k, v, name="attention")

    if marr_raw is not None:
        # not kernel-streamable — XLA reference with the RAW mask (lazy
        # broadcast) COMBINED with any segments (a seg-only kernel call
        # would silently drop the mask)
        def f_raw(qa, ka, va):
            return _ref_ext(qa, ka, va, marr_raw, qsa, ksa, causal,
                            scale)
        if _want_pallas():
            _fallback(f"mask shape {tuple(mask._data.shape)} not "
                      "kernel-streamable")
        return apply(f_raw, q, k, v, name="attention")

    _warn_big_dense_mask(marr)

    def f(qa, ka, va):
        return _flash_core_ext(qa, ka, va, marr, qsa, ksa, causal, scale)
    return apply(f, q, k, v, name="attention")


def flash_attention(query, key, value, dropout=0.0, causal=False,
                    return_softmax=False, fixed_seed_offset=None, rng_name="",
                    training=True, name=None):
    """Reference-parity API: paddle.nn.functional.flash_attention.

    `return_softmax=True` is HONORED (VERDICT r4 weak #8 — it used to
    silently return (out, None)): the post-dropout probabilities come
    back via the XLA reference path (counted fallback on TPU — the
    kernel never materializes the O(Sq·Sk) probs)."""
    drop_p = dropout if training else 0.0
    if return_softmax:
        return flash_attention_bshd(query, key, value, causal=causal,
                                    dropout_p=drop_p, return_probs=True)
    return flash_attention_bshd(query, key, value, causal=causal,
                                dropout_p=drop_p), None


# ---------------------------------------------------------------------------
# FlashMask (SURVEY §5.7c): compact column-bound masks at O(Sk) memory.
# Column j masks query rows [fm_start_j, fm_end_j) — the dense [Sq, Sk]
# additive slab never exists; the kernels stream (start, end) per key
# block and skip fully-dead blocks.


def _fm_dense_mask(fm_start, fm_end, sq, fm_start2=None, fm_end2=None):
    """Dense additive oracle for the column bounds ([B|1, H|1, Sk] →
    [B|1, H|1, Sq, Sk] 0/-inf); optional second band (C=4 form).
    Tests + fallback only."""
    rows = jnp.arange(sq)[None, None, :, None]
    dead = (rows >= fm_start[:, :, None, :]) & \
           (rows < fm_end[:, :, None, :])
    if fm_start2 is not None:
        dead = dead | ((rows >= fm_start2[:, :, None, :]) &
                       (rows < fm_end2[:, :, None, :]))
    return jnp.where(dead, -jnp.inf, 0.0).astype(jnp.float32)


def _fm_ref(q, k, v, fm_start, fm_end, fm_start2, fm_end2, causal,
            scale, dropout_p=0.0, dropout_key=None):
    m = _fm_dense_mask(fm_start, fm_end, q.shape[1], fm_start2, fm_end2)
    # fully-masked rows (padding rows whose visible columns are all
    # dead, or causally-dead rows at sq > sk): the kernel emits exact
    # zeros with zero grads; softmax of an all--inf row would emit nan
    # with NaN GRADS through the vjp. Fold causal INTO the mask, run
    # dead rows unmasked (mask and causal both neutralized), and zero
    # their output.
    sq, sk = q.shape[1], k.shape[1]
    if causal:
        cm = jnp.tril(jnp.ones((sq, sk), bool), k=sk - sq)
        m = jnp.where(cm[None, None], m, -jnp.inf)
    dead_row = jnp.all(~jnp.isfinite(m), axis=-1)      # [B|1, H|1, Sq]
    m_safe = jnp.where(dead_row[..., None], 0.0, m)
    out = _attention_ref(q, k, v, mask=m_safe, causal=False,
                         scale=scale, dropout_p=dropout_p,
                         dropout_key=dropout_key)
    return jnp.where(jnp.swapaxes(dead_row, 1, 2)[..., None], 0.0, out)


def _try_kernel_fm(q, k, v, fm, causal, scale, want_lse, site,
                   lse_bhs=False):
    """One shared kernel-dispatch body for both fm entry points: returns
    ``(kernel result, plan residual)`` or None after the standard
    counted fallback. fm = (start, end, start2, end2) with None
    placeholders for the single-band forms (fa_forward filters
    Nones)."""
    ok, plan = _kernel_plan(
        site, q.shape, k.shape,
        None if _streamed_kernels_enabled()
        else "disabled by PADDLE_TPU_FA_STREAMED=0")
    if not ok:
        return None
    try:
        res = _fa_fwd(plan, q, k, v, causal=causal, scale=scale,
                      return_lse=want_lse, lse_bhs=lse_bhs, fm=fm)
        return res, _PlanRes(plan)
    except Exception as e:
        _fallback(site, e)
    return None


@functools.partial(jax.custom_vjp, nondiff_argnums=(7, 8))
def _flash_core_fm(q, k, v, fm_start, fm_end, fm_start2, fm_end2,
                   causal, scale):
    fm = (fm_start, fm_end, fm_start2, fm_end2)
    res = _try_kernel_fm(q, k, v, fm, causal, scale, False,
                         "flashmask_forward")
    if res is not None:
        return res[0]
    return _fm_ref(q, k, v, fm_start, fm_end, fm_start2, fm_end2,
                   causal, scale)


def _fm_fwd(q, k, v, fm_start, fm_end, fm_start2, fm_end2, causal,
            scale):
    fm = (fm_start, fm_end, fm_start2, fm_end2)
    res = _try_kernel_fm(q, k, v, fm, causal, scale, True,
                         "flashmask_forward(train)")
    if res is not None:
        (out, lse_l), pres = res
        return out, (q, k, v, out, lse_l, fm, pres)
    out = _fm_ref(q, k, v, fm_start, fm_end, fm_start2, fm_end2,
                  causal, scale)
    return out, (q, k, v, None, None, fm, None)


def _fm_bwd(causal, scale, res, g):
    q, k, v, out, lse_l, fm, pres = res
    if lse_l is not None:
        dq, dk, dv = _fa_bwd(pres.plan, q, k, v, out, lse_l, g,
                             causal=causal, scale=scale, fm=fm)
    else:
        _, vjp_fn = jax.vjp(
            lambda q_, k_, v_: _fm_ref(q_, k_, v_, fm[0], fm[1], fm[2],
                                       fm[3], causal, scale), q, k, v)
        dq, dk, dv = vjp_fn(g)
    return tuple([dq, dk, dv] + [_int_zero(a) for a in fm])


_flash_core_fm.defvjp(_fm_fwd, _fm_bwd)


def _fm_causal_mask(fm, sq, sk, causal):
    """Dense additive slab for the fm bounds WITH causal folded in —
    the reference-side mask matching the kernel's lse semantics
    (fully-dead rows → lse -inf)."""
    m = _fm_dense_mask(fm[0], fm[1], sq, fm[2], fm[3])
    if causal:
        cm = jnp.tril(jnp.ones((sq, sk), bool), k=sk - sq)
        m = jnp.where(cm[None, None], m, -jnp.inf)
    return m


@functools.partial(jax.custom_vjp, nondiff_argnums=(7, 8))
def flash_core_fm_lse(q, k, v, fm_start, fm_end, fm_start2, fm_end2,
                      causal, scale):
    """FlashMask attention that ALSO returns the per-row logsumexp
    (round 5: the `return_softmax_lse=True` payload, previously a
    warned None shim — VERDICT r4 weak #8 follow-through)."""
    (out, lse), _ = _fm_lse_fwd(q, k, v, fm_start, fm_end, fm_start2,
                                fm_end2, causal, scale)
    return out, lse


def _fm_ref_lse(q, k, v, fm, causal, scale):
    """Reference (out, lse) for the fm bounds with the dead-row contract
    `_fm_ref` keeps: fully-masked rows emit ZERO output, lse = -inf, and
    ZERO (not NaN) grads — logsumexp's VJP at an all--inf row is
    exp(-inf − (-inf)) = NaN even under a zero cotangent, so dead rows
    run unmasked (safe) and are selected out after."""
    sq, sk = q.shape[1], k.shape[1]
    m = _fm_causal_mask(fm, sq, sk, causal)
    dead_row = jnp.all(~jnp.isfinite(m), axis=-1)      # [B|1, H|1, Sq]
    m_safe = jnp.where(dead_row[..., None], 0.0, m)
    out, lse = _attention_ref_lse(q, k, v, causal=False, scale=scale,
                                  mask=m_safe)
    out = jnp.where(jnp.swapaxes(dead_row, 1, 2)[..., None], 0.0, out)
    lse = jnp.where(dead_row, -jnp.inf, lse)
    return out, lse


def _fm_lse_fwd(q, k, v, fm_start, fm_end, fm_start2, fm_end2, causal,
                scale):
    fm = (fm_start, fm_end, fm_start2, fm_end2)
    res = _try_kernel_fm(q, k, v, fm, causal, scale, True,
                         "flashmask_lse", lse_bhs=True)
    if res is not None:
        (out, lse_l, lse), pres = res
        return (out, lse), (q, k, v, out, lse_l, fm, pres)
    out, lse = _fm_ref_lse(q, k, v, fm, causal, scale)
    return (out, lse), (q, k, v, None, None, fm, None)


def _fm_lse_bwd(causal, scale, res, gs):
    g_out, g_lse = gs
    q, k, v, out, lse_l, fm, pres = res
    b, sq, h, d = q.shape
    if lse_l is not None:
        dq, dk, dv = _fa_bwd(pres.plan, q, k, v, out, lse_l, g_out,
                             causal=causal, scale=scale, g_lse=g_lse,
                             fm=fm)
    else:
        if g_lse is None:
            g_lse = jnp.zeros((b, h, sq), jnp.float32)
        # -inf dead-row lse entries would turn a zero cotangent into
        # 0·(-inf) NaNs downstream of the primal select; the vjp of the
        # SAFE function with the dead-row select built in is NaN-free
        g_lse = jnp.where(jnp.isfinite(g_lse), g_lse, 0.0)
        _, vjp_fn = jax.vjp(
            lambda q_, k_, v_: _fm_ref_lse(q_, k_, v_, fm, causal,
                                           scale), q, k, v)
        dq, dk, dv = vjp_fn((g_out, g_lse))
    return tuple([dq, dk, dv] + [_int_zero(a) for a in fm])


flash_core_fm_lse.defvjp(_fm_lse_fwd, _fm_lse_bwd)


def _normalize_startend(startend_row_indices, sk):
    """PaddleNLP FlashMask layout [B, H|1, Sk, C] int32 →
    (start, end[, start2, end2]) [B, H|1, Sk] row bands. C=1: rows
    [start_j, Sq) masked (the LT-start causal document form); C=2: the
    [start_j, end_j) band; C=4: two bands — [LTS, LTE) below and
    [UTS, UTE) above (the bidirectional form)."""
    idx = startend_row_indices
    if idx.ndim != 4 or idx.shape[2] != sk or \
            idx.shape[3] not in (1, 2, 4):
        raise ValueError(
            "startend_row_indices must be [B, H|1, Sk, 1|2|4] int32, "
            f"got {tuple(idx.shape)}")
    start = idx[..., 0].astype(jnp.int32)
    if idx.shape[3] == 1:
        return (start, jnp.full_like(start, jnp.iinfo(jnp.int32).max))
    end = idx[..., 1].astype(jnp.int32)
    if idx.shape[3] == 2:
        return (start, end)
    return (start, end, idx[..., 2].astype(jnp.int32),
            idx[..., 3].astype(jnp.int32))


def flashmask_attention(query, key, value, startend_row_indices=None,
                        dropout=0.0, causal=True, window_size=None,
                        return_softmax_lse=False, fixed_seed_offset=None,
                        rng_name="", training=True, name=None):
    """Reference-parity API: paddle.nn.functional.flashmask_attention —
    attention with a COMPACT column-wise mask ([B, H|1, Sk, 1|2|4]
    int32 query-row bounds per key column; O(Sk) memory) instead of a
    dense [Sq, Sk] mask: C=1 LT-start, C=2 one [start, end) band, C=4
    two bands (bidirectional LT+UT). Composes with causal."""
    q = query
    k = key
    v = value
    sk = k.shape[1]
    # one unwrap + one validation site: raw [B, H|1, Sk, C] or None,
    # then everything below works on the NORMALIZED (start, end[, 2])
    # tuples — the window fold included
    raw = None
    fm = None
    if startend_row_indices is not None:
        raw = startend_row_indices._data \
            if hasattr(startend_row_indices, "_data") else \
            jnp.asarray(startend_row_indices)
        fm = list(_normalize_startend(raw, sk))
    win_rows = None
    if window_size is not None:
        # sliding-window causal attention IS an LT-start bound: key
        # column j is visible to query rows [j, j+w], i.e. rows
        # >= j+w+1 masked — O(Sk) bounds, no dense mask
        if not causal:
            raise NotImplementedError(
                "flashmask_attention window_size requires causal=True "
                "(the reference's sliding-window form)")
        w = window_size[0] if isinstance(window_size, (tuple, list)) \
            else int(window_size)
        if w >= 0:      # reference sentinel: -1 / (-1, -1) = disabled
            # bottom-right-aligned coordinates (the rectangular-grid
            # causal convention, offset = sk - sq): key j is visible to
            # query row i iff i + offset - w <= j <= i + offset, so
            # column j masks rows >= j + w + 1 - offset
            sq = q.shape[1]
            offset = sk - sq
            if fm is None and w + 1 - offset >= sq:
                # column 0 masks no row, so no column does: the window
                # cannot bind at these lengths and the call is plain
                # causal (the resident forward, no bounds to stream)
                _DISPATCH["window_as_causal"] += 1
            else:
                win_rows = jnp.maximum(
                    jnp.arange(sk, dtype=jnp.int32) + w + 1 - offset, 0
                )[None, None, :]                      # [1, 1, Sk]
    imax = jnp.iinfo(jnp.int32).max
    if win_rows is not None:
        # compose (round 5): the window is one more masked row band per
        # column, folded at the normalized level — C=1 takes the
        # column-wise min of LT-starts; C=2 promotes to the two-band
        # C=4 form with the window as band 2. C=4 already carries two
        # bands — a third cannot be encoded. Band arrays share the
        # FIRST band's batch/head dims (the kernel streams all bands
        # through one BlockSpec row map).
        if fm is None:
            fm = [win_rows, jnp.full_like(win_rows, imax)]
        elif len(fm) == 2 and raw.shape[3] == 1:
            fm[0] = jnp.minimum(fm[0], win_rows)
        elif len(fm) == 2:
            fm += [jnp.broadcast_to(win_rows, fm[0].shape),
                   jnp.full_like(fm[0], imax)]
        else:
            raise NotImplementedError(
                "flashmask_attention: window_size composes with C=1 or "
                "C=2 startend_row_indices (folded to min-start / the "
                "C=4 two-band form); C=4 already carries two bands and "
                "cannot take a third")
    drop_p = dropout if training else 0.0
    if return_softmax_lse and drop_p > 0.0:
        warnings.warn(
            "flashmask_attention(return_softmax_lse=True) with dropout>0 "
            "returns lse=None (the dropped-probs path does not carry "
            "lse); call with dropout=0 for a real lse")
    if fm is None:
        if return_softmax_lse and drop_p == 0.0:
            # honor the lse return on the plain-causal form: the
            # kernel-native flash_core_lse carries it (weak #8 —
            # no silent None where the value is computable)
            def f_lse(qa, ka, va):
                return flash_core_lse(qa, ka, va, causal, None)
            return apply(f_lse, q, k, v, name="flashmask_attention")
        out = flash_attention_bshd(q, k, v, causal=causal,
                                   dropout_p=drop_p)
        return (out, None) if return_softmax_lse else out
    b, h = q.shape[0], q.shape[2]
    if fm[0].shape[0] not in (1, b) or fm[0].shape[1] not in (1, h):
        # reject BEFORE the kernel: an out-of-range BlockSpec row index
        # would be silently clamped (wrong output, no error)
        raise ValueError(
            f"startend_row_indices batch/head dims "
            f"{tuple(fm[0].shape[:2])} incompatible with q "
            f"[B={b}, H={h}]")

    fm = tuple(fm) + (None,) * (4 - len(fm))   # fixed 4-slot protocol

    if drop_p > 0.0:
        # probability dropout (reference semantics, VERDICT r4 missing
        # #3): the fm bounds densify in the XLA reference — exact, loud
        # counted fallback on TPU
        dkey = next_key()
        if _want_pallas():
            _fallback("flashmask prob-dropout: XLA reference "
                      "(no in-kernel PRNG path)")

        def f_pd(qa, ka, va):
            return _fm_ref(qa, ka, va, fm[0], fm[1], fm[2], fm[3],
                           causal, None, dropout_p=drop_p,
                           dropout_key=dkey)
        out = apply(f_pd, q, k, v, name="flashmask_attention")
        return (out, None) if return_softmax_lse else out

    if return_softmax_lse:
        # round 5: real lse through the FlashMask custom_vjp (kernel
        # train path already carries it; reference computes it exactly)
        def f_lse(qa, ka, va):
            return flash_core_fm_lse(qa, ka, va, fm[0], fm[1], fm[2],
                                     fm[3], causal, None)
        return apply(f_lse, q, k, v, name="flashmask_attention")

    def f(qa, ka, va):
        return _flash_core_fm(qa, ka, va, fm[0], fm[1], fm[2], fm[3],
                              causal, None)
    return apply(f, q, k, v, name="flashmask_attention")
