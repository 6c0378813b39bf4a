"""The delta rule's one-row recurrence (``ops/delta_rule.py::delta_step``)
as one Pallas kernel over the whole lane-state array.

Each lane reads its ``[H, d, d]`` float32 state from its slot once,
applies the recurrence in VMEM and writes the state back into the same
slot: the state operand is aliased to the state output, so a donated
array is written where it lies and no gather, scatter or zeroed buffer
is made around the call. Per head, with ``S`` the slot's state (nought
where the lane is fresh):

    s' = exp(g) * S              (a row of S a key channel)
    u  = beta * (v - s'^T k)
    S  = s' + k u^T
    o  = scale * S^T q

all elementwise products and sublane sums in float32 on the VPU: the
state is never rounded and no product goes to the MXU.

The grid runs over (head block, lane), lanes innermost; the slots, a
``fresh`` flag and a ``live`` flag a lane come in as scalar prefetch and
the state's block is ``(slot[n], head block)``. A lane that is not live
leaves its slot as it was and gives ``o = 0``: dead lanes all name the
scratch slot 0, so a run of them is one block, fetched once and written
back once.

The vectors come as they are, ``[N, H, d]`` (``beta`` broadcast to a
row a head); those a head reads along the state's rows (``exp(g)``,
``k``, ``q``) are transposed in VMEM, a block's heads at once, so that
a head's column broadcasts across the state's lanes.

With ``rule="lightning"`` the same kernel runs Lightning Attention's
recurrence (``ops/lightning_attn.py``): no correction, ``S = exp(g) * S
+ k v^T``, ``o = scale * S^T q``, the decay a head broadcast along its
key channels; the call is then named ``lightning_decode``.

The output order is fixed: ``(state, o)``. A trace names the call by its
first output, ``f32[slots, H, d, d]``, the same shape as every other
operation on the state (``benchmark/traffic/longchain.json``'s
``kda_op_match``).
"""
from __future__ import annotations

import functools

import jax
import jax.numpy as jnp
from jax.experimental import pallas as pl
from jax.experimental.pallas import tpu as pltpu

F32 = jnp.float32
# VMEM for the state's blocks, in and out, each double-buffered: 8 heads
# of 128 x 128. At 64 lanes of 32 heads, blocks of 8, 16 and 32 heads
# timed within 4 % of each other on a v5e; the smallest compiles
# fastest.
STATE_VMEM = 2 * 2 ** 20


def heads_per_block(h: int, d: int) -> int:
    """The most heads a block whose four state buffers fit
    ``STATE_VMEM`` (else the fewest it may hold): a divisor of ``h``
    that is ``h`` or a multiple of 8 (the second-minor tile of the
    vector arrays)."""
    fits = [b for b in range(h, 0, -1)
            if h % b == 0 and (b == h or b % 8 == 0)]
    return next((b for b in fits if 4 * b * d * d * 4 <= STATE_VMEM),
                fits[-1])


def _kernel(slot_ref, fresh_ref, live_ref, q_ref, k_ref, g_ref, v_ref,
            b_ref, s_ref, s_out, o_ref, *, hb, scale, delta):
    n = pl.program_id(1)

    @pl.when(live_ref[n] != 0)
    def _():
        fresh = fresh_ref[n] != 0
        decay, k, q = (jnp.transpose(x) for x in (      # [d, hb] each
            jnp.exp(g_ref[0]), k_ref[0], q_ref[0]))
        for j in range(hb):
            kj, qj = k[:, j:j + 1], q[:, j:j + 1]
            s = jnp.where(fresh, 0.0, s_ref[0, j]) * decay[:, j:j + 1]
            u = v_ref[0, j:j + 1]
            if delta:
                kv = jnp.sum(s * kj, axis=0, keepdims=True)     # s'^T k
                u = b_ref[0, j:j + 1] * (u - kv)
            s = s + kj * u
            s_out[0, j] = s
            o_ref[0, j:j + 1] = scale * jnp.sum(s * qj, axis=0,
                                                keepdims=True)

    @pl.when(live_ref[n] == 0)
    def _():
        s_out[...] = s_ref[...]
        o_ref[...] = jnp.zeros_like(o_ref)


def delta_step_slots(q, k, v, g, beta, state, slot, fresh, live, *, scale,
                     interpret=False, rule="delta"):
    """q, k, g, v ``[N, H, d]``, beta ``[N, H]``, state ``[M, H, d, d]``
    float32 (every slot), slot ``[N]`` int32, fresh / live ``[N]`` bool.
    Returns ``(state [M, H, d, d], o [N, H, d] float32)``: lane ``n``'s
    state after its row in ``state[slot[n]]``, every other slot as it
    was. ``rule="lightning"``: no correction (``beta`` is not read)."""
    n, h, d = q.shape
    hb = heads_per_block(h, d)
    beta = jnp.broadcast_to(beta.astype(F32)[..., None], q.shape)
    vecs = [x.astype(F32) for x in (q, k, g, v)] + [beta]
    at_slot = lambda hh, nn, sl, fr, lv: (sl[nn], hh, 0, 0)  # noqa: E731
    vec_block = pl.BlockSpec((1, hb, d), lambda hh, nn, *_: (nn, hh, 0))
    state_block = pl.BlockSpec((1, hb, d, d), at_slot)
    block_bytes = hb * d * d * 4
    return pl.pallas_call(
        functools.partial(_kernel, hb=hb, scale=scale,
                          delta=rule == "delta"),
        grid_spec=pltpu.PrefetchScalarGridSpec(
            num_scalar_prefetch=3, grid=(h // hb, n),
            in_specs=[vec_block] * 5 + [state_block],
            out_specs=[state_block, vec_block]),
        out_shape=[jax.ShapeDtypeStruct(state.shape, F32),
                   jax.ShapeDtypeStruct((n, h, d), F32)],
        input_output_aliases={8: 0},
        compiler_params=pltpu.CompilerParams(
            dimension_semantics=("arbitrary", "arbitrary"),
            vmem_limit_bytes=4 * block_bytes + 16 * 2 ** 20),
        cost_estimate=pl.CostEstimate(
            flops=(8 if rule == "delta" else 5) * n * h * d * d,
            transcendentals=n * h * d,
            bytes_accessed=2 * n * block_bytes * (h // hb)),
        interpret=interpret,
        name="kda_decode" if rule == "delta" else f"{rule}_decode",
    )(slot.astype(jnp.int32), fresh.astype(jnp.int32),
      live.astype(jnp.int32), *vecs, state)
