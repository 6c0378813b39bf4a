"""Lightning Attention's recurrence (MiniMax-01, arXiv:2501.08313): linear
attention with a scalar decay a head, on arrays.

Per head, with a state ``S`` of ``[dk, dv]`` held in float32 and nought at
a sequence's start, a row ``t`` with a query and key ``q_t``, ``k_t``
``[dk]``, a value ``v_t`` ``[dv]`` and a log-decay ``g_t <= 0`` (the
head's ``log lambda``):

    S_t = exp(g_t) S_{t-1} + k_t v_t^T
    o_t = scale * S_t^T q_t

It is ``ops/delta_rule.py``'s recurrence without the rank-one correction.
Over ``N`` lanes of ``S`` rows each, carried in from ``state0`` and out as
the state after each lane's last *live* row: a row that is not live is
given ``g = 0`` and ``k = 0``, which leaves the state as it was, and its
``o`` is not to be read.

Two routes in plain ``jax.lax`` / ``jnp`` (:func:`lightning_attention`
chooses from the static shapes), and the delta rule's decode kernel
(``ops/pallas/_delta_kernel.py``, ``rule="lightning"``) where a lane brings
one row on a TPU:

- :func:`lightning_step`, one row a lane (the decode rows);
- :func:`lightning_chunk`, a prefill chunk's rows, in sub-chunks of
  ``SUB`` rows: with ``G`` the inclusive cumulative log-decay from the
  sub-chunk's start, ``O = (Q exp(G)) S + ((Q K^T) * exp(G_t - G_s))_{s<=t}
  V`` and ``S <- exp(G_last) S + (K exp(G_last - G))^T V``. Every
  exponent is a difference of later minus earlier, so nought or below:
  no decay, however strong, overflows.

Everything is computed in float32, the products at ``HIGHEST`` precision;
``o`` comes back in float32. Lanes may name their states by slot, as in
:func:`~.delta_rule.delta_rule`.
"""
from __future__ import annotations

import jax
import jax.numpy as jnp

from .delta_rule import _DISPATCH, _kernel_route

__all__ = ["lightning_attention", "lightning_step", "lightning_chunk",
           "SUB"]

F32 = jnp.float32
HI = jax.lax.Precision.HIGHEST
SUB = 64            # rows a sub-chunk of the chunkwise form


def _dead_rows_keep(k, g, live):
    """A row that is not live decays nothing and writes nothing."""
    if live is None:
        return k, g
    return (jnp.where(live[..., None, None], k, 0.0),
            jnp.where(live[..., None], g, 0.0))


def lightning_step(q, k, v, g, state, scale):
    """One row a lane: q, k ``[N, H, dk]``, v ``[N, H, dv]``, g ``[N, H]``,
    state ``[N, H, dk, dv]`` float32. Returns ``(o [N, H, dv] float32,
    state)``."""
    with jax.named_scope("lightning_step"):
        qf, kf, vf = q.astype(F32), k.astype(F32), v.astype(F32)
        s = (jnp.exp(g.astype(F32))[..., None, None] * state.astype(F32)
             + kf[..., None] * vf[..., None, :])
        return scale * jnp.sum(s * qf[..., None], axis=-2), s


def lightning_chunk(q, k, v, g, state0, scale, live=None):
    """A lane's run of rows: q, k ``[N, S, H, dk]``, v ``[N, S, H, dv]``, g
    ``[N, S, H]``, state0 ``[N, H, dk, dv]`` float32, live ``[N, S]`` bool
    or None. Returns ``(o [N, S, H, dv] float32, state)``."""
    with jax.named_scope("lightning_chunk"):
        n, s, h, dk = q.shape
        dv = v.shape[-1]
        k, g = _dead_rows_keep(k.astype(F32), g.astype(F32), live)
        sub = min(SUB, s)
        pad = (-s) % sub
        if pad:
            grow = lambda x: jnp.pad(                          # noqa: E731
                x, [(0, 0), (0, pad)] + [(0, 0)] * (x.ndim - 2))
            q, k, v, g = map(grow, (q, k, v, g))
        c = (s + pad) // sub

        def subs(x):              # [N, S, H, ...] -> [C, N, H, sub, ...]
            x = x.astype(F32).reshape((n, c, sub) + x.shape[2:])
            return jnp.moveaxis(jnp.moveaxis(x, 1, 0), 2, 3)

        q, k, v, g = map(subs, (q, k, v, g))          # g [C, N, H, sub]
        G = jnp.cumsum(g, axis=-1)
        lower = jnp.tril(jnp.ones((sub, sub), bool))
        decay = jnp.exp(jnp.where(lower, G[..., :, None] - G[..., None, :],
                                  -jnp.inf))          # [C,N,H,sub,sub]
        p = jnp.einsum("...tk,...sk->...ts", q, k, precision=HI) * decay
        intra = jnp.einsum("...ts,...sv->...tv", p, v, precision=HI)
        qd = q * jnp.exp(G)[..., None]
        last = G[..., -1:]                              # [C, N, H, 1]
        kd = k * jnp.exp(last - G)[..., None]

        def sub_chunk(st, x):
            qd_, kd_, v_, intra_, last_ = x
            o = jnp.einsum("...tk,...kv->...tv", qd_, st,
                           precision=HI) + intra_
            st = (jnp.exp(last_)[..., None] * st
                  + jnp.einsum("...tk,...tv->...kv", kd_, v_,
                               precision=HI))
            return st, o

        state, o = jax.lax.scan(sub_chunk, state0.astype(F32),
                                (qd, kd, v, intra, last))
        o = jnp.moveaxis(jnp.moveaxis(o, 3, 2), 0, 1)    # [N,C,sub,H,dv]
        return scale * o.reshape(n, c * sub, h, dv)[:, :s], state


def lightning_attention(q, k, v, g, state0, scale, live=None, slots=None,
                        fresh=None):
    """The route for the shapes: the decode kernel or
    :func:`lightning_step` where a lane brings one row,
    :func:`lightning_chunk` otherwise. Shapes as :func:`lightning_chunk`'s;
    with ``slots [N]`` int32 and ``fresh [N]`` bool, ``state0`` and the
    state returned are every slot's, ``[M, H, dk, dv]``."""
    if _kernel_route(q, state0, slots):
        from . import delta_rule
        from .pallas._delta_kernel import delta_step_slots
        _DISPATCH["kernel"] += 1
        lane_live = (jnp.ones(q.shape[0], bool) if live is None
                     else live[:, 0])
        # the decay a head along its key channels; beta is not read
        gk = jnp.broadcast_to(g[:, 0, :, None].astype(F32), q[:, 0].shape)
        state, o = delta_step_slots(
            q[:, 0], k[:, 0], v[:, 0], gk, g[:, 0], state0, slots, fresh,
            lane_live, scale=scale, interpret=delta_rule._FORCE_INTERPRET,
            rule="lightning")
        return o[:, None], state
    whole = state0
    if slots is not None:
        # a lane whose first row starts a sequence: nought
        state0 = jnp.where(fresh[:, None, None, None], 0, whole[slots])
    if q.shape[1] == 1:
        _DISPATCH["step"] += 1
        kk, gg = _dead_rows_keep(k.astype(F32), g.astype(F32), live)
        o, state = lightning_step(q[:, 0], kk[:, 0], v[:, 0], gg[:, 0],
                                  state0, scale)
        o = o[:, None]
    else:
        _DISPATCH["chunk"] += 1
        o, state = lightning_chunk(q, k, v, g, state0, scale, live)
    if slots is not None:
        state = whole.at[slots].set(state)
    return o, state
