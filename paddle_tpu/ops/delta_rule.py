"""The gated delta rule with a decay a channel: Kimi Delta Attention's
recurrence (KDA, arXiv:2510.26692), on arrays.

Per head, with a state ``S`` of ``[dk, dv]`` held in float32 and nought
at a sequence's start, a row ``t`` with a normed query and key ``q_t``,
``k_t`` ``[dk]``, a value ``v_t`` ``[dv]``, a log-decay ``g_t`` ``[dk]``
(``<= 0``) and a write strength ``beta_t``:

    S'  = Diag(exp(g_t)) S_{t-1}
    S_t = S' + beta_t k_t (v_t - S'^T k_t)^T
    o_t = scale * S_t^T q_t

over ``N`` lanes of ``S`` rows each, carried in from ``state0`` and out as
the state after each lane's last *live* row: a row that is not live
(padding behind a lane's rows) is given ``g = 0`` and ``beta = 0``, which
leaves the state as it was, and its ``o`` is not to be read.

Two routes, chosen from the static shapes (:func:`delta_rule`):

- :func:`delta_step`, one row a lane (the decode rows): the recurrence
  itself, elementwise products and sums in float32 over each lane's
  state, read and written once.
- :func:`delta_chunk`, a prefill chunk's rows: the chunkwise-parallel
  form over sub-chunks of ``SUB`` rows (the WY / UT transform). Within a
  sub-chunk with inclusive cumulative log-decay ``G`` from its start,
  ``K~ = k exp(G)``, ``Q~ = q exp(G)``, ``K^ = k exp(-G)``; ``A`` the
  strictly lower ``beta_t (K~_t . K^_s)``, ``T = (I + A)^-1``,
  ``W = T diag(beta) K~``, ``U~ = T diag(beta) V``, ``P`` the inclusive
  lower ``Q~_t . K^_s``; then, sub-chunk after sub-chunk,
  ``U = U~ - W S``, ``O = Q~ S + P U``, ``S <- Diag(exp(G_last)) S +
  (k exp(G_last - G))^T U``. ``exp(G_t) exp(-G_s)`` is formed only inside
  a sub-chunk: with every row's decay at least ``GATE_FLOOR`` (the safe
  gate's lower bound, -5) a sub-chunk of 16 spans at most -80, inside
  float32's exponent (``exp(-88)`` is its least normal number); a longer
  span could overflow ``exp(-G)``. The caller owns that bound.

Everything is computed in float32 (the products at ``HIGHEST``
precision, so the state is never rounded to bfloat16 on the way
through); ``o`` comes back in float32. Plain ``jax.lax`` / ``jnp``: a
kernel, when one is written, is a third route chosen here from the same
shapes. What the benchmark's ``kda_scan_roofline`` counts is this
contract's work whatever computes it (``benchmark/harness/
flops_kda_moe.py``).
"""
from __future__ import annotations

import jax
import jax.numpy as jnp

__all__ = ["delta_rule", "delta_step", "delta_chunk", "SUB", "GATE_FLOOR"]

F32 = jnp.float32
HI = jax.lax.Precision.HIGHEST
SUB = 16            # rows a sub-chunk: 16 x GATE_FLOOR = -80 > -88
GATE_FLOOR = -5.0   # the least log-decay a row may carry (kda_lower_bound)


def _dead_rows_keep(g, beta, live):
    """A row that is not live decays nothing and writes nothing."""
    if live is None:
        return g, beta
    return (jnp.where(live[..., None, None], g, 0.0),
            jnp.where(live[..., None], beta, 0.0))


def delta_step(q, k, v, g, beta, state, scale):
    """One row a lane: q, k, g ``[N, H, dk]``, v ``[N, H, dv]``, beta
    ``[N, H]``, state ``[N, H, dk, dv]`` float32. Returns ``(o [N, H,
    dv] float32, state)``."""
    with jax.named_scope("kda_step"):
        qf, kf, vf = q.astype(F32), k.astype(F32), v.astype(F32)
        s = jnp.exp(g.astype(F32))[..., None] * state.astype(F32)
        kv = jnp.sum(s * kf[..., None], axis=-2)               # S'^T k
        u = beta.astype(F32)[..., None] * (vf - kv)
        s = s + kf[..., None] * u[..., None, :]
        return scale * jnp.sum(s * qf[..., None], axis=-2), s


def _unit_lower_inverse(a):
    """``(I + A)^-1`` of a strictly lower ``A [.., C, C]``: with ``N = -A``
    nilpotent, ``(I - N)^-1 = (I + N)(I + N^2)(I + N^4)...`` until the
    power passes ``C``: a few products, no triangular solve."""
    c = a.shape[-1]
    eye = jnp.eye(c, dtype=F32)
    n, out, span = -a, eye + (-a), 2
    while span < c:
        n = jnp.matmul(n, n, precision=HI)
        out = jnp.matmul(out, eye + n, precision=HI)
        span *= 2
    return out


def delta_chunk(q, k, v, g, beta, state0, scale, live=None):
    """A lane's run of rows: q, k, g ``[N, S, H, dk]``, v ``[N, S, H,
    dv]``, beta ``[N, S, H]``, state0 ``[N, H, dk, dv]`` float32, live
    ``[N, S]`` bool or None. Rows are padded to whole sub-chunks of
    ``SUB`` with dead rows. Returns ``(o [N, S, H, dv] float32, state
    [N, H, dk, dv])``."""
    with jax.named_scope("kda_chunk"):
        n, s, h, dk = q.shape
        dv = v.shape[-1]
        g, beta = _dead_rows_keep(g.astype(F32), beta.astype(F32), live)
        pad = (-s) % SUB
        if pad:
            grow = lambda x: jnp.pad(                          # noqa: E731
                x, [(0, 0), (0, pad)] + [(0, 0)] * (x.ndim - 2))
            q, k, v, g, beta = map(grow, (q, k, v, g, beta))
        c = (s + pad) // SUB

        def subs(x):              # [N, S, H, ...] -> [C, N, H, SUB, ...]
            x = x.astype(F32).reshape((n, c, SUB) + x.shape[2:])
            return jnp.moveaxis(jnp.moveaxis(x, 1, 0), 2, 3)

        q, k, v, g, beta = map(subs, (q, k, v, g, beta))
        G = jnp.cumsum(g, axis=-2)                       # [C,N,H,SUB,dk]
        eg, eng = jnp.exp(G), jnp.exp(-G)
        kt, qt, kh = k * eg, q * eg, k * eng
        lower = jnp.tril(jnp.ones((SUB, SUB), bool), -1)
        a = jnp.where(lower, jnp.einsum("...tk,...sk->...ts", kt, kh,
                                        precision=HI), 0.0)
        tinv = _unit_lower_inverse(beta[..., None] * a)
        w = jnp.einsum("...ts,...sk->...tk", tinv, beta[..., None] * kt,
                       precision=HI)
        u0 = jnp.einsum("...ts,...sv->...tv", tinv, beta[..., None] * v,
                        precision=HI)
        p = jnp.where(lower | jnp.eye(SUB, dtype=bool),
                      jnp.einsum("...tk,...sk->...ts", qt, kh,
                                 precision=HI), 0.0)
        last = G[..., -1:, :]                            # [C,N,H,1,dk]
        kd = k * jnp.exp(last - G)

        def sub_chunk(st, x):
            qt_, w_, u0_, p_, kd_, last_ = x
            u = u0_ - jnp.einsum("...tk,...kv->...tv", w_, st,
                                 precision=HI)
            o = (jnp.einsum("...tk,...kv->...tv", qt_, st, precision=HI)
                 + jnp.einsum("...ts,...sv->...tv", p_, u, precision=HI))
            st = (jnp.exp(last_[..., 0, :])[..., None] * st
                  + jnp.einsum("...tk,...tv->...kv", kd_, u,
                               precision=HI))
            return st, o

        state, o = jax.lax.scan(sub_chunk, state0.astype(F32),
                                (qt, w, u0, p, kd, last))
        o = jnp.moveaxis(jnp.moveaxis(o, 3, 2), 0, 1)    # [N,C,SUB,H,dv]
        return scale * o.reshape(n, c * SUB, h, dv)[:, :s], state


def delta_rule(q, k, v, g, beta, state0, scale, live=None):
    """The route for the shapes: :func:`delta_step` where a lane brings
    one row, :func:`delta_chunk` otherwise. Shapes as
    :func:`delta_chunk`'s; returns ``(o [N, S, H, dv] float32, state)``."""
    if q.shape[1] == 1:
        gg, bb = _dead_rows_keep(g.astype(F32), beta.astype(F32), live)
        o, state = delta_step(q[:, 0], k[:, 0], v[:, 0], gg[:, 0],
                              bb[:, 0], state0, scale)
        return o[:, None], state
    return delta_chunk(q, k, v, g, beta, state0, scale, live)
