"""The selective scan of a Mamba-1 mixer (arXiv:2312.00752), on arrays.

One function, :func:`selective_scan`, whose contract is the recurrence

    S_t = exp(dt_t * A) * S_{t-1} + (dt_t * x_t) (x) B_t
    y_t = S_t C_t + D * x_t

over ``N`` independent lanes of ``S`` rows each, carried in from
``state0`` and out as the state after each lane's last *live* row: a row
that is not live (padding behind a lane's rows) changes no state, and its
``y`` is not to be read. The state is held ``[N, n, D]`` (``D`` the wide
axis, ``n`` the state size): the ``[D, n]`` of the paper transposed, so
that the wide axis is the minor one on the device. Everything is computed
in float32 whatever the operands' dtype; ``y`` comes back in ``x``'s.

This is the route in plain ``jax.lax`` (a sequential ``lax.scan`` over
the rows, every lane a step). A kernel, when one is written, is this
function's other route, chosen from the static shapes; what the
benchmark's ``ssm_scan_roofline`` counts is this contract's work
whatever computes it (``benchmark/harness/flops_sambay.py``).
"""
from __future__ import annotations

import jax
import jax.numpy as jnp

__all__ = ["selective_scan", "causal_conv_tail"]

F32 = jnp.float32


def selective_scan(x, dt, a, b, c, d, state0, live=None):
    """x, dt ``[N, S, D]`` (``dt`` already positive: after its
    softplus), a ``[n, D]`` (negative), b, c ``[N, S, n]``, d ``[D]``,
    state0 ``[N, n, D]``, live ``[N, S]`` bool or None (every row live).
    Returns ``(y [N, S, D] in x.dtype, state [N, n, D] float32)``."""
    with jax.named_scope("ssm_scan"):
        xf, dtf = x.astype(F32), dt.astype(F32)
        if live is not None:
            # a dead row: exp(0 * A) = 1 and 0 * x (x) B = 0: no change
            dtf = jnp.where(live[..., None], dtf, 0.0)
        af, df = a.astype(F32), d.astype(F32)
        bf, cf = b.astype(F32), c.astype(F32)

        def row(s, r):
            x_t, dt_t, b_t, c_t = r                # [N, D] x2, [N, n] x2
            s = (jnp.exp(dt_t[:, None, :] * af[None]) * s
                 + (dt_t * x_t)[:, None, :] * b_t[:, :, None])
            return s, jnp.sum(s * c_t[:, :, None], axis=1)

        rows = tuple(jnp.swapaxes(t, 0, 1) for t in (xf, dtf, bf, cf))
        n_rows = x.shape[1]
        if n_rows == 1:
            state, y = row(state0.astype(F32), tuple(t[0] for t in rows))
            y = y[None]
        else:
            state, y = jax.lax.scan(row, state0.astype(F32), rows,
                                    unroll=min(8, n_rows))
        y = jnp.swapaxes(y, 0, 1) + df * xf
        return y.astype(x.dtype), state


def causal_conv_tail(x, tail, w, bias, n_live=None):
    """The causal depthwise convolution in front of the scan, carried
    across calls by its tail. x ``[N, S, D]``, tail ``[N, K-1, D]`` (the
    lane's last ``K - 1`` inputs before these rows, nought at a
    sequence's start), w ``[K, D]`` (``w[K-1]`` meets the row itself),
    bias ``[D]``, n_live ``[N]`` int (rows of each lane that are live,
    None: all). Returns ``(conv [N, S, D] float32, new tail [N, K-1, D]
    in tail.dtype)``: the tail after a lane's last live row."""
    k = w.shape[0]
    s = x.shape[1]
    full = jnp.concatenate([tail.astype(F32), x.astype(F32)], axis=1)
    wf = w.astype(F32)
    out = bias.astype(F32) + sum(
        full[:, j:j + s] * wf[j] for j in range(k))
    if n_live is None:
        new_tail = full[:, s:]
    else:
        # rows n_live .. n_live + K - 2 of ``full`` are the last K - 1
        # inputs up to the last live row
        idx = n_live.astype(jnp.int32)[:, None] + jnp.arange(k - 1)
        new_tail = jnp.take_along_axis(full, idx[:, :, None], axis=1)
    return out, new_tail.astype(tail.dtype)
