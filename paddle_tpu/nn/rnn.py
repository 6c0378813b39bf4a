"""Recurrent layers: SimpleRNN/LSTM/GRU (+ cells).

Reference parity: paddle.nn.{SimpleRNN,LSTM,GRU,RNNCellBase,...}
(upstream python/paddle/nn/layer/rnn.py — unverified, see SURVEY.md §2.2).

TPU-native: the time loop is `jax.lax.scan` — one compiled loop, weights
resident in VMEM across steps — rather than a Python loop of kernel
launches. Multi-layer and bidirectional variants compose the scan.
"""
from __future__ import annotations

import functools
import math

import jax
import jax.numpy as jnp

from ..core.autograd import apply, mark_stable
from . import functional as F
from . import initializer as I
from .layer import Layer, LayerList


# The pure functions handed to apply() are built ONCE per configuration
# and shared by every layer of that configuration: apply()'s micro-jit
# (and lax.scan's compiled loop) key on the function's identity, so a
# lambda made in forward() would be traced — and, around a scan, compiled
# by XLA — again on every call.

@functools.lru_cache(maxsize=None)
def _cell_fn(mode, hidden, activation="tanh"):
    """One step `(x, *state, wi, wh, bi, bh) -> new state` of a cell."""
    if mode == "LSTM":
        def f(x, h, c, wi, wh, bi, bh):
            return LSTMCell._step(x, h, c, wi, wh, bi, bh, hidden)
    elif mode == "GRU":
        def f(x, h, wi, wh, bi, bh):
            return GRUCell._step(x, h, wi, wh, bi, bh, hidden)
    else:
        act = jnp.tanh if activation == "tanh" else jax.nn.relu

        def f(x, h, wi, wh, bi, bh):
            return act(x @ wi.T + bi + h @ wh.T + bh)
    return mark_stable(f)


@functools.lru_cache(maxsize=None)
def _scan_fn(mode, hidden, activation, reverse):
    """`(wi, wh, bi, bh, x[B,T,C]) -> (y[B,T,H], h, c)`: the cell's step
    under lax.scan over T, from a zero state."""
    cell = _cell_fn(mode, hidden, activation)
    is_lstm = mode == "LSTM"

    def f(wi, wh, bi, bh, xa):
        h0 = jnp.zeros((xa.shape[0], hidden), xa.dtype)

        def step(carry, xt):
            if is_lstm:
                h, c = cell(xt, carry[0], carry[1], wi, wh, bi, bh)
                return (h, c), h
            h = cell(xt, carry, wi, wh, bi, bh)
            return h, h

        carry, ys = jax.lax.scan(step, (h0, h0) if is_lstm else h0,
                                 jnp.moveaxis(xa, 1, 0),  # [T, B, C]
                                 reverse=reverse)
        final_h, final_c = carry if is_lstm else (carry, carry)
        return jnp.moveaxis(ys, 0, 1), final_h, final_c
    return mark_stable(f)


class RNNCellBase(Layer):
    def get_initial_states(self, batch_ref, shape=None, dtype=None,
                           init_value=0.0, batch_dim_idx=0):
        import paddle_tpu as P
        b = batch_ref.shape[batch_dim_idx]
        return P.full([b, self.hidden_size], init_value)


class SimpleRNNCell(RNNCellBase):
    def __init__(self, input_size, hidden_size, activation="tanh",
                 weight_ih_attr=None, weight_hh_attr=None, bias_ih_attr=None,
                 bias_hh_attr=None, name=None):
        super().__init__()
        self.input_size = input_size
        self.hidden_size = hidden_size
        self.activation = activation
        std = 1.0 / math.sqrt(hidden_size)
        init = I.Uniform(-std, std)
        self.weight_ih = self.create_parameter((hidden_size, input_size),
                                               default_initializer=init)
        self.weight_hh = self.create_parameter((hidden_size, hidden_size),
                                               default_initializer=init)
        self.bias_ih = self.create_parameter((hidden_size,), is_bias=True,
                                             default_initializer=init)
        self.bias_hh = self.create_parameter((hidden_size,), is_bias=True,
                                             default_initializer=init)

    def forward(self, inputs, states=None):
        h = states if states is not None else \
            self.get_initial_states(inputs)
        out = apply(
            _cell_fn("RNN", self.hidden_size, self.activation),
            inputs, h, self.weight_ih, self.weight_hh, self.bias_ih,
            self.bias_hh, name="rnn_cell")
        return out, out


class LSTMCell(RNNCellBase):
    def __init__(self, input_size, hidden_size, weight_ih_attr=None,
                 weight_hh_attr=None, bias_ih_attr=None, bias_hh_attr=None,
                 name=None):
        super().__init__()
        self.input_size = input_size
        self.hidden_size = hidden_size
        std = 1.0 / math.sqrt(hidden_size)
        init = I.Uniform(-std, std)
        self.weight_ih = self.create_parameter((4 * hidden_size, input_size),
                                               default_initializer=init)
        self.weight_hh = self.create_parameter(
            (4 * hidden_size, hidden_size), default_initializer=init)
        self.bias_ih = self.create_parameter((4 * hidden_size,),
                                             is_bias=True,
                                             default_initializer=init)
        self.bias_hh = self.create_parameter((4 * hidden_size,),
                                             is_bias=True,
                                             default_initializer=init)

    @staticmethod
    def _step(x, h, c, wi, wh, bi, bh, hidden):
        gates = x @ wi.T + bi + h @ wh.T + bh
        i = jax.nn.sigmoid(gates[..., 0:hidden])
        f = jax.nn.sigmoid(gates[..., hidden:2 * hidden])
        g = jnp.tanh(gates[..., 2 * hidden:3 * hidden])
        o = jax.nn.sigmoid(gates[..., 3 * hidden:4 * hidden])
        c_new = f * c + i * g
        h_new = o * jnp.tanh(c_new)
        return h_new, c_new

    def forward(self, inputs, states=None):
        if states is None:
            h = self.get_initial_states(inputs)
            c = self.get_initial_states(inputs)
        else:
            h, c = states
        h_new, c_new = apply(
            _cell_fn("LSTM", self.hidden_size), inputs, h, c,
            self.weight_ih, self.weight_hh, self.bias_ih,
            self.bias_hh, name="lstm_cell")
        return h_new, (h_new, c_new)


class GRUCell(RNNCellBase):
    def __init__(self, input_size, hidden_size, weight_ih_attr=None,
                 weight_hh_attr=None, bias_ih_attr=None, bias_hh_attr=None,
                 name=None):
        super().__init__()
        self.input_size = input_size
        self.hidden_size = hidden_size
        std = 1.0 / math.sqrt(hidden_size)
        init = I.Uniform(-std, std)
        self.weight_ih = self.create_parameter((3 * hidden_size, input_size),
                                               default_initializer=init)
        self.weight_hh = self.create_parameter(
            (3 * hidden_size, hidden_size), default_initializer=init)
        self.bias_ih = self.create_parameter((3 * hidden_size,),
                                             is_bias=True,
                                             default_initializer=init)
        self.bias_hh = self.create_parameter((3 * hidden_size,),
                                             is_bias=True,
                                             default_initializer=init)

    @staticmethod
    def _step(x, h, wi, wh, bi, bh, hidden):
        gi = x @ wi.T + bi
        gh = h @ wh.T + bh
        r = jax.nn.sigmoid(gi[..., :hidden] + gh[..., :hidden])
        z = jax.nn.sigmoid(gi[..., hidden:2 * hidden] +
                           gh[..., hidden:2 * hidden])
        n = jnp.tanh(gi[..., 2 * hidden:] + r * gh[..., 2 * hidden:])
        return (1 - z) * n + z * h

    def forward(self, inputs, states=None):
        h = states if states is not None else \
            self.get_initial_states(inputs)
        h_new = apply(
            _cell_fn("GRU", self.hidden_size), inputs, h,
            self.weight_ih, self.weight_hh, self.bias_ih,
            self.bias_hh, name="gru_cell")
        return h_new, h_new


class _RNNBase(Layer):
    """Multi-layer (optionally bidirectional) scan over a cell family."""

    MODE = ""

    def __init__(self, input_size, hidden_size, num_layers=1,
                 direction="forward", time_major=False, dropout=0.0,
                 **cell_kwargs):
        super().__init__()
        self.hidden_size = hidden_size
        self.num_layers = num_layers
        self.time_major = time_major
        self.bidirectional = direction in ("bidirect", "bidirectional")
        self.dropout = dropout
        ndir = 2 if self.bidirectional else 1
        cells = []
        for layer in range(num_layers):
            for _ in range(ndir):
                in_size = input_size if layer == 0 else hidden_size * ndir
                cells.append(self._make_cell(in_size, hidden_size,
                                             **cell_kwargs))
        self.cells = LayerList(cells)

    def _make_cell(self, i, h, **kw):
        raise NotImplementedError

    def _scan_direction(self, cell, x, reverse):
        """x: [B, T, C] → outputs [B, T, H] via lax.scan over T."""
        fn = _scan_fn(self.MODE, self.hidden_size,
                      getattr(cell, "activation", "tanh"), reverse)
        outs = apply(fn, cell.weight_ih, cell.weight_hh, cell.bias_ih,
                     cell.bias_hh, x, name=f"{self.MODE}_scan")
        return outs  # (y, h, c)

    def forward(self, inputs, initial_states=None, sequence_length=None):
        import paddle_tpu as P
        x = inputs
        if self.time_major:
            x = x.swapaxes(0, 1)
        ndir = 2 if self.bidirectional else 1
        hs, cs = [], []
        for layer in range(self.num_layers):
            outs = []
            for d in range(ndir):
                cell = self.cells[layer * ndir + d]
                y, h, c = self._scan_direction(cell, x, reverse=(d == 1))
                outs.append(y)
                hs.append(h)
                cs.append(c)
            x = outs[0] if ndir == 1 else P.concat(outs, axis=-1)
            if self.dropout and layer < self.num_layers - 1:
                x = F.dropout(x, self.dropout, training=self.training)
        out = x.swapaxes(0, 1) if self.time_major else x
        h_stack = P.stack(hs, axis=0)
        if self.MODE == "LSTM":
            return out, (h_stack, P.stack(cs, axis=0))
        return out, h_stack


class SimpleRNN(_RNNBase):
    MODE = "RNN"

    def _make_cell(self, i, h, activation="tanh", **kw):
        return SimpleRNNCell(i, h, activation=activation)


class LSTM(_RNNBase):
    MODE = "LSTM"

    def _make_cell(self, i, h, **kw):
        return LSTMCell(i, h)


class GRU(_RNNBase):
    MODE = "GRU"

    def _make_cell(self, i, h, **kw):
        return GRUCell(i, h)


class RNN(Layer):
    """Wrap a cell into a scan runner (reference: paddle.nn.RNN)."""

    def __init__(self, cell, is_reverse=False, time_major=False):
        super().__init__()
        self.cell = cell
        self.is_reverse = is_reverse
        self.time_major = time_major

    def forward(self, inputs, initial_states=None, sequence_length=None):
        x = inputs if not self.time_major else inputs.swapaxes(0, 1)
        outs = []
        states = initial_states
        T = x.shape[1]
        order = range(T - 1, -1, -1) if self.is_reverse else range(T)
        for t in order:
            out, states = self.cell(x[:, t], states)
            outs.append(out)
        if self.is_reverse:
            outs = outs[::-1]
        import paddle_tpu as P
        y = P.stack(outs, axis=1)
        if self.time_major:
            y = y.swapaxes(0, 1)
        return y, states


class BiRNN(Layer):
    """Reference parity: paddle.nn.BiRNN — run a forward cell and a
    backward cell over the sequence and concatenate the feature dims."""

    def __init__(self, cell_fw, cell_bw, time_major=False):
        super().__init__()
        # single registration per cell (via the RNN wrappers) — the
        # direct attributes are plain properties below
        self.rnn_fw = RNN(cell_fw, is_reverse=False, time_major=time_major)
        self.rnn_bw = RNN(cell_bw, is_reverse=True, time_major=time_major)
        self.time_major = time_major

    @property
    def cell_fw(self):
        return self.rnn_fw.cell

    @property
    def cell_bw(self):
        return self.rnn_bw.cell

    def forward(self, inputs, initial_states=None, sequence_length=None):
        if sequence_length is not None:
            # the reverse pass would start inside the padding; honest
            # failure beats silently-wrong backward states
            raise NotImplementedError(
                "BiRNN with sequence_length (padded batches) is not "
                "supported; trim/pack sequences instead")
        if initial_states is None:
            st_fw = st_bw = None
        else:
            st_fw, st_bw = initial_states
        import paddle_tpu as P
        y_fw, s_fw = self.rnn_fw(inputs, st_fw)
        y_bw, s_bw = self.rnn_bw(inputs, st_bw)
        return P.concat([y_fw, y_bw], axis=-1), (s_fw, s_bw)
