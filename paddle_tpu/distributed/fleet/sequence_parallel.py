"""Megatron-style sequence parallelism utilities.

Reference parity: fleet/utils/sequence_parallel_utils.py — ScatterOp,
GatherOp, AllGatherOp, ReduceScatterOp, ColumnSequenceParallelLinear,
RowSequenceParallelLinear, register_sequence_parallel_allreduce_hooks
(upstream, unverified; see SURVEY.md §2.3, §5.7a).

TPU-native dual mode, like mp_layers:
- GSPMD: ScatterOp/GatherOp become sequence-dim sharding constraints over
  the 'mp' axis — the partitioner emits reduce-scatter/all-gather pairs
  around the TP block, which is exactly Megatron-SP's activation saving.
- shard_map: explicit collectives with custom vjp.
"""
from __future__ import annotations

import jax
import jax.numpy as jnp
from jax.sharding import NamedSharding, PartitionSpec as P

from ...core.autograd import apply
from ...core.tensor import Tensor
from ...nn import functional as F
from ...nn import initializer as I
from ...nn.layer import Layer
from .._axis import current_axis_env
from .mp_layers import ColumnParallelLinear, RowParallelLinear, _mp_group


def _live(group):
    return group is not None and group.axis_name in current_axis_env()


def _constrain(x, group, shard_axis, name):
    """GSPMD layout hint: shard dim `shard_axis` of x over the group's
    mesh axis (None = fully replicated). The partitioner then emits the
    matching collective around adjacent TP matmuls."""
    spec = [None] * x.ndim
    if shard_axis is not None:
        spec[shard_axis] = group.axis_name

    def f(a):
        try:
            return jax.lax.with_sharding_constraint(
                a, NamedSharding(_current_mesh(), P(*spec)))
        except Exception:
            return a
    return apply(f, x, name=name)


def scatter(x, group=None, axis=0):
    """Sequence-dim scatter: keep this rank's sequence chunk.
    fwd: split; bwd: all-gather."""
    group = group if group is not None else _mp_group()
    if _live(group):
        from .mp_ops import _c_split
        return _c_split(x, group, axis=axis)
    if group is not None:
        return _constrain(x, group, axis, "sp_scatter")
    return x


def all_gather(x, group=None, axis=0):
    """fwd: gather sequence; bwd: reduce-scatter (grad splits back)."""
    group = group if group is not None else _mp_group()
    if _live(group):
        from .mp_ops import _c_concat
        return _c_concat(x, group, axis=axis)
    if group is not None:
        return _constrain(x, group, None, "sp_allgather")
    return x


ScatterOp = scatter
GatherOp = all_gather
AllGatherOp = all_gather


def reduce_scatter(x, group=None, axis=0):
    group = group if group is not None else _mp_group()
    if _live(group):
        ax = group.axis_name

        @jax.custom_vjp
        def f(a):
            return jax.lax.psum_scatter(a, ax, scatter_dimension=axis,
                                        tiled=True)

        def fwd(a):
            return f(a), a[:0]  # no data: carries the input's type

        def bwd(like, g):
            full = jax.lax.all_gather(g, ax, axis=axis, tiled=True)
            if ax in jax.typeof(g).vma and ax not in jax.typeof(like).vma:
                # check_vma typed the input unvarying over `ax` (a
                # replicated in_spec), so its cotangent must be too. Every
                # rank holds the same gathered value: pmax says so exactly
                full = jax.lax.pmax(full, ax)
            return (full,)

        f.defvjp(fwd, bwd)
        return apply(f, x, name="sp_reduce_scatter")
    if group is not None:
        # GSPMD: the reduce is the partitioner's job; constrain the output
        # to sequence-sharded layout so the activation actually lives
        # split (Megatron-SP's memory saving) instead of replicated.
        return _constrain(x, group, axis, "sp_reduce_scatter")
    return x


ReduceScatterOp = reduce_scatter


def _current_mesh():
    from .topology import get_hybrid_communicate_group
    hcg = get_hybrid_communicate_group()
    if hcg is None:
        raise RuntimeError("no hybrid mesh")
    return hcg.mesh


class ColumnSequenceParallelLinear(ColumnParallelLinear):
    """Column-parallel linear whose input is sequence-sharded: gathers the
    sequence before the matmul (activation lives sharded between blocks)."""

    def __init__(self, in_features, out_features, weight_attr=None,
                 has_bias=True, gather_output=False, mp_group=None,
                 name=None):
        super().__init__(in_features, out_features, weight_attr, has_bias,
                         gather_output, mp_group=mp_group)

    def forward(self, x):
        x = all_gather(x, self.group, axis=0 if x.ndim == 3 else 0)
        from .mp_ops import _identity
        x = _identity(x, self.group)
        out = F.linear(x, self.weight, self.bias)
        if self.gather_output:
            from .mp_ops import _c_concat
            out = _c_concat(out, self.group, axis=-1)
        return out


class RowSequenceParallelLinear(RowParallelLinear):
    """Row-parallel linear that reduce-scatters its output back to
    sequence-sharded layout (saving mp× activation memory)."""

    def __init__(self, in_features, out_features, weight_attr=None,
                 has_bias=True, input_is_parallel=True, mp_group=None,
                 name=None):
        super().__init__(in_features, out_features, weight_attr, has_bias,
                         input_is_parallel, mp_group=mp_group)

    def forward(self, x):
        if not self.input_is_parallel:
            from .mp_ops import _c_split
            x = _c_split(x, self.group, axis=-1)
        out = F.linear(x, self.weight, None)
        out = reduce_scatter(out, self.group, axis=0)
        if self.bias is not None:
            out = out + self.bias
        return out


def mark_as_sequence_parallel_parameter(param):
    param.sequence_parallel = True


def register_sequence_parallel_allreduce_hooks(model, accumulation_steps=1,
                                               fuse_sequence_parallel=False):
    """Reference: LayerNorm params inside an SP region produce per-rank
    partial grads that must be summed over mp. Under GSPMD this reduction
    is automatic; under shard_map the SPMD grad is already psum'ed by the
    engine. Kept as an API-parity registration that tags the params."""
    for p in model.parameters():
        if getattr(p, "sequence_parallel", False):
            p.needs_sp_allreduce = True
