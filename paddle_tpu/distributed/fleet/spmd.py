"""The SPMD hybrid-parallel training engine.

Reference parity: the *capabilities* of Fleet's wrappers — DataParallel
(bucketed allreduce), DygraphShardingOptimizer (ZeRO-1),
GroupShardedStage2/3 (ZeRO-2/3), tensor parallel, sequence parallel —
upstream fleet/meta_parallel/* (unverified, see SURVEY.md §2.3).

TPU-native design (SURVEY.md §2.4): instead of per-rank Python processes
issuing NCCL calls, ONE compiled XLA program runs across the mesh and the
GSPMD partitioner inserts the collectives:

- **DP**: batch sharded over the `dp` axis → XLA all-reduces grads (the
  EagerReducer's bucketed overlap == XLA's collective scheduling).
- **ZeRO-1** (sharding stage 1): optimizer states sharded over `sharding`;
  param update becomes reduce-scatter(grad)+sharded update+all-gather —
  exactly weight-update sharding.
- **ZeRO-2**: grads constrained to `sharding` → reduce-scatter replaces
  the grad all-reduce.
- **ZeRO-3**: params themselves sharded over `sharding`; XLA all-gathers
  on first use per step and re-gathers in backward under the remat policy
  — the pre-forward/pre-backward gather+release of GroupShardedStage3.
- **TP**: mpu layers carry `dist_spec` on weights (e.g. (None,'mp')); the
  partitioner turns the matmuls into sharded matmuls + psum.
- **SP**: sequence-dim sharding constraints around attention blocks.

The engine compiles forward+backward+fused-optimizer into one XLA
executable (see also hapi._JitStepper — this is its mesh-aware superset).
"""
from __future__ import annotations

import numpy as np

import jax
import jax.numpy as jnp
from jax.sharding import Mesh, NamedSharding, PartitionSpec as P

from ...core import random as _random
from ...core.tensor import Tensor
from ...nn.layer import Layer
from .._axis import mesh_env


def _add_sharding(spec, shape, sharding_degree, axis="sharding"):
    """Compose a ZeRO-style `axis` onto a (possibly TP-sharded) spec:
    take the largest FREE dim divisible by the degree. Returns None if
    no free dim qualifies (spec unchanged). ZeRO composes WITH tensor
    parallelism — each TP shard is further sharded across the sharding
    group (the reference's sharding×mp hybrid; same rule as the
    pipeline's `_pp_param_spec`). The pipeline reuses this with
    axis='pp' to store embedding/head params sharded over the pp group."""
    tail = list(spec) + [None] * (len(shape) - len(spec))
    if axis in tail:
        return None
    for d in np.argsort([-s for s in shape]):
        if tail[d] is None and shape[d] % sharding_degree == 0 \
                and shape[d] >= sharding_degree:
            tail[d] = axis
            return P(*tail)
    return None


def _reshard_identity(a):
    return a


# bounded: elastic re-forms build fresh meshes whose old shardings can
# never hit again — FIFO-evict so retired meshes/executables are not
# pinned for the process lifetime
_reshard_jits: dict = {}
_RESHARD_CACHE_MAX = 8


def device_put_global(x, sharding):
    """`jax.device_put` that also works when `sharding` spans
    NON-addressable devices — the multi-controller regime (one process
    per host, one global mesh; SURVEY §2.4). Contract: every process
    passes the same host value (replicated-input SPMD); each contributes
    its addressable shards via make_array_from_process_local_data.
    Single-controller (fully addressable) takes the plain device_put
    path unchanged."""
    if getattr(sharding, "is_fully_addressable", True):
        return jax.device_put(x, sharding)
    if isinstance(x, jax.Array):
        if x.sharding == sharding:
            return x
        if not x.is_fully_addressable:
            # global → global reshard: route through a jitted identity
            # (device_put cannot target non-addressable shardings);
            # cached per target sharding so repeat reshards hit the
            # jit cache instead of re-tracing
            fn = _reshard_jits.get(sharding)
            if fn is None:
                while len(_reshard_jits) >= _RESHARD_CACHE_MAX:
                    _reshard_jits.pop(next(iter(_reshard_jits)))
                fn = jax.jit(_reshard_identity, out_shardings=sharding)
                _reshard_jits[sharding] = fn
            return fn(x)
        x = np.asarray(x)
    else:
        x = np.asarray(x)
    return jax.make_array_from_process_local_data(sharding, x, x.shape)


def param_spec(param, shape, stage, sharding_degree, mp_degree) -> P:
    """Decide the PartitionSpec for a parameter.

    Explicit mpu `dist_spec` (TP) dims are kept; ZeRO-3 then shards the
    largest free divisible dim on top (TP×ZeRO-3 composition — without
    it every TP-sharded transformer weight would be replicated across
    the whole sharding group, forfeiting ZeRO's memory win at scale).
    """
    explicit = getattr(param, "dist_spec", None)
    spec = P(*explicit) if explicit is not None else P()
    if stage >= 3 and sharding_degree > 1 and len(shape) >= 1:
        composed = _add_sharding(spec, shape, sharding_degree)
        if composed is not None:
            return composed
    return spec


def state_spec(pspec: P, shape, stage, sharding_degree) -> P:
    """Optimizer-state sharding: stage>=1 shards states like ZeRO-1,
    composing with (not deferring to) the param's TP dims."""
    if stage >= 1 and sharding_degree > 1 and len(shape) >= 1 and \
            len(pspec) <= len(shape):
        composed = _add_sharding(pspec, shape, sharding_degree)
        if composed is not None:
            return composed
    return pspec


def batch_spec(ndim: int, dp_axes=("dp", "sharding")) -> P:
    """Data is sharded over dp×sharding (reference: sharding group is also
    a data-parallel group at the batch level)."""
    if ndim == 0:
        return P()
    return P(dp_axes)


class SPMDTrainer:
    """Compiled hybrid-parallel train step over a Mesh."""

    def __init__(self, layer: Layer, optimizer, loss_fn, mesh: Mesh,
                 strategy=None, sharding_stage=None, amp_level=None):
        self.layer = layer
        self.optimizer = optimizer
        self.loss_fn = loss_fn
        self.mesh = mesh
        st = strategy
        if sharding_stage is not None:
            self.stage = sharding_stage
        elif st is not None and st.sharding:
            self.stage = int(st.sharding_configs["stage"])
        elif st is not None and \
                st.hybrid_configs.get("sharding_degree", 1) > 1:
            self.stage = 1  # sharding axis without explicit config = ZeRO-1
        else:
            self.stage = 0
        # AMP: explicit arg wins; else the strategy's amp switch (so
        # fleet.distributed_model users get mixed precision too)
        if amp_level is None and st is not None and \
                getattr(st, "amp", False):
            amp_level = st.amp_configs.get("level", "O1")
        self.amp_level = amp_level
        # multi-controller: the mesh spans devices owned by other
        # processes (v5p-pod regime); arguments need explicit global
        # placement before jit
        self._multi_controller = any(
            d.process_index != jax.process_index()
            for d in mesh.devices.flat)
        ax = dict(zip(mesh.axis_names, mesh.devices.shape))
        self.sharding_degree = ax.get("sharding", 1)
        self.mp_degree = ax.get("mp", 1)
        self.dp_degree = ax.get("dp", 1)
        # context parallelism (sep axis): the step runs sequence-sharded
        # inside shard_map over 'sep'; see _build's sep branch
        self.sep_degree = ax.get("sep", 1)
        # gradient merge (reference: fleet gradient_merge dist pass):
        # accumulate k micro-steps' grads in f32 accumulators, apply the
        # optimizer on the k-th — two cached program flavors
        gm = bool(getattr(st, "gradient_merge", False)) if st else False
        self.k_steps = int(st.gradient_merge_configs.k_steps) if gm else 1
        self.gm_avg = bool(st.gradient_merge_configs.get("avg", True)) \
            if gm else True
        self._gacc = None
        self._micro = 0
        self._jits = {}
        self._sig = None
        self._placed = False

        self._train_named = [(n, p) for n, p in layer.named_parameters()
                             if not p.stop_gradient]
        self._frozen_named = [(n, p) for n, p in layer.named_parameters()
                              if p.stop_gradient]
        self._buf_named = list(layer.named_buffers())
        self._pspecs = [param_spec(p, tuple(p._data.shape), self.stage,
                                   self.sharding_degree, self.mp_degree)
                        for _, p in self._train_named]
        self._fspecs = [param_spec(p, tuple(p._data.shape), self.stage,
                                   self.sharding_degree, self.mp_degree)
                        for _, p in self._frozen_named]

    # -- placement ----------------------------------------------------------
    def shard_parameters(self):
        """Physically place params/buffers on the mesh per their specs.
        ZeRO-3's 'parameters are sharded at rest' + TP weight layout."""
        for (n, p), spec in zip(self._train_named, self._pspecs):
            s = NamedSharding(self.mesh, spec)
            p._data = device_put_global(p._data, s)
        for (n, p), spec in zip(self._frozen_named, self._fspecs):
            p._data = device_put_global(p._data,
                                        NamedSharding(self.mesh, spec))
        for n, b in self._buf_named:
            b._data = device_put_global(b._data,
                                        NamedSharding(self.mesh, P()))
        self._placed = True

    def _state_sharding(self, pspec, arr_shape):
        return NamedSharding(self.mesh, state_spec(
            pspec, arr_shape, max(self.stage, 1 if self.stage else 0),
            self.sharding_degree))

    # -- compiled step -------------------------------------------------------
    def _build(self, n_inputs, n_labels, states_tree_shapes,
               do_update=True):
        layer, opt, loss_fn = self.layer, self.optimizer, self.loss_fn
        train_named = self._train_named
        frozen_named = self._frozen_named
        buf_named = self._buf_named
        stage = self.stage
        sharding_degree = self.sharding_degree
        mesh = self.mesh
        k = self.k_steps
        gm_avg = self.gm_avg

        def pure(key, params, frozen, buffers, states, gacc, lr, step_i,
                 *batch):
            inputs = [Tensor(a) for a in batch[:n_inputs]]
            labels = [Tensor(a) for a in batch[n_inputs:]]
            all_t = ([t for _, t in train_named] +
                     [t for _, t in frozen_named] +
                     [t for _, t in buf_named])
            saved = [(t, t._data) for t in all_t]
            _random.push_trace_key(key)
            try:
                def loss_of(params_):
                    for (n, t), arr in zip(train_named, params_):
                        t._data = arr
                    for (n, t), arr in zip(frozen_named, frozen):
                        t._data = arr
                    for (n, t), arr in zip(buf_named, buffers):
                        t._data = arr
                    if self.amp_level:  # graftlint: disable=jit-constant-capture (static scalar config selecting the traced branch, not arrays; weights are jit arguments)
                        # AMP inside the trace — the compiled program IS
                        # the mixed-precision program (same contract as
                        # the single-device _JitStepper)
                        from ... import amp as amp_mod
                        with amp_mod.auto_cast(level=self.amp_level):
                            return _fwd_loss()
                    return _fwd_loss()

                def _fwd_loss():
                    outs = layer(*inputs)
                    outs = outs if isinstance(outs, (list, tuple)) else \
                        [outs]
                    loss = loss_fn(*(list(outs) + labels))
                    total = loss if isinstance(loss, Tensor) else loss[0]
                    new_buf = [t._data for _, t in buf_named]
                    return total._data.astype(jnp.float32), new_buf

                if self.sep_degree > 1:  # graftlint: disable=jit-constant-capture (static int config, not arrays)
                    loss_of = self._build_sep_loss(  # graftlint: disable=jit-constant-capture (builds the SP loss closure; its weights still arrive as params_ arguments)
                        key, frozen, buffers, batch, n_inputs)

                (loss_v, new_buf), grads = jax.value_and_grad(
                    loss_of, has_aux=True)(list(params))

                if stage >= 2 and sharding_degree > 1:
                    # force reduce-scatter: grads live sharded like states
                    grads = [
                        jax.lax.with_sharding_constraint(
                            g, NamedSharding(mesh, state_spec(
                                ps, g.shape, stage, sharding_degree)))
                        for g, ps in zip(grads, self._pspecs)]  # graftlint: disable=jit-constant-capture (PartitionSpecs are static sharding metadata, not arrays)

                if k > 1:
                    # merge this micro-step into the f32 accumulators
                    merged = [ga + g.astype(ga.dtype)
                              for ga, g in zip(gacc, grads)]
                    if not do_update:
                        # params/states untouched — return only the
                        # accumulators (no pointless whole-model copy)
                        return loss_v, new_buf, merged
                    grads = [(m / k if gm_avg else m).astype(g.dtype)
                             for m, g in zip(merged, grads)]
                    new_gacc = [jnp.zeros_like(m) for m in merged]
                else:
                    new_gacc = list(gacc)

                if opt._grad_clip is not None:
                    pg = [(t, Tensor(g)) for (n, t), g in
                          zip(train_named, grads)]
                    pg = opt._grad_clip(pg)
                    grads = [g._data for _, g in pg]

                new_params, new_states = opt._fused_apply(
                    list(params), grads, list(states), lr, step_i,
                    use_pallas=False)
                return loss_v, new_buf, new_params, new_states, new_gacc
            finally:
                _random.pop_trace_key()
                for t, arr in saved:
                    t._data = arr

        # shardings
        ns = lambda spec: NamedSharding(mesh, spec)
        param_sh = [ns(s) for s in self._pspecs]
        frozen_sh = [ns(s) for s in self._fspecs]
        buf_sh = [ns(P()) for _ in buf_named]
        state_sh = [
            jax.tree.map(
                lambda a, sp=sp: self._state_sharding(sp, a.shape), st)
            for st, sp in zip(states_tree_shapes[0], self._pspecs)]
        if self.sep_degree > 1:
            # [B, S] args: batch dim over data axes, seq dim over 'sep'
            batch_sh = [ns(P(("dp", "sharding"), "sep")) if nd == 2
                        else ns(batch_spec(nd))
                        for nd in states_tree_shapes[1]]
        else:
            batch_sh = [ns(batch_spec(nd)) for nd in states_tree_shapes[1]]

        gacc_sh = [self._state_sharding(sp, tuple(p._data.shape))
                   for (_, p), sp in zip(self._train_named, self._pspecs)] \
            if self.k_steps > 1 else []
        in_shardings = (ns(P()), param_sh, frozen_sh, buf_sh, state_sh,
                        gacc_sh, ns(P()), ns(P()), *batch_sh)
        if do_update:
            out_shardings = (ns(P()), buf_sh, param_sh, state_sh, gacc_sh)
        else:
            out_shardings = (ns(P()), buf_sh, gacc_sh)

        return jax.jit(pure, in_shardings=in_shardings,
                       out_shardings=out_shardings)

    def _build_sep_loss(self, key, frozen, buffers, batch, n_inputs):
        """Context-parallel loss (sep axis; SURVEY §5.7): the forward
        runs sequence-sharded inside shard_map MANUAL over 'sep' only —
        dp/sharding/mp stay GSPMD auto axes, same partial-manual design
        as the pipeline runtime. The model's attention layers route
        through ring/ulysses flash attention (cfg.context_parallel) and
        rope positions carry the global block offset. Labels are the
        GLOBALLY pre-shifted next-token ids (train_batch shifts before
        sharding), so the psum'd per-token CE sum/count equals the dense
        shifted CE EXACTLY — shard-boundary pairs included (a per-shard
        shifted loss would silently drop sep-1 of them)."""
        import jax
        from jax import shard_map

        from .._axis import axis_env

        if self.amp_level:
            raise NotImplementedError(
                "sep (context-parallel) training does not compose with "
                "amp auto_cast yet; run bf16-native via model.to()")
        cfg = getattr(self.layer, "cfg", None)
        if cfg is not None and getattr(cfg, "fuse_linear_cross_entropy",
                                       False):
            raise NotImplementedError(
                "sep training computes its own token CE; disable "
                "fuse_linear_cross_entropy")
        if n_inputs != 1 or len(batch) != 2:
            raise NotImplementedError(
                "sep (context-parallel) training expects exactly "
                "(input_ids, labels) — a causal-LM step")
        mesh = self.mesh
        layer = self.layer
        train_named = self._train_named
        frozen_named = self._frozen_named
        buf_named = self._buf_named

        def local_body(key_, params_, frozen_, buffers_, ids_l, lab_l):
            for (n, t), arr in zip(train_named, params_):
                t._data = arr
            for (n, t), arr in zip(frozen_named, frozen_):
                t._data = arr
            for (n, t), arr in zip(buf_named, buffers_):
                t._data = arr
            _random.push_trace_key(jax.random.fold_in(
                key_, jax.lax.axis_index("sep")))
            try:
                outs = layer(Tensor(ids_l))
                logits = (outs[0] if isinstance(outs, (list, tuple))
                          else outs)._data
                lp = jax.nn.log_softmax(logits.astype(jnp.float32),
                                        axis=-1)
                valid = lab_l >= 0
                lab_c = jnp.where(valid, lab_l, 0).astype(jnp.int32)
                tok = jnp.take_along_axis(lp, lab_c[..., None],
                                          axis=-1)[..., 0]
                s = jax.lax.psum(-jnp.sum(jnp.where(valid, tok, 0.0)),
                                 "sep")
                c = jax.lax.psum(jnp.sum(valid.astype(jnp.float32)),
                                 "sep")
                new_buf = [t._data for _, t in buf_named]
                return s / jnp.maximum(c, 1.0), new_buf
            finally:
                _random.pop_trace_key()

        smapped = shard_map(
            local_body, mesh=mesh,
            in_specs=(P(), P(), P(), P(), P(None, "sep"),
                      P(None, "sep")),
            out_specs=(P(), P()),
            axis_names=frozenset({"sep"}), check_vma=False)

        def loss_of(params_):
            with axis_env("sep"):
                return smapped(key, list(params_), list(frozen),
                               list(buffers), batch[0], batch[1])

        return loss_of

    def train_batch(self, inputs, labels):
        if not self._placed:
            self.shard_parameters()
        opt = self.optimizer
        inputs = [t if isinstance(t, Tensor) else Tensor(jnp.asarray(t))
                  for t in inputs]
        labels = [t if isinstance(t, Tensor) else Tensor(jnp.asarray(t))
                  for t in labels]
        if self.sep_degree > 1:
            # causal-LM labels are shifted GLOBALLY before sequence
            # sharding (see _build_sep_loss); ignore-pad the final slot.
            # The sep branch computes the standard shifted token CE
            # itself, so it REFUSES inputs it would silently reinterpret
            # (prompt-masked labels, custom criteria) instead of
            # training on a different objective than sep_degree=1 would.
            ids = inputs[0]._data
            if ids.ndim != 2 or ids.shape[1] % self.sep_degree:
                raise ValueError(
                    f"sep training needs [B, S] ids with S divisible by "
                    f"sep degree {self.sep_degree} (got {ids.shape})")
            if len(labels) != 1 or (labels[0]._data is not ids and not (
                    labels[0]._data.shape == ids.shape
                    and bool(jnp.all(labels[0]._data == ids)))):
                raise NotImplementedError(
                    "sep (context-parallel) training computes the "
                    "standard shifted causal-LM CE from input_ids; "
                    "pass labels == input_ids (prompt-masked or custom "
                    "labels are not supported yet)")
            from ...models.llama import LlamaPretrainingCriterion
            if self.loss_fn is not None and not isinstance(
                    self.loss_fn, LlamaPretrainingCriterion) and not \
                    getattr(self.loss_fn, "is_causal_lm_criterion",
                            False):
                raise NotImplementedError(
                    f"sep training replaces the criterion with the "
                    f"shifted token CE; {type(self.loss_fn).__name__} "
                    "would be silently ignored (mark it with "
                    "is_causal_lm_criterion=True if that is the same "
                    "objective)")
            labels = [Tensor(jnp.concatenate(
                [ids[:, 1:],
                 jnp.full((ids.shape[0], 1), -100, ids.dtype)], axis=1))]
        states = [opt._get_state(p) for _, p in self._train_named]
        batch_ndims = [t._data.ndim for t in inputs + labels]
        self._micro += 1
        do_update = self.k_steps == 1 or self._micro % self.k_steps == 0
        sig = (len(inputs), len(labels),
               tuple(tuple(t.shape) for t in inputs + labels),
               tuple(tuple(sorted(s.keys())) for s in states))
        if self._sig != sig:
            self._jits = {}
            self._sig = sig
        fn = self._jits.get(do_update)
        if fn is None:
            fn = self._build(len(inputs), len(labels),
                             (states, batch_ndims), do_update=do_update)
            self._jits[do_update] = fn
        if self.k_steps > 1 and self._gacc is None:
            self._gacc = [
                device_put_global(
                    jnp.zeros(p._data.shape, jnp.float32),
                    self._state_sharding(sp, tuple(p._data.shape)))
                for (_, p), sp in zip(self._train_named, self._pspecs)]
        gacc = self._gacc if self.k_steps > 1 else []
        if do_update:
            opt._step_count += 1
        key = _random.next_key()
        lr = jnp.asarray(opt.get_lr(), jnp.float32)
        step_i = jnp.asarray(opt._step_count, jnp.int32)
        if self._multi_controller:
            # every argument must be a GLOBAL array (jit cannot
            # auto-place process-local arrays onto non-addressable
            # shardings). After the first step every leaf already IS a
            # correctly-sharded jit output, and device_put_global
            # returns it untouched; single-controller skips this block
            # entirely (the jit's in_shardings do the placement).
            rep = NamedSharding(self.mesh, P())
            states = [jax.tree.map(
                lambda a, sp=sp: device_put_global(
                    a, self._state_sharding(sp, a.shape)), st)
                for st, sp in zip(states, self._pspecs)]
            key = device_put_global(key, rep)
            lr = device_put_global(lr, rep)
            step_i = device_put_global(step_i, rep)
        def _batch_sharding(nd):
            if self.sep_degree > 1 and nd == 2:
                return NamedSharding(self.mesh,
                                     P(("dp", "sharding"), "sep"))
            return NamedSharding(self.mesh, batch_spec(nd))

        batch_arrays = [
            device_put_global(t._data, _batch_sharding(t._data.ndim))
            for t in inputs + labels]
        # the first call traces the step: kernels dispatched inside it
        # must know the mesh (pallas_call has no partitioning rule)
        with mesh_env(self.mesh):
            out = fn(
                key,
                [p._data for _, p in self._train_named],
                [p._data for _, p in self._frozen_named],
                [b._data for _, b in self._buf_named],
                states,
                gacc,
                lr,
                step_i,
                *batch_arrays)
        if not do_update:
            loss_v, new_buf, new_gacc = out
            self._gacc = list(new_gacc)
            for (n, b), arr in zip(self._buf_named, new_buf):
                b._inplace_update(arr)
            return Tensor(loss_v)
        loss_v, new_buf, new_params, new_states, new_gacc = out
        if self.k_steps > 1:
            self._gacc = list(new_gacc)
        for (n, p), arr in zip(self._train_named, new_params):
            p._inplace_update(arr)
        for (n, p), st in zip(self._train_named, new_states):
            opt._accum[id(p)] = st
        for (n, b), arr in zip(self._buf_named, new_buf):
            b._inplace_update(arr)
        return Tensor(loss_v)

    # eval forward under the same shardings
    def eval_batch(self, inputs):
        if not self._placed:
            self.shard_parameters()
        from ...core.autograd import no_grad
        with no_grad():
            self.layer.eval()
            outs = self.layer(*[t if isinstance(t, Tensor) else Tensor(
                jnp.asarray(t)) for t in inputs])
            self.layer.train()
        return outs
