"""The chip's compiler, without the chip: the kernels and attention of
the two hot paths compile for a DESCRIBED TPU v5e (2x2) at the shapes
chip_smoke.py runs — what Mosaic or XLA:TPU refuses here costs no chip
time (on-chip-measurement guide §2.3). Nothing executes; a compile that
passes is not a chip run.

Code that asks ``jax.default_backend()`` still sees the CPU here, so the
cases call the kernels directly, or steer the dispatch from the test
(``_on_tpu`` patched) — never through an option of the program.
"""
import json
import os
import re
import sys

import numpy as np
import pytest

os.environ.setdefault("TPU_LOG_DIR", "disabled")  # else it logs to /tmp

import jax  # noqa: E402
import jax.numpy as jnp  # noqa: E402
from jax.sharding import (Mesh, NamedSharding,  # noqa: E402
                          PartitionSpec as P, SingleDeviceSharding)

sys.path.insert(0, os.path.dirname(os.path.dirname(
    os.path.abspath(__file__))))
import chip_smoke  # noqa: E402  (the shapes under test are the smoke's)

from paddle_tpu.ops.pallas import flash_attention as fa  # noqa: E402
from paddle_tpu.ops.pallas._fa_kernel import (fa_backward,  # noqa: E402
                                              fa_forward)

SZ = chip_smoke.REAL
B, S, H, D = SZ.train_batch, SZ.train_seq, SZ.heads, SZ.hidden // SZ.heads
BF16 = jnp.bfloat16


@pytest.fixture(scope="module")
def topo():
    """A described v5e:2x2, with the persistent compile cache off around
    the module: an entry compiled for a described device cannot be read
    back and would warn on the next run."""
    from jax.experimental import topologies
    from jax.experimental.compilation_cache import compilation_cache
    try:
        desc = topologies.get_topology_desc(platform="tpu",
                                            topology_name="v5e:2x2")
    except Exception as e:  # no libtpu, or it cannot describe a v5e
        pytest.skip(f"TPU topology cannot be described here: {e}")
    was = jax.config.jax_enable_compilation_cache
    jax.config.update("jax_enable_compilation_cache", False)
    compilation_cache.reset_cache()
    yield desc
    jax.config.update("jax_enable_compilation_cache", was)
    compilation_cache.reset_cache()


@pytest.fixture
def one(topo):
    return SingleDeviceSharding(topo.devices[0])


def _sds(shape, dtype, sharding):
    return jax.ShapeDtypeStruct(shape, dtype, sharding=sharding)


def _compile(fn, *avals):
    return jax.jit(fn).lower(*avals).compile()


def _kernels(compiled):
    return compiled.as_text().count("tpu_custom_call")


def _fwd_bwd(q, k, v, g):
    out, lse = fa_forward(q, k, v, causal=True, return_lse=True)
    return fa_backward(q, k, v, out, lse, g, causal=True)


class TestFlashAttentionCompiles:
    def test_device_is_the_v5e_jax_reports(self, topo):
        from paddle_tpu.utils.chip_specs import chip_spec
        assert len(topo.devices) == 4
        assert chip_spec(topo.devices[0].device_kind).bf16_flops == 197e12

    def test_forward_at_train_shape(self, one):
        q = _sds((B, S, H, D), BF16, one)
        c = _compile(lambda q, k, v: fa_forward(
            q, k, v, causal=True, return_lse=True), q, q, q)
        assert _kernels(c) == 1

    def test_forward_backward_at_train_shape(self, one):
        q = _sds((B, S, H, D), BF16, one)
        assert _kernels(_compile(_fwd_bwd, q, q, q, q)) == 3

    def test_forward_backward_gqa(self, one):
        q = _sds((B, 2048, H, D), BF16, one)
        kv = _sds((B, 2048, H // 4, D), BF16, one)
        assert _kernels(_compile(_fwd_bwd, q, kv, kv, q)) == 3

    def test_forward_packed_segments(self, one):
        q = _sds((B, S, H, D), BF16, one)
        seg = _sds((B, S), jnp.int32, one)
        c = _compile(lambda q, k, v, s: fa_forward(
            q, k, v, causal=True, q_seg=s, kv_seg=s), q, q, q, seg)
        assert _kernels(c) == 1

    def test_forward_cross_length(self, one):
        q = _sds((B, 128, H, D), BF16, one)
        kv = _sds((B, 2048, H // 4, D), BF16, one)
        c = _compile(lambda q, k, v: fa_forward(q, k, v, causal=True),
                     q, kv, kv)
        assert _kernels(c) == 1

    def test_forward_masked_stream(self, one):
        q = _sds((B, S, H, D), BF16, one)
        m = _sds((B, 1, S, S), jnp.float32, one)
        c = _compile(lambda q, k, v, m: fa_forward(q, k, v, mask=m),
                     q, q, q, m)
        assert _kernels(c) == 1


class TestTrainCellCall:
    """The benchmark's train cell makes this call twice a step: bf16
    [1, 4096, 32 | 8, 128] through ``flashmask_attention`` with Mistral's
    window, forward and backward, at the tiles the shape gives (512)."""

    @pytest.fixture
    def grads(self, one, monkeypatch):
        from paddle_tpu.core.tensor import Tensor
        monkeypatch.setattr(fa, "_on_tpu", lambda: True)
        for knob in ("PADDLE_TPU_FA_BLOCK_Q", "PADDLE_TPU_FA_BLOCK_K",
                     "PADDLE_TPU_FA_BWD_BLOCK_Q",
                     "PADDLE_TPU_FA_BWD_BLOCK_K"):
            monkeypatch.delenv(knob, raising=False)
        q = _sds((1, 4096, 32, 128), BF16, one)
        kv = _sds((1, 4096, 8, 128), BF16, one)

        def compiled(window):
            def loss(q, k, v):
                out = fa.flashmask_attention(
                    *(Tensor(x, stop_gradient=False) for x in (q, k, v)),
                    causal=True, window_size=window)
                return out._data.astype(jnp.float32).sum()
            fa.reset_dispatch_stats()
            c = _compile(jax.grad(loss, argnums=(0, 1, 2)), q, kv, kv)
            return c, fa.dispatch_stats()
        return compiled

    def test_window_of_the_length_compiles_as_the_causal_call(self, grads):
        c, stats = grads(4095)
        assert _kernels(c) == 3
        assert stats == {"pallas": 1, "fallback": 0, "resident": 1,
                         "streamed": 0, "window_as_causal": 1}

    def test_binding_window_compiles_on_the_flashmask_kernels(self, grads):
        c, stats = grads(1023)
        assert _kernels(c) == 3
        assert stats == {"pallas": 1, "fallback": 0, "resident": 0,
                         "streamed": 1, "window_as_causal": 0}

    @pytest.mark.parametrize("d,dtype,masked", [
        (64, BF16, False), (256, BF16, False), (256, jnp.float32, True)])
    def test_default_tiles_fit_vmem_at_other_head_dims(self, one, d,
                                                       dtype, masked):
        """512 x 512 tiles under the 16 MB of scoped VMEM: the widest
        head in f32 with a streamed dense mask and packed segments is
        the most any call holds."""
        s = 2048
        q = _sds((1, s, 8, d), dtype, one)
        kv = _sds((1, s, 2, d), dtype, one)
        avals = [_sds((1, 1, s, s), jnp.float32, one),
                 _sds((1, s), jnp.int32, one)] if masked else []

        def fwd_bwd(q, k, v, g, *more):
            kw = dict(mask=more[0], q_seg=more[1],
                      kv_seg=more[1]) if more else {}
            out, lse = fa_forward(q, k, v, causal=True, return_lse=True,
                                  **kw)
            return fa_backward(q, k, v, out, lse, g, causal=True, **kw)
        assert _kernels(_compile(fwd_bwd, q, kv, kv, q, *avals)) == 3


class TestShardedFlashAttention:
    """The fleet stepper's layout on the 2x2 mesh (sharding 2 x mp 2):
    batch over the data axes, heads over mp."""

    @pytest.fixture
    def sharded(self, topo, monkeypatch):
        monkeypatch.setattr(fa, "_on_tpu", lambda: True)
        mesh = Mesh(np.array(topo.devices).reshape(1, 1, 2, 1, 2),
                    ("dp", "pp", "sharding", "sep", "mp"))
        q = _sds((SZ.fleet_batch, S, H, D), BF16, NamedSharding(
            mesh, P(("dp", "sharding"), None, "mp", None)))

        def grads(q, k, v):
            return jax.grad(lambda *a: fa._flash_core_ext(
                *a, None, None, None, True, None).astype(
                    jnp.float32).sum(), argnums=(0, 1, 2))(q, k, v)
        return mesh, q, grads

    def test_kernel_runs_per_shard_under_the_stepper_mesh(self, sharded):
        from paddle_tpu.distributed._axis import mesh_env
        mesh, q, grads = sharded
        fa.reset_dispatch_stats()
        with mesh_env(mesh):
            c = _compile(grads, q, q, q)
        assert _kernels(c) == 3
        stats = fa.dispatch_stats()
        assert (stats["pallas"], stats["fallback"]) == (1, 0), stats
        # each chip works on its own shard: no collective is needed
        assert "all-gather" not in c.as_text()

    def test_unwrapped_sharded_operands_are_refused(self, sharded):
        """Why the dispatch wraps: without the stepper's mesh_env the
        same call hands Mosaic a sharded operand."""
        _, q, grads = sharded
        with pytest.raises(NotImplementedError,
                           match="cannot be automatically partitioned"):
            _compile(grads, q, q, q)


class TestServingAttention:
    def _operands(self, sharding):
        t = SZ.max_batch + SZ.prefill_chunk          # the mixed capacity
        lanes = SZ.max_batch + 1
        pages = -(-SZ.max_seq_len // SZ.page_size)
        pool = _sds((SZ.num_pages, SZ.page_size, H, D), BF16, sharding)
        i32 = lambda *s: _sds(s, jnp.int32, sharding)  # noqa: E731
        return t, lanes, pages, pool, i32

    def test_ragged_gather_path_at_pool_geometry(self, one):
        from paddle_tpu.serving.attention import ragged_paged_attention
        t, lanes, pages, pool, i32 = self._operands(one)
        c = _compile(
            lambda q, kp, vp, pt, cl, ql, qo: ragged_paged_attention(
                q, kp, vp, pt, cl, ql, qo, scale=D ** -0.5),
            _sds((t, H, D), BF16, one), pool, pool, i32(lanes, pages),
            i32(lanes), i32(lanes), i32(lanes))
        assert _kernels(c) == 0      # a gather, not a kernel (ROADMAP S4)

    def test_ragged_gather_is_a_lanes_not_a_tokens(self, one):
        """The chunk-carrying class at the pool geometry: a lane's page
        table is gathered once, so the chip's compiler leaves no array
        led by T x pages (the per-token form's gathered pages and their
        f32 copies: ``f32[4608,16,32,128]`` in the backlog cell's
        traces to PR 29), and the call's temporaries are under a
        quarter of that form's."""
        from paddle_tpu.serving.attention import (paged_attention_ref,
                                                  ragged_paged_attention)
        t, lanes, pages, pool, i32 = self._operands(one)

        def per_token(q, kp, vp, pt, cl, ql, qo):
            lane = jnp.minimum(jnp.arange(t), lanes - 1)
            return paged_attention_ref(
                q[:, None], kp, vp, pt[lane], cl[lane], qo[lane],
                scale=D ** -0.5)[:, 0]

        def by_lane(q, kp, vp, pt, cl, ql, qo):
            return ragged_paged_attention(q, kp, vp, pt, cl, ql, qo,
                                          scale=D ** -0.5)

        old, new = (_compile(
            f, _sds((t, H, D), BF16, one), pool, pool, i32(lanes, pages),
            i32(lanes), i32(lanes), i32(lanes)) for f in (per_token,
                                                          by_lane))
        assert f"[{t * pages}," in old.as_text()
        assert f"[{t * pages}," not in new.as_text()
        assert (new.memory_analysis().temp_size_in_bytes * 4
                < old.memory_analysis().temp_size_in_bytes)

    @pytest.mark.xfail(strict=True, raises=ValueError,
                       reason="Mosaic refuses the ragged paged kernel as "
                              "written: (1, P) page-table block not "
                              "divisible by (8, 128); S4 flips this")
    def test_ragged_pallas_kernel_compiles_under_mosaic(self, one,
                                                        monkeypatch):
        from jax.experimental import pallas as pl

        from paddle_tpu.serving.attention import _ragged_attention_kernel
        real = pl.pallas_call
        monkeypatch.setattr(
            pl, "pallas_call",
            lambda *a, **kw: real(*a, **{**kw, "interpret": False}))
        t, lanes, pages, pool, i32 = self._operands(one)
        _compile(
            lambda q, kp, vp, pt, cl, pos: _ragged_attention_kernel(
                q, kp, vp, pt, cl, pos, scale=D ** -0.5),
            _sds((t, H, D), BF16, one), pool, pool, i32(t, pages), i32(t),
            i32(t))


class TestRaggedStepTail:
    """The whole ragged step at the smoke's serving widths, one layer,
    at the capacity that carries a chunk: what the chip's compiler
    leaves of the sampler's sort."""

    def test_sorts_once_and_only_under_a_condition(self, one):
        import paddle_tpu as P
        from paddle_tpu.models import LlamaConfig, LlamaForCausalLM
        from paddle_tpu.serving import ServingEngine

        from serving_utils import hlo_sorts, ragged_step_avals
        with P.LazyGuard():
            model = LlamaForCausalLM(LlamaConfig(
                vocab_size=SZ.vocab, hidden_size=SZ.hidden,
                intermediate_size=SZ.ffn, num_hidden_layers=1,
                num_attention_heads=SZ.heads,
                max_position_embeddings=SZ.max_seq_len, dtype=SZ.dtype))
        for p in model.parameters():  # stay shapes: no initializer runs
            del p._lazy_init
        for lyr in model.sublayers(include_self=True):
            lyr.__dict__["_has_lazy_params"] = False
        model.eval()
        eng = ServingEngine(model, page_size=SZ.page_size,
                            num_pages=16, max_batch=SZ.max_batch,
                            prefill_chunk=SZ.prefill_chunk,
                            max_seq_len=SZ.max_seq_len)
        avals = ragged_step_avals(     # the chunk-carrying class
            eng, eng._ragged_tok_mixed,
            lambda shape, dt: _sds(tuple(shape), dt, one))
        avals[0][:] = [_sds(a.shape, BF16, one) for a in avals[0]]
        c = eng._step_program().lower(*avals).compile()   # about 25 s
        assert hlo_sorts(c.as_text()) == (0, 1)


class TestStepOwnsItsPools:
    """The engine's own step function (``ServingEngine._step_program``,
    the jit ``_run_ragged_step`` dispatches, not one made here) compiled
    for the described chip: every byte of the cache's state goes in
    donated and comes out in the same buffer, whatever the cache is
    made of and in both step classes. Tiny widths: what is read is the
    compiler's aliasing, not a size (the cells' sizes: PERF.md §4)."""

    @staticmethod
    def _llama(**ekw):
        import paddle_tpu as P
        from paddle_tpu.models import LlamaConfig, LlamaForCausalLM
        from paddle_tpu.serving import ServingEngine
        P.seed(0)
        model = LlamaForCausalLM(LlamaConfig(
            vocab_size=97, hidden_size=32, intermediate_size=64,
            num_hidden_layers=2, num_attention_heads=4,
            max_position_embeddings=64))
        model.eval()
        return ServingEngine(model, page_size=4, num_pages=32, max_batch=4,
                             prefill_chunk=8, **ekw)

    @pytest.mark.parametrize("chunk", [False, True],
                             ids=["decode_class", "chunk_class"])
    @pytest.mark.parametrize("cache", ["dense", "int8", "latent", "mixed"])
    def test_every_byte_of_cache_state_is_aliased(self, one, cache, chunk):
        from serving_utils import ragged_step_avals
        from test_serving_ragged import DONATION_CASES
        eng = {"dense": self._llama,
               "int8": lambda: self._llama(cache_dtype="int8"),
               "latent": DONATION_CASES["latent"][0],
               "mixed": DONATION_CASES["mixed"][0]}[cache]()
        c = eng.cache
        assert (c.quantized, c.latent and not c.mixed, c.mixed) == (
            cache == "int8", cache == "latent", cache == "mixed")
        avals = ragged_step_avals(
            eng, eng._ragged_tok_mixed if chunk else eng._ragged_tok_small,
            lambda shape, dt: _sds(tuple(shape), dt, one))
        state = jax.tree.leaves(avals[9:12])
        assert len(state) == len(jax.tree.leaves(
            (c.program_operands(), c.extra_operands())))
        compiled = eng._step_program().lower(*avals).compile()
        assert "jit__unknown" in compiled.as_text()[:200]
        # the state's bytes as the chip holds them (tiny widths pad to
        # the device's tiles): what a program of those operands alone
        # is handed
        held = jax.jit(lambda *s: s).lower(
            *state).compile().memory_analysis().argument_size_in_bytes
        assert held >= sum(a.size * a.dtype.itemsize for a in state) > 0
        assert compiled.memory_analysis().alias_size_in_bytes == held


class TestKdaDecodeKernelInTheStep:
    """The Ling step program (``ServingEngine._step_program``) compiled
    for the described chip with the TPU route steered (``_on_tpu``
    patched): tiny widths and four layers (three KDA), but the cell's 64
    lanes and 32 KDA heads of 128, so that a layer's state (65 slots,
    136 MB) is one the compiler cannot keep in VMEM, as a deployment's.
    Each KDA layer's decode lanes are one kernel call that the cell's
    ``kda_op_match`` names as it stands, its state operand aliased to
    its first output: no state is copied and no loop gathers one, in
    either step class. The plain route's decode class is compiled too:
    its per-lane gather is a ``while`` over those shapes, which is what
    the kernel takes away."""

    STATE = "f32[65,32,128,128]"

    @pytest.fixture
    def engine(self):
        """A fresh engine a test: its step's trace is cached by shapes,
        and each test steers the route the trace takes."""
        import paddle_tpu as P
        from paddle_tpu.models import KdaMoEConfig, KdaMoEForCausalLM
        from paddle_tpu.serving import ServingEngine
        P.seed(0)
        model = KdaMoEForCausalLM(KdaMoEConfig.tiny(
            head_dim=128, num_attention_heads=32, num_key_value_heads=32))
        model.eval()
        return ServingEngine(model, ragged=True, page_size=4, num_pages=32,
                             max_batch=64, prefill_chunk=8, max_seq_len=64)

    def _compiled(self, eng, one, chunk):
        from serving_utils import ragged_step_avals
        from paddle_tpu.ops import delta_rule as dr
        avals = ragged_step_avals(
            eng, eng._ragged_tok_mixed if chunk else eng._ragged_tok_small,
            lambda shape, dt: _sds(tuple(shape), dt, one))
        dr.reset_dispatch_stats()
        compiled = eng._step_program().lower(*avals).compile()
        # the cache state's bytes as the chip holds them (padded tiles)
        held = jax.jit(lambda *a: a).lower(*jax.tree.leaves(
            avals[9:12])).compile().memory_analysis().argument_size_in_bytes
        lines = [ln.strip().removeprefix("ROOT ")
                 for ln in compiled.as_text().splitlines()]
        return compiled, held, lines, dr.dispatch_stats()

    def _on_states(self, lines, op):
        """Instructions ``op`` whose shapes hold a whole layer's states
        or the decode lanes' gathered ones."""
        return [ln for ln in lines if re.search(rf"\b{op}\(", ln)
                and (self.STATE in ln or "f32[64,32,128,128]" in ln)]

    @pytest.mark.parametrize("chunk", [False, True],
                             ids=["decode_class", "chunk_class"])
    def test_the_decode_lanes_are_one_kernel_in_place(self, one, engine,
                                                      chunk, monkeypatch):
        from paddle_tpu.ops import delta_rule as dr
        monkeypatch.setattr(dr, "_on_tpu", lambda: True)
        compiled, held, lines, stats = self._compiled(engine, one, chunk)
        assert stats == {"kernel": 3, "step": 0, "chunk": 3 * chunk}
        with open(os.path.join(os.path.dirname(os.path.dirname(
                os.path.abspath(__file__))), "benchmark", "traffic",
                "longchain.json")) as f:
            match = re.compile(json.load(f)["kda_op_match"])
        kernels = [ln for ln in lines if ln.startswith("%kda_decode")]
        assert len(kernels) == 3
        for ln in kernels:
            assert ln.split(" = ")[1].startswith("(" + self.STATE)
            assert match.search(ln)
            assert "output_to_operand_aliasing={{0}: (8, {})}" in ln
        for op in ("copy", "copy-start", "while"):
            assert not self._on_states(lines, op), op
        assert compiled.memory_analysis().alias_size_in_bytes == held

    def test_the_plain_route_gathers_the_lanes_in_a_loop(self, one, engine,
                                                         monkeypatch):
        from paddle_tpu.ops import delta_rule as dr
        monkeypatch.setattr(dr, "_on_tpu", lambda: False)
        _, _, lines, stats = self._compiled(engine, one, False)
        assert stats == {"kernel": 0, "step": 3, "chunk": 0}
        assert len(self._on_states(lines, "while")) == 3


class TestSalaStep:
    """MiniCPM-SALA's serving step at its head widths (32 query heads and
    2 KV heads of 128, lightning 32 x 128, pages of 64), compiled for the
    chip: the lightning layers' decode rows are the delta rule's kernel
    with ``rule="lightning"``, one a layer, in place; and neither the
    sparse layers' pool nor their indexer is laid out anew (a reshape of
    the pool to ``[pages, 64, 2, 2, 128]`` cost a pool-sized copy a
    layer a step)."""

    POOL = "bf16[64,64,512]"

    @pytest.fixture
    def engine(self):
        import paddle_tpu as P
        from paddle_tpu.models import MiniCPMSalaConfig, MiniCPMSalaForCausalLM
        from paddle_tpu.serving import ServingEngine
        P.seed(0)
        mix = ["lightning-attn", "minicpm4", "lightning-attn",
               "lightning-attn", "minicpm4"]
        model = MiniCPMSalaForCausalLM(MiniCPMSalaConfig(
            vocab_size=512, hidden_size=256, intermediate_size=512,
            num_hidden_layers=4, mixer_types=mix, first_layer=1,
            dim_model_base=64, dtype="bfloat16"))
        model.eval()
        return ServingEngine(model, ragged=True, page_size=64, num_pages=64,
                             max_batch=4, prefill_chunk=64, max_seq_len=1024)

    @pytest.mark.parametrize("chunk", [False, True],
                             ids=["decode_class", "chunk_class"])
    def test_the_lightning_lanes_are_one_kernel_and_no_pool_moves(
            self, one, engine, chunk, monkeypatch):
        from serving_utils import ragged_step_avals
        from paddle_tpu.ops import delta_rule as dr
        monkeypatch.setattr(dr, "_on_tpu", lambda: True)
        avals = ragged_step_avals(
            engine, engine._ragged_tok_mixed if chunk
            else engine._ragged_tok_small,
            lambda shape, dt: _sds(tuple(shape), dt, one))
        dr.reset_dispatch_stats()
        compiled = engine._step_program().lower(*avals).compile()
        assert dr.dispatch_stats() == {"kernel": 2, "step": 0,
                                       "chunk": 2 * chunk}
        lines = [ln.strip().removeprefix("ROOT ")
                 for ln in compiled.as_text().splitlines()]
        kernels = [ln for ln in lines if ln.startswith("%lightning_decode")]
        assert len(kernels) == 2
        for ln in kernels:
            assert "output_to_operand_aliasing={{0}: (8, {})}" in ln
        # (a pool this small may be staged into fast memory: copy-start)
        assert not [ln for ln in lines if re.search(r"\bcopy\(", ln)
                    and (self.POOL in ln or "bf16[64,4,2,128]" in ln)]


def test_paged_kernel_knob_raises_off_cpu(monkeypatch):
    """PADDLE_TPU_PAGED_KERNEL=1 would run an interpreted kernel on a
    chip: anywhere but the cpu backend it raises."""
    from paddle_tpu.serving import attention
    monkeypatch.setenv("PADDLE_TPU_PAGED_KERNEL", "1")
    assert attention._kernel_requested() is True
    monkeypatch.setattr(jax, "default_backend", lambda: "tpu")
    with pytest.raises(RuntimeError, match="only runs on the cpu"):
        attention._kernel_requested()
