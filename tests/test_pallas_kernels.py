"""Pallas kernel tests (interpret mode on CPU; compiled on real TPU)."""
import numpy as np
import pytest

import jax.numpy as jnp

from paddle_tpu.ops.pallas._fa_kernel import fa_forward
from paddle_tpu.ops.pallas.flash_attention import _attention_ref


def qkv(b=2, s=256, h=2, d=64, seed=0, dtype=np.float32):
    rng = np.random.default_rng(seed)
    return [jnp.asarray(rng.standard_normal((b, s, h, d)).astype(dtype))
            for _ in range(3)]


class TestFlashAttentionKernel:
    @pytest.mark.parametrize("causal", [False, True])
    def test_matches_reference(self, causal):
        q, k, v = qkv()
        out = fa_forward(q, k, v, causal=causal, interpret=True)
        ref = _attention_ref(q, k, v, causal=causal)
        assert np.allclose(np.asarray(out), np.asarray(ref), atol=2e-4), \
            np.abs(np.asarray(out) - np.asarray(ref)).max()

    def test_small_seq_blocks(self):
        q, k, v = qkv(s=128, d=32)
        out = fa_forward(q, k, v, causal=True, block_q=64, block_k=64,
                         interpret=True)
        ref = _attention_ref(q, k, v, causal=True)
        assert np.allclose(np.asarray(out), np.asarray(ref), atol=2e-4)

    def test_bf16(self):
        q, k, v = qkv(s=128, d=64)
        qb, kb, vb = (x.astype(jnp.bfloat16) for x in (q, k, v))
        out = fa_forward(qb, kb, vb, causal=False, interpret=True)
        ref = _attention_ref(q, k, v, causal=False)
        assert np.allclose(np.asarray(out, dtype=np.float32),
                           np.asarray(ref), atol=3e-2)


class TestFlashAttentionBackward:
    """fa_backward vs jax.vjp of the XLA reference (interpret mode)."""

    def _check(self, b=2, s=256, h=2, d=64, causal=False, dtype=np.float32,
               block_q=128, block_k=128, atol=2e-3):
        import jax
        from paddle_tpu.ops.pallas._fa_kernel import fa_backward
        q, k, v = qkv(b=b, s=s, h=h, d=d, dtype=dtype)
        g = jnp.asarray(np.random.default_rng(7).standard_normal(
            (b, s, h, d)).astype(dtype))
        out, lse = fa_forward(q, k, v, causal=causal, interpret=True,
                              block_q=block_q, block_k=block_k,
                              return_lse=True)
        dq, dk, dv = fa_backward(q, k, v, out, lse, g, causal=causal,
                                 interpret=True, block_q=block_q,
                                 block_k=block_k)
        ref_out, vjp = jax.vjp(
            lambda a, b_, c: _attention_ref(a, b_, c, causal=causal),
            q, k, v)
        rdq, rdk, rdv = vjp(g)
        for got, ref, name in [(dq, rdq, "dq"), (dk, rdk, "dk"),
                               (dv, rdv, "dv")]:
            err = np.abs(np.asarray(got, np.float32) -
                         np.asarray(ref, np.float32)).max()
            assert err < atol, f"{name} max err {err}"

    @pytest.mark.parametrize("causal", [False, True])
    def test_grad_parity(self, causal):
        self._check(causal=causal)

    def test_uneven_blocks(self):
        self._check(s=256, block_q=64, block_k=128, causal=True)
        self._check(s=256, block_q=128, block_k=64, causal=True)

    def test_bf16(self):
        self._check(s=128, dtype=np.float32, causal=True)
        import jax
        from paddle_tpu.ops.pallas._fa_kernel import fa_backward
        q, k, v = qkv(s=128, d=64)
        qb, kb, vb = (x.astype(jnp.bfloat16) for x in (q, k, v))
        g = jnp.ones((2, 128, 2, 64), jnp.bfloat16)
        out, lse = fa_forward(qb, kb, vb, causal=True, interpret=True,
                              return_lse=True)
        dq, dk, dv = fa_backward(qb, kb, vb, out, lse, g, causal=True,
                                 interpret=True)
        _, vjp = jax.vjp(
            lambda a, b_, c: _attention_ref(a, b_, c, causal=True), q, k, v)
        rdq, rdk, rdv = vjp(jnp.ones_like(q))
        for got, ref in [(dq, rdq), (dk, rdk), (dv, rdv)]:
            assert np.allclose(np.asarray(got, np.float32),
                               np.asarray(ref), atol=5e-2)

    def test_custom_vjp_fallback_path(self):
        """Off-TPU the custom_vjp should still produce reference grads."""
        import jax
        from paddle_tpu.ops.pallas.flash_attention import _flash_core
        q, k, v = qkv(s=128, d=32)
        f = lambda a, b_, c: _flash_core(a, b_, c, True, None).sum()
        g1 = jax.grad(f, argnums=(0, 1, 2))(q, k, v)
        g2 = jax.grad(lambda a, b_, c: _attention_ref(
            a, b_, c, causal=True).sum(), argnums=(0, 1, 2))(q, k, v)
        for a, b_ in zip(g1, g2):
            assert np.allclose(np.asarray(a), np.asarray(b_), atol=2e-4)


class TestFusedAdamWKernel:
    """Pallas fused AdamW vs the XLA _update rule (interpret mode)."""

    def _states(self, shape, master_dtype=None, seed=0):
        rng = np.random.default_rng(seed)
        f = lambda: jnp.asarray(rng.standard_normal(shape).astype(np.float32))
        st = {"moment1": f() * 0.1, "moment2": jnp.abs(f()) * 0.01}
        if master_dtype is not None:
            st["master"] = f()
        return st

    @pytest.mark.parametrize("decoupled", [False, True])
    def test_parity_master_bf16(self, decoupled):
        from paddle_tpu.ops.pallas._adamw_kernel import adamw_update
        from paddle_tpu.optimizer.optimizers import Adam
        shape = (96, 128)
        st = self._states(shape, master_dtype=jnp.float32)
        rng = np.random.default_rng(3)
        g = jnp.asarray(rng.standard_normal(shape).astype(np.float32)
                        ).astype(jnp.bfloat16)
        p_bf16 = st["master"].astype(jnp.bfloat16)
        hp = {"b1": 0.9, "b2": 0.999, "eps": 1e-8, "weight_decay": 0.01,
              "decoupled": decoupled, "amsgrad": False}
        lr = jnp.asarray(1e-3, jnp.float32)
        step = jnp.asarray(7, jnp.int32)

        got_p, got_st = adamw_update(
            p_bf16, g, dict(st), lr, step, b1=hp["b1"], b2=hp["b2"],
            eps=hp["eps"], wd=hp["weight_decay"],
            decoupled=decoupled, interpret=True)
        ref_master, ref_st = Adam._update(
            st["master"], g.astype(jnp.float32), st, lr, step, hp)
        assert np.allclose(np.asarray(got_st["master"]),
                           np.asarray(ref_master), atol=1e-6)
        assert np.allclose(np.asarray(got_p, np.float32),
                           np.asarray(ref_master.astype(jnp.bfloat16),
                                      np.float32), atol=0)
        for k in ("moment1", "moment2"):
            assert np.allclose(np.asarray(got_st[k]),
                               np.asarray(ref_st[k]), atol=1e-6), k

    def test_parity_f32_no_master_uneven_grid(self):
        from paddle_tpu.ops.pallas._adamw_kernel import (adamw_update,
                                                         _BLOCK_ROWS)
        from paddle_tpu.optimizer.optimizers import Adam
        # rows = 600 does not divide _BLOCK_ROWS=512 -> exercises the
        # masked final block
        shape = (600, 128)
        assert shape[0] % _BLOCK_ROWS != 0
        st = self._states(shape)
        p = jnp.asarray(np.random.default_rng(5).standard_normal(
            shape).astype(np.float32))
        g = jnp.asarray(np.random.default_rng(6).standard_normal(
            shape).astype(np.float32))
        hp = {"b1": 0.9, "b2": 0.999, "eps": 1e-8, "weight_decay": 0.0,
              "decoupled": True, "amsgrad": False}
        lr = jnp.asarray(3e-4, jnp.float32)
        step = jnp.asarray(1, jnp.int32)
        got_p, got_st = adamw_update(p, g, dict(st), lr, step, b1=0.9,
                                     b2=0.999, eps=1e-8, wd=0.0,
                                     decoupled=True, interpret=True)
        ref_p, ref_st = Adam._update(p, g, st, lr, step, hp)
        assert np.allclose(np.asarray(got_p), np.asarray(ref_p), atol=1e-6)
        for k in ("moment1", "moment2"):
            assert np.allclose(np.asarray(got_st[k]),
                               np.asarray(ref_st[k]), atol=1e-6), k

    def test_eligibility(self):
        from paddle_tpu.ops.pallas._adamw_kernel import adamw_eligible
        st = {"moment1": 1, "moment2": 1}
        assert adamw_eligible((256, 128), jnp.bfloat16, st)
        assert adamw_eligible((2048,), jnp.float32, st)
        assert not adamw_eligible((100,), jnp.float32, st)   # not lane-div
        assert not adamw_eligible((256, 128), jnp.float32,
                                  dict(st, moment2_max=1))   # amsgrad

    def test_optimizer_fused_apply_pallas_route(self):
        """AdamW._fused_apply(use_pallas=True) == the XLA route."""
        import paddle_tpu as P
        lin = P.nn.Linear(128, 64)
        opt = P.optimizer.AdamW(1e-3, parameters=lin.parameters())
        params = [p._data for p in lin.parameters()]
        grads = [jnp.ones_like(p) * 0.01 for p in params]
        states = [opt._get_state(p) for p in lin.parameters()]
        lr = jnp.asarray(1e-3, jnp.float32)
        step = jnp.asarray(1, jnp.int32)
        got_p, got_st = opt._fused_apply(list(params), grads,
                                         [dict(s) for s in states],
                                         lr, step, use_pallas=True)
        ref_p, ref_st = opt._fused_apply(list(params), grads,
                                         [dict(s) for s in states],
                                         lr, step, use_pallas=False)
        for a, b in zip(got_p, ref_p):
            assert np.allclose(np.asarray(a), np.asarray(b), atol=1e-6)


def _seg_ids(b, s, n_seg, seed=3):
    """Monotone packed segment ids [B, S] (varlen packing layout)."""
    rng = np.random.default_rng(seed)
    out = np.zeros((b, s), np.int32)
    for bi in range(b):
        cuts = np.sort(rng.choice(np.arange(1, s), n_seg - 1,
                                  replace=False))
        out[bi] = np.searchsorted(cuts, np.arange(s), side="right")
    return jnp.asarray(out)


class TestKernelGQA:
    """Round-3 (VERDICT r2 item 2a): KV heads indexed in-kernel."""

    @pytest.mark.parametrize("causal", [False, True])
    def test_forward_parity(self, causal):
        q, _, _ = qkv(b=2, s=256, h=8, d=64)
        _, k, v = qkv(b=2, s=256, h=2, d=64, seed=5)
        out = fa_forward(q, k, v, causal=causal, interpret=True)
        ref = _attention_ref(q, k, v, causal=causal)  # ref repeats kv
        assert np.allclose(np.asarray(out), np.asarray(ref), atol=2e-4)

    @pytest.mark.parametrize("causal", [False, True])
    def test_backward_parity(self, causal):
        import jax
        from paddle_tpu.ops.pallas._fa_kernel import fa_backward
        q, _, _ = qkv(b=2, s=256, h=4, d=64)
        _, k, v = qkv(b=2, s=256, h=2, d=64, seed=5)
        g = jnp.asarray(np.random.default_rng(7).standard_normal(
            q.shape).astype(np.float32))
        out, lse = fa_forward(q, k, v, causal=causal, interpret=True,
                              return_lse=True)
        dq, dk, dv = fa_backward(q, k, v, out, lse, g, causal=causal,
                                 interpret=True)
        _, vjp = jax.vjp(lambda a, b_, c: _attention_ref(
            a, b_, c, causal=causal), q, k, v)
        rdq, rdk, rdv = vjp(g)
        for got, ref, name in [(dq, rdq, "dq"), (dk, rdk, "dk"),
                               (dv, rdv, "dv")]:
            assert np.allclose(np.asarray(got), np.asarray(ref),
                               atol=3e-3), \
                (name, np.abs(np.asarray(got) - np.asarray(ref)).max())
        assert dk.shape == k.shape and dv.shape == v.shape


class TestKernelSegments:
    """Round-3 (VERDICT r2 item 2b): packed varlen via segment ids."""

    @pytest.mark.parametrize("causal", [False, True])
    def test_forward_parity(self, causal):
        from paddle_tpu.ops.pallas.flash_attention import _ref_ext
        q, k, v = qkv(b=2, s=256, h=2, d=64)
        seg = _seg_ids(2, 256, 3)
        out = fa_forward(q, k, v, causal=causal, interpret=True,
                         q_seg=seg, kv_seg=seg)
        ref = _ref_ext(q, k, v, None, seg, seg, causal, None)
        assert np.allclose(np.asarray(out), np.asarray(ref), atol=2e-4)

    def test_padding_rows_zero(self):
        """Rows whose segment id never matches any key produce 0 (the
        padded-varlen contract)."""
        q, k, v = qkv(b=1, s=256, h=2, d=64)
        qseg = jnp.asarray(np.full((1, 256), -1, np.int32))
        kseg = jnp.asarray(np.full((1, 256), -2, np.int32))
        out = fa_forward(q, k, v, causal=False, interpret=True,
                         q_seg=qseg, kv_seg=kseg)
        assert np.allclose(np.asarray(out), 0.0)

    @pytest.mark.parametrize("causal", [False, True])
    def test_backward_parity(self, causal):
        import jax
        from paddle_tpu.ops.pallas._fa_kernel import fa_backward
        from paddle_tpu.ops.pallas.flash_attention import _ref_ext
        q, k, v = qkv(b=2, s=256, h=2, d=64)
        seg = _seg_ids(2, 256, 3)
        g = jnp.asarray(np.random.default_rng(7).standard_normal(
            q.shape).astype(np.float32))
        out, lse = fa_forward(q, k, v, causal=causal, interpret=True,
                              return_lse=True, q_seg=seg, kv_seg=seg)
        dq, dk, dv = fa_backward(q, k, v, out, lse, g, causal=causal,
                                 interpret=True, q_seg=seg, kv_seg=seg)
        _, vjp = jax.vjp(lambda a, b_, c: _ref_ext(
            a, b_, c, None, seg, seg, causal, None), q, k, v)
        rdq, rdk, rdv = vjp(g)
        for got, ref, name in [(dq, rdq, "dq"), (dk, rdk, "dk"),
                               (dv, rdv, "dv")]:
            assert np.allclose(np.asarray(got), np.asarray(ref),
                               atol=3e-3), \
                (name, np.abs(np.asarray(got) - np.asarray(ref)).max())


class TestKernelMask:
    """Round-3 (VERDICT r2 item 2c): additive masks stream per block."""

    @pytest.mark.parametrize("mshape", [(1, 1, 256, 256), (2, 1, 256, 256),
                                        (2, 2, 256, 256)])
    def test_forward_parity(self, mshape):
        rng = np.random.default_rng(11)
        q, k, v = qkv(b=2, s=256, h=2, d=64)
        # additive mask with some -inf (hard-masked) entries
        m = rng.standard_normal(mshape).astype(np.float32)
        m[..., ::7] = -np.inf
        m = jnp.asarray(m)
        out = fa_forward(q, k, v, interpret=True, mask=m)
        ref = _attention_ref(q, k, v, mask=m)
        assert np.allclose(np.asarray(out), np.asarray(ref), atol=2e-4)

    def test_backward_parity(self):
        import jax
        from paddle_tpu.ops.pallas._fa_kernel import fa_backward
        rng = np.random.default_rng(11)
        q, k, v = qkv(b=2, s=256, h=2, d=64)
        m = jnp.asarray(np.where(
            rng.random((2, 1, 256, 256)) < 0.2, -np.inf,
            0.0).astype(np.float32))
        g = jnp.asarray(rng.standard_normal(q.shape).astype(np.float32))
        out, lse = fa_forward(q, k, v, interpret=True, return_lse=True,
                              mask=m)
        dq, dk, dv = fa_backward(q, k, v, out, lse, g, interpret=True,
                                 mask=m)
        _, vjp = jax.vjp(lambda a, b_, c: _attention_ref(a, b_, c,
                                                         mask=m), q, k, v)
        rdq, rdk, rdv = vjp(g)
        for got, ref, name in [(dq, rdq, "dq"), (dk, rdk, "dk"),
                               (dv, rdv, "dv")]:
            assert np.allclose(np.asarray(got), np.asarray(ref),
                               atol=3e-3), \
                (name, np.abs(np.asarray(got) - np.asarray(ref)).max())

    def test_mask_with_gqa_and_causal(self):
        q, _, _ = qkv(b=1, s=256, h=4, d=64)
        _, k, v = qkv(b=1, s=256, h=2, d=64, seed=5)
        m = jnp.asarray(np.random.default_rng(2).standard_normal(
            (1, 1, 256, 256)).astype(np.float32))
        out = fa_forward(q, k, v, causal=True, interpret=True, mask=m)
        ref = _attention_ref(q, k, v, causal=True, mask=m)
        assert np.allclose(np.asarray(out), np.asarray(ref), atol=2e-4)


class TestDispatchDiscipline:
    """Round-3 (VERDICT r2 item 3): fallbacks are counted and loud."""

    def test_counter_and_strict_mode(self, monkeypatch):
        import paddle_tpu.ops.pallas.flash_attention as fa
        monkeypatch.setattr(fa, "_FORCE_INTERPRET", True)
        fa.reset_dispatch_stats()
        q, k, v = qkv(b=1, s=256, h=2, d=64)
        out = fa._flash_core(q, k, v, False, None)
        stats = fa.dispatch_stats()
        assert stats["pallas"] == 1 and stats["fallback"] == 0, stats
        # unsupported shape (seq not /128) → counted fallback + warning
        q2, k2, v2 = qkv(b=1, s=100, h=2, d=64)
        with pytest.warns(UserWarning, match="fell back"):
            fa._flash_core(q2, k2, v2, False, None)
        assert fa.dispatch_stats()["fallback"] == 1
        # strict mode raises instead
        monkeypatch.setenv("PADDLE_TPU_REQUIRE_PALLAS", "1")
        with pytest.raises(RuntimeError, match="fell back"):
            fa._flash_core(q2, k2, v2, False, None)
        fa.reset_dispatch_stats()


class TestKernelStreamedForward:
    """Round-4 (VERDICT r3 item 3): the forward streams (block_q, block_k)
    mask slabs through a 3-D grid with VMEM-scratch online-softmax state
    (no `_MASK_FWD_MAX_S` cap), and the grid is rectangular — q and kv
    lengths may differ, with the causal diagonal shifted by sk - sq
    (the reference's tril(k=sk-sq) semantics)."""

    def test_masked_long_seq_8192_dispatch_and_parity(self, monkeypatch):
        """Masked attention at s=8192 runs IN-KERNEL through the dispatch
        layer (the round-3 forward held the mask as a [block_q, S] slab
        capped at S<=4096 and fell back above it) and matches the
        reference."""
        import paddle_tpu.ops.pallas.flash_attention as fa
        monkeypatch.setattr(fa, "_FORCE_INTERPRET", True)
        monkeypatch.setenv("PADDLE_TPU_FA_BLOCK_Q", "512")
        monkeypatch.setenv("PADDLE_TPU_FA_BLOCK_K", "512")
        fa.reset_dispatch_stats()
        q, k, v = qkv(b=1, s=8192, h=1, d=64, seed=3)
        m = np.zeros((1, 1, 8192, 8192), np.float32)
        m[..., ::7] = -1e9
        m = jnp.asarray(m)
        out = fa._flash_core_ext(q, k, v, m, None, None, True, None)
        stats = fa.dispatch_stats()
        assert stats["pallas"] == 1 and stats["fallback"] == 0, stats
        ref = _attention_ref(q, k, v, mask=m, causal=True)
        assert np.allclose(np.asarray(out), np.asarray(ref), atol=2e-4)

    @pytest.mark.parametrize("causal", [False, True])
    def test_cross_length_forward(self, causal):
        """sq < sk (decode/chunked-prefill shape), GQA heads."""
        q, _, _ = qkv(b=2, s=256, h=4, d=64)
        _, k, v = qkv(b=2, s=512, h=2, d=64, seed=5)
        out = fa_forward(q, k, v, causal=causal, interpret=True)
        ref = _attention_ref(q, k, v, causal=causal)
        assert np.allclose(np.asarray(out), np.asarray(ref), atol=2e-4)

    @pytest.mark.parametrize("causal", [False, True])
    def test_cross_length_backward(self, causal):
        import jax
        from paddle_tpu.ops.pallas._fa_kernel import fa_backward
        q, _, _ = qkv(b=2, s=256, h=4, d=64)
        _, k, v = qkv(b=2, s=512, h=2, d=64, seed=5)
        g = jnp.asarray(np.random.default_rng(7).standard_normal(
            q.shape).astype(np.float32))
        out, lse = fa_forward(q, k, v, causal=causal, interpret=True,
                              return_lse=True)
        dq, dk, dv = fa_backward(q, k, v, out, lse, g, causal=causal,
                                 interpret=True)
        _, vjp = jax.vjp(lambda a, b_, c: _attention_ref(
            a, b_, c, causal=causal), q, k, v)
        rdq, rdk, rdv = vjp(g)
        for got, ref, name in [(dq, rdq, "dq"), (dk, rdk, "dk"),
                               (dv, rdv, "dv")]:
            assert np.allclose(np.asarray(got), np.asarray(ref),
                               atol=3e-3), \
                (name, np.abs(np.asarray(got) - np.asarray(ref)).max())
        assert dk.shape == k.shape and dq.shape == q.shape

    def test_cross_length_sk_lt_sq_fully_masked_rows(self):
        """sq > sk causal: rows i with i + (sk - sq) < 0 attend nothing
        and must produce exactly 0 (the reference nan-guards to 0)."""
        q, _, _ = qkv(b=1, s=512, h=2, d=64)
        _, k, v = qkv(b=1, s=256, h=2, d=64, seed=5)
        out = fa_forward(q, k, v, causal=True, interpret=True)
        ref = _attention_ref(q, k, v, causal=True)
        assert np.allclose(np.asarray(out), np.asarray(ref), atol=2e-4)
        assert np.allclose(np.asarray(out)[0, :256], 0.0)

    def test_cross_length_masked_uneven_blocks(self):
        rng = np.random.default_rng(13)
        q, _, _ = qkv(b=1, s=256, h=2, d=64)
        _, k, v = qkv(b=1, s=512, h=2, d=64, seed=5)
        m = jnp.asarray(rng.standard_normal((1, 1, 256, 512))
                        .astype(np.float32))
        out = fa_forward(q, k, v, causal=True, mask=m, interpret=True,
                         block_q=128, block_k=256)
        ref = _attention_ref(q, k, v, causal=True, mask=m)
        assert np.allclose(np.asarray(out), np.asarray(ref), atol=2e-4)

    def test_cross_length_dispatch_engaged(self, monkeypatch):
        """_shape_reason no longer rejects sq != sk (the round-3
        cross-length fallback is gone)."""
        import paddle_tpu.ops.pallas.flash_attention as fa
        monkeypatch.setattr(fa, "_FORCE_INTERPRET", True)
        fa.reset_dispatch_stats()
        q, _, _ = qkv(b=1, s=256, h=2, d=64)
        _, k, v = qkv(b=1, s=512, h=2, d=64, seed=5)
        out = fa._flash_core_ext(q, k, v, None, None, None, True, None)
        stats = fa.dispatch_stats()
        assert stats["pallas"] == 1 and stats["fallback"] == 0, stats
        ref = _attention_ref(q, k, v, causal=True)
        assert np.allclose(np.asarray(out), np.asarray(ref), atol=2e-4)

    def test_streamed_with_segments_and_mask(self):
        """mask + segments + causal compose in the streamed kernel."""
        from paddle_tpu.ops.pallas.flash_attention import _ref_ext
        rng = np.random.default_rng(17)
        q, k, v = qkv(b=2, s=256, h=2, d=64)
        seg = _seg_ids(2, 256, 3)
        m = jnp.asarray(rng.standard_normal((2, 1, 256, 256))
                        .astype(np.float32))
        out = fa_forward(q, k, v, causal=True, mask=m, q_seg=seg,
                         kv_seg=seg, interpret=True)
        ref = _ref_ext(q, k, v, m, seg, seg, True, None)
        assert np.allclose(np.asarray(out), np.asarray(ref), atol=2e-4)

    def test_streamed_lse_matches_resident_kernel(self):
        """The streamed kernel's lse agrees with the resident-K/V kernel
        (same rows, mask=0 forces the streamed path)."""
        q, k, v = qkv(b=1, s=256, h=2, d=64)
        zero_m = jnp.zeros((1, 1, 256, 256), jnp.float32)
        o1, l1 = fa_forward(q, k, v, causal=True, interpret=True,
                            return_lse=True)
        o2, l2 = fa_forward(q, k, v, causal=True, mask=zero_m,
                            interpret=True, return_lse=True)
        assert np.allclose(np.asarray(o1), np.asarray(o2), atol=1e-5)
        assert np.allclose(np.asarray(l1), np.asarray(l2), atol=1e-5)


class TestFlashMask:
    """Round-4 (SURVEY §5.7c): FlashMask — compact column-bound masks at
    O(Sk) memory, streamed per key block with dead-block skip. Oracle =
    the dense additive mask the bounds describe."""

    def _bounds(self, b, sk, c, seed=0, alive_col0=True):
        rng = np.random.default_rng(seed)
        starts = rng.integers(1, sk, (b, 1, sk, 1)).astype(np.int32)
        if alive_col0:
            starts[:, :, 0, 0] = sk  # keep every causal row alive
        if c == 1:
            return starts
        ends = starts + rng.integers(1, sk // 2, (b, 1, sk, 1))
        return np.concatenate([starts, ends.astype(np.int32)], axis=-1)

    def _dense(self, idx, sq):
        import jax.numpy as jnp
        from paddle_tpu.ops.pallas.flash_attention import (
            _fm_dense_mask, _normalize_startend)
        s, e = _normalize_startend(jnp.asarray(idx), idx.shape[2])
        return _fm_dense_mask(s, e, sq)

    @pytest.mark.parametrize("c", [1, 2])
    def test_forward_parity(self, c):
        import jax.numpy as jnp
        from paddle_tpu.ops.pallas.flash_attention import (
            _normalize_startend)
        q, k, v = qkv(b=2, s=256, h=2, d=64)
        idx = self._bounds(2, 256, c)
        s_, e_ = _normalize_startend(jnp.asarray(idx), 256)
        out = fa_forward(q, k, v, causal=True, interpret=True,
                         fm_start=s_, fm_end=e_)
        ref = _attention_ref(q, k, v, mask=self._dense(idx, 256),
                             causal=True)
        assert np.allclose(np.asarray(out), np.asarray(ref), atol=2e-4)

    def test_backward_parity_band(self):
        import jax
        import jax.numpy as jnp
        from paddle_tpu.ops.pallas._fa_kernel import fa_backward
        from paddle_tpu.ops.pallas.flash_attention import (
            _normalize_startend)
        q, k, v = qkv(b=1, s=256, h=4, d=64)      # GQA q heads
        _, k, v = qkv(b=1, s=256, h=2, d=64, seed=5)
        idx = self._bounds(1, 256, 2, seed=3)
        s_, e_ = _normalize_startend(jnp.asarray(idx), 256)
        g = jnp.asarray(np.random.default_rng(7).standard_normal(
            q.shape).astype(np.float32))
        out, lse = fa_forward(q, k, v, causal=True, interpret=True,
                              return_lse=True, fm_start=s_, fm_end=e_)
        dq, dk, dv = fa_backward(q, k, v, out, lse, g, causal=True,
                                 interpret=True, fm_start=s_, fm_end=e_)
        m = self._dense(idx, 256)
        _, vjp = jax.vjp(lambda a, b_, c_: _attention_ref(
            a, b_, c_, mask=m, causal=True), q, k, v)
        rdq, rdk, rdv = vjp(g)
        for got, ref, name in [(dq, rdq, "dq"), (dk, rdk, "dk"),
                               (dv, rdv, "dv")]:
            assert np.allclose(np.asarray(got), np.asarray(ref),
                               atol=3e-3), \
                (name, np.abs(np.asarray(got) - np.asarray(ref)).max())

    def test_public_api_dispatch_and_grad(self, monkeypatch):
        import paddle_tpu as P
        import paddle_tpu.ops.pallas.flash_attention as fa
        monkeypatch.setattr(fa, "_FORCE_INTERPRET", True)
        fa.reset_dispatch_stats()
        rng = np.random.default_rng(1)
        q = P.to_tensor(rng.standard_normal((1, 256, 2, 64))
                        .astype(np.float32), stop_gradient=False)
        k = P.to_tensor(rng.standard_normal((1, 256, 2, 64))
                        .astype(np.float32), stop_gradient=False)
        v = P.to_tensor(rng.standard_normal((1, 256, 2, 64))
                        .astype(np.float32), stop_gradient=False)
        idx = P.to_tensor(self._bounds(1, 256, 1, seed=2))
        out = P.nn.functional.flashmask_attention(
            q, k, v, startend_row_indices=idx, causal=True)
        stats = fa.dispatch_stats()
        assert stats["pallas"] == 1 and stats["fallback"] == 0, stats
        out.sum().backward()
        for t in (q, k, v):
            assert t.grad is not None
            assert np.isfinite(np.asarray(t.grad._data)).all()

    def test_bidirectional_c4_two_bands(self, monkeypatch):
        """C=4 layout: [LTS, LTE) + [UTS, UTE) bands per column
        (non-causal bidirectional form), fwd + grad parity vs the dense
        two-band oracle."""
        import jax
        import jax.numpy as jnp
        import paddle_tpu as P
        import paddle_tpu.ops.pallas.flash_attention as fa
        monkeypatch.setattr(fa, "_FORCE_INTERPRET", True)
        fa.reset_dispatch_stats()
        rng = np.random.default_rng(21)
        qn, kn, vn = (rng.standard_normal((1, 256, 2, 64))
                      .astype(np.float32) for _ in range(3))
        lts = rng.integers(1, 200, (1, 1, 256, 1))
        lte = lts + rng.integers(1, 40, (1, 1, 256, 1))
        uts = rng.integers(200, 250, (1, 1, 256, 1))
        ute = uts + rng.integers(1, 6, (1, 1, 256, 1))
        idx = np.concatenate([lts, lte, uts, ute], -1).astype(np.int32)
        q = P.to_tensor(qn, stop_gradient=False)
        k = P.to_tensor(kn, stop_gradient=False)
        v = P.to_tensor(vn, stop_gradient=False)
        out = P.nn.functional.flashmask_attention(
            q, k, v, startend_row_indices=P.to_tensor(idx), causal=False)
        stats = fa.dispatch_stats()
        assert stats["pallas"] == 1 and stats["fallback"] == 0, stats
        m = fa._fm_dense_mask(
            jnp.asarray(idx[..., 0]), jnp.asarray(idx[..., 1]), 256,
            jnp.asarray(idx[..., 2]), jnp.asarray(idx[..., 3]))
        ref = fa._attention_ref(jnp.asarray(qn), jnp.asarray(kn),
                                jnp.asarray(vn), mask=m)
        assert np.allclose(np.asarray(out._data), np.asarray(ref),
                           atol=2e-4)
        out.sum().backward()
        _, vjp = jax.vjp(lambda a, b_, c: fa._attention_ref(
            a, b_, c, mask=m), jnp.asarray(qn), jnp.asarray(kn),
            jnp.asarray(vn))
        rd = vjp(jnp.ones_like(out._data))
        for got, refv in zip((q.grad, k.grad, v.grad), rd):
            assert np.allclose(np.asarray(got._data), np.asarray(refv),
                               atol=3e-3)

    def test_sliding_window_via_bounds(self, monkeypatch):
        """window_size=w == dense band mask: row i attends [i-w, i]."""
        import paddle_tpu as P
        import jax.numpy as jnp
        import paddle_tpu.ops.pallas.flash_attention as fa
        monkeypatch.setattr(fa, "_FORCE_INTERPRET", True)
        rng = np.random.default_rng(9)
        qn = rng.standard_normal((1, 256, 2, 64)).astype(np.float32)
        kn = rng.standard_normal((1, 256, 2, 64)).astype(np.float32)
        vn = rng.standard_normal((1, 256, 2, 64)).astype(np.float32)
        w = 17
        out = P.nn.functional.flashmask_attention(
            P.to_tensor(qn), P.to_tensor(kn), P.to_tensor(vn),
            window_size=w, causal=True)
        i = np.arange(256)[:, None]
        j = np.arange(256)[None, :]
        band = (j <= i) & (j >= i - w)
        m = jnp.asarray(np.where(band, 0.0, -np.inf)[None, None]
                        .astype(np.float32))
        ref = _attention_ref(jnp.asarray(qn), jnp.asarray(kn),
                             jnp.asarray(vn), mask=m)
        assert np.allclose(np.asarray(out._data), np.asarray(ref),
                           atol=2e-4)

    def test_sliding_window_cross_length_and_sentinel(self, monkeypatch):
        """Chunked-prefill shape (sq < sk): the window is bottom-right
        aligned (row i ~ absolute position i + sk - sq); window_size=-1
        is the reference 'disabled' sentinel (plain causal)."""
        import paddle_tpu as P
        import jax.numpy as jnp
        import paddle_tpu.ops.pallas.flash_attention as fa
        monkeypatch.setattr(fa, "_FORCE_INTERPRET", True)
        rng = np.random.default_rng(13)
        sq, sk, w = 128, 512, 17
        qn = rng.standard_normal((1, sq, 2, 64)).astype(np.float32)
        kn = rng.standard_normal((1, sk, 2, 64)).astype(np.float32)
        vn = rng.standard_normal((1, sk, 2, 64)).astype(np.float32)
        out = P.nn.functional.flashmask_attention(
            P.to_tensor(qn), P.to_tensor(kn), P.to_tensor(vn),
            window_size=w, causal=True)
        off = sk - sq
        i = np.arange(sq)[:, None] + off      # absolute positions
        j = np.arange(sk)[None, :]
        band = (j <= i) & (j >= i - w)
        m = jnp.asarray(np.where(band, 0.0, -np.inf)[None, None]
                        .astype(np.float32))
        ref = _attention_ref(jnp.asarray(qn), jnp.asarray(kn),
                             jnp.asarray(vn), mask=m)
        assert np.allclose(np.asarray(out._data), np.asarray(ref),
                           atol=2e-4)
        # sentinel: -1 == no window == plain causal
        out2 = P.nn.functional.flashmask_attention(
            P.to_tensor(qn), P.to_tensor(kn), P.to_tensor(vn),
            window_size=(-1, -1), causal=True)
        ref2 = _attention_ref(jnp.asarray(qn), jnp.asarray(kn),
                              jnp.asarray(vn), causal=True)
        assert np.allclose(np.asarray(out2._data), np.asarray(ref2),
                           atol=2e-4)

    def test_window_composes_with_c1_bounds(self, monkeypatch):
        """round 5: window_size + C=1 startend_row_indices folds to the
        column-wise min of LT-starts — matches the dense AND of the two
        masks."""
        import paddle_tpu as P
        import jax.numpy as jnp
        import paddle_tpu.ops.pallas.flash_attention as fa
        monkeypatch.setattr(fa, "_FORCE_INTERPRET", True)
        rng = np.random.default_rng(21)
        s, w = 256, 31
        qn, kn, vn = (rng.standard_normal((1, s, 2, 64))
                      .astype(np.float32) for _ in range(3))
        # document mask: columns 64.. mask rows >= 128 (C=1 LT-start)
        se = np.full((1, 1, s, 1), s, np.int32)
        se[0, 0, 64:, 0] = 128
        out = P.nn.functional.flashmask_attention(
            P.to_tensor(qn), P.to_tensor(kn), P.to_tensor(vn),
            startend_row_indices=P.to_tensor(jnp.asarray(se)),
            window_size=w, causal=True)
        i = np.arange(s)[:, None]
        j = np.arange(s)[None, :]
        keep = (j <= i) & (j >= i - w) & \
            ~((i >= se[0, 0, :, 0][None, :]))
        m = jnp.asarray(np.where(keep, 0.0, -np.inf)[None, None]
                        .astype(np.float32))
        ref = _attention_ref(jnp.asarray(qn), jnp.asarray(kn),
                             jnp.asarray(vn), mask=m)
        assert np.allclose(np.asarray(out._data), np.asarray(ref),
                           atol=2e-4)

    def test_window_composes_with_c2_band(self, monkeypatch):
        """round 5: window_size + C=2 band promotes to the two-band C=4
        form (band 2 = the window's LT region)."""
        import paddle_tpu as P
        import jax.numpy as jnp
        import paddle_tpu.ops.pallas.flash_attention as fa
        monkeypatch.setattr(fa, "_FORCE_INTERPRET", True)
        rng = np.random.default_rng(22)
        s, w = 256, 25
        qn, kn, vn = (rng.standard_normal((1, s, 2, 64))
                      .astype(np.float32) for _ in range(3))
        # band mask: columns 32.. mask rows [96, 160) (C=2)
        se = np.zeros((1, 1, s, 2), np.int32)
        se[..., 0] = s
        se[..., 1] = s
        se[0, 0, 32:, 0] = 96
        se[0, 0, 32:, 1] = 160
        out = P.nn.functional.flashmask_attention(
            P.to_tensor(qn), P.to_tensor(kn), P.to_tensor(vn),
            startend_row_indices=P.to_tensor(jnp.asarray(se)),
            window_size=w, causal=True)
        i = np.arange(s)[:, None]
        j = np.arange(s)[None, :]
        band_dead = (i >= se[0, 0, :, 0][None, :]) & \
            (i < se[0, 0, :, 1][None, :])
        keep = (j <= i) & (j >= i - w) & ~band_dead
        m = jnp.asarray(np.where(keep, 0.0, -np.inf)[None, None]
                        .astype(np.float32))
        ref = _attention_ref(jnp.asarray(qn), jnp.asarray(kn),
                             jnp.asarray(vn), mask=m)
        assert np.allclose(np.asarray(out._data), np.asarray(ref),
                           atol=2e-4)

    def test_fm_lse_kernel_matches_reference(self, monkeypatch):
        """round 5: flash_core_fm_lse's kernel lse == masked logsumexp
        oracle, and grads flow through (out, lse) jointly."""
        import jax
        import jax.numpy as jnp
        import paddle_tpu.ops.pallas.flash_attention as fa
        monkeypatch.setattr(fa, "_FORCE_INTERPRET", True)
        rng = np.random.default_rng(31)
        s = 256
        qn, kn, vn = (jnp.asarray(rng.standard_normal((1, s, 2, 64))
                                  .astype(np.float32)) for _ in range(3))
        se = np.full((1, 1, s, 1), s, np.int32)
        se[0, 0, 64:, 0] = 128
        fm = fa._normalize_startend(jnp.asarray(se), s)
        fm = tuple(fm) + (None,) * (4 - len(fm))
        fa.reset_dispatch_stats()
        out, lse = fa.flash_core_fm_lse(qn, kn, vn, fm[0], fm[1], fm[2],
                                        fm[3], True, None)
        assert fa.dispatch_stats()["pallas"] == 1
        m = fa._fm_causal_mask(fm, s, s, True)
        ref_out, ref_lse = fa._attention_ref_lse(qn, kn, vn,
                                                 causal=False, mask=m)
        assert np.allclose(np.asarray(out), np.asarray(ref_out),
                           atol=2e-4)
        assert np.allclose(np.asarray(lse), np.asarray(ref_lse),
                           atol=2e-4)

        def loss_k(a):
            o, l = fa.flash_core_fm_lse(a, kn, vn, fm[0], fm[1], fm[2],
                                        fm[3], True, None)
            return o.sum() + 0.5 * l.sum()

        def loss_r(a):
            o, l = fa._attention_ref_lse(a, kn, vn, causal=False, mask=m)
            return o.sum() + 0.5 * l.sum()
        gk = jax.grad(loss_k)(qn)
        gr = jax.grad(loss_r)(qn)
        assert np.allclose(np.asarray(gk), np.asarray(gr), atol=3e-3)

    def test_window_with_c4_raises(self):
        import paddle_tpu as P
        import jax.numpy as jnp
        rng = np.random.default_rng(23)
        s = 128
        qn = rng.standard_normal((1, s, 2, 64)).astype(np.float32)
        se = np.zeros((1, 1, s, 4), np.int32)
        se[..., 0] = s
        se[..., 1] = s
        with pytest.raises(NotImplementedError, match="two bands"):
            P.nn.functional.flashmask_attention(
                P.to_tensor(qn), P.to_tensor(qn), P.to_tensor(qn),
                startend_row_indices=P.to_tensor(jnp.asarray(se)),
                window_size=9, causal=True)

    def test_fully_masked_rows_fallback_grads_finite(self):
        """The DENSE fallback (_fm_ref, off-TPU path) must match the
        kernel's fully-masked-row contract: zero output AND zero (not
        NaN) gradients — softmax-of-all--inf NaN'd packed-doc training
        through the fallback until round 4."""
        import jax
        import jax.numpy as jnp
        from paddle_tpu.ops.pallas.flash_attention import _fm_ref
        q, k, v = qkv(b=1, s=128, h=2, d=32)   # head_dim off-kernel
        start = jnp.zeros((1, 1, 128), jnp.int32)   # all rows masked
        end = jnp.full((1, 1, 128), 2 ** 31 - 1, jnp.int32)

        def loss(a, b_, c):
            return (_fm_ref(a, b_, c, start, end, None, None, True,
                            None) ** 2).sum()

        g = jax.grad(loss, argnums=(0, 1, 2))(q, k, v)
        for arr in g:
            assert np.isfinite(np.asarray(arr)).all()
            assert np.allclose(np.asarray(arr), 0.0)

    def test_fully_masked_rows_zero(self):
        """A row masked in every live column outputs exactly 0 (and the
        kernel never NaNs — the dense-oracle vjp would)."""
        import jax.numpy as jnp
        q, k, v = qkv(b=1, s=256, h=2, d=64)
        s_ = jnp.zeros((1, 1, 256), jnp.int32)       # all rows masked
        e_ = jnp.full((1, 1, 256), 2 ** 31 - 1, jnp.int32)
        out = fa_forward(q, k, v, causal=True, interpret=True,
                         fm_start=s_, fm_end=e_)
        assert np.allclose(np.asarray(out), 0.0)


def _tile_case(variant, dtype, seed=11):
    """(q, k, v, g, kernel kwargs, reference mask) of one variant of the
    tile tests: [1, S, 4 | 1, 64] at S = 1024 (two 512 tiles a side)."""
    from paddle_tpu.ops.pallas.flash_attention import (
        _fm_dense_mask, _seg_additive_mask)
    s, h, d = 1024, 4, 64
    sq = 512 if variant == "cross_length" else s
    hkv = 1 if variant in ("gqa", "cross_length") else h
    rng = np.random.default_rng(seed)

    def rand(*shape):
        return jnp.asarray(rng.standard_normal(shape).astype(np.float32),
                           dtype)
    q, g = rand(1, sq, h, d), rand(1, sq, h, d)
    k, v = rand(1, s, hkv, d), rand(1, s, hkv, d)
    kw, mask = {}, None
    if variant == "window":          # 300 keys before self: it binds
        start = (jnp.arange(s, dtype=jnp.int32) + 301)[None, None]
        end = jnp.full_like(start, jnp.iinfo(jnp.int32).max)
        kw = dict(fm_start=start, fm_end=end)
        mask = _fm_dense_mask(start, end, sq)
    elif variant == "segments":
        seg = jnp.asarray(np.searchsorted([300, 700], np.arange(s),
                                          side="right")[None], jnp.int32)
        kw = dict(q_seg=seg, kv_seg=seg)
        mask = _seg_additive_mask(seg, seg)
    return q, k, v, g, kw, mask


def _fwd_bwd(q, k, v, g, kw, tile):
    from paddle_tpu.ops.pallas._fa_kernel import fa_backward
    out, lse = fa_forward(q, k, v, causal=True, interpret=True,
                          return_lse=True, block_q=tile, block_k=tile,
                          **kw)
    grads = fa_backward(q, k, v, out, lse, g, causal=True, interpret=True,
                        block_q=tile, block_k=tile, **kw)
    return (out, *grads)


_VARIANTS = ["causal", "gqa", "window", "segments", "cross_length"]


class TestStoredDtypeTiles:
    """The kernels feed the MXU the dtype they are given, in tiles of
    128 / 256 / 512: forward, dq and dk/dv against the XLA reference."""

    @pytest.mark.parametrize("tile", [128, 256, 512])
    @pytest.mark.parametrize("variant", _VARIANTS)
    def test_bf16_matches_reference(self, variant, tile):
        import jax
        q, k, v, g, kw, mask = _tile_case(variant, jnp.bfloat16)
        got = _fwd_bwd(q, k, v, g, kw, tile)
        assert [x.dtype for x in got] == [jnp.bfloat16] * 4
        f32 = [x.astype(jnp.float32) for x in (q, k, v, g)]
        want, vjp = jax.vjp(lambda a, b_, c: _attention_ref(
            a, b_, c, mask=mask, causal=True), *f32[:3])
        for name, a, b_ in zip(("out", "dq", "dk", "dv"), got,
                               (want, *vjp(f32[3]))):
            b_ = np.asarray(b_)
            err = np.abs(np.asarray(a, np.float32) - b_).max()
            # bf16 keeps 8 bits (eps 3.9e-3): the stored result and the
            # p / ds operands each round once; read 3.3-4.6e-3 of the scale
            assert err < 8e-3 * np.abs(b_).max(), (name, err)

    @pytest.mark.parametrize("variant", _VARIANTS)
    def test_tile_size_leaves_f32_results_alone(self, variant):
        q, k, v, g, kw, _ = _tile_case(variant, jnp.float32)
        small = _fwd_bwd(q, k, v, g, kw, 128)
        large = _fwd_bwd(q, k, v, g, kw, 512)
        for name, a, b_ in zip(("out", "dq", "dk", "dv"), small, large):
            np.testing.assert_allclose(np.asarray(a), np.asarray(b_),
                                       atol=2e-5, rtol=2e-5, err_msg=name)

    @pytest.mark.parametrize("seq,tile", [(4096, 512), (768, 256),
                                          (640, 128), (64, 64)])
    def test_default_tile_follows_the_length(self, seq, tile, monkeypatch):
        from paddle_tpu.ops.pallas import _fa_kernel
        for name in ("PADDLE_TPU_FA_BLOCK_Q", "PADDLE_TPU_FA_BWD_BLOCK_K"):
            monkeypatch.delenv(name, raising=False)
            assert _fa_kernel._env_block(name, seq) == tile
        monkeypatch.setenv("PADDLE_TPU_FA_BLOCK_Q", "128")
        assert _fa_kernel._env_block("PADDLE_TPU_FA_BLOCK_Q", seq) == 128
