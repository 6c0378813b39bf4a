"""Mistral-style sliding-window attention in the LLaMA family:
teacher-forced parity vs a dense banded-mask oracle (same transplanted
weights through the plain XLA path), window proven load-bearing, and
the cached greedy decode matching a full-context banded rollout
token-for-token."""
import numpy as np
import pytest

import paddle_tpu as P
from paddle_tpu.models import LlamaConfig, LlamaForCausalLM

W = 8


def _band(s, w=W):
    qp = np.arange(s)[:, None]
    kp = np.arange(s)[None, :]
    return np.where((kp <= qp) & (kp > qp - w), 0.0,
                    -1e9).astype(np.float32)


class TestSlidingWindow:
    @pytest.fixture(scope="class")
    def pair(self):
        P.seed(0)
        m = LlamaForCausalLM(LlamaConfig.tiny(
            sliding_window=W, num_key_value_heads=2))
        m.eval()
        oracle = LlamaForCausalLM(LlamaConfig.tiny(
            num_key_value_heads=2, use_flash_attention=False))
        oracle.set_state_dict(m.state_dict())
        oracle.eval()
        return m, oracle

    def test_teacher_forced_matches_banded_oracle(self, pair):
        m, oracle = pair
        ids = P.to_tensor(np.random.default_rng(0).integers(
            0, 256, (2, 32)).astype(np.int32))
        got = np.asarray(m(ids)._data)
        ref = np.asarray(oracle(
            ids, attn_mask=P.to_tensor(_band(32)[None, None]))._data)
        np.testing.assert_allclose(got, ref, atol=3e-4, rtol=1e-3)
        # load-bearing: the full-causal oracle differs
        full = np.asarray(oracle(ids)._data)
        assert np.abs(full - ref).max() > 1e-3
        # the XLA debug path (use_flash_attention=False) builds its own
        # dense band — must agree with the same oracle
        dense = LlamaForCausalLM(LlamaConfig.tiny(
            sliding_window=W, num_key_value_heads=2,
            use_flash_attention=False))
        dense.set_state_dict(m.state_dict())
        dense.eval()
        got2 = np.asarray(dense(ids)._data)
        np.testing.assert_allclose(got2, ref, atol=3e-4, rtol=1e-3)

    def test_cached_decode_matches_banded_rollout(self, pair):
        m, oracle = pair
        rng = np.random.default_rng(1)
        prompt = rng.integers(0, 256, (2, 16)).astype(np.int32)
        out = np.asarray(m.generate(P.to_tensor(prompt),
                                    max_new_tokens=8)._data)
        # the full-context rollout, at ONE padded length (8 growing
        # lengths were 8 sets of per-op compiles): the band is causal, so
        # the zero tail is invisible to row s-1, whose logits pick token s
        cur = np.concatenate([prompt, np.zeros((2, 8), np.int32)], axis=1)
        mask = P.to_tensor(_band(24)[None, None])
        for s in range(16, 24):
            lg = np.asarray(oracle(P.to_tensor(cur), attn_mask=mask)._data)
            cur[:, s] = lg[:, s - 1].argmax(-1)
        np.testing.assert_array_equal(out, cur[:, 16:])

    def test_mistral_preset(self):
        # v0.1 pairing: theta 1e4 WITH the window (v0.2/v0.3 disable
        # the window and move theta — callers override)
        cfg = LlamaConfig.mistral_7b()
        assert cfg.sliding_window == 4096
        assert cfg.num_key_value_heads == 8
        assert cfg.rope_theta == 10000.0

    def test_window_composes_with_flashmask_bounds(self, pair):
        """sliding_window + attn_mask_startend_row_indices: the window
        folds into the FlashMask column bounds (not silently dropped —
        output must differ from the windowless packed run)."""
        m, oracle = pair
        ids = P.to_tensor(np.random.default_rng(2).integers(
            0, 256, (1, 32)).astype(np.int32))
        # one packed boundary at 20: rows >= 20 can't see cols < 20
        start = np.full((1, 1, 32, 1), 32, np.int32)
        start[0, 0, :20, 0] = 20
        st = P.to_tensor(start)
        win = np.asarray(m(ids, attn_mask_startend_row_indices=st)._data)
        nowin = np.asarray(oracle(
            ids, attn_mask_startend_row_indices=st)._data)
        assert np.abs(win - nowin).max() > 1e-3
        # oracle: dense mask = causal AND band AND segment-block
        qp = np.arange(32)[:, None]
        kp = np.arange(32)[None, :]
        seg_ok = ~((qp >= 20) & (kp < 20))
        dense = np.where((kp <= qp) & (kp > qp - W) & seg_ok, 0.0,
                         -1e9).astype(np.float32)
        ref = np.asarray(oracle(
            ids, attn_mask=P.to_tensor(dense[None, None]))._data)
        np.testing.assert_allclose(win, ref, atol=3e-4, rtol=1e-3)

    def test_loud_guards(self, pair):
        m, _ = pair
        ids = P.to_tensor(np.zeros((1, 8), np.int32))
        dense = P.to_tensor(np.zeros((1, 1, 8, 8), np.float32))
        with pytest.raises(NotImplementedError, match="dense"):
            m(ids, attn_mask=dense)


class TestWindowAgainstLength:
    """The dispatch reads the window against the static lengths: one
    that no row can reach the edge of is the plain causal call (the
    resident kernel, no FlashMask bounds), one row short of that it
    binds and takes the FlashMask kernels. Kernels interpreted."""

    S = 128

    @pytest.fixture
    def models(self, monkeypatch):
        import paddle_tpu.ops.pallas.flash_attention as fa
        monkeypatch.setattr(fa, "_FORCE_INTERPRET", True)
        kw = dict(hidden_size=256, num_key_value_heads=2,
                  num_hidden_layers=1)

        def build(**more):
            P.seed(0)
            m = LlamaForCausalLM(LlamaConfig.tiny(**kw, **more))
            m.eval()
            return m
        ids = P.to_tensor(np.random.default_rng(5).integers(
            0, 256, (1, self.S)).astype(np.int32))
        return fa, build, ids

    def test_window_of_the_length_is_the_causal_call(self, models):
        fa, build, ids = models
        causal = build()
        fa.reset_dispatch_stats()
        want = np.asarray(causal(ids)._data)
        assert fa.dispatch_stats()["window_as_causal"] == 0
        windowed = build(sliding_window=self.S)   # window_size = s - 1
        windowed.set_state_dict(causal.state_dict())
        fa.reset_dispatch_stats()
        got = np.asarray(windowed(ids)._data)
        stats = fa.dispatch_stats()
        assert stats["window_as_causal"] == 1, stats
        assert stats["streamed"] == 0 and stats["resident"] == 1, stats
        assert stats["fallback"] == 0, stats
        np.testing.assert_array_equal(got, want)

    def test_window_one_short_of_the_length_binds(self, models):
        fa, build, ids = models
        windowed = build(sliding_window=self.S - 1)  # window_size = s - 2
        fa.reset_dispatch_stats()
        got = np.asarray(windowed(ids)._data)
        stats = fa.dispatch_stats()
        assert stats["window_as_causal"] == 0, stats
        assert stats["streamed"] == 1 and stats["resident"] == 0, stats
        oracle = build(use_flash_attention=False)
        oracle.set_state_dict(windowed.state_dict())
        ref = np.asarray(oracle(ids, attn_mask=P.to_tensor(
            _band(self.S, self.S - 1)[None, None]))._data)
        np.testing.assert_allclose(got, ref, atol=3e-4, rtol=1e-3)
        # the one masked link (row s-1, column 0) is load-bearing
        full = np.asarray(oracle(ids)._data)
        assert np.abs(full[:, -1] - ref[:, -1]).max() > 1e-6
