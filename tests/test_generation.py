"""Static-KV-cache generation: parity with full-context recompute and
sampling-machinery checks."""
import numpy as np
import pytest

import jax
import jax.numpy as jnp

import paddle_tpu as P
from paddle_tpu.models import LlamaConfig, LlamaForCausalLM


def tiny_model(seed=0, **kw):
    P.seed(seed)
    cfg = LlamaConfig(vocab_size=97, hidden_size=32, intermediate_size=64,
                      num_hidden_layers=2, num_attention_heads=4,
                      max_position_embeddings=64, **kw)
    m = LlamaForCausalLM(cfg)
    m.eval()
    return m


def _full_context_rollout(m, ids, n):
    """Greedy tokens by re-running the whole prefix every step — at ONE
    padded length (n growing lengths were n sets of per-op compiles):
    the model is causal, so the zero tail is invisible to row s-1, whose
    logits pick token s."""
    b, p = ids.shape
    cur = np.concatenate([ids, np.zeros((b, n), np.int32)], axis=1)
    for s in range(p, p + n):
        logits = np.asarray(m(P.to_tensor(cur))._data)
        cur[:, s] = logits[:, s - 1].argmax(-1)
    return cur[:, p:]


class TestGenerate:
    def test_greedy_matches_full_context_recompute(self):
        """The cached decode must produce the same tokens as the naive
        'rerun the whole prefix every step' oracle."""
        m = tiny_model()
        rng = np.random.default_rng(0)
        ids = rng.integers(0, 97, (2, 5)).astype(np.int32)

        got = np.asarray(m.generate(P.to_tensor(ids),
                                    max_new_tokens=6)._data)

        # oracle: full forward each step, argmax of the last real logits
        np.testing.assert_array_equal(got, _full_context_rollout(m, ids, 6))

    def test_gqa_cached_decode(self):
        m = tiny_model(num_key_value_heads=2)
        ids = np.random.default_rng(1).integers(0, 97, (1, 4)).astype(
            np.int32)
        got = np.asarray(m.generate(P.to_tensor(ids),
                                    max_new_tokens=4)._data)
        np.testing.assert_array_equal(got, _full_context_rollout(m, ids, 4))

    def test_eos_freezes_row(self):
        m = tiny_model()
        ids = np.random.default_rng(2).integers(0, 97, (1, 3)).astype(
            np.int32)
        # pick the first greedily generated token as the "eos" so the row
        # finishes immediately and must keep emitting it
        first = np.asarray(m.generate(P.to_tensor(ids),
                                      max_new_tokens=1)._data)[0, 0]
        out = np.asarray(m.generate(P.to_tensor(ids), max_new_tokens=5,
                                    eos_token_id=int(first))._data)
        assert (out == first).all()

    def test_sampling_shapes_and_determinism(self):
        m = tiny_model()
        ids = np.zeros((2, 3), np.int32)
        a = np.asarray(m.generate(P.to_tensor(ids), max_new_tokens=4,
                                  do_sample=True, temperature=0.8,
                                  top_k=10, top_p=0.9, seed=7)._data)
        b = np.asarray(m.generate(P.to_tensor(ids), max_new_tokens=4,
                                  do_sample=True, temperature=0.8,
                                  top_k=10, top_p=0.9, seed=7)._data)
        assert a.shape == (2, 4)
        np.testing.assert_array_equal(a, b)  # same seed -> same tokens
        assert (a >= 0).all() and (a < 97).all()

    def test_topk1_sampling_equals_greedy(self):
        m = tiny_model()
        ids = np.random.default_rng(3).integers(0, 97, (2, 4)).astype(
            np.int32)
        greedy = np.asarray(m.generate(P.to_tensor(ids),
                                       max_new_tokens=3)._data)
        topk1 = np.asarray(m.generate(P.to_tensor(ids), max_new_tokens=3,
                                      do_sample=True, top_k=1,
                                      seed=0)._data)
        np.testing.assert_array_equal(greedy, topk1)


class TestGPTGenerate:
    def test_gpt_greedy_matches_full_context(self):
        from paddle_tpu.models import GPTConfig, GPTForCausalLM
        P.seed(0)
        cfg = GPTConfig(vocab_size=83, hidden_size=32,
                        intermediate_size=64, num_hidden_layers=2,
                        num_attention_heads=4,
                        max_position_embeddings=32,
                        hidden_dropout_prob=0.0,
                        attention_dropout_prob=0.0)
        m = GPTForCausalLM(cfg)
        m.eval()
        ids = np.random.default_rng(0).integers(0, 83, (2, 4)).astype(
            np.int32)
        got = np.asarray(m.generate(P.to_tensor(ids),
                                    max_new_tokens=5)._data)
        np.testing.assert_array_equal(got, _full_context_rollout(m, ids, 5))


class TestGenerateCacheInvalidation:
    def test_weight_update_invalidates_program(self):
        m = tiny_model(seed=5)
        ids = np.zeros((1, 3), np.int32)
        a = np.asarray(m.generate(P.to_tensor(ids), max_new_tokens=3)._data)
        # mutate a weight: cached program must NOT serve stale constants
        w = m.lm_head.weight
        w._inplace_update(w._data + 1.0)
        b = np.asarray(m.generate(P.to_tensor(ids), max_new_tokens=3)._data)
        # recompute oracle with the new weights
        cur = ids.copy()
        for i in range(3):
            logits = np.asarray(m(P.to_tensor(cur))._data)
            nxt = logits[:, -1].argmax(-1).astype(np.int32)
            assert b[0, i] == nxt[0], (i, a, b)
            cur = np.concatenate([cur, nxt[:, None]], axis=1)

    def test_generate_in_train_mode_uses_eval_semantics(self):
        m = tiny_model(seed=6)
        ids = np.zeros((1, 3), np.int32)
        ref = np.asarray(m.generate(P.to_tensor(ids), max_new_tokens=3)._data)
        m.train()
        got = np.asarray(m.generate(P.to_tensor(ids), max_new_tokens=3)._data)
        np.testing.assert_array_equal(got, ref)
        assert m.training  # restored


class TestGenerateGuards:
    def test_context_overflow_raises(self):
        m = tiny_model()  # max_position_embeddings=64
        ids = np.zeros((1, 60), np.int32)
        with pytest.raises(ValueError, match="max_position_embeddings"):
            m.generate(P.to_tensor(ids), max_new_tokens=10)

    def test_param_replacement_invalidates(self):
        from paddle_tpu.core.tensor import Parameter
        import jax.numpy as jnp
        m = tiny_model(seed=9)
        ids = np.zeros((1, 3), np.int32)
        m.generate(P.to_tensor(ids), max_new_tokens=2)
        # wholesale Parameter swap (LoRA/quant style), not inplace_update
        m.lm_head.weight = Parameter(
            jnp.asarray(np.random.default_rng(1).standard_normal(
                m.lm_head.weight.shape).astype(np.float32)))
        got = np.asarray(m.generate(P.to_tensor(ids),
                                    max_new_tokens=2)._data)
        cur = ids.copy()
        for i in range(2):
            logits = np.asarray(m(P.to_tensor(cur))._data)
            nxt = logits[:, -1].argmax(-1).astype(np.int32)
            assert got[0, i] == nxt[0], i
            cur = np.concatenate([cur, nxt[:, None]], axis=1)


class TestBeamSearch:
    """num_beams>1: jitted beam search vs a numpy full-context oracle."""

    def _oracle_beam(self, m, ids, max_new, K, eos=-1):
        """Reference beam search recomputing the full context each step."""
        b = ids.shape[0]
        outs = []
        for bi in range(b):
            beams = [(list(ids[bi]), 0.0, False)]
            # first expansion from the prompt
            first = True
            for step in range(max_new):
                cand = []
                for seq, score, fin in beams:
                    if fin:
                        cand.append((seq + [eos], score, True))
                        continue
                    lg = m(P.to_tensor(np.asarray([seq], np.int32)))
                    lp = np.asarray(
                        jax.nn.log_softmax(lg._data[0, -1].astype(
                            jnp.float32)))
                    for v in np.argsort(lp)[::-1][:K]:
                        cand.append((seq + [int(v)], score + lp[v],
                                     int(v) == eos))
                cand.sort(key=lambda t: -t[1])
                beams = cand[:K] if not first else cand[:K]
                first = False
            best = max(beams, key=lambda t: t[1])
            outs.append(best[0][ids.shape[1]:])
        return np.asarray(outs, np.int32)

    def test_beam_matches_oracle(self):
        m = tiny_model(seed=3)
        rng = np.random.default_rng(1)
        ids = rng.integers(0, 97, (2, 4)).astype(np.int32)
        got = m.generate(P.to_tensor(ids), max_new_tokens=3,
                         num_beams=3).numpy()
        ref = self._oracle_beam(m, ids, 3, 3)
        np.testing.assert_array_equal(got, ref)

    def test_beam1_equals_greedy(self):
        m = tiny_model(seed=4)
        rng = np.random.default_rng(2)
        ids = rng.integers(0, 97, (2, 4)).astype(np.int32)
        greedy = m.generate(P.to_tensor(ids), max_new_tokens=4).numpy()
        beam1 = m.generate(P.to_tensor(ids), max_new_tokens=4,
                           num_beams=1).numpy()
        np.testing.assert_array_equal(greedy, beam1)

    def test_beam_sampling_raises(self):
        m = tiny_model(seed=5)
        ids = np.zeros((1, 3), np.int32)
        with pytest.raises(NotImplementedError):
            m.generate(P.to_tensor(ids), max_new_tokens=2, num_beams=2,
                       do_sample=True)

    def test_eos_beam_freezes_score(self):
        m = tiny_model(seed=6)
        rng = np.random.default_rng(3)
        ids = rng.integers(0, 97, (1, 4)).astype(np.int32)
        out = m.generate(P.to_tensor(ids), max_new_tokens=5, num_beams=2,
                         eos_token_id=7).numpy()
        # after an eos, the winning beam emits only eos
        row = out[0]
        if 7 in row:
            i = list(row).index(7)
            assert all(t == 7 for t in row[i:]), row


class TestGenerateRepetitionControls:
    """repetition_penalty + min_new_tokens in the compiled decode loop
    (reference generate() kwargs)."""

    def _model(self):
        P.seed(0)
        return LlamaForCausalLM(LlamaConfig.tiny())

    def test_min_new_tokens_bans_early_eos(self):
        m = self._model()
        prompt = P.to_tensor(np.asarray([[1, 2, 3, 4]], np.int32))
        base = m.generate(prompt, max_new_tokens=6, do_sample=False)
        base = (base[0] if isinstance(base, (tuple, list))
                else base).numpy()[0]
        first = int(base[0])
        # eos == the first greedy token: without min_new everything is
        # eos immediately; with min_new=3 the first 3 differ from eos
        out = m.generate(prompt, max_new_tokens=6, do_sample=False,
                         eos_token_id=first)
        out = (out[0] if isinstance(out, (tuple, list))
               else out).numpy()[0]
        assert (out == first).all()
        out3 = m.generate(prompt, max_new_tokens=6, do_sample=False,
                          eos_token_id=first, min_new_tokens=3)
        out3 = (out3[0] if isinstance(out3, (tuple, list))
                else out3).numpy()[0]
        assert (out3[:3] != first).all()

    def test_repetition_penalty_reduces_repeats(self):
        m = self._model()
        prompt = P.to_tensor(np.asarray([[5, 6, 7, 8]], np.int32))

        def distinct(rp):
            o = m.generate(prompt, max_new_tokens=12, do_sample=False,
                           repetition_penalty=rp)
            o = (o[0] if isinstance(o, (tuple, list)) else o).numpy()[0]
            return o, len(set(o.tolist()))

        o1, d1 = distinct(1.0)
        o5, d5 = distinct(50.0)
        assert d5 >= d1
        assert not np.array_equal(o1, o5)
        # an extreme penalty forbids immediate re-emission entirely
        assert all(a != b for a, b in zip(o5[:-1], o5[1:])) or d5 == 12

    def test_guards(self):
        m = self._model()
        prompt = P.to_tensor(np.asarray([[1, 2]], np.int32))
        with pytest.raises(ValueError):
            m.generate(prompt, repetition_penalty=0.0)
        with pytest.raises(ValueError):
            m.generate(prompt, max_new_tokens=2, min_new_tokens=5)
        with pytest.raises(NotImplementedError):
            m.generate(prompt, num_beams=2, repetition_penalty=2.0)
