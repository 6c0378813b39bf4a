"""The plain reference against ``LlamaForCausalLM`` on the CPU at a tiny
grouped-query, windowed size in float32 -- and failing when the model side
is cast to bfloat16 -- and the controls' rounding."""
import jax.numpy as jnp
import numpy as np
import pytest

from benchmark.drivers.serve import build_model
from benchmark.harness import reference

CFG = dict(vocab_size=320, hidden_size=128, intermediate_size=256,
           num_hidden_layers=2, num_attention_heads=4, num_key_value_heads=2,
           max_position_embeddings=256, rms_norm_eps=1e-5, rope_theta=1e4,
           sliding_window=24, tie_word_embeddings=False,
           torch_dtype="float32", initializer_range=0.02,
           program={"use_flash_attention": False})
IDS = np.random.default_rng(5).integers(0, 320, (1, 64)).astype(np.int32)


def model_logits(dtype):
    import paddle_tpu as P
    model, w = build_model(dict(CFG), seed=11)
    model.eval()
    if dtype != "float32":
        model.to(dtype=dtype)
    out = model(P.to_tensor(IDS))
    out = out[0] if isinstance(out, (list, tuple)) else out
    return np.asarray(out._data.astype(jnp.float32))[0], w


def ref_logits(w, **kw):
    return np.asarray(reference.logits_at(w, CFG, IDS, np.arange(64),
                                          block=16, **kw))


def test_reference_agrees_with_the_model_in_float32():
    got, w = model_logits("float32")
    want = ref_logits(w)
    assert want.std() > 0.1
    assert np.abs(got - want).max() < 2e-4


def test_the_window_and_the_key_groups_matter():
    _, w = model_logits("float32")
    full = np.asarray(reference.logits_at(
        w, dict(CFG, sliding_window=None), IDS, np.arange(64), block=16))
    windowed = ref_logits(w)
    assert np.abs(full[:24] - windowed[:24]).max() < 1e-5   # not yet bound
    assert np.abs(full[40:] - windowed[40:]).max() > 1e-3
    one_block = np.asarray(reference.logits_at(w, CFG, IDS, np.arange(64),
                                               block=64))
    assert np.abs(one_block - windowed).max() < 1e-5


def test_a_bfloat16_model_fails_the_float32_comparison():
    got, w = model_logits("bfloat16")
    err = np.abs(got - ref_logits(w)).max()
    assert err > 2e-3                 # ten times the float32 tolerance


@pytest.mark.parametrize("prec", ["int8", "fp8"])
def test_the_controls_are_coarser_than_the_reference(prec):
    _, w = model_logits("float32")
    err = np.abs(ref_logits(w, prec=prec) - ref_logits(w)).max()
    assert 1e-3 < err < 1.0


def test_rounding_to_eight_bits():
    x = jnp.asarray([[0.0, 1.0, -127.0, 63.4, 0.26]])
    q = np.asarray(reference.fake_int8(x, -1))[0]
    assert q.tolist() == [0.0, 1.0, -127.0, 63.0, 0.0]
    y = jnp.asarray([[448.0, 100.0, 17.0, 1.0, 0.001]])
    f = np.asarray(reference.fake_fp8(y, -1))[0]
    # four significant bits: 100 -> 96 or 104, 17 -> 16 or 18
    assert f[0] == 448.0 and f[1] in (96.0, 104.0) and f[2] in (16.0, 18.0)
    assert f[3] == 1.0 and abs(f[4] - 0.001) <= 2.0 ** -10
