"""The serving step's hand-off to its compiled program: the weights'
tensors are found once a model (``weight_list_builds``) and read each
dispatch, so ``set_weights`` takes effect on the next step; the packed
host arrays reach the call as the numpy arrays they are, with the dtypes
that keep the step's two program classes; and the call, which may read
those arrays until its program has run, is waited for before the next
pack writes over them. Tiny models on the CPU: a Llama (13 host arrays a
step) and the SambaY hybrid of ``test_sambay.py`` (17: the lanes' slots
and window tables besides)."""
import numpy as np
import pytest

import jax
import jax.numpy as jnp

import paddle_tpu as P
from paddle_tpu.serving import ServingEngine
from serving_utils import serve_streams
from test_serving_ragged import ENG_KW, tiny_model


def _hand_off_case(kind, seed=7):
    """``(model, engine kwargs, prompts)`` of a tiny engine whose step
    takes 13 host arrays (``dense``) or 17 (``mixed``: the lanes' slots
    and window tables besides)."""
    if kind == "dense":
        rng = np.random.default_rng(seed)
        return (tiny_model(seed=seed), ENG_KW,
                [rng.integers(0, 97, n).astype(np.int32)
                 for n in (13, 4, 9)])
    import test_sambay
    model, _ = test_sambay.build(seed=seed)
    return (model, test_sambay.ENGINE,
            [test_sambay.prompt(n, seed=seed) for n in (13, 4, 9)])


def _steady(eng, prompts, max_new=6):
    """Serve ``prompts`` to their end: a chunk-carrying step at first,
    decode-only steps after; returns the number of steps."""
    for p in prompts:
        eng.add_request(p, max_new_tokens=max_new)
    steps = 0
    while not eng.scheduler.all_done():
        eng.step()
        steps += 1
    return steps


def _spy_dispatch(monkeypatch, eng):
    """Count, inside ``_run_ragged_step`` alone, every call of the names
    a Python transfer goes through, and the token capacity dispatched."""
    seen = {"transfers": [], "capacities": []}
    inside = []

    def counting(fn, name):
        def spy(*a, **k):
            if inside:
                seen["transfers"].append(name)
            return fn(*a, **k)
        return spy

    for mod, name in ((jnp, "asarray"), (jnp, "array"),
                      (jax, "device_put")):
        monkeypatch.setattr(mod, name, counting(getattr(mod, name),
                                                name))
    run = eng._run_ragged_step

    def dispatch(ids, *a):
        seen["capacities"].append(ids.shape[1])
        inside.append(1)
        try:
            return run(ids, *a)
        finally:
            inside.pop()

    monkeypatch.setattr(eng, "_run_ragged_step", dispatch)
    return seen


@pytest.fixture(scope="class", params=["dense", "mixed"])
def warm(request):
    """``(model, engine, prompts)``: an engine of ``_hand_off_case``
    that has compiled both of its step classes."""
    model, ekw, prompts = _hand_off_case(request.param)
    eng = ServingEngine(model, **ekw)
    _steady(eng, prompts)
    return model, eng, prompts


class TestTheHandOff:
    def test_the_weight_list_is_walked_once(self, monkeypatch, warm):
        """The module tree is walked for the weights' tensors at the
        first dispatch alone: over 20 and more steady steps
        ``_gen_state_tensors`` is not called and ``weight_list_builds``
        stays 1 while ``step_dispatches`` grows."""
        model, eng, prompts = warm
        mt = eng.metrics
        assert mt.weight_list_builds.value == 1
        walked = []
        walk = model._gen_state_tensors
        monkeypatch.setattr(model, "_gen_state_tensors",
                            lambda: walked.append(1) or walk())
        d0 = mt.step_dispatches.value
        steps = _steady(eng, prompts, max_new=20)
        assert steps >= 20
        assert walked == []
        assert mt.weight_list_builds.value == 1
        assert mt.step_dispatches.value - d0 == steps

    def test_the_dispatch_transfers_nothing_in_python(self, monkeypatch,
                                                      warm):
        """In a steady step the packed host arrays reach the compiled
        call as numpy arrays: no ``jnp.asarray``, ``jnp.array`` or
        ``jax.device_put`` inside ``_run_ragged_step``, in a decode-only
        step or a chunk-carrying one."""
        _, eng, prompts = warm
        seen = _spy_dispatch(monkeypatch, eng)
        _steady(eng, prompts)
        assert set(seen["capacities"]) == {eng._ragged_tok_small,
                                           eng._ragged_tok_mixed}
        assert seen["transfers"] == []

    def test_numpy_operands_keep_two_program_classes(self, warm):
        """The arrays keep the dtypes ``jnp.asarray`` gave them (int32,
        bool, float32; none weakly typed), so the step's jit cache holds
        two programs after decode-only and chunk-carrying steps, and
        ``step_program_classes`` reads 2."""
        _, eng, prompts = warm
        _steady(eng, prompts[::-1], max_new=9)
        assert eng.metrics.step_program_classes.value == 2
        assert eng._step_program()._cache_size() == 2

    def test_a_step_that_raised_after_its_dispatch_is_waited_for(
            self, monkeypatch):
        """The call reads the packed arrays as they stand: where a step
        raises between its dispatch and its fetch, the next pack waits
        for that step's program before it writes over them, and the
        retried run serves ``generate()``'s stream."""
        m = tiny_model(seed=9)
        prompt = np.random.default_rng(9).integers(0, 97, 9).astype(
            np.int32)
        eng = ServingEngine(m, **ENG_KW)
        eng.add_request(prompt, max_new_tokens=8)
        raised = []
        host = eng._host_sampling

        def raise_once():
            if not raised:
                raised.append(1)
                raise RuntimeError("after the dispatch")
            return host()

        monkeypatch.setattr(eng, "_host_sampling", raise_once)
        with pytest.raises(RuntimeError, match="after the dispatch"):
            eng.run()
        pending = eng._ragged_tokens
        assert pending is not None          # dispatched, never fetched
        waited = []

        class Spy:
            def block_until_ready(self):
                waited.append(1)
                return pending.block_until_ready()

        eng._ragged_tokens = Spy()
        res = eng.run()
        assert waited == [1]
        (rid,) = res
        want = np.asarray(m.generate(P.to_tensor(prompt[None]),
                                     max_new_tokens=8)._data)[0]
        np.testing.assert_array_equal(res[rid]["tokens"], want)

    def test_a_weight_swap_between_steps_on_a_mixed_cache(self):
        """``set_weights`` between two steps of a mixed-cache engine: the
        streams after it are those of an engine that always held the new
        weights, and the weight list is not walked again."""
        model, ekw, prompts = _hand_off_case("mixed")
        eng = ServingEngine(model, **ekw)
        kws = [dict(), dict(do_sample=True, temperature=0.9, seed=3),
               dict()]
        old = serve_streams(eng, prompts, kws, 6)
        new_model, _, _ = _hand_off_case("mixed", seed=8)
        arrays = [np.asarray(t._data)
                  for t in new_model._gen_state_tensors()]
        eng.set_weights("target", arrays, version=2)
        got = serve_streams(eng, prompts, kws, 6)
        want = serve_streams(ServingEngine(new_model, **ekw), prompts,
                             kws, 6)
        assert got == want
        assert got != old
        assert eng.metrics.weight_list_builds.value == 1
