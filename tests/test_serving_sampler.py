"""The sampler does the work whose result is consumed (PR 28).

``fused_sample`` sorts under a condition on the batch's own sampling
arguments, and once. Pinned here: (a) the values, bit for bit against a
frozen copy of the two-sort formula the sampler had, alone and through
the engine; (b) the step's program holds no ``sort`` outside a
``conditional``; (c) the host-side counter of the steps that sort;
(d) the readers of the step's logits (a fork at prefill completion,
the host-sampling oracle) beside a chunk in the step.
"""
import functools

import numpy as np
import pytest

import jax
import jax.numpy as jnp

from paddle_tpu.serving import ServingEngine, sampling
from paddle_tpu.serving.sampling import _lane_keys, fused_sample

from serving_utils import hlo_sorts, ragged_step_avals, served_alone
from test_serving_ragged import run_fleet, tiny_model


# ---------------------------------------------------------------------------
# (a) the frozen two-sort formula (sampling.py as PR 27 left it)


def _frozen_top_k(scaled, top_k):
    b, v = scaled.shape
    srt = jnp.sort(scaled, axis=-1)[:, ::-1]
    k = jnp.clip(top_k, 1, v)
    kth = jnp.take_along_axis(srt, (k - 1)[:, None], axis=-1)
    disabled = (top_k[:, None] <= 0) | (top_k[:, None] >= v)
    return disabled | (scaled >= kth)


def _frozen_top_p(filtered, top_p):
    srt = jnp.sort(filtered, axis=-1)[:, ::-1]
    probs = jax.nn.softmax(srt, axis=-1)
    cum = jnp.cumsum(probs, axis=-1)
    keep_sorted = (cum - probs) < top_p[:, None]
    thr = jnp.min(jnp.where(keep_sorted, srt, jnp.inf), axis=-1)
    disabled = (top_p[:, None] <= 0.0) | (top_p[:, None] >= 1.0)
    return disabled | (filtered >= thr[:, None])


@jax.jit
def _frozen_fused_sample(logits, do_sample, temperature, top_k, top_p,
                         seeds, steps):
    lg = logits.astype(jnp.float32)
    greedy = jnp.argmax(lg, axis=-1).astype(jnp.int32)
    scaled = lg / jnp.maximum(temperature, 1e-6)[:, None]
    keep = _frozen_top_k(scaled, top_k)
    filtered = jnp.where(keep, scaled, -jnp.inf)
    keep = keep & _frozen_top_p(filtered, top_p)
    final = jnp.where(keep, scaled, -jnp.inf)
    gumbel = jax.vmap(
        lambda key: jax.random.gumbel(key, (lg.shape[1],), jnp.float32)
    )(_lane_keys(seeds, steps))
    sampled = jnp.argmax(final + gumbel, axis=-1).astype(jnp.int32)
    tok = jnp.where(do_sample, sampled, greedy)
    dist = jnp.where(do_sample[:, None], final, lg)
    lp = jax.nn.log_softmax(dist, axis=-1)
    return tok, jnp.take_along_axis(lp, tok[:, None], axis=-1)[:, 0]


B, V = 6, 64


def _tied_logits():
    """Every row holds runs of equal values around its 3rd and 4th
    largest, where top_k = 3 cuts and where top_p = 0.5 crosses."""
    lg = np.random.default_rng(9).standard_normal((B, V)).astype(
        np.float32)
    order = np.argsort(-lg, axis=-1)
    for r in range(B):
        lg[r, order[r, 2:6]] = lg[r, order[r, 2]]
    return lg


# name -> (do_sample, temperature, top_k, top_p, logits or None)
ON = np.ones(B, bool)
CASES = {
    "all_greedy": (~ON, 1.0, 5, 0.7, None),
    "temperature_only": (ON, 0.7, 0, 1.0, None),
    "top_k_only": (ON, 1.0, 5, 1.0, None),
    "top_p_only": (ON, 1.0, 0, 0.8, None),
    "top_k_and_top_p": (ON, 0.9, 7, 0.6, None),
    "ties_at_kth": (ON, 1.0, 3, 1.0, _tied_logits),
    "ties_at_nucleus_edge": (ON, 1.0, 0, 0.5, _tied_logits),
    "top_k_at_least_vocab": (ON, 1.3, [V, V + 5, V, V, V + 1, V], 1.0,
                             None),
    "top_p_at_least_one": (ON, 1.3, 0, [1.0, 1.5, 1.0, 2.0, 1.0, 1.0],
                           None),
    "greedy_beside_sampled": (np.arange(B) % 2 == 1,
                              [1.0, 0.8, 1.0, 1.2, 1.0, 0.5],
                              [0, 4, 0, 0, 9, V], [1.0, 1.0, 0.3, 0.9,
                                                   0.75, 1.0], None),
}


def _case_args(name):
    do_sample, temperature, top_k, top_p, make = CASES[name]
    lg = (make() if make else np.random.default_rng(
        sorted(CASES).index(name)).standard_normal((B, V)) * 2.0)
    full = lambda a, dt: jnp.asarray(  # noqa: E731
        np.broadcast_to(np.asarray(a, dt), (B,)))
    return (jnp.asarray(lg, jnp.float32), full(do_sample, bool),
            full(temperature, np.float32), full(top_k, np.int32),
            full(top_p, np.float32),
            jnp.arange(B, dtype=jnp.int32) + 3,
            jnp.arange(B, dtype=jnp.int32) * 7)


@pytest.mark.parametrize("name", sorted(CASES))
def test_values_are_the_two_sort_formulas(name):
    args = _case_args(name)
    want_tok, want_lp = _frozen_fused_sample(*args)
    for _ in range(3):      # over several counter keys a case
        tok, lp = fused_sample(*args)
        np.testing.assert_array_equal(np.asarray(tok),
                                      np.asarray(want_tok))
        np.testing.assert_array_equal(np.asarray(lp).view(np.uint32),
                                      np.asarray(want_lp).view(np.uint32))
        args = args[:6] + (args[6] + 1,)
        want_tok, want_lp = _frozen_fused_sample(*args)


def test_sampler_program_sorts_once_and_under_a_condition():
    text = jax.jit(fused_sample).lower(*_case_args("all_greedy")).as_text(
        dialect="hlo")
    assert hlo_sorts(text) == (0, 1)
    greedy_only = jax.jit(functools.partial(
        fused_sample, sample_capable=False)).lower(
            *_case_args("all_greedy")).as_text(dialect="hlo")
    assert hlo_sorts(greedy_only) == (0, 0)
    assert "conditional" not in greedy_only


# ---------------------------------------------------------------------------
# (b) the step's program


def _engine(m, **kw):
    return ServingEngine(m, **{**dict(page_size=4, num_pages=200,
                                      max_batch=4, prefill_chunk=8),
                               **kw})


@pytest.mark.parametrize("mixed", [False, True],
                         ids=["all_decode", "chunk_carrying"])
def test_ragged_program_sorts_only_under_a_condition(mixed):
    eng = _engine(tiny_model())
    tcap = eng._ragged_tok_mixed if mixed else eng._ragged_tok_small
    low = eng._step_program().lower(*ragged_step_avals(eng, tcap))
    outside, inside = hlo_sorts(low.as_text(dialect="hlo"))
    assert (outside, inside) == (0, 1)
    assert low.out_info[2].shape == (tcap, 97)


# ---------------------------------------------------------------------------
# (c) the counter


def _drive(eng):
    """Step to the end; for every step that dispatched, whether the
    sampler's sort ran in it."""
    per_step = []
    while not eng.scheduler.all_done():
        was = eng.metrics.export()
        eng.step()
        now = eng.metrics.export()
        if now["step_dispatches"] > was["step_dispatches"]:
            per_step.append(now["sampler_sort_steps"]
                            - was["sampler_sort_steps"])
    return per_step


def _prompts(seed, sizes):
    rng = np.random.default_rng(seed)
    return [rng.integers(0, 97, n).astype(np.int32) for n in sizes]


def test_greedy_run_never_sorts():
    eng = _engine(tiny_model())
    for p in _prompts(0, (19, 5, 11)):
        eng.add_request(p, max_new_tokens=6)
    steps = _drive(eng)
    ex = eng.metrics.export()
    assert 6 < len(steps) == ex["step_dispatches"]
    assert sum(steps) == ex["sampler_sort_steps"] == 0
    assert ex["step_program_classes"] == 2        # both capacities ran


def test_one_top_p_lane_sorts_in_the_steps_its_sample_is_read():
    """A 19-token prompt takes three chunks of 8: the first two ask for
    no sort (their sample is discarded), the third samples the first
    token, and each of the 4 decode steps after it one more. The
    temperature-only and the greedy lane beside it never bind."""
    eng = _engine(tiny_model())
    a, b, c = _prompts(1, (19, 4, 6))
    eng.add_request(b, max_new_tokens=12, do_sample=True,
                    temperature=0.8, seed=2)
    eng.add_request(c, max_new_tokens=12)
    eng.add_request(a, max_new_tokens=5, do_sample=True, top_p=0.8,
                    seed=4)
    steps = _drive(eng)
    assert eng.metrics.sampler_sort_steps.value == 5
    assert sum(steps) == 5 and max(steps) == 1
    assert eng.metrics.step_dispatches.value == len(steps)
    # a filter that cannot bind is no filter: top_k >= vocab, top_p >= 1
    eng2 = _engine(tiny_model())
    eng2.add_request(b, max_new_tokens=4, do_sample=True, top_k=97,
                     top_p=1.0, seed=2)
    _drive(eng2)
    assert eng2.metrics.sampler_sort_steps.value == 0


# ---------------------------------------------------------------------------
# (d) the readers of the step's logits, beside a chunk


FORK_REQ = [dict(), dict(do_sample=True, temperature=0.9, seed=7, n=3),
            dict(do_sample=True, top_p=0.8, seed=11), dict()]


def test_fork_at_prefill_completion_is_token_exact_beside_a_chunk():
    """A decode lane is running when the forking request's last chunk
    arrives, so that chunk's last token is not the step's first. A
    child is its parent's request with the seed one further: each of
    the six streams is the one that request gets served alone."""
    m = tiny_model(seed=3)
    eng = ServingEngine(m, page_size=4, num_pages=200, max_batch=6,
                        prefill_chunk=8)
    prompts = _prompts(5, (5, 13, 21, 7))
    eng.add_request(prompts[0], max_new_tokens=6, **FORK_REQ[0])
    eng.step()
    for p, kw in zip(prompts[1:], FORK_REQ[1:]):
        eng.add_request(p, max_new_tokens=6, **kw)
    res = eng.run()
    got = [list(map(int, res[r]["tokens"])) for r in sorted(res)]
    assert len(got) == len(FORK_REQ) + 2           # the two children
    child = {k: v for k, v in FORK_REQ[1].items() if k != "n"}
    alone = served_alone(
        m, prompts + [prompts[1]] * 2,
        FORK_REQ[:1] + [child] + FORK_REQ[2:]
        + [dict(child, seed=8), dict(child, seed=9)], 6)
    assert got == [toks for toks, _ in alone]
    assert len({tuple(got[i]) for i in (1, 4, 5)}) > 1   # they diverge
    assert eng.metrics.step_program_classes.value <= 2


def test_host_sampling_oracle_is_token_exact_beside_a_chunk(
        monkeypatch):
    """Greedy is exact between the host oracle and the device sampler;
    the oracle reads ``logits[offset]`` of the step's [T, V]."""
    m = tiny_model(seed=4)
    prompts = _prompts(6, (5, 21, 13, 15))    # last chunks of 5-7
    kws = [dict()] * 4
    base, _ = run_fleet(m, prompts, kws, max_new=7)
    monkeypatch.setenv("PADDLE_TPU_SERVING_HOST_SAMPLE", "1")
    got, eng = run_fleet(m, prompts, kws, max_new=7)
    assert base == got
    # what the oracle fetched: the step's [T, 97] floats, whole
    assert eng.metrics.fetch_bytes.value % (97 * 4 * 4) == 0
    assert eng.metrics.step_program_classes.value <= 2


# ---------------------------------------------------------------------------
# (a) again, through the engine: the streams and their log-probabilities
# with the frozen formula in the sampler's place


def _parent_fused_sample(logits, do_sample, temperature, top_k, top_p,
                         seeds, steps, *, sample_capable=True):
    if sample_capable:
        return _frozen_fused_sample(logits, do_sample, temperature,
                                    top_k, top_p, seeds, steps)
    lg = logits.astype(jnp.float32)
    greedy = jnp.argmax(lg, axis=-1).astype(jnp.int32)
    lp = jax.nn.log_softmax(lg, axis=-1)
    return greedy, jnp.take_along_axis(lp, greedy[:, None], axis=-1)[:, 0]


PARITY_REQ = [dict(), dict(do_sample=True, temperature=0.9, seed=7, n=2),
              dict(do_sample=True, top_k=5, seed=3),
              dict(do_sample=True, top_p=0.8, seed=11),
              dict(do_sample=True, temperature=0.7, top_k=9, top_p=0.6,
                   seed=5), dict()]


def _streams(m, **ekw):
    """[[(token, the log-probability's bits), ...] a request, in the
    order of the ids]."""
    eng = ServingEngine(m, page_size=4, num_pages=200, max_batch=6,
                        prefill_chunk=8, **ekw)
    for p, kw in zip(_prompts(8, (5, 13, 21, 7, 17, 3)), PARITY_REQ):
        eng.add_request(p, max_new_tokens=6, logprobs=True, **kw)
    out = {}
    while not eng.scheduler.all_done():
        for ev in eng.step():
            if ev["type"] == "token":
                out.setdefault(ev["req_id"], []).append(
                    (ev["token"],
                     int(np.float32(ev["logprob"]).view(np.uint32))))
    return [out[r] for r in sorted(out)]


@pytest.mark.parametrize("ekw", [
    dict(), dict(speculative_k=3), dict(cache_dtype="int8"),
    dict(prefix_cache=True)],
    ids=["plain", "speculative", "int8_kv", "prefix_cache"])
def test_engine_streams_are_the_two_sort_samplers(ekw, monkeypatch):
    m = tiny_model(seed=5)
    if "speculative_k" in ekw:
        ekw = dict(ekw, draft_model=m)
    got = _streams(m, **ekw)
    monkeypatch.setattr(sampling, "fused_sample", _parent_fused_sample)
    want = _streams(m, **ekw)
    assert len(want) == len(PARITY_REQ) + 1          # the fork's child
    assert sum(map(len, want)) == 6 * len(want)
    assert got == want
