"""The delta rule's decode kernel (``ops/pallas/_delta_kernel.py``) in
interpret mode on the CPU, against ``delta_step`` over states gathered
by slot and scattered back: outputs and states agree to float32 rounding
(``TOL`` = 1e-6 of the magnitudes, which are about 1), every slot no live
lane names is left bit for bit, and a dead lane gives nought. A state
rounded to bfloat16 on its way in and a state written to the wrong slot
are planted below and have to fail the same check. Also: which route
``delta_rule`` takes, by ``dispatch_stats()``.
"""
import numpy as np
import pytest

import jax
import jax.numpy as jnp

from paddle_tpu.ops import delta_rule as dr
from paddle_tpu.ops.pallas import _delta_kernel as dk

TOL = 1e-6
D = 128
SCALE = 1.0


def case(lanes, h, slots, seed=0):
    """Unit q, k; v ~ N(0, 1); log-decay down to the gate's floor; a
    state ~ N(0, 1) in every slot."""
    rng = np.random.default_rng(seed)

    def unit(*shape):
        x = rng.normal(size=shape)
        return (x / np.linalg.norm(x, axis=-1, keepdims=True)).astype(
            np.float32)

    return dict(q=unit(lanes, h, D), k=unit(lanes, h, D),
                v=rng.normal(size=(lanes, h, D)).astype(np.float32),
                g=(dr.GATE_FLOOR * rng.uniform(size=(lanes, h, D))).astype(
                    np.float32),
                beta=rng.uniform(size=(lanes, h)).astype(np.float32),
                state=rng.normal(size=(slots, h, D, D)).astype(np.float32))


def plain(c, slot, fresh, live, scale):
    """Gather by slot, ``delta_step`` (dead lanes decay and write
    nothing), scatter back the live lanes."""
    s0 = np.where(fresh[:, None, None, None], 0.0, c["state"][slot])
    g = np.where(live[:, None, None], c["g"], 0.0)
    beta = np.where(live[:, None], c["beta"], 0.0)
    o, s1 = dr.delta_step(c["q"], c["k"], c["v"], g, beta, s0, scale)
    state = c["state"].copy()
    state[slot[live]] = np.asarray(s1)[live]
    return state, np.where(live[:, None, None], np.asarray(o), 0.0)


def kernel(c, slot, fresh, live, scale, state=None):
    run = jax.jit(lambda *a: dk.delta_step_slots(*a, scale=scale,
                                                 interpret=True))
    st, o = run(c["q"], c["k"], c["v"], c["g"], c["beta"],
                c["state"] if state is None else state, slot, fresh, live)
    return np.asarray(st), np.asarray(o)


def agrees(got, want, untouched):
    """Outputs and named slots to rounding, other slots bit for bit."""
    (gs, go), (ws, wo) = got, want
    return (np.abs(go - wo).max() <= TOL * np.abs(wo).max()
            and np.abs(gs - ws).max() <= TOL * np.abs(ws).max()
            and all(np.array_equal(gs[m], ws[m]) for m in untouched))


LAYOUTS = {
    # lanes in shuffled slot order, two fresh, three dead on slot 0
    "shuffled_fresh_dead": dict(
        slot=[5, 2, 7, 1, 4, 0, 0, 0], fresh=[0, 1, 0, 0, 1, 0, 0, 0],
        live=[1, 1, 1, 1, 1, 0, 0, 0], h=16, slots=9),
    # dead lanes between live ones: slot 0 read again after a live lane
    "dead_between": dict(
        slot=[0, 3, 0, 0, 6, 1], fresh=[0, 0, 0, 0, 0, 1],
        live=[0, 1, 0, 0, 1, 1], h=8, slots=8),
    # every lane live, one head block
    "all_live": dict(slot=[2, 1, 3], fresh=[0, 0, 0], live=[1, 1, 1],
                     h=2, slots=4),
}


@pytest.mark.parametrize("name", sorted(LAYOUTS))
def test_the_kernel_is_delta_step_over_gathered_slots(name):
    lay = LAYOUTS[name]
    slot = np.asarray(lay["slot"], np.int32)
    fresh = np.asarray(lay["fresh"], bool)
    live = np.asarray(lay["live"], bool)
    c = case(len(slot), lay["h"], lay["slots"], seed=len(name))
    got = kernel(c, slot, fresh, live, SCALE)
    want = plain(c, slot, fresh, live, SCALE)
    untouched = sorted(set(range(lay["slots"])) - set(slot[live]))
    assert np.abs(want[1]).max() > 0.1 and np.abs(want[0]).max() > 1.0
    assert agrees(got, want, untouched)
    assert not got[1][~live].any()                   # a dead lane: nought
    if name == "shuffled_fresh_dead":
        assert dk.heads_per_block(lay["h"], D) < lay["h"]


def test_the_planted_faults_fail_the_check():
    lay = LAYOUTS["shuffled_fresh_dead"]
    slot = np.asarray(lay["slot"], np.int32)
    fresh = np.asarray(lay["fresh"], bool)
    live = np.asarray(lay["live"], bool)
    c = case(len(slot), lay["h"], lay["slots"])
    want = plain(c, slot, fresh, live, SCALE)
    untouched = sorted(set(range(lay["slots"])) - set(slot[live]))
    rounded = np.asarray(jnp.asarray(c["state"]).astype(jnp.bfloat16)
                         .astype(jnp.float32))
    assert not agrees(kernel(c, slot, fresh, live, SCALE,
                             state=rounded), want, untouched)
    wrong = np.where(slot == 5, 6, slot).astype(np.int32)
    assert not agrees(kernel(c, wrong, fresh, live, SCALE), want,
                      untouched)


def _trace(monkeypatch, tpu, rows, d, slots=True):
    monkeypatch.setattr(dr, "_on_tpu", lambda: tpu)
    n, h = 4, 2
    x = jnp.zeros((n, rows, h, d), jnp.float32)
    dr.reset_dispatch_stats()
    jax.make_jaxpr(lambda x, b, s: dr.delta_rule(
        x, x, x, x, b, s, 1.0, jnp.ones((n, rows), bool),
        jnp.arange(n, dtype=jnp.int32) if slots else None,
        jnp.zeros(n, bool) if slots else None))(
        x, jnp.zeros((n, rows, h)),
        jnp.zeros((n + 1 if slots else n, h, d, d), jnp.float32))
    return dr.dispatch_stats()


@pytest.mark.parametrize("tpu,rows,d,slots,route", [
    (True, 1, D, True, "kernel"),
    (True, 1, 64, True, "step"),        # a head width that does not tile
    (True, 1, D, False, "step"),        # states handed in, not slots
    (True, 17, D, True, "chunk"),
    (False, 1, D, True, "step"),        # off the TPU
    (False, 17, D, True, "chunk"),
])
def test_the_route_is_chosen_from_shapes_and_backend(monkeypatch, tpu,
                                                     rows, d, slots, route):
    stats = _trace(monkeypatch, tpu, rows, d, slots)
    assert stats == {**{"kernel": 0, "step": 0, "chunk": 0}, route: 1}
