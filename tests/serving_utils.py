"""Shared serving-test helpers (round 17, chaos PR).

The round-11 addenda's lesson, promoted to a utility: fixed-sleep
assertions against a live engine loop RACE the lock (the loop may hold
it across a whole step, so "sleep 50 ms then assert" fails under suite
CPU load) — poll with a deadline instead.  The chaos fuzz shakes out
exactly this flake class, so every converted call site routes through
here."""
import time


def wait_until(cond, timeout=30.0, interval=0.01, msg=None):
    """Poll ``cond()`` until truthy; returns its value.  Raises
    AssertionError (with ``msg`` or the condition's repr) when the
    deadline passes — never a silent False, so a racing assertion
    becomes a labelled failure, not a flake."""
    deadline = time.monotonic() + timeout
    while True:
        value = cond()
        if value:
            return value
        if time.monotonic() >= deadline:
            raise AssertionError(
                msg or f"condition {cond!r} not met within {timeout}s")
        time.sleep(interval)


def wait_until_live(replica, n=1, timeout=30.0):
    """Deadline-poll until a replica reports >= n live requests (its
    engine loop actually picked the work up)."""
    return wait_until(
        lambda: replica.health().get("live", 0) >= n, timeout=timeout,
        msg=f"replica never reached {n} live request(s)")


def wait_until_reserved(replica, timeout=30.0):
    """Deadline-poll until a replica holds a nonzero page reservation
    (admission landed; the load signal other submits route on)."""
    return wait_until(lambda: replica.load() > 0, timeout=timeout,
                      msg="replica never reported a reservation")


def sequential_oracle(m, prompts, max_new):
    """Greedy streams from ``model.generate()``, one prompt at a time:
    the reference that shares no paging, scheduling or sampling code
    with the engine's step."""
    import numpy as np

    import paddle_tpu as P
    return [np.asarray(m.generate(P.to_tensor(p[None]),
                                  max_new_tokens=max_new)._data)[0]
            for p in prompts]


def serve_streams(eng, prompts, req_kws, max_new, alone=False):
    """Serve ``prompts`` through ``eng``; returns one ``(tokens,
    logprob bits)`` pair a request, in order. ``alone`` serves each
    request to its end before the next is admitted."""
    import numpy as np
    events = {}
    prev = eng.on_event

    def on_event(ev):
        if ev["type"] == "token":
            events.setdefault(ev["req_id"], []).append(
                (int(ev["token"]), ev["logprob"]))

    eng.on_event = on_event
    try:
        rids = []
        for p, kw in zip(prompts, req_kws):
            rids.append(eng.add_request(p, max_new_tokens=max_new,
                                        logprobs=True, **kw))
            if alone:
                eng.run()
        eng.run()
    finally:
        eng.on_event = prev
    return [([t for t, _ in events[r]],
             np.asarray([lp for _, lp in events[r]],
                        np.float32).view(np.uint32).tolist())
            for r in rids]


def served_alone(m, prompts, req_kws, max_new, **ekw):
    """The lone-request oracle of the counter-RNG contract: each
    request served by itself (``max_batch=1``, the whole prompt one
    prefill chunk, no draft model, nothing to preempt it). Token ``t``
    is a function of (weights, history, seed, ``t``) and of no
    schedule, so a crowd, a preemption, a chunk size and a speculative
    round must each reproduce these tokens and logprob bits (to the
    bit where the token's products keep their row counts, within
    :func:`assert_streams_within_ulps` where they do not)."""
    from paddle_tpu.serving import ServingEngine
    eng = ServingEngine(m, max_batch=1,
                        prefill_chunk=max(len(p) for p in prompts),
                        **{**dict(page_size=4, num_pages=64), **ekw})
    return serve_streams(eng, prompts, req_kws, max_new, alone=True)


def logprob_ulps(got, want):
    """The largest distance in f32 ulps between the log-probabilities
    of two ``serve_streams`` results, over each request's tokens up to
    the first that differs (``None`` where there are none): the bit
    patterns, read as sign and magnitude, order as the values do."""
    import numpy as np

    def ordered(bits):
        b = np.asarray(bits, np.int64)
        return np.where(b >> 31, -(b & 0x7FFFFFFF), b)

    worst = None
    for (ta, a), (tb, b) in zip(got, want):
        n = next((i for i, (x, y) in enumerate(zip(ta, tb)) if x != y),
                 min(len(ta), len(tb)))
        if n:
            d = int(np.abs(ordered(a[:n]) - ordered(b[:n])).max())
            worst = d if worst is None else max(worst, d)
    return worst


def assert_streams_within_ulps(got, want, ulps):
    """Two ``serve_streams`` results: the tokens exactly, the
    log-probabilities within ``ulps`` f32 ulps. For the comparisons in
    which a token meets products of another row count (see
    ``tests/test_serving_ragged.py::CROSS_SHAPE_ULPS``); every other
    comparison of streams is ``==`` on tokens and logprob bits."""
    assert [t for t, _ in got] == [t for t, _ in want]
    assert logprob_ulps(got, want) <= ulps


def hlo_sorts(hlo_text):
    """``(outside, inside)``: the ``sort`` instructions of an HLO module
    text that run whenever the program runs, and those that run only in
    a branch of a ``conditional``. A computation is outside when the
    entry reaches it through calls, fusions, loops or reducers alone."""
    import re
    comps, name = {}, None
    for line in hlo_text.splitlines():
        head = re.match(r"^(ENTRY\s+)?%?([\w.\-]+)\s.*\{\s*$", line)
        if head and "=" not in line.split("(")[0]:
            name = head.group(2)
            comps[name] = {"entry": bool(head.group(1)), "sorts": 0,
                           "calls": set(), "branches": set()}
            continue
        if name is None or " = " not in line:
            continue
        c = comps[name]
        if re.search(r"\ssort\(", line.split(" = ", 1)[1]):
            c["sorts"] += 1
        guarded = re.search(r"\sconditional\(", line) is not None
        for group in re.findall(
                r"(?:to_apply|calls|body|condition|true_computation|"
                r"false_computation|branch_computations)="
                r"(\{[^}]*\}|%?[\w.\-]+)", line):
            for callee in re.findall(r"[\w.\-]+", group):
                c["branches" if guarded else "calls"].add(callee)
    entry = [n for n, c in comps.items() if c["entry"]]
    assert len(entry) == 1, entry
    seen, todo = set(), entry
    while todo:
        n = todo.pop()
        if n in seen or n not in comps:
            continue
        seen.add(n)
        todo += comps[n]["calls"]
    total = sum(c["sorts"] for c in comps.values())
    outside = sum(comps[n]["sorts"] for n in seen)
    return outside, total - outside


def ragged_step_avals(engine, tcap, sds=None):
    """The operands ``engine._run_ragged_step`` hands the step program
    at token capacity ``tcap``, as shapes (``sds(shape, dtype)`` makes
    one: ``jax.ShapeDtypeStruct``, or one with a described sharding):
    the weights, the packed step, the cache's state (the pools, with
    their scale rows where the cache is int8; a mixed cache's window
    pools and lane states) and the lanes' slots and window tables."""
    import jax
    import jax.numpy as jnp
    sds = sds or jax.ShapeDtypeStruct
    lanes, cache = engine._ragged_lanes, engine.cache
    i32 = lambda *s: sds(s, jnp.int32)  # noqa: E731
    f32 = lambda *s: sds(s, jnp.float32)  # noqa: E731
    shapes = lambda tree: jax.tree.map(  # noqa: E731
        lambda a: sds(a.shape, a.dtype), tree)
    k_ops, v_ops = shapes(cache.program_operands())
    return (shapes([t._data for t in engine.model._gen_state_tensors()]),
            i32(1, tcap), i32(1, tcap),
            i32(lanes, engine.max_pages_per_seq), i32(lanes), i32(lanes),
            i32(lanes), i32(1, tcap),
            (sds((tcap,), jnp.bool_), f32(tcap), i32(tcap), f32(tcap),
             i32(tcap), i32(tcap)),
            k_ops, v_ops, shapes(cache.extra_operands()),
            dict(lane_slot=i32(lanes),
                 wpt=i32(lanes, max(cache.window_pages_per_lane, 1)),
                 wbase=i32(lanes), wslots=i32(tcap)) if cache.mixed else {})
