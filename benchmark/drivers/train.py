"""The one-chip training cell: ``paddle_tpu.Model(...).train_batch_loop``
(``steps_per_call`` optimizer steps in one compiled ``lax.scan``), fed by a
``paddle_tpu.io.DataLoader`` with worker processes over token rows drawn
from ``--seed``.

Set-up builds one ``Model`` (one stepper with its state) and one feed,
drives the first ``check_steps`` calls of that feed through the window's
own call (the first compiles the one program the cell has), reads the
optimizer's state after the first and after the last of them, and hands
the same object and the same feed to the window. The mix makes one
optimizer step a call: the state after step 1 is then the state after the
window's own first call, and no second program is built for the check.

The window dispatches ``calls_ahead`` calls ahead of the one it waits for
(some seconds of steps: a host that stands still for a second or two
leaves the device fed) and reads losses after it. It closes so: when its
time is up nothing more is sent, all that was sent is waited for, and the
clock is read after that wait.
"""
from __future__ import annotations

import gc
import time
from collections import deque

import numpy as np

from ..harness import check, reference, weights
from ..harness.window import Run, Tracer, annotate
from .serve import build_model

clock = time.perf_counter


class TokenRows:
    """Item i is one step's batch [B, S] of token ids, a function of
    (seed, i) alone; all rows differ. numpy only: the loader's forked
    workers never touch jax."""

    def __init__(self, seed, vocab, batch, seq, start, n):
        self.seed, self.vocab = int(seed), int(vocab)
        self.shape, self.start, self.n = (int(batch), int(seq)), start, n

    def __len__(self):
        return self.n

    def __getitem__(self, i):
        return rows(self.seed, self.start + i, self.vocab, self.shape)


def rows(seed, i, vocab, shape):
    return np.random.default_rng([int(seed), 0x7041, int(i)]).integers(
        0, vocab, shape, dtype=np.int32)


def feed_calls(loader_cls, seed, cfg, mix, start=0, rows_per_epoch=512):
    """An endless feed of [steps_per_call, B, S] blocks: one DataLoader
    after another over successive rows. Epochs are short because the
    loader enqueues every batch index of its dataset up front; one epoch
    outlasts a window, so no loader starts inside it."""
    n_call = int(mix["steps_per_call"])
    while True:
        data = TokenRows(seed, cfg["vocab_size"], mix["batch"],
                         mix["sequence"], start, rows_per_epoch)
        it = iter(loader_cls(data, batch_size=n_call,
                             num_workers=int(mix["loader_workers"])))
        try:
            yield from it
        finally:
            it.close()
        start += rows_per_epoch


def _leaf_norms(arrays, scale=1.0):
    import jax
    import jax.numpy as jnp
    f = jax.jit(lambda xs: [jnp.sqrt(jnp.sum(jnp.square(
        x.astype(jnp.float32)))) * scale for x in xs])
    return [float(v) for v in f(list(arrays))]


def _delta_norms(masters, originals):
    import jax
    import jax.numpy as jnp
    f = jax.jit(lambda ms, os: [jnp.sqrt(jnp.sum(jnp.square(
        m.astype(jnp.float32) - o.astype(jnp.float32))))
        for m, o in zip(ms, os)])
    return [float(v) for v in f(list(masters), list(originals))]


def first_steps(m, model, opt, cfg, mix, seed, n_check, feed):
    """The program's readings over its first steps, taken through the
    window's own call and feed: each step's loss, the norm of the first
    gradient as the optimizer got it (moment1 after one step is
    (1 - beta1) * g), and the norm of the master weights' change after
    the last. Keyed by parameter name."""
    if int(mix["steps_per_call"]) != 1:
        raise SystemExit("benchmark: the train check reads the state after "
                         "step 1 from the window's own call: steps_per_call "
                         "has to be 1")
    names = [n for n, _ in model.named_parameters()]
    params = [p for _, p in model.named_parameters()]
    b1 = float(cfg["optimizer"]["beta1"])
    losses, grad = [], None
    for i in range(n_check):
        with annotate("data_fetch"):
            xs = next(feed)
        with annotate("train_batch_loop"):
            out = m.train_batch_loop([xs], [xs])
        losses.append(float(np.asarray(out._data)[0]))
        if i == 0:
            grad = dict(zip(names, _leaf_norms(
                [opt._accum[id(p)]["moment1"] for p in params],
                scale=1.0 / (1.0 - b1))))
    w0 = weights.make(seed, cfg)          # the donated originals, again
    path_of = {n: p for p, n in weights.program_names(cfg).items()}
    delta = dict(zip(names, _delta_norms(
        [opt._accum[id(p)].get("master", p._data) for p in params],
        [weights.get(w0, path_of[n]) for n in names])))
    del w0
    return {"losses": losses, "grad": grad, "delta": delta}


def follow(cfg, mix, seed, n_check, prec="f32", **faults) -> dict:
    """The reference's readings over the same first steps, from the same
    weights and rows, keyed like the program's."""
    w = weights.make(seed, cfg)
    shape = (int(mix["batch"]), int(mix["sequence"]))
    batches = [rows(seed, i, cfg["vocab_size"], shape)
               for i in range(n_check)]
    losses, g1, d = reference.train_follow(
        w, cfg, batches, cfg["optimizer"], prec=prec,
        block=min(512, shape[1]), **faults)
    names = weights.program_names(cfg)
    return {"losses": losses,
            "grad": {n: weights.get(g1, p) for p, n in names.items()},
            "delta": {n: weights.get(d, p) for p, n in names.items()}}


def numbers_of(prog: dict, ref: dict) -> dict:
    """Every number is a reading of ``prog`` (the program, or the control
    or a fault put in its place) against the reference's."""
    moving = check.moving_leaves(ref["grad"])
    return {
        "loss_err_max": max(abs(a - b) / abs(b)
                            for a, b in zip(prog["losses"], ref["losses"])),
        "grad_norm_gap_worst": check.worst_leaf_gap(prog["grad"],
                                                    ref["grad"]),
        "update_norm_gap_worst": check.worst_leaf_gap(
            prog["delta"], ref["delta"], keep=moving),
        "_compared": {"steps": len(ref["losses"]),
                      "leaves": len(ref["grad"]),
                      "moving_leaves": len(moving),
                      "losses": prog["losses"],
                      "reference_losses": ref["losses"]}}


def compare(prog, cfg, mix, seed, n_check, control=None) -> dict:
    ref = follow(cfg, mix, seed, n_check)
    numbers = numbers_of(prog, ref)
    if control:       # the lower-precision reference, then the planted fault
        for key, kw in (("_control", {"prec": control}),
                        ("_fault_half_batch", {"half_batch": True})):
            got = numbers_of(follow(cfg, mix, seed, n_check, **kw), ref)
            got.pop("_compared")
            numbers[key] = got
    return numbers


def build(cfg, mix, seed):
    import paddle_tpu as P
    from paddle_tpu.models import LlamaPretrainingCriterion
    model, w = build_model(cfg, seed)
    del w                      # the stepper donates them: made again later
    model.train()
    crit = LlamaPretrainingCriterion(model.cfg).bind(model)
    o = cfg["optimizer"]
    opt = P.optimizer.AdamW(
        o["learning_rate"], beta1=o["beta1"], beta2=o["beta2"],
        epsilon=o["epsilon"], weight_decay=o["weight_decay"],
        parameters=model.parameters(), multi_precision=True)
    m = P.Model(model)
    m.prepare(opt, crit)
    return m, model, opt


def run(cell, args, ctx) -> dict:
    from paddle_tpu.io import DataLoader
    from paddle_tpu.ops.pallas import flash_attention as fa

    cfg, mix = ctx["cfg"], ctx["mix"]
    if ctx["rehearse"]:
        fa._FORCE_INTERPRET = True      # the tests' hook: kernels on CPU
    fa.reset_dispatch_stats()
    m, model, opt = build(cfg, mix, args.seed)
    n_check, n_call = int(mix["check_steps"]), int(mix["steps_per_call"])
    feed = feed_calls(DataLoader, args.seed, cfg, mix)
    prog = first_steps(m, model, opt, cfg, mix, args.seed, n_check, feed)
    stats = fa.dispatch_stats()
    if stats["fallback"] or not stats["pallas"]:
        raise SystemExit(f"benchmark: flash attention dispatch {stats}: "
                         "the kernel must run, never the XLA fallback")
    tokens_per_call = n_call * int(mix["batch"]) * int(mix["sequence"])
    tracer = Tracer(cell.root, bool(args.trace) and not ctx["rehearse"])
    seconds = float(args.seconds)
    if args.trace:
        seconds = min(seconds, float(mix.get("trace_seconds", 6.0)))
    run_ = Run(cfg=cfg, mix=mix, peaks=ctx["peaks"], chips=cell.chips)
    # -- the window -----------------------------------------------------------
    run_.setup_s = clock() - ctx["t_process"]
    ctx["compiles"].mark()
    tracer.start()
    run_.t0 = t_first = clock()
    # every step sent counts, over all the time to the last one's end
    ahead = int(mix["calls_ahead"])
    outs, sent, t_full = [], deque(), None
    while clock() - t_first < seconds:
        with annotate("data_fetch"):
            xs = next(feed)
        with annotate("train_batch_loop"):
            out = m.train_batch_loop([xs], [xs])
        sent.append(out)
        outs.append(out)
        if len(sent) > ahead:
            if t_full is None:
                t_full = clock()
            with annotate("wait_previous_call"):
                sent.popleft()._data.block_until_ready()
    t_sent = clock()
    with annotate("wait_all_sent"):
        for out in sent:
            out._data.block_until_ready()
    run_.t1 = t_last = clock()
    tracer.stop()
    run_.compiles_in_window = ctx["compiles"].since_mark()
    # -------------------------------------------------------------------------
    calls = len(outs)
    losses = np.concatenate([np.asarray(o._data).reshape(-1) for o in outs])
    run_.memory_peak_bytes = ctx["memory_peak"]()
    run_.train = {"steps": calls * n_call, "tokens": calls * tokens_per_call,
                  "first_call": t_first, "last_ready": t_last,
                  "last_loss": float(losses[-1])}
    run_.trace = tracer.reduce(cell.chips)

    feed.close()
    del m, model, opt, feed, sent, out, outs, xs
    gc.collect()
    numbers = compare(prog, cfg, mix, args.seed, n_check,
                      control=cfg.get("control_precision", "int8")
                      if ctx.get("control") else None)
    bad = int(np.sum(~np.isfinite(losses)))      # steps whose loss is no number
    numbers["window_losses_not_finite"] = float(bad)
    numbers["_info"] = {      # how far ahead the host got, and how soon
        "steps": calls * n_call, "calls_ahead": ahead,
        "ahead_full_after_s": None if t_full is None else t_full - t_first,
        "sent_all_after_s": t_sent - t_first,
        "window_s": t_last - t_first}
    return {"run": run_, "numbers": numbers, "attempted": calls * n_call,
            "failed": bad}
