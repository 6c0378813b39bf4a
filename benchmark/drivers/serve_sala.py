"""The serving cells of MiniCPM-SALA (``MiniCPMSalaForCausalLM``): block-
selected sparse attention over the page pool with its indexer, lightning
attention in lane state. ``drivers/serve.py``'s load, warm-up and window
around the same ``ServingEngine(ragged=True)`` behind the same
``ServingFrontend``, with this family's weights and plain reference
(``harness/weights_sala.py``, ``harness/reference_sala.py``) and the
selection and lightning counters beside the engine's others.
"""
from __future__ import annotations

import gc

from ..harness import check, check_sala, spec, traffic, weights_sala
from ..harness.window import Run, Tracer, sleep_until
from .serve import COUNTERS, Load, clock, sweep_view, warm_up

# counted by the engine where the layers differ
MIXED_COUNTERS = ("lightning_layer_steps", "lightning_lane_steps",
                  "lightning_rows_chunked", "sparse_blocks_read",
                  "sparse_blocks_live", "sparse_pages_selected",
                  "sparse_kernels_scored", "sparse_pages_streamed",
                  "sparse_kernels_read")


def build_model(cfg: dict, seed: int):
    """(model, the benchmark's weights): described under ``LazyGuard``,
    then given the benchmark's arrays leaf by leaf."""
    import paddle_tpu as P
    try:
        from paddle_tpu.models import (MiniCPMSalaConfig,
                                       MiniCPMSalaForCausalLM)
    except ImportError as e:        # a program without the model
        raise spec.SpecError(f"the program cannot run MiniCPM-SALA: {e}")
    P.seed(int(seed) % (2 ** 31 - 1))
    with P.LazyGuard():
        model = MiniCPMSalaForCausalLM(MiniCPMSalaConfig.from_published(
            cfg, **weights_sala.program_kw(cfg)))
    w = weights_sala.make(seed, cfg)
    params = dict(model.named_parameters())
    names = weights_sala.program_names(cfg)
    if set(names.values()) != set(params):
        raise SystemExit(
            "benchmark: the model's parameters are not the reference's "
            f"leaves: {sorted(set(names.values()) ^ set(params))}")
    for path, name in names.items():
        p, arr = params[name], weights_sala.get(w, path)
        if tuple(p.shape) != tuple(arr.shape):
            raise SystemExit(f"benchmark: {name} is {tuple(p.shape)}, the "
                             f"reference's leaf {tuple(arr.shape)}")
        p._data = arr
        if hasattr(p, "_lazy_init"):
            del p._lazy_init
    for lyr in model.sublayers(include_self=True):
        lyr.__dict__["_has_lazy_params"] = False
    return model, w


def _snapshot(engine) -> dict:
    m = engine.metrics
    snap = {k: float(getattr(m, k).value)
            for k in COUNTERS + MIXED_COUNTERS}
    snap["batch_size_sum"] = float(m.batch_size.total)
    snap["batch_size_count"] = float(m.batch_size.count)
    return snap


def run(cell, args, ctx) -> dict:
    from paddle_tpu.serving import ServingEngine
    from paddle_tpu.serving.frontend import ServingFrontend

    cfg, mix = ctx["cfg"], ctx["mix"]
    eng_kw = dict(cfg["engine"])
    max_queued = int(eng_kw.pop("max_queued", 64))
    model, w = build_model(cfg, args.seed)
    model.eval()
    engine = ServingEngine(model, ragged=True, eos_token_id=None, **eng_kw)
    frontend = ServingFrontend(engine, max_queued=max_queued).start()
    warm_up(frontend, cfg, args.seed)     # both step classes
    plan = traffic.Plan(mix, args.seed, cfg["vocab_size"],
                        int(eng_kw["max_seq_len"]))
    tracer = Tracer(cell.root, bool(args.trace) and not ctx["rehearse"])
    seconds = float(args.seconds)
    if args.trace:
        seconds = min(seconds, float(mix.get("trace_seconds", 6.0)))
    load = Load(frontend, plan, mix)

    run_ = Run(cfg=cfg, mix=mix, peaks=ctx["peaks"], chips=cell.chips)
    load.start()
    sleep_until(load.t_start + float(mix.get("ramp_seconds", 0.0)))
    # -- the window -----------------------------------------------------------
    run_.setup_s = clock() - ctx["t_process"]
    ctx["compiles"].mark()
    tracer.start()
    run_.t0 = clock()
    before = _snapshot(engine)
    sleep_until(run_.t0 + seconds)
    after = _snapshot(engine)
    run_.t1 = clock()
    tracer.stop()
    run_.compiles_in_window = ctx["compiles"].since_mark()
    # -------------------------------------------------------------------------
    run_.gave_up_at = load.finish(wait_s=float(mix.get("drain_seconds", 60)))
    run_.memory_peak_bytes = ctx["memory_peak"]()
    run_.records = load.records
    run_.counters = {k: after[k] - before[k] for k in after}
    run_.counters["step_program_classes"] = float(
        engine.metrics.step_program_classes.value)
    frontend.close(timeout=30.0)
    if load.errors or frontend.error is not None:
        raise SystemExit(f"benchmark: the load or the engine loop failed: "
                         f"{load.errors} {frontend.error!r}")
    run_.trace = tracer.reduce(cell.chips)

    # -- free the program, then the reference ---------------------------------
    del frontend, engine, model, load
    gc.collect()
    window = [r for r in run_.records if run_.t0 <= r.due < run_.t1]
    if not window:
        # a traced window is shorter than a long request and none may be
        # sent inside it: the operations it attempted are the requests
        # it served tokens of
        window = [r for r in run_.records
                  if any(run_.t0 <= s < run_.t1 for s in r.stamps)]
    sample = check.pick_sample(window or run_.records,
                               int(mix.get("check_requests", 4)), args.seed)
    numbers = check_sala.served_against_reference(
        w, cfg, sample, control=cfg.get("control_precision", "int8")
        if ctx.get("control") else None, **cfg["check"])
    numbers["unfinished_requests"] = float(
        sum(1 for r in window if not r.ok))
    if ctx.get("control"):
        limits = cfg["rehearse_limits" if ctx["rehearse"] else "limits"]
        unjudged = () if ctx["rehearse"] else cfg.get("not_compared", ())
        numbers["_info"] = check_sala.judge_faults(numbers, limits,
                                                   unjudged)
        if ctx["peaks"] is not None:
            numbers["_info"]["run"] = sweep_view(run_)
    elif ctx["peaks"] is not None and args.set:
        numbers["_info"] = sweep_view(run_)   # a sweep's view
    return {"run": run_, "numbers": numbers,
            "attempted": len(window),
            "failed": sum(1 for r in window if not r.ok)}
