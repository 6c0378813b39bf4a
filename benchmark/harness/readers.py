"""What the metric readers share. A reader file under
``benchmark/end_metrics`` or ``benchmark/layer_metrics`` is a few lines
that name one of these; a later PR's reader may as well carry its own
arithmetic. A reader returns ``None`` where it finds nothing to read --
never 0 for a share of a peak or of a roofline.
"""
from __future__ import annotations

import statistics

from . import flops, stats


# -- end to end (host clock) ---------------------------------------------------

def setup_s(run):
    return run.setup_s


def ttft_p95_ms(run):
    v = stats.ttft_s(run.records, run.t0, run.t1, run.gave_up_at)
    return 1e3 * stats.percentile(v, 95) if v else None


def gap_p95_ms(run):
    v = stats.token_gaps_s(run.records, run.t0, run.t1)
    return 1e3 * stats.percentile(v, 95) if v else None


def serve_tok_s(run):
    if not run.records:
        return None
    return stats.tokens_in_window(run.records, run.t0, run.t1) / run.seconds


def train_tok_s(run):
    t = run.train
    if not t or not t.get("steps"):
        return None
    return t["tokens"] / (t["last_ready"] - t["first_call"])


# -- per layer -----------------------------------------------------------------

def gen_late_p95_ms(run):
    v = stats.lateness_s(run.records, run.t0, run.t1)
    return 1e3 * stats.percentile(v, 95) if v else None


def decode_lanes_mean(run):
    n = run.counters.get("batch_size_count")
    return run.counters["batch_size_sum"] / n if n else None


def prefix_hit_pct(run):
    hit = run.counters.get("prefix_hit_pages", 0.0)
    total = hit + run.counters.get("prefix_miss_pages", 0.0)
    return 100.0 * hit / total if total else None


def _step_classes(run):
    """Device seconds of every execution of the step program in the traced
    window, one list per compiled program (a serving engine has two: the
    decode-only step and the step that carries a prefill chunk)."""
    if run.trace is None:
        return []
    return run.trace.module_classes(run.mix["step_module_match"])


def step_ms(run):
    """Mean device time of one step: the summed device time of every
    execution of the step program in the traced window over the steps
    they made. (A median would flip between a serving engine's two step
    classes with the share of steps that carry a chunk.)"""
    times = [t for ts in _step_classes(run) for t in ts]
    if not times:
        return None
    return 1e3 * sum(times) / (len(times)
                               * int(run.mix.get("steps_per_call", 1)))


def _class_median_ms(run, pick):
    """Median device time of one step class, the classes told apart by
    their compiled program and ordered by that median. Nothing to read
    unless both classes ran in the traced window."""
    meds = sorted(statistics.median(ts) for ts in _step_classes(run))
    return 1e3 * pick(meds) if len(meds) == 2 else None


def step_decode_ms(run):
    return _class_median_ms(run, min)


def step_chunk_ms(run):
    return _class_median_ms(run, max)


def device_idle_pct(run):
    if run.trace is None:
        return None
    return 100.0 * run.trace.idle_share()


def serve_window_flops(run) -> float:
    """FLOPs that the work completed in the window requires: every token
    event stamped inside it is one decode position (its matmuls, the
    head, attention over the keys it sees); a request whose first token
    arrived inside it had its prompt prefilled (less the tokens the prefix
    cache served, spread evenly over those requests; the head once)."""
    cfg, t0, t1 = run.cfg, run.t0, run.t1
    total, prefilled = 0.0, []
    for r in run.records:
        plen = len(r.prompt)
        for j, s in enumerate(r.stamps):
            if not t0 <= s < t1:
                continue
            if j == 0:
                prefilled.append(plen)
            else:
                pos = plen + j - 1
                total += flops.span_forward_flops(cfg, pos, pos + 1, 1)
    page = int(cfg["engine"]["page_size"])
    cached = run.counters.get("prefix_hit_pages", 0.0) * page
    start = int(cached / len(prefilled)) if prefilled else 0
    for plen in prefilled:
        total += flops.span_forward_flops(cfg, min(start, plen - 1), plen, 1)
    return total


def train_window_flops(run) -> float:
    t = run.train
    return t["steps"] * flops.train_step_flops(
        run.cfg, int(run.mix["batch"]), int(run.mix["sequence"]))


def mfu_pct(run):
    """Required FLOPs of the window's work over what the chips could do
    in the time they were busy. Times (1 - idle share) it is the
    conventional MFU."""
    if run.trace is None or run.peaks is None:
        return None
    need = train_window_flops(run) if run.train else serve_window_flops(run)
    busy = run.trace.busy_s
    if not need or not busy:
        return None
    return 100.0 * need / (busy * run.chips * run.peaks["bf16_flops"])


def _flash_roofline(run, which: str):
    if run.trace is None or run.peaks is None or not run.train:
        return None
    secs = run.trace.op_seconds(run.mix[f"flash_{which}_op_match"])
    if not secs:
        return None
    cost = {"fwd": flops.flash_fwd_cost, "bwd": flops.flash_bwd_cost}[which]
    fl, by = cost(run.cfg, int(run.mix["batch"]), int(run.mix["sequence"]))
    calls = run.train["steps"] * run.cfg["num_hidden_layers"]
    least, _ = flops.roofline_seconds(fl, by, run.peaks)
    return 100.0 * calls * least / secs


def flash_fwd_roofline(run):
    return _flash_roofline(run, "fwd")


def flash_bwd_roofline(run):
    return _flash_roofline(run, "bwd")
