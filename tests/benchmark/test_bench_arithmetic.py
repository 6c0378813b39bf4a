"""The yardstick's arithmetic against hand-worked values: FLOPs and bytes
for both published configurations, percentiles and lateness on a
synthetic schedule that holds a stall, the traffic generator."""
import json
import threading
import time
from pathlib import Path

import numpy as np
import pytest

from benchmark.harness import flops, readers, stats, traffic
from benchmark.harness.stats import ReqRecord
from benchmark.harness.window import Run

ROOT = Path(__file__).resolve().parents[2]


def cfg(name):
    return json.loads((ROOT / "benchmark/configs" / name).read_text())


DEEPSEEK = cfg("deepseek-llm-7b.serve-L8.json")
MISTRAL = cfg("mistral-7b-v0.1.serve-L8.json")
MISTRAL_TRAIN = cfg("mistral-7b-v0.1.train-L2.json")


def test_matmul_params_deepseek_mha():
    # a layer: q,k,v,o 4 x 4096^2 = 67,108,864; SwiGLU 3 x 4096 x 11008 =
    # 135,266,304; together 202,375,168. Head 4096 x 102400 = 419,430,400.
    assert flops.matmul_params(DEEPSEEK, head=False) == 8 * 202_375_168
    assert flops.matmul_params(DEEPSEEK) == 8 * 202_375_168 + 419_430_400
    assert flops.token_matmul_flops(DEEPSEEK) == 2 * 2_038_431_744


def test_matmul_params_mistral_gqa():
    # q,o 2 x 4096^2 = 33,554,432; k,v 2 x 4096 x 1024 = 8,388,608;
    # SwiGLU 3 x 4096 x 14336 = 176,160,768; a layer 218,103,808.
    assert flops.matmul_params(MISTRAL, head=False) == 8 * 218_103_808
    assert flops.matmul_params(MISTRAL) == 8 * 218_103_808 + 131_072_000


def test_attention_counts_only_visible_keys():
    # one token at position 99 sees 100 keys: 4 x 32 x 128 x 100 a layer
    assert flops.span_forward_flops(MISTRAL, 99, 100, 0) == \
        flops.token_matmul_flops(MISTRAL, head=False) + 4 * 4096 * 100 * 8
    # past the window a query sees the window, itself included
    assert flops.visible_keys(5000, 4096) == 4096
    assert flops.visible_keys(4095, 4096) == 4096
    assert flops.visible_keys(10, None) == 11
    # causal: positions 0..3 see 1+2+3+4 keys; from a cache of 2: 3+4
    assert flops.span_keys(0, 4, None) == 10
    assert flops.span_keys(2, 4, None) == 7
    # window 3 over 0..5: 1+2+3+3+3+3
    assert flops.span_keys(0, 6, 3) == 15
    assert flops.span_keys(0, 6, 3) == sum(
        flops.visible_keys(p, 3) for p in range(6))


def test_train_step_flops_by_hand():
    c = MISTRAL_TRAIN                     # depth 2, sequence 4096, batch 1
    per_tok = 2 * (2 * 218_103_808)       # layers' matmuls, forward
    head = 2 * 4096 * 32000
    attn = 4 * 4096 * 2 * (4096 * 4097 // 2)   # the window never binds
    fwd = 4096 * (per_tok + head) + attn
    assert flops.train_step_flops(c, 1, 4096) == 3 * fwd
    # the embedding table (131 M) counts nothing: 6N would add 3.2e12
    assert flops.train_step_flops(c, 1, 4096) < 6 * 698_000_000 * 4096


def test_flash_kernel_costs_and_bounds():
    c = MISTRAL_TRAIN
    keys = 4096 * 4097 // 2
    fl, by = flops.flash_fwd_cost(c, 1, 4096)
    assert fl == 4 * 32 * 128 * keys
    # q and o 32 heads, k and v 8 heads, bf16; lse f32 per head and row
    assert by == 4096 * 128 * 2 * (64 + 16) + 4096 * 32 * 4
    fb, bb = flops.flash_bwd_cost(c, 1, 4096)
    assert fb == 10 * 32 * 128 * keys and bb > by
    peaks = {"bf16_flops": 197e12, "hbm_bytes_per_s": 819e9}
    t, bound = flops.roofline_seconds(fl, by, peaks)
    assert bound == "compute" and t == pytest.approx(fl / 197e12)
    assert flops.roofline_seconds(1e6, 1e9, peaks)[1] == "memory"


def _schedule(stall_at=None, stall=0.0, every=None):
    """40 requests, one due every 0.1 s, first token 50 ms after it was
    due, then 10 tokens 20 ms apart. A stall holds every event that falls
    inside it until it ends (the engine stopped, the clock did not);
    ``every`` makes it recur: the engine stops for ``stall`` seconds at
    each multiple of ``every``."""
    starts = [] if stall_at is None else [stall_at]
    if every:
        starts = [10.0 + every * k for k in range(int(6 / every))]
    recs = []
    for i in range(40):
        due = 10.0 + 0.1 * i
        times = [due + 0.05 + 0.02 * j for j in range(11)]
        for s0 in starts:
            times = [s0 + stall if s0 <= t < s0 + stall else t
                     for t in times]
        r = ReqRecord(i, due, np.zeros(4, np.int32), 11, sent=due + 0.001,
                      stamps=times, tokens=[1] * 11, finished=times[-1])
        recs.append(r)
    return recs


def _run(recs):
    r = Run(cfg={}, mix={}, peaks=None, chips=1)
    r.records, r.t0, r.t1, r.gave_up_at = recs, 10.0, 14.0, 20.0
    return r


def test_end_to_end_numbers_on_a_smooth_schedule():
    run = _run(_schedule())
    assert readers.ttft_p95_ms(run) == pytest.approx(50.0)
    assert readers.gap_p95_ms(run) == pytest.approx(20.0)
    # 40 requests x 11 tokens, all inside the 4 s window but the tail of
    # the last two requests
    inside = sum(1 for r in run.records for s in r.stamps if s < 14.0)
    assert readers.serve_tok_s(run) == pytest.approx(inside / 4.0)
    assert readers.gen_late_p95_ms(run) == pytest.approx(1.0)


def test_a_stall_moves_every_end_to_end_number():
    smooth, stalled = _run(_schedule()), _run(_schedule(12.0, 1.5))
    assert readers.ttft_p95_ms(stalled) > 10 * readers.ttft_p95_ms(smooth)
    assert max(stats.token_gaps_s(stalled.records, 10, 14)) > 1.0
    # tokens held past the window's end are not delivered inside it
    late = _run(_schedule(13.0, 1.5))
    assert readers.serve_tok_s(late) < 0.8 * readers.serve_tok_s(smooth)
    # a 95th percentile moves once a stall touches a twentieth of the
    # samples: an engine that stops for 60 ms every 200 ms
    hiccup = _run(_schedule(stall=0.06, every=0.2))
    assert readers.gap_p95_ms(hiccup) > 2.5 * readers.gap_p95_ms(smooth)
    # a median would not have moved: that is why none is end to end
    for run in (stalled, hiccup):
        assert np.median(stats.token_gaps_s(run.records, 10, 14)) == \
            pytest.approx(0.02, abs=1e-3)


def test_train_rate_is_all_tokens_over_all_the_time():
    run = _run([])
    run.train = {"steps": 16, "tokens": 16 * 4096, "first_call": 5.0,
                 "last_ready": 9.0}
    assert readers.train_tok_s(run) == pytest.approx(16 * 4096 / 4.0)
    run.train["last_ready"] = 11.0          # a stall inside the window
    assert readers.train_tok_s(run) == pytest.approx(16 * 4096 / 6.0)


@pytest.mark.parametrize("have, want", [
    (None, {"max_inflight_computations": 256}),
    ("ml_framework_name:JAX;ml_framework_version:0.9.0",
     {"ml_framework_name": "JAX", "ml_framework_version": "0.9.0",
      "max_inflight_computations": 256}),
    ({"max_inflight_computations": 32, "a": "b"},
     {"max_inflight_computations": 256, "a": "b"})])
def test_a_mixes_client_options_lie_over_the_clients_own(have, want):
    from benchmark.harness import device
    mix = json.loads((ROOT / "benchmark" / "traffic" / "s4096.json")
                     .read_text())
    got = device.laid_over(have, mix["client_options"])
    assert got == want
    # a whole number stays one: the client refuses it as a string
    assert isinstance(got["max_inflight_computations"], int)


def test_a_failed_request_is_later_than_any_that_succeeded():
    recs = _schedule()
    recs[5].stamps, recs[5].error = [], "Rejected('full')"
    v = stats.ttft_s(recs, 10.0, 14.0, gave_up_at=20.0)
    assert max(v) == pytest.approx(20.0 - recs[5].due)
    assert not recs[5].ok and recs[6].ok


def test_a_reader_with_nothing_to_read_returns_nothing():
    run = _run([])
    for fn in (readers.ttft_p95_ms, readers.gap_p95_ms, readers.serve_tok_s,
               readers.train_tok_s, readers.mfu_pct, readers.step_ms,
               readers.device_idle_pct, readers.flash_fwd_roofline,
               readers.prefix_hit_pct, readers.step_decode_ms,
               readers.step_chunk_ms):
        assert fn(run) is None


MIXES = {n: json.loads((ROOT / "benchmark/traffic" / f"{n}.json").read_text())
         for n in ("backlog", "chat", "longout")}
MAX_SEQ = {"backlog": 1024, "chat": 1024, "longout": 2048}


@pytest.mark.parametrize("name", sorted(MIXES))
def test_every_seed_offers_the_same_sizes_on_the_same_schedule(name):
    mix, cap = MIXES[name], MAX_SEQ[name]
    a = traffic.Plan(mix, 1, 32000, cap)
    b = traffic.Plan(mix, 3_000_000_019, 32000, cap)
    n = mix["pool"]
    ra, rb = [a.next() for _ in range(n)], [b.next() for _ in range(n)]
    sizes = lambda rs: [(len(r.prompt), r.max_new, r.due) for r in rs]  # noqa
    assert sizes(ra) == sizes(rb)
    assert any((x.prompt != y.prompt).any() for x, y in zip(ra, rb))
    spec = mix["prompt_len"]
    assert all(spec["min"] <= len(r.prompt) <= spec["max"] for r in ra)
    assert all(len(r.prompt) + r.max_new <= cap for r in ra)
    again = traffic.Plan(mix, 1, 32000, cap)
    assert all((x.prompt == again.next().prompt).all() for x in ra)


def _same(x, y):
    return (x.index, x.due, x.max_new, x.prefix_id) == (
        y.index, y.due, y.max_new, y.prefix_id) and (
        x.prompt == y.prompt).all()


@pytest.mark.parametrize("name", sorted(MIXES))
def test_a_planned_request_is_a_function_of_its_index(name):
    """``Plan.at(i)`` equals the ``i``-th ``next()``, in any order of
    asking and past the pool's end (the pools cycle)."""
    mix, cap = MIXES[name], MAX_SEQ[name]
    n = mix["pool"] + 5
    inorder = traffic.Plan(mix, 2147483747, 32000, cap)
    seq = [inorder.next() for _ in range(n)]
    plan = traffic.Plan(mix, 2147483747, 32000, cap)
    for i in np.random.default_rng(3).permutation(n):
        assert _same(plan.at(i), seq[i])
    assert _same(plan.next(), seq[0])        # asking moved nothing
    assert len(seq[mix["pool"]].prompt) == len(seq[0].prompt)
    assert (seq[mix["pool"]].prompt != seq[0].prompt).any()
    if mix["loop"] == "open":
        assert seq[mix["pool"]].due == pytest.approx(
            seq[0].due + mix["pool"] / mix["rate_per_s"])


class _Frontend:
    """Stands where ``ServingFrontend`` stands for ``Load``: counts as
    admitted whatever was submitted, and answers once the first round
    (two callers) is in, as no request finishes inside a cell's."""

    def __init__(self, hold):
        self.hold, self.lock, self.sent = hold, threading.Lock(), []
        self.engine = self
        self.scheduler = self
        self.waiting = []

    def live_requests(self):
        return self.sent

    def submit(self, prompt, max_new_tokens, logprobs):
        time.sleep(self.hold(len(prompt)))   # who finishes first differs
        with self.lock:
            self.sent.append(len(prompt))
        return self

    def events(self, timeout, idle_s):
        while len(self.sent) < 2:
            time.sleep(0.0005)
        yield {"type": "token", "token": 1, "logprob": 0.0}
        yield {"type": "finish"}


@pytest.mark.parametrize("name", ["backlog", "longout"])
def test_a_closed_loop_deals_every_round_out_in_order(name):
    """Caller ``k`` of ``C`` sends the plan's ``k, k + C, k + 2C, ...``:
    the same sizes and ids in every run, whichever caller finishes first
    (two interleavings: the long prompts slow, then the short ones); and
    the first round is admitted in index order."""
    from benchmark.drivers.serve import Load
    pytest.importorskip("paddle_tpu.serving.frontend")
    mix = dict(MIXES[name], clients=2)
    plan = traffic.Plan(mix, 2147483747, 32000, MAX_SEQ[name])
    median = mix["prompt_len"]["median"]
    seen = []
    for hold in (lambda n: 0.004 if n > median else 0.0,
                 lambda n: 0.0 if n > median else 0.004):
        fe = _Frontend(hold)
        load = Load(fe, traffic.Plan(mix, 2147483747, 32000, MAX_SEQ[name]),
                    mix)
        load.start()
        while min(sum(1 for r in list(load.records) if r.index % 2 == k
                      and r.finished) for k in (0, 1)) < 4:
            time.sleep(0.001)
        load.finish(wait_s=5.0)
        assert not load.errors
        by_caller = {k: [r for r in load.records if r.index % 2 == k]
                     for k in (0, 1)}
        for k, recs in by_caller.items():
            idx = [r.index for r in recs]
            assert idx == list(range(k, k + 2 * len(idx), 2))  # no gap
            for r in recs:
                assert (r.prompt == plan.at(r.index).prompt).all()
                assert r.max_new == plan.at(r.index).max_new
        assert fe.sent[:2] == [len(plan.at(0).prompt),
                               len(plan.at(1).prompt)]
        seen.append({k: [(len(r.prompt), r.max_new) for r in recs[:4]]
                     for k, recs in by_caller.items()})
    assert seen[0] == seen[1]


def test_open_loop_arrivals_hold_the_rate_and_share_prefixes():
    mix = MIXES["chat"]
    plan = traffic.Plan(mix, 7, 32000, 1024)
    rs = [plan.next() for _ in range(mix["pool"])]
    assert rs[-1].due == pytest.approx(mix["pool"] / mix["rate_per_s"])
    assert all(b.due > a.due for a, b in zip(rs, rs[1:]))
    k = mix["shared_prefix"]["tokens"]
    heads = {tuple(r.prompt[:k]) for r in rs}
    assert len(heads) == mix["shared_prefix"]["prompts"]
    counts = sorted((sum(1 for r in rs if r.prefix_id == i)
                     for i in range(4)), reverse=True)
    assert counts[0] > 1.5 * counts[-1]          # Zipf, not uniform


def test_a_mix_that_could_fail_is_refused():
    mix = dict(MIXES["backlog"], output_len={"median": 600, "sigma": 0.1,
                                             "min": 500, "max": 700})
    with pytest.raises(ValueError, match="no operation may fail"):
        traffic.Plan(mix, 1, 32000, 1024)
