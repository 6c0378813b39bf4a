"""What PR 33 added to the benchmark for the SambaY family: the plain
reference against independent oracles (the recurrence step by step in
NumPy, differential attention as two plain softmax attentions), its int8
control, the FLOPs functions by hand-counted cases, the configuration
held whole under the rule of the cut, the weights by name, the check,
and the new readers on a recorded trace head."""
import json
import math
import re
from pathlib import Path

import numpy as np
import pytest

from benchmark.harness import flops_sambay as F
from benchmark.harness import reference_sambay as R
from benchmark.harness import spec, trace
from benchmark.harness import weights_sambay as W
from benchmark.harness.window import Run

ROOT = Path(__file__).resolve().parents[2]
DATA = Path(__file__).parent / "data"
CELL = "phi4-flash.serve.reasonturns"
FILE = "benchmark/configs/phi4flash/phi-4-mini-flash-reasoning.serve.json"
SMALL = dict(hidden_size=128, intermediate_size=256, num_hidden_layers=6,
             num_attention_heads=8, num_key_value_heads=4, sliding_window=8,
             vocab_size=320, layer_norm_eps=1e-5, torch_dtype="float32",
             initializer_range=0.09, program={"memory_layer": 2})
IDS = np.random.default_rng(5).integers(0, 320, (1, 48)).astype(np.int32)


def published():
    return json.loads((ROOT / FILE).read_text())


@pytest.fixture(scope="module")
def weights():
    return W.make(2147483747, SMALL)


def logits(w, **kw):
    return np.asarray(R.logits_at(w, SMALL, IDS, np.arange(48), block=16,
                                  **kw))


# -- the reference against independent oracles ---------------------------------------

def test_the_recurrence_is_the_step_by_step_one_in_numpy():
    rng = np.random.default_rng(0)
    s, d, n = 11, 6, 4
    xs, b, c = rng.normal(size=(s, d)), rng.normal(size=(s, n)), \
        rng.normal(size=(s, n))
    dt = np.log1p(np.exp(rng.normal(size=(s, d))))
    a, skip = -np.exp(rng.normal(size=(d, n))), rng.normal(size=d)
    state = np.zeros((d, n))
    want = np.zeros((s, d))
    for t in range(s):
        for i in range(d):
            for j in range(n):
                state[i, j] = math.exp(dt[t, i] * a[i, j]) * state[i, j] \
                    + dt[t, i] * xs[t, i] * b[t, j]
            want[t, i] = state[i] @ c[t] + skip[i] * xs[t, i]
    f = lambda v: np.asarray(v, np.float32)                 # noqa: E731
    got = np.asarray(R.recurrence(f(xs), f(dt), f(a), f(b), f(c), f(skip)))
    assert np.abs(got - want).max() < 1e-5 * np.abs(want).max()


def test_the_convolution_is_causal_and_meets_the_row_with_its_last_tap():
    rng = np.random.default_rng(1)
    xs, w, bias = rng.normal(size=(7, 3)), rng.normal(size=(4, 3)), \
        rng.normal(size=3)
    want = np.zeros((7, 3))
    for t in range(7):
        for j in range(4):
            if t - 3 + j >= 0:
                want[t] += w[j] * xs[t - 3 + j]
        want[t] += bias
    f = lambda v: np.asarray(v, np.float32)                 # noqa: E731
    assert np.abs(np.asarray(R.causal_conv(f(xs), f(w), f(bias)))
                  - want).max() < 1e-6


def _softmax_attention(q, k, v, window):
    """One head, plainly: q, k [S, d], v [S, e]; causal, at most
    ``window - 1`` keys back."""
    s = q.shape[0]
    sc = q @ k.T / math.sqrt(q.shape[1])
    for i in range(s):
        for j in range(s):
            if j > i or (window and j <= i - window):
                sc[i, j] = -np.inf
    p = np.exp(sc - sc.max(-1, keepdims=True))
    return (p / p.sum(-1, keepdims=True)) @ v


@pytest.mark.parametrize("window", [0, 5])
def test_differential_attention_is_two_plain_softmax_attentions(window):
    rng = np.random.default_rng(2)
    s, nh, nkv, d = 12, 8, 4, 6
    q, k, v = rng.normal(size=(s, nh, d)), rng.normal(size=(s, nkv, d)), \
        rng.normal(size=(s, nkv, d))
    w = {n: rng.normal(size=d) * 0.3 for n in ("lq1", "lk1", "lq2", "lk2")}
    w["subln"] = rng.normal(size=2 * d)
    lam_0 = W.lam0(3)
    lam = math.exp(w["lq1"] @ w["lk1"]) - math.exp(w["lq2"] @ w["lk2"]) \
        + lam_0
    want = np.zeros((s, nh * d))
    for j in range(nh // 2):                 # query pair j: heads 2j, 2j+1
        g = j // (nh // nkv)                 # its key/value pair
        vv = np.concatenate([v[:, 2 * g], v[:, 2 * g + 1]], -1)
        a1 = _softmax_attention(q[:, 2 * j], k[:, 2 * g], vv, window)
        a2 = _softmax_attention(q[:, 2 * j + 1], k[:, 2 * g + 1], vv,
                                window)
        a = a1 - lam * a2
        a = a / np.sqrt((a * a).mean(-1, keepdims=True) + 1e-5)
        want[:, 2 * d * j:2 * d * (j + 1)] = a * w["subln"] * (1 - lam_0)
    f = lambda t: np.asarray(t, np.float32)                 # noqa: E731
    got = np.asarray(R.diff_attend(
        f(q), f(k), f(v), {n: f(a) for n, a in w.items()},
        np.float32(lam_0), np.int32(window), block=4))
    assert np.abs(got - want).max() < 1e-5


def test_blocks_of_rows_and_of_columns_equal_the_whole(weights):
    want = logits(weights)
    assert want.std() > 0.05
    whole = np.asarray(R.logits_at(weights, SMALL, IDS, np.arange(48),
                                   block=48, vocab_block=64))
    assert np.abs(whole - want).max() < 1e-6
    # padding behind the last token is seen by no position that counts
    short = IDS.copy()
    short[0, 30:] = 0
    cut = np.asarray(R.logits_at(weights, SMALL, short, np.arange(30),
                                 block=16))
    assert np.abs(cut - want[:30]).max() < 1e-6


@pytest.mark.parametrize("prec", ["int8", "fp8"])
def test_the_control_is_coarser_than_the_reference(weights, prec):
    err = np.abs(logits(weights, prec=prec) - logits(weights)).max()
    assert 1e-2 < err < 3.0


def test_every_mechanism_moves_the_logits(weights):
    """A bias, the memory, the cross layer's keys, the window and the
    lambdas all count: left out, the reference answers otherwise."""
    want = logits(weights)

    def without(layer, leaf, value=0.0):
        lw = dict(weights["layers"][layer])
        lw[leaf] = lw[leaf] * 0 + value
        layers = list(weights["layers"])
        layers[layer] = lw
        return np.abs(logits(dict(weights, layers=layers)) - want).max()

    for layer, leaf in [(0, "conv_b"), (0, "dt_b"), (0, "D"), (1, "qkv_b"),
                        (1, "o_b"), (1, "lq1"), (2, "dt_w"), (3, "qkv_b"),
                        (4, "in"), (5, "q_b"), (5, "lk2"), (5, "o_b")]:
        assert without(layer, leaf) > 1e-4, (layer, leaf)
    # the decay: every state element forgetting at exp(-20 dt) moves the
    # memory less than a bias does, and still shows
    assert without(2, "A_log", 3.0) > 1e-5
    wide = dict(SMALL, sliding_window=48)
    assert np.abs(np.asarray(R.logits_at(
        weights, wide, IDS, np.arange(48), block=16)) - want).max() > 1e-4


# -- the weights, by name -----------------------------------------------------------------

def test_the_leaves_are_drawn_by_name(weights):
    m, a = weights["layers"][0], weights["layers"][1]
    assert np.allclose(np.asarray(m["A_log"])[:, 5],
                       np.log(np.arange(1, 17)))
    assert np.asarray(m["D"]).tolist() == [1.0] * 256
    step = np.log1p(np.exp(np.asarray(m["dt_b"], np.float64)))
    assert 0.9e-3 < step.min() < 2e-3 and 0.05 < step.max() < 0.11
    assert 0.3 < np.asarray(m["conv_w"]).std() < 0.7
    assert 0.07 < np.asarray(m["in"]).std() < 0.11
    assert np.asarray(a["ln1_b"]).tolist() == [0.0] * 128
    assert np.asarray(a["subln"]).tolist() == [1.0] * 32
    assert 0.05 < np.asarray(a["lq1"]).std() < 0.2
    assert np.abs(np.asarray(a["qkv_b"])).max() > 1e-3
    assert [W.kind(SMALL, i) for i in range(6)] == [
        "mamba", "window", "mamba", "full", "gmu", "cross"]
    assert "q_w" in weights["layers"][5] and "qkv_w" not in \
        weights["layers"][5]
    again = W.make(2147483747, SMALL)["layers"][4]["in"]
    assert np.array_equal(np.asarray(again),
                          np.asarray(weights["layers"][4]["in"]))
    other = W.make(2147483748, SMALL)["layers"][4]["in"]
    assert not np.array_equal(np.asarray(other), np.asarray(again))


def test_the_leaves_are_the_programs_parameters():
    from paddle_tpu.models import SambaYConfig, SambaYForCausalLM
    model = SambaYForCausalLM(SambaYConfig.from_published(
        SMALL, dtype="float32", **SMALL["program"]))
    params = {n: tuple(p.shape) for n, p in model.named_parameters()}
    names = W.program_names(SMALL)
    assert set(names.values()) == set(params)
    shapes = W.leaf_shapes(SMALL)
    for path, name in names.items():
        assert params[name] == W.get(shapes, path), name


# -- the configuration and the cell ---------------------------------------------------------

def _leaves(tree):
    if isinstance(tree, tuple):
        yield tree
    elif isinstance(tree, dict):
        for v in tree.values():
            yield from _leaves(v)
    else:
        for v in tree:
            yield from _leaves(v)


def test_the_configuration_is_the_model_held_whole():
    cfg = published()
    bench = json.loads((ROOT / "BENCHMARK.json").read_text())
    entry = next(c for c in bench["configs"]
                 if c["name"] == "phi-4-mini-flash-reasoning.serve")
    assert entry["reduced"] == [] and cfg["reduced"] == {}
    assert entry["file"] == FILE
    pub = spec.published(entry["source"])
    spec.check_cut(entry, cfg, pub)
    assert all(cfg[k] == v for k, v in pub["keys"].items())
    assert set(pub["counts"]) == {"num_hidden_layers", "vocab_size",
                                  "num_attention_heads",
                                  "num_key_value_heads"}
    # the catalog's numbers, value for value
    assert {k: cfg[k] for k in (
        "hidden_size", "intermediate_size", "num_hidden_layers",
        "num_attention_heads", "num_key_value_heads", "sliding_window",
        "mb_per_layer", "vocab_size", "max_position_embeddings")} == dict(
        hidden_size=2560, intermediate_size=10240, num_hidden_layers=32,
        num_attention_heads=40, num_key_value_heads=20, sliding_window=512,
        mb_per_layer=2, vocab_size=200064, max_position_embeddings=262144)
    n = sum(int(np.prod(s)) for s in _leaves(W.leaf_shapes(cfg)))
    assert n == 3_852_562_944              # 7.71 GB in bfloat16
    assert W.dims(cfg)["memory_layer"] == 16


@pytest.mark.parametrize("key,value", [("hidden_size", 2048),
                                       ("sliding_window", 256),
                                       ("intermediate_size", 8192),
                                       ("layer_norm_eps", 1e-6)])
def test_a_changed_width_or_constant_is_refused(key, value):
    cfg = published()
    entry = {"source": cfg["source"], "reduced": []}
    with pytest.raises(SystemExit, match="may never differ"):
        spec.check_cut(entry, dict(cfg, **{key: value}),
                       spec.published(cfg["source"]))


def test_a_cut_that_is_not_stated_is_refused():
    cfg = published()
    entry = {"source": cfg["source"], "reduced": []}
    with pytest.raises(SystemExit, match="does not list it"):
        spec.check_cut(entry, dict(cfg, num_hidden_layers=8),
                       spec.published(cfg["source"]))


def test_the_engine_holds_the_mixs_callers_at_their_worst_case():
    cfg = published()
    mix = json.loads((ROOT / "benchmark/traffic/reasonturns.json")
                     .read_text())
    eng = cfg["engine"]
    worst = -(-(mix["prompt_len"]["max"] + mix["output_len"]["max"])
              // eng["page_size"])
    assert worst == 176
    # every caller's worst case and a twentieth more (the issue's rule);
    # what admission needs is the lanes' worst case and the watermark
    assert mix["clients"] * worst * 1.05 <= eng["num_pages"]
    watermark = -(-(eng["num_pages"] - 1) * 5 // 100)
    assert eng["max_batch"] * worst + watermark <= eng["num_pages"] - 1
    assert mix["prompt_len"]["max"] + mix["output_len"]["max"] \
        <= eng["max_seq_len"]
    assert (mix["driver"], mix["loop"], mix["clients"], mix["pool"],
            mix["pool_seed"]) == ("serve_sambay", "closed", 36, 72, 1)
    assert mix["prompt_len"] == {"median": 1280, "sigma": 0.5, "min": 512,
                                 "max": 2304}
    assert mix["output_len"] == {"median": 320, "sigma": 0.35, "min": 192,
                                 "max": 512}
    assert (mix["ramp_seconds"], mix["drain_seconds"], mix["trace_seconds"],
            mix["check_requests"]) == (20, 60, 6, 4)
    assert (eng["max_batch"], eng["prefill_chunk"], eng["page_size"],
            eng["prefix_cache"]) == (24, 128, 16, False)
    # every window binds from a lane's first chunk on
    assert mix["prompt_len"]["min"] >= cfg["sliding_window"]


def test_the_cell_reports_what_the_contract_asks():
    cell = spec.load(CELL)
    assert cell.chips == 1
    assert {m["name"] for m in cell.end_to_end} == {"serve_tok_s", "setup_s"}
    names = {m["name"] for m in cell.per_layer}
    assert names == {
        "decode_lanes_mean.rate", "step_ms.rate", "step_decode_ms.rate",
        "step_chunk_ms.rate", "device_idle_pct.rate", "mfu_pct.hybrid_rate",
        "ssm_scan_roofline", "attn_gather_over_live",
        "window_pages_per_lane"}
    bench = json.loads((ROOT / "BENCHMARK.json").read_text())
    for m in bench["per_layer"]:
        if m["name"] in ("mfu_pct.hybrid_rate", "ssm_scan_roofline",
                         "attn_gather_over_live", "window_pages_per_lane"):
            assert m["workloads"] == [CELL] and m["moves"] == "serve_tok_s"


# -- the FLOPs, by hand -----------------------------------------------------------------------

def test_flops_by_hand_at_the_published_widths():
    cfg = published()
    h, f, di = 2560, 10240, 5120
    mlp = 3 * h * f
    assert F.mixer_params(cfg, 0) == (
        h * 2 * di + 4 * di + di * (160 + 32) + 160 * di + di * h)
    assert F.mixer_params(cfg, 0) == 41_144_320
    assert F.mixer_params(cfg, 1) == F.mixer_params(cfg, 17) == \
        h * (h + 2 * 1280) + h * h == 19_660_800
    assert F.mixer_params(cfg, 18) == 2 * h * di == 26_214_400
    assert F.mixer_params(cfg, 19) == 2 * h * h == 13_107_200
    assert F.layer_token_params(cfg, 19) == 13_107_200 + mlp
    assert F.layer_counts(cfg) == {"mamba": 9, "window": 8, "full": 1,
                                   "gmu": 7, "cross": 7}
    body = (9 * 41_144_320 + 9 * 19_660_800 + 7 * 26_214_400
            + 7 * 13_107_200 + 32 * mlp)
    assert F.token_matmul_flops(cfg) == 2 * (body + h * 200064)
    # a pair: 40 heads' scores over 64, 40 heads' values over 128
    assert F.pair_flops(cfg) == 2 * 40 * 64 + 2 * 40 * 128 == 15_360
    # a scanned row: three multiply-adds a state element and the skip
    assert F.scan_row_flops(cfg) == 2 * (3 * 5120 * 16 + 5120) == 501_760
    # one decode row at position 1,000: 512 keys in each window layer,
    # 1,001 in the full layer and the seven cross layers
    assert F.span_forward_flops(cfg, 1000, 1001, 1) == (
        2 * body + 9 * 501_760 + 2 * h * 200064
        + 15_360 * (8 * 512 + 8 * 1001))
    # a prompt of 600: the window's keys stop growing at 512
    tri = 600 * 601 // 2
    win = 512 * 513 // 2 + (600 - 512) * 512
    assert F.span_forward_flops(cfg, 0, 600, 1) == (
        600 * (2 * body + 9 * 501_760) + 2 * h * 200064
        + 15_360 * (8 * win + 8 * tri))


def test_the_scans_least_time_is_its_bytes_moved_once():
    from benchmark.harness.device import PEAKS
    cfg = published()
    fl, by = F.scan_cost(cfg, rows=128, lane_scans=1)
    assert fl == 128 * 501_760
    assert by == 128 * (5120 * (2 + 2 + 4) + 2 * 16 * 2) \
        + 2 * 5120 * 16 * 4
    peaks = PEAKS["TPU v5 lite"]
    assert F.scan_least_seconds(cfg, 128, 1, peaks) == pytest.approx(
        by / 819e9)                         # memory bound, 11 FLOPs a byte


def test_window_flops_count_tokens_stamped_in_the_window():
    from benchmark.harness.stats import ReqRecord
    cfg = published()
    r = ReqRecord(0, 0.0, np.zeros(4, np.int32), 3)
    r.stamps = [1.0, 2.0, 9.0]
    run = Run(cfg=cfg, mix={}, peaks=None, chips=1, t0=0.5, t1=5.0)
    run.records = [r]
    assert F.serve_window_flops(run) == (
        F.span_forward_flops(cfg, 0, 4, 1)
        + F.span_forward_flops(cfg, 4, 5, 1))


# -- the readers ----------------------------------------------------------------------------------

def _run_with(counters, tr=None, peaks=None):
    cell = spec.load(CELL)
    run = Run(cfg=cell.config, mix=cell.traffic, peaks=peaks, chips=1)
    run.counters, run.trace = counters, tr
    return run


def reader(name):
    return spec.reader(ROOT, "layer_metrics", name)


def test_counter_readers_and_a_program_without_the_counters():
    # 10 steps of 24 live lanes: 8 window layers, 33 pages a lane
    c = {"window_pages_held": 10 * 8 * 24 * 33.0,
         "window_layer_steps": 10 * 8 * 24.0,
         "attn_pages_gathered": 10 * 25 * (8 * 192 + 8 * 41.0),
         "attn_pages_live": 10 * 24 * (100 + 8 * 33.0)}
    assert reader("window_pages_per_lane")(_run_with(c)) == 33.0
    assert reader("attn_gather_over_live")(_run_with(c)) == pytest.approx(
        25 * 1864 / (24 * 364))
    # the parent's program has no such counter: nothing to read, no error
    for name in ("window_pages_per_lane", "attn_gather_over_live",
                 "ssm_scan_roofline", "mfu_pct.hybrid_rate"):
        assert reader(name)(_run_with({})) is None


# -- the check ------------------------------------------------------------------------------------

def _record(weights, alter=None, logprobs=True):
    """A request whose tokens are a random continuation, served with the
    reference's own log-probabilities for them."""
    import jax

    from benchmark.harness.stats import ReqRecord
    prompt, cont = IDS[0, :16], IDS[0, 16:40]
    ids = np.zeros((1, 48), np.int32)
    ids[0, :40] = IDS[0, :40]
    ref = R.logits_at(weights, SMALL, ids, 15 + np.arange(24), block=16)
    lsm = np.asarray(jax.nn.log_softmax(ref, -1))
    r = ReqRecord(0, 0.0, prompt, 24)
    r.tokens = [int(t) for t in np.asarray(ref).argmax(-1)] \
        if alter is None else [int(t) for t in cont]
    # teacher-forced on the random continuation, so only the all-greedy
    # record's positions are its own; the altered one's gap is what counts
    r.logprobs = [float(lsm[i, t]) for i, t in enumerate(r.tokens)] \
        if logprobs else []
    r.finished = 1.0
    return r, ids


def test_the_check_reads_the_gap_and_the_log_probabilities(weights):
    from benchmark.harness import check, check_sambay as C
    cfg = published()
    rec, _ = _record(weights, alter=0)
    got = C.served_against_reference(weights, SMALL, [rec], pad_to=48,
                                     control="int8")
    # a random continuation is not the reference's choice: a wide gap,
    # but its log-probabilities are the reference's own
    assert got["logit_gap_max"] > 0.1 and got["logprob_err_max"] < 1e-5
    assert got["short_answers"] == 0
    assert got["_compared"] == {"requests": 1, "tokens": 24}
    assert got["_control"]["logprob_err_max"] > 1e-3
    bare, _ = _record(weights, alter=0, logprobs=False)
    assert C.served_against_reference(
        weights, SMALL, [bare], pad_to=48)["logprob_err_max"] == np.inf
    empty = C.served_against_reference(weights, SMALL, [], pad_to=48)
    assert empty["logit_gap_max"] == empty["logprob_err_max"] == np.inf
    rec.max_new = 25
    assert C.served_against_reference(
        weights, SMALL, [rec], pad_to=48)["short_answers"] == 1
    # the limits file judges exactly what the check reads
    numbers = dict(got, unfinished_requests=0.0)
    for limits in (cfg["limits"], cfg["rehearse_limits"]):
        ok, table = check.judge(numbers, limits, cfg.get("not_compared", ()))
        assert set(table) == set(limits) and not ok


# -- the trace readers on a recorded step ---------------------------------------------------

def test_the_trace_readers_on_a_recorded_step():
    """One chunk-carrying step of the cell on a TPU v5e: the first step
    of a traced window's head (``record_trace_head.py``), operation
    names cut to 120 characters, gzipped: 33.1 ms, 7,619 operations,
    more than half of them the nine scans' loop bodies."""
    import gzip

    from benchmark.harness.device import PEAKS
    from benchmark.harness.stats import ReqRecord
    cell = spec.load(CELL)
    with gzip.open(DATA / "trace_sambay_head.json.gz") as f:
        loaded = json.loads(f.read())
    tr = trace.reduce(loaded, chips=1)
    (name, times), = tr.module_s.items()
    assert name.startswith("jit__unknown")
    assert times == [pytest.approx(0.033106993)]
    pattern = cell.traffic["ssm_op_match"]
    secs = tr.op_seconds(pattern)
    assert secs == pytest.approx(0.000989216)
    # the nine scans' loops, everything that runs inside one, and the
    # operations on state-shaped arrays around them (the decode rows'
    # one step, the lanes' states read and written)
    (dev,) = loaded["devices"].values()
    loops = [e for e in dev["ops"] if e[0].startswith("%while")]
    assert len(loops) == 9
    inside = [n for n, s, t in dev["ops"] for _, a, d in loops
              if a <= s and s + t <= a + d and not n.startswith("%while")]
    assert len(inside) > 4000
    assert all(re.search(pattern, n) for n in inside)
    assert 0.7e-3 < sum(d for _, _, d in loops) / 1e9 < secs
    other = [n for n in tr.op_self_s if not re.search(pattern, n)]
    assert any("200064" in n for n in other)            # the head is not
    assert any("bf16[6656,16,2560]" in n for n in other)    # nor the pool
    peaks = PEAKS["TPU v5 lite"]
    # one step: 9 layers, 128 chunk rows and 23 decode rows of 24 lanes
    counters = {"ssm_layer_steps": 9.0, "ssm_lane_scans": 9 * 24.0,
                "ssm_rows_scanned": 9 * 151.0}
    run = _run_with(counters, tr, peaks)
    least = 9 * (151 * (5120 * 8 + 64) + 24 * 2 * 5120 * 16 * 4) / 819e9
    share = reader("ssm_scan_roofline")(run)
    assert share == pytest.approx(100 * least / secs)
    assert 20 < share < 40
    # nothing matches: None, not 0
    run.mix = dict(run.mix, ssm_op_match="no such op")
    assert reader("ssm_scan_roofline")(run) is None
    # one request's last chunk (a prompt of 640) and 23 decode rows at
    # position 1,000, all stamped in the window
    run = _run_with(counters, tr, peaks)
    run.t0, run.t1 = 10.0, 10.05
    first = ReqRecord(0, 9.0, np.zeros(640, np.int32), 3)
    first.stamps = [10.01]
    rest = ReqRecord(1, 9.0, np.zeros(1000, np.int32), 30)
    rest.stamps = [9.0] + [10.02] * 23
    run.records = [first, rest]
    need = F.span_forward_flops(cell.config, 0, 640, 1) + sum(
        F.span_forward_flops(cell.config, 1000 + j, 1001 + j, 1)
        for j in range(23))
    assert reader("mfu_pct.hybrid_rate")(run) == pytest.approx(
        100 * need / (tr.busy_s * 197e12))
