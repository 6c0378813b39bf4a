"""What PR 35 added to the benchmark for the Kimi-delta-attention /
latent-attention hybrid with group-routed experts (Ling-3.0-flash-VL's
language model): the configuration held to the rule of the cut, the
reference's recurrence against NumPy, the FLOPs and bytes functions by
hand-counted cases, the weights by name, the new readers on a recorded
trace head, and a state not carried from one step to the next coming out
as not correct."""
import json
import os
import re
import subprocess
import sys
from pathlib import Path

import numpy as np
import pytest

from benchmark.harness import flops_kda_moe as F
from benchmark.harness import reference_kda_moe as R
from benchmark.harness import spec, trace
from benchmark.harness import weights_kda_moe as W
from benchmark.harness.window import Run

ROOT = Path(__file__).resolve().parents[2]
DATA = Path(__file__).parent / "data"
CELL = "ling3-flash.serve.longchain"
FILE = "benchmark/configs/ling3flash/ling-3.0-flash-vl.serve-L7-ep4.json"


def config():
    return json.loads((ROOT / FILE).read_text())


def entry():
    bench = json.loads((ROOT / "BENCHMARK.json").read_text())
    return next(c for c in bench["configs"]
                if c["file"] == FILE)


# -- the cut -----------------------------------------------------------------------

def test_the_cut_is_the_published_config_but_for_three_counts():
    cfg, e = config(), entry()
    pub = spec.published(e["source"])
    spec.check_cut(e, cfg, pub)
    assert sorted(e["reduced"]) == ["num_experts", "num_hidden_layers",
                                    "vocab_size"]
    assert (cfg["num_hidden_layers"], cfg["num_experts"],
            cfg["vocab_size"]) == (7, 128, 39296)
    assert W.routed(cfg) == (512, 0, 128)
    # every width, the groups, top-8 and the lists as published
    for k in ("hidden_size", "moe_intermediate_size", "n_group",
              "topk_group", "num_experts_per_tok", "head_dim",
              "kv_lora_rank", "expert_swiglu_limit_list"):
        assert cfg[k] == pub["keys"][k]
    assert len(cfg["share_expert_swiglu_limit_list"]) == 42
    wide = dict(cfg, hidden_size=4096)
    with pytest.raises(spec.SpecError, match="hidden_size"):
        spec.check_cut(e, wide, pub)
    short = dict(cfg, expert_swiglu_limit_list=cfg[
        "expert_swiglu_limit_list"][:7])
    with pytest.raises(spec.SpecError, match="expert_swiglu_limit_list"):
        spec.check_cut(e, short, pub)
    unshared = json.loads(json.dumps(cfg))
    del unshared["reduced"]["num_experts"]["chips"]
    with pytest.raises(spec.SpecError, match="chips"):
        spec.check_cut(e, unshared, pub)


def test_the_weights_are_the_programs_leaves_by_name():
    cfg = config()
    shapes = W.leaf_shapes(cfg)
    assert [W.kind(cfg, i) for i in range(7)] == ["kda"] * 5 + ["mla",
                                                                "kda"]
    layer2, layer5 = shapes["layers"][2], shapes["layers"][5]
    assert layer2["router"] == (2560, 512)
    assert layer2["w_gate"] == (128, 2560, 768)
    assert layer2["conv"] == (4, 3 * 4096) and layer2["f"] == (2560, 4096)
    assert layer5["q"] == (2560, 32 * 192) and "conv" not in layer5
    assert shapes["head"] == (2560, 39296)
    names = W.program_names(cfg)
    assert len(names) == 3 + sum(len(lyr) for lyr in shapes["layers"])
    n = sum(int(np.prod(s)) for lyr in shapes["layers"] for s in lyr.values())
    n += 2 * 2560 * 39296 + 2560
    assert 4.44e9 < n < 4.46e9                  # the issue's 4.45 B


# -- the reference against NumPy -----------------------------------------------------------

def test_the_reference_recurrence_is_the_delta_rule_in_numpy():
    rng = np.random.default_rng(0)
    s, h, d = 9, 2, 5
    q, k = rng.normal(size=(1, s, h, d)), rng.normal(size=(1, s, h, d))
    v, g = rng.normal(size=(1, s, h, d)), -rng.uniform(0, 5, (1, s, h, d))
    beta = rng.uniform(size=(1, s, h))
    st = np.zeros((h, d, d))
    want = np.zeros((s, h, d))
    for t in range(s):
        for j in range(h):
            sp = np.diag(np.exp(g[0, t, j])) @ st[j]
            st[j] = sp + beta[0, t, j] * np.outer(
                k[0, t, j], v[0, t, j] - sp.T @ k[0, t, j])
            want[t, j] = st[j].T @ q[0, t, j]
    f = lambda a: np.asarray(a, np.float32)                 # noqa: E731
    got = np.asarray(R.recurrence(f(q), f(k), f(v), f(g), f(beta)))[0]
    assert np.abs(got - want).max() < 1e-5 * np.abs(want).max()


def test_the_reference_router_keeps_four_of_eight_groups():
    rng = np.random.default_rng(1)
    y = rng.normal(size=(1, 6, 32)).astype(np.float32)
    router = rng.normal(size=(32, 512)).astype(np.float32)
    bias = (0.02 * rng.normal(size=512)).astype(np.float32)
    idx, w, margin = R.route(y, router, bias, top_k=8, scale=2.5,
                             n_group=8, topk_group=4, prec="f32")
    groups = np.asarray(idx) // 64
    assert all(len(set(row)) <= 4 for row in groups[0])
    assert np.allclose(np.asarray(w).sum(-1), 2.5, atol=1e-5)
    assert (np.asarray(margin) >= 0).all()


# -- the operations and bytes, counted by hand ---------------------------------------------

def test_flops_by_hand():
    cfg = config()
    # one KDA layer's mixer: W_q, W_k, W_v, W_f 2560 -> 4096, W_o back,
    # W_b and W_g 2560 -> 32, the convolution's 4 taps on 12288 channels
    assert F.mixer_params(cfg, 0) == (4 * 2560 * 4096 + 4096 * 2560
                                      + 2 * 2560 * 32 + 4 * 12288)
    # the MLA layer: W_q 2560 -> 32 x 192, W_kva -> 576, W_kvb 512 ->
    # 32 x 256, W_o 4096 -> 2560
    assert F.mixer_params(cfg, 5) == (2560 * 6144 + 2560 * 576
                                      + 512 * 8192 + 4096 * 2560)
    # one MoE share's layer: the router over 512, the shared expert, and
    # 8 x 128 / 512 = 2 held experts a token on average
    assert F.routed_per_token(cfg) == 2.0
    assert F.layer_token_params(cfg, 2) == F.mixer_params(cfg, 2) + (
        2560 * 512 + 3 * 2560 * 768 + 2 * 3 * 2560 * 768)
    assert F.layer_token_params(cfg, 0) == F.mixer_params(cfg, 0) \
        + 3 * 2560 * 6144
    # the delta rule: 8 H d^2 a recurrence row; the chunk form's
    # 2 H (5 C d + 3 d^2 + 6 C^2) at C = 16; a state of 32 x 128 x 128 f32
    assert F.step_row_flops(cfg) == 8 * 32 * 128 * 128
    assert F.chunk_row_flops(cfg) == 2 * 32 * (5 * 16 * 128
                                               + 3 * 128 * 128 + 6 * 256)
    assert F.state_bytes(cfg) == 2 * 2 ** 20
    fl, by = F.delta_cost(cfg, 6 * 64, 6 * 256)
    assert by == 6 * 64 * 2 * 2 * 2 ** 20        # 1.61 GB: the issue's
    assert fl == 6 * 64 * 8 * 32 * 128 ** 2 + 6 * 256 * F.chunk_row_flops(
        cfg)
    # a decode row at position 999: six KDA rows, the MLA layer's 1,000
    # keys, the head
    one = F.span_forward_flops(cfg, 999, 1000, 1)
    assert one == (F.token_matmul_flops(cfg, head=False)
                   + 6 * F.step_row_flops(cfg)
                   + 2 * 2560 * 39296 + 2 * 32 * 320 * 1000)
    # the held experts hit: three matrices each, read once (the latent
    # family's function, which moe_experts_roofline reads for this cell)
    from benchmark.harness import flops_latent_moe
    fl, by = flops_latent_moe.expert_stack_cost(cfg, 128.0, 80.0)
    assert fl == 2 * 3 * 2560 * 768 * 128
    assert by == 2 * (3 * 2560 * 768 * 80 + 2 * 2560 * 16)


# -- the readers -----------------------------------------------------------------------

def _run_with(counters, tr=None, peaks=None):
    cell = spec.load(CELL)
    run = Run(cfg=cell.config, mix=cell.traffic, peaks=peaks, chips=1)
    run.counters, run.trace = counters, tr
    return run


def reader(name):
    return spec.reader(ROOT, "layer_metrics", name)


NEW = ("kda_scan_roofline", "mfu_pct.kda_moe_rate",
       "moe_experts_roofline")


def test_the_new_readers_without_a_trace_or_the_counters():
    from benchmark.harness.device import PEAKS
    for name in NEW:
        assert reader(name)(_run_with({})) is None
        assert reader(name)(_run_with(
            {"delta_layer_steps": 6.0, "delta_lane_steps": 384.0,
             "moe_experts_hit": 80.0, "moe_assignments": 128.0},
            None, PEAKS["TPU v5 lite"])) is None


def test_the_trace_readers_on_a_recorded_step():
    """One step of the cell on a TPU v5e: the first step of a traced
    window's head (``record_trace_head.py``), operation names cut to 120
    characters, gzipped."""
    import gzip

    from benchmark.harness.device import PEAKS
    cell = spec.load(CELL)
    with gzip.open(DATA / "trace_kda_moe_head.json.gz") as f:
        loaded = json.loads(f.read())
    tr = trace.reduce(loaded, chips=1)
    (name, times), = tr.module_s.items()
    assert name.startswith("jit__unknown") and len(times) == 1
    peaks = PEAKS["TPU v5 lite"]
    pattern = cell.traffic["kda_op_match"]
    secs = tr.op_seconds(pattern)
    assert 0 < secs < times[0]
    names = list(tr.op_self_s)
    matched = [n for n in names if re.search(pattern, n)]
    assert any("f32[65,32,128,128]" in n for n in matched)    # the states
    assert not any("39296" in n for n in matched)             # not the head
    assert not any("45160" in n for n in matched)             # nor the pool
    # one chunk step: 6 KDA layers, 64 live lanes, a chunk of 256 rows
    counters = {"delta_layer_steps": 6.0, "delta_lane_steps": 6 * 64.0,
                "delta_rows_chunked": 6 * 256.0,
                "moe_layer_steps": 5.0, "moe_assignments": 5 * 640.0,
                "moe_experts_hit": 5 * 128.0}
    run = _run_with(counters, tr, peaks)
    fl, by = F.delta_cost(cell.config, 6 * 64.0, 6 * 256.0)
    least = max(fl / peaks["bf16_flops"], by / peaks["hbm_bytes_per_s"])
    share = reader("kda_scan_roofline")(run)
    assert share == pytest.approx(100 * least / secs)
    assert 0 < share <= 100
    moe = reader("moe_experts_roofline")(run)
    assert moe is not None and 0 < moe <= 100
    # nothing matches: None, not 0
    run.mix = dict(run.mix, kda_op_match="no such op",
                   moe_op_match="no such op")
    assert reader("kda_scan_roofline")(run) is None
    assert reader("moe_experts_roofline")(run) is None
    # one request's last chunk and 63 decode rows at position 3,000
    from benchmark.harness.stats import ReqRecord
    run = _run_with(counters, tr, peaks)
    run.t0, run.t1 = 10.0, 10.05
    first = ReqRecord(0, 9.0, np.zeros(2560, np.int32), 3)
    first.stamps = [10.01]
    rest = ReqRecord(1, 9.0, np.zeros(3000, np.int32), 80)
    rest.stamps = [9.0] + [10.02] * 63
    run.records = [first, rest]
    need = F.span_forward_flops(cell.config, 0, 2560, 1) + sum(
        F.span_forward_flops(cell.config, 3000 + j, 3001 + j, 1)
        for j in range(63))
    assert reader("mfu_pct.kda_moe_rate")(run) == pytest.approx(
        100 * need / (tr.busy_s * 197e12))


# -- a state not carried comes out as not correct -------------------------------------------

FORGET = """
import sys
import jax.numpy as jnp
from paddle_tpu.models import kda_moe
real = kda_moe.KimiDeltaAttention.run
def forgetful(self, u, tail, state, live=None, n_live=None):
    return real(self, u, tail, jnp.zeros_like(state), live, n_live)
kda_moe.KimiDeltaAttention.run = forgetful
from benchmark.run import main
sys.exit(main(sys.argv[1:]))
"""


def test_a_state_not_carried_comes_out_not_correct_in_rehearsal():
    env = {**os.environ, "JAX_PLATFORMS": "cpu", "PYTHONPATH": str(ROOT),
           "BENCH_RUN": "ignored"}
    p = subprocess.run(
        [sys.executable, "-c", FORGET, "--workload", CELL, "--seed",
         "3000000019", "--seconds", "1", "--trace", "0", "--rehearse"],
        cwd=ROOT, env=env, capture_output=True, text=True, timeout=280)
    assert p.returncode == 0, p.stderr[-3000:]
    line = json.loads(p.stdout.strip().splitlines()[-1])
    assert line["correct"] is False and line["failed"] == 0
    assert line["compared"]["logprob_err_p50"]["value"] > 0.001
