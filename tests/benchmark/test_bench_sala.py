"""What the benchmark holds for MiniCPM-SALA's language model: the
configuration held to the rule of the cut, the weights by name, the
reference's recurrence and selection against NumPy, the FLOPs and bytes
functions by hand-counted cases, the cell's pinned metrics, the new
readers on a recorded trace head, and a rehearsal in which the control
and the three planted faults come out as not correct."""
import json
import os
import subprocess
import sys
from pathlib import Path

import numpy as np
import pytest

from benchmark.harness import flops_sala as F
from benchmark.harness import reference_sala as R
from benchmark.harness import spec, trace
from benchmark.harness import weights_sala as W
from benchmark.harness.window import Run

ROOT = Path(__file__).resolve().parents[2]
DATA = Path(__file__).parent / "data"
CELL = "minicpm-sala.serve.longdoc"
FILE = "benchmark/configs/minicpmsala/minicpm-sala.serve-L8.json"


def config():
    return json.loads((ROOT / FILE).read_text())


def entry():
    bench = json.loads((ROOT / "BENCHMARK.json").read_text())
    return next(c for c in bench["configs"] if c["file"] == FILE)


# -- the cut -----------------------------------------------------------------

def test_the_cut_is_the_published_config_but_for_the_depth():
    cfg, e = config(), entry()
    pub = spec.published(e["source"])
    spec.check_cut(e, cfg, pub)
    assert e["reduced"] == ["num_hidden_layers"]
    assert cfg["num_hidden_layers"] == 8 and cfg["first_layer"] == 22
    assert [W.kind(cfg, i) for i in range(8)] == (
        ["minicpm4"] + ["lightning-attn"] * 6 + ["minicpm4"])
    assert W.depth_published(cfg) == 32 == len(cfg["mixer_types"])
    for k in ("hidden_size", "intermediate_size", "head_dim",
              "num_key_value_heads", "lightning_nh", "vocab_size",
              "mixer_types", "scale_emb", "scale_depth", "dim_model_base"):
        assert cfg[k] == pub["keys"][k]
    with pytest.raises(spec.SpecError, match="head_dim"):
        spec.check_cut(e, dict(cfg, head_dim=64), pub)
    with pytest.raises(spec.SpecError, match="mixer_types"):
        spec.check_cut(e, dict(cfg, mixer_types=cfg["mixer_types"][22:30]),
                       pub)
    assert cfg["engine"]["page_size"] == cfg["sparse_config"]["block_size"]
    # 16 callers x 1,036 pages + the 5 % watermark + the scratch page
    per_seq = -(-cfg["engine"]["max_seq_len"] // 64)
    assert per_seq == 1036
    assert cfg["engine"]["num_pages"] == int(np.ceil(16 * per_seq * 1.05)) + 1
    for key in ("sparse_config", "the decay", "muP", "engine"):
        assert any(key in a for a in cfg["assumed"]), key


def test_the_weights_are_the_programs_leaves_by_name():
    cfg = config()
    shapes = W.leaf_shapes(cfg)
    sparse, light = shapes["layers"][0], shapes["layers"][1]
    assert sparse["k"] == (4096, 256) and sparse["g"] == (4096, 4096)
    assert light["q"] == (4096, 4096) and light["o_norm"] == (4096,)
    assert shapes["head"] == (4096, 73448)
    names = W.program_names(cfg)
    assert len(names) == 3 + sum(len(lyr) for lyr in shapes["layers"])
    n = sum(int(np.prod(s)) for lyr in shapes["layers"] for s in lyr.values())
    assert 2.2186e9 < n < 2.2190e9      # 2,218.6 M in matrices, and the norms
    assert 2 * 73448 * 4096 == pytest.approx(601.7e6, rel=1e-3)


# -- the reference against NumPy ---------------------------------------------

def test_the_reference_lightning_layer_is_the_recurrence_in_numpy():
    rng = np.random.default_rng(0)
    cfg = dict(config(), first_layer=22)
    logd = np.asarray(R.log_decay(cfg, 1))          # published layer 23
    slopes = 2.0 ** (-8.0 * np.arange(1, 33) / 32)
    np.testing.assert_allclose(logd, -slopes * (1 - 23 / 31 + 1e-5),
                               rtol=1e-6)
    # the token recurrence the lightning block scans, on a small state
    h, d, s = 2, 4, 7
    q, k, v = (rng.normal(size=(s, h, d)) for _ in range(3))
    lam = np.array([0.5, 0.9])
    st = np.zeros((h, d, d))
    want = []
    for t in range(s):
        st = lam[:, None, None] * st + np.einsum("hk,hv->hkv", k[t], v[t])
        want.append(np.einsum("hkv,hk->hv", st, q[t]))
    import jax
    import jax.numpy as jnp

    def step(st, row):
        q_t, k_t, v_t = row
        st = jnp.asarray(lam, jnp.float32)[:, None, None] * st + \
            jnp.einsum("hk,hv->hkv", k_t, v_t)
        return st, jnp.einsum("hkv,hk->hv", st, q_t)
    _, got = jax.lax.scan(step, jnp.zeros((h, d, d)),
                          tuple(jnp.asarray(a, jnp.float32)
                                for a in (q, k, v)))
    np.testing.assert_allclose(np.asarray(got), np.array(want), rtol=1e-5,
                               atol=1e-5)


def test_the_reference_selection_takes_init_window_and_top_k():
    sc = dict(kernel_size=4, kernel_stride=2, block_size=4, topk=2,
              init_blocks=1, window_size=8, dense_len=16)
    rng = np.random.default_rng(3)
    pos = np.array([10, 40])
    q = rng.normal(size=(2, 1, 2, 8)).astype(np.float32)
    ck = rng.normal(size=(20, 1, 8)).astype(np.float32)
    ck[10] = 50 * q[1, 0, 0]                         # block 5: the best
    sel = np.asarray(R.selection(q, ck, pos, sc, 11))[:, 0]
    assert sel[0, :3].all() and not sel[0, 3:].any()     # dense: p < 16
    # p = 40: block 0, the window's blocks 8-10, the top-2 of blocks 1-7
    assert sel[1, [0, 8, 9, 10]].all() and sel[1, 5]
    assert sel[1].sum() == 6


# -- the operations and bytes, counted by hand --------------------------------

def test_flops_by_hand():
    cfg = config()
    # a sparse layer's mixer: W_q, W_g, W_o 4096 x 4096, W_k, W_v 4096 -> 256
    assert F.mixer_params(cfg, 0) == 3 * 4096 * 4096 + 2 * 4096 * 256
    # a lightning layer's: W_q, W_k, W_v, W_g, W_o
    assert F.mixer_params(cfg, 1) == 5 * 4096 * 4096
    assert F.token_matmul_flops(cfg) == 2 * (
        2 * F.mixer_params(cfg, 0) + 6 * F.mixer_params(cfg, 1)
        + 8 * 3 * 4096 * 16384 + 4096 * 73448)
    # a selected block: 64 keys met by a group's 16 heads twice
    assert F.block_flops(cfg) == 4 * 16 * 64 * 128
    assert F.score_flops(cfg) == 2 * 32 * 128
    assert F.page_head_bytes(cfg) == 2 * 64 * 128 * 2
    # the least bytes are the pages selected; what is streamed past them
    # is the program's over-read, and counts nothing
    fl, by = F.sparse_cost(cfg, {"sparse_blocks_read": 10.0,
                                 "sparse_kernels_scored": 3.0,
                                 "sparse_pages_selected": 4.0,
                                 "sparse_pages_streamed": 40.0,
                                 "sparse_kernels_read": 8.0})
    assert fl == 10 * 4 * 16 * 64 * 128 + 3 * 2 * 32 * 128
    assert by == 4 * 2 * 64 * 128 * 2 + 8 * 128 * 2
    assert F.step_row_flops(cfg) == 5 * 32 * 128 ** 2
    assert F.chunk_row_flops(cfg) == 2 * 32 * (2 * 64 * 128 + 2 * 128 ** 2)
    assert F.state_bytes(cfg) == 2 * 2 ** 20
    fl, by = F.lightning_cost(cfg, {"lightning_lane_steps": 6 * 13.0,
                                    "lightning_rows_chunked": 6 * 512.0})
    assert by == 6 * 13 * 2 * 2 * 2 ** 20
    assert fl == 6 * 13 * F.step_row_flops(cfg) + 6 * 512 * \
        F.chunk_row_flops(cfg)
    c = {"batch_size_sum": 11.0, "lightning_rows_chunked": 6 * 512.0,
         "tokens_generated": 12.0, "lightning_lane_steps": 6 * 13.0,
         "sparse_blocks_read": 10.0}
    assert F.window_flops(cfg, c) == (
        523 * F.token_matmul_flops(cfg, head=False)
        + 12 * 2 * 4096 * 73448 + 10 * F.block_flops(cfg) + fl)


# -- the readers --------------------------------------------------------------

def _run_with(counters, tr=None, peaks=None):
    cell = spec.load(CELL)
    run = Run(cfg=cell.config, mix=cell.traffic, peaks=peaks, chips=1)
    run.counters, run.trace = counters, tr
    return run


def reader(name):
    return spec.reader(ROOT, "layer_metrics", name)


NEW = ("sparse_attn_roofline", "lightning_scan_roofline", "mfu_pct.sala_rate")


def test_the_cells_metrics_are_pinned():
    cell = spec.load(CELL)
    assert [m["name"] for m in cell.end_to_end] == ["serve_tok_s",
                                                    "setup_s"]
    assert {m["name"] for m in cell.per_layer} == {
        "decode_lanes_mean.rate", "step_ms.rate", "device_idle_pct.rate",
        "host_ms_per_step.rate", "sparse_blocks_over_live",
        "sparse_attn_roofline", "lightning_scan_roofline",
        "mfu_pct.sala_rate"}


def test_the_new_readers_without_a_trace_or_the_counters():
    from benchmark.harness.device import PEAKS
    for name in NEW:
        assert reader(name)(_run_with({})) is None
        assert reader(name)(_run_with(
            {"sparse_blocks_read": 9.0, "lightning_layer_steps": 6.0,
             "batch_size_sum": 11.0}, None, PEAKS["TPU v5 lite"])) is None
    assert reader("sparse_blocks_over_live")(_run_with({})) is None
    assert reader("sparse_blocks_over_live")(_run_with(
        {"sparse_blocks_read": 97.0, "sparse_blocks_live": 512.0})) == \
        pytest.approx(97 / 512)


def test_the_trace_readers_on_a_recorded_step():
    """The head of a traced window of the cell on a TPU v5e
    (``record_trace_head.py``), operation names cut to 160 characters,
    gzipped."""
    import gzip

    from benchmark.harness.device import PEAKS
    path = DATA / "trace_sala_head.json.gz"
    if not path.exists():
        pytest.fail("no recorded trace head")
    cell = spec.load(CELL)
    with gzip.open(path) as f:
        loaded = json.loads(f.read())
    tr = trace.reduce(loaded, chips=1)
    peaks = PEAKS["TPU v5 lite"]
    for key in ("sparse_op_match", "lightning_op_match"):
        secs = tr.op_seconds(cell.traffic[key])
        assert secs and 0 < secs < tr.busy_s
    names = list(tr.op_self_s)
    lightning = [n for n in names
                 if __import__("re").search(cell.traffic[
                     "lightning_op_match"], n)]
    assert not any("73448" in n for n in lightning)         # not the head
    # one chunk step of the cell: 11 decode lanes and a chunk of 512 rows
    # at 32k, 6 lightning layers, 2 sparse
    counters = {"lightning_layer_steps": 6.0, "lightning_lane_steps": 72.0,
                "lightning_rows_chunked": 6 * 512.0,
                "sparse_blocks_read": 2 * 2 * 523 * 97.0,
                "sparse_blocks_live": 2 * 2 * 523 * 512.0,
                "sparse_pages_selected": 2 * 2 * (11 * 97 + 160.0),
                "sparse_kernels_scored": 2 * 523 * 2047.0,
                "sparse_pages_streamed": 2 * 2 * (11 * 128 + 512.0),
                "sparse_kernels_read": 2 * 2 * 4 * 12 * 512.0,
                "batch_size_sum": 11.0, "tokens_generated": 11.0}
    run = _run_with(counters, tr, peaks)
    for name in NEW:
        share = reader(name)(run)
        assert share is not None and 0 < share <= 100, name
    fl, by = F.lightning_cost(cell.config, counters)
    least = max(fl / peaks["bf16_flops"], by / peaks["hbm_bytes_per_s"])
    assert reader("lightning_scan_roofline")(run) == pytest.approx(
        100 * least / tr.op_seconds(cell.traffic["lightning_op_match"]))
    run.mix = dict(run.mix, sparse_op_match="no such op",
                   lightning_op_match="no such op")
    assert reader("sparse_attn_roofline")(run) is None
    assert reader("lightning_scan_roofline")(run) is None


# -- the control and the planted faults in rehearsal ------------------------------

def test_the_control_and_the_three_faults_come_out_not_correct():
    env = {**os.environ, "JAX_PLATFORMS": "cpu", "PYTHONPATH": str(ROOT),
           "BENCH_RUN": "ignored"}
    p = subprocess.run(
        [sys.executable, "-m", "benchmark.run", "--workload", CELL, "--seed",
         "2999999929", "--seconds", "1", "--trace", "1", "--rehearse",
         "--control"], cwd=ROOT, env=env, capture_output=True, text=True,
        timeout=280)
    assert p.returncode == 0, p.stderr[-3000:]
    line = json.loads(p.stdout.strip().splitlines()[-1])
    assert line["correct"] is True and line["failed"] == 0
    assert line["control"]["correct"] is False
    assert line["fault_half_batch"]["correct"] is False
    assert line["info"]["fault_dense"]["correct"] is False
    assert line["info"]["fault_no_decay"]["correct"] is False
    assert "sparse_blocks_over_live" in line["metrics"]
    assert 0 < line["metrics"]["sparse_blocks_over_live"]["value"] < 1
