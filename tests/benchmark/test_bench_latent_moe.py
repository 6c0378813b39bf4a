"""What PR 27 added to the benchmark for the latent-attention,
sparse-expert family: the plain reference (its int8 control separates,
experts in groups equal experts at once), the FLOPs functions by
hand-counted cases, the published keys of the configuration, and the new
trace readers on a recorded trace head."""
import json
import re
from pathlib import Path

import numpy as np
import pytest

from benchmark.harness import flops_latent_moe as F
from benchmark.harness import reference_latent_moe as R
from benchmark.harness import spec, trace
from benchmark.harness import weights_latent_moe as W
from benchmark.harness.window import Run

ROOT = Path(__file__).resolve().parents[2]
DATA = Path(__file__).parent / "data"
CELL = "joyai-flash.serve.longout"
SMALL = dict(vocab_size=320, hidden_size=128, intermediate_size=256,
             moe_intermediate_size=64, num_hidden_layers=3,
             num_attention_heads=4, q_lora_rank=48, kv_lora_rank=32,
             qk_nope_head_dim=16, qk_rope_head_dim=8, v_head_dim=16,
             n_routed_experts=16, num_experts_per_tok=4, n_shared_experts=1,
             first_k_dense_replace=1, routed_scaling_factor=2.5,
             norm_topk_prob=True, rms_norm_eps=1e-6, rope_theta=32e6,
             torch_dtype="float32", initializer_range=0.02)
IDS = np.random.default_rng(5).integers(0, 320, (1, 48)).astype(np.int32)


@pytest.fixture(scope="module")
def weights():
    return W.make(2147483747, SMALL)


def logits(w, cfg=SMALL, **kw):
    return np.asarray(R.logits_at(w, cfg, IDS, np.arange(48), block=16,
                                  **kw))


def test_groups_of_experts_equal_the_experts_at_once(weights):
    want = logits(weights, group_size=16)
    assert want.std() > 0.1
    assert np.abs(logits(weights, group_size=4) - want).max() < 1e-5
    assert np.abs(logits(weights, group_size=5) - want).max() < 1e-5
    blocks = logits(weights, vocab_block=128)
    assert blocks.shape == (48, 320)
    assert np.abs(blocks - want).max() < 1e-6


@pytest.mark.parametrize("prec", ["int8", "fp8"])
def test_the_control_is_coarser_than_the_reference(weights, prec):
    err = np.abs(logits(weights, prec=prec) - logits(weights)).max()
    assert 1e-3 < err < 1.0


def test_the_bias_is_drawn_and_the_norm_weights_are_one(weights):
    lw = weights["layers"][1]
    assert float(np.abs(np.asarray(lw["router_bias"])).max()) > 1e-3
    assert np.asarray(lw["kv_a_ln"]).tolist() == [1.0] * 32
    assert "router" not in weights["layers"][0]       # the leading dense one
    again = W.make(2147483747, SMALL)["layers"][2]["w_up"]
    assert np.array_equal(np.asarray(again),
                          np.asarray(weights["layers"][2]["w_up"]))
    other = W.make(2147483748, SMALL)["layers"][2]["w_up"]
    assert not np.array_equal(np.asarray(other), np.asarray(again))


def test_a_share_of_the_experts_is_the_reference_of_that_share(weights):
    """``experts_held``: routing over all, the sum over those held."""
    cut = dict(SMALL, experts_held=[4, 8])
    w = dict(weights, layers=[
        lw if "router" not in lw else dict(
            lw, **{n: lw[n][4:12] for n in ("w_gate", "w_up", "w_down")})
        for lw in weights["layers"]])
    assert W.leaf_shapes(cut)["layers"][1]["w_gate"] == (8, 128, 64)
    part = logits(w, cut)
    assert np.abs(part - logits(weights)).max() > 1e-3
    assert np.isfinite(part).all()


def published():
    cfg = json.loads((ROOT / "benchmark/configs/"
                      "joyai-llm-flash.serve-L5.json").read_text())
    return cfg


def test_flops_by_hand_at_the_published_widths():
    cfg = published()
    assert F.attention_params(cfg) == (
        2048 * 1536 + 1536 * 32 * 192 + 2048 * 576 + 512 * 32 * 256
        + 32 * 128 * 2048) == 26_345_472
    assert F.expert_params(cfg) == 3 * 2048 * 768 == 4_718_592
    assert F.layer_token_params(cfg, 0) == 26_345_472 + 3 * 2048 * 7168
    assert F.layer_token_params(cfg, 1) == (
        26_345_472 + 2048 * 256 + 9 * 4_718_592)
    assert F.pair_flops(cfg) == 2 * 32 * (192 + 128)
    per_tok = 2 * (F.layer_token_params(cfg, 0)
                   + 4 * F.layer_token_params(cfg, 1))
    assert F.token_matmul_flops(cfg, head=False) == per_tok
    assert F.token_matmul_flops(cfg) == per_tok + 2 * 2048 * 129280
    # one decode position at 0-based position 9 sees 10 keys in 5 layers
    assert F.span_forward_flops(cfg, 9, 10, 1) == (
        per_tok + 2 * 2048 * 129280 + 20480 * 5 * 10)
    # a prompt of 4: 1 + 2 + 3 + 4 keys, the head once
    assert F.span_forward_flops(cfg, 0, 4, 1) == (
        4 * per_tok + 2 * 2048 * 129280 + 20480 * 5 * 10)
    fl, by = F.expert_stack_cost(cfg, assignments=256, experts_hit=162)
    assert fl == 2 * 4_718_592 * 256
    assert by == 2 * (4_718_592 * 162 + 2 * 2048 * 32)
    secs, bound = F.roofline_seconds(fl, by, {"bf16_flops": 197e12,
                                              "hbm_bytes_per_s": 819e9})
    assert bound == "memory" and secs == pytest.approx(by / 819e9)


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


def test_the_configuration_file_holds_the_catalogs_numbers():
    cfg = published()
    assert cfg["num_hidden_layers"] == 5
    assert cfg["reduced"] == {"num_hidden_layers": {"published": 40,
                                                    "here": 5}}
    shapes = W.leaf_shapes(cfg)
    n = sum(int(np.prod(s)) for s in _leaves(shapes))
    assert n == 5_558_141_952             # 11.12 GB in bfloat16
    assert shapes["layers"][1]["w_gate"] == (256, 2048, 768)
    eng = cfg["engine"]
    worst = -(-eng["max_seq_len"] // eng["page_size"])
    mix = json.loads((ROOT / "benchmark/traffic/longout.json").read_text())
    watermark = -(-(eng["num_pages"] - 1) * 5 // 100)
    assert mix["clients"] * worst + watermark <= eng["num_pages"] - 1
    assert mix["driver"] == "serve_latent_moe"


def _leaves(tree):
    if isinstance(tree, tuple):
        yield tree
    elif isinstance(tree, dict):
        for v in tree.values():
            yield from _leaves(v)
    else:
        for v in tree:
            yield from _leaves(v)


def test_the_cell_reports_what_the_contract_asks():
    cell = spec.load(CELL)
    assert cell.chips == 1
    assert {m["name"] for m in cell.end_to_end} == {"serve_tok_s", "setup_s"}
    names = {m["name"] for m in cell.per_layer}
    assert {"moe_experts_hit_mean", "moe_load_max_over_mean",
            "mfu_pct.moe_rate", "moe_experts_roofline",
            "step_decode_ms.rate", "device_idle_pct.rate"} <= names
    assert "mfu_pct.rate" not in names     # its FLOPs are the Llama shape's


def _run_with(counters, tr=None, peaks=None):
    cell = spec.load(CELL)
    run = Run(cfg=cell.config, mix=cell.traffic, peaks=peaks, chips=1)
    run.counters, run.trace = counters, tr
    return run


def reader(name):
    return spec.reader(ROOT, "layer_metrics", name)


def test_counter_readers_and_a_program_without_the_counters():
    c = {"moe_assignments": 2 * 4 * 32 * 8.0, "moe_experts_hit": 8 * 150.0,
         "moe_expert_load_max": 8 * 5.0, "moe_layer_steps": 8.0}
    assert reader("moe_experts_hit_mean")(_run_with(c)) == 150.0
    # the fullest expert holds 5 tokens where the mean of those hit is
    # 256 / 150
    assert reader("moe_load_max_over_mean")(_run_with(c)) == pytest.approx(
        5.0 / (256 / 150))
    # the parent's program has no such counter: nothing to read, no error
    for name in ("moe_experts_hit_mean", "moe_load_max_over_mean",
                 "moe_experts_roofline", "mfu_pct.moe_rate"):
        assert reader(name)(_run_with({})) is None


def _record(weights, cfg, alter=None, logprobs=True):
    """A request whose tokens are the reference's own greedy choices
    (teacher-forced on a random continuation), served with the
    reference's log-probabilities."""
    import jax

    from benchmark.harness.stats import ReqRecord
    prompt, cont = IDS[0, :16], IDS[0, 16:40]
    ids = np.zeros((1, 48), np.int32)
    ids[0, :40] = IDS[0, :40]
    pos = 15 + np.arange(24)
    ref = R.logits_at(weights, cfg, ids, pos, block=16)
    lsm = np.asarray(jax.nn.log_softmax(ref, -1))
    r = ReqRecord(0, 0.0, prompt, 24)
    r.tokens = [int(t) for t in cont]
    if alter is not None:
        r.tokens[alter] = (r.tokens[alter] + 1) % 320
    r.logprobs = [float(lsm[i, t]) for i, t in enumerate(r.tokens)] \
        if logprobs else []
    r.finished = 1.0
    return r


def test_the_check_reads_all_tokens_and_the_surely_routed_ones(weights):
    from benchmark.harness import check_latent_moe as C
    cfg = dict(SMALL, check={"sure_margin": 0.004})
    sound = C.served_against_reference(weights, cfg,
                                       [_record(weights, cfg)], pad_to=48,
                                       control="int8")
    assert sound["logprob_err_max"] < 1e-5 and sound["short_answers"] == 0
    assert sound["logprob_err_p50"] < 1e-5
    assert sound["sure_logprob_err_max"] < 1e-5
    n_sure = sound["_compared"]["sure_tokens"]
    assert 0 < n_sure < sound["_compared"]["tokens"] == 24
    # a margin no token reaches: the two sure numbers have nothing to say
    none = C.served_against_reference(
        weights, dict(SMALL, check={"sure_margin": 1.0}),
        [_record(weights, cfg)], pad_to=48)
    assert none["_compared"]["sure_tokens"] == 0
    assert none["sure_logit_gap_max"] == none["sure_logprob_err_max"] == 0.0
    # the control reads coarser on the same positions
    assert sound["_control"]["logprob_err_p50"] > 1e-3
    # a served token without its log-probability, and no request at all
    bare = C.served_against_reference(
        weights, cfg, [_record(weights, cfg, logprobs=False)], pad_to=48)
    assert bare["logprob_err_max"] == bare["logprob_err_p50"] == np.inf
    empty = C.served_against_reference(weights, cfg, [], pad_to=48)
    assert empty["sure_logit_gap_max"] == empty["logprob_err_p50"] == np.inf


def test_the_limits_file_judges_what_the_check_reads():
    from benchmark.harness import check
    cfg = published()
    numbers = {"logit_gap_max": 0.8, "logprob_err_max": 0.9,
               "logprob_err_p50": 0.0093, "sure_logit_gap_max": 0.02,
               "sure_logprob_err_max": 0.03, "short_answers": 0.0,
               "unfinished_requests": 0.0}
    ok, table = check.judge(numbers, cfg["limits"], cfg["not_compared"])
    assert ok and set(table) == set(cfg["limits"])
    # the int8 control's readings on the chip (PERF.md section 6)
    ctrl = dict(numbers, logprob_err_p50=0.099, sure_logprob_err_max=0.27)
    assert not check.judge(ctrl, cfg["limits"], cfg["not_compared"])[0]
    assert set(cfg["rehearse_limits"]) == set(numbers)


def test_the_trace_readers_on_a_recorded_trace_head():
    """The first quarter second of a traced window of the cell on a TPU
    v5e (``record_trace_head.py``): two chunk-carrying steps of 88 ms."""
    from benchmark.harness.device import PEAKS
    from benchmark.harness.stats import ReqRecord
    cell = spec.load(CELL)
    tr = trace.reduce(json.loads(
        (DATA / "trace_latent_moe_head.json").read_text()), chips=1)
    (name, times), = tr.module_s.items()
    assert name.startswith("jit__unknown") and times == [
        pytest.approx(0.088116, rel=1e-3), pytest.approx(0.087793, rel=1e-3)]
    # the three products over the expert stacks, four layers, two steps:
    # down(+up) 3.45 ms and gate 1.93 ms a layer a step
    secs = tr.op_seconds(cell.traffic["moe_op_match"])
    assert secs == pytest.approx(0.042998435)
    hit = [k for k in tr.op_self_s
           if re.search(cell.traffic["moe_op_match"], k)]
    assert len(hit) == 8 and all("[256," in k for k in hit)
    peaks = PEAKS["TPU v5 lite"]
    # two steps x four layers, every expert hit, 160 tokens a step
    counters = {"moe_layer_steps": 8.0, "moe_experts_hit": 8 * 256.0,
                "moe_assignments": 8 * 160 * 8.0,
                "moe_expert_load_max": 8 * 12.0}
    run = _run_with(counters, tr, peaks)
    least = 8 * 2 * (256 * 4_718_592 + 2 * 2048 * 160) / 819e9
    share = reader("moe_experts_roofline")(run)
    assert share == pytest.approx(100 * least / secs)
    assert 50 < share < 60
    # nothing matches: None, not 0
    run.mix = dict(run.mix, moe_op_match="no such op")
    assert reader("moe_experts_roofline")(run) is None
    # one request prefilled (128 tokens) and two more tokens decoded
    run = _run_with(counters, tr, peaks)
    run.t0, run.t1 = 10.0, 10.25
    r = ReqRecord(0, 9.0, np.zeros(128, np.int32), 3)
    r.stamps = [10.1, 10.15, 10.2]
    run.records = [r]
    need = (F.span_forward_flops(cell.config, 0, 128, 1)
            + F.span_forward_flops(cell.config, 128, 129, 1)
            + F.span_forward_flops(cell.config, 129, 130, 1))
    assert reader("mfu_pct.moe_rate")(run) == pytest.approx(
        100 * need / (tr.busy_s * 197e12))
    assert reader("mfu_pct.moe_rate")(run) < 1.0
