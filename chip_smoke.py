"""chip_smoke.py — the quickest proof that the system still starts on the chip.

One process drives the two hot paths through the entry points a user
calls, at the full width of the repo's own ``LlamaConfig.llama2_7b``
(hidden 4096, FFN 11008, 32 heads of 128, vocab 32000, bf16) with only
depth cut to what one 16 GB chip holds, weights random from ``--seed``:

- **train** — ``paddle_tpu.Model(...).prepare(AdamW(multi_precision=True),
  LlamaPretrainingCriterion)`` fed by a ``paddle_tpu.io.DataLoader`` with
  two worker processes: a few ``train_batch`` steps, then one
  ``train_batch_loop``. Checked against a plain float32 ``jax.numpy``
  forward of the same weights, and the Pallas kernels against the XLA
  reference attention.
- **serve** — ``ServingEngine`` (its token-packed step) behind
  ``ServingServer`` in a thread of this process; HTTP completions,
  streaming and not, checked token by token against
  ``model.generate()`` (the static-cache path), which is itself checked
  against the plain forward.

``--chips 4`` runs instead, and only, the two cross-chip paths and what
they are compared with: the fleet SPMD stepper (sharding stage 3 × mp 2)
against the same model on one of the chips, and
``ServingEngine(tp_degree=4)`` against ``tp_degree=1``.

``--rehearse`` runs the same control flow at a tiny size on the CPU with
the Pallas kernels in interpret mode, so a test can drive it. Without it
the script refuses any platform but ``tpu``. No other knob.

Every line it prints before the last is one JSON object of smoke facts
(not benchmark numbers). The last line is the contract's:
``{"ok": true, "device": {"platform": ..., "kind": ..., "count": ...}}``.
"""
from __future__ import annotations

import argparse
import faulthandler
import functools
import gc
import http.client
import json
import sys
import threading
import time
from dataclasses import dataclass

import numpy as np


@dataclass(frozen=True)
class Sizes:
    hidden: int
    ffn: int
    heads: int
    vocab: int
    dtype: str
    # train: depth, batch, sequence, steps of the compiled loop
    train_layers: int
    train_batch: int
    train_seq: int
    loop_steps: int
    # serve: depth, pool geometry, request mix
    serve_layers: int
    page_size: int
    num_pages: int
    max_batch: int
    prefill_chunk: int
    max_seq_len: int
    prompt_lens: tuple
    shared_prefix: int
    new_tokens: int
    # four chips: the fleet step's global batch
    fleet_batch: int


# LlamaConfig.llama2_7b widths, unchanged. Depth is the one cut: ~202 M
# params per layer + 262 M embed/head; training keeps 14 B/param (bf16 +
# f32 master and two moments), serving 2 B/param beside a pool the
# undonated step holds twice (PERF.md). The compiler's memory_analysis()
# for these sizes is in PERF.md.
REAL = Sizes(hidden=4096, ffn=11008, heads=32, vocab=32000,
             dtype="bfloat16",
             train_layers=2, train_batch=2, train_seq=1024, loop_steps=4,
             serve_layers=8, page_size=16, num_pages=512, max_batch=8,
             prefill_chunk=64, max_seq_len=256,
             prompt_lens=(24, 24, 40, 40, 100, 100, 150, 150),
             shared_prefix=64, new_tokens=16, fleet_batch=4)

# the rehearsal: same control flow, shapes the interpret-mode kernel
# still accepts (seq a multiple of 128, head_dim 64; four heads for
# tp_degree=4), float32 so greedy streams cannot tie
TINY = Sizes(hidden=256, ffn=512, heads=4, vocab=256, dtype="float32",
             train_layers=1, train_batch=2, train_seq=128, loop_steps=4,
             serve_layers=1, page_size=4, num_pages=128, max_batch=8,
             prefill_chunk=8, max_seq_len=48,
             prompt_lens=(5, 5, 7, 7, 12, 12, 20, 20),
             shared_prefix=8, new_tokens=6, fleet_batch=4)

# stated tolerances, by dtype: (first-step loss vs the f32 plain forward
# — absolute, on a loss near ln(vocab); kernel out / grads vs the XLA
# reference attention — max abs on unit-normal inputs)
TOL = {"bfloat16": dict(loss=5e-2, fa_fwd=5e-2, fa_bwd=1e-1),
       "float32": dict(loss=1e-3, fa_fwd=2e-3, fa_bwd=5e-3)}


def say(**facts):
    print(json.dumps(facts), flush=True)


def check(cond, what):
    if not cond:
        raise SystemExit(f"chip_smoke: FAILED: {what}")


class CompileClock:
    """Seconds jax spent tracing, lowering and compiling (or fetching
    from the persistent cache), summed from its own monitoring events."""

    def __init__(self):
        import jax.monitoring
        self.total = 0.0
        jax.monitoring.register_event_duration_secs_listener(self._on)

    def _on(self, name, secs, **_):
        if name.startswith("/jax/core/compile/"):
            self.total += secs

    def facts_since(self, t0, c0):
        """How every phase line ends (t0, c0 = the wall clock and this
        clock's total when the phase began)."""
        return dict(
            compile_seconds=round(self.total - c0, 1),
            wall_seconds=round(time.perf_counter() - t0, 1),
            peak_bytes_in_use=memory_stat("peak_bytes_in_use"))


def device_record():
    import jax
    d = jax.devices()[0]
    return {"platform": d.platform, "kind": d.device_kind,
            "count": len(jax.devices())}


def memory_stat(key):
    """Per device; the CPU backend reports none."""
    import jax
    return [(d.memory_stats() or {}).get(key) for d in jax.devices()]


def build_model(sz, layers, seed, **kw):
    import paddle_tpu as P
    from paddle_tpu.models import LlamaConfig, LlamaForCausalLM
    P.seed(seed)
    cfg = LlamaConfig.llama2_7b(
        hidden_size=sz.hidden, intermediate_size=sz.ffn,
        num_attention_heads=sz.heads, vocab_size=sz.vocab,
        num_hidden_layers=layers, dtype=sz.dtype,
        max_position_embeddings=max(sz.train_seq, sz.max_seq_len), **kw)
    model = LlamaForCausalLM(cfg)
    if sz.dtype != "float32":
        model.to(dtype=sz.dtype)
    return cfg, model


def config_facts(sz, layers):
    """What every phase line says about the model it ran."""
    return dict(
        device=device_record(),
        config="llama2_7b widths: hidden %d ffn %d heads %dx%d vocab %d %s"
               % (sz.hidden, sz.ffn, sz.heads, sz.hidden // sz.heads,
                  sz.vocab, sz.dtype),
        depth_cut=f"{layers} of 32 layers")


# ---------------------------------------------------------------------------
# the plain reference: float32 jax.numpy, independent of the package's
# layers, kernels and caches. One jitted layer applied in a Python loop,
# so only one layer's weights are ever upcast at a time.


@functools.lru_cache(maxsize=None)
def _plain_fns(heads, eps, theta):
    import jax
    import jax.numpy as jnp

    def rms(x, w):
        return x * jax.lax.rsqrt(
            jnp.mean(x * x, axis=-1, keepdims=True) + eps) * w

    def rope(x):
        s, d = x.shape[1], x.shape[-1]
        inv = 1.0 / theta ** (jnp.arange(0, d, 2, dtype=jnp.float32) / d)
        f = jnp.arange(s, dtype=jnp.float32)[:, None] * inv
        sin, cos = jnp.sin(f)[None, :, None], jnp.cos(f)[None, :, None]
        x1, x2 = x[..., :d // 2], x[..., d // 2:]
        return jnp.concatenate([x1 * cos - x2 * sin,
                                x2 * cos + x1 * sin], -1)

    @jax.jit
    def embed(table, ids):
        return table.astype(jnp.float32)[ids]

    @jax.jit
    def layer(x, w):
        w = {k: v.astype(jnp.float32) for k, v in w.items()}
        b, s, hdim = x.shape
        h = rms(x, w["ln1"])
        q, k, v = (jnp.reshape(h @ w[n], (b, s, heads, hdim // heads))
                   for n in ("q", "k", "v"))
        sc = jnp.einsum("bqhd,bkhd->bhqk", rope(q), rope(k))
        sc = sc / (hdim // heads) ** 0.5
        sc = jnp.where(jnp.tril(jnp.ones((s, s), bool)), sc, -jnp.inf)
        o = jnp.einsum("bhqk,bkhd->bqhd", jax.nn.softmax(sc, -1), v)
        x = x + o.reshape(b, s, hdim) @ w["o"]
        h = rms(x, w["ln2"])
        return x + (jax.nn.silu(h @ w["gate"]) * (h @ w["up"])) @ w["down"]

    @jax.jit
    def head(x, norm_w, head_w):
        return rms(x, norm_w.astype(jnp.float32)) @ \
            head_w.astype(jnp.float32)

    return embed, layer, head


def plain_logits(model, ids):
    """[B, S, V] float32 logits of ``model``'s weights by the plain
    forward (no tensor of the package is touched but its raw arrays)."""
    import jax
    cfg = model.cfg
    w = {n: p._data for n, p in model.named_parameters()}
    embed, layer, head = _plain_fns(cfg.num_attention_heads,
                                    cfg.rms_norm_eps, cfg.rope_theta)
    with jax.default_matmul_precision("highest"):
        x = embed(w["llama.embed_tokens.weight"], ids)
        for i in range(cfg.num_hidden_layers):
            pre = f"llama.layers.{i}."
            x = layer(x, {
                "ln1": w[pre + "input_layernorm.weight"],
                "ln2": w[pre + "post_attention_layernorm.weight"],
                "q": w[pre + "self_attn.q_proj.weight"],
                "k": w[pre + "self_attn.k_proj.weight"],
                "v": w[pre + "self_attn.v_proj.weight"],
                "o": w[pre + "self_attn.o_proj.weight"],
                "gate": w[pre + "mlp.gate_proj.weight"],
                "up": w[pre + "mlp.up_proj.weight"],
                "down": w[pre + "mlp.down_proj.weight"]})
        return head(x, w["llama.norm.weight"], w["lm_head.weight"])


def plain_loss(model, ids):
    """Shifted next-token cross entropy of the plain forward."""
    import jax
    import jax.numpy as jnp
    lp = jax.nn.log_softmax(plain_logits(model, ids)[:, :-1], -1)
    tok = jnp.take_along_axis(lp, jnp.asarray(ids)[:, 1:, None], -1)
    return float(-jnp.mean(tok))


# ---------------------------------------------------------------------------
# kernel parity (the checks of the former gated on-chip test module)


def kernel_parity(sz, seed):
    """fa_forward / fa_backward, compiled for this device, against the
    XLA reference attention: the train step's own shape, then GQA,
    packed segments, an additive mask and cross-length on a short
    sequence. Returns {family: (fwd_err, bwd_err)} and checks each
    against TOL."""
    import jax
    import jax.numpy as jnp

    from paddle_tpu.ops.pallas import flash_attention as fa
    from paddle_tpu.ops.pallas._fa_kernel import fa_backward, fa_forward

    interp = fa._FORCE_INTERPRET
    dt = jnp.dtype(sz.dtype)
    d = sz.hidden // sz.heads
    rng = np.random.default_rng(seed)
    tol = TOL[sz.dtype]

    def rand(*shape):
        return jnp.asarray(rng.standard_normal(shape), dt)

    def err(a, b):
        return jnp.max(jnp.abs(a.astype(jnp.float32)
                               - b.astype(jnp.float32)))

    def family(q, k, v, bwd=True, **kw):
        """One compiled program per family: kernel forward (+ backward)
        and the reference with its vjp; only two scalars come back."""
        @jax.jit
        def run(q, k, v, g, kw):
            out, lse = fa_forward(q, k, v, causal=True, return_lse=True,
                                  interpret=interp, **kw)
            want, vjp = jax.vjp(
                lambda q_, k_, v_: fa._ref_ext(
                    q_, k_, v_, kw.get("mask"), kw.get("q_seg"),
                    kw.get("kv_seg"), True, None), q, k, v)
            if not bwd:
                return err(out, want), None
            got = fa_backward(q, k, v, out, lse, g, causal=True,
                              interpret=interp, **kw)
            return err(out, want), jnp.max(jnp.stack(
                [err(a, b) for a, b in zip(got, vjp(g))]))
        e_fwd, e_bwd = run(q, k, v, rand(*q.shape), kw)
        return float(e_fwd), None if e_bwd is None else float(e_bwd)

    b, s, h = sz.train_batch, sz.train_seq, sz.heads
    short = min(s, 512)
    seg = jnp.asarray(np.searchsorted(
        [short // 3, 2 * short // 3], np.arange(short),
        side="right")[None].repeat(b, 0).astype(np.int32))
    mask = jnp.asarray(np.where(rng.random((b, 1, short, short)) < 0.15,
                                -np.inf, 0.0).astype(np.float32))
    kvh = max(1, h // 4)
    errs = {
        "train_shape": family(rand(b, s, h, d), rand(b, s, h, d),
                              rand(b, s, h, d)),
        "gqa": family(rand(b, short, h, d), rand(b, short, kvh, d),
                      rand(b, short, kvh, d)),
        "packed_segments": family(rand(b, short, h, d),
                                  rand(b, short, h, d),
                                  rand(b, short, h, d), q_seg=seg,
                                  kv_seg=seg),
        "additive_mask": family(rand(b, short, h, d),
                                rand(b, short, h, d),
                                rand(b, short, h, d), mask=mask),
        "cross_length": family(rand(b, 128, h, d), rand(b, short, kvh, d),
                               rand(b, short, kvh, d), bwd=False),
    }
    for name, (e_fwd, e_bwd) in errs.items():
        check(e_fwd < tol["fa_fwd"], f"kernel {name} fwd err {e_fwd}")
        check(e_bwd is None or e_bwd < tol["fa_bwd"],
              f"kernel {name} bwd err {e_bwd}")
    return errs


# ---------------------------------------------------------------------------
# train


class _RepeatedBatch:
    """Token sequences from the seed; sample i repeats sample i % batch,
    so every batch the loader yields is the same batch (the loss must
    fall on it). __getitem__ returns numpy only: the loader's forked
    workers never touch jax."""

    def __init__(self, sz, seed, n_batches):
        self.rows = np.random.default_rng(seed).integers(
            0, sz.vocab, (sz.train_batch, sz.train_seq)).astype(np.int32)
        self.n = n_batches * sz.train_batch

    def __len__(self):
        return self.n

    def __getitem__(self, i):
        return self.rows[i % len(self.rows)]


def _on_device(arrays, platform):
    return all(dev.platform == platform
               for a in arrays for dev in a.devices())


def train_state_arrays(model, opt):
    import jax
    params = [p._data for p in model.parameters()]
    states = jax.tree.leaves([opt._accum[id(p)]
                              for p in model.parameters()])
    return params, states


def phase_train(sz, seed, clock):
    import jax

    import paddle_tpu as P
    from paddle_tpu.io import DataLoader
    from paddle_tpu.models import LlamaPretrainingCriterion
    from paddle_tpu.ops.pallas import flash_attention as fa

    t0, c0 = time.perf_counter(), clock.total
    platform = jax.devices()[0].platform
    parity = kernel_parity(sz, seed)

    fa.reset_dispatch_stats()
    cfg, model = build_model(sz, sz.train_layers, seed,
                             fuse_linear_cross_entropy=True)
    data = _RepeatedBatch(sz, seed, n_batches=3)
    # before the optimizer state exists: the f32 reference needs room
    ref_loss = plain_loss(model, data.rows)

    crit = LlamaPretrainingCriterion(cfg).bind(model)
    opt = P.optimizer.AdamW(1e-4, parameters=model.parameters(),
                            multi_precision=True)
    m = P.Model(model)
    m.prepare(opt, crit)
    # num_workers=2 forks while this process holds the chip
    loader = DataLoader(data, batch_size=sz.train_batch, num_workers=2)
    losses = [m.train_batch([x], [x]) for x in loader]
    check(len(losses) == 3, f"loader yielded {len(losses)} batches")
    xs = P.to_tensor(np.broadcast_to(
        data.rows, (sz.loop_steps,) + data.rows.shape).copy())
    loop = np.asarray(m.train_batch_loop([xs], [xs])._data)  # host fetch
    losses += [float(v) for v in loop]

    check(all(np.isfinite(losses)) and losses[-1] < losses[0],
          f"loss not finite and falling on a repeated batch: {losses}")
    tol = TOL[sz.dtype]["loss"]
    check(abs(losses[0] - ref_loss) < tol,
          f"first-step loss {losses[0]} vs plain f32 forward {ref_loss} "
          f"(tolerance {tol})")
    stats = fa.dispatch_stats()
    check(stats["fallback"] == 0 and stats["pallas"] > 0,
          f"flash attention dispatch {stats}: the kernel must run, "
          "never the XLA fallback")
    params, states = train_state_arrays(model, opt)
    check(_on_device(params + states, platform),
          "params / optimizer state not on the device")
    n_params = sum(int(np.prod(p.shape)) for p in params)
    say(phase="train", smoke_facts_not_benchmark=True,
        **config_facts(sz, sz.train_layers), params=n_params,
        batch=sz.train_batch, seq=sz.train_seq,
        steps=len(losses), tokens=len(losses) * sz.train_batch
        * sz.train_seq,
        losses=[round(v, 4) for v in losses],
        plain_f32_first_loss=round(ref_loss, 4), loss_tolerance=tol,
        kernel_parity_max_abs_err={k: v for k, v in parity.items()},
        flash_dispatch=stats, dataloader_workers=2,
        **clock.facts_since(t0, c0))


# ---------------------------------------------------------------------------
# serve


def make_prompts(sz, seed):
    """Mixed lengths; each equal-length pair shares its first
    ``shared_prefix`` tokens where it is long enough."""
    rng = np.random.default_rng(seed + 1)
    prompts, base = [], {}
    for n in sz.prompt_lens:
        p = rng.integers(0, sz.vocab, n).astype(np.int32)
        if n > sz.shared_prefix:
            if n in base:
                p[:sz.shared_prefix] = base[n][:sz.shared_prefix]
            base.setdefault(n, p)
        prompts.append(p)
    return prompts


def _http(host, port, method, path, body=None, timeout=900):
    c = http.client.HTTPConnection(host, port, timeout=timeout)
    try:
        c.request(method, path,
                  None if body is None else json.dumps(body),
                  {"Content-Type": "application/json"})
        r = c.getresponse()
        return r.status, r.read()
    finally:
        c.close()


def _complete(host, port, prompt, new_tokens, stream):
    status, data = _http(host, port, "POST", "/v1/completions", {
        "prompt": [int(t) for t in prompt], "max_tokens": new_tokens,
        "stream": stream})
    check(status == 200, f"completion status {status}: {data[:200]!r}")
    if not stream:
        return json.loads(data)["choices"][0]["token_ids"]
    lines = data.decode().splitlines()
    check("data: [DONE]" in lines, "stream ended without [DONE]")
    chunks = [json.loads(ln[6:]) for ln in lines
              if ln.startswith("data: {")]
    return [c["choices"][0]["token_id"] for c in chunks
            if "token_id" in c["choices"][0]]


def serve_over_http(engine, prompts, new_tokens):
    """All prompts at once through ServingServer, odd ones streaming;
    the second of each shared-prefix pair follows in a second wave so
    the first has registered its pages. Returns (tokens, healthz,
    metrics text)."""
    from paddle_tpu.serving import ServingServer
    srv = ServingServer(engine, stream_timeout_s=900.0)
    host, port = srv.start()
    out, errors = [None] * len(prompts), []

    def fire(i):
        try:
            out[i] = _complete(host, port, prompts[i], new_tokens,
                               stream=bool(i % 2))
        except BaseException as e:  # surfaced after the join
            errors.append((i, repr(e)))

    try:
        for wave in (range(0, len(prompts), 2),
                     range(1, len(prompts), 2)):
            threads = [threading.Thread(target=fire, args=(i,))
                       for i in wave]
            for t in threads:
                t.start()
            for t in threads:
                t.join(timeout=900)
            check(not any(t.is_alive() for t in threads),
                  "a completion did not return in 900 s")
            check(not errors, f"completions failed: {errors}")
        hs, hbody = _http(host, port, "GET", "/healthz")
        ms, mbody = _http(host, port, "GET", "/metrics")
        check(hs == 200 and ms == 200, f"/healthz {hs} /metrics {ms}")
    finally:
        srv.close(timeout=60)
    return out, json.loads(hbody), mbody.decode()


def tie_noise(err):
    """The logit gap below which two candidates count as tied: two
    programs each within err of the f32 truth can differ from each other
    by twice that; half as much again because err was measured at one
    position only. A wrong token is off by a large part of the logits'
    std, an order of magnitude more."""
    return max(3 * err, 1e-4)


def compare_streams(model, prompts, got, want, noise):
    """Token-exact, or — where two bf16 programs of different shapes
    part ways — parted at a position the plain f32 forward calls a tie:
    the two candidates' reference logits closer than ``noise``, the
    error of the model's own logits against that reference as measured
    in this run. Returns (n_exact, [divergence facts]); anything else
    fails."""
    import jax.numpy as jnp
    exact, ties = 0, []
    for i, (p, g, w) in enumerate(zip(prompts, got, want)):
        check(len(g) == len(w), f"stream {i}: {len(g)} tokens, "
                                f"wanted {len(w)}")
        if list(g) == list(w):
            exact += 1
            continue
        j = next(k for k, (a, b) in enumerate(zip(g, w)) if a != b)
        ids = np.concatenate([p, np.asarray(w[:j], np.int32)])[None]
        row = np.asarray(plain_logits(model, jnp.asarray(ids))[0, -1])
        gap = abs(float(row[g[j]]) - float(row[w[j]]))
        check(gap < noise,
              f"stream {i} diverges at token {j} ({g[j]} vs {w[j]}) "
              f"and it is no tie: f32 logit gap {gap} >= {noise}")
        ties.append({"stream": i, "at": j, "f32_logit_gap": gap})
    return exact, ties


def generate_oracle(model, prompts, new_tokens):
    import jax.numpy as jnp
    return [np.asarray(model.generate(
        jnp.asarray(p)[None], max_new_tokens=new_tokens)._data)[0].tolist()
        for p in prompts]


def static_cache_vs_plain(model, prompt):
    """The static-cache path's first decode step against the plain
    forward. Returns (its argmax, the max abs logit error — the run's
    measure of the model dtype's noise —, the reference logits' std)."""
    import jax.numpy as jnp
    ids = jnp.asarray(prompt)[None]
    caches = model._init_caches(1, len(prompt) + 1)
    got = np.asarray(model._forward_cached(ids, caches, 0)[0]
                     .astype(jnp.float32))[0, -1]
    want = np.asarray(plain_logits(model, ids))[0, -1]
    err = float(np.max(np.abs(got - want)))
    std = float(np.std(want))
    check(err < 0.1 * std, f"static-cache logits off the plain forward "
                           f"by {err} (logit std {std})")
    return int(np.argmax(got)), err, std


def phase_serve(sz, seed, clock):
    from paddle_tpu.serving import ServingEngine

    t0, c0 = time.perf_counter(), clock.total
    cfg, model = build_model(sz, sz.serve_layers, seed)
    model.eval()
    prompts = make_prompts(sz, seed)
    want = generate_oracle(model, prompts, sz.new_tokens)
    first, err, ref_std = static_cache_vs_plain(model, prompts[0])
    check(first == want[0][0], "generate()'s first token is not the "
                               "argmax of its own prefill logits")
    noise = tie_noise(err)

    engine = ServingEngine(
        model, page_size=sz.page_size, num_pages=sz.num_pages,
        max_batch=sz.max_batch, prefill_chunk=sz.prefill_chunk,
        max_seq_len=sz.max_seq_len, prefix_cache=True)
    got, health, metrics = serve_over_http(engine, prompts,
                                           sz.new_tokens)
    exact, ties = compare_streams(model, prompts, got, want, noise)
    compiled = engine._ragged_fn._cache_size()
    classes = int(engine.metrics.step_program_classes.value)
    check(compiled == classes <= 2,
          f"step programs compiled {compiled}, classes {classes}: "
          "the step is bounded at 2")
    check(health["status"] in ("ok", "draining")
          and health["platform"] == device_record()["platform"],
          f"/healthz {health}")
    check("paddle_tpu_serving" in metrics or "# TYPE" in metrics,
          "/metrics is not a Prometheus exposition")
    say(phase="serve", smoke_facts_not_benchmark=True,
        **config_facts(sz, sz.serve_layers),
        pool={"pages": sz.num_pages, "page_size": sz.page_size,
              "bytes": engine.cache.bytes_total,
              "held_twice_by_undonated_step": True},
        requests=len(prompts), prompt_lens=list(sz.prompt_lens),
        prefill_chunk=sz.prefill_chunk, new_tokens=sz.new_tokens,
        tokens=sum(len(g) for g in got),
        streams_exact_vs_generate=exact, near_tie_divergences=ties,
        static_cache_vs_plain_f32_logit_err=err, logit_std=ref_std,
        step_programs_compiled=compiled,
        step_dispatches=int(engine.metrics.step_dispatches.value),
        prefix_hit_pages=int(engine.metrics.prefix_hit_pages.value),
        healthz_platform=health["platform"],
        **clock.facts_since(t0, c0))


# ---------------------------------------------------------------------------
# four chips


def _sharded_over(arrays, n):
    """Every array lives on n distinct devices, 1/n of it on each."""
    return all(len({s.device for s in a.addressable_shards}) == n
               and all(s.data.size * n == a.size
                       for s in a.addressable_shards)
               for a in arrays)


def _balanced(sizes):
    """bytes_in_use of chips 1.. within 2x of chip 0, either way."""
    if any(s is None for s in sizes):   # the CPU reports none
        return True
    return all(sizes[0] / 2 <= s <= sizes[0] * 2 for s in sizes[1:])


def phase_fleet_train(sz, seed, clock):
    """Sharding stage 3 x mp 2 over four chips against the same model,
    seed and batch on one of them."""
    import jax

    import paddle_tpu as P
    from paddle_tpu.distributed import fleet
    from paddle_tpu.distributed.fleet import DistributedStrategy
    from paddle_tpu.models import LlamaPretrainingCriterion
    from paddle_tpu.ops.pallas import flash_attention as fa

    t0, c0 = time.perf_counter(), clock.total
    n_dev, steps = len(jax.devices()), 3
    ids_np = np.random.default_rng(seed).integers(
        0, sz.vocab, (sz.fleet_batch, sz.train_seq)).astype(np.int32)

    def build():
        cfg, model = build_model(sz, sz.train_layers, seed,
                                 fuse_linear_cross_entropy=True,
                                 tensor_parallel=True)
        crit = LlamaPretrainingCriterion(cfg).bind(model)
        opt = P.optimizer.AdamW(1e-4, parameters=model.parameters(),
                                multi_precision=True)
        return model, crit, opt

    # what it is compared with: the single-device stepper on chip 0
    # (before fleet.init — Model routes through fleet once it is up)
    model, crit, opt = build()
    m = P.Model(model)
    m.prepare(opt, crit)
    ids = P.to_tensor(ids_np)
    one_chip = [m.train_batch([ids], [ids]) for _ in range(steps)]
    del m, model, crit, opt
    gc.collect()

    fa.reset_dispatch_stats()
    strategy = DistributedStrategy()
    strategy.sharding = True
    strategy.sharding_configs = {"stage": 3, "sharding_degree": 2}
    strategy.hybrid_configs = {"dp_degree": 1, "mp_degree": 2,
                               "sharding_degree": 2}
    fleet.init(is_collective=True, strategy=strategy)
    model, crit, opt = build()
    opt = fleet.distributed_optimizer(opt)
    dmodel = fleet.distributed_model(model)
    ids = P.to_tensor(ids_np)
    sharded = [float(dmodel.train_batch([ids], [ids], opt, crit).numpy())
               for _ in range(steps)]

    # the first step is a pure forward; later ones compound the
    # difference through the optimizer, so they get a relative bound
    tol, rel = TOL[sz.dtype]["loss"], 0.05
    check(all(np.isfinite(sharded)) and sharded[-1] < sharded[0],
          f"fleet losses {sharded}")
    check(abs(sharded[0] - one_chip[0]) < tol
          and all(abs(a - b) < rel * b
                  for a, b in zip(sharded[1:], one_chip[1:])),
          f"fleet losses {sharded} vs one chip {one_chip} (first step "
          f"within {tol}, later steps within {rel:.0%})")
    stats = fa.dispatch_stats()
    check(stats["fallback"] == 0 and stats["pallas"] > 0,
          f"flash attention under the mesh: {stats} — the kernel must "
          "run on each shard, not be demoted")
    params, states = train_state_arrays(model, opt)
    big = [a for a in params + states if a.ndim >= 2]
    check(_sharded_over(big, n_dev),
          "a parameter or optimizer state is not split over all chips")
    in_use = memory_stat("bytes_in_use")
    check(_balanced(in_use), f"bytes_in_use unbalanced: {in_use}")
    say(phase="fleet_train", smoke_facts_not_benchmark=True,
        **config_facts(sz, sz.train_layers),
        mesh={"sharding": 2, "mp": 2}, sharding_stage=3,
        batch=sz.fleet_batch, seq=sz.train_seq,
        losses_four_chips=[round(v, 4) for v in sharded],
        losses_one_chip=[round(v, 4) for v in one_chip],
        loss_tolerance={"first_step_abs": tol, "later_steps_rel": rel},
        flash_dispatch=stats,
        flash_attention="pallas kernel per shard under shard_map",
        bytes_in_use=in_use,
        **clock.facts_since(t0, c0))
    # leave no mesh behind for the serving phase
    from paddle_tpu.distributed.fleet.fleet import _state
    from paddle_tpu.distributed.fleet.topology import \
        set_hybrid_communicate_group
    _state.initialized = False
    set_hybrid_communicate_group(None)


def _run_engine(engine, prompts, new_tokens):
    rids = [engine.add_request(p, max_new_tokens=new_tokens)
            for p in prompts]
    done = engine.run()
    return [list(done[r]["tokens"]) for r in rids]


def phase_tp_serve(sz, seed, clock):
    """ServingEngine(tp_degree=4) against tp_degree=1 on one chip."""
    import jax

    from paddle_tpu.serving import ServingEngine

    t0, c0 = time.perf_counter(), clock.total
    n_dev = len(jax.devices())
    cfg, model = build_model(sz, sz.serve_layers, seed)
    model.eval()
    prompts = make_prompts(sz, seed)
    kw = dict(page_size=sz.page_size, num_pages=sz.num_pages,
              max_batch=sz.max_batch, prefill_chunk=sz.prefill_chunk,
              max_seq_len=sz.max_seq_len)
    _, err, _ = static_cache_vs_plain(model, prompts[0])
    one = ServingEngine(model, **kw)
    want = _run_engine(one, prompts, sz.new_tokens)
    del one
    gc.collect()
    # the TP engine re-places the model's weights over the mesh, so it
    # runs second
    tp = ServingEngine(model, tp_degree=n_dev, **kw)
    got = _run_engine(tp, prompts, sz.new_tokens)
    exact, ties = compare_streams(model, prompts, got, want,
                                  tie_noise(err))
    weights = [t._data for t in model._gen_state_tensors()
               if t._data.ndim >= 2]
    pools = list(tp.cache.k_pages) + list(tp.cache.v_pages)
    check(_sharded_over(weights + pools, n_dev),
          "a weight or pool is not split over all chips")
    in_use = memory_stat("bytes_in_use")
    check(_balanced(in_use), f"bytes_in_use unbalanced: {in_use}")
    say(phase="tp_serve", smoke_facts_not_benchmark=True,
        **config_facts(sz, sz.serve_layers), tp_degree=n_dev,
        requests=len(prompts),
        tokens=sum(len(g) for g in got),
        streams_exact_vs_tp1=exact, near_tie_divergences=ties,
        step_program_classes=int(tp.metrics.step_program_classes.value),
        bytes_in_use=in_use,
        **clock.facts_since(t0, c0))


# ---------------------------------------------------------------------------


def main():
    ap = argparse.ArgumentParser(description=__doc__.split("\n\n")[0])
    ap.add_argument("--seed", type=int, default=0)
    ap.add_argument("--chips", type=int, choices=(1, 4), default=1)
    ap.add_argument("--rehearse", action="store_true")
    args = ap.parse_args()

    # the contract's limit is 1200 s: past 1100 dump every thread's
    # stack and exit non-zero rather than hang
    faulthandler.dump_traceback_later(1100, exit=True)

    import jax
    if args.rehearse:
        jax.config.update("jax_platforms", "cpu")
        jax.config.update("jax_num_cpu_devices", args.chips)
    dev = jax.devices()[0]
    if dev.platform != "tpu" and not args.rehearse:
        sys.exit(f"chip_smoke: needs a TPU, jax reports platform "
                 f"{dev.platform!r}; --rehearse is the only way it runs "
                 "on the CPU")
    if len(jax.devices()) != args.chips:
        sys.exit(f"chip_smoke: --chips {args.chips} but jax reports "
                 f"{len(jax.devices())} device(s)")

    from paddle_tpu.ops.pallas import flash_attention as fa
    from paddle_tpu.utils.compile_cache import enable_compile_cache
    sz = REAL
    if args.rehearse:
        # no persistent cache: XLA:CPU executables read back from one
        # log a machine-feature warning per load
        sz = TINY
        fa._FORCE_INTERPRET = True   # the tests' hook: kernels on CPU
    else:
        say(compile_cache_dir=enable_compile_cache())
    clock = CompileClock()

    if args.chips == 1:
        phase_train(sz, args.seed, clock)
        gc.collect()
        phase_serve(sz, args.seed, clock)
    else:
        phase_fleet_train(sz, args.seed, clock)
        gc.collect()
        phase_tp_serve(sz, args.seed, clock)
    say(compile_seconds_total=round(clock.total, 1))
    print(json.dumps({"ok": True, "device": device_record()}))


if __name__ == "__main__":
    main()
