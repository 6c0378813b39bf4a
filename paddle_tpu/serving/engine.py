"""Continuous-batching inference engine over the paged KV cache.

Reference capability: the serving loop of vLLM / Paddle FastDeploy —
admission, chunked prefill, batched decode, preemption — realized
TPU-natively (SURVEY.md §7 static-shape stance):

- ONE token-packed step program: a step's plain decode lanes (one
  token each), speculative-verify lanes (up to k+1) and the prefill
  chunk are packed along one token axis (``attention.py::
  ragged_paged_attention`` lane layout) and run as one dispatch with
  one host fetch. Lanes are always ``max_batch + 1``; a decode/verify
  lane owns ``k1 = speculative_k + 1`` rows of a static rectangle and
  the chunk (the last lane) the rows behind it, so a row's place says
  its lane and each lane's page table is gathered once a layer. The
  token capacity is one of TWO static shapes (``max_batch * k1`` for a
  step with no chunk, ``max_batch * k1 + prefill_chunk`` with one), so
  the engine compiles at most two programs in its lifetime.
- Weights enter every compiled step as ARGUMENTS, never baked constants
  (the round-3 HTTP-413 lesson in models/generation.py): weight updates
  flow through with NO recompile and NO stale-constant hazard, and the
  serialized program stays O(HLO). Parameter-object replacement rewires
  positionally (order comes from the module tree, which is stable) —
  the same contract the generate() program cache relies on.
- Padded lanes are real lanes pointed at the cache's SCRATCH page: every
  program sees fully-defined fixed-shape operands; garbage lanes are
  masked on the host.
- The decode loop targets RoPE causal-LM families (LLaMA zoo shape:
  ``model.llama`` or a module exposing embed_tokens/layers/norm +
  lm_head); positions are computed analytically, so chunk padding can
  run past the context limit without a table clamp-gather hazard.

The engine is host-driven: ``step()`` runs one scheduler iteration
(decode-priority batch + at most one prefill chunk, one program),
advances request state, and ``run()`` loops until drained. All device
work is CPU-mesh testable; nothing here compiles a first-time Mosaic
kernel (the paged Pallas stub stays interpret-gated).

Decode hot path (round 10):

- **Sampling runs INSIDE the compiled step program**
  (:mod:`.sampling`): greedy/temperature/top-k/top-p with per-lane
  counter-based RNG driven by per-request ``(seed, token_index)`` int32
  ARGUMENTS, so the per-step host fetch is ``[B]`` int32 token ids plus
  ``[B]`` float32 logprobs (``fetch_bytes`` metric: <= B*8, down from
  B*V*4) and streams stay reproducible across preemption + recompute.
  The host numpy sampler remains the oracle path behind
  ``PADDLE_TPU_SERVING_HOST_SAMPLE=1`` (greedy is token-exact against
  it; sampled modes are distributionally checked).
- **Radix-tree prefix caching** (``prefix_cache=True`` or
  ``PADDLE_TPU_SERVING_PREFIX_CACHE=1``): ``add_request`` pins the
  longest cached prompt prefix, the scheduler admits on UNCACHED page
  need, and the prefill starts past the cached tokens and registers
  fresh full prompt pages back into the tree.
- Steps are staged through PERSISTENT per-capacity host buffers
  (``_ragged_bufs``) — no per-step np.zeros garbage on the hot path.

Batched speculative decoding (round 12):

- ``draft_model=``/``speculative_k=``: per decode round a small draft
  model proposes up to k tokens per running lane (ONE fused
  ``lax.scan`` program — k+1 draft steps, one dispatch); the lane
  then rides the step with k+1 tokens, and the step's sampler gives
  the target's own token at every one of them.
- Verification is DETERMINISTIC-SAMPLE MATCHING, not distributional
  rejection sampling: the verify step recomputes the target's own
  counter-RNG sample at every position (token ``t`` is pure in
  ``(weights, history, seed, t)`` — the PR-3 contract), and a draft
  proposal is accepted iff it EQUALS that sample. Every emitted token
  is therefore exactly what the non-speculative engine would have
  emitted — greedy AND seeded-sampled streams are token-exact, so
  router failover splicing and preemption recompute work unchanged.
  The draft shares the per-lane counter keys, so its Gumbel noise is
  correlated with the target's — a well-matched draft accepts at the
  argmax-agreement rate even for sampled lanes.
- Rejected positions roll back by ACCOUNTING only
  (``PagedKVCache.free_tail``): the garbage K/V stays masked by
  context_len and is overwritten when the lane grows again. The draft
  keeps its own (cheap, narrow) paged cache, rebuilt lazily after
  preemption/fork — draft-cache state can be dropped at ANY time
  without affecting output correctness, only the acceptance rate.
- Admission reserves each lane's worst-case round growth (k+1 tokens,
  ``Scheduler.spec_reserve_tokens``) so a verify burst never preempts
  a running decode; per-request opt-out rides ``speculative=False``.

Quantized serving (round 15):

- ``cache_dtype="int8"`` (or ``PADDLE_TPU_SERVING_KV_DTYPE``) selects
  the quantized paged cache: codes + per-(slot, head) f32 scales,
  quantized on append INSIDE the compiled step (deterministic — all
  recompute/failover/migration exactness contracts hold within the
  config), dequantized inline by ``paged_attention``; ~2x the bf16
  page capacity at an equal ``hbm_budget_mb``. The draft cache follows
  the SAME resolved dtype.
- ``weight_quant="int8"|"int4"`` (or
  ``PADDLE_TPU_SERVING_WEIGHT_QUANT``) swaps nn.Linear layers for
  weight-only-quantized storage (lm_head exempt); the quantized
  buffers ride every step as ARGUMENTS like all other weights.
"""
from __future__ import annotations

import functools
import json
import logging
import math
import os
import time

import numpy as np

from .chaos import ChaosConfig, ChaosInjector
from .distill import distill_buffer_from_env
from .kv_cache import (SCRATCH_PAGE, GeometryMismatch, LayerCache,
                       OutOfPages, PagedKVCache)
from .kvtier import KVTier, host_pool_from_env
from .metrics import ServingMetrics
from .sampling import filter_binds
from .scheduler import Request, RequestState, Scheduler
from .tp import resolve_tp
from .trace import ServingTrace

__all__ = ["EngineDraining", "FaultInjected", "ServingEngine"]

_log = logging.getLogger("paddle_tpu.serving")


class EngineDraining(RuntimeError):
    """Raised by add_request once drain() started — in-flight work
    finishes; new admissions are refused (the front-end maps it to
    HTTP 503)."""


class FaultInjected(RuntimeError):
    """The env-gated fault hook fired at a step boundary. Injected
    BEFORE any device work or state mutation, so the step is safely
    retryable — the front-end loop counts it and keeps stepping."""


class ServingEngine:
    @staticmethod
    def _validate_causal_lm(model, what="model"):
        cfg = getattr(model, "cfg", None)
        core = getattr(model, "llama", model)
        for attr in ("embed_tokens", "layers", "norm"):
            if not hasattr(core, attr):
                raise TypeError(
                    "ServingEngine needs a causal LM whose core "
                    "(model.llama, or the model itself) has "
                    "embed_tokens/layers/norm, the layers either "
                    "LLaMA-shaped (self_attn.q_proj/k_proj/v_proj/"
                    "o_proj + mlp) or bringing their own "
                    f"paged_forward; {what} {type(model).__name__} "
                    f"lacks {attr!r}")
        if not hasattr(model, "lm_head"):
            raise TypeError(f"{what} must expose lm_head")
        if cfg is None:
            raise TypeError(f"{what} must carry a .cfg")
        return cfg, core

    @staticmethod
    def _cache_layout(cfg, core):
        """The cache's make-up, a :class:`~.kv_cache.LayerCache` a
        layer, asked of EVERY layer (a model's first layer may own no
        pool): a layer that brings its own ``paged_forward`` says what
        it keeps through ``paged_cache``; a LLaMA-shaped one owns a
        full pool of K and V by head."""
        nh = cfg.num_attention_heads
        nkv = getattr(cfg, "num_key_value_heads", None) or nh
        plain = LayerCache(pool="full", n_kv_heads=nkv,
                           head_dim=cfg.hidden_size // nh)
        return tuple(getattr(layer, "paged_cache", plain)
                     for layer in core.layers)

    @classmethod
    def _cache_geometry(cls, cfg, core):
        """What one token holds in a full pool: ``(n_kv_heads,
        head_dim, latent_dim)``, the one geometry of the layers that
        own one (a latent pool: one entry of ``latent_dim`` a token a
        layer, shared by every head)."""
        geo = {(lc.n_kv_heads, lc.head_dim, lc.latent)
               for lc in cls._cache_layout(cfg, core) if lc.pool == "full"}
        if len(geo) != 1:
            raise NotImplementedError(
                f"the layers' full pools have {len(geo)} geometries "
                f"({sorted(geo)}): one allocator serves one")
        (nkv, hd, latent), = geo
        return nkv, hd, (hd if latent else None)

    @staticmethod
    def _resolve_cache_dtype(cache_dtype, cfg):
        """Resolve the KV cache dtype: explicit arg, else the
        PADDLE_TPU_SERVING_KV_DTYPE knob, else bfloat16-or-float32 from
        the model config. "int8" selects the quantized codes+scales
        layout (generation.py's proven recipe); other integer dtypes
        would astype-truncate K/V to garbage and are rejected."""
        import jax.numpy as jnp
        if cache_dtype is None:
            cache_dtype = os.environ.get(
                "PADDLE_TPU_SERVING_KV_DTYPE") or None
        if cache_dtype is None:
            return ("bfloat16" if getattr(cfg, "dtype", "float32")
                    == "bfloat16" else "float32")
        try:
            name = str(jnp.dtype(cache_dtype))
        except TypeError:
            name = str(cache_dtype)
        if name not in ("int8", "bfloat16", "float16", "float32"):
            raise ValueError(
                f"unsupported cache_dtype {cache_dtype!r}: use "
                "'int8' (quantized codes+scales) or a float dtype")
        return name

    def __init__(self, model, *, page_size=16, num_pages=None,
                 hbm_budget_mb=None, max_batch=8, prefill_chunk=32,
                 max_seq_len=None, eos_token_id=None, watermark_frac=0.05,
                 cache_dtype=None, on_event=None, prefix_cache=None,
                 draft_model=None, speculative_k=None,
                 weight_quant=None, chaos=None, host_pool=None,
                 distill=None, ragged=None, mesh=None, tp_degree=None):
        cfg, core = self._validate_causal_lm(model)
        if weight_quant is None:
            weight_quant = os.environ.get(
                "PADDLE_TPU_SERVING_WEIGHT_QUANT") or None
        if weight_quant not in (None, "int8", "int4"):
            raise ValueError(
                f"weight_quant must be 'int8', 'int4' or None, got "
                f"{weight_quant!r}")
        self.weight_quant = weight_quant
        if weight_quant:
            # decode is HBM-bound: int8/int4 weight storage halves/
            # quarters the bytes every step streams. lm_head stays full
            # precision (the usual LLM recipe, as in bench_generate).
            # The swapped-in qweight/scale are BUFFERS, so they ride
            # the compiled step as ARGUMENTS like every other weight
            # (never baked constants — the HTTP-413/stale-cache
            # contract holds). Converting an already-converted model is
            # a no-op (only exact nn.Linear instances are swapped).
            from ..nn.quant import convert_to_weight_only
            convert_to_weight_only(model,
                                   algo=f"weight_only_{weight_quant}",
                                   exclude=("lm_head",))
        self.model = model
        self._core = core
        nh = cfg.num_attention_heads
        nkv, hd, latent_dim = self._cache_geometry(cfg, core)
        layout = self._cache_layout(cfg, core)
        n_full = sum(lc.pool == "full" for lc in layout)
        self.max_seq_len = int(max_seq_len
                               or cfg.max_position_embeddings)
        maxpos = getattr(cfg, "max_position_embeddings", None)
        if maxpos is not None and self.max_seq_len > maxpos:
            raise ValueError(
                f"max_seq_len({self.max_seq_len}) exceeds "
                f"max_position_embeddings({maxpos})")
        cache_dtype = self._resolve_cache_dtype(cache_dtype, cfg)
        self.cache_dtype = cache_dtype
        # -- tensor-parallel SPMD step (round 23 / ISSUE 19) ----------------
        # resolve_tp returns None at degree <= 1, so the TP=1 hot path
        # carries zero TP code; heads must split evenly or the
        # per-shard q/kv slices would be ragged (loud at build time,
        # never silently at step time)
        self._tp = resolve_tp(mesh=mesh, tp_degree=tp_degree)
        if self._tp is not None and latent_dim is not None:
            raise NotImplementedError(
                "tensor parallelism (tp_degree > 1 / mesh) over a latent "
                "page pool is not built: one entry serves every head")
        if self._tp is not None and (nh % self._tp.degree
                                     or nkv % self._tp.degree):
            raise ValueError(
                f"tp_degree={self._tp.degree} must divide "
                f"num_attention_heads={nh} and num_key_value_heads="
                f"{nkv}")
        self.tp_degree = self._tp.degree if self._tp else 1
        self.tp_mesh_shape = self._tp.mesh_shape if self._tp else None
        self._tp_kernel_warned = False
        if prefix_cache is None:
            prefix_cache = os.environ.get(
                "PADDLE_TPU_SERVING_PREFIX_CACHE") == "1"
        self.cache = PagedKVCache(
            n_full, nkv, hd, page_size=page_size,
            num_pages=num_pages,
            hbm_budget_bytes=(int(hbm_budget_mb * 2 ** 20)
                              if hbm_budget_mb is not None else None),
            dtype=cache_dtype, prefix_cache=bool(prefix_cache),
            tp_degree=self.tp_degree, latent_dim=latent_dim,
            layout=layout, max_lanes=max_batch,
            prefill_chunk=prefill_chunk)
        self.max_pages_per_seq = math.ceil(
            self.max_seq_len / self.cache.page_size)
        # lane state or window pools beside the pages: what is not built
        # for them refuses by name (the cache itself refuses int8,
        # tp_degree, the prefix cache, forks and page shipping)
        if self.cache.mixed and (draft_model is not None or speculative_k):
            raise NotImplementedError(
                "speculative decoding (speculative_k > 0 / draft_model) "
                "beside a lane state is not built: a rejected draft "
                "needs the state rolled back")
        # layers that gather the full pools' tables in a step: the
        # owners and the layers that read another's
        self._pool_readers = sum(lc.pool == "full" or lc.reads is not None
                                 for lc in layout)
        # where the pools actually live (advertised in /healthz): a
        # process-backed fleet worker defaults to cpu, and a router
        # must be able to see that without touching jax itself
        self.platform = next(iter(
            self.cache.k_pages[0].devices())).platform
        # -- speculative decoding (round 12) -------------------------------
        self.draft = draft_model
        if draft_model is not None:
            dcfg, dcore = self._validate_causal_lm(draft_model,
                                                   what="draft_model")
            if getattr(dcfg, "vocab_size", None) != cfg.vocab_size:
                raise ValueError(
                    "draft and target models must share a vocab "
                    f"({dcfg.vocab_size} vs {cfg.vocab_size})")
            dmax = getattr(dcfg, "max_position_embeddings", None)
            if dmax is not None and self.max_seq_len > dmax:
                raise ValueError(
                    f"draft max_position_embeddings({dmax}) < "
                    f"max_seq_len({self.max_seq_len})")
            k = 4 if speculative_k is None else int(speculative_k)
            if not 1 <= k <= 16:
                raise ValueError(
                    f"speculative_k must be in [1, 16], got {k}")
            self.spec_k = k
            self._draft_core = dcore
            self._draft_window = getattr(dcfg, "sliding_window",
                                         None) or None
            dnkv, dhd, dlatent = self._cache_geometry(dcfg, dcore)
            if latent_dim is not None or dlatent is not None:
                # the cache refuses the rest by name (int8, tp_degree,
                # kvtier, page shipping); a draft is the engine's
                raise NotImplementedError(
                    "a draft model (speculative decoding) beside a "
                    "latent page pool is not built")
            # same page geometry/count as the target (token-capacity
            # parity), narrow per-page bytes (the draft is the cheap
            # model); no prefix cache — draft K/V is disposable state.
            # The dtype FOLLOWS the resolved cache_dtype (incl. int8):
            # a duplicated bf16-or-f32 decision here once let draft and
            # target caches silently diverge (regression-tested).
            self._draft_cache = PagedKVCache(
                dcfg.num_hidden_layers, dnkv, dhd, page_size=page_size,
                num_pages=self.cache.num_pages,
                dtype=self.cache_dtype)
        else:
            if speculative_k:
                raise ValueError("speculative_k needs a draft_model")
            self.spec_k = 0
            self._draft_cache = None
            self._draft_core = None
            self._draft_window = None
        if self._tp is not None:
            # committed placements: weights last-dim sharded, pools
            # head-sharded — both ride every compiled step as ARGUMENTS,
            # so the shardings persist across steps with no per-step
            # host work.  A DISTINCT draft model replicates instead:
            # its propose/catchup programs then stay byte-identical to
            # the TP=1 engine's draft (a self-draft shares the target's
            # sharded tensors; the verify contract keeps the emitted
            # stream exact regardless of draft numerics).
            self._tp.shard_model_weights(self.model)
            self._tp.shard_cache_pools(self.cache)
            if self.draft is not None and self.draft is not self.model:
                self._tp.shard_model_weights(self.draft,
                                             replicate=True)
        self.scheduler = Scheduler(self.cache, max_batch=max_batch,
                                   prefill_chunk=prefill_chunk,
                                   watermark_frac=watermark_frac,
                                   spec_reserve_tokens=self.spec_k)
        # -- the step (round 22 / PR 18; the only one since PR 29) --------
        # ONE token-packed program for mixed prefill+decode+verify
        # steps (attention.py::ragged_paged_attention lane layout).
        # ``ragged=`` selects nothing: the benchmark's drivers still
        # pass ragged=True, so the keyword stays until they drop it.
        if ragged not in (None, True):
            raise ValueError(
                "ServingEngine(ragged=False): the bucketed step was "
                "removed in PR 29; the token-packed step is the engine")
        self._ragged_fn = None        # one jit fn; <= 2 token shapes
        self._ragged_bufs = {}        # per-capacity persistent buffers
        # static geometry: L lanes always (max_batch decode/verify + 1
        # prefill), k1 = speculative_k + 1 rows a decode/verify lane;
        # token capacity is one of TWO shapes — a step with no prefill
        # chunk packs into the max_batch * k1 rows of the rectangle,
        # a step with one adds the chunk's rows behind it. That pins
        # the compiled-program-class count at <= 2.
        self._ragged_lanes = max_batch + 1
        self._ragged_tok_small = max_batch * (self.spec_k + 1)
        self._ragged_tok_mixed = self._ragged_tok_small + prefill_chunk
        self._program_classes = set()  # static shape keys dispatched
        self.metrics = ServingMetrics()
        # always-on span timeline + flight recorder (round 16): every
        # mutation happens from the thread that drives the engine —
        # i.e. under the front-end lock — so no new locking appears
        self.trace = ServingTrace()
        # capacity observability: with dtype="int8" the same HBM budget
        # yields ~2*D/(D+4) x the bf16 page count — surface the honest
        # per-page cost so a scrape can verify the sizing
        self.metrics.kv_page_bytes.set(self.cache.bytes_total
                                       / self.cache.num_pages)
        self.metrics.cache_bytes_per_token.set(self.cache.bytes_per_token)
        self.metrics.state_bytes_per_lane.set(
            self.cache.state_bytes_per_lane)
        # routing counts of sparse-expert layers, summed on the device
        # by the ragged step and fetched with its tokens
        self._moe_counts_dev = None
        self.eos = eos_token_id
        self.window = getattr(cfg, "sliding_window", None) or None
        self._draft_fn = None         # draft catchup prefill (trunk only)
        self._propose_fn = None       # fused k+1-step draft scan program
        self._logits_dev = None       # last step's on-device [T,V] logits
        self._logits_row = 0          # the row _last_logits_probe reads
        self._seed_rng = np.random.default_rng()  # seed=None fallback
        self._requests: dict[int, Request] = {}
        self._finished: dict[int, Request] = {}
        self._held: dict[int, Request] = {}   # "prefilled", pages kept
        self._rngs: dict[int, np.random.Generator] = {}
        # streaming callback: called synchronously with every event dict
        # the moment it is emitted (token/finish), from the thread that
        # runs step(). Must be cheap and non-blocking — the front-end
        # uses it to route tokens into per-request stream queues.
        self.on_event = on_event
        self._draining = False
        # unified chaos layer (round 17): ONE injector per engine —
        # accepts a ChaosInjector, a ChaosConfig, or None (env mode:
        # the legacy FAULT_* knobs keep working as aliases, re-read
        # per evaluation so monkeypatch-mid-test workflows still work)
        if isinstance(chaos, ChaosInjector):
            self.chaos = chaos
        else:
            assert chaos is None or isinstance(chaos, ChaosConfig)
            self.chaos = ChaosInjector(chaos, name="engine")
        self.chaos.bind(self.trace)
        self._chaos_spike = None  # (seq_id, steps_left) alloc pressure
        # hierarchical KV tier (round 20): host-RAM/disk page pools
        # behind the prefix cache.  ``host_pool=`` injects a (possibly
        # engine-shared) kvtier.HostPagePool; None resolves the
        # PADDLE_TPU_SERVING_HOST_POOL_* knobs.  Meaningless without
        # the prefix cache — nothing ever spills from a tree that
        # doesn't exist — so it is quietly absent there.
        if host_pool is None:
            host_pool = host_pool_from_env()
        if host_pool is not None and self.cache.prefix_cache_enabled:
            self.kvtier = KVTier(host_pool, chaos=self.chaos,
                                 metrics=self.metrics, trace=self.trace)
            self.cache.attach_tier(self.kvtier)
        else:
            self.kvtier = None
        # versioned live weight deployment (round 21): the per-set
        # version this engine is serving — 0 = the build-time weights.
        # Advertised in /healthz (frontend.health) and /metrics so the
        # router's version-pin skew guard reads it fresh.  Mutates only
        # through set_weights (graftlint weight-swap-lock).
        self.weight_version = {"target": 0, "draft": 0}
        # online draft distillation (round 21): when a DistillBuffer
        # rides here, the speculative verify loop logs one (history,
        # target-token) pair per emitted token — free hard-target
        # supervision for the draft.  None = logging off, the verify
        # loop pays nothing (distill= arg, else the knob).
        if distill is None:
            distill = distill_buffer_from_env()
        self.distill = distill

    # -- public API --------------------------------------------------------
    def add_request(self, prompt, max_new_tokens=32, *, deadline_s=None,
                    do_sample=False, temperature=1.0, top_k=0,
                    top_p=1.0, seed=None, n=1, logprobs=False,
                    request_id=None, speculative=None,
                    prefill_only=False):
        """Queue a request; returns its req_id (n>1 returns the PARENT id
        — forked children surface as their own req_ids in events). With
        the prefix cache on, the longest cached prompt prefix is PINNED
        here (so the front-end's reservation math, run under the same
        lock, can count only uncached pages without an eviction race)."""
        if self._draining:
            raise EngineDraining(
                "engine is draining: in-flight requests finish, new "
                "admissions are refused")
        prompt = np.asarray(prompt, np.int32).reshape(-1)
        if prompt.size == 0:
            raise ValueError("empty prompt")
        if int(max_new_tokens) < 1:
            raise ValueError(f"max_new_tokens={max_new_tokens}")
        total = prompt.size + int(max_new_tokens)
        if total > self.max_seq_len:
            raise ValueError(
                f"prompt({prompt.size}) + max_new_tokens"
                f"({max_new_tokens}) exceeds max_seq_len"
                f"({self.max_seq_len})")
        if n > 1 and not do_sample:
            raise ValueError("n>1 needs do_sample=True (greedy forks "
                             "would be identical streams)")
        if prefill_only and n > 1:
            raise ValueError(
                "prefill_only is incompatible with n>1: forks are "
                "created at prefill completion on the DECODE side of a "
                "migration, not the prefill side")
        if not 0.0 <= float(top_p) <= 1.0:
            raise ValueError(f"top_p={top_p} outside [0, 1]")
        if self.cache.mixed and (n > 1 or prefill_only):
            raise NotImplementedError(
                ("n > 1 (a fork needs the parent's lane state and window "
                 "pages copied)" if n > 1 else
                 "prefill_only (disagg: the lane state and the window "
                 "pools do not ship)")
                + " is not built for a cache with lane state or window "
                "pools beside its pages")
        now = self._now()
        req = Request(prompt=prompt, max_new_tokens=int(max_new_tokens),
                      arrival=now,
                      deadline=(now + deadline_s
                                if deadline_s is not None else None),
                      do_sample=bool(do_sample),
                      temperature=float(temperature), top_k=int(top_k),
                      top_p=float(top_p), seed=seed, n=int(n),
                      logprobs=bool(logprobs),
                      request_id=(str(request_id)
                                  if request_id is not None else None),
                      speculative=(None if speculative is None
                                   else bool(speculative)),
                      prefill_only=bool(prefill_only))
        req.device_seed = (int(seed) & 0x7FFFFFFF if seed is not None
                           else int(self._seed_rng.integers(
                               1, 2 ** 31 - 1)))
        self._requests[req.req_id] = req
        self._rngs[req.req_id] = np.random.default_rng(seed)
        tier_restored = 0
        if self.cache.prefix_cache_enabled:
            # host-tier restore FIRST (round 20), so the pages it lands
            # are pinned by the acquire below like any shipped prefix;
            # best-effort — a miss/failure just means recompute
            if self.kvtier is not None:
                tier_restored = self.kvtier.restore(self.cache, prompt)
            req.cached_pages = self.cache.acquire_prefix(
                req.seq_id, prompt, prompt.size)
        self.scheduler.add(req)
        if self.trace.enabled:
            self.trace.begin(req.req_id, req.request_id)
            self.trace.mark(req.req_id, "queued_t0", now)
            if req.cached_pages:
                self.trace.span(req.req_id, "prefix_hit", now,
                                pages=req.cached_pages)
            if tier_restored:
                self.trace.span(req.req_id, "tier_restore_hit", now,
                                pages=tier_restored)
            elif (self.kvtier is not None and req.cached_pages
                  < (prompt.size - 1) // self.cache.page_size):
                self.trace.span(req.req_id, "tier_restore_miss", now)
            self.trace.flight.record(
                "admit", req_id=req.req_id,
                request_id=req.request_id,
                prompt_tokens=int(prompt.size),
                max_new_tokens=int(max_new_tokens))
        return req.req_id

    def step(self):
        """One scheduler iteration. Returns a list of event dicts
        ({"type": "token"|"finish", "req_id", ...})."""
        self._maybe_inject_fault()
        was_training = [m for m in (self.model, self.draft)
                        if m is not None
                        and getattr(m, "training", False)]
        for m in was_training:
            m.eval()
        try:
            return self._step_inner()
        finally:
            for m in was_training:
                m.train()

    def _step_inner(self):
        now = self._now()
        out = self.scheduler.schedule(now)
        if self.trace.enabled:
            # composition FIRST, duration at the end: a loop failure
            # mid-step leaves the failing step's batch shape in the
            # ring for the post-mortem dump
            self.trace.flight.record(
                "step_begin",
                decode=len(out.decode),
                prefill=(out.prefill[0].req_id
                         if out.prefill is not None else None),
                expired=len(out.expired),
                waiting=self.scheduler.queue_depth())
        events = []
        for r in out.expired:  # graceful: pages freed, partial output kept
            if self.cache.has_seq(r.seq_id):
                self.cache.free_seq(r.seq_id)
            self._free_draft_seq(r.seq_id)
            self.metrics.deadline_evictions.inc()
            self._record_finish(r, events)
        self.sweep_held_deadlines(now)
        self._ragged_step(out, events)
        if not out.decode and out.prefill is None and not out.expired \
                and self.scheduler.waiting \
                and not self.scheduler.live_requests():
            # idle engine + blocked admission head: first give back any
            # prefix pins held by OTHER waiting requests (they re-match
            # at admission), then loud, not a silent spin — the request
            # can never fit
            req = self.scheduler.waiting[0]
            if not self._release_waiting_pins(exclude=req) \
                    and not self._release_chaos_spike():
                need = self.scheduler.worst_case_need(req)
                if need + self.scheduler.watermark_pages \
                        > self.cache.available_pages:
                    raise RuntimeError(
                        f"request {req.req_id} can never be admitted: "
                        f"needs {need} pages + "
                        f"{self.scheduler.watermark_pages} watermark > "
                        f"{self.cache.available_pages} available; grow "
                        "the cache budget or shrink the prompt")
        self.metrics.queue_depth.record(self.scheduler.queue_depth())
        self.metrics.page_occupancy.record(self.cache.occupancy())
        self.metrics.queue_depth_gauge.set(self.scheduler.queue_depth())
        self.metrics.page_occupancy_gauge.set(self.cache.occupancy())
        self.metrics.running_gauge.set(len(self.scheduler.running))
        if self.kvtier is not None:
            # drain deferred spills at the step boundary (the eviction
            # loop itself never serializes)
            self.kvtier.flush()
        self._sync_prefix_metrics()
        step_wall = self._now() - now
        self.metrics.step_duration_s.record(step_wall)
        if self.trace.enabled:
            self.trace.flight.record("step_end",
                                     wall_s=round(step_wall, 6),
                                     events=len(events))
        return events

    def run(self, max_steps=100000):
        """Step until every queued request finished; returns
        {req_id: {"tokens", "finish_reason", "preemptions"}}.

        On ANY failure the live requests' pages are returned to the free
        list (requests are requeued for recompute, generated tokens
        kept), so the engine stays reusable: a later run() retries them
        and — greedy or seeded — reproduces the uninterrupted streams.
        A step program that failed after it was handed the pools costs
        the prefix tree besides (``release_live``).
        """
        steps = 0
        try:
            while not self.scheduler.all_done():
                self.step()
                steps += 1
                if steps > max_steps:
                    raise RuntimeError(
                        f"serving loop did not drain in {max_steps} "
                        "steps (starvation or a stuck request)")
        except Exception:
            self.release_live()
            raise
        self._release_chaos_spike()  # chaos residue dies with the run
        return self.results()

    def cancel(self, req_id):
        """Cancel a live request: frees its KV pages, purges it from
        every scheduler queue, and emits a ``finish`` event with reason
        ``"cancelled"`` (partial output is kept in results()). Returns
        True if the request was live, False for unknown/finished ids.

        NOT safe to call concurrently with step() — the front-end
        serializes both under one lock; direct users call it between
        steps.
        """
        req = self._requests.get(req_id)
        if req is None:
            return False
        if req.state == RequestState.FINISHED:
            # a held ("prefilled") request is finished but still owns
            # pages awaiting export — cancellation must release them
            return self.release_request(req_id)
        if self.cache.has_seq(req.seq_id):
            self.cache.free_seq(req.seq_id)
        self._free_draft_seq(req.seq_id)
        self.scheduler.remove(req)
        req.state = RequestState.FINISHED
        req.finish_reason = "cancelled"
        self.metrics.cancellations.inc()
        if self.trace.enabled:
            self.trace.flight.record("cancel", req_id=req_id)
        self._record_finish(req, [])
        return True

    @property
    def draining(self):
        return self._draining

    def start_drain(self):
        """Refuse new admissions; everything already queued (waiting/
        prefilling/running) keeps going to completion."""
        self._draining = True
        if self.trace.enabled:
            self.trace.flight.record(
                "drain", live=len(self.scheduler.live_requests()),
                waiting=self.scheduler.queue_depth())

    def resume_admissions(self):
        """Lift drain mode (the rolling-drain re-admit path): a drained
        engine accepts new requests again. Weight reloads happen while
        drained — weights are ARGUMENTS of the compiled step, so the
        update flows through with no recompile; the prefix cache must
        be flushed by the caller (stale K/V of the OLD weights)."""
        self._draining = False

    def drain(self, max_steps=100000):
        """start_drain() + run(): finish all in-flight work while
        rejecting admissions; returns results()."""
        self.start_drain()
        return self.run(max_steps)

    def set_weights(self, which, arrays, version):
        """Versioned weight hot-swap (round 21) — the ONE blessed
        mutation site of a serving pytree (graftlint
        ``weight-swap-lock``); all multi-threaded use goes through
        ``ServingFrontend.swap_weights``, whose lock is the one-step
        quiesce.

        Weights are ARGUMENTS of every compiled step (``warrs`` /
        ``dwarrs`` are rebuilt from ``_gen_state_tensors`` per
        dispatch), so swapping ``t._data`` here takes effect on the
        very next step with NO recompile and no jit-cache
        invalidation.  All-or-nothing: the full payload is validated
        (count + shape per tensor) before the first write, so a torn
        push (``distill_push_torn``) leaves the old version serving.

        Target swaps flush the prefix cache — every cached page holds
        K/V computed under the OLD weights — which also detaches and
        invalidates the attached KV tier (spilled chains of the old
        version must never restore).  Draft swaps skip the flush:
        draft K/V is disposable state and the draft only PROPOSES;
        the target's verify step decides every emitted token, so a
        mid-stream draft refresh changes acceptance rate, never
        output."""
        import jax.numpy as jnp
        if which not in ("target", "draft"):
            raise ValueError(
                f"unknown weight set {which!r}; 'target' or 'draft'")
        model = self.model if which == "target" else self.draft
        if model is None:
            raise ValueError("engine has no draft model")
        tensors = model._gen_state_tensors()
        if len(arrays) != len(tensors):
            self.metrics.weight_swap_rejects.inc()
            raise ValueError(
                f"torn weight payload: {len(arrays)} array(s) for "
                f"{len(tensors)} tensors")
        staged = []
        for i, (t, a) in enumerate(zip(tensors, arrays)):
            a = np.asarray(a)
            if tuple(a.shape) != tuple(np.shape(t._data)):
                self.metrics.weight_swap_rejects.inc()
                raise ValueError(
                    f"weight {i} shape {a.shape} != "
                    f"{tuple(np.shape(t._data))}")
            staged.append(jnp.asarray(a, dtype=t._data.dtype))
        for t, a in zip(tensors, staged):
            t._data = a
        if self._tp is not None:
            # swapped arrays arrive host-resident: re-commit them to
            # the mesh placement or the next step compiles against
            # unsharded operands (a silent program-class change)
            self._tp.shard_model_weights(
                model, replicate=(which == "draft"
                                  and model is not self.model))
        flushed = 0
        if which == "target":
            flushed = self.cache.clear_prefix()
        self.weight_version[which] = int(version)
        m = self.metrics
        m.weight_swaps.inc()
        (m.weight_version_target if which == "target"
         else m.weight_version_draft).set(int(version))
        if self.trace.enabled:
            self.trace.flight.record(
                "weight_swap", which=which, version=int(version),
                tensors=len(tensors), prefix_flushed=flushed)
        return flushed

    def release_live(self):
        """Error path: free every live request's pages and requeue the
        requests (front of queue, recompute-style — generated tokens
        kept) so a failed run() leaves the allocator clean and the
        engine reusable. Where the failed step had already been handed
        the pools, they are gone with it: the caches are rebuilt empty
        (``PagedKVCache.recover_lost_pools``), prefix tree and all."""
        for r in self.scheduler.live_requests():
            if self.cache.has_seq(r.seq_id):
                self.cache.free_seq(r.seq_id)
            self._free_draft_seq(r.seq_id)
            self.scheduler.preempt(r)
        # WAITING requests hold pages too: add_request pins the matched
        # prefix (acquire_prefix) before the request is ever scheduled,
        # so a loop failure landing between admit and first schedule
        # would leak those pins forever. Free the seq and leave the
        # request queued — _admit re-matches the prefix on admission
        # (the recompute path) whenever the seq is gone.
        for r in list(self.scheduler.waiting):
            if self.cache.has_seq(r.seq_id):
                self.cache.free_seq(r.seq_id)
            self._free_draft_seq(r.seq_id)
        for rid in list(self._held):
            self.release_request(rid)
        self._release_chaos_spike()
        # a step that failed AFTER its dispatch took the pools with it
        # (they are donated): every sequence is released by now, so the
        # caches come back empty and usable, and the requeued requests
        # recompute into them
        for what, cache in (("the cache", self.cache),
                            ("the draft's cache", self._draft_cache)):
            lost = cache.recover_lost_pools() if cache is not None else None
            if lost is not None:
                _log.error(json.dumps({
                    "event": "pools_lost",
                    "detail": f"a step program failed after {what}'s "
                              "pools were donated to it; they are built "
                              "anew (zeros), every live request is "
                              "requeued for recompute and the prefix "
                              f"tree's {lost} cached page(s) count as "
                              "evicted"}))

    def _maybe_inject_fault(self):
        """Chaos fault hook, evaluated at the step BOUNDARY (before any
        device work or state mutation, so a raised step is safely
        retryable).  Three engine-level fault points ride it:
        ``step_latency`` (added per-step latency, via the injected
        sleeper), ``alloc_pressure`` (a chaos sequence grabs a fraction
        of the free pages for a few steps — exercising preemption and
        load shedding), and ``step_fault`` (raises FaultInjected).  The
        legacy PADDLE_TPU_SERVING_FAULT_* knobs alias into the same
        schedule (ChaosConfig.from_env)."""
        chaos = self.chaos
        cfg = chaos.cfg
        if not cfg.any_enabled and self._chaos_spike is None:
            return
        if chaos.fire("step_latency", cfg=cfg):
            chaos.sleep(cfg.step_latency_s)
        self._chaos_pressure_tick(chaos, cfg)
        if chaos.fire("step_fault", cfg=cfg):
            self.metrics.faults_injected.inc()
            if self.trace.enabled:
                self.trace.flight.record("fault",
                                         rate=cfg.rate("step_fault"))
            raise FaultInjected(
                "injected step fault "
                f"(chaos step_fault rate={cfg.rate('step_fault')})")

    _CHAOS_SEQ = "__chaos_pressure__"

    def _chaos_pressure_tick(self, chaos, cfg):
        """Allocator pressure spike: on fire, a chaos-owned sequence
        swallows ``alloc_pressure_frac`` of the current free pages for
        ``alloc_pressure_steps`` steps, then releases them.  The spike
        is accounted like any live sequence (conservation holds) and is
        itself the LAST thing released under terminal page pressure
        (``_release_chaos_spike``), so it degrades service — sheds,
        preemptions — without ever deadlocking it."""
        if self._chaos_spike is not None:
            sid, left = self._chaos_spike
            if left <= 1:
                self._release_chaos_spike()
            else:
                self._chaos_spike = (sid, left - 1)
            return
        if not chaos.fire("alloc_pressure", cfg=cfg):
            return
        pages = int(self.cache.free_pages * cfg.alloc_pressure_frac)
        if pages <= 0:
            return
        sid = self._CHAOS_SEQ
        self.cache.alloc_seq(sid)
        try:
            self.cache.append_slots(sid, pages * self.cache.page_size)
        except OutOfPages:  # pragma: no cover - sized from free_pages
            self.cache.free_seq(sid)
            return
        self._chaos_spike = (sid, max(1, cfg.alloc_pressure_steps))

    def _release_chaos_spike(self):
        """Give back the alloc-pressure spike's pages.  Returns True
        when pages were actually released."""
        if self._chaos_spike is None:
            return False
        sid, _ = self._chaos_spike
        self._chaos_spike = None
        if self.cache.has_seq(sid):
            self.cache.free_seq(sid)
            return True
        return False

    def chaos_idle_tick(self):
        """Idle-loop chaos upkeep (called by the front-end between
        steps when the scheduler is drained): the held-deadline sweep
        plus the alloc-pressure spike countdown — a spike must expire
        even when no step runs, or an idle engine would shed every new
        admission until traffic somehow restarted it."""
        released = self.sweep_held_deadlines()
        if self._chaos_spike is not None:
            sid, left = self._chaos_spike
            if left <= 1:
                self._release_chaos_spike()
            else:
                self._chaos_spike = (sid, left - 1)
        return released

    def sweep_held_deadlines(self, now=None):
        """Release HELD ("prefilled") requests whose deadline passed —
        the round-14 rule (anything that can drop a request must
        release held pages) enforced for timeouts: a migration that
        never came back must not pin pages forever.  Called per step
        and from the front-end's idle loop (a pure prefill replica
        idles between handoffs).  Returns the number released."""
        if not self._held:
            return 0
        now = self._now() if now is None else now
        expired = [rid for rid, r in self._held.items()
                   if r.deadline is not None and now >= r.deadline]
        for rid in expired:
            self.release_request(rid)
            self.metrics.held_expired.inc()
            if self.trace.enabled:
                self.trace.flight.record("held_expired", req_id=rid)
            _log.info(json.dumps({"event": "held_deadline_expired",
                                  "req_id": rid}))
        return len(expired)

    def results(self):
        return {rid: {"tokens": list(r.out_tokens),
                      "finish_reason": r.finish_reason,
                      "preemptions": r.preemptions}
                for rid, r in self._finished.items()}

    # -- internals ---------------------------------------------------------
    @staticmethod
    def _now():
        return time.perf_counter()

    def _bucket(self, n):
        b = 1
        while b < n:
            b <<= 1
        return min(b, self.scheduler.max_batch)

    def _alloc_with_preemption(self, req, n_tokens):
        """Allocate slots for req, preempting by page pressure (newest
        victim first) until it fits or no victim remains. Prefix pins
        held by WAITING requests are released before giving up — their
        cached pages become reclaimable and the requests simply
        re-match at admission."""
        while True:
            try:
                slots, copies = self.cache.append_slots(req.seq_id,
                                                        n_tokens)
            except OutOfPages:
                victim = self.scheduler.pick_victim(exclude=(req,))
                if victim is None:
                    if self._release_waiting_pins():
                        continue
                    if self._release_chaos_spike():
                        continue
                    raise RuntimeError(
                        f"KV cache too small: request {req.req_id} "
                        f"cannot fit even alone "
                        f"(allocatable={self.cache.allocatable_pages} "
                        f"pages of {self.cache.page_size} tokens)")
                self._preempt(victim)
                continue
            if copies:
                self.cache.apply_copies(copies)
                self.metrics.cow_copies.inc(len(copies))
            return slots

    def _release_waiting_pins(self, exclude=None):
        """Free the prefix-cache pins of WAITING (not-yet-admitted)
        requests so their cached pages become reclaimable under page
        pressure; the requests re-run the longest-prefix match when the
        scheduler admits them. Returns the number of pins released."""
        released = 0
        for r in self.scheduler.waiting:
            if r is exclude:
                continue
            if self.cache.has_seq(r.seq_id):
                self.cache.free_seq(r.seq_id)
                r.cached_pages = 0
                released += 1
        return released

    def _preempt(self, victim):
        if self.cache.has_seq(victim.seq_id):
            self.cache.free_seq(victim.seq_id)
        self._free_draft_seq(victim.seq_id)
        self.scheduler.preempt(victim)
        self.metrics.preemptions.inc()
        if self.trace.enabled:
            now = self._now()
            self.trace.span(victim.req_id, "preempted", now,
                            tokens_kept=len(victim.out_tokens))
            self.trace.mark(victim.req_id, "queued_t0", now)
            self.trace.flight.record("preempt", req_id=victim.req_id)

    def _free_draft_seq(self, seq_id):
        """Drop a lane's draft-cache state (request finished/cancelled/
        preempted). Draft K/V is disposable — the next speculative round
        rebuilds it by catchup prefill; output tokens never depend on
        it."""
        if self._draft_cache is not None \
                and self._draft_cache.has_seq(seq_id):
            self._draft_cache.free_seq(seq_id)

    def _spec_enabled(self, req):
        """Does this lane ride the draft-verify rounds? Engine-level
        config gates it; a request opts out with speculative=False."""
        return (self.spec_k > 0 and self.draft is not None
                and req.speculative is not False)

    # -- speculative decoding (round 12) -----------------------------------
    def _draft_alloc(self, seq_id, n, protect=()):
        """Allocate ``n`` draft-cache slots, evicting OTHER lanes' draft
        state under pressure (their next round pays a catchup prefill;
        output tokens are unaffected — draft K/V is disposable). Lanes
        in ``protect`` are never evicted (they are mid-round: their
        page tables are about to enter a program). Returns None when
        the draft pool cannot serve."""
        dc = self._draft_cache
        while True:
            try:
                slots, copies = dc.append_slots(seq_id, n)
                if copies:  # pragma: no cover - draft seqs never fork
                    raise AssertionError("draft cache saw a CoW copy")
                return slots
            except OutOfPages:
                victims = [s for s in dc.live_seqs()
                           if s != seq_id and s not in protect]
                if not victims:
                    return None
                dc.free_seq(victims[0])

    def _draft_ready(self, req, protect=()):
        """Bring the draft cache up to date for ``req``: every history
        token but the last must have its draft K/V written (catchup
        runs the draft's trunk over a rectangular [1, prefill_chunk]
        chunk — a lane's first speculative round after prefill/
        preemption/fork pays it once). False -> the lane falls back to
        plain decode this round."""
        dc = self._draft_cache
        sid = req.seq_id
        target = req.prompt.size + len(req.out_tokens) - 1
        if not dc.has_seq(sid):
            dc.alloc_seq(sid)
        have = dc.seq_len(sid)
        if have > target:  # pragma: no cover - defensive resync
            dc.free_tail(sid, target)
            have = target
        if have == target:
            return True
        hist = req.token_history()
        c = self.scheduler.prefill_chunk
        while have < target:
            n = min(c, target - have)
            slots = self._draft_alloc(sid, n, protect)
            if slots is None:
                return False
            ids = np.zeros((1, c), np.int32)
            ids[0, :n] = hist[have:have + n]
            positions = (have + np.arange(c, dtype=np.int32))[None, :]
            pt = dc.page_table(sid, self.max_pages_per_seq)[None, :]
            cl = np.asarray([have + n], np.int32)
            slot_map = np.zeros((1, c), np.int32)
            slot_map[0, :n] = slots
            self._run_draft_step(ids, positions, pt, cl, slot_map)
            have += n
        return True

    def _stage_draft_propose(self, active):
        """Build the bucketed draft arrays for the surviving verify
        lanes and run the fused k+1-step proposal scan (its own
        dispatch beside the step: different model, disposable K/V).
        ``active`` rows are ``(req, hist0, n_slots, tslots, dslots)``.
        Returns the proposals, ``[bb, k+1]`` int32."""
        k1 = self.spec_k + 1
        bb = self._bucket(len(active))
        mp = self.max_pages_per_seq
        dids = np.zeros((bb, 1), np.int32)
        dpos = np.zeros(bb, np.int32)
        dpt = np.full((bb, mp), SCRATCH_PAGE, np.int32)
        dcl = np.ones(bb, np.int32)
        dslot = np.zeros((bb, k1), np.int32)
        do_sample = np.zeros(bb, np.bool_)
        temperature = np.ones(bb, np.float32)
        top_k = np.zeros(bb, np.int32)
        top_p = np.ones(bb, np.float32)
        seeds = np.zeros(bb, np.int32)
        steps0 = np.zeros(bb, np.int32)
        for i, (r, hist0, n_slots, tslots, dslots) in enumerate(active):
            dids[i, 0] = r.out_tokens[-1]
            dpos[i] = hist0 - 1
            dpt[i] = self._draft_cache.page_table(r.seq_id, mp)
            dcl[i] = hist0
            dslot[i, :n_slots] = dslots
            do_sample[i] = r.do_sample
            temperature[i] = r.temperature
            top_k[i] = r.top_k
            top_p[i] = r.top_p
            seeds[i] = r.device_seed
            steps0[i] = len(r.out_tokens)
        samp = (do_sample, temperature, top_k, top_p, seeds, steps0)
        sample_capable = any(r.do_sample for r, *_ in active)
        props = np.asarray(self._run_draft_propose(
            dids, dpos, dpt, dcl, dslot, samp, sample_capable),
            np.int32)                                  # [bb, k+1]
        self.metrics.fetch_bytes.inc(props.nbytes)
        self.metrics.step_fetches.inc()
        return props

    def _run_draft_step(self, ids, positions, pt, cl, slot_map):
        """Draft catchup prefill: the draft's trunk over one
        rectangular chunk, for the K/V it writes (no head, no
        sampler: nothing of a catchup is ever emitted)."""
        import jax
        import jax.numpy as jnp
        if self._draft_fn is None:
            # no TP context: the draft program never pins TP layouts —
            # a distinct draft's weights are replicated (byte-identical
            # program to TP=1), a self-draft's sharded tensors fall to
            # GSPMD auto.  Either way the verify step's deterministic-
            # sample matching keeps the EMITTED stream token-exact.
            # The draft's pools are donated as the step's are: a
            # program that returns a pool was given it.
            self._draft_fn = jax.jit(
                functools.partial(_draft_catchup_pure, self.draft,
                                  self._draft_core, self._draft_window),
                donate_argnums=(6, 7))
        dc = self._draft_cache
        dwarrs = [t._data for t in self.draft._gen_state_tensors()]
        k_ops, v_ops = dc.program_operands()
        k_pages, v_pages = self._draft_fn(
            dwarrs, jnp.asarray(ids), jnp.asarray(positions),
            jnp.asarray(pt), jnp.asarray(cl), jnp.asarray(slot_map),
            k_ops, v_ops)
        dc.store_operands(k_pages, v_pages)
        self._count_dispatch(("draft_step", ids.shape))

    def _run_draft_propose(self, ids0, pos0, pt, cl0, slot_mat, samp,
                           sample_capable):
        """The fused k+1-step draft proposal scan: one dispatch per
        round, K/V written in place, proposals fetched as [B, k+1]
        int32 (the k+1-th output is the generation.py 'extra step'
        trick — it lands d_k's K/V so a full-accept round leaves no
        hole; the token itself is discarded)."""
        import jax
        import jax.numpy as jnp
        if self._propose_fn is None:
            self._propose_fn = jax.jit(
                functools.partial(_spec_draft_pure, self.draft,
                                  self._draft_core, self._draft_window),
                static_argnums=(0,), donate_argnums=(8, 9))
        dc = self._draft_cache
        dwarrs = [t._data for t in self.draft._gen_state_tensors()]
        k_ops, v_ops = dc.program_operands()
        props, k_pages, v_pages = self._propose_fn(
            bool(sample_capable), dwarrs, jnp.asarray(ids0),
            jnp.asarray(pos0), jnp.asarray(pt), jnp.asarray(cl0),
            jnp.asarray(slot_mat),
            tuple(jnp.asarray(a) for a in samp),
            k_ops, v_ops)
        dc.store_operands(k_pages, v_pages)
        self._count_dispatch(("draft_propose", slot_mat.shape,
                              bool(sample_capable)))
        return props

    def _prefill_finish(self, req, events, host, row_idx, tok, lp):
        """Prefill-completion tail of the step (``row_idx`` is the
        packed token offset of the request's last prompt token: its
        row in the step's [T, V] logits). Fork BEFORE sampling
        (children share the prefix pages; the parent may finish — and
        free — immediately). A RECOMPUTE prefill (out_tokens non-empty
        after preemption) must NOT fork again: the children already
        exist."""
        children = []
        if req.n > 1 and not req.out_tokens:
            for i in range(1, req.n):
                children.append(self._fork(req, i))
        if host:
            row = self._fetch_logits()[row_idx]
            self._emit_token(req, self._sample(req, row), events)
            for child in children:
                self._emit_token(child, self._sample(child, row),
                                 events)
        else:
            self._emit_token(req, tok, events, logprob=lp)
            if children:
                # one fetched row, several seeds: children sample
                # eagerly with the SAME counter-RNG function; a child's
                # later recompute (token index >= 1) goes through the
                # compiled path with the same (seed, step) arguments
                row = self._fetch_logits()[row_idx]
                for child in children:
                    ctok, clp = _counter_sample_row(row, child)
                    self._emit_token(child, ctok, events, logprob=clp)
        if req.prefill_only and req.state == RequestState.RUNNING:
            # disagg handoff point: the first token is emitted (TTFT is
            # the prefill replica's to measure) and the request stops
            # BEFORE the first decode step — pages stay resident for
            # export_request until release_request/cancel frees them
            self._hold_prefilled(req, events)

    def _hold_prefilled(self, req, events):
        self.scheduler.finish(req, "prefilled")
        req.held = True
        self._held[req.req_id] = req
        self.metrics.prefills_held.inc()
        if self.trace.enabled:
            self.trace.mark(req.req_id, "held_t0", self._now())
        self._record_finish(req, events)

    # -- the step: token-packed, one program (round 22 / PR 18) ------------
    def _ragged_step(self, out, events):
        """ONE token-packed dispatch for the whole step: plain decode
        lanes (q=1), speculative-verify lanes (q=k+1), and the prefill
        chunk ride a single compiled program over the
        ``ragged_paged_attention`` lane layout (lane i's tokens at
        rows [i*k1, (i+1)*k1), the chunk's behind the rectangle; rows
        a lane leaves unused are padding) — one dispatch + one
        host fetch per step (FEASIBILITY.md: per-dispatch overhead
        ~0.79 of a small CPU step; not measured on a chip). A token's
        counter-RNG key is (seed, token-index) and knows no schedule,
        so a stream is the one the request would get served alone,
        whatever the crowd, the chunking and the preemption order —
        any valid schedule replays the same (weights, history, seed, t)
        function. The draft-proposal scan stays its own dispatch
        (different model, disposable K/V); draft catchup prefills ride
        ahead of it."""
        t0 = self._now()
        k = self.spec_k
        k1 = k + 1
        mp = self.max_pages_per_seq
        spec, plain = [], []
        for r in out.decode:
            (spec if self._spec_enabled(r) else plain).append(r)
        # 1. draft staging (catchup prefills are draft-model
        # dispatches); lanes the draft cannot serve demote to plain
        staged = []
        protect = {r.seq_id for r in spec}
        for r in spec:
            if r.state != RequestState.RUNNING:
                continue
            if not self._draft_ready(r, protect):
                self.metrics.spec_fallbacks.inc()
                plain.append(r)
                continue
            staged.append(r)
        spec_alloc = []
        for r in staged:
            if r.state != RequestState.RUNNING:
                continue  # preempted by an earlier member's allocation
            hist0 = r.prompt.size + len(r.out_tokens)
            rem = r.max_new_tokens - len(r.out_tokens)
            n_slots = min(k1, rem)
            tslots = self._alloc_with_preemption(r, n_slots)
            if r.state != RequestState.RUNNING:  # pragma: no cover
                continue
            dslots = self._draft_alloc(r.seq_id, n_slots, protect)
            if dslots is None:
                self.cache.free_tail(r.seq_id, hist0 - 1)
                self.metrics.spec_fallbacks.inc()
                plain.append(r)
                continue
            spec_alloc.append((r, hist0, n_slots, tslots, dslots))
        # 2. plain decode allocation
        plain_alloc = []
        for r in plain:
            if r.state != RequestState.RUNNING:
                continue
            slots = self._alloc_with_preemption(r, 1)
            plain_alloc.append((r, int(slots[0])))
        # 3. prefill-chunk allocation (it may preempt a staged decode
        # lane; the re-filter below drops that lane — its pages are
        # gone, and the recompute replays an identical stream)
        pf = None
        if out.prefill is not None:
            req, start, end = out.prefill
            if req.state == RequestState.PREFILLING:
                if self.trace.enabled:
                    q0 = self.trace.pop_mark(req.req_id, "queued_t0")
                    if q0 is not None:
                        self.trace.span(req.req_id, "queued", q0,
                                        t0 - q0)
                if not self.cache.has_seq(req.seq_id):
                    self.cache.alloc_seq(req.seq_id)
                chunk = req.token_history()[start:end]
                n = int(chunk.size)
                pslots = self._alloc_with_preemption(req, n)
                if req.state == RequestState.PREFILLING:
                    pf = (req, start, end, chunk, n, pslots)
        # 4. re-filter: every lane must still be live AFTER all
        # allocations — a preempted lane's page-table row is dead
        spec_active = [a for a in spec_alloc
                       if a[0].state == RequestState.RUNNING]
        plain_active = [(r, s) for r, s in plain_alloc
                        if r.state == RequestState.RUNNING]
        if not spec_active and not plain_active and pf is None:
            return
        # 5. draft proposals for the surviving verify lanes
        props = None
        if spec_active:
            props = self._stage_draft_propose(spec_active)
        # 6. pack the token batch into the step's two static regions:
        # decode/verify lane i owns rows [i*k1, (i+1)*k1), the chunk
        # (always the last lane) the rows from chunk_off on, so a
        # row's place says its lane and the device gathers a lane's
        # page table once. Two static token capacities only (see
        # __init__): a step with no chunk takes the decode class.
        n_tok = (sum(a[2] for a in spec_active) + len(plain_active)
                 + (pf[4] if pf is not None else 0))
        chunk_lane = self._ragged_lanes - 1
        chunk_off = chunk_lane * k1
        assert len(spec_active) + len(plain_active) <= chunk_lane
        tcap = (self._ragged_tok_small if pf is None
                else self._ragged_tok_mixed)
        b = self._ragged_bufs.get(tcap)
        if b is None:
            nl = self._ragged_lanes
            b = self._ragged_bufs[tcap] = {
                "ids": np.zeros((1, tcap), np.int32),
                "positions": np.zeros((1, tcap), np.int32),
                "slot_map": np.zeros((1, tcap), np.int32),
                "pt": np.full((nl, mp), SCRATCH_PAGE, np.int32),
                "cl": np.ones(nl, np.int32),
                "ql": np.zeros(nl, np.int32),
                "qoff": np.zeros(nl, np.int32),
                "do_sample": np.zeros(tcap, np.bool_),
                "temperature": np.ones(tcap, np.float32),
                "top_k": np.zeros(tcap, np.int32),
                "top_p": np.ones(tcap, np.float32),
                "seeds": np.zeros(tcap, np.int32),
                "steps": np.zeros(tcap, np.int32),
            }
            if self.cache.mixed:
                # a lane's slot in the state arrays (0: the scratch
                # lane), its window-pool pages and where they start,
                # a row's slot in the window pools (0: scratch)
                b["lane_slot"] = np.zeros(nl, np.int32)
                b["wpt"] = np.full(
                    (nl, max(self.cache.window_pages_per_lane, 1)),
                    SCRATCH_PAGE, np.int32)
                b["wbase"] = np.zeros(nl, np.int32)
                b["wslots"] = np.zeros(tcap, np.int32)
        else:
            # full padding reset: lane composition changes every step
            # (padded lanes keep context 1 / scratch pages / neutral
            # sampling — the NaN-free contract)
            b["ids"][:] = 0
            b["positions"][:] = 0
            b["slot_map"][:] = 0
            b["pt"][:] = SCRATCH_PAGE
            b["cl"][:] = 1
            b["ql"][:] = 0
            b["qoff"][:] = 0
            b["do_sample"][:] = False
            b["temperature"][:] = 1.0
            b["top_k"][:] = 0
            b["top_p"][:] = 1.0
            b["seeds"][:] = 0
            b["steps"][:] = 0
            if self.cache.mixed:
                b["lane_slot"][:] = 0
                b["wpt"][:] = SCRATCH_PAGE
                b["wbase"][:] = 0
                b["wslots"][:] = 0
        lane = 0
        emit_spec = []                    # (req, hist0, n_slots, i, off)
        for i, (r, hist0, n_slots, tslots, dslots) in \
                enumerate(spec_active):
            off = lane * k1
            b["pt"][lane] = self.cache.page_table(r.seq_id, mp)
            b["cl"][lane] = hist0 - 1 + n_slots
            b["ql"][lane] = n_slots
            b["qoff"][lane] = hist0 - 1
            sl = slice(off, off + n_slots)
            b["ids"][0, off] = r.out_tokens[-1]
            if n_slots > 1:
                b["ids"][0, off + 1:off + n_slots] = \
                    props[i, :n_slots - 1]
            b["positions"][0, sl] = hist0 - 1 + np.arange(
                n_slots, dtype=np.int32)
            b["slot_map"][0, sl] = tslots
            b["do_sample"][sl] = r.do_sample
            b["temperature"][sl] = r.temperature
            b["top_k"][sl] = r.top_k
            b["top_p"][sl] = r.top_p
            b["seeds"][sl] = r.device_seed
            # verify token j samples with counter key steps0+j: the
            # key a plain decode lane has at that token index
            b["steps"][sl] = len(r.out_tokens) + np.arange(
                n_slots, dtype=np.int32)
            emit_spec.append((r, hist0, n_slots, i, off))
            lane += 1
        emit_plain = []                                  # (req, off)
        for r, slot in plain_active:
            off = lane * k1
            hist_len = r.prompt.size + len(r.out_tokens)
            b["pt"][lane] = self.cache.page_table(r.seq_id, mp)
            b["cl"][lane] = hist_len
            b["ql"][lane] = 1
            b["qoff"][lane] = hist_len - 1
            b["ids"][0, off] = r.out_tokens[-1]
            b["positions"][0, off] = hist_len - 1
            b["slot_map"][0, off] = slot
            b["do_sample"][off] = r.do_sample
            b["temperature"][off] = r.temperature
            b["top_k"][off] = r.top_k
            b["top_p"][off] = r.top_p
            b["seeds"][off] = r.device_seed
            b["steps"][off] = len(r.out_tokens)
            if self.cache.mixed:
                self._pack_lane_extras(b, lane, r.seq_id,
                                       slice(off, off + 1))
            emit_plain.append((r, off))
            lane += 1
        pf_off = None
        if pf is not None:
            req, start, end, chunk, n, pslots = pf
            b["pt"][chunk_lane] = self.cache.page_table(req.seq_id, mp)
            b["cl"][chunk_lane] = start + n
            b["ql"][chunk_lane] = n
            b["qoff"][chunk_lane] = start
            sl = slice(chunk_off, chunk_off + n)
            b["ids"][0, sl] = chunk
            b["positions"][0, sl] = start + np.arange(n,
                                                      dtype=np.int32)
            b["slot_map"][0, sl] = pslots
            if self.cache.mixed:
                self._pack_lane_extras(b, chunk_lane, req.seq_id, sl)
            # only the chunk's LAST token's sample is ever consumed,
            # and only at prefill completion: it takes the request's
            # params in the prompt's last chunk alone, so an earlier
            # chunk asks the sampler for no sort and no draw; every
            # other token keeps the neutral params and its greedy
            # output is discarded
            pf_off = chunk_off + n - 1
            if end >= req.prompt.size + len(req.out_tokens):
                b["do_sample"][pf_off] = req.do_sample
                b["temperature"][pf_off] = req.temperature
                b["top_k"][pf_off] = req.top_k
                b["top_p"][pf_off] = req.top_p
                b["seeds"][pf_off] = req.device_seed
                b["steps"][pf_off] = len(req.out_tokens)
        # 7. ONE dispatch, ONE [T]+[T] host fetch
        tok_d, lp_d = self._run_ragged_step(
            b["ids"], b["positions"], b["pt"], b["cl"], b["ql"],
            b["qoff"], b["slot_map"],
            (b["do_sample"], b["temperature"], b["top_k"], b["top_p"],
             b["seeds"], b["steps"]),
            {k: b[k] for k in ("lane_slot", "wpt", "wbase", "wslots")}
            if self.cache.mixed else {})
        self._logits_row = pf_off if pf_off is not None else 0
        # what the padded tables cost: page-table entries a layer's
        # attention gathered in this step's class (counted where the
        # gather is: _step_tables_gathered) against the pages its live
        # lanes hold
        live = b["ql"] > 0
        full_live = int(np.sum(-(-b["cl"][live] // self.cache.page_size)))
        if self.cache.mixed:
            self._count_mixed_step(b, live, full_live, tcap, k1,
                                   pf is not None and pf[1] == 0)
        else:
            self.metrics.attn_pages_gathered.inc(
                mp * _step_tables_gathered(self._core, self._ragged_lanes,
                                           tcap, k1))
            self.metrics.attn_pages_live.inc(full_live)
        if spec_active:
            self.metrics.spec_rounds.inc()
            self.metrics.spec_draft_tokens.inc(
                sum(min(k, a[2]) for a in spec_active))
        if spec_active or plain_active:
            self.metrics.decode_steps.inc()
            self.metrics.batch_size.record(
                len(spec_active) + len(plain_active))
        if pf is not None:
            self.metrics.prefill_chunks.inc()
        host = self._host_sampling()
        toks = lps = logits = None
        if host:
            logits = self._fetch_logits()                     # [T, V]
        else:
            toks = np.asarray(tok_d, np.int32)
            lps = np.asarray(lp_d, np.float32)
            self.metrics.fetch_bytes.inc(toks.nbytes + lps.nbytes)
            self.metrics.step_fetches.inc()
        experts_hit = self._record_moe_counts()
        # 8. host-side per-lane processing, in event order: verify
        # lanes, plain lanes, then the prefill completion
        accepted = 0
        for r, hist0, n_slots, i, toff in emit_spec:
            emitted = 0
            lane_accepted = 0
            for j in range(n_slots):
                if host:
                    v = self._sample(r, logits[toff + j])
                    lp = None
                else:
                    v = int(toks[toff + j])
                    lp = float(lps[toff + j])
                is_draft = j < k and v == int(props[i, j])
                if self.distill is not None:
                    self.distill.log(r.prompt, r.out_tokens, v)
                    self.metrics.distill_pairs.inc()
                self._emit_token(r, v, events, logprob=lp)
                emitted += 1
                if is_draft:
                    accepted += 1
                    lane_accepted += 1
                if r.state == RequestState.FINISHED or not is_draft:
                    break  # mismatch emits the correction; j==k bonus
            if r.state != RequestState.FINISHED:
                new_len = hist0 + emitted - 1
                self.cache.free_tail(r.seq_id, new_len)
                self._draft_cache.free_tail(r.seq_id, new_len)
            if self.trace.enabled:
                self.trace.run_span(r.req_id, "spec_round", t0,
                                    self._now() - t0,
                                    batch=len(spec_active),
                                    proposed=min(k, n_slots),
                                    accepted=lane_accepted,
                                    emitted=emitted)
        if spec_active:
            self.metrics.spec_accepted_tokens.inc(accepted)
        for r, toff in emit_plain:
            if host:
                self._emit_token(r, self._sample(r, logits[toff]),
                                 events)
            else:
                self._emit_token(r, int(toks[toff]), events,
                                 logprob=float(lps[toff]))
            if self.trace.enabled:
                self.trace.run_span(r.req_id, "decode_round", t0,
                                    self._now() - t0,
                                    batch=len(plain_active))
        if pf is not None:
            req, start, end, chunk, n, pslots = pf
            if self.trace.enabled:
                self.trace.span(
                    req.req_id,
                    ("recompute" if (req.out_tokens or req.preemptions)
                     else "prefill_chunk"),
                    t0, self._now() - t0, start=int(start),
                    end=int(end), tokens=n)
            if self.cache.prefix_cache_enabled:
                self.cache.commit_prefix(req.seq_id, req.prompt, end)
            self.scheduler.prefill_advanced(req, end)
            if req.state == RequestState.RUNNING:
                if host:
                    self._prefill_finish(req, events, True, pf_off,
                                         None, None)
                else:
                    self._prefill_finish(req, events, False, pf_off,
                                         int(toks[pf_off]),
                                         float(lps[pf_off]))
        if self.trace.enabled:
            self.trace.flight.record(
                "ragged_step", tokens=int(n_tok), cap=int(tcap),
                lanes=int(lane) + (pf is not None), spec=len(emit_spec),
                plain=len(emit_plain),
                prefill=(pf[0].req_id if pf is not None else None),
                experts_hit=experts_hit)

    def _pack_lane_extras(self, b, lane, seq_id, rows):
        """A lane's part of a mixed cache into the step's buffers: its
        state slot, its window-pool table, and the window-pool slots of
        the rows it sends (those its last allocation reserved)."""
        b["lane_slot"][lane] = self.cache.lane_slot(seq_id)
        if self.cache.window_layers:
            b["wpt"][lane], b["wbase"][lane] = \
                self.cache.window_table(seq_id)
            b["wslots"][rows] = self.cache.window_slots(seq_id)

    def _count_mixed_step(self, b, live, full_live, tcap, k1, starts):
        """A step's counts where the layers differ (lane state, window
        pools, layers that read another's pool), from what it packed:
        page-table entries gathered are summed over EVERY layer that
        attends (a reader of another layer's pool gathers too; a window
        layer its own short table), pages live over every pool."""
        from .attention import tables_gathered
        c, m = self.cache, self.metrics
        tables = tables_gathered(self._ragged_lanes, tcap, k1)
        lanes = int(live.sum())
        nw, ns = len(c.window_layers), len(c.state_layers)
        held = int(np.sum(b["wpt"][live] != SCRATCH_PAGE)) if nw else 0
        m.attn_pages_gathered.inc(
            tables * (self._pool_readers * self.max_pages_per_seq
                      + nw * c.window_pages_per_lane))
        m.attn_pages_live.inc(len(c.full_layers) * full_live + nw * held)
        m.window_pages_held.inc(nw * held)
        m.window_layer_steps.inc(nw * lanes)
        m.ssm_layer_steps.inc(ns)
        m.ssm_lane_scans.inc(ns * lanes)
        m.ssm_rows_scanned.inc(ns * int(b["ql"].sum()))
        m.ssm_state_resets.inc(int(starts))

    # -- KV page migration (disaggregated serving, round 14) ---------------
    def export_request(self, req_id, skip_pages=0):
        """Export a HELD request's KV page chain for migration.
        Returns ``(meta, k_arrays, v_arrays)`` — the allocator payload
        plus the continuation fields (prompt/out_tokens/device_seed)
        the adopting engine needs for a token-exact splice.  Read-only:
        the request stays held until :meth:`release_request`."""
        req = self._held.get(req_id)
        if req is None:
            raise KeyError(
                f"export_request: request {req_id!r} is not held "
                "(not prefill_only, already released, or unknown)")
        t0 = self._now()
        meta, k, v = self.cache.export_pages(req.seq_id, skip_pages)
        meta.update(
            prompt=[int(t) for t in req.prompt],
            out_tokens=[int(t) for t in req.out_tokens],
            device_seed=int(req.device_seed),
            # trace context rides the export meta: the adopting engine
            # keys its timeline on the same X-Request-Id, so the router
            # can stitch both phases into one timeline
            request_id=req.request_id)
        self.metrics.pages_exported.inc(int(meta["n_pages"]))
        if self.trace.enabled:
            self.trace.span(req.req_id, "migration", t0,
                            self._now() - t0, direction="export",
                            pages=int(meta["n_pages"]),
                            skip_pages=int(skip_pages))
        return meta, k, v

    def release_request(self, req_id):
        """Free a held request's pages (migration committed on the
        destination, or abandoned). Idempotent: False when nothing was
        held under this id."""
        req = self._held.pop(req_id, None)
        if req is None:
            return False
        req.held = False
        if self.cache.has_seq(req.seq_id):
            self.cache.free_seq(req.seq_id)
        if self.trace.enabled:
            h0 = self.trace.pop_mark(req.req_id, "held_t0")
            if h0 is not None:
                self.trace.span(req.req_id, "held", h0,
                                self._now() - h0)
        return True

    def adopt_request(self, meta, k_arrays, v_arrays, *,
                      max_new_tokens, deadline_s=None, do_sample=False,
                      temperature=1.0, top_k=0, top_p=1.0, seed=None,
                      logprobs=False, request_id=None, speculative=None):
        """Register a migrated-in request: import its KV page chain
        (geometry-checked, shared prefix resolved against THIS
        allocator's radix tree) and enter it RUNNING — the next decode
        step continues the stream exactly where the prefill replica
        stopped (token t is pure in (weights, history, seed, t), and
        ``device_seed`` rides in ``meta``).  Raises GeometryMismatch /
        PrefixDrift / OutOfPages with no state left behind."""
        if self._draining:
            raise EngineDraining(
                "engine is draining: in-flight requests finish, new "
                "admissions are refused")
        if self.chaos.fire("shard_geometry_mismatch"):
            raise GeometryMismatch(
                "chaos: shard geometry mismatch (tp_degree skew)")
        prompt = np.asarray(meta["prompt"], np.int32).reshape(-1)
        out_tokens = [int(t) for t in meta["out_tokens"]]
        if prompt.size == 0 or not out_tokens:
            raise ValueError(
                "adopt_request needs a non-empty prompt and at least "
                "the prefill replica's first sampled token")
        if int(meta["seq_len"]) != prompt.size + len(out_tokens) - 1:
            raise ValueError(
                f"adopt_request: payload seq_len={meta['seq_len']} != "
                f"history-1 ({prompt.size}+{len(out_tokens)}-1) — the "
                "last sampled token must not have been fed yet")
        if len(out_tokens) >= int(max_new_tokens):
            raise ValueError(
                f"adopt_request: {len(out_tokens)} token(s) already "
                f"emitted >= max_new_tokens({max_new_tokens}) — "
                "nothing left to decode")
        if request_id is None:
            # trace context rides the export meta (round 16): the
            # adopted timeline keys on the SOURCE request's id so the
            # router stitches both phases
            request_id = meta.get("request_id")
        total = prompt.size + int(max_new_tokens)
        if total > self.max_seq_len:
            raise ValueError(
                f"prompt({prompt.size}) + max_new_tokens"
                f"({max_new_tokens}) exceeds max_seq_len"
                f"({self.max_seq_len})")
        now = self._now()
        req = Request(prompt=prompt, max_new_tokens=int(max_new_tokens),
                      arrival=now,
                      deadline=(now + deadline_s
                                if deadline_s is not None else None),
                      do_sample=bool(do_sample),
                      temperature=float(temperature), top_k=int(top_k),
                      top_p=float(top_p), seed=seed, n=1,
                      logprobs=bool(logprobs),
                      request_id=(str(request_id)
                                  if request_id is not None else None),
                      speculative=(None if speculative is None
                                   else bool(speculative)),
                      adopted=True)
        req.out_tokens = out_tokens
        req.device_seed = int(meta["device_seed"]) & 0x7FFFFFFF
        # TTFT belongs to the prefill replica; tokens here are TPOT
        req.first_token_at = now
        req.last_token_at = now
        self.cache.import_pages(req.seq_id, meta, k_arrays, v_arrays,
                                prompt=prompt,
                                hist_len=prompt.size + len(out_tokens))
        self._requests[req.req_id] = req
        self._rngs[req.req_id] = np.random.default_rng(seed)
        self.scheduler.register_adopted(req)
        self.metrics.pages_imported.inc(int(meta["n_pages"]))
        self.metrics.adoptions.inc()
        if self.trace.enabled:
            self.trace.begin(req.req_id, req.request_id)
            self.trace.span(req.req_id, "migration", now,
                            self._now() - now, direction="import",
                            pages=int(meta["n_pages"]))
            self.trace.flight.record("adopt", req_id=req.req_id,
                                     request_id=req.request_id,
                                     pages=int(meta["n_pages"]))
        return req.req_id

    # -- fleet prefix transfer (round 18) ----------------------------------
    def export_prefix(self, prompt, skip_pages=0):
        """Serve this engine's cached prefix of ``prompt`` for a fleet
        prefix ship (the router moves it to the replica it is about to
        place a matching request on).  Read-only on refcounts; raises
        PrefixDrift when the local chain is shorter than the skip the
        router probed."""
        t0 = self._now()
        meta, k, v = self.cache.export_prefix_pages(prompt, skip_pages)
        self.metrics.prefix_pages_exported.inc(int(meta["n_pages"]))
        if self.trace.enabled:
            self.trace.flight.record(
                "prefix_export", pages=int(meta["n_pages"]),
                skip_pages=int(skip_pages),
                wall_s=round(self._now() - t0, 6))
        return meta, k, v

    def import_prefix(self, meta, k_arrays, v_arrays):
        """Land a shipped prefix payload in this engine's radix tree
        (pages enter CACHED at rc==0 — reclaimable capacity, exactly
        like a locally-prefilled prefix).  Returns the page count."""
        t0 = self._now()
        if self.chaos.fire("shard_geometry_mismatch"):
            raise GeometryMismatch(
                "chaos: shard geometry mismatch (tp_degree skew)")
        n = self.cache.import_prefix_pages(meta, k_arrays, v_arrays)
        self.metrics.prefix_pages_imported.inc(n)
        if self.trace.enabled:
            self.trace.flight.record(
                "prefix_import", pages=n,
                skip_pages=int(meta["skip_pages"]),
                wall_s=round(self._now() - t0, 6))
        return n

    def drop_prefix(self, prompt):
        """Router-driven dedup: evict this engine's unpinned cached
        chain for ``prompt`` (deepest-first).  Returns pages freed."""
        n = self.cache.drop_prefix(prompt)
        self.metrics.prefix_drops.inc(n)
        if self.trace.enabled and n:
            self.trace.flight.record("prefix_drop", pages=n)
        return n

    # -- hierarchical KV tier (round 20) -----------------------------------
    def restore_prefix(self, prompt):
        """Best-effort host-tier restore of ``prompt``'s missing prefix
        pages (the router's local-tier probe, between its device probe
        and the remote-donor loop).  Restored pages enter CACHED at
        rc==0 — shipped-prefix semantics, so admission accounting needs
        no new case.  Returns pages restored; 0 with no tier."""
        if self.kvtier is None:
            return 0
        return self.kvtier.restore(self.cache, prompt)

    def prewarm_prefix(self, max_chains=None):
        """Restore the hottest spilled chains into the device tree —
        the autoscaler's warm-up for a newly grown replica.  Returns
        total pages restored; strictly best-effort."""
        if self.kvtier is None:
            return 0
        return self.kvtier.prewarm(self.cache, max_chains)

    def tier_stats(self):
        """Host/disk tier occupancy + counters (``/healthz`` shape);
        None when no tier is attached."""
        return None if self.kvtier is None else self.kvtier.stats()

    def _fork(self, parent, i):
        child = Request(prompt=parent.prompt,
                        max_new_tokens=parent.max_new_tokens,
                        arrival=parent.arrival, deadline=parent.deadline,
                        do_sample=parent.do_sample,
                        temperature=parent.temperature,
                        top_k=parent.top_k, top_p=parent.top_p,
                        seed=(parent.seed or 0) + i, n=1,
                        logprobs=parent.logprobs,
                        request_id=parent.request_id)
        child.device_seed = (parent.device_seed + i) & 0x7FFFFFFF
        child.parent_id = parent.req_id
        child.first_token_at = None
        if self.trace.enabled:
            self.trace.begin(child.req_id, child.request_id)
            self.trace.span(child.req_id, "forked", self._now(),
                            parent=parent.req_id, index=i)
        self.cache.fork(parent.seq_id, child.seq_id)
        self._requests[child.req_id] = child
        self._rngs[child.req_id] = np.random.default_rng(child.seed)
        self.scheduler.register_fork(child)
        return child

    def _emit_token(self, req, tok, events, logprob=None):
        req.out_tokens.append(tok)
        now = self._now()
        if req.first_token_at is None:
            req.first_token_at = now
            self.metrics.ttft_s.record(now - req.arrival)
        else:
            self.metrics.inter_token_s.record(now - req.last_token_at)
        req.last_token_at = now
        self.metrics.tokens_generated.inc()
        ev = {"type": "token", "req_id": req.req_id, "token": tok}
        if req.logprobs and logprob is not None:
            ev["logprob"] = logprob
        self._event(ev, events)
        if self.eos is not None and tok == self.eos:
            self._finish(req, "stop", events)
        elif len(req.out_tokens) >= req.max_new_tokens:
            self._finish(req, "length", events)

    def _finish(self, req, reason, events):
        if self.cache.has_seq(req.seq_id):
            self.cache.free_seq(req.seq_id)
        self._free_draft_seq(req.seq_id)
        self.scheduler.finish(req, reason)
        self._record_finish(req, events)

    def _record_finish(self, req, events):
        self.metrics.requests_finished.inc()
        self._finished[req.req_id] = req
        tr = self.trace.finish(req.req_id)
        self._event({"type": "finish", "req_id": req.req_id,
                     "reason": req.finish_reason,
                     "n_tokens": len(req.out_tokens)}, events)
        if _log.isEnabledFor(logging.INFO):
            n = len(req.out_tokens)
            ttft = (req.first_token_at - req.arrival
                    if req.first_token_at is not None else None)
            tpot = ((req.last_token_at - req.first_token_at) / (n - 1)
                    if n > 1 else None)
            line = {
                "event": "request_finished", "req_id": req.req_id,
                "reason": req.finish_reason, "n_tokens": n,
                "prompt_tokens": int(req.prompt.size),
                "ttft_s": ttft, "tpot_s": tpot,
                "preemptions": req.preemptions,
                "cached_prompt_pages": req.cached_pages,
                "parent_id": req.parent_id,
                "request_id": req.request_id}
            if tr is not None:
                # span-derived phase decomposition: log scrapers get
                # queue/prefill/decode/stall without /debug/trace
                line["phases"] = tr.phase_breakdown()
                if tr.dropped:
                    line["trace_spans_dropped"] = tr.dropped
            _log.info(json.dumps(line))

    def _event(self, ev, events):
        events.append(ev)
        if self.on_event is not None:
            self.on_event(ev)

    def request(self, req_id):
        """Look up a Request by id (live or finished) — the front-end
        uses this to map forked children onto their parent's stream."""
        return self._requests.get(req_id)

    @staticmethod
    def _host_sampling():
        """Oracle escape hatch: PADDLE_TPU_SERVING_HOST_SAMPLE=1 keeps
        sampling on the host from fully-fetched logits (numpy RNG).
        Read per step so tests can flip it with monkeypatch."""
        return os.environ.get("PADDLE_TPU_SERVING_HOST_SAMPLE") == "1"

    def _sample(self, req, logits_row):
        """Host numpy sampling — the oracle path. Max-subtraction
        BEFORE exp is load-bearing: logits of ~1e3 otherwise overflow
        to inf/NaN (regression-tested)."""
        lg = np.asarray(logits_row, np.float32)
        if not req.do_sample:
            return int(lg.argmax())
        if req.temperature != 1.0:
            lg = lg / max(req.temperature, 1e-6)
        if req.top_k and req.top_k < lg.size:
            kth = np.partition(lg, -req.top_k)[-req.top_k]
            lg = np.where(lg < kth, -np.inf, lg)
        if 0.0 < req.top_p < 1.0:
            shifted = lg - lg.max()
            srt = np.sort(shifted)[::-1]
            p = np.exp(srt)
            p /= p.sum()
            keep = (np.cumsum(p) - p) < req.top_p  # keeps the crosser
            thr = srt[keep][-1]                    # smallest kept logit
            lg = np.where(shifted < thr, -np.inf, lg)
        lg = lg - lg.max()
        p = np.exp(lg)
        p /= p.sum()
        return int(self._rngs[req.req_id].choice(lg.size, p=p))

    @property
    def _last_logits_probe(self):
        """One row of the last step's logits, fetched on demand: its
        prefill chunk's last token where it carried a chunk (the row a
        completed prefill samples from), else its first packed token —
        parity-test observability (the hot path fetches no logits)."""
        if self._logits_dev is None:
            return None
        return np.asarray(self._logits_dev[self._logits_row], np.float32)

    def _fetch_logits(self):
        """Pull the last step's full [T, V] logits to the host (oracle
        sampling / fork seeding) and account the fetch."""
        out = np.asarray(self._logits_dev, np.float32)
        self.metrics.fetch_bytes.inc(out.nbytes)
        self.metrics.step_fetches.inc()
        return out

    def _record_moe_counts(self):
        """The last ragged step's routing counts into the metrics; the
        16 bytes come with the step's tokens (the program has finished
        by then: no further wait). Returns the experts hit, None for a
        model without sparse experts."""
        if self._moe_counts_dev is None:
            return None
        counts = np.asarray(self._moe_counts_dev)
        self._moe_counts_dev = None
        self.metrics.fetch_bytes.inc(counts.nbytes)
        self.metrics.record_moe_counts(counts)
        return int(counts[1])

    def _sync_prefix_metrics(self):
        c, m = self.cache, self.metrics
        m.prefix_hit_pages.value = c.prefix_hit_pages
        m.prefix_miss_pages.value = c.prefix_miss_pages
        m.prefix_evictions.value = c.prefix_evictions
        total = c.prefix_hit_pages + c.prefix_miss_pages
        m.prefix_hit_rate.set(c.prefix_hit_pages / total if total
                              else 0.0)
        m.cached_pages_gauge.set(c.cached_pages)
        if self.kvtier is not None:
            st = self.kvtier.pool.stats()
            m.host_pool_pages.set(st["host_pool_pages"])
            m.host_pool_bytes.set(st["host_pool_bytes"])
            m.disk_pool_pages.set(st.get("disk_pool_pages", 0))
        if m.spec_draft_tokens.value:
            m.spec_acceptance_rate.set(m.spec_accepted_tokens.value
                                       / m.spec_draft_tokens.value)

    def _count_dispatch(self, key):
        """Account one device dispatch and its compiled program class
        (``key`` is the static shape signature that keys the jit trace
        cache). ``step_program_classes`` is the gauge the step's two
        token capacities bound at <= 2. Draft-model programs
        (the propose scan is its own dispatch by design — different
        model, disposable K/V) count as dispatches but not as step
        classes."""
        self.metrics.step_dispatches.inc()
        if key[0].startswith("draft"):
            return
        if key not in self._program_classes:
            self._program_classes.add(key)
            self.metrics.step_program_classes.set(
                len(self._program_classes))

    def _count_sort(self, samp):
        """Count a target step of a sample-capable program in which the
        sampler's sort ran: the condition ``sampling.fused_sample``
        computes on the device, from the same arrays as the host packed
        them (no fetch; a greedy batch costs one ``any``)."""
        do_sample, _, top_k, top_p = samp[:4]
        if do_sample.any() and np.any(do_sample & filter_binds(
                top_k, top_p, self.model.cfg.vocab_size)):
            self.metrics.sampler_sort_steps.inc()

    def _tp_kernel_guard(self):
        """The loud Pallas guard (round 23): a TP step must never
        trace ``pallas_call`` into the SPMD program (no GSPMD
        partitioning rule — CLAUDE.md invariant), so when the mesh is
        active and ``PADDLE_TPU_PAGED_KERNEL=1`` asks for the kernel,
        the step refuses-and-falls-back to the jnp gather path —
        logged once, counted per step (``tp_kernel_fallbacks``).  The
        knob is re-read per step like ``_host_sampling`` so
        monkeypatch-mid-test workflows see honest accounting; the
        in-program bypass itself rides ``spmd=True`` through
        ``_paged_forward`` regardless of this metric."""
        if self._tp is None:
            return
        if os.environ.get("PADDLE_TPU_PAGED_KERNEL") != "1":
            return
        if not self._tp_kernel_warned:
            self._tp_kernel_warned = True
            _log.warning(json.dumps({
                "event": "tp_pallas_fallback",
                "tp_degree": self.tp_degree,
                "detail": "PADDLE_TPU_PAGED_KERNEL=1 ignored under "
                          "tensor parallelism: pallas_call has no "
                          "GSPMD partitioning rule; using the jnp "
                          "gather path"}))
        self.metrics.tp_kernel_fallbacks.inc()

    def _step_program(self):
        """The engine's one step function, built once. ONE jit fn; the
        token capacity in {small, mixed} bounds its trace cache at two
        entries — the <= 2-program-class contract. The sampler is
        always compiled sample-capable, so greedy and sampled steps
        share a class: a batch's sort and draw run under conditions on
        its own sampling arguments (sampling.py), and a greedy lane
        takes the argmax and the raw logprob either way.

        The cache's state (the K and V pools, with their scale rows
        where the cache is int8; a mixed cache's window pools and lane
        states) is DONATED: the program owns what it is handed, writes
        the step's rows in place, and its outputs are the same buffers
        (docs/SERVING.md "Who owns the pools"). A jit of a
        ``functools.partial`` has no name of its own: the program is
        ``jit__unknown``, which the benchmark's mixes match on."""
        import jax
        if self._ragged_fn is None:
            self._ragged_fn = jax.jit(
                functools.partial(_ragged_step_pure, self.model,
                                  self._core, self.window, self._tp,
                                  k1=self.spec_k + 1),
                donate_argnums=(9, 10, 11))     # k_pages, v_pages, extra
        return self._ragged_fn

    def _run_ragged_step(self, ids, positions, pt, cl, ql, qoff,
                         slot_map, samp, lane_extras):
        import jax.numpy as jnp
        self._tp_kernel_guard()
        warrs = [t._data for t in self.model._gen_state_tensors()]
        # what the program is handed and gives back: the pools and, of
        # a mixed cache, the window pools and lane states (``extra``:
        # empty where every layer owns a full pool); the lanes' slots
        # and window tables are the step's own host arrays
        state = (*self.cache.program_operands(),
                 self.cache.extra_operands())
        tok, lp, logits, k_pages, v_pages, moe_counts, extra = \
            self._step_program()(
                warrs, jnp.asarray(ids), jnp.asarray(positions),
                jnp.asarray(pt), jnp.asarray(cl), jnp.asarray(ql),
                jnp.asarray(qoff), jnp.asarray(slot_map),
                tuple(jnp.asarray(a) for a in samp), *state,
                {k: jnp.asarray(a) for k, a in lane_extras.items()})
        self.cache.store_operands(k_pages, v_pages)
        self.cache.store_extra(extra)
        self.metrics.pool_bytes_donated.set(_bytes_handed_over(state))
        self._logits_dev = logits          # [T, V], fetched on demand
        self._moe_counts_dev = moe_counts  # fetched with the tokens
        self._count_dispatch(("ragged", ids.shape[1]))
        self._count_sort(samp)
        return tok, lp


# -- the compiled step (weights as arguments; generation.py idiom) ---------

def _counter_sample_row(logits_row, req):
    """Eagerly sample ONE token from a fetched logits row with the same
    counter-RNG fused sampler the compiled program runs — fork children
    at prefill completion (one row, several seeds)."""
    import jax.numpy as jnp

    from .sampling import fused_sample
    tok, lp = fused_sample(
        jnp.asarray(logits_row, jnp.float32)[None],
        jnp.asarray([True]),
        jnp.asarray([req.temperature], jnp.float32),
        jnp.asarray([req.top_k], jnp.int32),
        jnp.asarray([req.top_p], jnp.float32),
        jnp.asarray([req.device_seed], jnp.int32),
        jnp.asarray([len(req.out_tokens)], jnp.int32))
    return int(np.asarray(tok)[0]), float(np.asarray(lp)[0])


def _draft_catchup_pure(draft, core, window, dwarrs, ids, positions,
                        pt, cl, slot_map, k_pages, v_pages):
    """The draft's catchup prefill: the trunk over one rectangular
    [1, C] chunk, run for the K/V it scatters into the draft's pools.
    Returns ``(new_k, new_v)``; the hidden state is not an output."""
    tensors = draft._gen_state_tensors()
    saved = [(t, t._data) for t in tensors]
    for t, arr in zip(tensors, dwarrs):
        t._data = arr
    try:
        _, new_k, new_v, _ = _paged_forward(core, window, ids, positions,
                                            pt, cl, slot_map, k_pages,
                                            v_pages)
        return new_k, new_v
    finally:
        for t, arr in saved:
            t._data = arr


def _paged_forward(core, window, ids, positions, pt, cl, slot_map,
                   k_pages, v_pages, ragged=None, tp=None, stats=None,
                   extra=None):
    """The transformer trunk over the paged cache: embed, attend (K/V
    scattered into the page pool), final norm. The step runs it with
    ``ragged=(query_lens, q_offsets, k1)``, the token-packed lane
    layout (``k1`` rows a decode/verify lane, then the chunk's rows):
    ids/positions/slot_map are [1, T] (the scatter is shape-agnostic)
    while pt/cl are the [L, P]/[L] PER-LANE arrays, each lane's table
    gathered once a layer. ``ragged=None``, the rectangular [B, S]
    form over ``paged_attention``, is the draft model's alone (catchup
    prefill and proposal scan); the packed layout gathers by lane too
    since PR 30, so the draft could take it at no extra gather
    (ROADMAP D-queue; not switched). Returns ``(hidden [B, S, D] jnp
    array, new_k, new_v, new_extra)``.

    ``tp`` (a :class:`~.tp.TPContext`) makes the trunk ONE SPMD
    program over the mesh.  The constraints below are the whole
    exactness argument (tp.py module docstring): activations are
    pinned REPLICATED wherever a sharded dim would otherwise feed a
    contraction (GSPMD would partial-sum + all-reduce there — a
    different f32 summation order than TP=1), and q/k/v plus the page
    pools are pinned head-sharded so the attention inner loop is
    shard-local.  The MLP is inlined under TP because
    ``layer.mlp(...)`` offers no hook to replicate the swiglu output
    before down_proj's contraction — the inline mirrors
    ``down_proj(swiglu(gate_proj(x), up_proj(x)))`` exactly.

    A layer that brings its own ``paged_forward(x, step)`` (latent
    attention and sparse experts, ``models/latent_moe.py``; state-space,
    window, shared-pool and gated-memory layers, ``models/sambay.py``)
    is asked for it under ONE protocol, :class:`~.attention.PagedStep`
    (:func:`_layers_own_forward`): it may own a pool, read another
    layer's, own a window pool or a lane state, and hand an activation
    on. ``k_pages`` holds the full pools of the layers that own one,
    in layer order, one entry a token (``v_pages`` is empty); ``extra`` the window pools, the lane states and the lanes'
    slots and window tables (``PagedKVCache.extra_operands`` and the
    step's buffers), empty where every layer owns a full pool.
    ``stats``, a list, receives such layers' routing counts. Such a
    model has no draft form, so its layers see the packed layout only.
    LLaMA-shaped layers run the code below (two paged forwards until D1
    gives the block one definition)."""
    from ..core.autograd import no_grad
    from ..core.tensor import Tensor
    from ..incubate.nn.functional import (
        fused_rotary_position_embedding, swiglu)
    from .attention import (paged_attention, quantize_q8,
                            ragged_paged_attention)

    spmd = tp is not None
    b, s = ids.shape
    flat_slots = slot_map.reshape(-1)
    with no_grad():
        x = core.embed_tokens(Tensor(ids))
        if spmd:
            # the embedding table is sharded on its hidden column dim,
            # so the gathered rows come out hidden-sharded: replicate
            # before the first layernorm (its reduction runs over the
            # hidden dim)
            x = Tensor(tp.replicate(x._data))
        pos_t = Tensor(positions)
        new_k, new_v = [], []
        if _brings_paged_forward(core):
            return _layers_own_forward(core, x, positions, flat_slots, pt,
                                       cl, ragged, k_pages, v_pages,
                                       extra or {}, stats)
        for layer, kp, vp in zip(core.layers, k_pages, v_pages):
            at = layer.self_attn
            nh, nkv, hd = at.num_heads, at.num_kv_heads, at.head_dim
            y = layer.input_layernorm(x)
            q = at.q_proj(y).reshape([b, s, nh, hd])
            k = at.k_proj(y).reshape([b, s, nkv, hd])
            v = at.v_proj(y).reshape([b, s, nkv, hd])
            if spmd:
                q = Tensor(tp.shard_heads(q._data))
                k = Tensor(tp.shard_heads(k._data))
                v = Tensor(tp.shard_heads(v._data))
            q, k, _ = fused_rotary_position_embedding(
                q, k, None, position_ids=pos_t,
                rotary_emb_base=at.cfg.rope_theta)
            if isinstance(kp, tuple):
                # int8 cache: quantize-on-append (deterministic
                # rounding — recompute regenerates identical pages),
                # codes and per-(slot, head) scales scattered side by
                # side; padded lanes land on the scratch page
                kq, ksc = kp
                vq, vsc = vp
                npg, ps, _, _ = kq.shape
                knq, kns = quantize_q8(k._data.reshape(b * s, nkv, hd))
                vnq, vns = quantize_q8(v._data.reshape(b * s, nkv, hd))
                kq = kq.reshape(npg * ps, nkv, hd).at[flat_slots].set(
                    knq).reshape(npg, ps, nkv, hd)
                ksc = ksc.reshape(npg * ps, nkv).at[flat_slots].set(
                    kns).reshape(npg, ps, nkv)
                vq = vq.reshape(npg * ps, nkv, hd).at[flat_slots].set(
                    vnq).reshape(npg, ps, nkv, hd)
                vsc = vsc.reshape(npg * ps, nkv).at[flat_slots].set(
                    vns).reshape(npg, ps, nkv)
                kp = (kq, ksc)
                vp = (vq, vsc)
            else:
                npg, ps, _, _ = kp.shape
                kp = kp.reshape(npg * ps, nkv, hd).at[flat_slots].set(
                    k._data.reshape(b * s, nkv, hd).astype(kp.dtype)
                ).reshape(npg, ps, nkv, hd)
                vp = vp.reshape(npg * ps, nkv, hd).at[flat_slots].set(
                    v._data.reshape(b * s, nkv, hd).astype(vp.dtype)
                ).reshape(npg, ps, nkv, hd)
            if spmd:
                # pin the freshly-scattered pools back to the head
                # sharding: the scatter is shard-aligned (values and
                # pools split on the same kv-head axis) and the pinned
                # outputs carry the layout into the NEXT step's
                # operands with no host round-trip
                kp = tp.shard_pool(kp)
                vp = tp.shard_pool(vp)
            new_k.append(kp)
            new_v.append(vp)
            if ragged is None:
                out = paged_attention(
                    q._data, kp, vp, pt, cl, positions[:, 0],
                    scale=1.0 / (hd ** 0.5), window=window, spmd=spmd)
            else:
                ql, qoff, k1 = ragged
                out = ragged_paged_attention(
                    q._data[0], kp, vp, pt, cl, ql, qoff,
                    scale=1.0 / (hd ** 0.5), window=window,
                    spmd=spmd, k1=k1)[None]
            ao = Tensor(out).reshape([b, s, nh * hd])
            if spmd:
                # o_proj contracts over the head dim — gather the
                # head-sharded attention rows first, then replicate
                # o_proj's column-sharded output before the residual
                ao = Tensor(tp.replicate(ao._data))
                o = at.o_proj(ao)
                h = x + Tensor(tp.replicate(o._data))
                h2 = layer.post_attention_layernorm(h)
                g = layer.mlp.gate_proj(h2)
                u = layer.mlp.up_proj(h2)
                a = swiglu(g, u)
                # down_proj contracts over the ffn dim gate/up sharded
                a = Tensor(tp.replicate(a._data))
                mo = layer.mlp.down_proj(a)
                x = h + Tensor(tp.replicate(mo._data))
            else:
                h = x + at.o_proj(ao)
                x = h + layer.mlp(layer.post_attention_layernorm(h))
        x = core.norm(x)
    return x._data, new_k, new_v, {}


def _brings_paged_forward(core):
    """Whether the layers run through their own ``paged_forward(x,
    step)`` (:func:`_layers_own_forward`) and not through the
    LLaMA-shaped block of :func:`_paged_forward`."""
    return all(hasattr(layer, "paged_forward") for layer in core.layers)


def _layers_own_forward(core, x, positions, flat_slots, pt, cl, ragged,
                        k_pages, v_pages, extra, stats):
    """The trunk of a model whose layers bring their own
    ``paged_forward(x, step)``: the step's operands are laid out by
    layer index in a :class:`~.attention.PagedStep`, every layer is
    asked in order, and what they left there is gathered up again in
    the operands' order."""
    from .attention import PagedStep
    layout = [layer.paged_cache for layer in core.layers]
    full = [i for i, lc in enumerate(layout) if lc.pool == "full"]
    wins = [i for i, lc in enumerate(layout) if lc.pool == "window"]
    stts = [i for i, lc in enumerate(layout) if lc.state]
    ql, qoff, k1 = ragged
    if v_pages:
        raise NotImplementedError(
            "a layer that brings its own paged_forward keeps one entry a "
            "token in a pool (LayerCache(latent=True)), not K and V apart")
    step = PagedStep(
        positions, flat_slots, pt, cl, ql, qoff, k1,
        pools=dict(zip(full, k_pages)),
        extra=dict(extra,
                   window_pools=dict(zip(wins, extra.get("window", ()))),
                   states=dict(zip(stts, extra.get("state", ())))),
        stats=stats)
    for layer in core.layers:
        x = layer.paged_forward(x, step)
    new_extra = {}
    if wins or stts:
        new_extra = {"window": [step.window_pools[i] for i in wins],
                     "state": [tuple(step.states[i]) for i in stts]}
    new_k = [step.pools[i] for i in full]
    return core.norm(x)._data, new_k, [], new_extra


def _step_tables_gathered(core, lanes, t, k1):
    """Page tables a layer's attention gathers in a step of ``t``
    packed rows, where every layer owns a full pool: one a lane and
    region (``attention.py::tables_gathered``), or one a row where the
    layers attend with ``PagedStep.per_token`` (the latent layers:
    ROADMAP R3). (Where the layers differ: ``_count_mixed_step``.)"""
    from .attention import tables_gathered
    return (t if _brings_paged_forward(core)
            else tables_gathered(lanes, t, k1))


# -- the step program (round 22 / PR 18) -----------------------------------

def _bytes_handed_over(state):
    """Bytes of the cache state a program took for its own: the operands
    its dispatch left deleted (donated). Host arithmetic over shapes;
    nought where a program was handed nothing."""
    import jax
    return sum(a.nbytes for a in jax.tree.leaves(state) if a.is_deleted())


def _ragged_step_pure(model, core, window, tp, warrs, ids, positions,
                      pt, cl, ql, qoff, slot_map, samp, k_pages,
                      v_pages, extra=None, lanes=None, k1=1):
    """``extra``: a mixed cache's window pools and lane states
    (``PagedKVCache.extra_operands``), returned updated; ``lanes``: the
    lanes' slots and window tables of this step, host arrays that are
    not state (a caller that lowers the step by hand may pass both in
    ``extra``)."""
    tensors = model._gen_state_tensors()
    saved = [(t, t._data) for t in tensors]
    for t, arr in zip(tensors, warrs):
        t._data = arr
    try:
        return _ragged_step_body(model, core, window, tp, ids,
                                 positions, pt, cl, ql, qoff, slot_map,
                                 samp, k_pages, v_pages,
                                 {**(extra or {}), **(lanes or {})}, k1)
    finally:
        for t, arr in saved:
            t._data = arr


def _ragged_step_body(model, core, window, tp, ids, positions, pt, cl,
                      ql, qoff, slot_map, samp, k_pages, v_pages,
                      extra=None, k1=1):
    """The token-packed step: the trunk runs at [1, T] (``k1`` rows a
    decode/verify lane, then the chunk's rows: ``_ragged_step``),
    lm_head + fused sampling cover EVERY packed row (each with its own
    per-token counter key — a verify token j carries steps0+j, the
    key a plain decode lane has at that token index; a prefill chunk's
    tokens but the prompt's last carry neutral params and their
    samples are discarded), and the host fetch is [T] ids + [T]
    logprobs. Always compiled sample-capable: a greedy lane takes
    fused_sample's argmax and raw logprob, so greedy and sampled steps
    share ONE class; the sort and the draw run only in a step where a
    token asks for them (sampling.py)."""
    import jax.numpy as jnp

    from ..core.autograd import no_grad
    from ..core.tensor import Tensor

    stats = []
    x, new_k, new_v, new_extra = _paged_forward(
        core, window, ids, positions, pt, cl, slot_map, k_pages, v_pages,
        ragged=(ql, qoff, k1), tp=tp, stats=stats, extra=extra)
    # sparse-expert layers' routing counts of this step, summed over
    # layers: int32 [4] (MOE_COUNTS), None for a model without them
    moe_counts = sum(stats[1:], stats[0]) if stats else None
    from .sampling import fused_sample
    do_sample, temperature, top_k, top_p, seeds, steps = samp
    with no_grad():
        logits = model.lm_head(Tensor(x))._data[0]           # [T, V]
    if tp is not None:
        # lm_head shards the vocab columns: gather the partial
        # (column-sliced, never partially-summed) logits so the fused
        # per-token sampling runs replicated — identical to TP=1
        logits = tp.replicate(logits)
    logits = logits.astype(jnp.float32)
    tokens, logprobs = fused_sample(
        logits, do_sample, temperature, top_k, top_p, seeds, steps,
        sample_capable=True)
    return tokens, logprobs, logits, new_k, new_v, moe_counts, new_extra


# -- the fused draft-proposal scan (speculative decoding, round 12) --------

def _spec_draft_pure(draft, core, window, sample_capable, dwarrs, ids0,
                     pos0, pt, cl0, slot_mat, samp, k_pages, v_pages):
    tensors = draft._gen_state_tensors()
    saved = [(t, t._data) for t in tensors]
    for t, arr in zip(tensors, dwarrs):
        t._data = arr
    try:
        return _spec_draft_body(draft, core, window, sample_capable,
                                ids0, pos0, pt, cl0, slot_mat, samp,
                                k_pages, v_pages)
    finally:
        for t, arr in saved:
            t._data = arr


def _spec_draft_body(draft, core, window, sample_capable, ids0, pos0,
                     pt, cl0, slot_mat, samp, k_pages, v_pages):
    """k+1 chained draft steps inside ONE compiled program
    (``lax.scan``): step j feeds the previous token at position
    ``pos0 + j`` (slot ``slot_mat[:, j]``, context ``cl0 + j``) and
    samples the next proposal with the SAME counter key the target's
    verify step will use for that position — correlated Gumbel noise
    is what lets a well-matched draft accept at the argmax-agreement
    rate even on sampled lanes. Returns ``(proposals [B, k+1] int32,
    new_k, new_v)``."""
    import jax
    import jax.numpy as jnp

    from ..core.autograd import no_grad
    from ..core.tensor import Tensor
    from .sampling import fused_sample

    do_sample, temperature, top_k, top_p, seeds, steps0 = samp
    n_steps = slot_mat.shape[1]

    def step(carry, xs):
        j, slots = xs
        kps, vps, tok = carry
        x, nk, nv, _ = _paged_forward(core, window, tok,
                                      (pos0 + j)[:, None], pt, cl0 + j,
                                      slots[:, None], kps, vps)
        with no_grad():
            logits = draft.lm_head(Tensor(x[:, -1:]))._data[:, 0]
        nxt, _ = fused_sample(
            logits.astype(jnp.float32), do_sample, temperature, top_k,
            top_p, seeds, steps0 + j, sample_capable=sample_capable)
        return (nk, nv, nxt[:, None]), nxt

    (new_k, new_v, _), toks = jax.lax.scan(
        step, (list(k_pages), list(v_pages), ids0),
        (jnp.arange(n_steps, dtype=jnp.int32),
         jnp.swapaxes(slot_mat, 0, 1)))
    return jnp.swapaxes(toks, 0, 1), new_k, new_v
