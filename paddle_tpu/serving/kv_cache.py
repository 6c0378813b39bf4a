"""Block-paged KV cache — the serving engine's memory subsystem.

Reference capability: vLLM's PagedAttention block manager and the TPU
ragged-paged-attention cache layout (PAPERS.md "Ragged Paged Attention");
Paddle analogue: FastDeploy/paddle.inference KV cache management.

Design (SURVEY.md §7 static-shape stance):
- K/V live in per-layer device buffers of shape
  ``[num_pages, page_size, n_kv_heads, head_dim]`` — FIXED shape for the
  whole engine lifetime, so every compiled step program sees the same
  cache operands and the jit cache stays bounded.
- The HOST owns all bookkeeping (free list, per-sequence page tables,
  refcounts): allocation never traces, and the device only ever sees
  int32 page-table/slot arrays as program ARGUMENTS.
- Page 0 is a reserved SCRATCH page: padded batch lanes write their
  garbage K/V there and padded page-table entries point at it, so every
  lane of a fixed-shape program has defined (masked-out) memory to touch.
- Copy-on-fork for n>1 sampling: ``fork()`` shares pages by refcount;
  the first append into a SHARED partial tail page triggers a
  copy-on-write (the allocator returns the page copies for the engine to
  apply on device before scattering new K/V).
- Radix-tree prefix caching (``prefix_cache=True``; vLLM automatic
  prefix caching / SGLang RadixAttention capability): FULL pages of
  PROMPT tokens are registered in a hash-keyed radix tree
  (``commit_prefix``) when their K/V lands on device, and a later
  sequence with the same token prefix shares them
  (``acquire_prefix`` — refcount bump, zero device work). Page
  refcounts count the SEQUENCES mapping a page; a cached page whose
  refcount drops to 0 stays resident (CACHED, reclaimable) instead of
  returning to the free list, and is LRU-evicted leaf-first only when
  the allocator actually needs the page. The last prompt token is never
  served from cache (its logits must come out of a real prefill step),
  so a lookup is capped at ``(hist_len - 1)`` tokens.

Page lifecycle with the prefix cache on::

    FREE ──append_slots──► ACTIVE (rc>0) ──commit_prefix──► ACTIVE+cached
      ▲                      │ free_seq                        │ free_seq
      │                      ▼                                 ▼ (rc→0)
      └────────── rc==0, not cached                CACHED (rc==0, in tree)
      ▲                                                        │
      └───────────── LRU leaf eviction (append_slots pressure)─┘

Sizing: pass ``num_pages`` directly or an ``hbm_budget_bytes`` — the
constructor derives the page count from the per-page byte cost across
all layers (both K and V), the way an engine start-up would budget VMEM/
HBM headroom left over after weights.

int8 quantized pages (``dtype="int8"``, round 15): each page stores
int8 CODES plus a float32 per-(slot, kv-head) absmax scale — the same
recipe the generation path proved at delta-NLL ~1e-3
(``generation._quantize_q8`` / BENCH_kv8_quality.json). Scales live in
separate ``k_scales``/``v_scales`` buffers of shape
``[num_pages, page_size, n_kv_heads]`` so the attention einsums can
stream the codes and fold the scales in post-dot; sizing accounts for
them (``page_bytes_per_page`` adds 4 bytes per slot per head), so an
``hbm_budget_bytes`` cache honestly yields ``2*D/(D+4)``× the bf16 page
count.  Quantization happens ON APPEND inside the compiled step
(deterministic rounding — preemption recompute and failover re-prefill
regenerate bit-identical pages) and export/import/migration carry the
scale arrays alongside the codes (each of the k/v array lists holds the
``n_layers`` code arrays followed by the ``n_layers`` scale arrays).
"""
from __future__ import annotations

import math
from collections import deque
from dataclasses import dataclass

import numpy as np

__all__ = ["PagedKVCache", "OutOfPages", "SCRATCH_PAGE", "LayerCache",
           "GeometryMismatch", "PrefixDrift"]

# page 0 is never handed to a sequence: padded lanes scatter/gather there
SCRATCH_PAGE = 0


@dataclass(frozen=True)
class LayerCache:
    """What ONE layer keeps for a sequence: the cache's make-up is a
    tuple of these, one a layer, taken from the model.

    - ``pool="full"``: a pool of ``num_pages`` pages addressed by the
      sequence's one page table, a key a token for the whole length
      (K and V ``[.., n_kv_heads, head_dim]``; ``latent``: one entry
      ``[.., head_dim]`` a token in one array, whatever the layer
      keeps in it: a compressed latent, or keys and values joined).
    - ``pool="window"`` (``latent`` entries only): a pool of its own in
      which a lane keeps only
      the pages a later row may still see (``window`` keys back): the
      lane's last ``ceil((window + prefill_chunk) / page) + 1`` pages,
      the rest released as the lane advances.
    - ``state``: fixed-size arrays a LANE (not a page): ``((name,
      shape, dtype), ...)``, dtype ``None`` = the cache's; held
      ``[max_lanes + 1, *shape]``, slot 0 the scratch lane.
    - ``reads``: the index of the layer whose full pool this one
      attends; it owns nothing.
    - all defaults: the layer keeps nothing for a sequence.
    """
    pool: str | None = None
    n_kv_heads: int = 0
    head_dim: int = 0
    latent: bool = False
    window: int = 0
    state: tuple = ()
    reads: int | None = None


class OutOfPages(RuntimeError):
    """Raised by the allocator when the free list cannot cover a request
    — the scheduler's signal to preempt or defer admission."""

    def __init__(self, needed, free):
        super().__init__(
            f"paged KV cache exhausted: need {needed} page(s), "
            f"{free} free")
        self.needed = needed
        self.free = free


class GeometryMismatch(ValueError):
    """A page-migration payload does not match this allocator's cache
    geometry (layers / kv heads / head dim / page size / dtype) — K/V
    bytes from a differently-shaped cache can never be spliced in."""


class PrefixDrift(RuntimeError):
    """The importing allocator's radix tree no longer matches the page
    count the exporter skipped: the shared prefix grew (another request
    committed more pages) or shrank (LRU eviction) between the probe
    and the import.  Carries ``cached_pages`` — the pages the importer
    ACTUALLY holds — so the migration driver can re-export the right
    suffix and retry."""

    def __init__(self, skip_pages, cached_pages):
        super().__init__(
            f"prefix drift: exporter skipped {skip_pages} cached "
            f"page(s) but the importer matched {cached_pages}")
        self.skip_pages = skip_pages
        self.cached_pages = cached_pages


class _RadixNode:
    """One FULL page of prompt tokens in the prefix tree. ``key`` is the
    page's token tuple (dict-hashed under the parent — the radix edge),
    so chains of nodes spell out token prefixes page by page."""

    __slots__ = ("key", "page", "parent", "children", "last_used")

    def __init__(self, key, page, parent, last_used):
        self.key = key
        self.page = page
        self.parent = parent
        self.children = {}
        self.last_used = last_used


class PagedKVCache:
    """Fixed-size-page KV pool with a free-list allocator, per-sequence
    page tables, and refcounted copy-on-fork sharing.

    Host bookkeeping is transactional: an allocation either fully
    succeeds or raises :class:`OutOfPages` with no state mutated, so the
    engine can preempt and retry safely.
    """

    def __init__(self, n_layers, n_kv_heads, head_dim, *, page_size=16,
                 num_pages=None, hbm_budget_bytes=None, dtype="float32",
                 prefix_cache=False, tp_degree=1, latent_dim=None,
                 layout=None, max_lanes=None, prefill_chunk=None):
        import jax.numpy as jnp
        if page_size < 1:
            raise ValueError(f"page_size must be >= 1, got {page_size}")
        # latent geometry (MLA): ONE pool a layer, [num_pages, page_size,
        # latent_dim] -- a token's compressed entry, shared by every
        # head -- held in ``k_pages``; ``v_pages`` is empty. Allocation,
        # page tables, copy-on-write and the prefix tree work on pages
        # and do not care. What moves page BYTES elsewhere (migration,
        # the tiers, an int8 layout, head-sharded pools) is not built
        # for it and refuses by name.
        self.latent = latent_dim is not None
        if self.latent:
            n_kv_heads, head_dim = 1, int(latent_dim)
            if str(jnp.dtype(dtype)) == "int8":
                raise NotImplementedError(
                    "an int8 latent cache is not built: the latent page "
                    "pool holds its entries in a float dtype")
            if int(tp_degree or 1) > 1:
                raise NotImplementedError(
                    "a latent page pool under tensor parallelism "
                    "(tp_degree > 1) is not built: one entry serves "
                    "every head")
        self.n_layers = int(n_layers)
        self.n_kv_heads = int(n_kv_heads)
        self.head_dim = int(head_dim)
        self.page_size = int(page_size)
        # tensor-parallel geometry (round 23): drives the migration
        # contract (geometry dict + per-shard wire payload lists) only
        # — device placement is the engine's tp.TPContext's job, the
        # cache stays jax-sharding-agnostic
        self.tp_degree = int(tp_degree or 1)
        if self.tp_degree < 1 or self.n_kv_heads % self.tp_degree:
            raise ValueError(
                f"tp_degree={tp_degree} must divide n_kv_heads="
                f"{n_kv_heads}")
        self.dtype = jnp.dtype(dtype)
        # int8 = quantized codes + per-(slot, head) f32 scales; any other
        # integer dtype would silently astype-truncate K/V to garbage
        if self.dtype.kind in "iu" and str(self.dtype) != "int8":
            raise ValueError(
                f"unsupported cache dtype {dtype!r}: use a float dtype "
                "or 'int8' (quantized codes + scales)")
        self.quantized = str(self.dtype) == "int8"
        per_page = self.page_bytes_per_page(
            n_layers, n_kv_heads, head_dim, page_size, self.dtype,
            latent=self.latent)
        if num_pages is None:
            if hbm_budget_bytes is None:
                raise ValueError(
                    "size the cache with either num_pages or "
                    "hbm_budget_bytes")
            num_pages = int(hbm_budget_bytes) // per_page
        num_pages = int(num_pages)
        # scratch + at least one allocatable page
        if num_pages < 2:
            raise ValueError(
                f"cache budget yields {num_pages} page(s); need >= 2 "
                f"({per_page} bytes/page across {n_layers} layers)")
        self.num_pages = num_pages
        self.bytes_total = num_pages * per_page
        # device buffers: per layer, [num_pages, page_size, n_kv, hd].
        # The cache OWNS them: a step program is handed them (donated)
        # through program_operands() / extra_operands() and gives them
        # back through store_operands() / store_extra(); nothing else
        # keeps a pool across a step (docs/SERVING.md "Who owns the
        # pools")
        shape = ((num_pages, self.page_size, self.head_dim) if self.latent
                 else (num_pages, self.page_size, self.n_kv_heads,
                       self.head_dim))
        self.k_pages = [jnp.zeros(shape, self.dtype)
                        for _ in range(self.n_layers)]
        self.v_pages = [] if self.latent else [
            jnp.zeros(shape, self.dtype) for _ in range(self.n_layers)]
        if self.quantized:
            sshape = (num_pages, self.page_size, self.n_kv_heads)
            self.k_scales = [jnp.zeros(sshape, jnp.float32)
                             for _ in range(self.n_layers)]
            self.v_scales = [jnp.zeros(sshape, jnp.float32)
                             for _ in range(self.n_layers)]
        else:
            self.k_scales = None
            self.v_scales = None
        # host bookkeeping
        self._free = deque(range(1, num_pages))  # page 0 = scratch
        self._rc = np.zeros(num_pages, np.int32)
        self._tables: dict[object, list[int]] = {}
        self._lens: dict[object, int] = {}
        # prefix cache (radix tree over full prompt-token pages)
        self.prefix_cache_enabled = bool(prefix_cache)
        self._prefix_root = _RadixNode(None, None, None, 0)
        self._cached: dict[int, _RadixNode] = {}  # page -> tree node
        self._clock = 0
        self.prefix_hit_pages = 0
        self.prefix_miss_pages = 0
        self.prefix_evictions = 0
        # page-transfer fast path (round 18): ONE compiled gather (and
        # ONE compiled scatter) across every pool per export/import,
        # instead of 2*n_layers(+scales) separate dispatches; indexes
        # are padded to powers of two onto the scratch page so the jit
        # trace cache stays bounded at log2(num_pages) entries
        self._gather_fn = None
        self._scatter_fn = None
        # hierarchical KV tier (round 20): when attached, LRU-evicted
        # rc-0 cached pages spill their wire payload to the host tier
        # instead of vanishing (kvtier.KVTier; strictly best-effort)
        self._tier = None
        self._init_mixed(layout, max_lanes, prefill_chunk)

    # -- a make-up in which the layers differ -------------------------------
    def _init_mixed(self, layout, max_lanes, prefill_chunk):
        """``layout`` (a :class:`LayerCache` a layer, from the model)
        says which layers own one of the ``n_layers`` full pools built
        above, which a window pool, which a lane state, and which read
        another layer's pool. ``None``: every layer owns a full pool.
        ``mixed`` is true where something beside full pools is kept;
        such a cache has ONE allocator and one page table a sequence
        for the full pools, a second free list for the window pools'
        pages (every window layer uses the same page ids: they advance
        together), and a lane slot a sequence for the states. The
        window pools and the states are sized for ``max_lanes``
        sequences at once, so they are a FIXED cost a lane that
        admission counts in lanes, not in pages."""
        import jax.numpy as jnp
        n = self.n_layers
        self.layout = tuple(layout) if layout is not None else tuple(
            LayerCache(pool="full", n_kv_heads=self.n_kv_heads,
                       head_dim=self.head_dim, latent=self.latent)
            for _ in range(n))
        self.full_layers = [i for i, lc in enumerate(self.layout)
                            if lc.pool == "full"]
        self.window_layers = [i for i, lc in enumerate(self.layout)
                              if lc.pool == "window"]
        self.state_layers = [i for i, lc in enumerate(self.layout)
                             if lc.state]
        if len(self.full_layers) != n:
            raise ValueError(
                f"the layout has {len(self.full_layers)} full pools, the "
                f"cache was built with {n}")
        self.mixed = bool(self.window_layers or self.state_layers)
        self.w_pages, self.lane_state = [], []
        self.window = 0
        self.window_pages_per_lane = 0
        self.state_bytes_per_lane = 0
        if not self.mixed:
            return
        for refused, what in (
                (self.quantized, "an int8 cache"),
                (self.tp_degree > 1,
                 "tensor parallelism (tp_degree > 1)"),
                (self.prefix_cache_enabled,
                 "prefix_cache=True: the tree keys on pages, and a hit "
                 "needs the lane state at the boundary")):
            if refused:
                raise NotImplementedError(
                    "a cache with window pools or lane state beside its "
                    f"pages is not built for {what}")
        if not max_lanes or not prefill_chunk:
            raise ValueError("a mixed layout is sized from max_lanes and "
                             "prefill_chunk")
        self.max_lanes = int(max_lanes)
        ps = self.page_size
        if self.window_layers:
            geo = {(lc.head_dim, lc.window, lc.latent)
                   for lc in map(self.layout.__getitem__,
                                 self.window_layers)}
            if len(geo) != 1 or not next(iter(geo))[2]:
                raise NotImplementedError(
                    "window layers keep one entry a token (latent=True) "
                    f"of one width and one window, not {sorted(geo)}")
            (whd, self.window, _), = geo
            # the keys a chunk's rows may see start window - 1 before
            # its first row: ceil((window + chunk) / page) pages, and one
            # more because neither end need lie on a page boundary
            self.window_pages_per_lane = math.ceil(
                (self.window + int(prefill_chunk)) / ps) + 1
            wn = self.max_lanes * self.window_pages_per_lane + 1
            self.w_pages = [jnp.zeros((wn, ps, whd), self.dtype)
                            for _ in self.window_layers]
            self._wfree = deque(range(1, wn))     # page 0 = scratch
            self._wtables: dict[object, list] = {}  # seq -> [first, pages]
            self._wslots: dict[object, np.ndarray] = {}
            self.state_bytes_per_lane += (
                len(self.window_layers) * self.window_pages_per_lane
                * ps * whd * self.dtype.itemsize)
        for i in self.state_layers:
            arrs = []
            for _, shape, dt in self.layout[i].state:
                dt = jnp.dtype(dt) if dt is not None else self.dtype
                arrs.append(jnp.zeros((self.max_lanes + 1,) + tuple(shape),
                                      dt))
                self.state_bytes_per_lane += (
                    int(np.prod(shape)) * dt.itemsize)
            self.lane_state.append(tuple(arrs))
        self._lane_free = deque(range(1, self.max_lanes + 1))  # 0 = scratch
        self._lane_slot: dict[object, int] = {}

    def _refuse_mixed(self, what):
        if self.mixed:
            raise NotImplementedError(
                f"{what} is not built for a cache with window pools or "
                "lane state beside its pages: its payload is pages of "
                "one pool geometry")

    def can_hold_lanes(self, n=1):
        """Whether ``n`` more sequences fit the part of the cache that
        is a fixed cost a lane (lane-state slots, window pages)."""
        return not self.mixed or len(self._lane_free) >= n

    def lane_slot(self, seq_id):
        """The sequence's slot in the lane-state arrays."""
        return self._lane_slot[seq_id]

    def window_pages_held(self, seq_id):
        """Pages of a window pool the sequence holds now."""
        return len(self._wtables[seq_id][1]) if self.window_layers else 0

    def window_table(self, seq_id):
        """``(row int32 [window_pages_per_lane], base)``: the window
        pools' pages the sequence holds, oldest first (pad = scratch),
        and the position of the first slot of the first of them."""
        first, pages = self._wtables[seq_id]
        row = np.full(self.window_pages_per_lane, SCRATCH_PAGE, np.int32)
        row[:len(pages)] = pages
        return row, first * self.page_size

    def window_slots(self, seq_id):
        """Flat window-pool slots of the tokens the sequence's last
        :meth:`append_slots` reserved."""
        return self._wslots[seq_id]

    def _append_window(self, seq_id, start, n_tokens):
        """The window pools' side of :meth:`append_slots`: release the
        pages no row from ``start`` on may see (every key of theirs lies
        more than ``window - 1`` behind ``start``), then take pages for
        positions ``start .. start + n_tokens - 1``. Cannot run out: the
        pools hold ``window_pages_per_lane`` pages for each of
        ``max_lanes`` sequences, and a lane slot was taken first."""
        ps = self.page_size
        tab = self._wtables[seq_id]
        keep_from = max(0, start - self.window + 1) // ps
        while tab[1] and tab[0] < keep_from:
            self._wfree.append(tab[1].pop(0))
            tab[0] += 1
        if not tab[1]:
            tab[0] = max(tab[0], start // ps)
        slots = np.empty(n_tokens, np.int32)
        for i in range(n_tokens):
            pos = start + i
            if pos // ps - tab[0] >= len(tab[1]):
                tab[1].append(self._wfree.popleft())
            slots[i] = tab[1][pos // ps - tab[0]] * ps + pos % ps
        if len(tab[1]) > self.window_pages_per_lane:  # pragma: no cover
            raise AssertionError(
                f"sequence {seq_id!r} holds {len(tab[1])} window pages, "
                f"over the bound {self.window_pages_per_lane}")
        self._wslots[seq_id] = slots

    def extra_operands(self):
        """What a step program takes beside :meth:`program_operands`:
        the window pools and the lane states, by layer (an empty dict
        where every layer owns a full pool)."""
        if not self.mixed:
            return {}
        return {"window": list(self.w_pages),
                "state": list(self.lane_state)}

    def store_extra(self, new):
        if self.mixed:
            self.w_pages = list(new["window"])
            self.lane_state = [tuple(s) for s in new["state"]]

    def attach_tier(self, tier):
        """Bind a :class:`~.kvtier.KVTier` so prefix-cache evictions
        spill to the host tier.  ``None`` detaches."""
        if tier is not None:
            self._refuse_latent("kvtier (host/disk page tiers)")
            self._refuse_mixed("kvtier (host/disk page tiers)")
        self._tier = tier

    def _refuse_latent(self, what):
        if self.latent:
            raise NotImplementedError(
                f"{what} is not built for the latent page pool: its "
                "payload format is K and V by head")

    # -- sizing helpers ---------------------------------------------------
    @staticmethod
    def page_bytes_per_page(n_layers, n_kv_heads, head_dim, page_size,
                            dtype, latent=False):
        """Bytes one page costs across every layer's K and V buffers
        (``latent``: across every layer's one pool of ``head_dim``-wide
        entries). int8 pages carry their f32 scale rows (4 bytes per
        slot per kv head, K and V each) so ``hbm_budget_bytes`` sizing
        honestly reflects the quantized capacity."""
        import jax.numpy as jnp
        dt = jnp.dtype(dtype)
        per_slot_head = int(head_dim) * dt.itemsize
        if latent:
            return int(n_layers) * int(page_size) * per_slot_head
        if str(dt) == "int8":
            per_slot_head += 4  # the float32 absmax scale
        return (2 * int(n_layers) * int(page_size) * int(n_kv_heads)
                * per_slot_head)

    @property
    def bytes_per_token(self):
        """What one cached token costs across every layer."""
        return self.bytes_total // (self.num_pages * self.page_size)

    def pages_for(self, n_tokens):
        """Pages a sequence of n_tokens occupies."""
        return math.ceil(max(int(n_tokens), 0) / self.page_size)

    # -- observability ----------------------------------------------------
    @property
    def allocatable_pages(self):
        return self.num_pages - 1  # minus scratch

    @property
    def free_pages(self):
        return len(self._free)

    @property
    def cached_pages(self):
        """Pages registered in the prefix tree (shared or reclaimable)."""
        return len(self._cached)

    @property
    def reclaimable_pages(self):
        """Cached pages no live sequence maps (rc==0) — evictable
        leaf-first, so all of them can be turned into free pages."""
        return sum(1 for p in self._cached if self._rc[p] == 0)

    @property
    def prefix_tree_depth(self):
        """Deepest chain in the radix tree, in pages — /healthz
        advertises it next to ``cached_pages`` so a router can see how
        much reusable prefix a replica actually holds."""
        best = 0
        stack = [(self._prefix_root, 0)]
        while stack:
            node, d = stack.pop()
            if d > best:
                best = d
            stack.extend((c, d + 1) for c in node.children.values())
        return best

    @property
    def available_pages(self):
        """Pages an allocation can actually obtain: the free list plus
        LRU-evictable cached pages. Equals ``free_pages`` with the
        prefix cache off — admission/watermark math uses this."""
        return len(self._free) + self.reclaimable_pages

    @property
    def used_pages(self):
        return self.allocatable_pages - len(self._free)

    def occupancy(self):
        return self.used_pages / max(self.allocatable_pages, 1)

    def has_seq(self, seq_id):
        return seq_id in self._tables

    def seq_len(self, seq_id):
        return self._lens[seq_id]

    def live_seqs(self):
        return list(self._tables)

    # -- sequence lifecycle -----------------------------------------------
    def alloc_seq(self, seq_id):
        """Register an empty sequence (pages arrive via append_slots)."""
        if seq_id in self._tables:
            raise ValueError(f"sequence {seq_id!r} already allocated")
        if self.mixed:
            if not self._lane_free:
                raise OutOfPages(1, 0)     # lanes, not pages: admission
            #                                counts them (can_hold_lanes)
            self._lane_slot[seq_id] = self._lane_free.popleft()
            if self.window_layers:
                self._wtables[seq_id] = [0, []]
        self._tables[seq_id] = []
        self._lens[seq_id] = 0

    def fork(self, parent_id, child_id):
        """Copy-on-fork: the child SHARES the parent's pages (refcounts
        bumped); the first append into the shared partial tail page
        copy-on-writes it. O(pages) host work, zero device copies."""
        if child_id in self._tables:
            raise ValueError(f"sequence {child_id!r} already allocated")
        self._refuse_mixed("fork (n > 1: the child would need the "
                           "parent's lane state and window pages copied)")
        table = self._tables[parent_id]
        for p in table:
            self._rc[p] += 1
        self._tables[child_id] = list(table)
        self._lens[child_id] = self._lens[parent_id]

    def free_seq(self, seq_id):
        """Release a sequence's pages (refcounted). Unknown ids raise —
        the double-free guard the allocator invariants tests pin. Pages
        registered in the prefix tree stay resident (CACHED) at rc==0
        instead of returning to the free list; eviction reclaims them
        under pressure."""
        if seq_id not in self._tables:
            raise KeyError(
                f"free_seq: unknown (or already freed) sequence "
                f"{seq_id!r}")
        for p in self._tables.pop(seq_id):
            self._rc[p] -= 1
            if self._rc[p] < 0:  # pragma: no cover - internal invariant
                raise AssertionError(f"page {p} refcount underflow")
            if self._rc[p] == 0 and p not in self._cached:
                self._free.append(p)
        del self._lens[seq_id]
        if self.mixed:
            # the slot's arrays are not zeroed here: the step starts a
            # lane whose first row is position 0 from a zero state
            self._lane_free.append(self._lane_slot.pop(seq_id))
            if self.window_layers:
                self._wfree.extend(self._wtables.pop(seq_id)[1])
                self._wslots.pop(seq_id, None)

    # -- allocation --------------------------------------------------------
    def append_slots(self, seq_id, n_tokens):
        """Reserve flat slot ids (page * page_size + offset) for the next
        ``n_tokens`` of ``seq_id``, allocating pages as needed.

        Returns ``(slots int32 [n_tokens], copies list[(src, dst)])``:
        ``copies`` is non-empty when a shared partial tail page had to be
        copy-on-written — the engine MUST ``apply_copies(copies)`` on the
        device buffers before scattering the new K/V.

        Transactional for SEQUENCE state: raises :class:`OutOfPages`
        (no sequence state touched) when free + reclaimable-cached pages
        cannot cover the need. When the free list alone falls short but
        reclaimable cached pages exist, the LRU cached leaves are
        evicted here — a cache-internal mutation, invisible to every
        live sequence.
        """
        if n_tokens <= 0:
            raise ValueError(f"append_slots: n_tokens={n_tokens}")
        table = self._tables[seq_id]
        ln = self._lens[seq_id]
        off = ln % self.page_size
        cow = (off != 0 and table and self._rc[table[-1]] > 1)
        new_pages = self.pages_for(ln + n_tokens) - self.pages_for(ln)
        need = new_pages + (1 if cow else 0)
        if need > self.available_pages:
            raise OutOfPages(need, self.available_pages)
        while need > len(self._free):
            if not self._evict_lru_leaf():  # pragma: no cover - guarded
                raise OutOfPages(need, self.available_pages)
        copies = []
        if cow:
            fresh = self._free.popleft()
            self._rc[fresh] = 1
            self._rc[table[-1]] -= 1  # shared page: rc stays >= 1
            copies.append((table[-1], fresh))
            table[-1] = fresh
        slots = np.empty(n_tokens, np.int32)
        for i in range(n_tokens):
            pos = ln + i
            if pos % self.page_size == 0:
                page = self._free.popleft()
                self._rc[page] = 1
                table.append(page)
            slots[i] = table[pos // self.page_size] * self.page_size \
                + pos % self.page_size
        self._lens[seq_id] = ln + n_tokens
        if self.window_layers:
            self._append_window(seq_id, ln, n_tokens)
        return slots, copies

    def free_tail(self, seq_id, new_len):
        """Roll a sequence BACK to ``new_len`` tokens — the speculative-
        decoding rejection path: slots written for rejected draft tokens
        are released by accounting alone (the K/V bytes stay in place,
        masked by context_len, and are overwritten when the sequence
        grows again). Pages that fall entirely beyond the new length are
        refcount-released; refcount-safe under prefix-cache sharing
        (cached pages stay RESIDENT at rc==0, exactly like free_seq) and
        n>1 forks (shared pages are only decref'd — the co-owner keeps
        them; spec writes CoW the shared tail first, so a rolled-back
        page is never one the sibling still reads through this table).
        """
        if seq_id not in self._tables:
            raise KeyError(f"free_tail: unknown sequence {seq_id!r}")
        self._refuse_mixed("free_tail (a rejected draft needs the lane "
                           "state rolled back)")
        new_len = int(new_len)
        ln = self._lens[seq_id]
        if new_len < 0 or new_len > ln:
            raise ValueError(
                f"free_tail: new_len={new_len} outside [0, {ln}]")
        table = self._tables[seq_id]
        keep = self.pages_for(new_len)
        for p in table[keep:]:
            self._rc[p] -= 1
            if self._rc[p] < 0:  # pragma: no cover - internal invariant
                raise AssertionError(f"page {p} refcount underflow")
            if self._rc[p] == 0 and p not in self._cached:
                self._free.append(p)
        del table[keep:]
        self._lens[seq_id] = new_len

    def apply_copies(self, copies):
        """Perform pending copy-on-write page copies on the device
        buffers (one batched gather-scatter per layer; quantized caches
        copy the scale rows along with the codes)."""
        if not copies:
            return
        import jax.numpy as jnp
        srcs = jnp.asarray([s for s, _ in copies], jnp.int32)
        dsts = jnp.asarray([d for _, d in copies], jnp.int32)
        self.k_pages = [kp.at[dsts].set(kp[srcs]) for kp in self.k_pages]
        self.v_pages = [vp.at[dsts].set(vp[srcs]) for vp in self.v_pages]
        if self.quantized:
            self.k_scales = [ks.at[dsts].set(ks[srcs])
                             for ks in self.k_scales]
            self.v_scales = [vs.at[dsts].set(vs[srcs])
                             for vs in self.v_scales]

    def program_operands(self):
        """The per-layer K/V operands a compiled step program consumes:
        plain arrays for float caches, ``(codes, scales)`` tuples for
        int8 — the shape :func:`~.attention.paged_attention` and the
        engine's scatter path branch on. Returns ``(k_ops, v_ops)``."""
        if not self.quantized:
            return self.k_pages, self.v_pages
        return ([tuple(p) for p in zip(self.k_pages, self.k_scales)],
                [tuple(p) for p in zip(self.v_pages, self.v_scales)])

    def store_operands(self, new_k, new_v):
        """Write a step program's updated K/V operands back (the inverse
        of :meth:`program_operands`)."""
        if not self.quantized:
            self.k_pages = list(new_k)
            self.v_pages = list(new_v)
            return
        self.k_pages = [p for p, _ in new_k]
        self.k_scales = [s for _, s in new_k]
        self.v_pages = [p for p, _ in new_v]
        self.v_scales = [s for _, s in new_v]

    def recover_lost_pools(self):
        """The failure path's: a step program that fails after it was
        handed the pools takes them with it (they were donated, so the
        arrays held here are deleted). Brings the cache back usable:
        fresh zero pools, window pools and lane states, each placed as
        the lost one was, and an empty prefix tree — the cached pages'
        bytes are gone, so they count as evictions (and are not
        spilled: there is nothing to spill). Called once every sequence
        is released (``ServingEngine.release_live``). Returns the cached
        pages lost, or ``None`` where no pool was lost: a step that
        failed before its dispatch costs nothing."""
        import jax
        import jax.numpy as jnp
        arrays = jax.tree.leaves(
            (self._all_pools(), self.w_pages, self.lane_state))
        if not any(a.is_deleted() for a in arrays):
            return None
        if self._tables:
            raise RuntimeError(
                f"recover_lost_pools: {len(self._tables)} sequence(s) "
                "still map pages whose bytes are lost; release them first")
        for a in arrays:            # a pool that survived holds half a page
            if not a.is_deleted():
                a.delete()

        def fresh(a):
            return jnp.zeros(a.shape, a.dtype, device=a.sharding)

        self._store_pools([fresh(a) for a in self._all_pools()])
        self.w_pages = [fresh(a) for a in self.w_pages]
        self.lane_state = [tuple(fresh(a) for a in s)
                           for s in self.lane_state]
        lost = len(self._cached)
        self._free.extend(self._cached)
        self._cached = {}
        self._prefix_root = _RadixNode(None, None, None, 0)
        self.prefix_evictions += lost
        return lost

    def page_table(self, seq_id, max_pages):
        """Padded int32 page-table row for the fixed-shape step program
        (padding points at the scratch page; masked by context_len)."""
        table = self._tables[seq_id]
        if len(table) > max_pages:
            raise ValueError(
                f"sequence {seq_id!r} spans {len(table)} pages > "
                f"max_pages_per_seq {max_pages}")
        row = np.full(max_pages, SCRATCH_PAGE, np.int32)
        row[:len(table)] = table
        return row

    def refcount(self, page):
        return int(self._rc[page])

    def pages_held(self, seq_id):
        """Pages currently mapped by seq_id (0 for unknown sequences) —
        admission accounting for admitted-but-unallocated requests."""
        return len(self._tables.get(seq_id, ()))

    # -- prefix cache (radix tree over full prompt-token pages) ------------
    def _prefix_cap_pages(self, prompt_len, hist_len):
        """Pages of ``prompt`` a lookup may serve from cache. The last
        HISTORY token is never cached-over (its logits must come from a
        real prefill step), and only prompt tokens are ever in the
        tree."""
        return max(0, min(int(prompt_len), int(hist_len) - 1)) \
            // self.page_size

    def _walk(self, tokens, cap_pages):
        """Longest-prefix match: the chain of tree nodes whose pages
        spell out ``tokens``'s leading full pages (up to cap_pages)."""
        node = self._prefix_root
        chain = []
        ps = self.page_size
        for i in range(cap_pages):
            child = node.children.get(
                tuple(int(t) for t in tokens[i * ps:(i + 1) * ps]))
            if child is None:
                break
            chain.append(child)
            node = child
        return chain

    def probe_prefix(self, prompt, hist_len=None):
        """Lookup-only longest-prefix match: how many of ``prompt``'s
        pages the cache could serve right now. No refcount or LRU
        mutation — safe for reservation math (the front-end's
        uncached-page accounting)."""
        if not self.prefix_cache_enabled:
            return 0
        if hist_len is None:
            hist_len = len(prompt)
        return len(self._walk(
            prompt, self._prefix_cap_pages(len(prompt), hist_len)))

    def acquire_prefix(self, seq_id, prompt, hist_len):
        """Register ``seq_id`` with its longest cached prompt prefix
        PINNED (refcount bump per matched page — eviction cannot touch
        them while the sequence lives). Creates the sequence, so call it
        INSTEAD of :meth:`alloc_seq`; with the cache disabled it is
        exactly alloc_seq. Returns the number of cached pages mapped;
        the sequence's length starts at ``matched * page_size`` and the
        prefill path must skip those tokens."""
        if seq_id in self._tables:
            raise ValueError(f"sequence {seq_id!r} already allocated")
        if not self.prefix_cache_enabled:
            self._tables[seq_id] = []
            self._lens[seq_id] = 0
            return 0
        cap = self._prefix_cap_pages(len(prompt), hist_len)
        chain = self._walk(prompt, cap)
        self._clock += 1
        for node in chain:
            node.last_used = self._clock
            self._rc[node.page] += 1
        self._tables[seq_id] = [n.page for n in chain]
        self._lens[seq_id] = len(chain) * self.page_size
        return len(chain)

    def record_prefix_stats(self, prompt, hist_len, hit_pages):
        """Account one request's hit/miss page counts — called by the
        scheduler ONCE per prefill, when the request actually starts
        (pins made at submit/admission may be refreshed before then, so
        counting at acquire time would double-count)."""
        cap = self._prefix_cap_pages(len(prompt), hist_len)
        self.prefix_hit_pages += hit_pages
        self.prefix_miss_pages += max(0, cap - hit_pages)

    def commit_prefix(self, seq_id, prompt, upto):
        """Insert ``seq_id``'s now-prefilled FULL prompt pages into the
        tree (tokens ``[0, min(upto, len(prompt)))``). Pages whose token
        chunk already has a canonical node keep that node (duplicate
        content under a different page is simply not registered — the
        K/V bytes are equivalent, so mixed chains stay exact). Returns
        the number of nodes added."""
        if not self.prefix_cache_enabled or seq_id not in self._tables:
            return 0
        ps = self.page_size
        n_full = min(int(upto), len(prompt)) // ps
        table = self._tables[seq_id]
        node = self._prefix_root
        self._clock += 1
        added = 0
        for i in range(n_full):
            key = tuple(int(t) for t in prompt[i * ps:(i + 1) * ps])
            child = node.children.get(key)
            if child is None:
                page = table[i]
                if page in self._cached:  # pragma: no cover - invariant
                    raise AssertionError(
                        f"page {page} already registered in the tree")
                child = _RadixNode(key, page, node, self._clock)
                node.children[key] = child
                self._cached[page] = child
                added += 1
            child.last_used = self._clock
            node = child
        return added

    def clear_prefix(self):
        """Flush every reclaimable (rc==0) cached page back to the free
        list — the weight-reload path: cached K/V computed under OLD
        weights must never be served to post-reload requests. On an
        idle (drained) engine every cached page has rc==0, so this is a
        full tree flush. Returns the number of pages reclaimed.

        The attached KV tier (if any) is detached for the loop and
        INVALIDATED after it: reload-flushed pages hold K/V computed
        under the OLD weights, so spilling them — or keeping anything
        already spilled — would serve stale bytes to post-reload
        requests."""
        n = 0
        tier, self._tier = self._tier, None
        try:
            while self._evict_lru_leaf():
                n += 1
        finally:
            self._tier = tier
        if tier is not None:
            tier.invalidate()
        return n

    # -- page migration (disaggregated prefill/decode, round 14) -----------
    def geometry(self):
        """The shape contract a migration payload must satisfy."""
        return {"n_layers": self.n_layers, "n_kv_heads": self.n_kv_heads,
                "head_dim": self.head_dim, "page_size": self.page_size,
                "dtype": str(self.dtype), "tp_degree": self.tp_degree}

    def check_geometry(self, meta):
        mine = self.geometry()
        theirs = {k: meta.get(k) for k in mine}
        if mine != theirs:
            raise GeometryMismatch(
                f"page payload geometry {theirs} does not match this "
                f"cache ({mine})")

    def export_pages(self, seq_id, skip_pages=0):
        """Fetch a sequence's page chain — K/V bytes plus layout meta —
        for migration to another allocator (the disaggregated
        prefill→decode handoff).  ``skip_pages`` leading pages are
        omitted: the radix tree is the transfer index, and prefix pages
        the importer already holds resident are never re-transferred.

        Read-only (refcounts untouched): migration is copy-then-release,
        so a failed transfer leaves the source sequence intact.  Returns
        ``(meta, k_arrays, v_arrays)`` — per-layer numpy arrays of shape
        ``[n_pages, page_size, n_kv_heads, head_dim]``.  Quantized
        (int8) caches append the per-layer float32 scale arrays
        (``[n_pages, page_size, n_kv_heads]``) AFTER the code arrays in
        each list — the wire format records every array's own shape and
        dtype, so the scale geometry rides the same payload.

        Tensor-parallel caches (``tp_degree=t > 1``) split every array
        into t per-shard chunks along the kv-head axis, layer-major /
        shard-minor (``[L0S0, L0S1, ..., L1S0, ...]``; int8 scale
        arrays after ALL code arrays, split the same way — scales ride
        every shard, the round-15 rule).  ``tp_degree`` is part of
        :meth:`geometry`, so a degree-skewed import bounces on
        :class:`GeometryMismatch` up front — the router/disagg
        re-prefill fallback covers it.
        """
        self._refuse_latent("pagewire / disagg page shipping (export_pages)")
        self._refuse_mixed("pagewire / disagg page shipping (export_pages)")
        if seq_id not in self._tables:
            raise KeyError(f"export_pages: unknown sequence {seq_id!r}")
        table = self._tables[seq_id]
        skip_pages = int(skip_pages)
        if not 0 <= skip_pages <= len(table):
            raise ValueError(
                f"export_pages: skip_pages={skip_pages} outside "
                f"[0, {len(table)}]")
        pages = table[skip_pages:]
        meta = dict(self.geometry(), seq_len=self._lens[seq_id],
                    skip_pages=skip_pages, n_pages=len(pages))
        if not pages:
            empty = self._empty_payload()
            return meta, empty, [a.copy() for a in empty]
        k, v = self._fetch_pages(pages)
        return meta, self._split_shards(k), self._split_shards(v)

    def import_pages(self, seq_id, meta, k_arrays, v_arrays,
                     prompt=None, hist_len=None):
        """Splice an exported page chain into THIS allocator as a new
        sequence: acquire the locally-cached shared prefix (the pages
        the exporter skipped), allocate fresh pages for the transferred
        suffix, scatter the K/V bytes into the device buffers, and —
        with the prefix cache on — register the now-resident full
        prompt pages back into the radix tree.

        Raises :class:`GeometryMismatch` when the payload's cache shape
        differs, :class:`PrefixDrift` when the local radix match no
        longer equals ``meta["skip_pages"]`` (pages committed or
        evicted since the exporter probed — the caller re-exports with
        the carried ``cached_pages`` and retries), :class:`OutOfPages`
        when free + reclaimable pages cannot host the suffix.  All
        failures roll back fully (no sequence state left behind).
        """
        self._refuse_latent("pagewire / disagg page shipping (import_pages)")
        self._refuse_mixed("pagewire / disagg page shipping (import_pages)")
        self.check_geometry(meta)
        if seq_id in self._tables:
            raise ValueError(f"sequence {seq_id!r} already allocated")
        skip = int(meta["skip_pages"])
        n_pages = int(meta["n_pages"])
        seq_len = int(meta["seq_len"])
        if self.pages_for(seq_len) != skip + n_pages:
            raise ValueError(
                f"import_pages: seq_len={seq_len} spans "
                f"{self.pages_for(seq_len)} page(s), payload covers "
                f"{skip}+{n_pages}")
        self._check_payload_shapes(n_pages, k_arrays, v_arrays)
        # pin the locally-resident prefix; must match what the exporter
        # skipped or the page/token alignment breaks (PrefixDrift)
        if self.prefix_cache_enabled and prompt is not None:
            matched = self.acquire_prefix(
                seq_id, prompt,
                len(prompt) + 1 if hist_len is None else hist_len)
        else:
            self._tables[seq_id] = []
            self._lens[seq_id] = 0
            matched = 0
        if matched != skip:
            self.free_seq(seq_id)
            raise PrefixDrift(skip, matched)
        try:
            if n_pages > self.available_pages:
                raise OutOfPages(n_pages, self.available_pages)
            while n_pages > len(self._free):
                if not self._evict_lru_leaf():  # pragma: no cover
                    raise OutOfPages(n_pages, self.available_pages)
        except OutOfPages:
            self.free_seq(seq_id)
            raise
        table = self._tables[seq_id]
        fresh = [self._free.popleft() for _ in range(n_pages)]
        for p in fresh:
            self._rc[p] = 1
        table.extend(fresh)
        self._lens[seq_id] = seq_len
        self._scatter_pages(fresh, self._merge_shards(k_arrays),
                            self._merge_shards(v_arrays))
        if self.prefix_cache_enabled and prompt is not None:
            # the imported prompt pages are canonical K/V: later
            # shared-prefix requests on THIS replica hit them.  Bounded
            # by seq_len: a sequence imported SHORTER than its prompt
            # (rolled back below it) holds fewer pages than the prompt
            # spans, and commit must never index past its table.
            self.commit_prefix(seq_id, prompt, min(len(prompt),
                                                   seq_len))
        return len(table)

    def _check_payload_shapes(self, n_pages, k_arrays, v_arrays):
        """Validate an incoming page payload's array count and shapes
        against this cache's geometry (codes + scales for int8).  The
        wire unit is the per-shard chunk: t = tp_degree chunks per
        layer, kv-head extent n_kv_heads // t each."""
        t = self.tp_degree
        kv = self.n_kv_heads // t
        shape = (n_pages, self.page_size, kv, self.head_dim)
        sshape = (n_pages, self.page_size, kv)
        n_codes = self.n_layers * t
        per_list = n_codes * (2 if self.quantized else 1)
        for arrs, what in ((k_arrays, "k"), (v_arrays, "v")):
            if len(arrs) != per_list:
                raise GeometryMismatch(
                    f"{what} payload has {len(arrs)} array(s), this "
                    f"cache expects {per_list} ({self.n_layers} "
                    f"layer(s) x {t} shard(s)"
                    + (" of codes + scales)" if self.quantized
                       else ")"))
            for a in arrs[:n_codes]:
                if tuple(a.shape) != shape:
                    raise GeometryMismatch(
                        f"{what} page array shape {tuple(a.shape)} != "
                        f"{shape}")
            for a in arrs[n_codes:]:
                if tuple(a.shape) != sshape:
                    raise GeometryMismatch(
                        f"{what} scale array shape {tuple(a.shape)} != "
                        f"{sshape}")

    def _empty_payload(self):
        """A zero-page export's array list — the SAME per-shard wire
        structure as a real payload so shape validation never branches
        on emptiness."""
        t = self.tp_degree
        kv = self.n_kv_heads // t
        empty = [np.empty((0, self.page_size, kv, self.head_dim),
                          self.dtype)
                 for _ in range(self.n_layers * t)]
        if self.quantized:
            empty += [np.empty((0, self.page_size, kv), np.float32)
                      for _ in range(self.n_layers * t)]
        return empty

    def _split_shards(self, arrays):
        """Per-layer fetched arrays -> the per-shard wire lists
        (layer-major / shard-minor; no-op at tp_degree=1).  Works for
        codes [n, PS, KV, D] and scales [n, PS, KV] alike — the
        kv-head axis is axis 2 in both."""
        if self.tp_degree == 1:
            return list(arrays)
        out = []
        for a in arrays:
            out.extend(np.split(np.asarray(a), self.tp_degree, axis=2))
        return out

    def _merge_shards(self, arrays):
        """Inverse of :meth:`_split_shards`: t consecutive per-shard
        chunks concatenate back into one per-layer array."""
        if self.tp_degree == 1:
            return list(arrays)
        t = self.tp_degree
        return [np.concatenate([np.asarray(x) for x in
                                arrays[i:i + t]], axis=2)
                for i in range(0, len(arrays), t)]

    def _all_pools(self):
        """Every device pool in canonical order (k, v[, k_scales,
        v_scales]) — the operand list of the fused transfer programs."""
        pools = list(self.k_pages) + list(self.v_pages)
        if self.quantized:
            pools += list(self.k_scales) + list(self.v_scales)
        return pools

    def _store_pools(self, pools):
        ln = self.n_layers
        self.k_pages = list(pools[:ln])
        self.v_pages = list(pools[ln:2 * ln])
        if self.quantized:
            self.k_scales = list(pools[2 * ln:3 * ln])
            self.v_scales = list(pools[3 * ln:])

    @staticmethod
    def _pad_pow2(pages):
        """Pow2-padded int32 index row; padding points at the scratch
        page (garbage by contract), bounding the transfer programs'
        trace cache."""
        pad = 1
        while pad < len(pages):
            pad <<= 1
        idx = np.full(pad, SCRATCH_PAGE, np.int32)
        idx[:len(pages)] = pages
        return idx

    def _fetch_pages(self, pages):
        """Fetch a page chain from every pool — ONE compiled gather +
        ONE host transfer (the per-layer dispatch overhead otherwise
        dominates a prefix ship).  Returns ``(k_arrays, v_arrays)`` in
        the export list shape (codes then scales)."""
        import jax
        import jax.numpy as jnp
        n = len(pages)
        idx = self._pad_pow2(pages)
        if self._gather_fn is None:
            self._gather_fn = jax.jit(
                lambda pools, i: [p[i] for p in pools])
        out = jax.device_get(
            self._gather_fn(self._all_pools(), jnp.asarray(idx)))
        out = [a[:n] for a in out]
        ln = self.n_layers
        k = out[:ln]
        v = out[ln:2 * ln]
        if self.quantized:
            k += out[2 * ln:3 * ln]
            v += out[3 * ln:]
        return k, v

    def _scatter_pages(self, dsts, k_arrays, v_arrays):
        """Write an imported payload's K/V (and scales) into freshly
        allocated device pages — ONE compiled scatter across every
        pool."""
        if not dsts:
            return
        import jax
        import jax.numpy as jnp
        n = len(dsts)
        idx = self._pad_pow2(dsts)
        ln = self.n_layers
        vals = list(k_arrays[:ln]) + list(v_arrays[:ln])
        if self.quantized:
            vals += list(k_arrays[ln:]) + list(v_arrays[ln:])
        if len(idx) != n:
            vals = [np.concatenate(
                [np.asarray(a),
                 np.zeros((len(idx) - n,) + tuple(a.shape[1:]),
                          np.asarray(a).dtype)]) for a in vals]
        if self._scatter_fn is None:
            self._scatter_fn = jax.jit(
                lambda pools, i, vs: [
                    p.at[i].set(v.astype(p.dtype))
                    for p, v in zip(pools, vs)])
        self._store_pools(self._scatter_fn(
            self._all_pools(), jnp.asarray(idx),
            [jnp.asarray(a) for a in vals]))

    # -- fleet prefix transfer (router-driven prefix ships, round 18) ------
    def export_prefix_pages(self, prompt, skip_pages=0):
        """Export the CACHED prefix of ``prompt`` — no live sequence
        involved: the radix tree itself is the source (the fleet prefix
        ship: a donor replica serves its cached pages to a replica the
        router is about to place a matching request on).  ``skip_pages``
        leading pages are omitted (the recipient already holds them).

        Read-only on refcounts; the exported chain's LRU clocks are
        refreshed (a donated prefix is demonstrably hot).  Raises
        :class:`PrefixDrift` when the local match is SHORTER than
        ``skip_pages`` (the tree shrank since the router probed —
        ``cached_pages`` carries the true count).  Returns
        ``(meta, k_arrays, v_arrays)`` with ``meta["kind"] ==
        "prefix"`` and ``meta["prompt"]`` holding the FULL matched
        token prefix (skipped pages included, so the importer can walk
        its own tree from the root)."""
        self._refuse_latent(
            "pagewire / disagg page shipping (export_prefix_pages)")
        prompt = np.asarray(prompt, np.int32).reshape(-1)
        chain = self._walk(prompt, len(prompt) // self.page_size)
        matched = len(chain)
        skip_pages = int(skip_pages)
        if skip_pages > matched:
            raise PrefixDrift(skip_pages, matched)
        self._clock += 1
        for node in chain:
            node.last_used = self._clock
        pages = [n.page for n in chain[skip_pages:]]
        meta = dict(self.geometry(), kind="prefix",
                    skip_pages=skip_pages, n_pages=len(pages),
                    cached_pages=matched,
                    prompt=[int(t) for t in
                            prompt[:matched * self.page_size]])
        if not pages:
            empty = self._empty_payload()
            return meta, empty, [a.copy() for a in empty]
        k, v = self._fetch_pages(pages)
        return meta, self._split_shards(k), self._split_shards(v)

    def import_prefix_pages(self, meta, k_arrays, v_arrays):
        """Splice a shipped prefix payload into THIS allocator's radix
        tree: the imported pages enter as CACHED (rc==0, reclaimable)
        full prompt pages — exactly the state a locally-prefilled-and-
        freed prefix leaves behind, so every existing accounting rule
        (LRU eviction, uncached-only admission, conservation) applies
        unchanged.

        The local tree must match exactly ``meta["skip_pages"]`` pages
        of the payload's token prefix — :class:`PrefixDrift` otherwise
        (pages committed or evicted since the router probed; the
        carried ``cached_pages`` lets the driver re-export the right
        suffix).  :class:`GeometryMismatch` on any shape/dtype skew,
        :class:`OutOfPages` when the suffix cannot be hosted.  All
        failures roll back fully.  Returns the number of pages
        imported."""
        self._refuse_latent(
            "pagewire / disagg page shipping (import_prefix_pages)")
        if not self.prefix_cache_enabled:
            raise GeometryMismatch(
                "prefix ship into a cache with prefix_cache disabled: "
                "imported pages could never be registered or reused")
        self.check_geometry(meta)
        prompt = np.asarray(meta["prompt"], np.int32).reshape(-1)
        skip = int(meta["skip_pages"])
        n_pages = int(meta["n_pages"])
        if prompt.size != (skip + n_pages) * self.page_size:
            raise ValueError(
                f"import_prefix_pages: prompt of {prompt.size} token(s)"
                f" does not span exactly {skip}+{n_pages} full page(s)")
        self._check_payload_shapes(n_pages, k_arrays, v_arrays)
        # pin the locally-resident lead (a temp sequence protects both
        # the matched chain and the fresh pages from the evict loop)
        sid = ("__prefix_import__", self._clock)
        matched = self.acquire_prefix(sid, prompt, prompt.size + 1)
        if matched != skip:
            self.free_seq(sid)
            raise PrefixDrift(skip, matched)
        try:
            if n_pages > self.available_pages:
                raise OutOfPages(n_pages, self.available_pages)
            while n_pages > len(self._free):
                if not self._evict_lru_leaf():  # pragma: no cover
                    raise OutOfPages(n_pages, self.available_pages)
        except OutOfPages:
            self.free_seq(sid)
            raise
        table = self._tables[sid]
        fresh = [self._free.popleft() for _ in range(n_pages)]
        for p in fresh:
            self._rc[p] = 1
        table.extend(fresh)
        self._lens[sid] = prompt.size
        self._scatter_pages(fresh, self._merge_shards(k_arrays),
                            self._merge_shards(v_arrays))
        self.commit_prefix(sid, prompt, prompt.size)
        # drop the pin: committed pages stay resident (CACHED, rc==0)
        self.free_seq(sid)
        return n_pages

    def drop_prefix(self, prompt):
        """Evict ``prompt``'s cached chain AND its whole unpinned
        subtree — the router's dedup lever for hot prefixes resident on
        more replicas than the fleet needs.  A hot system prompt's
        chain always has tail extensions committed under it, so the
        subtree must go leaf-first or nothing is ever droppable; a
        pinned page (rc>0: a live sequence maps it) survives and keeps
        its ancestors matchable.  Returns the number of pages reclaimed
        to the free list."""
        prompt = np.asarray(prompt, np.int32).reshape(-1)
        chain = self._walk(prompt, len(prompt) // self.page_size)
        if not chain:
            return 0
        dropped = 0

        def evict(node):
            del node.parent.children[node.key]
            del self._cached[node.page]
            self._free.append(node.page)
            self.prefix_evictions += 1

        def prune(node):
            nonlocal dropped
            for child in list(node.children.values()):
                prune(child)
            if node.children or self._rc[node.page] != 0:
                return
            evict(node)
            dropped += 1

        prune(chain[-1])
        # ancestors can only go once the deep end is gone (matching
        # always walks from the root, so an interior hole would leak
        # unreachable-but-resident pages)
        for node in reversed(chain[:-1]):
            if node.children or self._rc[node.page] != 0:
                break
            evict(node)
            dropped += 1
        return dropped

    def _evict_lru_leaf(self):
        """Reclaim the least-recently-used cached LEAF page no sequence
        maps (rc==0). Leaf-first keeps every remaining chain matchable
        from the root. Returns False when nothing is evictable."""
        victim = None
        for page, node in self._cached.items():
            if self._rc[page] == 0 and not node.children:
                if victim is None or node.last_used < victim.last_used:
                    victim = node
        if victim is None:
            return False
        if self._tier is not None:
            # spill BEFORE unlinking: the tier walks the victim's
            # ancestors to rebuild the token chain, and the page bytes
            # must be captured before the page re-enters the free list.
            # Best-effort by contract — the eviction proceeds whatever
            # happens in there.
            self._tier.spill(self, victim)
        del victim.parent.children[victim.key]
        del self._cached[victim.page]
        self._free.append(victim.page)
        self.prefix_evictions += 1
        return True
