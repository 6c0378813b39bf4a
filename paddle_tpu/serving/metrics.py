"""Serving observability: counters, gauges and reservoir histograms,
exported as JSON for the bench harness (PERF.md convention: one JSON
artifact per measurement, banked the moment it lands) and as Prometheus
text exposition for the HTTP front-end's ``/metrics`` endpoint.

Host-side and allocation-light by design — metrics must never add a
device sync; the engine records values it already fetched.
"""
from __future__ import annotations

import bisect
import json
import re

import numpy as np

__all__ = ["Counter", "Gauge", "Histogram", "LabeledCounter",
           "ServingMetrics", "merge_prometheus"]

# Prometheus histogram bucket bounds for serving latencies (seconds).
# TTFT and TPOT land here; the cumulative _bucket{le=...} exposition is
# what lets a scraper compute real quantiles across replicas (summary
# quantiles are NOT aggregatable — the router's merged /metrics needs
# buckets).
LATENCY_BUCKETS = (0.001, 0.0025, 0.005, 0.01, 0.025, 0.05, 0.1,
                   0.25, 0.5, 1.0, 2.5, 5.0, 10.0, 30.0, 60.0)

# Count-shaped buckets for the queue-depth histogram (requests, not
# seconds): powers of two so a scraper can see where admission backs up
# across replicas (round 16 — the tracing/observability PR)
DEPTH_BUCKETS = (0.0, 1.0, 2.0, 4.0, 8.0, 16.0, 32.0, 64.0, 128.0)


class Counter:
    def __init__(self):
        self.value = 0

    def inc(self, n=1):
        self.value += n

    def export(self):
        return self.value


class Gauge:
    """A point-in-time value (queue depth, occupancy, batch size) —
    ``set()`` overwrites; the exposition shows the LAST value, unlike a
    Histogram which keeps the distribution."""

    def __init__(self):
        self.value = 0.0

    def set(self, v):
        self.value = float(v)

    def export(self):
        return self.value


class LabeledCounter:
    """A counter family with fixed label names — the router's
    ``routed_total{policy,replica}`` class of metric. Values are kept
    per label-value tuple; ``inc`` creates series on demand."""

    def __init__(self, *label_names):
        self.label_names = tuple(label_names)
        self._values: dict[tuple, int | float] = {}

    def inc(self, n=1, **labels):
        key = tuple(str(labels[k]) for k in self.label_names)
        self._values[key] = self._values.get(key, 0) + n

    def value(self, **labels):
        key = tuple(str(labels[k]) for k in self.label_names)
        return self._values.get(key, 0)

    @property
    def total(self):
        return sum(self._values.values())

    def export(self):
        return {",".join(k): v for k, v in sorted(self._values.items())}

    def prom_lines(self, full):
        out = []
        for key, v in sorted(self._values.items()):
            labels = ",".join(f'{n}="{x}"'
                              for n, x in zip(self.label_names, key))
            out.append(f"{full}{{{labels}}} {v}")
        return out


class Histogram:
    """Bounded reservoir of samples; percentiles computed at export.
    Keeps the LAST `cap` samples (serving metrics care about recent
    behavior; a trace replay fits entirely).

    With ``buckets=`` (ascending upper bounds, seconds for latencies)
    the Prometheus exposition switches from a summary to a REAL
    histogram: cumulative ``_bucket{le=...}`` lines per the 0.0.4 text
    format, aggregatable across replicas. Bucket counts run over ALL
    samples (like ``count``/``total``), not just the reservoir."""

    def __init__(self, cap=65536, buckets=None):
        self.cap = int(cap)
        self._samples: list[float] = []
        self.count = 0
        self.total = 0.0  # running sum over ALL samples (summary _sum)
        self.buckets = tuple(buckets) if buckets else None
        if self.buckets and list(self.buckets) != sorted(self.buckets):
            raise ValueError("histogram buckets must be ascending")
        # per-bucket (non-cumulative) counts; the +Inf bucket is `count`
        self.bucket_counts = ([0] * len(self.buckets)
                              if self.buckets else None)

    def record(self, v):
        v = float(v)
        self.count += 1
        self.total += v
        if self.buckets is not None:
            i = bisect.bisect_left(self.buckets, v)
            if i < len(self.bucket_counts):
                self.bucket_counts[i] += 1
        self._samples.append(v)
        if len(self._samples) > self.cap:
            del self._samples[: len(self._samples) - self.cap]

    def percentile(self, p):
        """Percentile over the reservoir; None (never a raise) while no
        sample has been recorded — scrapes happen before traffic."""
        if not self._samples:
            return None
        return float(np.percentile(np.asarray(self._samples), p))

    def export(self):
        if not self._samples:
            return {"count": self.count, "mean": None, "p50": None,
                    "p99": None, "max": None}
        a = np.asarray(self._samples)
        return {"count": self.count,
                "mean": float(a.mean()),
                "p50": float(np.percentile(a, 50)),
                "p99": float(np.percentile(a, 99)),
                "max": float(a.max())}


class ServingMetrics:
    """The engine's counter/gauge/histogram set (names are the export
    keys and, prefixed, the Prometheus metric family names)."""

    def __init__(self):
        # TTFT/TPOT carry REAL Prometheus buckets (the router-merged
        # /metrics must stay aggregatable; summary quantiles are not)
        self.ttft_s = Histogram(buckets=LATENCY_BUCKETS)
        self.inter_token_s = Histogram(buckets=LATENCY_BUCKETS)
        # engine step wall time (round 16): the flight recorder keeps
        # the recent per-step detail; this keeps the aggregatable
        # distribution on /metrics
        self.step_duration_s = Histogram(buckets=LATENCY_BUCKETS)
        # bucketed (round 16) so the router-merged /metrics can show
        # WHERE admission backs up, not just the last gauge value
        self.queue_depth = Histogram(buckets=DEPTH_BUCKETS)
        self.batch_size = Histogram()         # decode lanes, per step
        self.page_occupancy = Histogram()     # used/allocatable, per step
        self.prefill_chunks = Counter()
        self.decode_steps = Counter()
        self.tokens_generated = Counter()
        self.requests_finished = Counter()
        self.preemptions = Counter()
        self.deadline_evictions = Counter()
        self.cow_copies = Counter()
        # front-end lifecycle (round 9)
        self.cancellations = Counter()        # cancel() calls that landed
        self.rejections = Counter()           # load-shed admissions (429)
        self.faults_injected = Counter()      # injected step faults
        # chaos/robustness layer (round 17)
        self.held_expired = Counter()         # held pages released on
        #                                       deadline expiry
        # speculative decoding (round 12)
        self.spec_rounds = Counter()          # draft-propose/verify rounds
        self.spec_draft_tokens = Counter()    # tokens the draft proposed
        self.spec_accepted_tokens = Counter()  # proposals verified+emitted
        self.spec_fallbacks = Counter()       # lanes demoted to plain
        # tensor-parallel SPMD serving (round 23)
        self.tp_kernel_fallbacks = Counter()  # Pallas kernel requests
        #                                       demoted to the jnp path
        #                                       (no GSPMD rule for
        #                                       pallas_call)
        # disaggregated prefill/decode (round 14)
        self.prefills_held = Counter()        # requests held "prefilled"
        self.pages_exported = Counter()       # KV pages shipped out
        self.pages_imported = Counter()       # KV pages spliced in
        self.adoptions = Counter()            # migrated-in requests
        # fleet prefix cache (round 18): router-driven prefix ships
        self.prefix_pages_exported = Counter()  # cached pages donated
        self.prefix_pages_imported = Counter()  # cached pages received
        self.prefix_drops = Counter()         # dedup drop_prefix pages
        # decode hot path (round 10)
        self.fetch_bytes = Counter()          # host<-device bytes/steps
        # round 22 (PR 18, the token-packed step): dispatch accounting —
        # every device dispatch / host fetch the engine issues, and the
        # number of distinct compiled program classes behind them. The
        # step's contract is <= 2 classes and ONE dispatch + ONE
        # fetch per mixed prefill+decode step.
        self.step_dispatches = Counter()      # device dispatches issued
        self.step_fetches = Counter()         # host<-device fetches
        self.step_program_classes = Gauge()   # distinct compiled classes
        # over step_dispatches: the target steps whose batch held a
        # sampling row with a binding top-k or top-p (the sampler's
        # sort ran), counted on the host from the arrays it packs
        self.sampler_sort_steps = Counter()
        # what the padded page tables cost (PR 30), counted on the host
        # from the arrays it packs: page-table entries the step's
        # attention gathers (tables gathered x table width) against the
        # pages its live lanes hold (ceil(context_len / page_size)): of
        # one layer where every layer owns a full pool; where the layers
        # differ, summed over every layer that attends (a reader of
        # another layer's pool gathers too) against every pool's pages
        self.attn_pages_gathered = Counter()
        self.attn_pages_live = Counter()
        self.prefix_hit_pages = Counter()     # prompt pages served from
        self.prefix_miss_pages = Counter()    # the radix tree vs prefilled
        self.prefix_evictions = Counter()     # cached pages LRU-reclaimed
        # point-in-time gauges, refreshed per step and at /metrics scrape
        self.queue_depth_gauge = Gauge()
        self.page_occupancy_gauge = Gauge()
        self.running_gauge = Gauge()          # running decode batch size
        self.prefix_hit_rate = Gauge()        # hit/(hit+miss), cumulative
        self.cached_pages_gauge = Gauge()     # pages resident in the tree
        self.spec_acceptance_rate = Gauge()   # accepted/proposed, cumul.
        # quantized serving (round 15): honest per-page byte cost incl.
        # int8 scale rows — what the hbm_budget sizing divides by
        self.kv_page_bytes = Gauge()
        # hierarchical KV tiers (round 20): host/disk spill + restore
        self.tier_spill_pages = Counter()     # pages landed in the tier
        self.tier_spill_dropped = Counter()   # spills shed/failed
        self.tier_restore_pages = Counter()   # pages restored to device
        self.tier_restore_hits = Counter()    # restores that moved pages
        self.tier_restore_misses = Counter()  # probes the tier missed
        self.tier_corrupt_dropped = Counter()  # CRC-failed entries purged
        self.tier_spill_s = Histogram(buckets=LATENCY_BUCKETS)
        self.tier_restore_s = Histogram(buckets=LATENCY_BUCKETS)
        self.tier_restore_hit_rate = Gauge()  # hits/(hits+misses), cumul.
        self.host_pool_pages = Gauge()        # RAM-tier resident pages
        self.host_pool_bytes = Gauge()
        self.disk_pool_pages = Gauge()        # disk-tier resident pages
        # versioned live weight deployment (round 21): swap counts +
        # per-swap quiesce latency (lock-held window), and the version
        # each weight set is serving (what /healthz advertises — the
        # router's version-pin skew guard reads the same numbers)
        self.weight_swaps = Counter()         # set_weights that landed
        self.weight_swap_rejects = Counter()  # torn/mismatched payloads
        self.weight_swap_s = Histogram(buckets=LATENCY_BUCKETS)
        self.weight_version_target = Gauge()
        self.weight_version_draft = Gauge()
        self.distill_pairs = Counter()        # verify pairs logged
        # sparse-expert layers in the ragged step: counted on the device
        # over a step's real tokens, summed over the layers
        # (incubate/moe.py::routing_counts), fetched with its tokens
        self.moe_assignments = Counter()      # tokens x top-k x layers
        self.moe_experts_hit = Counter()      # distinct experts with a
        #                                       token, per layer-step
        self.moe_expert_load_max = Counter()  # the fullest expert's
        #                                       tokens, per layer-step
        self.moe_layer_steps = Counter()      # expert layers x steps
        # what one cached token costs across every layer (a latent pool:
        # (rank + rope) x itemsize x layers; K and V by head otherwise)
        self.cache_bytes_per_token = Gauge()
        # what a lane's FIXED part costs where the layers differ (lane
        # states and the window pools' pages a lane may hold): nought
        # where every layer owns a full pool
        self.state_bytes_per_lane = Gauge()
        # bytes of cache state (pools, scale rows, window pools, lane
        # states) the last step handed to its program for good: the
        # operands its dispatch left deleted. Nought: the step copies
        # every pool it writes
        self.pool_bytes_donated = Gauge()
        # state-space and window layers in the step, counted on the host
        # from what it packs (engine._count_mixed_step)
        self.ssm_layer_steps = Counter()      # state layers x steps
        self.ssm_lane_scans = Counter()       # ... x live lanes: a
        #                                       state read and written
        self.ssm_rows_scanned = Counter()     # ... x live packed rows
        self.ssm_state_resets = Counter()     # lanes started from a
        #                                       zero state (position 0)
        self.window_pages_held = Counter()    # window pages held, summed
        #                                       over live lanes, a window
        #                                       layer a step
        self.window_layer_steps = Counter()   # window layers x live
        #                                       lanes x steps

    # the order incubate/moe.py::routing_counts packs its int32 [4] in
    MOE_COUNTS = ("moe_assignments", "moe_experts_hit",
                  "moe_expert_load_max", "moe_layer_steps")

    def record_moe_counts(self, counts):
        for name, n in zip(self.MOE_COUNTS, counts):
            getattr(self, name).inc(int(n))

    def export(self):
        return {name: m.export() for name, m in vars(self).items()}

    def to_json(self, **extra):
        return json.dumps({**self.export(), **extra})

    def to_prometheus(self, prefix="paddle_tpu_serving"):
        """Prometheus text exposition (format 0.0.4): counters and
        gauges as single samples; bucketed histograms (TTFT/TPOT) as
        REAL histograms with cumulative ``_bucket{le=...}`` lines plus
        ``le="+Inf"``; bucket-less histograms as summaries with p50/p99
        quantiles. Empty summaries expose only _count/_sum (a quantile
        of no data is omitted, not NaN, so the text stays trivially
        parseable)."""
        lines = []
        for name, m in vars(self).items():
            full = f"{prefix}_{name}"
            if isinstance(m, Counter):
                lines += [f"# TYPE {full} counter", f"{full} {m.value}"]
            elif isinstance(m, LabeledCounter):
                lines.append(f"# TYPE {full} counter")
                lines += m.prom_lines(full)
            elif isinstance(m, Gauge):
                lines += [f"# TYPE {full} gauge", f"{full} {m.value}"]
            elif isinstance(m, Histogram) and m.buckets:
                lines.append(f"# TYPE {full} histogram")
                acc = 0
                for bound, c in zip(m.buckets, m.bucket_counts):
                    acc += c
                    lines.append(
                        f'{full}_bucket{{le="{bound:g}"}} {acc}')
                lines += [f'{full}_bucket{{le="+Inf"}} {m.count}',
                          f"{full}_count {m.count}",
                          f"{full}_sum {m.total}"]
            elif isinstance(m, Histogram):
                lines.append(f"# TYPE {full} summary")
                for q, p in ((0.5, 50), (0.99, 99)):
                    v = m.percentile(p)
                    if v is not None:
                        lines.append(f'{full}{{quantile="{q}"}} {v}')
                lines += [f"{full}_count {m.count}",
                          f"{full}_sum {m.total}"]
        return "\n".join(lines) + "\n"


# -- multi-replica merge (router /metrics) ----------------------------------

_SAMPLE_RE = re.compile(
    r"^([a-zA-Z_:][a-zA-Z0-9_:]*)(\{.*\})? (.*)$")


def _label_sample(line, key, value):
    """Inject ``key="value"`` into one exposition sample line."""
    m = _SAMPLE_RE.match(line)
    if m is None:  # pragma: no cover - we only feed our own output
        return line
    name, labels, val = m.groups()
    tag = f'{key}="{value}"'
    if labels:
        return f"{name}{{{tag},{labels[1:-1]}}} {val}"
    return f"{name}{{{tag}}} {val}"


def merge_prometheus(parts, label="replica"):
    """Merge several Prometheus expositions into one, tagging every
    sample with ``label="<value>"`` and grouping families (one # TYPE
    line per family, all its samples together — the 0.0.4 grouping
    rule). ``parts`` is an iterable of ``(label_value, text)``; a
    ``label_value`` of None passes the part through UNLABELLED (the
    router's own families carry their labels already). Texts must be
    TYPE-then-samples shaped, which is what
    :meth:`ServingMetrics.to_prometheus` emits."""
    families: dict[str, tuple[str, list]] = {}
    order = []
    for value, text in parts:
        fam = None
        for line in text.splitlines():
            if not line:
                continue
            if line.startswith("# TYPE "):
                _, _, name, kind = line.split()
                if name not in families:
                    families[name] = (kind, [])
                    order.append(name)
                fam = families[name]
                continue
            if line.startswith("#"):
                continue
            if fam is not None:
                fam[1].append(line if value is None
                              else _label_sample(line, label, value))
    lines = []
    for name in order:
        kind, samples = families[name]
        lines.append(f"# TYPE {name} {kind}")
        lines += samples
    return "\n".join(lines) + "\n"
