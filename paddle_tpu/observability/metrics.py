"""Low-overhead metrics registry: counters, gauges, histograms.

The reference Fluid's observability is its platform/profiler +
DeviceTracer; beyond traces it has no *metrics* surface — every PR of
this rebuild grew a one-off reporting dict instead (``Engine.counters``,
``retry_stats()``, ``FaultPlan.counts``). This module is the single
registry those feed into, with a Prometheus-style data model:

* :class:`Counter` — monotonically increasing total;
* :class:`Gauge` — point-in-time value (optionally labeled);
* :class:`Histogram` — exponential-bucket latency distribution
  (``_bucket{le=...}`` / ``_sum`` / ``_count`` exposition);
* *collectors* — callables sampled at scrape time, so existing stat
  dicts (``Engine.counters``, ``resilience.retry_stats()``, circuit
  breaker states) are exported with ZERO hot-path cost: nothing is
  mirrored per increment, the registry reads them when asked.

Hot-path contract (docs/OBSERVABILITY.md): the engine step loop checks
exactly one boolean — ``_HOT[0]`` — before doing ANY telemetry work
(phase timing, histogram observes, flight-recorder appends). ``_HOT``
is true while telemetry is enabled (``FLAGS_telemetry`` /
:func:`enable_telemetry`) or while the flight recorder is armed (fault
plan installed, step watchdog configured). With everything off, a step
pays one list index read.
"""
from __future__ import annotations

import bisect
import math
import os
import threading
import weakref
from typing import Callable, Dict, Iterable, List, Optional, Tuple

__all__ = ["Counter", "Gauge", "Histogram", "Family", "MetricsRegistry",
           "default_registry", "telemetry_active", "enable_telemetry",
           "register_engine", "EngineCounters", "counter", "gauge",
           "histogram"]

# THE hot-path gate (see module docstring). Mutated only through
# _recompute_hot(); read directly (``_HOT[0]``) by the engine.
_HOT = [False]
_TELEMETRY = [False]


def telemetry_active() -> bool:
    """True while metric observation is on (histogram observes, step
    phase attribution). Cheap: one list read."""
    return _TELEMETRY[0]


def _recompute_hot() -> None:
    rec = False
    try:
        from . import recorder
        rec = recorder.recording_active()
    except Exception:
        pass
    if not rec and not _TELEMETRY[0]:
        # memory.enable(True) arms the per-step HBM census on its own
        # (bench --compare-memory, tests) without full telemetry
        try:
            from . import memory
            rec = memory.census_enabled()
        except Exception:
            pass
    _HOT[0] = _TELEMETRY[0] or rec


def enable_telemetry(on: bool = True) -> None:
    """Turn per-step metric observation on/off. ``FLAGS_telemetry``
    (env or ``set_flags``) routes here."""
    _TELEMETRY[0] = bool(on)
    _recompute_hot()


# ---------------------------------------------------------------------------
# data model
# ---------------------------------------------------------------------------

class Family:
    """One exposition family: every sample shares name/type/help."""

    __slots__ = ("name", "type", "help", "samples")

    def __init__(self, name: str, mtype: str, help: str,
                 samples: Optional[List[Tuple[Dict[str, str], float]]]
                 = None):
        self.name = name
        self.type = mtype          # "counter" | "gauge" | "histogram"
        self.help = help
        # histogram families carry (labels, HistogramState) samples
        self.samples = samples if samples is not None else []


class Counter:
    """Monotonic total. ``inc()`` is a plain float add under the GIL —
    no lock; exact enough for telemetry (the same tradeoff
    Engine.counters already makes).

    ``inc(v, **labels)`` additionally tracks one labeled series per
    label tuple (e.g. ``pt_anomalies_total{class=...,policy=...}``);
    the unlabeled sample stays first in the exposition and always
    carries the grand total, so pre-label readers keep working."""

    __slots__ = ("name", "help", "value", "_series")

    def __init__(self, name: str, help: str = ""):
        self.name = name
        self.help = help
        self.value = 0.0
        self._series: Dict[Tuple[Tuple[str, str], ...], float] = {}

    def inc(self, v: float = 1.0, **labels) -> None:
        self.value += v
        if labels:
            k = tuple(sorted(labels.items()))
            self._series[k] = self._series.get(k, 0.0) + v

    def get(self, **labels) -> float:
        if not labels:
            return self.value
        return self._series.get(tuple(sorted(labels.items())), 0.0)

    def collect(self) -> Family:
        samples = [({}, self.value)]
        samples.extend((dict(k), v)
                       for k, v in sorted(self._series.items()))
        return Family(self.name, "counter", self.help, samples)


class Gauge:
    """Point-in-time value, optionally labeled (one series per label
    tuple)."""

    __slots__ = ("name", "help", "_series")

    def __init__(self, name: str, help: str = ""):
        self.name = name
        self.help = help
        self._series: Dict[Tuple[Tuple[str, str], ...], float] = {}

    def set(self, v: float, **labels) -> None:
        self._series[tuple(sorted(labels.items()))] = float(v)

    def inc(self, v: float = 1.0, **labels) -> None:
        k = tuple(sorted(labels.items()))
        self._series[k] = self._series.get(k, 0.0) + v

    def get(self, **labels) -> float:
        return self._series.get(tuple(sorted(labels.items())), 0.0)

    def collect(self) -> Family:
        return Family(self.name, "gauge", self.help,
                      [(dict(k), v) for k, v in self._series.items()])


def exponential_buckets(start: float, factor: float,
                        count: int) -> List[float]:
    """``count`` upper bounds: start, start*factor, ... (no +Inf — the
    histogram adds the overflow bucket itself)."""
    if start <= 0 or factor <= 1 or count < 1:
        raise ValueError("need start > 0, factor > 1, count >= 1")
    return [start * factor ** i for i in range(count)]


# default latency buckets: 0.5ms .. ~16s, factor 2 — wide enough for a
# CPU-backed test step and a real TPU step on one scale
DEFAULT_BUCKETS = exponential_buckets(0.0005, 2.0, 16)


class Histogram:
    """Cumulative-bucket histogram over exponential bounds.

    ``observe(v)`` does one ``bisect`` + two adds — cheap enough to sit
    behind the telemetry gate on the step hot path. Bucket counts are
    stored per-bucket (non-cumulative) and accumulated at collect time.
    """

    __slots__ = ("name", "help", "bounds", "counts", "sum", "count")

    def __init__(self, name: str, help: str = "",
                 buckets: Optional[Iterable[float]] = None):
        self.name = name
        self.help = help
        self.bounds = sorted(float(b) for b in
                             (buckets if buckets is not None
                              else DEFAULT_BUCKETS))
        self.counts = [0] * (len(self.bounds) + 1)  # +1 overflow
        self.sum = 0.0
        self.count = 0

    def observe(self, v: float) -> None:
        self.counts[bisect.bisect_left(self.bounds, v)] += 1
        self.sum += v
        self.count += 1

    def cumulative(self) -> List[Tuple[float, int]]:
        """[(upper_bound, cumulative_count)] including (+inf, total)."""
        out, acc = [], 0
        for b, c in zip(self.bounds, self.counts):
            acc += c
            out.append((b, acc))
        out.append((math.inf, acc + self.counts[-1]))
        return out

    def reset(self) -> None:
        self.counts = [0] * (len(self.bounds) + 1)
        self.sum = 0.0
        self.count = 0

    def collect(self) -> Family:
        return Family(self.name, "histogram", self.help, [({}, self)])


# ---------------------------------------------------------------------------
# registry
# ---------------------------------------------------------------------------

class MetricsRegistry:
    """Name -> metric, plus scrape-time collectors."""

    def __init__(self):
        self._lock = threading.Lock()
        self._metrics: Dict[str, object] = {}
        self._collectors: List[Callable[[], Iterable[Family]]] = []

    def register(self, metric):
        with self._lock:
            prev = self._metrics.get(metric.name)
            if prev is not None:
                return prev
            self._metrics[metric.name] = metric
            return metric

    def counter(self, name: str, help: str = "") -> Counter:
        return self.register(Counter(name, help))

    def gauge(self, name: str, help: str = "") -> Gauge:
        return self.register(Gauge(name, help))

    def histogram(self, name: str, help: str = "",
                  buckets=None) -> Histogram:
        return self.register(Histogram(name, help, buckets))

    def register_collector(
            self, fn: Callable[[], Iterable[Family]]) -> None:
        with self._lock:
            self._collectors.append(fn)

    def get(self, name: str):
        return self._metrics.get(name)

    def collect(self) -> List[Family]:
        with self._lock:
            metrics = list(self._metrics.values())
            collectors = list(self._collectors)
        fams = [m.collect() for m in metrics]
        for fn in collectors:
            try:
                fams.extend(fn())
            except Exception:
                # a broken collector must never take down a scrape
                continue
        return fams


_DEFAULT: Optional[MetricsRegistry] = None


def default_registry() -> MetricsRegistry:
    global _DEFAULT
    if _DEFAULT is None:
        _DEFAULT = MetricsRegistry()
        _install_standard_families(_DEFAULT)
    return _DEFAULT


def counter(name: str, help: str = "") -> Counter:
    return default_registry().counter(name, help)


def gauge(name: str, help: str = "") -> Gauge:
    return default_registry().gauge(name, help)


def histogram(name: str, help: str = "", buckets=None) -> Histogram:
    return default_registry().histogram(name, help, buckets)


# ---------------------------------------------------------------------------
# Engine.counters compatibility view
# ---------------------------------------------------------------------------

class EngineCounters(dict):
    """``Engine.counters``: still a dict (every existing reader —
    tests, tools, CheckpointManager — keeps working) with a stable
    snapshot/reset API, exported into the registry by the engine
    collector at scrape time (zero per-increment mirroring cost)."""

    def snapshot(self) -> Dict[str, float]:
        """Stable point-in-time copy (the dict itself keeps mutating
        under async dispatch)."""
        return dict(self)

    def reset(self, keys=None) -> Dict[str, float]:
        """Zero the named counters (all by default), returning the
        pre-reset snapshot. Types are preserved (float gauges stay
        float)."""
        snap = dict(self)
        for k in (list(self) if keys is None else keys):
            v = self.get(k)
            if v is not None:
                self[k] = type(v)(0)
        return snap


# engine counters that are point-in-time gauges, not monotonic totals
_ENGINE_GAUGE_KEYS = frozenset({
    "ckpt_inflight", "grad_collectives_per_step", "comm_overlap_frac",
    "islands_concurrent", "pipeline_fill_frac"})

_ENGINES: "weakref.WeakSet" = weakref.WeakSet()


def register_engine(engine) -> None:
    """Weakly track an Engine so its counters dict is exported by the
    ``pt_engine_*`` scrape-time collector. Also auto-starts the
    standalone metrics endpoint when ``PT_METRICS_PORT`` is set (so
    every launched trainer is scrapeable without code changes)."""
    default_registry()
    _ENGINES.add(engine)
    if os.environ.get("PT_METRICS_PORT"):
        try:
            from .export import maybe_start_from_env
            maybe_start_from_env()
        except Exception:
            pass


def _engine_families() -> List[Family]:
    sums: Dict[str, float] = {}
    gauges: Dict[str, float] = {}
    for eng in list(_ENGINES):
        for k, v in dict(getattr(eng, "counters", {})).items():
            if k in _ENGINE_GAUGE_KEYS:
                gauges[k] = max(gauges.get(k, 0.0), float(v))
            else:
                sums[k] = sums.get(k, 0.0) + float(v)
    fams = [Family(f"pt_engine_{k}_total", "counter",
                   f"Engine.counters[{k!r}] summed over live engines",
                   [({}, v)])
            for k, v in sorted(sums.items())]
    fams.extend(Family(f"pt_engine_{k}", "gauge",
                       f"Engine.counters[{k!r}] (max over live engines)",
                       [({}, v)])
                for k, v in sorted(gauges.items()))
    return fams


def _rpc_families() -> List[Family]:
    """RPC retry/deadline/breaker accounting, sampled from the
    resilience layer's own stores at scrape time."""
    fams: List[Family] = []
    try:
        from ..distributed import resilience
    except Exception:
        return fams
    for k, v in sorted(resilience.retry_stats().items()):
        fams.append(Family(f"pt_rpc_{k}_total", "counter",
                           f"resilience retry_stats[{k!r}]",
                           [({}, float(v))]))
    states = {"closed": 0.0, "half_open": 1.0, "open": 2.0}
    snap = resilience.endpoint_health.snapshot()
    state_samples = [({"endpoint": ep},
                      states.get(info["state"], -1.0))
                     for ep, info in sorted(snap.items())]
    fail_samples = [({"endpoint": ep},
                     float(info["consecutive_failures"]))
                    for ep, info in sorted(snap.items())]
    fams.append(Family("pt_rpc_breaker_state", "gauge",
                       "circuit breaker state per endpoint "
                       "(0=closed 1=half_open 2=open)", state_samples))
    fams.append(Family("pt_rpc_breaker_consecutive_failures", "gauge",
                       "consecutive failures per endpoint",
                       fail_samples))
    return fams


def _install_standard_families(reg: MetricsRegistry) -> None:
    """Pre-register every metric family this framework emits, so the
    exposition endpoint advertises the full catalog even before the
    first sample (docs/OBSERVABILITY.md)."""
    # engine step phase latencies (seconds)
    reg.histogram("pt_step_feed_seconds",
                  "host feed conversion + H2D per step")
    reg.histogram("pt_step_trace_seconds",
                  "trace_step build time (only steps that traced)")
    reg.histogram("pt_step_dispatch_seconds",
                  "XLA executable dispatch call per step (includes "
                  "compile on the first dispatch of a trace)")
    reg.histogram("pt_step_fetch_seconds",
                  "synchronous fetch D2H per step (0-cost deferred "
                  "under FLAGS_async_dispatch)")
    reg.histogram("pt_step_total_seconds", "whole Engine.run() call")
    reg.histogram("pt_step_lane_idle_seconds",
                  "per-step dispatch-lane idle time under the op "
                  "scheduler: sum over same-phase concurrent islands "
                  "of (phase window - island dispatch span); 0 when "
                  "FLAGS_op_scheduler is off (docs/SCHEDULING.md)")
    # checkpoint subsystem
    reg.histogram("pt_ckpt_save_seconds",
                  "background shard write + commit per save")
    reg.histogram("pt_ckpt_restore_seconds",
                  "checkpoint read + scope restore")
    # distributed liveness
    reg.counter("pt_heartbeats_sent_total",
                "trainer heartbeats delivered")
    reg.counter("pt_heartbeats_failed_total",
                "trainer heartbeats that failed to send")
    reg.counter("pt_trainers_evicted_total",
                "trainers evicted by the pserver liveness registry")
    # flight recorder
    reg.counter("pt_flight_dumps_total",
                "flight-recorder postmortem dumps written")
    # stability guard (FLAGS_stability_guard; docs/STABILITY.md)
    reg.counter("pt_anomalies_total",
                "stability-guard anomaly verdicts by class and "
                "applied policy (docs/STABILITY.md)")
    reg.counter("pt_rollbacks_total",
                "ghost-snapshot rollbacks performed by the stability "
                "guard")
    reg.histogram("pt_guard_overhead_seconds",
                  "host-side stability-guard controller time per step "
                  "(verdict read + policy + ghost capture)")
    # integrity sentinel (FLAGS_integrity_sentinel; docs/RESILIENCE.md)
    reg.counter("pt_integrity_checks_total",
                "sentinel verification windows completed "
                "(docs/RESILIENCE.md)")
    reg.counter("pt_integrity_mismatch_total",
                "parameter-integrity mismatches by worker and bucket "
                "(docs/RESILIENCE.md)")
    reg.counter("pt_integrity_rollbacks_total",
                "integrity incidents recovered by ghost-ring rollback "
                "(docs/RESILIENCE.md)")
    reg.gauge("pt_integrity_drift",
              "max |fingerprint sum drift| of the last integrity "
              "incident")
    # exactly-once elastic resume (checkpoint/train_state.py;
    # docs/RESILIENCE.md)
    reg.counter("pt_resume_restores_total",
                "TrainState restores applied by CheckpointManager")
    reg.counter("pt_resume_replayed_batches_total",
                "batches skipped-to on reader-cursor resume (the "
                "replay fast-forward, not duplicate training)")
    reg.counter("pt_resume_cursor_stale_total",
                "registered readers whose cursor could not be "
                "captured or applied on save/restore")
    reg.gauge("pt_resume_resumed_step",
              "global step the last TrainState restore resumed at")
    # elastic topology resume (distributed/elastic.py;
    # docs/RESILIENCE.md "Elastic topology")
    reg.counter("pt_elastic_resumes_total",
                "checkpoint restores taken through the elastic "
                "topology path (saved-vs-current mismatch -> replan + "
                "reshard + cursor redistribution)")
    reg.histogram("pt_elastic_reshard_seconds",
                  "wall time of elastic restores: placement re-search "
                  "+ global tensor reassembly + cursor redistribution")
    reg.gauge("pt_elastic_world_size",
              "device world size after the last elastic resume")
    # custom-kernel registry (FLAGS_use_custom_kernels; docs/KERNELS.md)
    reg.counter("pt_kernel_dispatch_total",
                "trace-time kernel-registry decisions, labeled "
                "{kernel, outcome} with outcome one of custom "
                "(kernel selected), lowered (eligibility/backend kept "
                "the lowered path), denied (flag or PT_KERNEL_DENY)")
    # distributed tracing (docs/TRACING.md)
    reg.counter("pt_spans_recorded_total",
                "trace spans recorded, labeled {kind} (step, phase, "
                "lane, rpc.client, rpc.server, fetch, ckpt)")
    reg.counter("pt_span_dumps_total",
                "span-ring postmortem dumps written")
    reg.gauge("pt_step_skew_seconds",
              "fleet step-duration skew: slowest minus fastest "
              "per-worker mean step time, from heartbeat-piggybacked "
              "summaries")
    reg.gauge("pt_step_slowest_worker_seconds",
              "mean step duration of the currently slowest worker, "
              "labeled {worker}")
    reg.counter("pt_deep_profiles_total",
                "deep-profile captures that emitted a merged timeline "
                "(PT_DEEP_PROFILE_EVERY / request_deep_profile)")
    # feedback-directed autotuner (FLAGS_autotune, paddle_tpu/tuning,
    # docs/TUNING.md)
    reg.counter("pt_tuning_searches_total",
                "knob searches run to completion (one per program that "
                "missed the tuning cache)")
    reg.counter("pt_tuning_trials_total",
                "objective evaluations performed by the search driver "
                "(each = restore scope, apply config, measure steps)")
    reg.counter("pt_tuning_cache_hits_total",
                "programs whose winning config was replayed from the "
                "persistent tuning cache (zero trials)")
    reg.gauge("pt_tuning_best_ms",
              "objective (median fetch-fenced step ms) of the applied "
              "winning config for the most recently tuned program")
    reg.histogram("pt_tuning_trial_seconds",
                  "wall time of one search trial, including the trace "
                  "+ compile a trace-affecting candidate pays")
    # SPMD placement search (analysis/placement.py, docs/PARALLELISM.md)
    reg.counter("pt_placement_searches_total",
                "placement searches run to completion (one per program "
                "that missed the placement plan cache)")
    reg.counter("pt_placement_cache_hits_total",
                "programs whose placement plan was replayed from the "
                "tuning cache (zero search trials)")
    reg.gauge("pt_placement_search_seconds",
              "wall time of the last placement search (candidate "
              "enumeration + static scoring)")
    reg.gauge("pt_placement_predicted_ms",
              "static cost-model predicted step ms of the chosen "
              "placement plan")
    reg.gauge("pt_placement_collective_bytes",
              "predicted per-device collective bytes per step of the "
              "chosen plan, labeled {axis} (data / fsdp / tp / pp)")
    # pipeline engines (parallel/pipeline.py, parallel/mpmd_pipeline.py;
    # docs/PARALLELISM.md)
    reg.counter("pt_pipeline_steps_total",
                "pipeline training steps, labeled {schedule} "
                "(gpipe-spmd / 1f1b / gpipe)")
    reg.gauge("pt_pipeline_stages",
              "pipeline stage count of the last pipelined step")
    reg.gauge("pt_pipeline_bubble_frac",
              "measured schedule bubble fraction of the last "
              "pipelined step (idle device-slots / total slots)")
    reg.counter("pt_pipeline_activation_exchange_bytes_total",
                "bytes handed across stage boundaries (activations "
                "forward + cotangents backward)")
    reg.gauge("pt_pipeline_stage_hbm_peak_bytes",
              "static per-stage HBM estimate from the synthesized "
              "cut plan, labeled {stage} (max over stages when "
              "unlabeled)")
    # HBM memory observatory (observability/memory.py, docs/MEMORY.md)
    reg.gauge("pt_hbm_owner_bytes",
              "owner-attributed live HBM bytes from the buffer census, "
              "labeled {owner} (scope, ghost_ring, ckpt_snapshot, "
              "prefetch, pending_step, pending_fetch, engine_updated, "
              "orphan = live_arrays bytes nobody claimed)")
    reg.gauge("pt_hbm_live_bytes",
              "total non-deleted jax.live_arrays() bytes at the last "
              "census (the census denominator)")
    reg.gauge("pt_island_hbm_peak_bytes",
              "per-scheduler-island compiled HBM peak, labeled "
              "{island}: memory_analysis temp + argument bytes of the "
              "island's own executable")
    reg.gauge("pt_hbm_leak_suspect_bytes",
              "leak-sentinel verdict, labeled {owner}: window growth "
              "in bytes for owners whose census bytes rose "
              "monotonically across the sliding window, 0 otherwise")
    reg.counter("pt_memdumps_total",
                "memory postmortem dumps written (memdump_*.jsonl: "
                "oom, watermark, or explicit)")
    reg.counter("pt_oom_postmortems_total",
                "RESOURCE_EXHAUSTED exceptions that produced a memory "
                "postmortem (deduped: one per exception chain)")
    # multi-step dispatch (PT_MULTI_STEP, core/engine.py;
    # docs/ASYNC_DISPATCH.md "Multi-step dispatch")
    reg.gauge("pt_multistep_k",
              "substeps fused per dispatched executable "
              "(PT_MULTI_STEP): the scan trip count of the multi-step "
              "driver, 1 when slab mode is off")
    reg.counter("pt_multistep_dispatches_total",
                "multi-step slab dispatches (each amortizes one "
                "host dispatch over K training substeps)")
    reg.counter("pt_multistep_substeps_total",
                "training substeps executed inside multi-step slabs "
                "(= dispatches x K when no slab exited early)")
    reg.counter("pt_multistep_early_exits_total",
                "slabs cut short by a stability-guard verdict: the "
                "scan carry froze at the anomalous substep and the "
                "host replayed the tail through the K=1 path")
    # cross-path lowering conformance (analysis/conformance.py,
    # docs/STATIC_ANALYSIS.md)
    reg.counter("pt_conformance_checks_total",
                "verify_conformance runs (one per program × config "
                "verified across the four execution paths)")
    reg.counter("pt_conformance_divergences_total",
                "cross-path lowering divergences observed, labeled "
                "{declared}: yes = justified support-matrix cell "
                "(INFO), no = undeclared drift (ERROR)")
    reg.gauge("pt_conformance_verify_seconds",
              "wall time of the last conformance verification "
              "(trace extraction + pairwise diff; runs pre-compile, "
              "so it must stay cheap)")
    # serving engine (inference/serving/, docs/SERVING.md)
    reg.gauge("pt_serve_queue_depth",
              "requests waiting in the serving admission queue "
              "(admitted-but-unscheduled + queued)")
    reg.gauge("pt_serve_batch_occupancy",
              "live sequences in the last dispatched serving batch, "
              "labeled {phase} (prefill / decode); continuous "
              "batching holds this near the bucket size under load")
    reg.histogram("pt_serve_request_seconds",
                  "end-to-end request latency, submit to completion; "
                  "p50/p99 come from the bucket counts")
    reg.counter("pt_serve_tokens_total",
                "tokens generated by the serving engine, labeled "
                "{tenant}")
    reg.gauge("pt_serve_tokens_per_second",
              "decode throughput over the engine's last metrics "
              "window (generated tokens / wall seconds)")
    reg.gauge("pt_serve_kv_pages_in_use",
              "KV-cache pages currently allocated to live sequences "
              "(free-list size is total minus this)")
    reg.counter("pt_serve_kv_evictions_total",
                "sequences preempted (pages reclaimed, request "
                "re-queued for recompute) under KV memory pressure")
    reg.counter("pt_serve_rejections_total",
                "requests rejected at admission, labeled {reason} "
                "(quota / queue_full / too_long)")
    reg.counter("pt_serve_requests_total",
                "serving requests retired, labeled {status} "
                "(ok / deadline_expired / quota_exceeded / failed)")
    reg.counter("pt_serve_step_errors_total",
                "unexpected ServingEngine.step() exceptions contained "
                "by serve_loop (should stay 0; nonzero means a "
                "scheduler invariant broke)")
    reg.register_collector(_engine_families)
    reg.register_collector(_rpc_families)


# honor FLAGS_telemetry set via environment before this import
try:
    from ..core.flags import FLAGS as _FLAGS
    if getattr(_FLAGS, "telemetry", False):
        enable_telemetry(True)
except Exception:
    pass
