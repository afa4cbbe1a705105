"""Fleet metrics report: aggregate per-trainer telemetry into one view,
with CI gates.

Inputs (any combination; all three default on):

* **flight/metrics dump files** (``--flight-dir``, default
  ``$PT_FLIGHT_DIR``): the ``flight_*.jsonl`` postmortems and
  ``metrics_*.jsonl`` snapshot files written by
  ``paddle_tpu/observability`` — one directory per job, many pids.
* **live scrapes** (``--scrape host:port,host:port``): the
  ``{"t": "metrics_json"}`` endpoint every trainer serves when
  ``PT_METRICS_PORT`` is set (and every pserver serves natively).
* **the local registry** — so running the tool inside a trainer
  process reports without any files.

Fleet merge: counters sum across sources, gauges keep per-source
samples (labeled by origin), histograms sum bucket counts / sums — so
``pt_step_total_seconds`` becomes the cluster-wide step latency
distribution.

CI gate (exit 1 on failure): ``--check-families`` — every
REQUIRED_FAMILIES name must be present; a refactor silently dropping
``pt_step_dispatch_seconds`` fails here, not in a dashboard three
weeks later.

Usage::

    python tools/metrics_report.py --flight-dir /tmp/flight --json
    python tools/metrics_report.py --scrape 127.0.0.1:9460
    python tools/metrics_report.py --check-families
"""
from __future__ import annotations

import argparse
import json
import os
import sys
from typing import Dict, List, Optional

_REPO = os.path.dirname(os.path.dirname(os.path.abspath(__file__)))
if _REPO not in sys.path:
    sys.path.insert(0, _REPO)
os.environ.setdefault("JAX_PLATFORMS", "cpu")

# the metric catalog the framework promises (docs/OBSERVABILITY.md);
# removal of any of these is a CI failure under --check-families
REQUIRED_FAMILIES = (
    "pt_step_feed_seconds", "pt_step_trace_seconds",
    "pt_step_dispatch_seconds", "pt_step_fetch_seconds",
    "pt_step_total_seconds",
    "pt_ckpt_save_seconds", "pt_ckpt_restore_seconds",
    "pt_heartbeats_sent_total", "pt_heartbeats_failed_total",
    "pt_trainers_evicted_total", "pt_flight_dumps_total",
    # distributed tracing (docs/TRACING.md)
    "pt_spans_recorded_total", "pt_span_dumps_total",
    "pt_step_skew_seconds", "pt_step_slowest_worker_seconds",
    "pt_deep_profiles_total",
    # feedback-directed autotuner (FLAGS_autotune, docs/TUNING.md)
    "pt_tuning_searches_total", "pt_tuning_trials_total",
    "pt_tuning_cache_hits_total", "pt_tuning_best_ms",
    "pt_tuning_trial_seconds",
    # HBM memory observatory (docs/MEMORY.md)
    "pt_hbm_owner_bytes", "pt_hbm_live_bytes",
    "pt_island_hbm_peak_bytes", "pt_hbm_leak_suspect_bytes",
    "pt_memdumps_total", "pt_oom_postmortems_total",
    # integrity sentinel + exactly-once resume (docs/RESILIENCE.md)
    "pt_integrity_checks_total", "pt_integrity_mismatch_total",
    "pt_integrity_rollbacks_total", "pt_integrity_drift",
    "pt_resume_restores_total", "pt_resume_replayed_batches_total",
    "pt_resume_cursor_stale_total", "pt_resume_resumed_step",
    # elastic topology resume (docs/RESILIENCE.md "Elastic topology")
    "pt_elastic_resumes_total", "pt_elastic_reshard_seconds",
    "pt_elastic_world_size",
    # multi-axis placement search (docs/PARALLELISM.md)
    "pt_placement_searches_total", "pt_placement_cache_hits_total",
    "pt_placement_search_seconds", "pt_placement_predicted_ms",
    "pt_placement_collective_bytes",
    # pipeline engines: pp axis + 1F1B schedule (docs/PARALLELISM.md)
    "pt_pipeline_steps_total", "pt_pipeline_stages",
    "pt_pipeline_bubble_frac",
    "pt_pipeline_activation_exchange_bytes_total",
    "pt_pipeline_stage_hbm_peak_bytes",
    # cross-path lowering conformance (docs/STATIC_ANALYSIS.md)
    "pt_conformance_checks_total", "pt_conformance_divergences_total",
    "pt_conformance_verify_seconds",
    # multi-step dispatch (PT_MULTI_STEP, docs/ASYNC_DISPATCH.md)
    "pt_multistep_k", "pt_multistep_dispatches_total",
    "pt_multistep_substeps_total", "pt_multistep_early_exits_total",
    # serving engine (inference/serving/, docs/SERVING.md)
    "pt_serve_queue_depth", "pt_serve_batch_occupancy",
    "pt_serve_request_seconds", "pt_serve_tokens_total",
    "pt_serve_tokens_per_second", "pt_serve_kv_pages_in_use",
    "pt_serve_kv_evictions_total", "pt_serve_rejections_total",
    "pt_serve_requests_total", "pt_serve_step_errors_total",
)


# ---------------------------------------------------------------------------
# fleet merge over metrics_snapshot()-shaped dicts
# ---------------------------------------------------------------------------

def merge_snapshots(sources: List[tuple]) -> Dict[str, dict]:
    """``sources``: [(origin_label, families_dict)] or
    [(origin_label, families_dict, worker_id)] where families_dict is
    ``observability.export.metrics_snapshot()`` output. Returns one
    merged families dict of the same shape. Gauge samples keep one
    series per source, labeled with ``origin`` (which file/endpoint)
    and ``worker`` (which fleet member, docs/TRACING.md) — so
    ``pt_step_skew_seconds`` etc. stay attributable after the merge."""
    out: Dict[str, dict] = {}
    for src in sources:
        origin, families = src[0], src[1]
        worker = src[2] if len(src) > 2 and src[2] else str(origin)
        for name, fam in (families or {}).items():
            ftype = fam.get("type")
            dst = out.setdefault(name, {"type": ftype, "samples": []})
            for s in fam.get("samples", []):
                if ftype == "histogram":
                    _merge_hist_sample(dst, s)
                elif ftype == "counter":
                    _merge_counter_sample(dst, s)
                else:  # gauge: point-in-time, keep per-source series
                    labels = dict(s.get("labels") or {})
                    labels["origin"] = str(origin)
                    labels.setdefault("worker", str(worker))
                    dst["samples"].append(
                        {"labels": labels,
                         "value": float(s.get("value", 0.0))})
    return out


def _labels_key(s):
    return tuple(sorted((s.get("labels") or {}).items()))


def _merge_counter_sample(dst: dict, s: dict) -> None:
    key = _labels_key(s)
    for existing in dst["samples"]:
        if _labels_key(existing) == key:
            existing["value"] += float(s.get("value", 0.0))
            return
    dst["samples"].append({"labels": dict(s.get("labels") or {}),
                           "value": float(s.get("value", 0.0))})


def _merge_hist_sample(dst: dict, s: dict) -> None:
    key = _labels_key(s)
    for existing in dst["samples"]:
        if _labels_key(existing) == key:
            existing["sum"] += float(s.get("sum", 0.0))
            existing["count"] += int(s.get("count", 0))
            cum = {str(le): c for le, c in existing.get("buckets", [])}
            for le, c in s.get("buckets", []):
                cum[str(le)] = cum.get(str(le), 0) + int(c)
            existing["buckets"] = [
                [le if le == "+Inf" else float(le), c]
                for le, c in sorted(
                    cum.items(),
                    key=lambda kv: (kv[0] == "+Inf",
                                    float(kv[0]) if kv[0] != "+Inf"
                                    else 0.0))]
            return
    dst["samples"].append({
        "labels": dict(s.get("labels") or {}),
        "sum": float(s.get("sum", 0.0)),
        "count": int(s.get("count", 0)),
        "buckets": [[le, int(c)] for le, c in s.get("buckets", [])]})


# ---------------------------------------------------------------------------
# sources
# ---------------------------------------------------------------------------

def collect_dump_sources(flight_dir: Optional[str]):
    """(snapshot sources, flight summaries) from one dump directory."""
    from paddle_tpu.observability import recorder, export
    d = flight_dir or recorder.default_dir()
    sources, flights = [], []
    if not os.path.isdir(d):
        return sources, flights
    flights = recorder.summarize_dumps(d)
    for name in sorted(os.listdir(d)):
        if not (name.startswith("metrics_") and name.endswith(".jsonl")):
            continue
        try:
            snaps = export.read_metrics_dump(os.path.join(d, name))
        except (OSError, ValueError):
            continue
        if snaps:   # last snapshot per process wins (cumulative)
            snap = snaps[-1]
            tid = snap.get("trainer_id")
            worker = (snap.get("worker")
                      or (f"trainer{tid}" if tid not in (None, "")
                          else f"pid{snap.get('pid', '?')}"))
            sources.append((name, snap.get("families", {}), worker))
    return sources, flights


def collect_scrape_sources(endpoints: List[str]):
    from paddle_tpu.observability import export
    sources, errors = [], {}
    for ep in endpoints:
        try:
            sources.append((ep, export.scrape(ep, as_json=True), ep))
        except Exception as exc:
            errors[ep] = f"{type(exc).__name__}: {exc}"
    return sources, errors


def local_registry_source():
    from paddle_tpu.observability import export, tracing
    return ("local", export.metrics_snapshot(), tracing.worker_id())


# ---------------------------------------------------------------------------
# gates
# ---------------------------------------------------------------------------

def missing_families(merged: Dict[str, dict]) -> List[str]:
    return [n for n in REQUIRED_FAMILIES if n not in merged]


# ---------------------------------------------------------------------------
# report
# ---------------------------------------------------------------------------

def fleet_report(flight_dir=None, endpoints=(), include_local=True,
                 last_n: int = 8) -> dict:
    sources, flights = collect_dump_sources(flight_dir)
    scraped, scrape_errors = collect_scrape_sources(list(endpoints))
    sources.extend(scraped)
    if include_local:
        sources.append(local_registry_source())
    merged = merge_snapshots(sources)
    step_hist = merged.get("pt_step_total_seconds", {})
    total_steps = sum(s.get("count", 0)
                      for s in step_hist.get("samples", []))
    return {
        "sources": [s[0] for s in sources],
        "workers": sorted({str(s[2]) for s in sources if len(s) > 2}),
        "scrape_errors": scrape_errors or None,
        "flight_dumps": flights,
        "total_steps_observed": total_steps,
        "families": merged,
    }


def main(argv=None) -> int:
    p = argparse.ArgumentParser(description=__doc__.splitlines()[0])
    p.add_argument("--flight-dir", default=None,
                   help="dump directory (default $PT_FLIGHT_DIR)")
    p.add_argument("--scrape", default="",
                   help="comma-separated host:port metrics endpoints")
    p.add_argument("--no-local", action="store_true",
                   help="exclude this process's own registry")
    p.add_argument("--check-families", action="store_true",
                   help="exit 1 if any required metric family is "
                        "missing from the merged view")
    p.add_argument("--last-n", type=int, default=8,
                   help="steps summarized per flight dump")
    p.add_argument("--json", action="store_true",
                   help="print the full machine-readable report")
    args = p.parse_args(argv)

    endpoints = [e.strip() for e in args.scrape.split(",") if e.strip()]
    rep = fleet_report(flight_dir=args.flight_dir, endpoints=endpoints,
                       include_local=not args.no_local,
                       last_n=args.last_n)
    failures = []

    if args.check_families:
        missing = missing_families(rep["families"])
        rep["missing_families"] = missing
        if missing:
            failures.append(f"required metric families missing: "
                            f"{missing}")

    if args.json:
        print(json.dumps(rep, indent=2, default=str))
    else:
        print(f"sources: {', '.join(rep['sources']) or '(none)'}")
        print(f"steps observed (fleet): {rep['total_steps_observed']}")
        print(f"metric families: {len(rep['families'])}")
        for fl in rep["flight_dumps"]:
            if "error" in fl:
                print(f"  flight dump error: {fl['error']}")
                continue
            print(f"  flight {fl['file']}: reason={fl['reason']} "
                  f"steps {fl['first_step']}..{fl['last_step']} "
                  f"mean_phase_ms={fl['mean_phase_ms']}")
    if failures:
        for f in failures:
            print("GATE FAILURE: " + f, file=sys.stderr)
        return 1
    return 0


if __name__ == "__main__":
    sys.exit(main())
