"""paddle_tpu.observability — unified telemetry subsystem.

Three layers (docs/OBSERVABILITY.md):

* :mod:`.metrics` — low-overhead registry (counters, gauges,
  exponential-bucket histograms, scrape-time collectors) that
  supersedes the ad-hoc per-PR stat dicts;
* :mod:`.recorder` — step flight recorder: fixed ring of per-step span
  records, dumped automatically on watchdog trip / injected fault /
  sticky async error / SIGTERM;
* :mod:`.export` — Prometheus-style exposition over the hardened RPC
  framing, JSONL dumps, chrome-trace merge;
* :mod:`.tracing` — correlated cross-worker spans with deterministic
  per-step trace ids, RPC context propagation, and fleet skew
  detection (docs/TRACING.md);
* :mod:`.attribution` — HLO cost/memory + measured device-time
  attribution per op category and scheduler island, the measured-MFU
  gauge, and the deep-profile merged-timeline trigger;
* :mod:`.memory` — HBM memory observatory: owner-attributed
  live-buffer census reconciled against ``jax.live_arrays()``,
  OOM/pressure postmortem dumps, and the leak sentinel
  (docs/MEMORY.md);
* :mod:`.moe` — readers of the `moe_expert_load` and `moe_rows_worked`
  counters a mixture-of-experts step keeps (docs/TRACING.md);
* :mod:`.sparse_attention` — reader of the `sparse_attn_kept` counter a
  learned-sparse-attention step overwrites (docs/TRACING.md);
* :mod:`.mamba` — reader of the `mamba_ssd_tokens` counter a step with
  Mamba-2 mixers overwrites (docs/TRACING.md);
* :mod:`.short_conv` — reader of the `short_conv_tokens` counter a step
  with gated short convolution layers overwrites (docs/TRACING.md);
* :mod:`.window_attention` — reader of the `window_attn_pairs` counter a
  step with sliding-window attention layers overwrites (docs/TRACING.md).

Hot-path contract: one boolean (``metrics._HOT[0]``) gates all
per-step telemetry work. The step's own profiler spans and clock stamps
(``profiler.StepClock``) are not behind it: they cost microseconds and
feed a profiler session, the slow-step detector and, while ``_HOT``,
this layer's record.
"""
from . import metrics, recorder, export, tracing, attribution, \
    memory, moe, sparse_attention, mamba, short_conv, \
    window_attention  # noqa: F401
from .metrics import (  # noqa: F401
    Counter, Gauge, Histogram, MetricsRegistry, EngineCounters,
    default_registry, counter, gauge, histogram,
    enable_telemetry, telemetry_active, register_engine)
from .recorder import (  # noqa: F401
    FlightRecorder, flight_recorder, record_step, dump,
    recording_active, find_dumps, read_dump, summarize_dumps)
from .export import (  # noqa: F401
    render_exposition, metrics_snapshot, dump_metrics, MetricsServer,
    scrape, maybe_start_from_env, flight_to_chrome_trace)

__all__ = [
    "metrics", "recorder", "export", "tracing", "attribution",
    "memory",
    "Counter", "Gauge", "Histogram", "MetricsRegistry",
    "EngineCounters", "default_registry", "counter", "gauge",
    "histogram", "enable_telemetry", "telemetry_active",
    "register_engine",
    "FlightRecorder", "flight_recorder", "record_step", "dump",
    "recording_active", "find_dumps", "read_dump", "summarize_dumps",
    "render_exposition", "metrics_snapshot", "dump_metrics",
    "MetricsServer", "scrape", "maybe_start_from_env",
    "flight_to_chrome_trace",
]
