"""Runtime flag system (gflags parity).

The reference defines ~40 ``DEFINE_*`` gflags scattered across C++ modules
(SURVEY Appendix C) and surfaces them to Python via env vars read in
``python/paddle/fluid/__init__.py:124-221`` (``__bootstrap__`` →
``core.init_gflags``). The TPU build keeps the same contract — every flag
has a default here, ``FLAGS_<name>`` environment variables override it at
import time, and ``get_flags``/``set_flags`` read/write at runtime — but
the flag *set* is honest about what the XLA runtime subsumes:

* flags with live behavior in this framework are marked ``live=True``
  (e.g. ``check_nan_inf`` instruments every traced op);
* reference flags whose job XLA/PJRT performs automatically (allocator
  tuning, eager deletion, cudnn knobs …) are registered ``live=False`` so
  user programs that set them keep working, and ``flag_info()`` reports
  exactly which category a flag is in. Setting an *unknown* flag raises —
  silently accepting typos is how inert knobs are born.
"""
from __future__ import annotations

import os
import threading
from typing import Any, Dict

__all__ = ["get_flags", "set_flags", "flag_info", "Flag", "FLAGS"]


class Flag:
    __slots__ = ("name", "default", "type", "live", "help")

    def __init__(self, name: str, default, live: bool, help: str = ""):
        self.name = name
        self.default = default
        self.type = type(default)
        self.live = live
        self.help = help


_REGISTRY: Dict[str, Flag] = {}
_VALUES: Dict[str, Any] = {}
_LOCK = threading.Lock()


def _define(name: str, default, live: bool, help: str = ""):
    _REGISTRY[name] = Flag(name, default, live, help)
    _VALUES[name] = default


# -- live flags: read by this framework's runtime ---------------------------
_define("check_nan_inf", False, True,
        "after every traced op, verify float outputs are finite and raise "
        "EnforceNotMet naming the first offending op/var (reference "
        "operator.cc:953-983)")
_define("async_dispatch", False, True,
        "pipelined step dispatch: run(..., return_numpy=False) returns "
        "fetch handles backed by live jax.Arrays instead of synced host "
        "copies, and NaN/Inf checks (FLAGS_check_nan_inf) are deferred to "
        "handle materialization / Executor.synchronize() so step N+1's "
        "host work overlaps step N's device compute and D2H "
        "(docs/ASYNC_DISPATCH.md)")
_define("async_checkpoint", False, True,
        "route io.save_persistables/load_persistables (and the fleet "
        "save paths) through the async sharded checkpoint subsystem "
        "(paddle_tpu/checkpoint): snapshot on the step-loop thread, "
        "background D2H + serialization, atomic commit with manifest + "
        "checksums, LATEST pointer updated last "
        "(docs/CHECKPOINTING.md)")
_define("allreduce_bucket_mb", 32.0, True,
        "gradient-communication bucket size cap in MB for the comm "
        "scheduler (paddle_tpu/parallel/comm_scheduler): param grads "
        "are grouped into dtype-homogeneous buckets of at most this "
        "many MB in reverse-backward (production) order and each "
        "bucket is flattened into ONE fused all-reduce issued as soon "
        "as its last grad is produced, overlapping collectives with "
        "the remaining backward. <= 0 disables bucketing (one "
        "collective per tensor, the pre-scheduler behavior); reference "
        "FLAGS_fuse_parameter_memory_size analog (docs/COLLECTIVES.md)")
_define("quantized_allreduce", "", True,
        "quantize comm-scheduler bucket payloads on the wire: '' "
        "(off, exact dtype), 'int8' (EQuARX-style scale-per-bucket "
        "symmetric int8), or 'bf16' (cast). Small (<64KB) and "
        "non-float buckets always fall back to the exact dtype. "
        "Lossy — see docs/COLLECTIVES.md for tolerance accounting")
_define("sharded_weight_update", False, True,
        "shard the optimizer weight update across the data-parallel "
        "axis (arXiv:2004.13336 / ZeRO-1): optimizer state shards "
        "dim 0 over dp, XLA's partitioner turns grad all-reduce + "
        "replicated update into reduce-scatter + 1/|dp| local update "
        "+ all-gather of the updated params. Composes with an "
        "explicit DistributedStrategy (strategy rules win first); "
        "docs/COLLECTIVES.md")
_define("paddle_num_threads", 2, True,
        "default reader worker threads for the native data feed")
_define("seed", 0, True, "global default RNG seed when a Program sets none")
_define("validate_program", False, True,
        "run the static analyzer (paddle_tpu/analysis) over each program "
        "before execution and raise EnforceNotMet on error-severity "
        "findings; cached per program fingerprint so steady-state "
        "training pays the cost once")
_define("validate_tier", 1, True,
        "validation depth when FLAGS_validate_program is on: tier 1 "
        "analyzes the program at the executor boundary with statically "
        "inferred feed/update sets; tier 2 additionally cross-checks "
        "each traced step's lowering decisions (guard gate, collective "
        "plan) against the static lowering trace before it compiles — "
        "docs/STATIC_ANALYSIS.md")
# fully-async communicator knobs (reference communicator.cc:29-41)
_define("communicator_independent_recv_thread", True, True,
        "pull params on an independent thread (reference "
        "communicator.cc:29); False pulls inline after each send round")
_define("communicator_send_queue_size", 20, True,
        "per-grad-var bounded queue capacity (communicator.cc:31)")
_define("communicator_min_send_grad_num_before_recv", 20, True,
        "grads sent since last pull before the recv thread refreshes "
        "params (communicator.cc:33)")
_define("communicator_thread_pool_size", 5, True,
        "send/recv RPC worker threads (communicator.cc:35)")
_define("communicator_send_wait_times", 5, True,
        "empty-queue polls before a partial merge is sent "
        "(communicator.cc:36)")
_define("communicator_max_merge_var_num", 20, True,
        "max queued grads merged (summed) into one push "
        "(communicator.cc:39)")
_define("communicator_fake_rpc", False, True,
        "skip the wire; measure trainer-side overhead "
        "(communicator.cc:41)")
_define("communicator_merge_sparse_grad", True, True,
        "merge-add SelectedRows grads by row before push; False "
        "concatenates rows (communicator.cc:42)")
# resilience layer (paddle_tpu/distributed/resilience.py,
# docs/RESILIENCE.md) — the live successors of the reference's
# FLAGS_rpc_deadline/FLAGS_rpc_retry_times (grpc_client.h:176)
_define("rpc_deadline_s", 60.0, True,
        "total per-RPC deadline in seconds across every retry of one "
        "async_ps request (reference FLAGS_rpc_deadline was per-call "
        "milliseconds with blind retries)")
_define("rpc_max_retries", 5, True,
        "retries after the first failed attempt of one async_ps RPC "
        "(exponential backoff with jitter, bounded by rpc_deadline_s)")
_define("rpc_backoff_base_s", 0.1, True,
        "first-retry backoff; retry i sleeps base * 2**i (+ jitter), "
        "capped at rpc_backoff_max_s")
_define("rpc_backoff_max_s", 2.0, True,
        "upper bound on a single backoff sleep (before jitter)")
_define("rpc_backoff_jitter", 0.5, True,
        "jitter fraction: each backoff is scaled by a uniform factor "
        "in [1, 1+jitter] to decorrelate trainer retry storms")
_define("rpc_breaker_failures", 5, True,
        "consecutive failures to one endpoint before its circuit "
        "breaker opens (fast-fail instead of full retry schedules)")
_define("rpc_breaker_cooldown_s", 2.0, True,
        "seconds an open breaker waits before allowing one half-open "
        "probe to the endpoint")
_define("rpc_max_message_mb", 1024, True,
        "reject any wire message whose length prefix exceeds this many "
        "MB before allocating — a corrupted/hostile 8-byte prefix must "
        "not OOM the pserver")
_define("pserver_handler_threads", 16, True,
        "AsyncParameterServer request-handler pool size; a connection "
        "flood degrades to queuing instead of unbounded thread "
        "creation")
_define("heartbeat_interval_s", 1.0, True,
        "trainer->pserver liveness heartbeat cadence (the Communicator "
        "starts the beacon); <= 0 disables heartbeating")
_define("trainer_timeout_s", 0.0, True,
        "pserver evicts a trainer silent (no heartbeat/push) for this "
        "long: it is counted toward fanin so serve() cannot hang on a "
        "crashed trainer's missing complete; <= 0 (default) disables "
        "eviction")
_define("step_timeout_s", 0.0, True,
        "engine step watchdog: a step exceeding this raises a "
        "diagnosable EnforceNotMet with pending-op context from the "
        "async-dispatch layer; <= 0 (default) disables the watchdog")
# observability subsystem (paddle_tpu/observability, docs/OBSERVABILITY.md)
_define("telemetry", False, True,
        "per-step metric observation (paddle_tpu/observability): phase "
        "latency histograms, flight-recorder appends, registry "
        "collectors. Off (default) the step loop pays one boolean "
        "check; the flight recorder still arms itself under a fault "
        "plan or step watchdog so postmortems exist without telemetry")
_define("flight_recorder_steps", 64, True,
        "flight-recorder ring capacity: per-step span records retained "
        "for the postmortem dump (watchdog trip, PT_FAULT_PLAN, sticky "
        "async error, SIGTERM); sized at first use")
# custom-kernel registry (paddle_tpu/kernels, docs/KERNELS.md)
_define("use_custom_kernels", True, True,
        "route eligible ops through the Pallas custom-kernel registry "
        "(paddle_tpu/kernels/registry.py): fused Adam/SGD update, "
        "quantized matmul, flash attention. Selection happens at trace "
        "time inside the op lowerings, so the whole-block trace, the "
        "eager-island fallback, and dygraph all dispatch from "
        "the same table; ops with no eligible kernel keep the lowered "
        "path bit-identically. Per-kernel denial: PT_KERNEL_DENY="
        "name[,name]; eligibility floor: PT_KERNEL_MIN_NUMEL. On CPU "
        "backends kernels stay off unless the Pallas interpret-mode "
        "test hook is armed (docs/KERNELS.md)")
# training stability guard (paddle_tpu/stability, docs/STABILITY.md)
_define("stability_guard", False, True,
        "training stability guard (paddle_tpu/stability): fuse a "
        "finite/overflow check over the loss and gradient tensors plus "
        "an EMA grad-global-norm spike detector INTO the traced step, "
        "so the anomaly verdict is one on-device scalar instead of "
        "FLAGS_check_nan_inf's per-op host-visible flags. Anomalous "
        "parameter/optimizer-state updates are gated on device; the "
        "host-side policy (PT_STABILITY_POLICY: skip|clip|rescale|"
        "rollback|abort per anomaly class) decides recovery — rollback "
        "restores the in-memory ghost-snapshot ring captured every "
        "PT_GHOST_EVERY steps and re-executes the step "
        "(docs/STABILITY.md)")
# cross-replica integrity sentinel (paddle_tpu/stability/integrity.py,
# docs/RESILIENCE.md)
_define("integrity_sentinel", False, True,
        "parameter integrity sentinel (paddle_tpu/stability/"
        "integrity.py): fold a per-bucket parameter fingerprint "
        "(float sum + bit-level checksum over the comm-scheduler "
        "bucket layout) into the traced step every PT_INTEGRITY_EVERY "
        "steps. The host controller compares the pre-step fingerprint "
        "against the post-step fingerprint of the previous sentinel "
        "step: any bit that changed OUTSIDE the traced update (silent "
        "HBM corruption, a diverged replica's write, an injected "
        "bitflip fault) raises a classified 'integrity' anomaly "
        "through the stability-guard policy machinery "
        "(PT_STABILITY_POLICY: integrity=rollback by default), writes "
        "exactly one attributed postmortem (worker, bucket, params, "
        "drift) via the flight recorder, and restores the sentinel's "
        "ghost ring. Escalates to abort after "
        "PT_INTEGRITY_ESCALATE_AFTER consecutive mismatches "
        "(docs/RESILIENCE.md)")
# feedback-directed autotuner (paddle_tpu/tuning, docs/TUNING.md)
_define("autotune", False, True,
        "feedback-directed autotuner (paddle_tpu/tuning): at the first "
        "step of a program, look the program up in the persistent "
        "tuning cache (PT_TUNING_CACHE_DIR) and apply the stored "
        "winning knob config before the first trace; on a miss, run a "
        "scope-snapshotted coordinate-descent search over the knob "
        "registry (measured step ms objective, successive-halving "
        "budgets), persist the winner atomically, then apply it. "
        "Lossy knobs (quantized allreduce / quantized matmul) are "
        "excluded from the search unless PT_TUNE_ALLOW_LOSSY=1, so "
        "the tuned trajectory stays value-preserving. Search extras: "
        "PT_TUNE_BUDGETS, PT_TUNE_ROUNDS, PT_TUNE_SEED, "
        "PT_TUNE_VARIANTS (Pallas kernel variant search) "
        "(docs/TUNING.md)")

# -- subsumed flags: accepted, validated, no effect under XLA/PJRT ----------
for _name, _default, _help in [
    ("eager_delete_tensor_gb", -1.0,
     "XLA liveness-based freeing is always on"),
    ("allocator_strategy", "naive_best_fit", "PJRT owns allocation"),
    ("fraction_of_gpu_memory_to_use", 0.92, "PJRT owns device memory"),
    ("initial_cpu_memory_in_mb", 500, "host allocator is malloc"),
    ("fraction_of_cpu_memory_to_use", 1.0, "host allocator is malloc"),
    ("init_allocated_mem", False, "XLA buffers are always defined"),
    ("free_idle_memory", False, "PJRT owns freeing"),
    ("fast_eager_deletion_mode", True, "XLA liveness subsumes GC"),
    ("memory_fraction_of_eager_deletion", 1.0, "XLA liveness subsumes GC"),
    ("use_pinned_memory", True, "PJRT owns host staging"),
    ("use_mkldnn", False, "single XLA backend"),
    ("use_ngraph", False, "single XLA backend"),
    ("cudnn_deterministic", False, "XLA determinism instead"),
    ("cudnn_exhaustive_search", False, "XLA autotuning instead"),
    ("conv_workspace_size_limit", 4096, "XLA autotuning instead"),
    ("cudnn_batchnorm_spatial_persistent", False, "XLA fusion instead"),
    ("sync_nccl_allreduce", True, "XLA collectives are ordered"),
    ("enable_parallel_graph", False, "SPMD partitioner instead"),
    ("fuse_parameter_memory_size", -1, "XLA fusion instead"),
    ("inner_op_parallelism", 0, "XLA runtime owns threading"),
    ("rpc_deadline", 180000, "superseded by live FLAGS_rpc_deadline_s"),
    ("dist_threadpool_size", 0,
     "superseded by live FLAGS_pserver_handler_threads"),
]:
    _define(_name, _default, False, "subsumed: " + _help)


def _coerce(flag: Flag, value):
    if flag.type is bool:
        if isinstance(value, str):
            return value.strip().lower() in ("1", "true", "yes", "on")
        return bool(value)
    return flag.type(value)


def set_flags(flags: Dict[str, Any]):
    """Set flags by name (``{"FLAGS_check_nan_inf": True}`` or bare name)."""
    with _LOCK:
        for raw, value in flags.items():
            name = raw[6:] if raw.startswith("FLAGS_") else raw
            flag = _REGISTRY.get(name)
            if flag is None:
                raise ValueError(
                    f"unknown flag {raw!r}; known flags: "
                    f"{sorted(_REGISTRY)}")
            _VALUES[name] = _coerce(flag, value)
            if name == "telemetry":
                # route into the observability gate so a runtime
                # set_flags toggle takes effect mid-training
                try:
                    from ..observability import metrics as _obs_metrics
                    _obs_metrics.enable_telemetry(_VALUES[name])
                except ImportError:
                    pass


def get_flags(names) -> Dict[str, Any]:
    if isinstance(names, str):
        names = [names]
    out = {}
    for raw in names:
        name = raw[6:] if raw.startswith("FLAGS_") else raw
        if name not in _REGISTRY:
            raise ValueError(f"unknown flag {raw!r}")
        out["FLAGS_" + name] = _VALUES[name]
    return out


def flag_info(name: str) -> Flag:
    name = name[6:] if name.startswith("FLAGS_") else name
    return _REGISTRY[name]


class _FlagsView:
    """Attribute access used by runtime code: ``FLAGS.check_nan_inf``."""

    def __getattr__(self, name):
        try:
            return _VALUES[name]
        except KeyError:
            raise AttributeError(name) from None


FLAGS = _FlagsView()


def __bootstrap__():
    """Read FLAGS_* env vars once at import (reference __init__.py:124-221).

    Unknown FLAGS_* env vars are ignored (the environment is shared with
    other processes), unlike set_flags which raises on typos.
    """
    for env_name, value in os.environ.items():
        if not env_name.startswith("FLAGS_"):
            continue
        name = env_name[6:]
        flag = _REGISTRY.get(name)
        if flag is not None:
            _VALUES[name] = _coerce(flag, value)


__bootstrap__()
