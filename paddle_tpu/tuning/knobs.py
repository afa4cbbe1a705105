"""Declarative registry of every tunable knob (docs/TUNING.md).

Until this PR, the config surface ROADMAP item 3 calls "flag
archaeology" was scattered: ``kernels/registry.py`` parsed
``PT_KERNEL_MIN_NUMEL``/``PT_KERNEL_DENY`` inline, the prefetcher
depth had no knob at all, and nothing recorded which knobs change
numerics or compiled-trace content. This module is the single catalog:

* every knob declares its backing store (a live ``FLAGS_*`` flag or a
  ``PT_*`` env var), type, safe default, and search candidates;
* ``lossy`` marks knobs that change numerics (quantized allreduce,
  quantized matmul) — the search driver excludes them unless
  ``PT_TUNE_ALLOW_LOSSY=1``;
* ``trace_affecting`` marks knobs that change compiled-trace content —
  the audit test asserts every one of them shows up in BOTH engine
  cache keys (``_cache_key`` and ``_fast_key``, built from one shared
  tuple);
* :func:`apply`/:func:`restore`/:func:`applied` snapshot the RAW
  backing state (env-var presence included) and put it back exactly,
  even when a trial raises mid-flight — tuning must never leak knob
  state into training.

Runtime readers (kernel eligibility floor, prefetch depth, ghost
cadence) call :func:`value` instead of ``os.getenv`` so a
runtime change — ``set_flags``, ``os.environ``, or an applied tuning
config — takes effect without re-import.
"""
from __future__ import annotations

import contextlib
import hashlib
import json
import os
from typing import Any, Dict, List, Optional, Sequence, Tuple

__all__ = ["Knob", "knobs", "get", "value", "set_value", "snapshot",
           "apply", "restore", "applied", "search_space", "key_items",
           "config_digest", "allow_lossy", "defaults"]


class Knob:
    """One tunable: where it lives, what it may be, what it touches."""

    __slots__ = ("name", "kind", "key", "type", "default", "candidates",
                 "lossy", "trace_affecting", "help")

    def __init__(self, name: str, kind: str, key: str, type_, default,
                 candidates: Sequence, lossy: bool,
                 trace_affecting: bool, help: str = ""):
        assert kind in ("flag", "env"), kind
        self.name = name
        self.kind = kind
        self.key = key           # "FLAGS_..." name or "PT_..." env var
        self.type = type_
        self.default = default
        self.candidates = tuple(candidates)
        self.lossy = lossy
        self.trace_affecting = trace_affecting
        self.help = help

    # -- backing-store access ------------------------------------------

    def get(self):
        """Current typed value from the live backing store."""
        if self.kind == "flag":
            from ..core.flags import get_flags
            return get_flags(self.key)["FLAGS_" + self._flag_name()]
        raw = os.environ.get(self.key)
        if raw is None or raw == "":
            return self.default
        try:
            return self._coerce(raw)
        except (TypeError, ValueError):
            return self.default

    def set(self, v) -> None:
        if self.kind == "flag":
            from ..core.flags import set_flags
            set_flags({self.key: v})
        elif v is None:
            os.environ.pop(self.key, None)
        else:
            os.environ[self.key] = str(self._coerce(v))

    def raw(self):
        """Raw backing state for exact restore: the flag value, or the
        env string (None = variable absent)."""
        if self.kind == "flag":
            return self.get()
        return os.environ.get(self.key)

    def set_raw(self, raw) -> None:
        if self.kind == "flag":
            from ..core.flags import set_flags
            set_flags({self.key: raw})
        elif raw is None:
            os.environ.pop(self.key, None)
        else:
            os.environ[self.key] = raw

    def _flag_name(self) -> str:
        return self.key[6:] if self.key.startswith("FLAGS_") else self.key

    def _coerce(self, v):
        if self.type is bool:
            if isinstance(v, str):
                return v.strip().lower() in ("1", "true", "yes", "on")
            return bool(v)
        return self.type(v)

    def __repr__(self):  # pragma: no cover - debugging aid
        return (f"Knob({self.name!r}, {self.kind}:{self.key}, "
                f"default={self.default!r}, lossy={self.lossy}, "
                f"trace={self.trace_affecting})")


_KNOBS: Dict[str, Knob] = {}


def _def(name, kind, key, type_, default, candidates, *, lossy=False,
         trace_affecting=False, help=""):
    _KNOBS[name] = Knob(name, kind, key, type_, default, candidates,
                        lossy, trace_affecting, help)


# -- the catalog (docs/TUNING.md keeps the prose version) -------------------

_def("allreduce_bucket_mb", "flag", "FLAGS_allreduce_bucket_mb", float,
     32.0, (8.0, 32.0, 128.0), trace_affecting=True,
     help="comm-scheduler fused-allreduce bucket cap in MB "
          "(parallel/comm_scheduler.py); element-wise sums are "
          "unchanged by grouping, so lossless")
_def("quantized_allreduce", "flag", "FLAGS_quantized_allreduce", str,
     "", ("", "bf16", "int8"), lossy=True, trace_affecting=True,
     help="on-the-wire bucket quantization; changes gradient numerics "
          "(docs/COLLECTIVES.md tolerance accounting)")
_def("kernel_min_numel", "env", "PT_KERNEL_MIN_NUMEL", int, 65536,
     (16384, 65536, 262144), trace_affecting=True,
     help="eligibility floor for size-gated custom kernels "
          "(kernels/registry.py); admitted kernels are parity-gated "
          "value-preserving (<= 4 ulp), see docs/TUNING.md for the "
          "bit-identity caveat where kernels actually route")
_def("kernel_deny", "env", "PT_KERNEL_DENY", str, "", ("",),
     trace_affecting=True,
     help="comma-separated kernel deny list; single-candidate (the "
          "per-kernel off switch is an operator decision, not a "
          "search axis)")
_def("kernel_quant_matmul", "env", "PT_KERNEL_QUANT_MATMUL", str, "",
     ("", "int8", "bf16"), lossy=True, trace_affecting=True,
     help="quantized-matmul opt-in mode; changes GEMM numerics "
          "(docs/KERNELS.md)")
_def("prefetch_depth", "env", "PT_PREFETCH_DEPTH", int, 2, (1, 2, 4),
     help="DeviceFeedPrefetcher staged-batch bound "
          "(reader/prefetcher.py); host-side only")
_def("ghost_every", "env", "PT_GHOST_EVERY", int, 10, (5, 10, 20),
     help="stability-guard ghost-snapshot cadence in steps "
          "(stability/guard.py); snapshot cost vs rollback loss "
          "window, never touches the traced step")
_def("ghost_keep", "env", "PT_GHOST_KEEP", int, 2, (2,),
     help="ghost-snapshot ring depth; single-candidate (memory "
          "budget, not a latency axis)")
_def("multi_step_k", "env", "PT_MULTI_STEP", int, 1, (1, 2, 4),
     trace_affecting=True,
     help="training substeps fused into ONE dispatched executable "
          "(core/engine.py multi-step scan driver + prefetcher slab "
          "mode, docs/ASYNC_DISPATCH.md); amortizes the host dispatch "
          "cost over K batches — bit-identical to K sequential steps "
          "when anomaly-free, so lossless")
_def("compiler_options", "env", "PT_COMPILER_OPTIONS", str, "", ("",),
     trace_affecting=True,
     help="backend compiler k=v options baked into the compiled step "
          "(core/engine.py _compiler_options); candidates are curated "
          "per backend and filled in lazily by search_space() — CPU "
          "keeps the single empty candidate (not searched)")
_def("recompute", "env", "PT_RECOMPUTE", str, "", ("",),
     trace_affecting=True,
     help="op types re-derived at the fwd/bwd boundary (core/engine.py "
          "_recompute_types); measured loss on ResNet (July 2026) so "
          "not searched, but trace-affecting and key-audited")
_def("mesh_axes", "env", "PT_MESH_AXES", str, "", ("",),
     trace_affecting=True,
     help="hand-pinned mesh layout 'data=4,fsdp=2,tp=1' — short-"
          "circuits the placement search (analysis/placement.py); "
          "single-candidate (an operator decision, not a search axis)")
_def("mesh_fsdp", "env", "PT_MESH_FSDP", int, 0, (0,),
     trace_affecting=True,
     help="pin the fsdp axis size in the placement search (0 = free); "
          "single-candidate — the search itself explores the axis, "
          "this knob only constrains it (docs/PARALLELISM.md)")
_def("mesh_tp", "env", "PT_MESH_TP", int, 0, (0,),
     trace_affecting=True,
     help="pin the tensor-parallel axis size in the placement search "
          "(0 = free); single-candidate like mesh_fsdp")
_def("mesh_pp", "env", "PT_MESH_PP", int, 0, (0,),
     trace_affecting=True,
     help="pin the pipeline axis size in the placement search "
          "(0 = free); single-candidate like mesh_fsdp — a pp>1 plan "
          "routes execution through the stage-cut pipeline engines "
          "(docs/PARALLELISM.md)")
_def("pipeline_micro", "env", "PT_PIPELINE_MICRO", int, 8, (8,),
     trace_affecting=True,
     help="micro-batch count M the placement cost model uses for the "
          "pp bubble term (M+pp-1)/M (analysis/placement.py); a "
          "different M can flip the chosen plan, so trace-affecting")
_def("placement_auto", "env", "PT_PLACEMENT_AUTO", bool, False,
     (False,), trace_affecting=True,
     help="arm cost-driven automatic SPMD placement: Engine.run "
          "resolves (or replays from the tuning cache) a mesh layout "
          "before the first trace (analysis/placement.py); the chosen "
          "layout changes the traced shardings, so trace-affecting")
_def("placement_budget", "env", "PT_PLACEMENT_BUDGET", int, 64, (64,),
     trace_affecting=True,
     help="candidate cap for the placement search (deterministic cut "
          "after the sorted enumeration); a different budget can pick "
          "a different layout, so trace-affecting")


# -- registry access --------------------------------------------------------

def knobs() -> List[Knob]:
    return list(_KNOBS.values())


def get(name: str) -> Knob:
    try:
        return _KNOBS[name]
    except KeyError:
        raise KeyError(
            f"unknown knob {name!r}; known: {sorted(_KNOBS)}") from None


def value(name: str):
    """Typed current value of one knob — THE runtime read path."""
    return get(name).get()


def set_value(name: str, v) -> None:
    get(name).set(v)


def defaults() -> Dict[str, Any]:
    return {k.name: k.default for k in _KNOBS.values()}


def allow_lossy() -> bool:
    """Lossy-knob search opt-in (PT_TUNE_ALLOW_LOSSY=1)."""
    return os.environ.get("PT_TUNE_ALLOW_LOSSY", "").strip() in (
        "1", "true", "yes", "on")


# curated per-backend compiler_options candidate sets: every entry is a
# scheduling/fusion toggle (trace-affecting, value-preserving) — never a
# precision or fast-math knob, so the lossless search may explore them.
# The empty string (backend defaults) is always candidate 0.
_COMPILER_OPTION_SETS: Dict[str, Tuple[str, ...]] = {
    "tpu": (
        "",
        "xla_tpu_enable_latency_hiding_scheduler=true",
        "xla_tpu_enable_latency_hiding_scheduler=true,"
        "xla_tpu_enable_async_collective_fusion=true",
    ),
    "gpu": (
        "",
        "xla_gpu_enable_latency_hiding_scheduler=true",
        "xla_gpu_enable_while_loop_double_buffering=true",
    ),
}


def _refresh_compiler_candidates() -> None:
    """Fill compiler_options candidates for the LIVE backend, once.

    Deferred to search time because importing this catalog must not
    initialize a jax backend; on backends with no curated set (cpu)
    the knob keeps its single empty candidate and is not searched.
    """
    k = _KNOBS["compiler_options"]
    if len(k.candidates) > 1:
        return
    try:
        import jax
        backend = jax.default_backend()
    except Exception:
        return
    cands = _COMPILER_OPTION_SETS.get(backend)
    if cands:
        k.candidates = tuple(cands)


def search_space(include_lossy: Optional[bool] = None
                 ) -> List[Tuple[str, Tuple]]:
    """(knob name, candidate values) for every searchable knob.

    Knobs with a single candidate are catalog entries (apply/restore +
    key audit), not search axes. Lossy knobs are excluded unless
    ``PT_TUNE_ALLOW_LOSSY=1`` (or ``include_lossy=True``).
    """
    _refresh_compiler_candidates()
    lossy_ok = allow_lossy() if include_lossy is None else include_lossy
    return [(k.name, k.candidates) for k in _KNOBS.values()
            if len(k.candidates) > 1 and (lossy_ok or not k.lossy)]


def key_items(names: Optional[Sequence[str]] = None
              ) -> Tuple[Tuple[str, str], ...]:
    """(name, stringified current value) for trace-affecting knobs —
    the knob half of the tuning-cache identity (cache.py)."""
    ks = ([get(n) for n in names] if names is not None
          else [k for k in _KNOBS.values() if k.trace_affecting])
    return tuple((k.name, str(k.get())) for k in ks)


def config_digest(config: Dict[str, Any]) -> str:
    """Short stable digest of a knob config (the engine cache-key
    token for an applied tuning config)."""
    canon = json.dumps({k: str(v) for k, v in sorted(config.items())},
                       sort_keys=True)
    return hashlib.sha1(canon.encode()).hexdigest()[:16]


# -- exception-safe apply / restore -----------------------------------------

def snapshot(names: Optional[Sequence[str]] = None) -> Dict[str, Any]:
    """Raw backing state of the named knobs (all by default): flag
    values and env strings with None marking an ABSENT env var, so
    restore reproduces absence, not an empty string."""
    ks = [get(n) for n in names] if names is not None \
        else list(_KNOBS.values())
    return {k.name: k.raw() for k in ks}


def restore(snap: Dict[str, Any]) -> None:
    for name, raw in snap.items():
        get(name).set_raw(raw)


def apply(config: Dict[str, Any]) -> Dict[str, Any]:
    """Apply a knob config, returning the pre-apply snapshot.

    All-or-nothing: if any set fails (unknown knob, bad value), the
    knobs already touched are rolled back before the error propagates.
    """
    snap = snapshot(list(config))  # raises on unknown knob, pre-mutation
    done: List[str] = []
    try:
        for name, v in config.items():
            get(name).set(v)
            done.append(name)
    except BaseException:
        restore({n: snap[n] for n in done})
        raise
    return snap


@contextlib.contextmanager
def applied(config: Dict[str, Any]):
    """``with applied({...}):`` — apply for the body, restore exactly
    on exit, exception or not."""
    snap = apply(config)
    try:
        yield
    finally:
        restore(snap)
