"""Custom-kernel registry: one dispatch table for every execution path.

Kernels (hand-written Pallas TPU programs) register themselves here with
an op signature — the op types they can stand in for plus an eligibility
predicate over the concrete operand dtypes/shapes.  Op lowerings in
``paddle_tpu/ops`` consult :func:`select` at trace time; because the
engine whole-block trace, the ``FLAGS_op_scheduler`` island path, and
dygraph all execute ops through ``OPS.get(op.type).lowering(ctx)``, one
consultation point covers all three — no triple wiring.

Gating, outermost first:

* ``FLAGS_use_custom_kernels`` — master switch (live flag, default on).
* ``PT_KERNEL_DENY`` — comma-separated kernel names to skip (env).
* backend — on CPU backends the registry selects nothing unless the
  ``_INTERPRET`` test hook is armed, so tier-1 CI never routes hot paths
  through Pallas interpret mode by accident; tests monkeypatch
  ``_INTERPRET = True`` to exercise kernels on the host.
* partitioning — inside :func:`auto_partitioned` (the engine tracing a
  step XLA will partition over a multi-device mesh) every decision is
  ``lowered`` and counted as such: JAX refuses to lower a Mosaic kernel
  there ("cannot be automatically partitioned"), so the lowered op,
  which XLA can partition, stays.
* per-kernel ``eligible(sig)`` — dtype/shape/layout checks, including
  the ``PT_KERNEL_MIN_NUMEL`` floor where size matters.

Every decision increments ``pt_kernel_dispatch_total`` (labels:
``kernel``, ``outcome``) and a process-local stats dict
(:func:`dispatch_stats`).  All four knobs that change
trace content (the flag plus the three ``PT_KERNEL_*`` env vars) are
part of the engine ``_cache_key``/``_fast_key``, so toggling them can
never serve a stale compiled artifact.

See docs/KERNELS.md for the registry model and how to add a kernel.
"""

from __future__ import annotations

import contextlib
import os
import threading
from typing import Any, Callable, Dict, List, Optional, Sequence, Tuple

import jax

__all__ = [
    "Signature", "Kernel", "register_kernel", "select", "signature",
    "routable", "allowed", "count", "kernels", "kernel_names", "get",
    "dispatch_stats", "reset_stats", "min_numel", "interpret",
    "abstract_select", "candidate_op_types", "auto_partitioned",
    "in_auto_partitioned_trace",
]

# Test hook: arm to let the registry (and the kernels it selects) run in
# Pallas interpret mode on CPU backends.  Mirrors the module-level
# ``_INTERPRET`` hook in flash_attention.py.
_INTERPRET = False

_DEFAULT_MIN_NUMEL = 65536


class Signature:
    """Concrete operand signature a kernel is matched against."""

    __slots__ = ("op_type", "dtypes", "shapes")

    def __init__(self, op_type: str,
                 dtypes: Tuple[str, ...],
                 shapes: Tuple[Tuple[int, ...], ...]):
        self.op_type = op_type
        self.dtypes = dtypes
        self.shapes = shapes

    @property
    def numel(self) -> int:
        """Element count of the largest operand."""
        best = 0
        for s in self.shapes:
            n = 1
            for d in s:
                n *= int(d)
            best = max(best, n)
        return best

    def __repr__(self):  # pragma: no cover - debugging aid
        return ("Signature(%r, dtypes=%r, shapes=%r)"
                % (self.op_type, self.dtypes, self.shapes))


def signature(op_type: str, *arrays) -> Signature:
    """Build a :class:`Signature` from concrete (or traced) arrays.

    ``None`` operands (optional inputs) are skipped; only dtype and
    static shape are read, so tracers are fine.
    """
    dts, shps = [], []
    for a in arrays:
        if a is None:
            continue
        dts.append(str(getattr(a, "dtype", type(a).__name__)))
        shps.append(tuple(int(d) for d in getattr(a, "shape", ())))
    return Signature(op_type, tuple(dts), tuple(shps))


class Kernel:
    """One registered custom kernel."""

    __slots__ = ("name", "op_types", "run", "eligible", "doc")

    def __init__(self, name: str, op_types: Tuple[str, ...],
                 run: Callable, eligible: Callable[[Signature], bool],
                 doc: str = ""):
        self.name = name
        self.op_types = op_types
        self.run = run
        self.eligible = eligible
        self.doc = doc


_KERNELS: Dict[str, Kernel] = {}       # name -> Kernel, insertion order
_BY_OP: Dict[str, List[Kernel]] = {}   # op type -> kernels, in order

_STATS_LOCK = threading.Lock()
_STATS: Dict[str, Dict[str, int]] = {}  # kernel name -> outcome counts


def register_kernel(name: str, *, op_types: Sequence[str],
                    eligible: Callable[[Signature], bool],
                    run: Callable, doc: str = "") -> Kernel:
    """Register (or re-register, e.g. on module reload) a kernel."""
    kern = Kernel(name, tuple(op_types), run, eligible, doc)
    if name in _KERNELS:
        for lst in _BY_OP.values():
            lst[:] = [k for k in lst if k.name != name]
    _KERNELS[name] = kern
    for op in kern.op_types:
        _BY_OP.setdefault(op, []).append(kern)
    return kern


def kernels() -> List[Kernel]:
    return list(_KERNELS.values())


def kernel_names() -> List[str]:
    return list(_KERNELS)


def get(name: str) -> Optional[Kernel]:
    return _KERNELS.get(name)


def min_numel() -> int:
    """Eligibility floor for size-gated kernels, via the knob registry
    (tuning/knobs.py) so the autotuner and env agree on one read
    path."""
    try:
        from ..tuning import knobs
        return int(knobs.value("kernel_min_numel"))
    except Exception:
        return _DEFAULT_MIN_NUMEL


def interpret() -> bool:
    """Whether kernels invoked now should run Pallas in interpret mode.

    True exactly on CPU backends — a directly-invoked kernel (parity
    harness, unit test) is always runnable on the host; :func:`select`
    separately refuses to *route* ops here on CPU unless ``_INTERPRET``
    is armed.
    """
    return jax.default_backend() == "cpu"


def _platform() -> Optional[str]:
    """Backend platform if one is already initialized, else ``None``.

    Must NEVER force backend initialization: :func:`select` runs inside
    ``jax.eval_shape`` during graph building (framework
    ``_infer_op_shapes``), which happens before deferred bootstraps
    like ``jax.distributed.initialize()`` in multi-process workers —
    spinning up a backend there aborts the whole job.  Returning None
    keeps the lowered path, whose output shapes the kernels match by
    the parity contract, so shape inference is unaffected.
    """
    from jax._src import xla_bridge as xb
    if not xb._backends:
        return None
    return jax.default_backend()


_TRACE = threading.local()


@contextlib.contextmanager
def auto_partitioned():
    """Trace-time scope: the step being traced will be partitioned by
    XLA over a multi-device mesh (core/engine.py enters it for meshes
    of more than one device). A Mosaic kernel cannot be partitioned
    automatically — JAX raises NotImplementedError when it lowers one
    outside a fully-manual shard_map — so inside this scope the
    dispatchers (:func:`select`; flash attention's use_kernel_path)
    count every decision ``lowered``."""
    prev = in_auto_partitioned_trace()
    _TRACE.auto_partitioned = True
    try:
        yield
    finally:
        _TRACE.auto_partitioned = prev


def in_auto_partitioned_trace() -> bool:
    return getattr(_TRACE, "auto_partitioned", False)


def _deny() -> Tuple[str, ...]:
    try:
        from ..tuning import knobs
        raw = str(knobs.value("kernel_deny") or "")
    except Exception:
        raw = os.environ.get("PT_KERNEL_DENY", "")
    return tuple(p.strip() for p in raw.split(",") if p.strip())


def allowed(name: str) -> bool:
    """Flag + deny-list gate for one kernel (no backend/shape checks).

    Used by kernels with their own dispatch logic (flash attention) so
    the master switch and deny list still govern them.
    """
    from ..core.flags import FLAGS
    if not FLAGS.use_custom_kernels:
        return False
    return name not in _deny()


def _metric_inc(name: str, outcome: str) -> None:
    try:
        from ..observability import metrics
        metrics.counter("pt_kernel_dispatch_total").inc(
            1, kernel=name, outcome=outcome)
    except Exception:
        pass


_DECISIONS = ("custom", "lowered", "denied")


def count(name: str, outcome: str) -> None:
    """Record one dispatch outcome for *name*.

    outcome: a decision — ``custom`` (kernel chosen), ``lowered``
    (eligibility or backend said no), ``denied`` (flag/deny list said
    no) — or what a kernel says of the call it then made (the fused
    optimizer's ``native_view`` / ``flat_view``, the flash backward's
    ``fused_bwd`` / ``split_bwd`` and ``narrow_lse``) or a lowering of
    the form it took (``mul``'s ``in_rank``), which
    :func:`dispatch_stats` lists per name and leaves out of
    ``decisions`` and ``hit_rate``.
    """
    with _STATS_LOCK:
        d = _STATS.setdefault(name, {})
        d[outcome] = d.get(outcome, 0) + 1
    _metric_inc(name, outcome)


def routable(op_type: str) -> bool:
    """Cheap pre-gate for lowerings: could :func:`select` possibly
    route *op_type* to a kernel right now?

    Lowerings run for every op at build-time shape inference, at every
    trace, AND per step in eager/per-op dispatch — so the disabled
    path (CPU tier-1, flag off, backend not up) must cost a dict probe
    and two attribute reads, with no Signature construction and no
    stats traffic.  Call this before building a Signature.
    """
    if op_type not in _BY_OP:
        return False
    from ..core.flags import FLAGS
    if not FLAGS.use_custom_kernels:
        return False
    plat = _platform()
    if plat is None:
        return False
    return _INTERPRET or plat != "cpu"


def select(op_type: str, sig: Signature) -> Optional[Kernel]:
    """Pick a kernel for *sig*, or ``None`` to keep the lowered path.

    First registered eligible kernel wins.  Dispatch stats count only
    decisions made at a LIVE routing point (backend up, and not a CPU
    host without the interpret hook) — so hit rates in
    ``dispatch_stats()`` reflect real trace-time decisions, not the
    build-time shape-inference sweeps or hosts where routing is
    structurally impossible.
    """
    cands = _BY_OP.get(op_type)
    if not cands:
        return None
    plat = _platform()
    if plat is None or (plat == "cpu" and not _INTERPRET):
        # backend not up yet (build-time shape inference) or a CPU
        # host without the interpret hook: keep the lowered path
        return None
    from ..core.flags import FLAGS
    flag_on = bool(FLAGS.use_custom_kernels)
    deny = _deny()
    partitioned = in_auto_partitioned_trace()
    for kern in cands:
        if not flag_on or kern.name in deny:
            count(kern.name, "denied")
            continue
        if not partitioned and kern.eligible(sig):
            count(kern.name, "custom")
            return kern
        count(kern.name, "lowered")
    return None


def candidate_op_types() -> Tuple[str, ...]:
    """Op types with at least one registered kernel, sorted — the ops
    whose lowering a :func:`select` decision can change."""
    return tuple(sorted(_BY_OP))


def abstract_select(op_type: str, sig: Signature,
                    platform: str = "tpu") -> Optional[str]:
    """Replay :func:`select`'s dispatch decision under an ASSUMED live
    platform — no backend probe, no stats traffic, no side effects.

    This is the conformance verifier's view of kernel routing
    (analysis/conformance.py): on a CPU tier-1 host ``select`` always
    keeps the lowered path, so the cross-path comparison instead asks
    which kernel each path WOULD route to once the real backend is up.
    Same gating order as ``select``: candidates, platform, master
    flag, deny list, per-kernel eligibility; first eligible wins.
    """
    cands = _BY_OP.get(op_type)
    if not cands:
        return None
    if platform == "cpu" and not _INTERPRET:
        return None
    from ..core.flags import FLAGS
    if not FLAGS.use_custom_kernels:
        return None
    deny = _deny()
    for kern in cands:
        if kern.name in deny:
            continue
        if kern.eligible(sig):
            return kern.name
    return None


def dispatch_stats() -> Dict[str, Any]:
    """Process-local dispatch counters."""
    with _STATS_LOCK:
        per = {k: dict(v) for k, v in _STATS.items()}
    total = sum(v.get(d, 0) for v in per.values() for d in _DECISIONS)
    custom = sum(v.get("custom", 0) for v in per.values())
    return {
        "per_kernel": per,
        "decisions": total,
        "custom": custom,
        "hit_rate": (custom / total) if total else 0.0,
        "registered": kernel_names(),
    }


def reset_stats() -> None:
    with _STATS_LOCK:
        _STATS.clear()
