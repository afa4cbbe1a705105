"""Flag-gated program validation for the executor hot path.

``validate_cached`` is what ``Executor.run`` / ``CompiledProgram._run``
call when ``FLAGS_validate_program`` is on: it runs the full pass
pipeline once per program fingerprint (uid, version) and raises
``EnforceNotMet`` listing every error-severity diagnostic. The cache
means a training loop re-running the same program pays the analysis
cost exactly once, and an edited program (version bump) is re-checked.
"""
from __future__ import annotations

from typing import List, Optional, Sequence

from ..core.enforce import EnforceNotMet
from .diagnostics import Diagnostic, format_report, has_errors
from .passes import analyze_program

__all__ = ["validate_program", "validate_cached", "validate_transpiled",
           "validate_collective_plan", "clear_validation_cache"]


def validate_program(program, feed_names=None, fetch_names=(),
                     passes: Optional[Sequence[str]] = None,
                     label: str = "") -> List[Diagnostic]:
    """Analyze and raise ``EnforceNotMet`` if any ERROR finding exists.

    Returns the full diagnostic list (warnings included) on success so
    callers can surface non-fatal findings.
    """
    diags = analyze_program(program, feed_names=feed_names,
                            fetch_names=fetch_names, passes=passes,
                            label=label)
    if has_errors(diags):
        first_err = next(d for d in diags if d.is_error)
        raise EnforceNotMet(
            format_report([d for d in diags if d.is_error],
                          header="program validation failed"),
            op_type=first_err.op_type)
    return diags


# fingerprint -> frozenset of feed/fetch keys already validated clean
_VALIDATED = {}
_CACHE_LIMIT = 256


def validate_cached(program, feed_names=None, fetch_names=()) -> None:
    """``validate_program`` memoized on (program fingerprint, feed set,
    fetch set). Failures are not cached: a raising program re-raises on
    every run, matching the enforce semantics of the uncached path."""
    key = (program.fingerprint,
           None if feed_names is None else frozenset(feed_names),
           tuple(fetch_names))
    if key in _VALIDATED:
        return
    validate_program(program, feed_names=feed_names,
                     fetch_names=fetch_names)
    if len(_VALIDATED) >= _CACHE_LIMIT:
        _VALIDATED.clear()
    _VALIDATED[key] = True


def clear_validation_cache() -> None:
    _VALIDATED.clear()


def validate_transpiled(program, fetch_names=(),
                        label: str = "transpiled program") -> None:
    """Validation tier 2 for the transpiler path: verify the program
    the transpiler ACTUALLY emitted, at emission time.

    The engine's tier-2 hook only fires when the program is later run
    through ``Engine.run``; this hook closes the gap between transpile
    and dispatch — a malformed emitted collective plan (bucket member
    dropped, order violating grad production, mixed dtypes) raises
    here, in the rank that produced it, before the ring can hang.
    Called from ``transpiler.collective`` when ``FLAGS_validate_program``
    and ``FLAGS_validate_tier >= 2``; raises ``EnforceNotMet``."""
    from .passes import AnalysisContext
    from .races import _bucket_plan_diags
    ctx = AnalysisContext(program, None, tuple(fetch_names), label)
    diags = list(_bucket_plan_diags(ctx))
    if has_errors(diags):
        first_err = next(d for d in diags if d.is_error)
        raise EnforceNotMet(
            format_report([d for d in diags if d.is_error],
                          header="transpiled-program validation "
                                 "failed (tier 2)"),
            op_type=first_err.op_type)


def validate_collective_plan(items, buckets, bucket_bytes,
                             label: str = "collective plan") -> None:
    """Validation tier 2 for the dygraph path: re-prove the bucket
    plan ``apply_collective_grads`` is about to reduce.

    ``items`` is the planner input ([(name, shape, dtype), ...]) and
    ``buckets`` the ``plan_named_buckets`` output.  Invariants: every
    item lands in exactly one bucket, bucket members are contiguous in
    item order (a reordered tiling would scatter the reduced payload
    back to the wrong grads), members share one dtype, and multi-member
    buckets respect the byte cap.  Raises ``EnforceNotMet``."""
    import numpy as np
    problems: List[str] = []
    order = [it[0] for it in items]
    pos = {n: i for i, n in enumerate(order)}
    covered: dict = {}
    cursor = 0
    for bi, b in enumerate(buckets):
        names = list(b.names)
        for n in names:
            if n not in pos:
                problems.append(
                    f"bucket {bi} member {n!r} is not a planner input")
                continue
            if n in covered:
                problems.append(
                    f"grad {n!r} appears in buckets {covered[n]} and "
                    f"{bi}: it would be reduced twice")
            covered[n] = bi
        idxs = [pos[n] for n in names if n in pos]
        if idxs and idxs != list(range(cursor, cursor + len(idxs))):
            problems.append(
                f"bucket {bi} members {names} are not a contiguous "
                f"run of the planner input order — the flattened "
                f"payload would scatter back to the wrong grads")
        cursor = (idxs[-1] + 1) if idxs else cursor
        dts = {str(np.result_type(it[2])) for it in items
               if it[0] in set(names)}
        if len(dts) > 1:
            problems.append(
                f"bucket {bi} mixes dtypes {sorted(dts)}: one fused "
                f"payload cannot carry both")
        if len(names) > 1 and bucket_bytes > 0 and \
                int(getattr(b, "bytes", 0)) > int(bucket_bytes):
            problems.append(
                f"bucket {bi} holds {int(b.bytes)} bytes over the "
                f"{int(bucket_bytes)}-byte cap with "
                f"{len(names)} members")
    missing = [n for n in order if n not in covered]
    if missing:
        problems.append(
            f"{len(missing)} grad(s) missing from every bucket "
            f"(first: {missing[0]!r}) — they would never be reduced")
    if problems:
        lines = "\n".join(f"  - {p}" for p in problems)
        raise EnforceNotMet(
            f"collective-plan validation failed (tier 2) for "
            f"{label}:\n{lines}")
