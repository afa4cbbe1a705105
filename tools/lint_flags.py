"""lint_flags: meta-lint for trace-cache key completeness.

The engine memoizes traced steps on ``Engine._cache_key`` /
``Engine._fast_key``, both built from the one tuple
``Engine._trace_key_items`` returns. Any ``FLAGS_*`` or ``PT_*``
environment read that happens while a step is being TRACED but is
missing from that tuple is a cache-poisoning bug: flip the flag, rerun,
and the engine silently serves a step traced under the old value
(``PT_COMPILER_OPTIONS`` was once such a read); this lint makes the
audit mechanical instead of archaeological.

How it works — all static, no imports of the scanned code:

1. Parse ``core/engine.py`` and collect every ``FLAGS.<name>`` read and
   every ``"PT_*"`` string constant inside the key functions. That is
   the KEYED set.
2. Parse every module that runs during trace construction
   (``TRACE_MODULES``) and collect every ``FLAGS.<name>`` /
   ``getattr(FLAGS, ...)`` / ``os.environ.get("PT_*")`` /
   ``os.getenv("PT_*")`` / ``os.environ["PT_*"]`` read site.
3. A read that is in neither the KEYED set nor the ALLOWLIST (curated
   host-side reads, each with a one-line justification) is a finding.
4. Cross-check the tuning catalog: every knob marked
   ``trace_affecting`` must have its backing flag/env in the KEYED set
   (the knob metadata and the key must not drift apart).

Exit codes: 0 clean, 1 findings, 2 usage — CI-gateable, and
``tests/test_lint_flags.py`` runs it as a tier-1 test with a planted
uncached read to prove the scanner actually sees new code.

Usage:
  python tools/lint_flags.py
  python tools/lint_flags.py --extra /path/to/new_trace_module.py
"""
from __future__ import annotations

import argparse
import ast
import os
import sys
from typing import Dict, List, Optional, Set, Tuple

_REPO = os.path.dirname(os.path.dirname(os.path.abspath(__file__)))
if _REPO not in sys.path:
    sys.path.insert(0, _REPO)

EXIT_CLEAN = 0
EXIT_FINDINGS = 1
EXIT_USAGE = 2

ENGINE_PATH = os.path.join(_REPO, "paddle_tpu", "core", "engine.py")
KEY_FUNCTIONS = ("_cache_key", "_fast_key", "_trace_key_items")

# Modules whose code executes while a step's lowering is DECIDED — for
# the engine that is trace time (kernel selection, stability gates,
# bucket planning); for the transpiler it is emission
# time (the c_allreduce_* plan is baked into the program); for dygraph
# it is the per-call eager path whose fused-allreduce callable is
# memoized per quantize mode. A flag read anywhere else happens at
# dispatch/observation time and cannot poison a cached artifact.
TRACE_MODULES = (
    "paddle_tpu/core/engine.py",
    "paddle_tpu/kernels/",
    "paddle_tpu/stability/",
    "paddle_tpu/parallel/comm_scheduler.py",
    "paddle_tpu/transpiler/",
    "paddle_tpu/dygraph/",
)

# Reads inside TRACE_MODULES that are deliberately NOT part of the
# trace key. Every entry needs a justification: "host-side" means the
# value steers dispatch/IO around the compiled step, never the traced
# computation itself.
ALLOWLIST: Dict[str, str] = {
    "FLAGS.async_dispatch": "host-side: picks sync vs async dispatch "
                            "of the SAME compiled step",
    "FLAGS.autotune": "host-side: arms the tuning driver between steps",
    "FLAGS.seed": "runtime state: seeds the RNG key that is a traced "
                  "ARGUMENT, not trace content",
    "FLAGS.step_timeout_s": "host-side: watchdog on the dispatch future",
    "FLAGS.validate_program": "host-side: gates the static analyzer",
    "FLAGS.validate_tier": "host-side: gates the tier-2 verifier",
    "PT_REPLAY_DIR": "host-side: where guard replay bundles land",
    "PT_GUARD_REPLAY_MAX": "host-side: replay bundle retention",
}


def _const_str(node) -> Optional[str]:
    if isinstance(node, ast.Constant) and isinstance(node.value, str):
        return node.value
    return None


def _os_aliases(tree) -> Set[str]:
    """Every name the module binds to the os module (``import os``,
    ``import os as _os``) — an aliased import must not hide an env
    read from the scan (dygraph/parallel.py imports ``os as _os``)."""
    names = {"os"}
    for node in ast.walk(tree):
        if isinstance(node, ast.Import):
            for alias in node.names:
                if alias.name == "os":
                    names.add(alias.asname or "os")
    return names


def _is_os_environ(node, os_names: Set[str]) -> bool:
    return (isinstance(node, ast.Attribute) and node.attr == "environ"
            and isinstance(node.value, ast.Name)
            and node.value.id in os_names)


def _read_name(node, os_names: Set[str] = frozenset(("os",))
               ) -> Optional[str]:
    """The canonical name of a flag/env read at this AST node, or None.

    Returns "FLAGS.<attr>" or the "PT_*" env var name.
    """
    # FLAGS.<attr>
    if isinstance(node, ast.Attribute) and \
            isinstance(node.value, ast.Name) and node.value.id == "FLAGS":
        return f"FLAGS.{node.attr}"
    if isinstance(node, ast.Call):
        f = node.func
        # getattr(FLAGS, "name", ...)
        if isinstance(f, ast.Name) and f.id == "getattr" and node.args:
            tgt = node.args[0]
            if isinstance(tgt, ast.Name) and tgt.id == "FLAGS" and \
                    len(node.args) >= 2:
                s = _const_str(node.args[1])
                if s:
                    return f"FLAGS.{s}"
        # os.environ.get("PT_...") / os.getenv("PT_...")
        if isinstance(f, ast.Attribute):
            if f.attr == "get" and _is_os_environ(f.value, os_names) \
                    and node.args:
                s = _const_str(node.args[0])
                if s and s.startswith("PT_"):
                    return s
            if f.attr == "getenv" and isinstance(f.value, ast.Name) \
                    and f.value.id in os_names and node.args:
                s = _const_str(node.args[0])
                if s and s.startswith("PT_"):
                    return s
    # os.environ["PT_..."]
    if isinstance(node, ast.Subscript) and \
            _is_os_environ(node.value, os_names):
        s = _const_str(node.slice)
        if s and s.startswith("PT_"):
            return s
    return None


def keyed_names(engine_path: str = ENGINE_PATH) -> Set[str]:
    """Everything ``_cache_key`` / ``_fast_key`` / ``_trace_key_items``
    fold into the trace key: FLAGS attrs read there, plus every PT_*
    string constant (the env reads)."""
    with open(engine_path, "r") as f:
        tree = ast.parse(f.read(), filename=engine_path)
    keyed: Set[str] = set()
    for fn in ast.walk(tree):
        if not isinstance(fn, (ast.FunctionDef, ast.AsyncFunctionDef)):
            continue
        if fn.name not in KEY_FUNCTIONS:
            continue
        for node in ast.walk(fn):
            name = _read_name(node)
            if name:
                keyed.add(name)
            s = _const_str(node)
            if s and s.startswith("PT_"):
                keyed.add(s)
    return keyed


def _in_key_function(path: str, lineno: int, spans) -> bool:
    return any(a <= lineno <= b for a, b in spans.get(path, ()))


def scan_reads(paths: List[str]) -> List[Tuple[str, int, str]]:
    """(file, line, name) for every flag/env read site in ``paths``."""
    out: List[Tuple[str, int, str]] = []
    spans: Dict[str, List[Tuple[int, int]]] = {}
    for path in paths:
        with open(path, "r") as f:
            try:
                tree = ast.parse(f.read(), filename=path)
            except SyntaxError as exc:
                out.append((path, exc.lineno or 0,
                            f"<unparseable: {exc.msg}>"))
                continue
        os_names = _os_aliases(tree)
        if os.path.abspath(path) == os.path.abspath(ENGINE_PATH):
            # the key functions READ the flags to key them; those
            # sites are the fix, not the bug
            spans[path] = [
                (fn.lineno, max(n.lineno for n in ast.walk(fn)
                                if hasattr(n, "lineno")))
                for fn in ast.walk(tree)
                if isinstance(fn, (ast.FunctionDef,
                                   ast.AsyncFunctionDef))
                and fn.name in KEY_FUNCTIONS]
        for node in ast.walk(tree):
            name = _read_name(node, os_names)
            if name is None:
                continue
            lineno = getattr(node, "lineno", 0)
            if _in_key_function(path, lineno, spans):
                continue
            out.append((path, lineno, name))
    return out


def trace_module_paths() -> List[str]:
    paths: List[str] = []
    for entry in TRACE_MODULES:
        full = os.path.join(_REPO, entry)
        if entry.endswith("/"):
            for fn in sorted(os.listdir(full)):
                if fn.endswith(".py"):
                    paths.append(os.path.join(full, fn))
        else:
            paths.append(full)
    return paths


def knob_gaps(keyed: Set[str]) -> List[str]:
    """trace_affecting knobs whose backing flag/env is not keyed."""
    from paddle_tpu.tuning import knobs as _knobs
    gaps = []
    for k in _knobs.knobs():
        if not k.trace_affecting:
            continue
        name = k.key if k.kind == "env" else \
            "FLAGS." + k.key[len("FLAGS_"):]
        if name not in keyed:
            gaps.append(f"knob '{k.name}' is trace_affecting but its "
                        f"backing {k.kind} '{k.key}' is not in the "
                        f"trace key")
    return gaps


def run(extra_paths: Optional[List[str]] = None) -> int:
    keyed = keyed_names()
    paths = trace_module_paths() + [
        os.path.abspath(p) for p in (extra_paths or [])]
    findings: List[str] = []
    seen: Set[Tuple[str, str]] = set()
    for path, lineno, name in scan_reads(paths):
        rel = os.path.relpath(path, _REPO)
        if name.startswith("<unparseable"):
            findings.append(f"{rel}:{lineno}: {name}")
            continue
        if name in keyed or name in ALLOWLIST:
            continue
        if (rel, name) in seen:
            continue
        seen.add((rel, name))
        findings.append(
            f"{rel}:{lineno}: trace-phase read of '{name}' is in "
            f"neither _cache_key/_fast_key nor the lint allowlist — "
            f"flipping it would serve a stale cached trace")
    findings.extend(knob_gaps(keyed))
    if findings:
        for f in findings:
            print(f"  {f}")
        print(f"lint_flags: {len(findings)} uncached trace-affecting "
              f"read(s); key them in Engine._cache_key/_fast_key or "
              f"allowlist them with a justification", file=sys.stderr)
        return EXIT_FINDINGS
    print(f"lint_flags: {len(keyed)} keyed name(s), "
          f"{len(paths)} trace-phase module(s), "
          f"{len(ALLOWLIST)} allowlisted host-side read(s) — clean")
    return EXIT_CLEAN


def main(argv: Optional[List[str]] = None) -> int:
    p = argparse.ArgumentParser(
        prog="lint_flags",
        description="find FLAGS_*/PT_* reads that can poison the "
                    "engine's trace cache")
    p.add_argument("--extra", nargs="*", default=None, metavar="FILE",
                   help="additional trace-phase files to scan (the "
                        "lint's own test plants a defect here)")
    ns = p.parse_args(argv)
    for f in ns.extra or []:
        if not os.path.isfile(f):
            print(f"lint_flags: no such file: {f}", file=sys.stderr)
            return EXIT_USAGE
    return run(ns.extra)


if __name__ == "__main__":
    sys.exit(main())
