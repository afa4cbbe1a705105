"""Reduction of a JAX profiler trace (`*.xplane.pb`) to what the per-layer
metrics read.

Read with `jax.profiler.ProfileData` alone. On the TPU a device plane
`/device:TPU:<n>` carries the lines "Steps", "XLA Modules", "XLA Ops" and
"Async XLA Ops"; "XLA Ops" is the per-HLO-instruction level (the other
lines are parent spans, and the async line is copies that overlap it).
An op event's name is the optimized HLO instruction's own text, so a
Pallas kernel is an event whose text has
`custom_call_target="tpu_custom_call"`, with its operand and result types
(and the memory space XLA put each in) beside it. On a CPU (rehearsal
only) the op events are those of the host plane that carry an `hlo_op`
stat.

  busy_s      the UNION of the op events' intervals (a sum would count
              nested or overlapping events twice), averaged over the
              device planes;
  idle gaps   the spaces between consecutive busy intervals, attributed
              to the innermost host span (TraceMe) that covers them on
              the thread that carries the harness's own `bench.step`
              annotation.
"""
from __future__ import annotations

import collections
import glob
import os
import re

PALLAS_TARGET = 'custom_call_target="tpu_custom_call"'
STEP_SPAN = "bench.step"

_DTYPE_BYTES = {"pred": 1, "s8": 1, "u8": 1, "s16": 2, "u16": 2, "s32": 4,
                "u32": 4, "s64": 8, "u64": 8, "bf16": 2, "f16": 2,
                "f32": 4, "f64": 8, "f8e4m3fn": 1, "f8e5m2": 1}
# one typed array with its layout: f32[8192,128]{1,0:T(8,128)S(1)}
_ARRAY_RE = re.compile(r"\b([a-z]+[0-9]*[a-z0-9]*)\[([0-9,]*)\](\{[^}]*\})?")
_HEAD_RE = re.compile(r"^%?([\w.\-]+) = (.*?) ([a-z\-]+)\(")


class Op:
    __slots__ = ("name", "start_ns", "dur_ns")

    def __init__(self, name, start_ns, dur_ns):
        self.name, self.start_ns, self.dur_ns = name, start_ns, dur_ns

    @property
    def end_ns(self):
        return self.start_ns + self.dur_ns


def newest_xplane(trace_dir):
    paths = glob.glob(os.path.join(trace_dir, "**", "*.xplane.pb"),
                      recursive=True)
    if not paths:
        raise RuntimeError(f"the profiler wrote no xplane under {trace_dir}")
    return max(paths, key=os.path.getmtime)


def load(path):
    """{"devices": {plane name: [Op]}, "host": {line name: [Op]}}."""
    import jax
    data = jax.profiler.ProfileData.from_file(path)
    devices, host, cpu_ops = {}, {}, []
    for plane in data.planes:
        if plane.name.startswith("/device:TPU:"):
            for line in plane.lines:
                if line.name == "XLA Ops":
                    devices[plane.name] = [
                        Op(e.name, e.start_ns, e.duration_ns)
                        for e in line.events]
        elif plane.name == "/host:CPU":
            for line in plane.lines:
                spans = []
                for e in line.events:
                    if e.duration_ns <= 0:
                        continue
                    op = Op(e.name, e.start_ns, e.duration_ns)
                    if any(k == "hlo_op" for k, _ in e.stats):
                        cpu_ops.append(op)
                    else:
                        spans.append(op)
                if spans:
                    host[line.name] = spans
    if not devices and cpu_ops:           # CPU rehearsal
        devices["/host:CPU (XLA:CPU ops)"] = sorted(
            cpu_ops, key=lambda o: o.start_ns)
    return {"devices": devices, "host": host}


def busy_intervals(ops):
    """Merged [start, end] intervals (ns) of a list of ops."""
    merged = []
    for a, b in sorted((o.start_ns, o.end_ns) for o in ops if o.dur_ns > 0):
        if merged and a <= merged[-1][1]:
            if b > merged[-1][1]:
                merged[-1][1] = b
        else:
            merged.append([a, b])
    return merged


def busy_seconds(ops):
    return sum(b - a for a, b in busy_intervals(ops)) / 1e9


def is_pallas(op):
    return PALLAS_TARGET in op.name


def opcode(op):
    """The HLO opcode of an op event ('fusion', 'custom-call', ...); a
    name that is not instruction text is its own opcode."""
    m = _HEAD_RE.match(op.name)
    return m.group(3) if m else op.name.split(".")[0]


def _arrays(text):
    """[(dtype, elements, in_hbm)] of the typed arrays in HLO text. An
    array whose layout names a memory space, S(n), was put by XLA in
    on-chip memory; one without lives in HBM."""
    out = []
    for dtype, dims, layout in _ARRAY_RE.findall(text):
        if dtype not in _DTYPE_BYTES:
            continue
        n = 1
        for d in dims.split(","):
            if d:
                n *= int(d)
        out.append((dtype, n, "S(" not in (layout or "")))
    return out


def instruction_parts(op):
    """(result text, operand text) of an instruction-text event name."""
    m = _HEAD_RE.match(op.name)
    if not m:
        return "", ""
    rest = op.name[m.end():]
    cut = rest.find("), ")
    return m.group(2), rest if cut < 0 else rest[:cut]


def hbm_bytes(op):
    """Bytes of the op's operands and results that live in HBM."""
    res, args = instruction_parts(op)
    return sum(_DTYPE_BYTES[d] * n for d, n, hbm in _arrays(res + " " + args)
               if hbm)


def signature(op):
    """Result and operand dtypes/shapes without layouts or names."""
    res, args = instruction_parts(op)
    fmt = lambda t: [f"{d}[{n}]" for d, n, _ in _arrays(t)]
    return fmt(res), fmt(args)


def idle_gaps(ops, spans, min_ns=20_000):
    """{host span name: idle seconds}: every gap of at least `min_ns`
    between busy intervals, cut at the host spans' boundaries, each piece
    given to the shortest span that covers it."""
    out = collections.defaultdict(float)
    busy = busy_intervals(ops)
    spans = sorted(spans, key=lambda s: s.start_ns)
    for (_, a), (b, _) in zip(busy, busy[1:]):
        if b - a < min_ns:
            continue
        near = [s for s in spans if s.start_ns < b and s.end_ns > a]
        cuts = sorted({a, b} | {t for s in near
                                for t in (s.start_ns, s.end_ns)
                                if a < t < b})
        for lo, hi in zip(cuts, cuts[1:]):
            cover = [s for s in near
                     if s.start_ns <= lo and s.end_ns >= hi]
            name = min(cover, key=lambda s: s.dur_ns).name if cover \
                else "no host span"
            out[name] += (hi - lo) / 1e9
    return dict(out)


def step_line(host):
    """The host thread's spans that carry the harness's step annotation."""
    for spans in host.values():
        if any(s.name == STEP_SPAN for s in spans):
            return spans
    return []


def top(table, n=10):
    return [[k, v] for k, v in sorted(table.items(),
                                      key=lambda kv: -kv[1])[:n]]


def summarize(path, classify):
    """What the readers get: per device the ops, the busy seconds, time by
    category, kernel events by `classify(op) -> kernel name or None`."""
    loaded = load(path)
    if not loaded["devices"]:
        raise RuntimeError("the trace holds no device op events")
    spans = step_line(loaded["host"])
    per_device = []
    by_category = collections.defaultdict(float)
    gaps = collections.defaultdict(float)
    kernels = collections.defaultdict(list)
    for name, ops in sorted(loaded["devices"].items()):
        per_device.append({"plane": name, "ops": len(ops),
                           "busy_s": busy_seconds(ops)})
        for op in ops:
            kernel = classify(op) if is_pallas(op) else None
            if kernel is not None:
                kernels[kernel].append(op)
            by_category[f"pallas:{kernel}" if kernel
                        else ("pallas:unknown" if is_pallas(op)
                              else "xla:" + opcode(op))] += op.dur_ns / 1e9
        for k, v in idle_gaps(ops, spans).items():
            gaps[k] += v
    n = len(per_device)
    return {
        "devices": per_device,
        "busy_s": sum(d["busy_s"] for d in per_device) / n,
        "steps_annotated": sum(s.name == STEP_SPAN for s in spans),
        "by_category_s": {k: v / n for k, v in by_category.items()},
        "idle_gaps_s": {k: v / n for k, v in gaps.items()},
        "kernels": dict(kernels),
        "n_devices": n,
    }
