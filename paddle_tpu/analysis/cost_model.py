"""Static per-op cost model (``analysis.cost``): FLOPs and bytes
moved, derived from declared operand shapes/dtypes on the def-use
graph — no tracing, no compilation.

This is the substrate ROADMAP item 1's SPMD placement search consumes
("Synthesizing Optimal Parallelism Placement and Reduction Strategies
on Hierarchical Systems" needs a per-op cost to score candidate
placements without compiling each one), and the model is
**calibrated** against XLA's own analysis (``Engine.compiled_stats``'s
flops).

Cost formulas are deliberately simple closed forms (dense GEMM/conv
arithmetic, element-wise/reduction byte counts, ring-allreduce 2N
wire bytes): the model's job is *ranking* placements, and
the calibration report quantifies how well the ranking tracks
reality instead of pretending the constants are exact.

The registered ``cost-model`` pass is silent unless
``PT_STATIC_FLOP_LIMIT`` is set (same opt-in contract as the
memory-plan pass): it then flags single ops whose static FLOPs exceed
the budget — the "accidentally quadratic batch dim" class of defect.
"""
from __future__ import annotations

import os
from typing import Any, Dict, List, Optional, Tuple

import numpy as np

from .diagnostics import Diagnostic, Severity

__all__ = ["OpCost", "ProgramCost", "program_cost"]


def _shape_of(block, name: str, dynamic_dim: int
              ) -> Optional[Tuple[int, ...]]:
    v = block._find_var_recursive(name)
    if v is None:
        return None
    try:
        shape = list(v.shape)
    except Exception:
        return None
    if shape is None:
        return None
    return tuple(dynamic_dim if int(d) < 0 else int(d) for d in shape)


def _numel(shape: Optional[Tuple[int, ...]]) -> int:
    if shape is None:
        return 0
    n = 1
    for d in shape:
        n *= d
    return n


def _itemsize(block, name: str) -> int:
    from ..core.types import dtype_to_np
    v = block._find_var_recursive(name)
    if v is None:
        return 4
    try:
        return np.dtype(dtype_to_np(v.dtype)).itemsize
    except Exception:
        return 4


class OpCost:
    __slots__ = ("op_idx", "op_type", "flops", "bytes_in", "bytes_out")

    def __init__(self, op_idx: int, op_type: str, flops: int,
                 bytes_in: int, bytes_out: int):
        self.op_idx = op_idx
        self.op_type = op_type
        self.flops = int(flops)
        self.bytes_in = int(bytes_in)
        self.bytes_out = int(bytes_out)

    @property
    def bytes_moved(self) -> int:
        return self.bytes_in + self.bytes_out

    def to_dict(self) -> Dict[str, Any]:
        return {"op_idx": self.op_idx, "op_type": self.op_type,
                "flops": self.flops, "bytes_in": self.bytes_in,
                "bytes_out": self.bytes_out}


class ProgramCost:
    """Per-op rows plus the aggregations every consumer wants."""

    __slots__ = ("rows", "block_idx", "dynamic_dim")

    def __init__(self, rows: List[OpCost], block_idx: int,
                 dynamic_dim: int):
        self.rows = rows
        self.block_idx = block_idx
        self.dynamic_dim = dynamic_dim

    @property
    def total_flops(self) -> int:
        return sum(r.flops for r in self.rows)

    @property
    def total_bytes(self) -> int:
        return sum(r.bytes_moved for r in self.rows)

    def by_type(self) -> Dict[str, Dict[str, int]]:
        out: Dict[str, Dict[str, int]] = {}
        for r in self.rows:
            agg = out.setdefault(r.op_type,
                                 {"count": 0, "flops": 0, "bytes": 0})
            agg["count"] += 1
            agg["flops"] += r.flops
            agg["bytes"] += r.bytes_moved
        return out

    def to_dict(self, top: int = 10) -> Dict[str, Any]:
        hot = sorted(self.by_type().items(),
                     key=lambda kv: -kv[1]["flops"])[:top]
        return {"total_flops": self.total_flops,
                "total_bytes": self.total_bytes,
                "ops": len(self.rows),
                "by_type": {k: v for k, v in hot}}


# -- per-op FLOP rules -------------------------------------------------------
# Each rule: fn(ins, outs) -> flops, where ins/outs map slot name ->
# list of (shape, numel). Missing rules fall back to element-wise cost
# (max operand numel), doubled for *_grad ops (one backward pass
# touches roughly two forward-sized products).

def _gemm_flops(ins, outs):
    x = ins.get("X") or [(None, 0)]
    y = ins.get("Y") or [(None, 0)]
    # grad variants have no Out OUTPUT slot, but they carry the
    # forward Out as an input — same M*N geometry either way
    out = outs.get("Out") or ins.get("Out") or [(None, 0)]
    xs, ys = x[0][0], y[0][0]
    if xs and ys:
        k = xs[-1]
        return 2 * _numel(out[0][0]) * max(1, k)
    return 2 * out[0][1]


def _conv_flops(ins, outs):
    f = ins.get("Filter") or [(None, 0)]
    out = (outs.get("Output") or outs.get("Out")
           or ins.get("Output") or ins.get("Out") or [(None, 0)])
    fs = f[0][0]
    if fs and len(fs) >= 4:
        cin_khkw = fs[1] * fs[2] * fs[3]
        return 2 * out[0][1] * max(1, cin_khkw)
    return 2 * out[0][1]


def _all_numel(slots) -> int:
    return sum(n for vals in slots.values() for _, n in vals)


_RULES = {
    "mul": _gemm_flops, "matmul": _gemm_flops, "matmul_v2": _gemm_flops,
    "conv2d": _conv_flops, "depthwise_conv2d": _conv_flops,
    "softmax": lambda i, o: 5 * _all_numel(o),
    "log_softmax": lambda i, o: 5 * _all_numel(o),
    "cross_entropy": lambda i, o: 3 * _all_numel(i),
    "softmax_with_cross_entropy": lambda i, o: 8 * _all_numel(i),
    "batch_norm": lambda i, o: 10 * _all_numel(
        {"X": i.get("X", [])}),
    "layer_norm": lambda i, o: 8 * _all_numel({"X": i.get("X", [])}),
    "lookup_table": lambda i, o: _all_numel(o),
    "lookup_table_v2": lambda i, o: _all_numel(o),
    "sgd": lambda i, o: 2 * _all_numel({"Param": i.get("Param", [])}),
    "momentum": lambda i, o: 3 * _all_numel(
        {"Param": i.get("Param", [])}),
    "adam": lambda i, o: 10 * _all_numel(
        {"Param": i.get("Param", [])}),
    "dropout": lambda i, o: 2 * _all_numel({"X": i.get("X", [])}),
    "reduce_sum": lambda i, o: _all_numel(i),
    "reduce_mean": lambda i, o: _all_numel(i),
    "mean": lambda i, o: _all_numel(i),
    "sum": lambda i, o: _all_numel(i),
}

# grads of the dense ops: backward is two forward-shaped GEMMs/convs
for _t in ("mul", "matmul", "matmul_v2", "conv2d", "depthwise_conv2d"):
    _RULES[_t + "_grad"] = lambda i, o, _f=_RULES[_t]: 2 * _f(i, o)

_COLLECTIVES = {"c_allreduce_sum", "c_allreduce_fused", "c_allgather",
                "c_broadcast", "c_reducescatter", "allreduce",
                "broadcast"}


def program_cost(program, block_idx: int = 0,
                 dynamic_dim: int = 1) -> ProgramCost:
    """Cost every op in the block from declared shapes. ``dynamic_dim``
    substitutes -1 dims (pass the real batch size when calibrating)."""
    block = program.block(block_idx)
    rows: List[OpCost] = []
    for op_idx, op in enumerate(block.ops):
        if op.type in ("feed", "fetch"):
            continue
        ins: Dict[str, List] = {}
        outs: Dict[str, List] = {}
        bytes_in = bytes_out = 0
        for slot in op.input_slots():
            vals = []
            for n in op.input(slot):
                if not n:
                    continue
                s = _shape_of(block, n, dynamic_dim)
                numel = _numel(s)
                vals.append((s, numel))
                bytes_in += numel * _itemsize(block, n)
            if vals:
                ins[slot] = vals
        for slot in op.output_slots():
            vals = []
            for n in op.output(slot):
                if not n:
                    continue
                s = _shape_of(block, n, dynamic_dim)
                numel = _numel(s)
                vals.append((s, numel))
                bytes_out += numel * _itemsize(block, n)
            if vals:
                outs[slot] = vals
        rule = _RULES.get(op.type)
        if rule is not None:
            flops = int(rule(ins, outs))
        elif op.type in _COLLECTIVES:
            # ring allreduce moves ~2N bytes per rank; FLOPs ~N adds
            flops = _all_numel(ins)
            bytes_in *= 2
        elif op.type.endswith("_grad"):
            flops = 2 * max(_all_numel(ins), _all_numel(outs))
        else:
            flops = max(_all_numel(ins), _all_numel(outs))
        rows.append(OpCost(op_idx, op.type, flops, bytes_in, bytes_out))
    return ProgramCost(rows, block_idx, dynamic_dim)


# -- the registered pass ----------------------------------------------------

from .passes import register_analysis_pass  # noqa: E402


@register_analysis_pass("cost-model")
def cost_model_pass(ctx) -> List[Diagnostic]:
    """Flag single ops whose static FLOPs exceed ``PT_STATIC_FLOP_LIMIT``
    (opt-in, silent otherwise) — catches accidentally-quadratic shapes
    before a multi-minute compile does."""
    raw = os.environ.get("PT_STATIC_FLOP_LIMIT")
    if not raw:
        return []
    try:
        limit = int(float(raw))
    except ValueError:
        return []
    if limit <= 0:
        return []
    cost = program_cost(ctx.program)
    block = ctx.program.block(0)
    diags: List[Diagnostic] = []
    for r in cost.rows:
        if r.flops > limit:
            diags.append(ctx.diag(
                Severity.WARNING, "cost-model",
                f"op #{r.op_idx} {r.op_type!r} has static cost "
                f"{r.flops:.3e} FLOPs, over the PT_STATIC_FLOP_LIMIT "
                f"budget {limit:.3e} — check its declared operand "
                f"shapes before paying the compile",
                op=block.ops[r.op_idx], block_idx=0, op_idx=r.op_idx))
    return diags
