"""Program IR: Program / Block / Operator / Variable / Parameter.

Parity: reference python/paddle/fluid/framework.py (Program :2782, Block
:1443, Operator :992, Variable :383, Parameter :3595) and the C++ desc layer
(program_desc.h / block_desc.h / op_desc.h). TPU-first differences:

* One layer instead of two: these classes ARE the desc (serialize straight
  to paddle_tpu.proto.framework_pb2), no C++ mirror to keep in sync.
* Shape/dtype inference runs the op's JAX lowering under jax.eval_shape
  (single source of truth; replaces per-op InferShape).
* Every op gets a program-unique uid attr so randomness replays identically
  between a forward op and its vjp-derived grad op.
"""
from __future__ import annotations

import contextlib
import itertools
import threading
import time
from typing import Any, Dict, List, Optional, Sequence

import numpy as np

import jax
import jax.numpy as jnp

from .proto import framework_pb2 as fpb
from .core import types as core_types
from .core.registry import OPS, ExecContext, OP_UID_ATTR, GRAD_SUFFIX
from .core.types import convert_dtype, dtype_to_np, dtype_to_str
from .observability import tracing as _obs_tracing

__all__ = [
    "Program", "Block", "Operator", "Variable", "Parameter",
    "default_startup_program", "default_main_program", "program_guard",
    "grad_var_name", "unique_name", "name_scope", "in_dygraph_mode",
    "_dygraph_tracer", "dygraph_guard_level",
]

# Sentinel used when abstractly evaluating lowerings over -1 (dynamic) dims.
# Highly composite so merged dims remain multiples of it; mapped back to -1.
_DYN_SENTINEL = 55440


# ---------------------------------------------------------------------------
# unique names
# ---------------------------------------------------------------------------

class _UniqueNameGenerator:
    def __init__(self):
        self._ids: Dict[str, int] = {}
        self._lock = threading.Lock()

    def __call__(self, key: str) -> str:
        with self._lock:
            i = self._ids.get(key, 0)
            self._ids[key] = i + 1
        return f"{key}_{i}"

    def reset(self):
        self._ids.clear()


_name_gen = _UniqueNameGenerator()


class _UniqueNameNS:
    """fluid.unique_name compatible module-like helper."""

    @staticmethod
    def generate(key):
        return _name_gen(key)

    @staticmethod
    def reset():
        _name_gen.reset()
        # also reset the op uid counter so two identically-built
        # programs replay identical per-op randomness (fixed-seed
        # initializers match across builds, like the reference's
        # seeded random kernels)
        _uid_counter[0] = 0

    @staticmethod
    @contextlib.contextmanager
    def guard(new_generator=None):
        global _name_gen
        old = _name_gen
        _name_gen = _UniqueNameGenerator()
        try:
            yield
        finally:
            _name_gen = old


unique_name = _UniqueNameNS()


def grad_var_name(name: str) -> str:
    return name + GRAD_SUFFIX


# ---------------------------------------------------------------------------
# dygraph mode switch (the tracer lives in paddle_tpu.dygraph)
# ---------------------------------------------------------------------------

_dygraph_tracer_holder = threading.local()


def _dygraph_tracer():
    return getattr(_dygraph_tracer_holder, "tracer", None)


def in_dygraph_mode() -> bool:
    return _dygraph_tracer() is not None


@contextlib.contextmanager
def dygraph_guard_level(tracer):
    old = getattr(_dygraph_tracer_holder, "tracer", None)
    _dygraph_tracer_holder.tracer = tracer
    try:
        yield
    finally:
        _dygraph_tracer_holder.tracer = old


# ---------------------------------------------------------------------------
# Variable
# ---------------------------------------------------------------------------

class Variable:
    """Graph-mode symbolic variable (reference framework.py:383)."""

    def __init__(self, block: "Block", name: Optional[str] = None,
                 shape: Optional[Sequence[int]] = None, dtype=None,
                 lod_level: int = 0, persistable: bool = False,
                 stop_gradient: bool = False,
                 kind: int = fpb.VK_DENSE_TENSOR, **kwargs):
        self.block = block
        self.name = name or unique_name.generate("_generated_var")
        self.shape = tuple(int(d) for d in shape) if shape is not None else ()
        self.dtype = convert_dtype(dtype) if dtype is not None else \
            fpb.DT_FLOAT32
        self.lod_level = lod_level
        self.persistable = persistable
        self.stop_gradient = stop_gradient
        self.kind = kind
        self.is_data = kwargs.get("is_data", False)
        self.dim_sharding: List[str] = list(kwargs.get("dim_sharding", ()))
        self.op: Optional[Operator] = None   # producer op (set on append)

    # -- info ---------------------------------------------------------------
    @property
    def persistable_(self):
        return self.persistable

    def astype(self, dtype):
        from .layers import tensor as _t
        return _t.cast(self, dtype)

    @property
    def grad_name(self) -> str:
        return grad_var_name(self.name)

    def to_proto(self) -> fpb.VarDesc:
        p = fpb.VarDesc()
        p.name = self.name
        p.kind = self.kind
        p.persistable = self.persistable
        p.stop_gradient = self.stop_gradient
        p.tensor.data_type = self.dtype
        p.tensor.dims.extend(self.shape)
        p.tensor.lod_level = self.lod_level
        p.dim_sharding.extend(self.dim_sharding)
        return p

    @staticmethod
    def from_proto(block, p: fpb.VarDesc) -> "Variable":
        return Variable(block, name=p.name, shape=tuple(p.tensor.dims),
                        dtype=p.tensor.data_type,
                        lod_level=p.tensor.lod_level,
                        persistable=p.persistable,
                        stop_gradient=p.stop_gradient, kind=p.kind,
                        dim_sharding=list(p.dim_sharding))

    # numpy-ish niceties used by tests/user code
    def __repr__(self):
        return (f"Variable(name={self.name!r}, shape={self.shape}, "
                f"dtype={dtype_to_str(self.dtype)}, "
                f"persistable={self.persistable})")

    __str__ = __repr__

    # operator sugar (graph mode builds ops)
    def _binary(self, other, op, reverse=False):
        from .layers import math_ops
        return math_ops.elementwise_binary_sugar(self, other, op, reverse)

    def __add__(self, o): return self._binary(o, "elementwise_add")
    def __radd__(self, o): return self._binary(o, "elementwise_add", True)
    def __sub__(self, o): return self._binary(o, "elementwise_sub")
    def __rsub__(self, o): return self._binary(o, "elementwise_sub", True)
    def __mul__(self, o): return self._binary(o, "elementwise_mul")
    def __rmul__(self, o): return self._binary(o, "elementwise_mul", True)
    def __truediv__(self, o): return self._binary(o, "elementwise_div")
    def __rtruediv__(self, o): return self._binary(o, "elementwise_div", True)
    def __pow__(self, o): return self._binary(o, "elementwise_pow")
    def __neg__(self):
        from .layers import tensor as _t
        return _t.scale(self, scale=-1.0)


class Parameter(Variable):
    """Trainable persistable variable (reference framework.py:3595)."""

    def __init__(self, block, shape, dtype, **kwargs):
        kwargs.setdefault("persistable", True)
        self.trainable = kwargs.pop("trainable", True)
        self.optimize_attr = kwargs.pop("optimize_attr",
                                        {"learning_rate": 1.0})
        self.regularizer = kwargs.pop("regularizer", None)
        self.gradient_clip_attr = kwargs.pop("gradient_clip_attr", None)
        self.do_model_average = kwargs.pop("do_model_average", None)
        self.initializer = kwargs.pop("initializer", None)
        super().__init__(block, shape=shape, dtype=dtype, **kwargs)
        self.stop_gradient = not self.trainable
        # the name scope it was created under: its update op's too
        self._name_scope = _NAME_SCOPES[-1]


# ---------------------------------------------------------------------------
# Operator
# ---------------------------------------------------------------------------

_uid_counter = [0]


def _next_uid() -> int:
    _uid_counter[0] += 1
    return _uid_counter[0]


class Operator:
    """One op in a block (reference framework.py:992 / op_desc.h:29).

    inputs/outputs map slot name -> list of var names; attrs are python
    values (ints/floats/strs/lists/bools/block indices).
    """

    def __init__(self, block: "Block", type: str,
                 inputs: Optional[Dict[str, Any]] = None,
                 outputs: Optional[Dict[str, Any]] = None,
                 attrs: Optional[Dict[str, Any]] = None):
        self.block = block
        self.type = type
        self._inputs: Dict[str, List[str]] = {}
        self._outputs: Dict[str, List[str]] = {}
        self._attrs: Dict[str, Any] = dict(attrs or {})
        self._attrs.setdefault(OP_UID_ATTR, _next_uid())
        if _NAME_SCOPES[-1]:
            self._attrs.setdefault(OP_NAMESCOPE_ATTR, _NAME_SCOPES[-1])

        def _names(v):
            if v is None:
                return []
            if isinstance(v, (list, tuple)):
                return [x.name if isinstance(x, Variable) else str(x)
                        for x in v]
            return [v.name if isinstance(v, Variable) else str(v)]

        for slot, v in (inputs or {}).items():
            self._inputs[slot] = _names(v)
        for slot, v in (outputs or {}).items():
            names = _names(v)
            self._outputs[slot] = names
            if isinstance(v, Variable):
                v.op = self
            elif isinstance(v, (list, tuple)):
                for x in v:
                    if isinstance(x, Variable):
                        x.op = self

    # -- registry-facing view ----------------------------------------------
    def input(self, slot: str) -> List[str]:
        return self._inputs.get(slot, [])

    def output(self, slot: str) -> List[str]:
        return self._outputs.get(slot, [])

    def input_slots(self):
        return list(self._inputs)

    def output_slots(self):
        return list(self._outputs)

    def attr(self, name: str, default=None):
        return self._attrs.get(name, default)

    def has_attr(self, name: str) -> bool:
        return name in self._attrs

    def set_attr(self, name, val):
        self._attrs[name] = val
        self.block.program._bump_version()

    def _all_attrs(self):
        return self._attrs.items()

    @property
    def input_arg_names(self):
        return [n for ns in self._inputs.values() for n in ns]

    @property
    def output_arg_names(self):
        return [n for ns in self._outputs.values() for n in ns]

    @property
    def attr_names(self):
        return [a for a in self._attrs if not a.startswith("__")]

    def all_attrs(self):
        return {k: v for k, v in self._attrs.items()
                if not k.startswith("__")}

    def __repr__(self):
        ins = {k: v for k, v in self._inputs.items()}
        outs = {k: v for k, v in self._outputs.items()}
        return f"Op({self.type}, in={ins}, out={outs})"

    # -- serialization ------------------------------------------------------
    def to_proto(self) -> fpb.OpDesc:
        p = fpb.OpDesc()
        p.type = self.type
        for slot, names in self._inputs.items():
            s = p.inputs.add(); s.parameter = slot; s.arguments.extend(names)
        for slot, names in self._outputs.items():
            s = p.outputs.add(); s.parameter = slot; s.arguments.extend(names)
        for name, val in self._attrs.items():
            a = p.attrs.add()
            a.name = name
            _encode_attr(a, val)
        return p

    @staticmethod
    def from_proto(block, p: fpb.OpDesc) -> "Operator":
        inputs = {s.parameter: list(s.arguments) for s in p.inputs}
        outputs = {s.parameter: list(s.arguments) for s in p.outputs}
        attrs = {a.name: _decode_attr(a) for a in p.attrs}
        op = Operator.__new__(Operator)
        op.block = block
        op.type = p.type
        op._inputs = inputs
        op._outputs = outputs
        op._attrs = attrs
        return op


def _encode_attr(a: fpb.Attr, val):
    if isinstance(val, bool):
        a.type = fpb.AT_BOOL; a.b = val
    elif isinstance(val, (int, np.integer)):
        a.type = fpb.AT_LONG; a.i = int(val)
    elif isinstance(val, float):
        a.type = fpb.AT_FLOAT; a.d = val; a.f = val
    elif isinstance(val, str):
        a.type = fpb.AT_STRING; a.s = val
    elif isinstance(val, (list, tuple)):
        if all(isinstance(x, bool) for x in val) and val:
            a.type = fpb.AT_BOOLS; a.bools.extend(val)
        elif all(isinstance(x, (int, np.integer)) for x in val):
            a.type = fpb.AT_LONGS; a.ints.extend(int(x) for x in val)
        elif all(isinstance(x, float) for x in val):
            a.type = fpb.AT_FLOATS; a.floats.extend(val)
        elif all(isinstance(x, str) for x in val):
            a.type = fpb.AT_STRINGS; a.strings.extend(val)
        else:
            raise TypeError(f"unsupported list attr: {val!r}")
    elif isinstance(val, Block):
        a.type = fpb.AT_BLOCK; a.block_idx = val.idx
    elif isinstance(val, _BlockRef):
        # round-tripping a deserialized program (clone/prune/save)
        a.type = fpb.AT_BLOCK; a.block_idx = val.idx
    elif val is None:
        a.type = fpb.AT_NONE
    else:
        raise TypeError(f"unsupported attr type: {type(val)}")


def _decode_attr(a: fpb.Attr):
    t = a.type
    if t == fpb.AT_BOOL:
        return a.b
    if t in (fpb.AT_INT, fpb.AT_LONG):
        return int(a.i)
    if t == fpb.AT_FLOAT:
        return float(a.d) if a.d else float(a.f)
    if t == fpb.AT_STRING:
        return a.s
    if t in (fpb.AT_INTS, fpb.AT_LONGS):
        return [int(x) for x in a.ints]
    if t == fpb.AT_FLOATS:
        return list(a.floats)
    if t == fpb.AT_STRINGS:
        return list(a.strings)
    if t == fpb.AT_BOOLS:
        return list(a.bools)
    if t == fpb.AT_BLOCK:
        return _BlockRef(a.block_idx)
    if t == fpb.AT_BLOCKS:
        return [_BlockRef(i) for i in a.block_idxs]
    return None


class _BlockRef:
    """Deserialized block attr: resolved lazily against the program."""

    __slots__ = ("idx",)

    def __init__(self, idx):
        self.idx = idx


# ---------------------------------------------------------------------------
# Block
# ---------------------------------------------------------------------------

class Block:
    """Ordered ops + named vars (reference framework.py:1443)."""

    def __init__(self, program: "Program", idx: int, parent_idx: int = -1):
        self.program = program
        self.idx = idx
        self.parent_idx = parent_idx
        self.forward_block_idx = -1
        self.vars: Dict[str, Variable] = {}
        self.ops: List[Operator] = []

    @property
    def parent(self) -> Optional["Block"]:
        return (self.program.block(self.parent_idx)
                if self.parent_idx >= 0 else None)

    # -- vars ---------------------------------------------------------------
    def create_var(self, **kwargs) -> Variable:
        name = kwargs.get("name") or unique_name.generate("_generated_var")
        kwargs["name"] = name
        if name in self.vars:
            return self.vars[name]
        v = Variable(self, **kwargs)
        self.vars[name] = v
        self.program._bump_version()
        return v

    def create_parameter(self, **kwargs) -> Parameter:
        name = kwargs.get("name") or unique_name.generate("_param")
        kwargs["name"] = name
        p = Parameter(self, kwargs.pop("shape"), kwargs.pop("dtype"),
                      **kwargs)
        # parameters live in block 0 (reference: global block)
        gb = self.program.global_block()
        gb.vars[name] = p
        p.block = gb
        self.program._bump_version()
        return p

    def var(self, name: str) -> Variable:
        v = self._find_var_recursive(name)
        if v is None:
            raise ValueError(f"variable {name!r} not found in block "
                             f"{self.idx}")
        return v

    def has_var(self, name: str) -> bool:
        return name in self.vars

    def _find_var_recursive(self, name: str) -> Optional[Variable]:
        b = self
        while b is not None:
            if name in b.vars:
                return b.vars[name]
            b = b.parent
        return None

    def all_parameters(self) -> List[Parameter]:
        return [v for v in self.vars.values() if isinstance(v, Parameter)]

    # -- ops ----------------------------------------------------------------
    def append_op(self, type: str, inputs=None, outputs=None, attrs=None,
                  infer_shape: bool = True) -> Operator:
        op = Operator(self, type, inputs, outputs, attrs)
        self.ops.append(op)
        self.program._bump_version()
        if infer_shape:
            t0 = time.perf_counter()
            try:
                self._infer_op_shapes(op)
            except NotImplementedError:
                pass
            # build-time shape inference has a counter, not a span: a
            # program appends thousands of ops (tracing.build_totals)
            _obs_tracing.note_infer_shapes(type, time.perf_counter() - t0)
        return op

    def _prepend_op(self, type: str, inputs=None, outputs=None, attrs=None):
        op = Operator(self, type, inputs, outputs, attrs)
        self.ops.insert(0, op)
        self.program._bump_version()
        return op

    def remove_op(self, index: int):
        del self.ops[index]
        self.program._bump_version()

    # -- build-time shape inference via abstract eval -----------------------
    def _infer_op_shapes(self, op: Operator):
        """Run the lowering under jax.eval_shape with -1 dims replaced by a
        sentinel; write inferred shapes/dtypes onto output Variables."""
        info = OPS.get(op.type)
        if info.infer_shape is not None:
            info.infer_shape(op, self)
            return

        env: Dict[str, Any] = {}
        for slot in op.input_slots():
            for name in op.input(slot):
                if name in env:
                    continue
                v = self._find_var_recursive(name)
                if v is None:
                    raise NotImplementedError(f"unknown input var {name}")
                shape = tuple(_DYN_SENTINEL if d == -1 else d
                              for d in v.shape)
                env[name] = jax.ShapeDtypeStruct(shape, dtype_to_np(v.dtype))

        out_names = [n for slot in op.output_slots()
                     for n in op.output(slot)]

        def _run(abstract_env):
            local = dict(abstract_env)
            ctx = ExecContext(op, local, rng_ctx=None, block_runner=None)
            info.lowering(ctx)
            return [local.get(n) for n in out_names]

        try:
            outs = jax.eval_shape(_run, env)
        except Exception:
            # data-dependent or unsupported at build time: leave shapes as-is
            return
        for name, aval in zip(out_names, outs):
            if aval is None:
                continue
            v = self._find_var_recursive(name)
            if v is None:
                continue
            shape = tuple(-1 if (d >= _DYN_SENTINEL and d % _DYN_SENTINEL == 0)
                          else int(d) for d in aval.shape)
            v.shape = shape
            v.dtype = convert_dtype(aval.dtype)

    # -- serialization ------------------------------------------------------
    def to_proto(self) -> fpb.BlockDesc:
        p = fpb.BlockDesc()
        p.idx = self.idx
        p.parent_idx = self.parent_idx
        p.forward_block_idx = self.forward_block_idx
        for v in self.vars.values():
            p.vars.append(v.to_proto())
        for op in self.ops:
            p.ops.append(op.to_proto())
        return p

    def __repr__(self):
        return f"Block(idx={self.idx}, ops={[o.type for o in self.ops]})"


# ---------------------------------------------------------------------------
# Program
# ---------------------------------------------------------------------------

class Program:
    """A serializable program: list of blocks (reference framework.py:2782).

    Maintains a version counter used by the executor's compile cache.
    """

    # monotonically increasing program ids: id(self) can be reused after a
    # Program is GC'd, which would let a stale Engine cache entry collide
    # with a fresh Program of the same CPython address.
    _next_program_uid = itertools.count()

    def __init__(self):
        self.blocks: List[Block] = [Block(self, 0)]
        self.current_block_idx = 0
        self._seed = 0
        self._uid = next(Program._next_program_uid)
        self._version = 0
        self._is_test = False
        self.op_role = "forward"
        # distribution annotations consumed by CompiledProgram
        self._mesh_axes: Dict[str, int] = {}

    # -- versioning (compile-cache key) ------------------------------------
    def _bump_version(self):
        self._version += 1

    @property
    def fingerprint(self):
        return (self._uid, self._version)

    # -- blocks -------------------------------------------------------------
    def global_block(self) -> Block:
        return self.blocks[0]

    def block(self, idx: int) -> Block:
        return self.blocks[idx]

    def current_block(self) -> Block:
        return self.blocks[self.current_block_idx]

    def _create_block(self, parent_idx: Optional[int] = None) -> Block:
        parent = self.current_block_idx if parent_idx is None else parent_idx
        b = Block(self, len(self.blocks), parent)
        self.blocks.append(b)
        self.current_block_idx = b.idx
        self._bump_version()
        return b

    def _rollback(self):
        self.current_block_idx = self.current_block().parent_idx

    @property
    def num_blocks(self) -> int:
        return len(self.blocks)

    # -- seeds --------------------------------------------------------------
    @property
    def random_seed(self):
        return self._seed

    @random_seed.setter
    def random_seed(self, s):
        self._seed = int(s)
        self._bump_version()

    # -- clone / prune ------------------------------------------------------
    def clone(self, for_test: bool = False) -> "Program":
        p = Program.from_proto(self.to_proto())
        p._seed = self._seed
        # the proto schema has no parameter flag (same as the reference's
        # framework.proto), so the round-trip demotes Parameters to plain
        # Variables; restore the subclass so all_parameters() and passes
        # that key off parameter-ness work on clones (the reference clone
        # copies parameter info explicitly, framework.py:2881)
        for sb, db in zip(self.blocks, p.blocks):
            for name, v in sb.vars.items():
                if isinstance(v, Parameter) and name in db.vars:
                    old = db.vars[name]
                    param = Parameter(
                        db, shape=old.shape, dtype=old.dtype, name=name,
                        lod_level=old.lod_level,
                        persistable=old.persistable,
                        trainable=v.trainable,
                        optimize_attr=dict(v.optimize_attr),
                        regularizer=v.regularizer,
                        gradient_clip_attr=v.gradient_clip_attr,
                        do_model_average=v.do_model_average)
                    param.kind = old.kind
                    param.dim_sharding = list(old.dim_sharding)
                    db.vars[name] = param
        if for_test:
            p._is_test = True
            for b in p.blocks:
                for op in b.ops:
                    if op.has_attr("is_test"):
                        op._attrs["is_test"] = True
                    # dropout/batch_norm style train-only behavior keys off
                    # is_test; mark globally too
        p._bump_version()
        return p

    def list_vars(self):
        for b in self.blocks:
            yield from b.vars.values()

    def all_parameters(self):
        return self.global_block().all_parameters()

    # -- serialization ------------------------------------------------------
    def to_proto(self) -> fpb.ProgramDesc:
        p = fpb.ProgramDesc()
        p.version = 1
        for b in self.blocks:
            p.blocks.append(b.to_proto())
        return p

    def serialize_to_string(self) -> bytes:
        return self.to_proto().SerializeToString()

    def to_string(self, throw_on_error=True, with_details=False):
        """Human-readable program text (reference Program.to_string)."""
        return str(self.to_proto())

    @staticmethod
    def parse_from_string(s: bytes) -> "Program":
        p = fpb.ProgramDesc()
        p.ParseFromString(s)
        return Program.from_proto(p)

    @staticmethod
    def from_proto(proto: fpb.ProgramDesc) -> "Program":
        prog = Program()
        prog.blocks = []
        for bp in proto.blocks:
            b = Block(prog, bp.idx, bp.parent_idx)
            b.forward_block_idx = bp.forward_block_idx
            for vp in bp.vars:
                b.vars[vp.name] = Variable.from_proto(b, vp)
            prog.blocks.append(b)
        # second pass: ops (need vars present)
        for bp, b in zip(proto.blocks, prog.blocks):
            for opp in bp.ops:
                op = Operator.from_proto(b, opp)
                b.ops.append(op)
        if not prog.blocks:
            prog.blocks = [Block(prog, 0)]
        prog.current_block_idx = 0
        prog._bump_version()
        return prog

    def __repr__(self):
        return (f"Program(blocks={len(self.blocks)}, "
                f"ops={[o.type for o in self.global_block().ops]})")


# ---------------------------------------------------------------------------
# default programs + guards (reference framework.py:3690-3850)
# ---------------------------------------------------------------------------

_main_program = Program()
_startup_program = Program()


def default_main_program() -> Program:
    return _main_program


def default_startup_program() -> Program:
    return _startup_program


def switch_main_program(p: Program) -> Program:
    global _main_program
    old, _main_program = _main_program, p
    return old


def switch_startup_program(p: Program) -> Program:
    global _startup_program
    old, _startup_program = _startup_program, p
    return old


@contextlib.contextmanager
def program_guard(main_program: Program,
                  startup_program: Optional[Program] = None):
    old_main = switch_main_program(main_program)
    old_startup = None
    if startup_program is not None:
        old_startup = switch_startup_program(startup_program)
    try:
        yield
    finally:
        switch_main_program(old_main)
        if old_startup is not None:
            switch_startup_program(old_startup)


# full paths of the open name scopes, innermost last ("enc_0/ffn/")
_NAME_SCOPES: List[str] = [""]
OP_NAMESCOPE_ATTR = "op_namescope"


@contextlib.contextmanager
def name_scope(prefix: str):
    """Ops appended inside carry the scope's path as their
    `op_namescope` attribute (reference framework.py name_scope); so do
    the grad ops made from them (they copy the forward op's attributes)
    and the update op of a parameter created inside. The compiled
    step's HLO metadata names each op
    `<op_namescope><op_role>/<op type>` (core/engine.py)."""
    with _name_scope_path(f"{_NAME_SCOPES[-1]}{prefix}/" if prefix
                          else ""):
        yield


@contextlib.contextmanager
def _name_scope_path(path: str):
    """Open `path` whole (a parameter's own scope, for its update op);
    an empty path leaves the current scope open."""
    _NAME_SCOPES.append(path or _NAME_SCOPES[-1])
    try:
        yield
    finally:
        _NAME_SCOPES.pop()
