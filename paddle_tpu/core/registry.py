"""Operator registry: one registration per op gives lowering (JAX), shape
inference (via abstract eval of the lowering), and gradient definition.

Parity: reference op registry / OpInfo
(/root/reference/paddle/fluid/framework/op_registry.h:197-268, op_info.h:80)
and GradOpDescMaker (grad_op_desc_maker.h). TPU-first twists:

* An op "kernel" is a pure JAX lowering traced into whole-block XLA
  computations — there is no per-op dispatch at run time.
* Shape/dtype inference does not exist as a separate contract: we abstractly
  evaluate the lowering with jax.eval_shape, so the lowering is the single
  source of truth (replaces InferShape/InferVarType,
  reference operator.cc:935-993).
* The default gradient is derived mechanically from the forward lowering via
  jax.vjp — one grad registry serves graph mode (append_backward) and
  dygraph (tracer tape), preserving the reference's single-grad-source
  property (reference backward.py:431 + imperative/tracer.cc:239).
* Randomness is explicit: ops draw keys derived from a per-op uid and the
  step's threaded PRNG state, so forward and vjp-recomputed forward see
  identical randomness inside one compiled step.
"""
from __future__ import annotations

import functools
from typing import Any, Callable, Dict, List, Optional, Sequence, Tuple

import numpy as np

import jax
import jax.numpy as jnp

from . import amp as _amp

# Attr names used internally by the framework (filtered from user attrs).
OP_UID_ATTR = "__op_uid__"
FWD_TYPE_ATTR = "__fwd_type__"
GRAD_SUFFIX = "@GRAD"
RENAME_SEP = "@RENAME@"


class OpInfo:
    __slots__ = ("type", "lowering", "grad_maker", "no_grad_slots",
                 "infer_shape", "intermediate_outputs", "is_grad_op",
                 "stateful_outputs")

    def __init__(self, type, lowering, grad_maker=None, no_grad_slots=(),
                 infer_shape=None, intermediate_outputs=(), is_grad_op=False,
                 stateful_outputs=()):
        self.type = type
        self.lowering = lowering
        self.grad_maker = grad_maker
        self.no_grad_slots = frozenset(no_grad_slots)
        self.infer_shape = infer_shape
        # outputs only consumed by this op's grad (e.g. softmax saved output)
        self.intermediate_outputs = frozenset(intermediate_outputs)
        self.is_grad_op = is_grad_op
        self.stateful_outputs = frozenset(stateful_outputs)


class OpInfoMap:
    """Global op registry (reference OpInfoMap, op_info.h:80)."""

    def __init__(self):
        self._map: Dict[str, OpInfo] = {}

    def insert(self, info: OpInfo):
        if info.type in self._map:
            raise ValueError(f"op '{info.type}' registered twice")
        self._map[info.type] = info

    def get(self, op_type: str) -> OpInfo:
        try:
            return self._map[op_type]
        except KeyError:
            raise NotImplementedError(
                f"op '{op_type}' is not registered; registered ops: "
                f"{len(self._map)}") from None

    def has(self, op_type: str) -> bool:
        return op_type in self._map

    def types(self):
        return sorted(self._map)


OPS = OpInfoMap()


def register_op(op_type: str, *, no_grad_slots: Sequence[str] = (),
                grad_maker=None, infer_shape=None,
                intermediate_outputs: Sequence[str] = (),
                stateful_outputs: Sequence[str] = ()):
    """Decorator registering a forward lowering.

    The lowering has signature ``lowering(ctx)`` where ``ctx`` is an
    ExecContext; it reads inputs/attrs and sets outputs. Registration also
    creates ``<type>_grad`` with the generic vjp lowering unless the op
    opts out via ``grad_maker=None`` explicitly passed as False-y sentinel
    or registers its own grad op.
    """
    def deco(fn):
        info = OpInfo(op_type, fn, grad_maker=grad_maker,
                      no_grad_slots=no_grad_slots, infer_shape=infer_shape,
                      intermediate_outputs=intermediate_outputs,
                      stateful_outputs=stateful_outputs)
        OPS.insert(info)
        grad_type = op_type + "_grad"
        if not OPS.has(grad_type):
            OPS.insert(OpInfo(grad_type, _make_generic_grad_lowering(op_type),
                              is_grad_op=True))
        return fn
    return deco


def register_no_grad_op(op_type: str, **kw):
    """Register an op that has no gradient (metrics, readers, assign-likes)."""
    def deco(fn):
        OPS.insert(OpInfo(op_type, fn, **kw))
        return fn
    return deco


def override_grad_lowering(fwd_type: str):
    """Replace the auto-derived `<fwd_type>_grad` lowering with a custom
    one (the analog of a hand-written grad kernel next to the reference's
    GradOpMaker). The custom lowering can delegate to the generic vjp via
    `generic_grad_lowering(fwd_type)(ctx)`."""
    def deco(fn):
        OPS.get(fwd_type + "_grad").lowering = fn
        return fn
    return deco


def generic_grad_lowering(fwd_type: str):
    return _make_generic_grad_lowering(fwd_type)


class ExecContext:
    """Per-op view during block tracing (reference ExecutionContext,
    operator.h:230). Values are JAX tracers/arrays; `env` maps var name to
    value. Missing optional inputs return None.

    Under an active amp_guard, input()/inputs()/set_output() apply the
    central mixed-precision policy (core/amp.py op_mode/cast_in/cast_out)
    — white MXU ops read f32 operands as bf16 (so their result dtype,
    derived from inputs, stays bf16), gray ops follow an already-reduced
    input, black ops read reduced floats as f32. This is the trace-time
    analog of the reference's cast-insertion pass
    (contrib/mixed_precision/fp16_utils.py:103)."""

    __slots__ = ("op", "env", "rng_ctx", "block_runner", "lod_env",
                 "_amp_mode", "_amp_follow")

    def __init__(self, op, env, rng_ctx=None, block_runner=None,
                 lod_env=None):
        self.op = op          # framework.Operator-like (inputs/outputs/attrs)
        self.env = env
        self.rng_ctx = rng_ctx
        self.block_runner = block_runner  # callable for control-flow sub-blocks
        # host-side LoD metadata: var name -> list of offset vectors. Static
        # per trace (part of the executor's compile-cache key), the
        # XLA-friendly encoding of ragged batches.
        self.lod_env = lod_env if lod_env is not None else {}
        self._amp_mode = _amp.op_mode(op.type)
        self._amp_follow = False
        if self._amp_mode == "gray":
            dt = _amp.amp_dtype()
            slots = getattr(op, "input_slots", None)
            for slot in (slots() if slots else ()):
                for n in op.input(slot):
                    v = env.get(n) if hasattr(env, "get") else None
                    if v is not None and \
                            getattr(v, "dtype", None) == dt:
                        self._amp_follow = True
                        break
                if self._amp_follow:
                    break

    # ---- inputs / outputs -------------------------------------------------
    def input_names(self, slot: str) -> List[str]:
        return self.op.input(slot)

    def output_names(self, slot: str) -> List[str]:
        return self.op.output(slot)

    def has_input(self, slot: str) -> bool:
        names = self.op.input(slot)
        return bool(names)

    def has_output(self, slot: str) -> bool:
        return bool(self.op.output(slot))

    def input(self, slot: str):
        names = self.op.input(slot)
        if not names:
            return None
        if len(names) != 1:
            raise ValueError(
                f"op {self.op.type} input slot {slot} is multi-arg; "
                f"use inputs()")
        v = self.env[names[0]]
        if self._amp_mode is not None:
            v = _amp.cast_in(self._amp_mode, v, self._amp_follow)
        return v

    def inputs(self, slot: str):
        vals = [self.env[n] for n in self.op.input(slot)]
        if self._amp_mode is not None:
            vals = [_amp.cast_in(self._amp_mode, v, self._amp_follow)
                    for v in vals]
        return vals

    def set_output(self, slot: str, value):
        names = self.op.output(slot)
        if not names:
            return  # optional output not bound
        assert len(names) == 1, f"{self.op.type}.{slot} is multi-arg"
        if self._amp_mode is not None:
            value = _amp.cast_out(self._amp_mode, value)
        self.env[names[0]] = value

    def set_outputs(self, slot: str, values):
        names = self.op.output(slot)
        assert len(names) == len(values), (
            f"{self.op.type}.{slot}: {len(names)} names vs "
            f"{len(values)} values")
        if self._amp_mode is not None:
            values = [_amp.cast_out(self._amp_mode, v) for v in values]
        for n, v in zip(names, values):
            self.env[n] = v

    # ---- attrs ------------------------------------------------------------
    def attr(self, name: str, default=None):
        return self.op.attr(name, default)

    def has_attr(self, name: str) -> bool:
        return self.op.has_attr(name)

    # ---- LoD (ragged metadata, host side) --------------------------------
    def get_lod(self, slot_or_name: str):
        names = self.op.input(slot_or_name)
        name = names[0] if names else slot_or_name
        return self.lod_env.get(name, [])

    def set_lod(self, slot_or_name: str, lod):
        names = self.op.output(slot_or_name)
        name = names[0] if names else slot_or_name
        self.lod_env[name] = [list(map(int, lv)) for lv in lod]

    # ---- randomness -------------------------------------------------------
    def rng(self) -> jax.Array:
        """Deterministic per-op key: fold the op uid (shared between a
        forward op and its grad op) into the step key, honoring a nonzero
        `seed` attr the way reference random kernels do."""
        uid = self.op.attr(OP_UID_ATTR, 0)
        seed = self.op.attr("seed", 0) or 0
        if self.rng_ctx is None or seed:
            base = jax.random.PRNGKey(seed)
        else:
            base = self.rng_ctx.step_key()
        return jax.random.fold_in(base, uid)


class _RngCtx:
    """Carries the step's base PRNG key during tracing."""

    __slots__ = ("key",)

    def __init__(self, key):
        self.key = key

    def step_key(self):
        return self.key


# ---------------------------------------------------------------------------
# Generic gradient via jax.vjp of the forward lowering
# ---------------------------------------------------------------------------

class _SlotView:
    """Minimal op-view used to re-run a forward lowering inside a grad
    lowering: same attrs, inputs/outputs remapped to local names."""

    __slots__ = ("type", "_inputs", "_outputs", "_attrs")

    def __init__(self, type, inputs, outputs, attrs):
        self.type = type
        self._inputs = inputs
        self._outputs = outputs
        self._attrs = attrs

    def input(self, slot):
        return self._inputs.get(slot, [])

    def output(self, slot):
        return self._outputs.get(slot, [])

    def input_slots(self):
        return list(self._inputs)

    def output_slots(self):
        return list(self._outputs)

    def attr(self, name, default=None):
        return self._attrs.get(name, default)

    def has_attr(self, name):
        return name in self._attrs


def _zeros_like_abstract(v):
    return jnp.zeros(jnp.shape(v), jnp.result_type(v))


def _make_generic_grad_lowering(fwd_type: str):
    """Build the lowering for `<fwd_type>_grad`.

    The grad op's desc (built by the default grad maker in backward.py) binds:
      inputs:  every forward input slot S -> same names; every forward output
               slot O -> fwd output names; every O+"@GRAD" -> cotangents
               (possibly missing -> zero).
      outputs: S+"@GRAD" for each forward input slot needing grad.
      attrs:   copy of the forward attrs (incl. the forward op uid so rng
               replays identically).
    The lowering reconstructs the pure forward function of the
    differentiated inputs and applies jax.vjp. For a forward made of plain
    XLA ops, XLA CSE dedupes the recomputation against the forward pass
    inside the same compiled step. It does NOT merge custom calls (Pallas /
    Mosaic kernels): a kernel forward recomputed here runs twice. An op
    whose forward is a custom call declares an intermediate output that
    carries what its backward needs and overrides this lowering to read it
    (fused_attention's SoftmaxLse, ops/fused.py).
    """

    def grad_lowering(ctx: ExecContext):
        fwd_info = OPS.get(fwd_type)
        op = ctx.op

        # forward output slots = grad-op input slots that carry "@GRAD"
        out_slots = sorted({s[:-len(GRAD_SUFFIX)] for s in op.input_slots()
                            if s.endswith(GRAD_SUFFIX)})
        # forward input slots = every non-@GRAD grad-op input that is not a
        # forward output slot
        fwd_in_slots = [s for s in op.input_slots()
                        if not s.endswith(GRAD_SUFFIX) and s not in out_slots]
        # differentiated slots: those with a bound X@GRAD output
        diff_slots = [s for s in fwd_in_slots if op.output(s + GRAD_SUFFIX)]
        const_slots = [s for s in fwd_in_slots if s not in diff_slots]

        diff_vals = {s: ctx.inputs(s) for s in diff_slots}
        const_vals = {s: ctx.inputs(s) for s in const_slots}
        flat_names = [(s, i) for s in diff_slots
                      for i in range(len(diff_vals[s]))]

        def fwd_fn(*flat_args):
            local_env = {}
            local_lod = {}
            inputs_map = {}
            for s in const_slots:
                names = [f"__c_{s}_{i}" for i in range(len(const_vals[s]))]
                inputs_map[s] = names
                for n, v, orig in zip(names, const_vals[s], op.input(s)):
                    local_env[n] = v
                    if orig in ctx.lod_env:
                        local_lod[n] = ctx.lod_env[orig]
            for (s, i), v in zip(flat_names, flat_args):
                inputs_map.setdefault(s, [None] * len(diff_vals[s]))
                name = f"__d_{s}_{i}"
                inputs_map[s][i] = name
                local_env[name] = v
                orig = op.input(s)[i]
                if orig in ctx.lod_env:
                    local_lod[name] = ctx.lod_env[orig]
            outputs_map = {}
            for s in out_slots:
                n_out = max(len(op.input(s)), 1)
                outputs_map[s] = [f"__o_{s}_{i}" for i in range(n_out)]
            view = _SlotView(fwd_type, inputs_map, outputs_map,
                             dict(op._all_attrs()))
            sub = ExecContext(view, local_env, ctx.rng_ctx,
                              ctx.block_runner, local_lod)
            fwd_info.lowering(sub)
            outs = []
            for s in out_slots:
                for n in outputs_map[s]:
                    outs.append(local_env.get(n))
            return tuple(outs)

        flat_primals = [diff_vals[s][i] for (s, i) in flat_names]
        primals_out, vjp_fn = jax.vjp(fwd_fn, *flat_primals)

        # cotangents aligned with fwd_fn outputs
        cts = []
        k = 0
        for s in out_slots:
            n_out = len(op.input(s)) if op.input(s) else 1
            g_names = op.input(s + GRAD_SUFFIX)
            for i in range(n_out):
                primal = primals_out[k]; k += 1
                if primal is None:
                    # optional output the forward never bound (e.g.
                    # sequence_pool's MaxIndex outside MAX mode):
                    # cotangent structure must mirror it
                    cts.append(None)
                    continue
                if i < len(g_names) and g_names[i] in ctx.env and \
                        ctx.env[g_names[i]] is not None:
                    g = ctx.env[g_names[i]]
                    if jnp.result_type(g) != jnp.result_type(primal):
                        g = g.astype(jnp.result_type(primal))
                    cts.append(g)
                else:
                    cts.append(_zeros_like_abstract(primal))
        grads = vjp_fn(tuple(cts))

        # scatter grads back to X@GRAD outputs
        by_slot: Dict[str, list] = {}
        for (s, i), g in zip(flat_names, grads):
            by_slot.setdefault(s, []).append(g)
        for s in diff_slots:
            names = op.output(s + GRAD_SUFFIX)
            vals = by_slot.get(s, [])
            for n, v in zip(names, vals):
                if n:  # empty name = grad not needed
                    ctx.env[n] = v

    grad_lowering.__name__ = f"{fwd_type}_grad_lowering"
    grad_lowering._generic_vjp_of = fwd_type
    return grad_lowering


# ---------------------------------------------------------------------------
# trace-time activation sharding hook (multi-axis SPMD; ops/ lowerings)
# ---------------------------------------------------------------------------

def shard_hint(ctx: ExecContext, slot: str, value,
               weight_slot: Optional[str] = None):
    """Pin an op's ``slot`` output with the engine's activation-scope
    sharding constraint (parallel/strategy.py), identity when no scope
    is live. With ``weight_slot``, the constraint is the Megatron
    dispatch derived from that weight's PartitionSpec (column-split
    keeps tp on the output, row-split pins the all-reduce point);
    otherwise it is the name-based/batch-dim pin. The strategy module
    is consulted only if already imported — no import cycle, zero cost
    on the single-device path."""
    import sys
    strat_mod = sys.modules.get("paddle_tpu.parallel.strategy")
    if strat_mod is None or strat_mod.activation_scope() is None:
        return value
    out_names = ctx.op.output(slot)
    out_name = out_names[0] if out_names else ""
    if weight_slot:
        w_names = ctx.op.input(weight_slot)
        w_name = w_names[0] if w_names else None
        w = ctx.env.get(w_name) if w_name and \
            hasattr(ctx.env, "get") else None
        return strat_mod.constrain_matmul(
            out_name, w_name, getattr(w, "shape", None), value)
    return strat_mod.constrain_activation(out_name, value)


_SHARD_HINT_SLOTS: Dict[str, Tuple[str, ...]] = {}


def shard_hinted_slots(op_type: str) -> Tuple[str, ...]:
    """Output slots whose registered lowering routes through
    :func:`shard_hint`, read off the lowering's own source (AST walk
    for ``shard_hint(ctx, "<slot>", ...)`` calls).

    This is the conformance verifier's ground truth for which ops
    attach sharding constraints (analysis/conformance.py): discovering
    the call sites statically means a new hinted lowering is tracked
    the moment it is written, with no parallel registry to forget.
    Returns () for unknown ops or unreadable source; memoized per op
    type (lowerings are module-level functions, fixed after import).
    """
    hit = _SHARD_HINT_SLOTS.get(op_type)
    if hit is not None:
        return hit
    slots: List[str] = []
    try:
        import ast
        import inspect
        import textwrap
        fn = OPS.get(op_type).lowering
        tree = ast.parse(textwrap.dedent(inspect.getsource(fn)))
        for node in ast.walk(tree):
            if not isinstance(node, ast.Call):
                continue
            f = node.func
            name = f.id if isinstance(f, ast.Name) \
                else getattr(f, "attr", "")
            if name == "shard_hint" and len(node.args) >= 2:
                s = node.args[1]
                if isinstance(s, ast.Constant) and \
                        isinstance(s.value, str):
                    slots.append(s.value)
    except Exception:
        slots = []
    out = tuple(dict.fromkeys(slots))
    _SHARD_HINT_SLOTS[op_type] = out
    return out
