"""Python-constructed autodiff: append_backward.

Parity: reference python/paddle/fluid/backward.py (append_backward :558,
grad-op creation via the registered grad makers :431, repeated-grad
accumulation _addup_repetitive_outputs_ :135, no-grad pruning :211).
TPU-native: the default grad op is `<type>_grad` whose lowering applies
jax.vjp to the forward lowering (core/registry.py), so every registered op
is differentiable from one definition; custom grad makers can still override
per op. The same registry drives dygraph's tape (dygraph/base.py), keeping
the reference's single-grad-source property.
"""
from __future__ import annotations

from typing import Dict, List, Optional, Sequence, Set

from . import framework
from .core.registry import OPS, GRAD_SUFFIX, OP_UID_ATTR
from .core.types import is_float_dtype
from .observability import tracing as _obs_tracing

__all__ = ["append_backward", "gradients"]

OP_ROLE_ATTR = "op_role"


def _grad_name(name: str) -> str:
    return name + GRAD_SUFFIX


class _GradAccumulator:
    """Tracks grad contributions per forward var; finalizes with sum ops."""

    def __init__(self, block):
        self.block = block
        self.contribs: Dict[str, List[str]] = {}
        self.finalized: Dict[str, str] = {}

    def add(self, var_name: str) -> str:
        """Reserve a fresh output name for a new grad contribution."""
        lst = self.contribs.setdefault(var_name, [])
        if not lst:
            out = _grad_name(var_name)
        else:
            out = f"{_grad_name(var_name)}@RENAME@{len(lst)}"
        lst.append(out)
        self.finalized.pop(var_name, None)
        return out

    def has(self, var_name: str) -> bool:
        return bool(self.contribs.get(var_name))

    def final(self, var_name: str) -> Optional[str]:
        """Name holding the fully-accumulated grad of var_name (inserting a
        sum op on first request if there were multiple contributions)."""
        if var_name in self.finalized:
            return self.finalized[var_name]
        lst = self.contribs.get(var_name)
        if not lst:
            return None
        gname = _grad_name(var_name)
        if len(lst) > 1:
            fwd = self.block._find_var_recursive(var_name)
            self.block.create_var(name=gname, shape=fwd.shape,
                                  dtype=fwd.dtype)
            self.block.append_op(
                "sum", inputs={"X": list(lst)}, outputs={"Out": gname},
                attrs={OP_ROLE_ATTR: "backward"})
        self.finalized[var_name] = gname
        return gname


def _create_grad_var(block, fwd_name: str, grad_name: str):
    fwd = block._find_var_recursive(fwd_name)
    if block.has_var(grad_name):
        return block.vars[grad_name]
    return block.create_var(
        name=grad_name,
        shape=fwd.shape if fwd is not None else (),
        dtype=fwd.dtype if fwd is not None else "float32",
        lod_level=fwd.lod_level if fwd is not None else 0)


def _input_needs_grad(block, name: str, no_grad_set: Set[str]) -> bool:
    if name in no_grad_set:
        return False
    v = block._find_var_recursive(name)
    if v is None:
        return False
    if v.stop_gradient:
        return False
    return is_float_dtype(v.dtype)


def _make_grad_op(block, op, acc: _GradAccumulator, no_grad_set: Set[str]):
    """Default grad maker: build `<type>_grad` binding forward ins/outs,
    output grads, and input-grad outputs. Returns False if nothing to do."""
    info = OPS.get(op.type)
    grad_type = op.type + "_grad"
    if not OPS.has(grad_type):
        return False

    out_names = [n for slot in op.output_slots() for n in op.output(slot)]
    if not any(acc.has(n) for n in out_names):
        return False  # no grad flows through this op

    inputs = {}
    outputs = {}
    any_input_grad = False
    for slot in op.input_slots():
        names = op.input(slot)
        inputs[slot] = list(names)
        if slot in info.no_grad_slots:
            continue
        g_names = []
        needed = False
        for n in names:
            if _input_needs_grad(block, n, no_grad_set):
                g_names.append(acc.add(n))
                needed = True
            else:
                g_names.append("")  # positional hole: grad not needed
        if needed:
            outputs[slot + GRAD_SUFFIX] = g_names
            any_input_grad = True
    if not any_input_grad:
        return False

    for slot in op.output_slots():
        names = op.output(slot)
        inputs[slot] = list(names)
        g_names = []
        have_any = False
        for n in names:
            g = acc.final(n)
            g_names.append(g or "")
            have_any = have_any or bool(g)
        inputs[slot + GRAD_SUFFIX] = g_names

    attrs = {k: v for k, v in op._all_attrs()}
    attrs[OP_ROLE_ATTR] = "backward"
    # keep the forward uid so rng-consuming forwards replay identically
    attrs[OP_UID_ATTR] = op.attr(OP_UID_ATTR)

    for slot, names in outputs.items():
        for n in names:
            if n:
                fwd_name = n.split(GRAD_SUFFIX)[0]
                _create_grad_var(block, fwd_name, n)

    block.append_op(grad_type, inputs=inputs, outputs=outputs, attrs=attrs,
                    infer_shape=False)
    return True


def _grad_op_input_filter(op):
    """Names whose grads the op's lowering may read (O@GRAD inputs)."""
    return [n for slot in op.input_slots() if slot.endswith(GRAD_SUFFIX)
            for n in op.input(slot) if n]


def append_backward(loss, parameter_list=None, no_grad_set=None,
                    callbacks=None, checkpoints=None):
    """Append backward ops computing d loss / d params to loss's program.

    Returns list of (param, grad_var) tuples (reference backward.py:558).
    """
    with _obs_tracing.setup_span("program_build.backward",
                                 program=loss.block.program.fingerprint[0]):
        return _append_backward(loss, parameter_list, no_grad_set)


def _append_backward(loss, parameter_list, no_grad_set):
    block = loss.block
    program = block.program
    no_grad = set(no_grad_set or ())

    if tuple(loss.shape) not in ((), (1,)):
        raise ValueError(
            f"loss must be a scalar (shape () or (1,)), got {loss.shape}")

    # seed: d loss / d loss = 1
    loss_grad = _grad_name(loss.name)
    block.create_var(name=loss_grad, shape=loss.shape, dtype=loss.dtype)
    block.append_op(
        "fill_constant",
        inputs={}, outputs={"Out": loss_grad},
        attrs={"shape": list(loss.shape), "value": 1.0,
               "dtype": int(loss.dtype), OP_ROLE_ATTR: "backward"})

    acc = _GradAccumulator(block)
    acc.contribs[loss.name] = [loss_grad]

    fwd_ops = [op for op in block.ops
               if op.attr(OP_ROLE_ATTR, "forward") == "forward"]

    # find the op producing `loss`; everything after it can't influence loss
    loss_idx = len(fwd_ops)
    for i, op in enumerate(fwd_ops):
        if loss.name in [n for s in op.output_slots()
                         for n in op.output(s)]:
            loss_idx = i
    relevant = fwd_ops[:loss_idx + 1]

    for op in reversed(relevant):
        info = OPS.get(op.type)
        if info.grad_maker is not None:
            info.grad_maker(op, block, acc, no_grad)
        else:
            _make_grad_op(block, op, acc, no_grad)

    params = parameter_list
    if params is None:
        params = [p.name for p in block.program.all_parameters()
                  if p.trainable]
    else:
        params = [p.name if isinstance(p, framework.Variable) else p
                  for p in params]

    params_and_grads = []
    for pname in params:
        g = acc.final(pname)
        if g is None:
            continue
        p_var = block._find_var_recursive(pname)
        g_var = block._find_var_recursive(g)
        params_and_grads.append((p_var, g_var))
    return params_and_grads


def gradients(targets, inputs, target_gradients=None, no_grad_set=None):
    """fluid.gradients parity: grads of targets w.r.t. inputs."""
    targets = targets if isinstance(targets, (list, tuple)) else [targets]
    inputs = inputs if isinstance(inputs, (list, tuple)) else [inputs]
    if len(targets) != 1:
        raise NotImplementedError("multi-target gradients not yet supported")
    pg = append_backward(targets[0], parameter_list=None,
                         no_grad_set=no_grad_set)
    block = targets[0].block
    outs = []
    for v in inputs:
        g = block._find_var_recursive(_grad_name(v.name))
        outs.append(g)
    return outs
