"""One-element persistables packed into a few step arguments.

A compiled step takes every persistable it reads as one argument, and
the host pays per argument on every launch: the step's argument dicts
are built, flattened, checked and handed to the runtime one array at a
time, and each updated value comes back as its own array to be written
into the scope. With Adam two of every parameter's five persistables
are `f32[1]` beta-power accumulators; with the learning rate, the step
counters and the loss scale, about 40% of a Transformer step's 1,283
arguments hold one element each.

Here the step takes those one-element persistables as ONE flat array
per group (one dtype, updated or read-only), slices each member out at
its entry and writes the updated members back into one flat array at
its exit. What is packed is decided from the trace, per persistable:
one element, never an operand of a Pallas call (a slice handed to a
custom call is materialised as a copy), a plain array in the scope,
and for an updated one the same shape and dtype out as in. Steps on a
mesh keep one argument per persistable: their shardings are per tensor.

Vectors stay one argument each. Packed too (norm scales, biases, their
moments), they left the step's live bytes as they were but moved XLA's
placement of its temporaries: a described-v5e compile of the 30B
hybrid cell's step grew its temporary allocation by 300-650 MB, and no
form of the pack tried (aliased or not, written by a final join or in
place, tile-aligned or chunked) kept every cell within 0.25% of its
peak. One-element members leave eight of the nine benchmark cells
within 5 MB; the ninth's allocation moves by 0.25% of its peak with its
live bytes unchanged (PERF.md §6).

The scope keeps its per-name contract: a packed Variable reads its
value as a view of the pack (`core/scope.py` ``Pack``), a host write
releases it from the pack, and the engine packs the members again
before the next step (docs/ASYNC_DISPATCH.md "Packed persistables").
"""
from __future__ import annotations

import weakref
from collections.abc import Mapping
from typing import Dict, List, Sequence, Tuple

import numpy as np

import jax
import jax.numpy as jnp

from .scope import Pack

PACK_PREFIX = "@PACK@"

# primitives whose output is the operand's buffer seen another way: a
# persistable that reaches a Pallas call only through these is the
# call's operand
_VIEWS = frozenset({"reshape", "squeeze", "expand_dims", "copy",
                    "copy_p"})


def _numel(aval) -> int:
    return int(np.prod(getattr(aval, "shape", ()), dtype=np.int64))


def kernel_operands(closed_jaxpr, arg_names: Sequence[str]) -> set:
    """Names among ``arg_names`` (the jaxpr's first invars, in order)
    that a ``pallas_call`` takes as an operand, directly or through a
    reshape, at any depth of the trace."""
    jcore = jax._src.core
    hit = set()

    def has_kernel(jaxpr):
        return any(eqn.primitive.name == "pallas_call"
                   or any(has_kernel(j) for j in
                          jcore.jaxprs_in_params(eqn.params))
                   for eqn in jaxpr.eqns)

    def walk(jaxpr, taint):
        for eqn in jaxpr.eqns:
            ins = [() if isinstance(v, jcore.Literal) else
                   taint.get(v, ()) for v in eqn.invars]
            if not any(ins):
                continue
            prim = eqn.primitive.name
            if prim == "pallas_call":
                for names in ins:
                    hit.update(names)
            elif prim in _VIEWS:
                for o in eqn.outvars:
                    taint[o] = ins[0]
            for sub in jcore.jaxprs_in_params(eqn.params):
                p = eqn.params
                if prim == "while":
                    cn, bn = p["cond_nconsts"], p["body_nconsts"]
                    carry = ins[cn + bn:]
                    mapped = (ins[:cn] + carry
                              if sub is p["cond_jaxpr"].jaxpr
                              else ins[cn:cn + bn] + carry)
                elif prim == "cond":
                    mapped = ins[1:]
                else:
                    mapped = ins
                if len(mapped) == len(sub.invars):
                    walk(sub, {v: n for v, n in zip(sub.invars, mapped)
                               if n})
                elif has_kernel(sub):
                    # operands this walk cannot place: any of them may
                    # reach the call
                    for names in ins:
                        hit.update(names)

    jaxpr = closed_jaxpr.jaxpr
    walk(jaxpr, {v: (n,) for v, n in zip(jaxpr.invars, arg_names)})
    return hit


class PackLayout:
    """One pack: its members ``names``, one element each, in order in a
    1-D array of ``dtype``; ``shapes`` are their own (`()`, `(1,)`).
    One object per distinct layout (:func:`layout`), so steps that read
    the same members share it, its packs and its two host functions."""

    __slots__ = ("key", "dtype", "donated", "names", "shapes", "total",
                 "_split", "_join", "__weakref__")

    def __init__(self, dtype, donated, names, shapes):
        self.dtype = np.dtype(dtype)
        self.donated = bool(donated)
        self.key = (f"{PACK_PREFIX}{'donated' if donated else 'const'}"
                    f"@{self.dtype.name}")
        self.names = tuple(names)
        self.shapes = tuple(tuple(s) for s in shapes)
        self.total = len(self.names)
        self._split = self._join = None

    # -- inside the step ---------------------------------------------------
    def unpack(self, flat) -> Dict[str, jax.Array]:
        return {n: flat[i:i + 1].reshape(shape) for i, (n, shape) in
                enumerate(zip(self.names, self.shapes))}

    def pack(self, values: Dict[str, jax.Array]):
        return jnp.concatenate([
            jnp.reshape(values[n], (1,)).astype(self.dtype)
            for n in self.names])

    # -- on the host ---------------------------------------------------------
    def split(self, flat) -> Tuple[jax.Array, ...]:
        """Every member's value out of a pack, in one dispatch."""
        if self._split is None:
            self._split = jax.jit(
                lambda a: tuple(self.unpack(a)[n] for n in self.names))
        return self._split(flat)

    def join(self, values: Sequence, dev=None):
        """A pack from the members' values, in one dispatch, committed
        to ``dev`` when one is given."""
        for n, v, shape in zip(self.names, values, self.shapes):
            if tuple(np.shape(v)) != shape:
                raise ValueError(
                    f"persistable {n!r} holds shape {tuple(np.shape(v))}; "
                    f"the compiled step was traced with {shape}")
        if self._join is None:
            self._join = jax.jit(lambda vs: self.pack(
                dict(zip(self.names, vs))))
        flat = self._join(list(values))
        return jax.device_put(flat, dev) if dev is not None else flat


_LAYOUTS: "weakref.WeakValueDictionary" = weakref.WeakValueDictionary()


def layout(dtype, donated, names, shapes) -> PackLayout:
    """The one :class:`PackLayout` of these members."""
    sig = (np.dtype(dtype).name, bool(donated), tuple(names),
           tuple(tuple(s) for s in shapes))
    lay = _LAYOUTS.get(sig)
    if lay is None:
        lay = PackLayout(dtype, donated, names, shapes)
        _LAYOUTS[sig] = lay
    return lay


def plan(closed_jaxpr, params_sig, out_updated, donated,
         const, plain) -> List[PackLayout]:
    """The packs of one step, from its trace. ``params_sig`` is the
    step's persistable arguments (the jaxpr's first invars, in sorted
    order), ``out_updated`` the avals of the values it writes back,
    ``plain`` the names whose scope value is a plain array. A member
    holds one element; a group of fewer than two members is left as
    it is."""
    operands = kernel_operands(closed_jaxpr, sorted(params_sig))
    groups: Dict[Tuple[bool, str], List[str]] = {}
    for is_donated, members in ((True, donated), (False, const)):
        for n in members:
            sig = params_sig[n]
            if _numel(sig) != 1 or n in operands or n not in plain:
                continue
            if is_donated:
                out = out_updated.get(n)
                if out is None or (tuple(out.shape), out.dtype) != (
                        tuple(sig.shape), sig.dtype):
                    continue
            groups.setdefault((is_donated, np.dtype(sig.dtype).name),
                              []).append(n)
    return [layout(dt, d, sorted(ns), [params_sig[n].shape
                                       for n in sorted(ns)])
            for (d, dt), ns in sorted(groups.items()) if len(ns) > 1]


def unpack_args(args: Dict[str, jax.Array], layouts) -> Dict[str, jax.Array]:
    """Traced: the per-name persistables of one argument dict."""
    out = dict(args)
    for lay in layouts:
        out.update(lay.unpack(out.pop(lay.key)))
    return out


def repack(updated: Dict[str, jax.Array], layouts) -> Dict[str, jax.Array]:
    """Traced: the step's updated dict with each donated pack's members
    written into one flat array."""
    out = dict(updated)
    for lay in layouts:
        out[lay.key] = lay.pack({n: out.pop(n) for n in lay.names})
    return out


class PackSlot:
    """One pack of a step as one scope sees it: the member Variables,
    and for a read-only pack the array last built with what it was
    built from. ``take`` puts the pack's current array in the step's
    argument dict; an updated pack is owned by its members (a
    :class:`~paddle_tpu.core.scope.Pack`) and is rebuilt only when a
    member was written from the host or joined another pack; a
    read-only pack is rebuilt when any member's value changed."""

    __slots__ = ("layout", "vars", "_from", "_array")

    def __init__(self, lay: PackLayout, scope):
        self.layout = lay
        self.vars = [scope.find_var(n) or scope.var(n)
                     for n in lay.names]
        self._from = None
        self._array = None

    def stale(self) -> bool:
        """True when a host write gave a member another shape than the
        step was traced with: the step must be traced again (the
        per-name step retraces on such a write as well)."""
        lay = self.layout
        if lay.donated:
            p = self.vars[0].pack
            if p is not None and p.layout is lay and p.intact:
                return False
        return any(tuple(np.shape(_plain(v))) != shape
                   for v, shape in zip(self.vars, lay.shapes))

    def take(self, args: Dict[str, jax.Array], dev):
        """Put the pack in ``args``; returns its owning Pack when the
        step updates it, else None."""
        lay = self.layout
        if lay.donated:
            p = self.vars[0].pack
            if p is None or p.layout is not lay or not p.intact:
                p = Pack(lay.join([_plain(v) for v in self.vars], dev),
                         lay, self.vars)
            args[lay.key] = p.array
            return p
        now = [_plain(v) for v in self.vars]
        seen = self._from
        if seen is None or any(a is not b for a, b in zip(now, seen)):
            self._array = lay.join(now, dev)
            self._from = now
        args[lay.key] = self._array
        return None


class Members(Mapping):
    """A step's updated dict by persistable name, for the host's
    controllers (stability guard, integrity sentinel): a packed member
    reads as its view of the pack the writeback just updated."""

    def __init__(self, updated, packs):
        self._plain = {n: v for n, v in updated.items() if n not in packs}
        self._where = {n: (p, i) for p in packs.values()
                       for i, n in enumerate(p.layout.names)}

    def __getitem__(self, name):
        if name in self._plain:
            return self._plain[name]
        p, i = self._where[name]
        return p.view(i)

    def __iter__(self):
        yield from self._plain
        yield from self._where

    def __len__(self):
        return len(self._plain) + len(self._where)


def _plain(var):
    val = var.get_value()
    return getattr(val, "array", val)
