"""Variable containers and hierarchical scopes.

Parity: reference Variable/Scope
(/root/reference/paddle/fluid/framework/variable.h:26, scope.h:46). Values
are jax.Arrays (device-resident), LoDTensor wrappers, TensorArrays, or
arbitrary Python payloads (readers, rng state).
"""
from __future__ import annotations

from typing import Any, Dict, Optional

import numpy as np

import jax
import jax.numpy as jnp


class LoDTensor:
    """Dense tensor + level-of-detail offsets (ragged-batch metadata).

    Parity: reference LoDTensor (lod_tensor.h:110). TPU-first: the payload is
    always a dense, statically-shaped jax.Array; `lod` is host-side metadata
    (list of offset vectors) consumed by sequence ops to build masks/segment
    ids. This keeps XLA shapes static while passing the sequence-op suite.
    """

    __slots__ = ("_array", "_lod")

    def __init__(self, array=None, lod=None):
        self._array = array
        self._lod = [list(map(int, level)) for level in (lod or [])]

    # -- fluid-compatible surface -----------------------------------------
    def set(self, array, place=None):
        arr = np.asarray(array)
        if place is not None and getattr(place, "jax_device", None):
            self._array = jax.device_put(arr, place.jax_device())
        else:
            self._array = jnp.asarray(arr)

    def set_lod(self, lod):
        self._lod = [list(map(int, level)) for level in lod]

    def lod(self):
        return self._lod

    def recursive_sequence_lengths(self):
        return [[b - a for a, b in zip(level[:-1], level[1:])]
                for level in self._lod]

    def has_valid_recursive_sequence_lengths(self):
        """Reference LoDTensor::HasValidRecursiveSequenceLengths
        (lod_tensor.cc CheckLoD): offsets ascending from 0; each
        level's last offset partitions the next level (rows for the
        last level)."""
        rows = self.array.shape[0] if getattr(
            self.array, "ndim", 0) else 0
        expect = rows
        for level in reversed(self._lod):
            if not level or level[0] != 0:
                return False
            if any(b < a for a, b in zip(level[:-1], level[1:])):
                return False
            if level[-1] != expect:
                return False
            expect = len(level) - 1
        return True

    def set_recursive_sequence_lengths(self, lengths):
        self._lod = []
        for level in lengths:
            offs = [0]
            for l in level:
                offs.append(offs[-1] + int(l))
            self._lod.append(offs)

    def shape(self):
        return tuple(self._array.shape) if self._array is not None else ()

    @property
    def array(self):
        return self._array

    def __array__(self, dtype=None):
        a = np.asarray(self._array)
        return a.astype(dtype) if dtype else a

    def __repr__(self):
        return f"LoDTensor(shape={self.shape()}, lod={self._lod})"


def create_lod_tensor(data, recursive_seq_lens, place=None):
    t = LoDTensor()
    t.set(data, place)
    t.set_recursive_sequence_lengths(recursive_seq_lens)
    return t


class TensorArray(list):
    """LoDTensorArray analog (lod_tensor_array.h)."""

    def append(self, tensor):
        """list.append wrapped as a Python method so the API manifest
        lists it (reference LoDTensorArray.append)."""
        list.append(self, tensor)


class LoDRankTable:
    """Sequence rank table (reference lod_rank_table.h): sequence
    indices sorted by length, descending. Host-side static metadata in
    the TPU build (LoD is static per compiled step), driving
    DynamicRNN's sort/pad/unsort plumbing. Indexable as (index, length)
    pairs for parity with the reference's items()."""

    __slots__ = ("items", "offsets")

    def __init__(self, offsets):
        lengths = [int(offsets[i + 1]) - int(offsets[i])
                   for i in range(len(offsets) - 1)]
        order = sorted(range(len(lengths)),
                       key=lambda i: (-lengths[i], i))
        self.items = [(i, lengths[i]) for i in order]
        self.offsets = [int(o) for o in offsets]

    def __getitem__(self, i):
        return self.items[i]

    def __len__(self):
        return len(self.items)

    @property
    def indices(self):
        return [i for i, _ in self.items]

    @property
    def lengths(self):
        return [l for _, l in self.items]

    @property
    def max_len(self):
        return self.items[0][1] if self.items else 0


class Pack:
    """Several persistables of one dtype held end to end in ONE device
    array, which a compiled step takes and returns as one argument
    (core/packing.py). Each member Variable reads its value as a view
    of the array; a host write to a member releases it from the pack
    (``intact`` turns False), and the engine packs the members again
    before the next step."""

    __slots__ = ("array", "layout", "intact", "_views")

    def __init__(self, array, layout, variables):
        self.array = array
        self.layout = layout
        self.intact = True
        self._views = None
        for i, v in enumerate(variables):
            old = v._pack
            if old is not None and old is not self:
                old.intact = False
            v._pack, v._slot, v._value = self, i, None

    def view(self, i):
        """Member ``i``'s value; all members are split out at once, on
        the first read after the array changed."""
        views = self._views
        if views is None:
            views = self._views = self.layout.split(self.array)
        return views[i]

    def update(self, array):
        self.array = array
        self._views = None


class Variable:
    """Type-erased runtime variable (reference variable.h:26). A packed
    variable's value lives in its :class:`Pack`."""

    __slots__ = ("name", "_value", "_pack", "_slot")

    def __init__(self, name: str):
        self.name = name
        self._value = None
        self._pack = None
        self._slot = 0

    @property
    def pack(self) -> Optional[Pack]:
        return self._pack

    def get_value(self):
        if self._pack is not None:
            return self._pack.view(self._slot)
        return self._value

    def set_value(self, v):
        if self._pack is not None:
            self._pack.intact = False
            self._pack = None
        self._value = v

    # fluid calls this get_tensor(); returns the LoDTensor view
    def get_tensor(self) -> LoDTensor:
        if self._pack is not None:
            # the caller may write through the tensor: it is the value
            self.set_value(self.get_value())
        if isinstance(self._value, LoDTensor):
            return self._value
        t = LoDTensor(self._value)
        self._value = t
        return t

    def is_initialized(self):
        if self._pack is not None:
            return True
        v = self._value
        if isinstance(v, LoDTensor):
            return v.array is not None
        return v is not None


class Scope:
    """Hierarchical name->Variable map (reference scope.h:46)."""

    def __init__(self, parent: Optional["Scope"] = None):
        self._vars: Dict[str, Variable] = {}
        self._parent = parent
        self._kids = []

    def var(self, name: str) -> Variable:
        v = self._vars.get(name)
        if v is None:
            v = Variable(name)
            self._vars[name] = v
        return v

    def find_var(self, name: str) -> Optional[Variable]:
        s = self
        while s is not None:
            v = s._vars.get(name)
            if v is not None:
                return v
            s = s._parent
        return None

    def var_refs(self, names):
        """(name, Variable) pairs for `names`, creating as needed — the
        engine's steady-state dispatch caches these references so the
        per-step persistable read/writeback loop performs no name
        lookups (values stay device-resident jax.Arrays end to end;
        see docs/ASYNC_DISPATCH.md)."""
        return [(n, self.var(n)) for n in names]

    def initialized_refs(self, names):
        """`var_refs` filtered to initialized variables — the
        checkpoint snapshot's read set (a missing/uninitialized
        persistable is the caller's policy decision: warn or raise)."""
        return [(n, v) for n, v in self.var_refs(names)
                if v.is_initialized()]

    def new_scope(self) -> "Scope":
        kid = Scope(self)
        self._kids.append(kid)
        return kid

    def drop_kids(self):
        self._kids.clear()

    def local_var_names(self):
        return list(self._vars)

    def erase(self, names):
        for n in names:
            self._vars.pop(n, None)


_global_scope = Scope()


def global_scope() -> Scope:
    return _global_scope


class _ScopeGuard:
    def __init__(self, scope):
        self._scope = scope

    def __enter__(self):
        global _global_scope
        self._old = _global_scope
        _global_scope = self._scope

    def __exit__(self, *exc):
        global _global_scope
        _global_scope = self._old


def scope_guard(scope: Scope):
    """`with scope_guard(scope):` — swap the global scope (executor.py parity)."""
    return _ScopeGuard(scope)
