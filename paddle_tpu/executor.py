"""Public Executor: the fluid.Executor-compatible entry point.

Parity: reference python/paddle/fluid/executor.py (Executor :295, run :537)
and C++ Executor (executor.cc:172). Differences are the TPU-native execution
model: `run` compiles the whole block to one XLA executable per feed
signature (see core/engine.py) instead of interpreting ops, and `place` is
a TPUPlace backed by PJRT.
"""
from __future__ import annotations

from typing import Any, Dict, List, Optional, Sequence

import jax
import numpy as np

from . import compiler as _compiler
from . import framework
from . import profiler as _profiler
from .core.engine import Engine
from .core.flags import FLAGS
from .core.place import CPUPlace, TPUPlace, Place, default_place
from .core.scope import LoDTensor, Scope, global_scope, scope_guard

__all__ = ["Executor", "global_scope", "scope_guard"]


def _to_name_str(fetch):
    if isinstance(fetch, str):
        return fetch
    if isinstance(fetch, framework.Variable):
        return fetch.name
    raise TypeError(f"fetch target must be Variable or str, got "
                    f"{type(fetch)}")


class Executor:
    def __init__(self, place: Optional[Place] = None):
        self.place = place if place is not None else default_place()
        self._engine = Engine()
        self._ckpt_managers = {}
        self._closed = False

    def close(self):
        self._closed = True
        managers, self._ckpt_managers = self._ckpt_managers, {}
        for m in managers.values():
            m.close()   # drain in-flight checkpoint saves
        self._engine = Engine()

    def checkpoint_manager(self, dirname, **options):
        """The async checkpoint subsystem bound to this executor: the
        returned :class:`~paddle_tpu.checkpoint.CheckpointManager`
        reports save-in-flight counts through this executor's
        ``Engine.counters`` (``ckpt_saves`` / ``ckpt_inflight``) and is
        drained by :meth:`close`. One manager per directory is cached —
        repeated calls return the same instance
        (docs/CHECKPOINTING.md)."""
        m = self._ckpt_managers.get(dirname)
        if m is None:
            from .checkpoint import CheckpointManager
            m = CheckpointManager(dirname, engine=self._engine,
                                  **options)
            self._ckpt_managers[dirname] = m
        return m

    def run(self, program=None, feed=None, fetch_list=None, feed_var_name="feed",
            fetch_var_name="fetch", scope=None, return_numpy=True,
            use_program_cache=True):
        """Run a Program (or a CompiledProgram built from one).

        ``use_program_cache=False`` bypasses (and does not populate) the
        engine's trace/fast-path caches: the step is re-traced and
        re-compiled on every call — the reference's semantics for
        programs whose desc mutates between runs without a version bump.
        With ``FLAGS.async_dispatch`` on and ``return_numpy=False``,
        fetches come back as live FetchHandles; call their ``.numpy()``
        or :meth:`synchronize` to materialize (docs/ASYNC_DISPATCH.md).
        """
        if self._closed:
            raise RuntimeError("Executor is closed")
        if program is None:
            program = framework.default_main_program()
        compiled = isinstance(program, _compiler.CompiledProgram)
        engine = program._step_engine(self) if compiled else self._engine
        # one `pt.step` per call, in whatever profiler session is open
        # (docs/TRACING.md); the phases inside stamp the thread's clock
        clock = _profiler.step_clock()
        with jax.profiler.StepTraceAnnotation(
                _profiler.STEP_SPAN,
                step_num=engine.counters["runs"] + 1):
            clock.begin_step(opened=True)
            try:
                if compiled:
                    # data-parallel / distributed
                    return program._run(self, feed, fetch_list, scope,
                                        return_numpy)
                with clock.phase(_profiler.P_EXECUTOR_FEED):
                    scope = scope or global_scope()
                    fetch_names = [_to_name_str(f)
                                   for f in fetch_list or ()]
                    feed = self._canonical_feed(feed, program)
                    if FLAGS.validate_program:
                        from .analysis import validate_cached
                        validate_cached(program, feed_names=list(feed),
                                        fetch_names=fetch_names)
                return engine.run(program, scope, self.place, feed,
                                  fetch_names, return_numpy=return_numpy,
                                  use_program_cache=use_program_cache)
            finally:
                clock.opened = False

    def synchronize(self):
        """Block until every step dispatched by this executor has
        finished on device, draining all deferred FLAGS.async_dispatch
        checks: NaN/Inf trips (FLAGS_check_nan_inf) and deferred XLA
        errors are re-raised here with their original op context."""
        self._engine.synchronize()

    def _canonical_feed(self, feed, program):
        if feed is None:
            return {}
        if isinstance(feed, (list, tuple)):
            # list-of-dicts is the multi-device feed form; merge by concat
            # along batch is the ParallelExecutor contract — handled by
            # CompiledProgram; a single executor takes dict only.
            if len(feed) == 1:
                feed = feed[0]
            else:
                raise TypeError(
                    "list feed is only valid for CompiledProgram "
                    "with_data_parallel")
        out = {}
        for k, v in feed.items():
            if isinstance(v, LoDTensor):
                out[k] = v
            elif isinstance(v, jax.Array):
                # already device-resident (e.g. from the
                # DeviceFeedPrefetcher): np.asarray here would force a
                # D2H sync on the dispatch hot path; dtype-matching
                # arrays pass through untouched (compare against the
                # CANONICALIZED dtype — x64-disabled jax stores int64
                # feeds as int32, which must not astype every step)
                var = program.global_block()._find_var_recursive(k)
                if var is not None:
                    want = jax.dtypes.canonicalize_dtype(
                        framework.dtype_to_np(var.dtype))
                    if v.dtype != want:
                        v = v.astype(want)
                out[k] = v
            else:
                arr = np.asarray(v)
                var = program.global_block()._find_var_recursive(k)
                if var is not None and arr.dtype != \
                        framework.dtype_to_np(var.dtype):
                    arr = arr.astype(framework.dtype_to_np(var.dtype))
                out[k] = arr
        return out

    # ---- dataset training loop (train_from_dataset parity) ---------------
    def train_from_dataset(self, program=None, dataset=None, scope=None,
                           thread=0, debug=False, fetch_list=None,
                           fetch_info=None, print_period=100):
        from .reader.dataset import run_from_dataset
        return run_from_dataset(self, program, dataset, scope, fetch_list,
                                fetch_info, print_period, train=True)

    def infer_from_dataset(self, program=None, dataset=None, scope=None,
                           thread=0, debug=False, fetch_list=None,
                           fetch_info=None, print_period=100):
        from .reader.dataset import run_from_dataset
        return run_from_dataset(self, program, dataset, scope, fetch_list,
                                fetch_info, print_period, train=False)
