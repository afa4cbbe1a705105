"""Data-parallel engine behind CompiledProgram.with_data_parallel.

Parity: reference ParallelExecutor (parallel_executor.cc:356) +
SSA-graph executors. TPU-native: one Engine compiled under a Mesh with
batch-dim sharding (see core/engine.py trace_step) — param broadcast
(BCastParamsToDevices) is XLA replication; AllReduce insertion is the SPMD
partitioner; ScaleLossGrad is unnecessary because reductions are computed
over the global batch exactly.
"""
from __future__ import annotations

from typing import Dict, List, Optional

import numpy as np

import jax

from ..core.engine import Engine
from ..core.scope import LoDTensor
from .mesh import make_mesh

__all__ = ["DataParallelEngine"]


class DataParallelEngine:
    """Grad communication goes through the comm scheduler
    (comm_scheduler.py): with FLAGS_allreduce_bucket_mb > 0 the traced
    step fuses param-grad all-reduces into size-capped buckets
    interleaved with the backward, FLAGS_quantized_allreduce applies
    the bucket quantization round-trip, and FLAGS_sharded_weight_update
    shards the optimizer update over the mesh's data axis — all inside
    the one Engine this class owns (counters on `self.counters`)."""

    def __init__(self, program, build_strategy=None, places=None,
                 data_axis: str = "dp"):
        self._program = program
        devices = None
        if places is not None:
            if not len(places):
                # e.g. places=fluid.tpu_places() on a host where JAX
                # found no TPU: the caller's error, never a quiet mesh
                # over whatever the default backend has
                raise ValueError(
                    "with_data_parallel: places is empty — no device "
                    "to run on (fluid.tpu_places() is [] when this "
                    "process has no local TPU chip); pass places=None "
                    "for every device of the executor's platform")
            # honor the executor's device platform: an Executor(CPUPlace)
            # with_data_parallel must mesh over CPU devices even when the
            # process default backend is TPU (mixing platforms between
            # feed placement and mesh shardings is a hard error in jax)
            devices = [p.jax_device() if hasattr(p, "jax_device") else p
                       for p in places]
        self.mesh = make_mesh({data_axis: len(devices)}
                              if devices is not None else None,
                              devices=devices)
        self._engine = Engine(mesh=self.mesh, data_axis=data_axis)

    @property
    def device_count(self):
        return self.mesh.size

    @property
    def counters(self):
        """Engine dispatch + collective instrumentation
        (collective_bytes / collective_buckets /
        grad_collectives_per_step / comm_overlap_frac ... —
        docs/COLLECTIVES.md)."""
        return self._engine.counters

    def comm_plan(self):
        """The comm scheduler's bucket plan for this program under the
        current FLAGS_allreduce_bucket_mb (introspection + benches)."""
        from .comm_scheduler import plan_program_buckets
        return plan_program_buckets(self._program)

    def run(self, feed, fetch_names, scope, return_numpy=True,
            loss_name=None, iterations=1):
        """One data-parallel dispatch.

        ``iterations`` is ExecutionStrategy.num_iteration_per_run routed
        from CompiledProgram._run: K chained steps compile into ONE
        lax.scan executable under the mesh (same trace_step path as the
        single-device engine), so the host dispatches once per K steps
        instead of fully syncing each iteration. Remaining gap vs the
        single-device path: ragged (LoD) feeds cannot scan — those
        host-loop the K iterations here (one dispatch per iteration,
        but still no per-iteration fetch sync), as do the eager/islands
        trace fallbacks internally.
        """
        # reference contract: list feed = per-device dicts -> concat batch
        if isinstance(feed, (list, tuple)):
            merged: Dict[str, object] = {}
            keys = feed[0].keys()
            for k in keys:
                parts = [np.asarray(d[k].array if isinstance(
                    d[k], LoDTensor) else d[k]) for d in feed]
                merged[k] = np.concatenate(parts, axis=0)
            feed = merged
        if iterations > 1 and any(
                isinstance(v, LoDTensor) and v.lod()
                for v in (feed or {}).values()):
            out = None
            for _ in range(iterations):
                out = self._engine.run(self._program, scope, None, feed,
                                       fetch_names,
                                       return_numpy=return_numpy)
            return out
        return self._engine.run(self._program, scope, None, feed,
                                fetch_names, return_numpy=return_numpy,
                                iterations=iterations)
