"""MPMD pipeline parallelism: per-stage compiled executables exchanging
activations under a host schedule.

Parity: the reference's PipelineTrainer/SectionWorker model — each
section is an arbitrary program pinned to its own place, tensors flow
between sections through queues
(/root/reference/paddle/fluid/framework/pipeline_trainer.cc:35-48,
section_worker.cc:141). This is the HETEROGENEOUS counterpart of the
SPMD GPipe engine (parallel/pipeline.py): that engine compiles ONE
lax.switch step over a "pp" mesh axis and therefore requires
structurally uniform stages; this one compiles one XLA executable PER
STAGE, so a ResNet-style conv->pool->fc pipeline — different activation
shapes, different param sets per stage — is fully expressible, and a
parameter shared by several stages (tied embeddings) lives only on the
stages that use it, with its gradient summed across them.

TPU-native mapping of the reference's pieces:
* section program        -> per-stage jitted forward / backward
                            executables built by replaying the stage's
                            ops through the op-lowering registry
* cross-section queue    -> jax.device_put of the activation onto the
                            consumer stage's device (JAX dispatch is
                            async, so with stages on distinct devices
                            the fill/drain host loop overlaps exactly
                            like the reference's section threads)
* backward section       -> per-stage jitted vjp that RECOMPUTES the
                            stage forward from its stashed inputs
                            (GPipe-style recompute: activation stash
                            holds only stage INPUTS, not internals)
* sync_steps / updates   -> gradients accumulated over microbatches,
                            then the optimizer program's update ops run
                            per stage via the same registered lowerings
                            the graph executor uses
"""
from __future__ import annotations

from typing import Dict, List, Optional, Sequence

import numpy as np

import jax
import jax.numpy as jnp

from ..core.registry import ExecContext, OPS, _RngCtx
from ..core.engine import run_block_ops
from ..core.scope import LoDTensor, Scope


def _producer_index(ops, name):
    for i, op in enumerate(ops):
        for slot in op.output_slots():
            if name in op.output(slot):
                return i
    raise ValueError(f"no op produces {name!r}")


def _op_reads(op):
    for slot in op.input_slots():
        for n in op.input(slot):
            yield n


def _op_writes(op):
    for slot in op.output_slots():
        for n in op.output(slot):
            yield n


class MPMDPipelineEngine:
    """Host-scheduled heterogeneous pipeline over per-stage executables.

    program: FORWARD program (up to the loss); cut_vars split it into
    n_stages = len(cut_vars)+1 sections — or ``cut_vars=None`` to
    synthesize balanced cuts from the static cost model
    (parallel/auto_cut.py; pass ``n_stages``). optimizer_program: the
    update ops (PipelineOptimizer.opt_program). devices: one per stage
    (cycled when shorter, which makes n_stages > len(devices) the
    Megatron-style interleaved layout — device d hosts model chunks
    d, d+D, ...; on a single chip all stages share it — the MPMD
    structure still holds, only the overlap disappears).

    ``schedule`` picks the micro-batch dispatch order
    (parallel/pipeline.pipeline_schedule): "1f1b" (default) drains each
    backward as soon as it is ready, capping the activation stash at
    the pipeline depth; "gpipe" is the legacy fill/drain. Both
    execute the same F/B events with the same fold_in keys, so the
    loss is schedule-invariant; ``last_stats`` reports the measured
    bubble fraction of whichever schedule ran."""

    def __init__(self, program, loss_name: str,
                 cut_vars: Optional[Sequence[str]] = None,
                 optimizer_program=None, devices=None,
                 num_microbatches: int = 4, n_stages: int = None,
                 schedule: str = "1f1b"):
        self.program = program
        self.loss_name = loss_name
        self.cut_plan = None
        if cut_vars is None:
            if n_stages is None:
                raise ValueError(
                    "MPMDPipelineEngine: automatic cutting needs "
                    "n_stages=")
            from .auto_cut import propose_cuts
            self.cut_plan = propose_cuts(program, loss_name,
                                         n_stages, uniform=False)
            cut_vars = self.cut_plan.cut_vars
        self.cut_vars = list(cut_vars)
        self.n_stages = len(self.cut_vars) + 1
        self.n_micro = num_microbatches
        self.schedule = schedule
        self.last_stats: Dict[str, object] = {}
        self._opt_program = optimizer_program
        devs = list(devices) if devices else jax.devices()
        self.n_devices = min(len(devs), self.n_stages)
        self.stage_devices = [devs[s % len(devs)]
                              for s in range(self.n_stages)]
        self._built = False
        # cross-stage hazard proof on the cutting itself (the slot
        # table is verified separately per step in _verify_schedule)
        from ..analysis.races import verify_stage_partition
        errs = [d for d in verify_stage_partition(
            self.program, self.cut_vars, label="pipeline-mpmd")
            if d.is_error]
        if errs:
            raise ValueError(
                "MPMDPipelineEngine: unsafe stage cutting: "
                + "; ".join(d.message for d in errs))

    # -- program analysis ---------------------------------------------------
    def _split(self):
        block = self.program.block(0)
        ops = [op for op in block.ops
               if op.type not in ("feed", "fetch")]
        cuts = [_producer_index(ops, v) + 1 for v in self.cut_vars]
        if cuts != sorted(cuts):
            raise ValueError(
                f"cut_vars must be produced in order; got indices {cuts}")
        bounds = [0] + cuts + [len(ops)]
        return block, [ops[a:b] for a, b in zip(bounds[:-1], bounds[1:])]

    def _analyze(self, scope: Scope, feed_names):
        """Per-stage (params, act_inputs, feed_inputs, act_outputs)."""
        block, stages = self._split()
        persistable = set()
        for b in self.program.blocks:
            for name, v in b.vars.items():
                if v.persistable:
                    persistable.add(name)
        produced_by = {}
        for s, ops_s in enumerate(stages):
            for op in ops_s:
                for n in _op_writes(op):
                    produced_by.setdefault(n, s)
        stage_params, stage_acts_in, stage_feeds_in = [], [], []
        consumed_later: Dict[int, set] = {s: set()
                                          for s in range(self.n_stages)}
        for s, ops_s in enumerate(stages):
            params, acts, feeds = set(), set(), set()
            for op in ops_s:
                for n in _op_reads(op):
                    src = produced_by.get(n)
                    if src == s:
                        continue  # stage-internal value
                    if n in persistable:
                        params.add(n)
                    elif n in feed_names:
                        feeds.add(n)
                    elif src is not None and src < s:
                        acts.add(n)
                        consumed_later[src].add(n)
            stage_params.append(sorted(params))
            stage_acts_in.append(sorted(acts))
            stage_feeds_in.append(sorted(feeds))
        stage_acts_out = []
        for s in range(self.n_stages):
            outs = sorted(consumed_later[s])
            stage_acts_out.append(outs)
        return stages, stage_params, stage_acts_in, stage_feeds_in, \
            stage_acts_out

    # -- per-stage executables ---------------------------------------------
    def _build(self, scope: Scope, feed_names):
        (stages, s_params, s_ain, s_fin, s_aout) = \
            self._analyze(scope, feed_names)
        self._stages = stages
        self._s_params = s_params
        self._s_ain = s_ain
        self._s_fin = s_fin
        self._s_aout = s_aout
        self._fwd = []
        self._bwd = []
        last = self.n_stages - 1

        for s in range(self.n_stages):
            ops_s = stages[s]
            outs = list(s_aout[s]) + ([self.loss_name] if s == last
                                      else [])

            def make_f(ops_s=ops_s, outs=outs):
                def f(params, acts, feeds, key):
                    env = {}
                    env.update(params)
                    env.update(acts)
                    env.update(feeds)
                    rng_ctx = _RngCtx(key)

                    def block_runner(idx, sub_env=None):
                        e = sub_env if sub_env is not None else env
                        run_block_ops(self.program.block(idx), e,
                                      rng_ctx, {}, block_runner)
                        return e

                    run_block_ops(None, env, rng_ctx, {}, block_runner,
                                  ops=ops_s)
                    return {n: env[n] for n in outs}
                return f

            f = make_f()
            # placement: computation follows its committed inputs — the
            # schedule device_puts each stage's activations/feeds onto
            # stage_devices[s] (the reference's cross-place queue copy)
            self._fwd.append(jax.jit(f))

            def make_b(f=f):
                def b(params, acts, feeds, key, cot):
                    def g(params, acts):
                        return f(params, acts, feeds, key)
                    _, vjp = jax.vjp(g, params, acts)
                    dparams, dacts = vjp(cot)
                    return dparams, dacts
                return b

            self._bwd.append(jax.jit(make_b()))

        # optimizer ops grouped by the stage that owns the param
        self._opt_groups = None
        if self._opt_program is not None:
            owner = {}
            for s in range(self.n_stages):
                for p in s_params[s]:
                    owner.setdefault(p, s)
            groups: Dict[int, list] = {}
            opt_ops = [op for op in self._opt_program.block(0).ops]
            for op in opt_ops:
                pn = (op.input("Param") or [None])[0] \
                    if "Param" in op.input_slots() else None
                s = owner.get(pn, 0) if pn else 0
                groups.setdefault(s, []).append(op)
            self._opt_groups = groups
            self._opt_fns = {}
            for s, ops_g in groups.items():
                def make_u(ops_g=ops_g):
                    def u(env):
                        env = dict(env)
                        rng_ctx = _RngCtx(jax.random.PRNGKey(0))

                        def block_runner(idx, sub_env=None):
                            return sub_env if sub_env is not None \
                                else env

                        run_block_ops(None, env, rng_ctx, {},
                                      block_runner, ops=ops_g)
                        return env
                    return u
                self._opt_fns[s] = jax.jit(make_u())
        self._built = True

    # -- one training step --------------------------------------------------
    def run(self, scope: Scope, feed: Dict[str, np.ndarray],
            base_key=None):
        """One pipelined training step. feed arrays split on their
        leading dim into num_microbatches slices. Returns the mean loss
        over microbatches (float)."""
        feed_names = sorted(feed)
        if not self._built:
            self._build(scope, set(feed_names))
        n_micro = self.n_micro
        for n, a in feed.items():
            if a.shape[0] % n_micro:
                raise ValueError(
                    f"feed {n!r} batch {a.shape[0]} not divisible by "
                    f"num_microbatches={n_micro}")
        micro = [{n: jnp.asarray(a[m * (a.shape[0] // n_micro):
                                   (m + 1) * (a.shape[0] // n_micro)])
                  for n, a in feed.items()} for m in range(n_micro)]
        key = base_key if base_key is not None else \
            jax.random.PRNGKey(0)

        params = {s: {n: jax.device_put(_scope_val(scope, n),
                                        self.stage_devices[s])
                      for n in self._s_params[s]}
                  for s in range(self.n_stages)}
        last = self.n_stages - 1

        # ---- schedule-driven dispatch: interleaved 1F1B (or the
        # gpipe fill/drain baseline). Every schedule runs the SAME
        # F/B events with the same fold_in keys — only the order (and
        # therefore the stash cap and bubble) differs. The slot table
        # is statically verified against the F/B dependence DAG
        # (analysis/races.verify_pipeline_schedule) before anything
        # dispatches.
        import time
        from .pipeline import pipeline_schedule
        sched = pipeline_schedule(self.n_stages, n_micro,
                                  self.n_devices, kind=self.schedule)
        self._verify_schedule(sched)
        t_step = time.perf_counter()
        spans: List[dict] = []
        dispatch_ms = 0.0
        xfer_bytes = 0
        stash: Dict[tuple, tuple] = {}
        stash_live = stash_peak = 0
        acts: List[Dict[str, jax.Array]] = [dict()
                                            for _ in range(n_micro)]
        cot_acts: List[Dict[str, jax.Array]] = [dict()
                                                for _ in range(n_micro)]
        losses = [None] * n_micro
        g_params = [None] * self.n_stages
        inv = 1.0 / n_micro
        for tick, dev_idx, kind, s, m in sched["events"]:
            dev = self.stage_devices[s]
            t0 = time.perf_counter()
            if kind == "F":
                a_in = {n: jax.device_put(acts[m][n], dev)
                        for n in self._s_ain[s]}
                f_in = {n: jax.device_put(micro[m][n], dev)
                        for n in self._s_fin[s]}
                skey = jax.random.fold_in(jax.random.fold_in(key, m), s)
                stash[(s, m)] = (a_in, f_in, skey)
                stash_live += 1
                stash_peak = max(stash_peak, stash_live)
                xfer_bytes += sum(int(getattr(v, "nbytes", 0))
                                  for v in a_in.values())
                outs = self._fwd[s](params[s], a_in, f_in, skey)
                acts[m].update(outs)
                if s == last:
                    losses[m] = outs[self.loss_name]
            else:
                # reverse queue transfer: cotangents produced on the
                # consumer stage's device hop back to stage s; a skip
                # connection consumed by several stages accumulates by
                # addition below, matching sum-of-uses vjp semantics
                a_in, f_in, skey = stash.pop((s, m))
                stash_live -= 1
                cot_full = {n: jax.device_put(cot_acts[m][n], dev)
                            for n in self._s_aout[s]}
                xfer_bytes += sum(int(getattr(v, "nbytes", 0))
                                  for v in cot_full.values())
                if s == last:
                    cot_full[self.loss_name] = jnp.asarray(
                        inv, dtype=losses[m].dtype)
                dp, da = self._bwd[s](params[s], a_in, f_in, skey,
                                      cot_full)
                if g_params[s] is None:
                    g_params[s] = dp
                else:
                    g_params[s] = jax.tree_util.tree_map(
                        jnp.add, g_params[s], dp)
                for n, v in da.items():
                    if n in cot_acts[m]:
                        cot_acts[m][n] = cot_acts[m][n] + v
                    else:
                        cot_acts[m][n] = v
            t1 = time.perf_counter()
            dispatch_ms += (t1 - t0) * 1e3
            spans.append({"tick": tick, "device": dev_idx,
                          "kind": kind, "stage": s, "micro_batch": m,
                          "t0_ms": round((t0 - t_step) * 1e3, 3),
                          "dur_ms": round((t1 - t0) * 1e3, 3)})
        window_ms = (time.perf_counter() - t_step) * 1e3
        self._record_stats(sched, spans, dispatch_ms, window_ms,
                           stash_peak, xfer_bytes)

        # ---- optimizer update per stage ------------------------------
        if self._opt_groups is not None:
            # shared params: sum grads across stages, update once (at
            # the owner stage)
            # accumulate on ONE device (stage 0's): shared-param grads
            # arrive committed to different stage devices, and adding
            # arrays committed to different devices is an error
            dev0 = self.stage_devices[0]
            grad_env: Dict[str, jax.Array] = {}
            for s in range(self.n_stages):
                if g_params[s] is None:
                    continue
                for n, g in g_params[s].items():
                    g = g.astype(jnp.float32) if g.dtype == jnp.bfloat16 \
                        else g
                    g = jax.device_put(g, dev0)
                    grad_env[n] = grad_env[n] + g if n in grad_env \
                        else g
            for s, ops_g in self._opt_groups.items():
                env = {}
                needed = set()
                for op in ops_g:
                    needed.update(_op_reads(op))
                for n in needed:
                    if n.endswith("@GRAD"):
                        base = n[: -len("@GRAD")]
                        if base in grad_env:
                            env[n] = grad_env[base]
                        else:
                            continue
                    else:
                        v = _scope_val(scope, n, none_ok=True)
                        if v is not None:
                            env[n] = v
                out_env = self._opt_fns[s](env)
                for op in ops_g:
                    for n in _op_writes(op):
                        if n in out_env:
                            scope.var(n).set_value(out_env[n])
        loss = float(np.mean([np.asarray(l) for l in losses]))
        return loss

    # -- schedule verification & stats ---------------------------------------
    def _verify_schedule(self, sched):
        """Statically prove the slot table safe before dispatching:
        every F/B event must respect the pipeline dependence DAG and
        no device may run two events in one tick (analysis/races)."""
        from ..analysis.races import verify_pipeline_schedule
        diags = verify_pipeline_schedule(
            sched["events"], self.n_stages, self.n_micro,
            label=f"mpmd-{self.schedule}")
        errors = [d for d in diags if d.severity.value >= 2]
        if errors:
            raise RuntimeError(
                "MPMDPipelineEngine: unsafe schedule: "
                + "; ".join(d.message for d in errors))

    def _record_stats(self, sched, spans, dispatch_ms, window_ms,
                      stash_peak, xfer_bytes):
        from .pipeline import gpipe_bubble_fraction
        self.last_stats = {
            "schedule": self.schedule,
            "n_stages": self.n_stages,
            "n_devices": self.n_devices,
            "micro_batches": self.n_micro,
            "n_chunks": sched["n_chunks"],
            # measured from the slot table the step actually executed
            "bubble_frac": sched["bubble_frac"],
            # analytic fill/drain bubble at the same microbatch count,
            # for the --compare-pipeline A/B without a second run
            "bubble_frac_gpipe": gpipe_bubble_fraction(
                self.n_stages, self.n_micro),
            "stash_peak": stash_peak,
            "activation_exchange_bytes": int(xfer_bytes),
            "pipeline_fill_frac": (dispatch_ms / window_ms
                                   if window_ms > 0 else 0.0),
            "spans": spans,
        }
        if self.cut_plan is not None:
            self.last_stats["stage_hbm_bytes"] = list(
                self.cut_plan.stage_hbm_bytes)
        self._emit_metrics()

    def _emit_metrics(self):
        try:
            from ..observability import metrics as M
        except Exception:
            return
        st = self.last_stats
        M.counter("pt_pipeline_steps_total",
                  "pipeline training steps").inc(
            1, schedule=str(st["schedule"]))
        M.gauge("pt_pipeline_stages", "pipeline stage count").set(
            st["n_stages"], schedule=str(st["schedule"]))
        M.gauge("pt_pipeline_bubble_frac",
                "measured pipeline bubble fraction").set(
            float(st["bubble_frac"]), schedule=str(st["schedule"]))
        M.counter("pt_pipeline_activation_exchange_bytes_total",
                  "bytes moved across stage boundaries").inc(
            int(st["activation_exchange_bytes"]),
            schedule=str(st["schedule"]))
        for s, b in enumerate(st.get("stage_hbm_bytes", ())):
            M.gauge("pt_pipeline_stage_hbm_peak_bytes",
                    "static per-stage HBM estimate").set(
                int(b), stage=str(s))


def _scope_val(scope: Scope, name, none_ok=False):
    var = scope.find_var(name)
    if var is None or not var.is_initialized():
        if none_ok:
            return None
        raise KeyError(name)
    v = var.get_value()
    return v.array if isinstance(v, LoDTensor) else v
