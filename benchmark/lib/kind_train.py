"""The closed training loop every `train` family's cells run, and the
readings the comparison takes from it.

One trainer, the loop every Fluid user writes:

    exe = fluid.Executor(fluid.TPUPlace(0)); exe.run(startup)
    loss, = exe.run(main, feed=batch_i, fetch_list=[avg_cost])

NumPy in, NumPy out, every step; the fetch fences the step. Set-up builds
ONE session (programs, executor, scope), puts the seed's weights in it,
drives it through its first three steps on the pool's first three batches
(the comparison's readings), warms up, and hands the same session to the
window. No flag or environment variable of the program is set.
"""
from __future__ import annotations

import gc
import math
import time
import warnings

import numpy as np

PROOF_STEPS = 3
WARM_STEPS = 5
TRACE_MAX_STEPS = 16
TRACE_MAX_SECONDS = 4.0


class Session:
    """The compiled step with its state. `step(i)` is the window's call."""

    def __init__(self, family, sz, tr, seed, rehearsal):
        import paddle_tpu as fluid
        from paddle_tpu.core.scope import Scope
        from paddle_tpu.kernels import registry
        self.fluid, self.registry = fluid, registry
        self.family, self.sz, self.tr, self.seed = family, sz, tr, seed
        self.pool = family.make_pool(sz, tr, seed)
        self.items = [family.items(b) for b in self.pool]
        self.main, self.startup, self.cost = family.build(fluid, sz, seed)
        self.scope = Scope()
        registry.reset_stats()
        place = fluid.CPUPlace() if rehearsal else fluid.TPUPlace(0)
        with fluid.scope_guard(self.scope):
            self.exe = fluid.Executor(place)
            self.exe.run(self.startup)
        self.names = family.param_names(sz)
        have = {p.name: tuple(p.shape) for p in self.main.all_parameters()}
        want = family.param_shapes(sz)
        if have != want:
            odd = sorted(set(have.items()) ^ set(want.items()))[:6]
            raise RuntimeError(f"the program's parameters are not the "
                               f"family's: {odd}")
        # drop the startup program's own draws first, so that the seed's
        # weights never stand beside them in the peak memory reading
        import jax.numpy as jnp
        for n in self.names:
            self.scope.find_var(n).set_value(jnp.zeros((), jnp.float32))
        params = family.init_params(sz, seed)
        for n in self.names:
            self.scope.find_var(n).set_value(params[n])

    def get(self, name):
        value = self.scope.find_var(name).get_value()
        return getattr(value, "array", value)

    def step(self, i):
        with self.fluid.scope_guard(self.scope):
            out = self.exe.run(self.main, feed=self.pool[i % len(self.pool)],
                               fetch_list=[self.cost])
        return float(np.asarray(out[0]).reshape(()))

    def prove(self):
        """The first steps from the seed, through `step`: each loss, the
        first gradient's norms from the state after ONE step, the norms of
        the parameters' change after all of them."""
        losses = [self.step(0)]
        grad = self.family.read_first_gradient_norms(
            self.get, self.names, self.sz)
        sample = self.family.read_first_gradient_sample(
            self.get, self.names, self.sz, self.seed)
        losses += [self.step(i) for i in range(1, PROOF_STEPS)]
        delta = self.family.read_delta_norms(
            self.get, self.names, self.sz, self.seed)
        return {"losses": losses, "grad_norms": grad, "grad_sample": sample,
                "delta_norms": delta}

    def engine_state(self):
        eng = self.exe._engine
        return {"traces": int(eng.counters["traces"]),
                "executables": list(eng.step_executables())}

    def routing(self):
        return {k: dict(v) for k, v in
                self.registry.dispatch_stats()["per_kernel"].items()}

    def reseed(self, seed):
        """The same compiled step on another seed's weights and batches,
        its optimizer state as the startup program leaves it (for reading
        many seeds in one process)."""
        import jax
        dev = next(iter(self.get(self.names[0]).devices()))
        self.seed = seed
        self.pool = self.family.make_pool(self.sz, self.tr, seed)
        state = self.family.init_params(self.sz, seed)
        state.update(self.family.fresh_optimizer_state(self.sz, self.names))
        for n, a in state.items():
            self.scope.find_var(n).set_value(jax.device_put(a, dev))

    def close(self):
        self.exe = self.scope = self.main = self.startup = None
        gc.collect()


def _fallback_warnings(caught):
    return [str(w.message)[:200] for w in caught
            if "EAGER" in str(w.message) or "island" in str(w.message)]


def _checks(before, after, fell_back, failed, routing, expected):
    """{name: (value, limit)}: what makes a run a failed run and not a
    slow one. Nothing compiled inside the window, one executable per
    jitted step, no island or eager fallback, every step's loss finite,
    the kernels routed as the family expects."""
    checks = {
        "traces_in_window": (after["traces"] - before["traces"], 0),
        # one XLA executable per jitted step (startup, main): a second is
        # the whole step compiled again, which the trace counter cannot see
        "steps_not_one_executable": (
            sum(n != 1 for n in after["executables"])
            + (0 if after["executables"] else 1), 0),
        "fallback_warnings": (len(fell_back), 0),
        "failed_steps": (failed, 0),
    }
    for kernel, want in expected.items():
        custom = routing.get(kernel, {}).get("custom", 0)
        if want == "custom":      # routed: at least one site took it
            checks[f"{kernel}_unrouted"] = (0 if custom else 1, 0)
        else:                     # bypassed: no site took it
            checks[f"{kernel}_routed"] = (custom, 0)
    return checks


def run(cell, seed, seconds, trace, rehearsal, t0, say, session_hook=None):
    """Set-up, window, checks, memory, then the reference and the
    comparison. Returns the parts of the result line."""
    import jax
    from . import compare
    fam = cell.family
    sz = fam.sizes(cell.config, rehearsal)
    tr = fam.traffic(cell.traffic, rehearsal)
    devices = jax.devices()[:cell.chips]

    with warnings.catch_warnings(record=True) as caught:
        warnings.simplefilter("always")
        sess = Session(fam, sz, tr, seed, rehearsal)
        if session_hook is not None:      # tests plant faults here
            session_hook(sess)
        say("session built: programs, startup run, the seed's weights set")
        got = sess.prove()
        say(f"proof steps: losses {got['losses']}")
        for i in range(PROOF_STEPS, PROOF_STEPS + WARM_STEPS):
            sess.step(i)
        before = sess.engine_state()
        n_warm = PROOF_STEPS + WARM_STEPS
        limit_s = min(seconds, TRACE_MAX_SECONDS) if trace else seconds
        limit_n = TRACE_MAX_STEPS if trace else None
        # the interpreter's one full collection of a young process fell on
        # the window's 21st step (170 ms); have it now, as set-up
        gc.collect()
        trace_dir = None
        if trace:
            import tempfile
            trace_dir = tempfile.mkdtemp(prefix="bench_trace_")
            opts = jax.profiler.ProfileOptions()
            opts.python_tracer_level = 0
            opts.host_tracer_level = 2
            jax.profiler.start_trace(trace_dir, profiler_options=opts)
        step_s, losses, items = [], [], 0.0
        w0 = time.perf_counter()
        setup_s = w0 - t0
        try:
            i = n_warm
            while True:
                a = time.perf_counter()
                if a - w0 >= limit_s or (limit_n and len(step_s) >= limit_n):
                    break
                if trace:
                    with jax.profiler.TraceAnnotation("bench.step"):
                        loss = sess.step(i)
                else:
                    loss = sess.step(i)
                step_s.append(time.perf_counter() - a)
                losses.append(loss)
                items += sess.items[i % len(sess.items)]
                i += 1
            window_s = time.perf_counter() - w0
        finally:
            if trace:
                jax.profiler.stop_trace()
        after = sess.engine_state()
        routing = sess.routing()
        fell_back = _fallback_warnings(caught)

    stats = [d.memory_stats() or {} for d in devices]
    # live arrays and the loaded executables' scratch are counted apart by
    # this runtime (bytes_in_use + bytes_reserved + largest free block =
    # bytes_limit), so the peak a co-tenant could not have used is the sum
    peak = max((s.get("peak_bytes_in_use", 0)
                + s.get("peak_bytes_reserved", 0)) for s in stats)
    say(f"memory_stats {stats[0]}")
    say(f"window: {len(step_s)} steps in {window_s:.3f} s; loss "
        f"{losses[0]:.4f} -> {losses[-1]:.4f}; routing {routing}")

    failed = sum(not math.isfinite(x) for x in losses)
    checks = _checks(before, after, fell_back, failed, routing,
                     fam.expected_routing(sz, tr, rehearsal))
    if fell_back:
        say(f"engine fell back: {fell_back}")

    step_ms = np.asarray(step_s) * 1e3
    e2e = {"items_per_s": items / window_s,
           "step_ms_p95": float(np.percentile(step_ms, 95)),
           "peak_hbm_gib": peak / 2 ** 30,
           "setup_s": setup_s}
    slow = [(int(j), round(float(step_ms[j]), 1))
            for j in np.argsort(step_ms)[::-1][:3]]
    say(f"step ms: median {np.median(step_ms):.3f} p95 "
        f"{e2e['step_ms_p95']:.3f} max {step_ms.max():.3f}; slowest "
        f"(index in window, ms) {slow}")

    traced = None
    if trace:
        from . import trace as trace_lib
        import shutil
        try:
            traced = trace_lib.summarize(
                trace_lib.newest_xplane(trace_dir),
                lambda op: fam.classify_kernel(*trace_lib.signature(op),
                                               op.name))
        finally:
            shutil.rmtree(trace_dir, ignore_errors=True)
        traced.update(window_s=window_s, steps=len(step_s))

    sess.close()
    del sess
    gc.collect()
    t_ref = time.perf_counter()
    # the reference draws its own batches from the seed: nothing of the
    # session reaches it
    ref = fam.run_reference(sz, tr, fam.make_pool(sz, tr, seed), seed,
                            PROOF_STEPS)
    values, where = compare.gaps(got, ref)
    say(f"reference: losses {ref['losses']} in "
        f"{time.perf_counter() - t_ref:.1f} s; gaps {values}; read at {where}")
    compared, ok = compare.judge(values, cell.limits_for(rehearsal))
    for name, (value, limit) in checks.items():
        compared[name] = {"value": value, "limit": limit}
        ok = ok and value <= limit
    return {"correct": bool(ok), "attempted": len(step_s), "failed": failed,
            "end_to_end": e2e, "traced": traced, "compared": compared,
            "memory_peak_bytes": int(peak), "sizes": sz, "traffic": tr,
            "routing": routing}
