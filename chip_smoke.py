"""chip_smoke.py — does the compiled training step come up on the TPU?

The quickest proof that the framework's main path still starts on the
chip: Program -> fluid.Executor(fluid.TPUPlace(0)) -> core/engine.py
whole-block jit -> kernel registry, at transformer-base's full width
with seeded random weights. One process; nothing is spawned.

  python chip_smoke.py                  legs A, B, C on one TPU chip
  python chip_smoke.py --four-chip      leg D on a four-chip host,
                                        after a 5-step one-chip
                                        reference (A, B, C are the
                                        one-chip run's); fails under
                                        four chips
  python chip_smoke.py --rehearse-cpu   tiny sizes on a CPU host, to
                                        debug the script itself; every
                                        line says so, and nothing it
                                        prints is a device result

Legs (a leg that raises ends the run non-zero; a run does every leg of
its mode):
  A  transformer-base, B=96 S=128, bf16 AMP + Adam, 20 steps on one
     fixed batch: loss falls, every persistable on the TPU, the step
     traced once and compiled once, fused_adam routed; then 5 steps
     from the same init with FLAGS_use_custom_kernels=0 must give the
     same losses.
  B  the same model at B=8 S=1024 and B=4 S=4096 (dropout 0.1): the
     flash-attention forward and fused backward kernels with the
     in-kernel hardware-PRNG dropout, compiled by Mosaic; no lowered decision.
  C  every registered kernel compiled and held to its parity
     tolerance (kernels/parity.py), the dropout mask-identity probe,
     quantized_matmul and the tuning/variants.py GEMM variants.
  D  (--four-chip) leg A's model over a dp2 x mp2 mesh and through
     CompiledProgram.with_data_parallel(places=fluid.tpu_places()):
     shards on four devices, losses track one chip, every kernel
     decision counted "lowered" (XLA partitions the step).

Without --rehearse-cpu the script exits non-zero, printing no result,
unless jax.default_backend() == "tpu". The last stdout line of a
passing run is {"ok": true, "device": {...}} as JAX reports the device.

Times printed here are set-up seconds (trace + compile + first step);
the script measures no rate.
"""
from __future__ import annotations

import argparse
import importlib
import json
import sys
import time
import warnings

import numpy as np

_T0 = time.perf_counter()

# kernels-on vs kernels-off loss agreement over leg A's 5 steps: the two
# programs differ only in who computes the Adam update (Mosaic kernel
# vs XLA fusion). Measured on the v5e: 1.7e-5 (CHANGES.md, PR 21); the
# bound leaves a decade for another compiler's fusion choices.
_ONOFF_RTOL = 2e-4
# four-chip vs one-chip loss agreement over 5 steps. Measured on 4 x
# v5e: 2.2e-5 (dp2 x mp2), 9.8e-6 (dp4) — the partitioned step draws the
# same dropout bits, so this is reduction-order rounding (CHANGES.md,
# PR 21)
_MESH_RTOL = 2e-4

FULL = dict(
    model=dict(src_vocab_size=32000, trg_vocab_size=32000, dropout=0.1,
               fuse_attention=True),
    a_shape=(96, 128), a_steps=20, onoff_steps=5,
    b_shapes=((8, 1024), (4, 4096)), b_steps=5)
# rehearsal only: same code path, sizes a CPU interpreter can run
TINY = dict(
    model=dict(src_vocab_size=2048, trg_vocab_size=2048, dropout=0.1,
               fuse_attention=True, d_model=64, d_inner=128, n_head=2,
               n_layer=1),
    a_shape=(4, 16), a_steps=6, onoff_steps=3,
    b_shapes=((1, 256),), b_steps=2)


class Smoke:
    def __init__(self, rehearse):
        import jax
        dev = jax.devices()[0]
        self.device = {"platform": dev.platform,
                       "kind": dev.device_kind,
                       "count": len(jax.devices())}
        import paddle_tpu as fluid
        self.rehearse = rehearse
        self.sizes = TINY if rehearse else FULL
        self.place = fluid.CPUPlace() if rehearse else fluid.TPUPlace(0)
        self._tag = ("[platform={platform} device_kind={kind!r} "
                     "devices={count}]".format(**self.device)
                     + (" [CPU REHEARSAL - not a device result]"
                        if rehearse else ""))

    def say(self, msg):
        print(f"{self._tag} t+{time.perf_counter() - _T0:.0f}s {msg}",
              flush=True)

    def check(self, cond, msg):
        if not cond:
            raise AssertionError(f"{self._tag} {msg}")


def _build(model_kw):
    import paddle_tpu as fluid
    from paddle_tpu import models
    cfg = models.transformer.transformer_base(**model_kw)
    fluid.framework.unique_name.reset()
    main, startup = fluid.Program(), fluid.Program()
    with fluid.program_guard(main, startup):
        cost, _, _ = models.transformer_train(cfg)
        opt = fluid.contrib.mixed_precision.decorate(
            fluid.optimizer.AdamOptimizer(learning_rate=2e-4))
        opt.minimize(cost)
    return cfg, main, startup, cost


def _persistables(program, scope):
    from paddle_tpu.core.scope import LoDTensor
    out = {}
    for v in program.list_vars():
        if not v.persistable:
            continue
        var = scope.find_var(v.name)
        if var is None or not var.is_initialized():
            continue
        val = var.get_value()
        out[v.name] = val.array if isinstance(val, LoDTensor) else val
    return out


def _train(sm, built, batch_shape, steps, label):
    """`steps` steps on one fixed batch through Executor.run from a
    fresh scope (same seed -> same init and dropout stream). Returns
    (losses, scope)."""
    import paddle_tpu as fluid
    from paddle_tpu import models
    from paddle_tpu.core.scope import Scope
    cfg, main, startup, cost = built
    b, s = batch_shape
    feed = models.transformer.make_batch(cfg, b, s, s)
    scope = Scope()
    losses = []
    with fluid.scope_guard(scope), warnings.catch_warnings(record=True) \
            as caught:
        warnings.simplefilter("always")
        exe = fluid.Executor(sm.place)
        exe.run(startup)
        t0 = time.perf_counter()
        for i in range(steps):
            out = exe.run(main, feed=feed, fetch_list=[cost])
            losses.append(float(np.asarray(out[0]).reshape(())))
            if i == 0:
                setup_s = time.perf_counter() - t0
                traces = exe._engine.counters["traces"]
    fell_back = [str(w.message) for w in caught
                 if "EAGER" in str(w.message)
                 or "island" in str(w.message)]
    sm.check(not fell_back, f"{label}: engine fell back: {fell_back}")
    sm.check(exe._engine.counters["traces"] == traces,
             f"{label}: retraced after warm-up "
             f"({traces} -> {exe._engine.counters['traces']})")
    # one XLA executable per jitted step (startup, main): a second one
    # is the whole step compiled again for other argument shardings,
    # which the trace counter cannot see
    n_exec = exe._engine.step_executables()
    sm.check(n_exec and set(n_exec) == {1},
             f"{label}: executables per jitted step {n_exec}, expected "
             f"1 each (the step was compiled more than once)")
    with fluid.scope_guard(scope):
        stats = exe._engine.compiled_stats(main, scope, feed,
                                           [cost.name])
    sm.check(stats is not None,
             f"{label}: no compiled executable (island/eager fallback)")
    sm.check(all(np.isfinite(losses)), f"{label}: losses {losses}")
    sm.check(len(set(losses)) == len(losses),
             f"{label}: losses not pairwise distinct {losses}")
    sm.say(f"{label}: B={b} S={s} steps={steps} setup_s={setup_s:.1f} "
           f"loss {losses[0]:.4f} -> {losses[-1]:.4f}")
    return losses, scope


def _kernel_counts(name):
    from paddle_tpu.kernels import registry
    return dict(registry.dispatch_stats()["per_kernel"].get(name, {}))


def leg_a(sm):
    from paddle_tpu.core.flags import set_flags
    from paddle_tpu.kernels import registry
    built = _build(sm.sizes["model"])
    main = built[1]

    registry.reset_stats()
    losses, scope = _train(sm, built, sm.sizes["a_shape"],
                           sm.sizes["a_steps"], "leg A")
    sm.check(losses[-1] < losses[0], f"leg A: loss did not fall {losses}")
    params = _persistables(main, scope)
    off_chip = {n: sorted(d.platform for d in a.devices())
                for n, a in params.items()
                if any(d.platform != sm.device["platform"]
                       for d in a.devices())}
    sm.check(params and not off_chip,
             f"leg A: persistables off the {sm.device['platform']}: "
             f"{off_chip}")
    # every Adam update is either routed to the Pallas kernel or is
    # under the size floor; the two counts must split exactly that way
    sizes = [int(np.prod(p.shape)) for p in main.all_parameters()]
    n_big = sum(n >= registry.min_numel() for n in sizes)
    adam = _kernel_counts("fused_adam")
    sm.check(adam.get("custom", 0) > 0 and n_big > 0
             and adam["custom"] % n_big == 0
             and adam.get("lowered", 0)
             == adam["custom"] // n_big * (len(sizes) - n_big)
             and set(adam) <= {"custom", "lowered", "native_view",
                               "flat_view"}
             and adam.get("native_view", 0) + adam.get("flat_view", 0)
             == adam["custom"],
             f"leg A: fused_adam dispatch {adam} does not match "
             f"{n_big} params over / {len(sizes) - n_big} under the "
             f"{registry.min_numel()}-element floor")
    # S=128 sits below the measured kernel/composed crossover
    # (kernels/flash_attention.py _KERNEL_MIN_SEQ_PRODUCT), so every
    # attention site here is a deliberate "lowered"; the interpreter
    # (rehearsal) always takes the kernels
    fa = _kernel_counts("flash_attention")
    want = "custom" if sm.rehearse else "lowered"
    sm.check(set(fa) == {want},
             f"leg A: flash_attention dispatch {fa}, expected only "
             f"{want!r} at S={sm.sizes['a_shape'][1]}")
    sm.say(f"leg A: {len(params)} persistables on "
           f"{sm.device['platform']}; fused_adam {adam}; "
           f"flash_attention {fa}")
    del scope, params

    n = sm.sizes["onoff_steps"]
    set_flags({"FLAGS_use_custom_kernels": False})
    try:
        registry.reset_stats()
        off, _ = _train(sm, built, sm.sizes["a_shape"], n,
                        "leg A kernels-off")
        sm.check(not _kernel_counts("fused_adam").get("custom"),
                 "leg A kernels-off: fused_adam still routed")
    finally:
        set_flags({"FLAGS_use_custom_kernels": True})
    rel = max(abs(a - b) / abs(b) for a, b in zip(losses[:n], off))
    sm.check(rel <= _ONOFF_RTOL,
             f"leg A: kernels-on {losses[:n]} vs kernels-off {off}: "
             f"rel diff {rel:.3g} > {_ONOFF_RTOL}")
    sm.say(f"leg A: kernels-on vs kernels-off over {n} steps: max rel "
           f"loss diff {rel:.3g} (tolerance {_ONOFF_RTOL})")


def leg_b(sm):
    from paddle_tpu.kernels import registry
    built = _build(sm.sizes["model"])
    for shape in sm.sizes["b_shapes"]:
        registry.reset_stats()
        label = f"leg B S={shape[1]}"
        _train(sm, built, shape, sm.sizes["b_steps"], label)
        fa = _kernel_counts("flash_attention")
        sm.check(fa.get("custom", 0) > 0 and set(fa) == {"custom"},
                 f"{label}: flash_attention dispatch {fa}: every "
                 f"attention site must route to the Pallas kernels")
        sm.say(f"{label}: flash_attention {fa}, no lowered decision")


def leg_c(sm):
    from paddle_tpu.kernels import parity, registry
    from paddle_tpu.tuning import variants
    sm.check(registry.interpret() == (sm.device["platform"] == "cpu"),
             "registry.interpret() must be true on CPU hosts only")
    mask_case = parity.Case(
        "flash_attention",
        "flash_attention/dropout_mask_identity/fwd=dq=dkv",
        parity.dropout_mask_identity)
    rows = parity.run_all() + [parity.run_case(mask_case)] + [
        parity.run_case(case) for _, case in variants.variant_cases()]
    for r in rows:
        sm.say(f"leg C: {r['label']}: {r['metric']}={r['value']:.4g} "
               f"(tol {r['tol']:.4g}) {'ok' if r['passed'] else 'MISS'}"
               + (f" [{r['note']}]" if "note" in r else ""))
    missing = parity.missing_parity()
    sm.check(not missing, f"leg C: kernels with no parity case {missing}")
    bad = [r["label"] for r in rows if not r["passed"]]
    sm.check(not bad, f"leg C: parity missed: {bad}")
    sm.say(f"leg C: {len(rows)} kernel cases compiled"
           f"{' (interpreted)' if registry.interpret() else ''} and "
           f"within tolerance")


def leg_d(sm):
    """Four chips: leg A's model sharded dp2 x mp2, then 4-way data
    parallel through CompiledProgram, each against the same steps on
    one chip."""
    import paddle_tpu as fluid
    from paddle_tpu import models
    from paddle_tpu.core.engine import Engine
    from paddle_tpu.core.scope import Scope
    from paddle_tpu.kernels import registry
    from paddle_tpu.parallel import (
        DistributedStrategy, transformer_rules, transformer_feed_rules)
    # rehearsal: four of the host's virtual CPU devices
    # (XLA_FLAGS=--xla_force_host_platform_device_count=8)
    places = fluid.cpu_places(4) if sm.rehearse else fluid.tpu_places()
    devices = [p.jax_device() for p in places[:4]]
    sm.check(len(set(devices)) == 4,
             f"--four-chip needs four local "
             f"{'CPU devices' if sm.rehearse else 'TPU chips'}, JAX has "
             f"{len(set(devices))}")
    built = _build(sm.sizes["model"])
    cfg, main, startup, cost = built
    b, s = sm.sizes["a_shape"]
    feed = models.transformer.make_batch(cfg, b, s, s)
    n = sm.sizes["onoff_steps"]
    # [0]: the reference's scope (a whole model on device 0) is dropped
    one_chip_losses = _train(sm, built, (b, s), n,
                             "leg D one-chip reference")[0]
    registry.reset_stats()

    def in_use():
        # CPU devices (rehearsal) keep no memory statistics
        return [(d.memory_stats() or {}).get("bytes_in_use")
                for d in devices]

    def tracks(losses, label):
        rel = max(abs(a - r) / abs(r)
                  for a, r in zip(losses, one_chip_losses[:n]))
        sm.check(all(np.isfinite(losses)) and rel <= _MESH_RTOL,
                 f"{label}: losses {losses} vs one chip "
                 f"{one_chip_losses[:n]}: rel diff {rel:.3g}")
        return rel

    # -- dp2 x mp2 through the SPMD strategy path -----------------------
    strat = DistributedStrategy(
        axes={"dp": 2, "mp": 2}, rules=transformer_rules(),
        feed_rules=transformer_feed_rules(sp_axis=None),
        devices=devices)
    scope = Scope()
    with fluid.scope_guard(scope):
        fluid.Executor(sm.place).run(startup)
        eng = Engine(strategy=strat)
        losses = [float(np.asarray(eng.run(main, scope, None, feed,
                                           [cost.name])[0]).reshape(()))
                  for _ in range(n)]
    params = _persistables(main, scope)
    tp = {k: a for k, a in params.items()
          if k.endswith(("_q.w_0", "_fc1.w_0", "_o.w_0", "_fc2.w_0"))}
    narrow = {k: len(a.sharding.device_set) for k, a in tp.items()
              if len(a.sharding.device_set) != 4
              or a.sharding.is_fully_replicated}
    sm.check(tp and not narrow,
             f"leg D: tensor-parallel params not spread over four "
             f"devices: {narrow}")
    total = sum(a.nbytes for a in params.values())
    on0 = sum(sh.data.nbytes for a in params.values()
              for sh in a.addressable_shards if sh.device == devices[0])
    use = in_use()
    sm.check((sm.rehearse or all(u > 0 for u in use)) and on0 < total,
             f"leg D: bytes_in_use {use}; device 0 holds {on0} of "
             f"{total} model bytes")
    rel = tracks(losses, "leg D dp2xmp2")
    sm.say(f"leg D dp2xmp2: {len(tp)} tensor-parallel params on 4 "
           f"devices each; device 0 holds {on0} of {total} model "
           f"bytes; bytes_in_use {use}; loss {losses[0]:.4f} -> "
           f"{losses[-1]:.4f}, max rel diff vs one chip {rel:.3g}")
    del eng, scope, params, tp

    # -- 4-way data parallel through the fluid API ----------------------
    scope = Scope()
    with fluid.scope_guard(scope):
        exe = fluid.Executor(sm.place)
        exe.run(startup)
        compiled = fluid.CompiledProgram(main).with_data_parallel(
            loss_name=cost.name, places=places[:4])
        losses = [float(np.mean(np.asarray(
            exe.run(compiled, feed=feed, fetch_list=[cost])[0])))
            for _ in range(n)]
    use = in_use()
    sm.check(sm.rehearse or all(u > 0 for u in use),
             f"leg D dp4: bytes_in_use {use}")
    rel = tracks(losses, "leg D dp4")
    sm.say(f"leg D dp4 (with_data_parallel): bytes_in_use {use}; loss "
           f"{losses[0]:.4f} -> {losses[-1]:.4f}, max rel diff vs one "
           f"chip {rel:.3g}")
    # XLA partitions both steps, and a Mosaic kernel cannot be
    # partitioned automatically: every decision was made (counted),
    # and every one is "lowered"
    stats = registry.dispatch_stats()["per_kernel"]
    sm.check(all(stats.get(k, {}).get("lowered", 0) > 0
                 for k in ("fused_adam", "flash_attention"))
             and not any(set(v) - {"lowered"} for v in stats.values()),
             f"leg D: kernel decisions under a mesh {stats}: expected "
             f"fused_adam and flash_attention counted, 'lowered' only")
    sm.say(f"leg D: kernel decisions under the meshes, all lowered: "
           f"{ {k: v['lowered'] for k, v in stats.items()} }")


def main(argv=None):
    ap = argparse.ArgumentParser(description=__doc__.splitlines()[0])
    ap.add_argument("--rehearse-cpu", action="store_true",
                    help="tiny sizes on a CPU host (debugging the "
                         "script; proves nothing about the chip)")
    ap.add_argument("--four-chip", action="store_true",
                    help="leg D on a four-chip host (after a one-chip "
                         "reference); fails with under four chips")
    args = ap.parse_args(argv)

    import jax
    want = "cpu" if args.rehearse_cpu else "tpu"
    if jax.default_backend() != want:
        found = sorted({d.platform for d in jax.devices()})
        sys.exit(f"chip_smoke: needs jax.default_backend() == {want!r}; "
                 f"JAX found platforms {found} (default backend "
                 f"{jax.default_backend()!r}, {len(jax.devices())} "
                 f"device(s)). No result.")
    sm = Smoke(args.rehearse_cpu)
    if args.rehearse_cpu:
        # route through the kernels under the Pallas interpreter, so
        # the rehearsal walks the same dispatch code as the chip run
        from paddle_tpu.kernels import registry
        registry._INTERPRET = True
        importlib.import_module(
            "paddle_tpu.kernels.flash_attention")._INTERPRET = True

    sm.say(f"jax {jax.__version__}; import+backend "
           f"{time.perf_counter() - _T0:.1f}s")
    if args.four_chip:
        leg_d(sm)
        legs = "D"
    else:
        leg_a(sm)
        leg_b(sm)
        leg_c(sm)
        legs = "A B C"
    sm.say(f"legs {legs} passed in {time.perf_counter() - _T0:.0f}s "
           f"wall (set-up included)")
    result = {"ok": True, "device": sm.device}
    if args.rehearse_cpu:
        result["rehearsal"] = True
    print(json.dumps(result), flush=True)


if __name__ == "__main__":
    main()
