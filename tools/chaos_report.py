"""Chaos survival report: run a short async-PS training job under a
seeded fault plan and report whether the runtime rode it out.

The acceptance scenario from docs/RESILIENCE.md: a supervised
2-trainer + 1-pserver job where trainer 1's fault plan kills it
mid-run (``kill_at_step``) and refuses ~10% of its RPC connections
must still complete — the supervisor relaunches the killed trainer
(which resumes from its CheckpointManager snapshot), the retry layer
absorbs the refused connections, the pserver's liveness registry keeps
``serve()`` from hanging on the dead incarnation — and the final loss
must land within tolerance of a fault-free run of the same job.

Two modes:

* orchestrator (default): run the job twice — clean, then faulted —
  and print a JSON survival report:

    {"clean": {...}, "faulted": {...}, "loss_delta": ..,
     "survived": true}

  `faulted` aggregates every worker's injected-fault counters and
  retry/breaker statistics so a regression in ANY resilience layer
  (injection not firing, retries not consumed, restart not happening)
  is visible in the report, not just in the pass/fail bit.

* worker (``--role pserver`` / ``--role trainer``): one process of the
  job; spawned by the orchestrator, never run by hand.

Usage:
  python tools/chaos_report.py                      # full report
  python tools/chaos_report.py --steps 20 \
      --fault "seed=7,connect_refuse=0.1,kill_at_step=8"
  python tools/chaos_report.py --steps 16 \
      --fault "seed=7,nan=0.2"                      # stability guard
  python tools/chaos_report.py --steps 16 \
      --fault "seed=7,bitflip_step=6"               # integrity sentinel

``nan`` / ``grad_spike`` fault plans automatically arm
``FLAGS_stability_guard`` in every trainer of both runs and add an
``anomalies`` section (detected / recovered_by_rollback /
degraded_to_skip / aborted) to the report — docs/STABILITY.md.

``bitflip`` / ``data_dup`` fault plans additionally run a single-
process sentinel probe (``FLAGS_integrity_sentinel`` armed, the
in-trace shadow-checksum path of docs/RESILIENCE.md — the async-PS
trainers can't arm it, their params are refreshed out-of-band by the
communicator's recv thread) and add an ``integrity`` section with
honest ``{injected, detected, recovered, missed}`` accounting: a
bitflip must be detected and rolled back; a duplicated batch is a
LEGITIMATE update twice and is correctly not flagged (missed=1 —
that's the data-pipeline cursor's job, not the sentinel's).

``device_loss_step`` fault plans additionally run the ELASTIC probe
(docs/RESILIENCE.md "Elastic topology"): a 2-rank checkpointing gang
under ``launch.supervise(elastic=True)`` where rank 1's device
permanently burns out mid-run (exit ``DEVICE_LOSS_EXIT_CODE``). The
supervisor must shrink to the surviving rank instead of retrying the
dead world size, the shrunk incarnation must resume through the
elastic restore path (re-place / reshard / redistribute cursors), and
its stitched loss trajectory must be BIT-IDENTICAL to a fresh
single-rank run launched from the same checkpoint step — the
``elastic`` section reports honest ``{injected, detected,
resumed_elastic, bit_identical_vs_fresh}`` accounting and all four
gate ``survived``.

  python tools/chaos_report.py --steps 12 \
      --fault "seed=7,device_loss_step=6"       # elastic topology
"""
from __future__ import annotations

import argparse
import json
import os
import subprocess
import sys
import tempfile
import time

REPO = os.path.dirname(os.path.dirname(os.path.abspath(__file__)))

DEFAULT_STEPS = 24
DEFAULT_FAULT = "seed=7,connect_refuse=0.1,kill_at_step=8"
# per-class default for numeric-anomaly plans: injected NaNs roll back
# to the last ghost, while grad-norm spikes — routine in early async-PS
# training, where pulled params jump between steps — are clipped in
# place instead of burning a rollback each time
DEFAULT_STABILITY_POLICY = "nonfinite=rollback,spike=clip"
# |final_loss_faulted - final_loss_clean| bound for "survived": the job
# is a 4-feature linear regression whose loss decays below 0.05 within
# the step budget on BOTH runs, so an absolute tolerance is meaningful
LOSS_TOL = 0.25
JOB_TIMEOUT_S = 180.0


# ---------------------------------------------------------------------------
# worker mode
# ---------------------------------------------------------------------------

def _worker(role: str) -> None:
    os.environ.setdefault("JAX_PLATFORMS", "cpu")
    os.environ.pop("XLA_FLAGS", None)
    sys.path.insert(0, REPO)

    import numpy as np

    import paddle_tpu as fluid
    from paddle_tpu import layers
    from paddle_tpu.core.flags import set_flags
    from paddle_tpu.distributed import faults, resilience
    from paddle_tpu.incubate.fleet.base.role_maker import (
        Role, UserDefinedRoleMaker)
    from paddle_tpu.incubate.fleet.parameter_server import (
        DistributeTranspilerConfig, fleet)

    rank = int(os.environ.get("PADDLE_TRAINER_ID", "0"))
    n_trainers = int(os.environ["PADDLE_TRAINERS_NUM"])
    server_ep = os.environ["PADDLE_PSERVER_EP"]
    steps = int(os.environ.get("CHAOS_STEPS", str(DEFAULT_STEPS)))
    ckpt_dir = os.environ.get("CHAOS_CKPT_DIR")

    def dump_stats(engine=None):
        plan = faults.current()
        stats = {
            "role": role, "rank": rank,
            "faults": dict(plan.counts) if plan is not None else {},
            "retry": resilience.retry_stats(),
        }
        if engine is not None:
            # stability-guard accounting (docs/STABILITY.md): lets the
            # orchestrator report anomalies recovered-by-rollback vs
            # aborted, not just that the job finished
            stats["stability"] = {
                k: engine.counters.get(k, 0)
                for k in ("anomalies", "rollbacks",
                          "rollback_reexec_failures", "guard_aborts",
                          "ghost_snapshots", "replay_bundles",
                          "integrity_checks", "integrity_mismatches",
                          "integrity_rollbacks", "integrity_aborts")}
        print("CHAOS_STATS " + json.dumps(stats), flush=True)

    fluid.framework.unique_name.reset()
    role_obj = UserDefinedRoleMaker(
        current_id=rank,
        role=Role.SERVER if role == "pserver" else Role.WORKER,
        worker_num=n_trainers, server_endpoints=[server_ep])
    fleet.init(role_obj)

    main, startup = fluid.Program(), fluid.Program()
    with fluid.program_guard(main, startup):
        x = layers.data("x", [4], dtype="float32")
        y = layers.data("y", [1], dtype="float32")
        pred = layers.fc(x, 1, param_attr=fluid.ParamAttr(name="w"),
                         bias_attr=fluid.ParamAttr(name="b"))
        loss = layers.mean(layers.square_error_cost(pred, y))
        opt = fluid.optimizer.SGDOptimizer(0.05)
        cfg = DistributeTranspilerConfig()
        cfg.sync_mode = False
        cfg.fully_async = True
        opt = fleet.distributed_optimizer(opt, cfg)
        opt.minimize(loss)

    if role == "pserver":
        fleet.run_server()     # liveness registry keeps this from hanging
        dump_stats()
        print("SERVER_DONE", flush=True)
        return

    set_flags({"communicator_min_send_grad_num_before_recv": 2,
               "communicator_max_merge_var_num": 2})
    if os.environ.get("CHAOS_STABILITY"):
        # numeric-anomaly chaos (nan / grad_spike fault kinds): arm the
        # stability guard so detection + recovery is what's under test
        set_flags({"FLAGS_stability_guard": True})
    exe = fluid.Executor(fluid.CPUPlace())
    exe.run(fleet.startup_program or startup)
    fleet.init_worker()

    # elastic resume: attempt 0 starts fresh; a relaunched incarnation
    # continues from the last committed snapshot of its OWN state
    # (parameters re-sync from the pserver on the next pull anyway —
    # the step counter is the part that must survive)
    manager = None
    start_step = 0
    if ckpt_dir:
        from paddle_tpu.checkpoint import CheckpointManager
        manager = CheckpointManager(ckpt_dir)
        restored = manager.maybe_restore(scope=fluid.global_scope(),
                                         vars=["w", "b"])
        if restored is not None:
            start_step = int(restored)
            print(f"CHAOS_RESUMED {start_step}", flush=True)

    rng = np.random.RandomState(11 + rank)
    w_true = np.array([[1.0], [-2.0], [0.5], [3.0]], np.float32)
    # replay the data stream up to the resume point so the faulted run
    # sees the same batches the clean run saw
    for _ in range(start_step):
        rng.rand(16, 4)
    losses = []
    import warnings
    try:
        with warnings.catch_warnings():
            warnings.simplefilter("ignore")
            for step in range(start_step + 1, steps + 1):
                bx = rng.rand(16, 4).astype(np.float32)
                by = bx @ w_true + 0.25
                out = exe.run(fleet.main_program,
                              feed={"x": bx, "y": by},
                              fetch_list=[loss.name])
                losses.append(
                    float(np.asarray(out[0]).reshape(-1)[0]))
                if manager is not None:
                    manager.save(step, scope=fluid.global_scope(),
                                 vars=["w", "b"])
                time.sleep(0.05)
    except Exception:
        # a guard abort (PT_STABILITY_POLICY=abort) still reports its
        # counters so the orchestrator can count aborted anomalies
        dump_stats(engine=exe._engine)
        raise
    if manager is not None:
        manager.close()
    fleet.stop_worker()
    final = float(np.mean(losses[-3:])) if losses else float("nan")
    print("CHAOS_LOSS " + json.dumps(final), flush=True)
    dump_stats(engine=exe._engine)


def _sentinel_worker() -> None:
    """Single-process sentinel probe: same 4-feature regression, local
    SGD (update ops stay in-trace, so the integrity sentinel arms),
    fault plan from PT_FAULT_PLAN. Spawned by the orchestrator for
    bitflip / data_dup plans."""
    os.environ.setdefault("JAX_PLATFORMS", "cpu")
    os.environ.pop("XLA_FLAGS", None)
    sys.path.insert(0, REPO)

    import numpy as np

    import paddle_tpu as fluid
    from paddle_tpu import layers
    from paddle_tpu.core.flags import set_flags
    from paddle_tpu.distributed import faults

    steps = int(os.environ.get("CHAOS_STEPS", str(DEFAULT_STEPS)))
    set_flags({"FLAGS_integrity_sentinel": True})
    fluid.framework.unique_name.reset()
    main, startup = fluid.Program(), fluid.Program()
    with fluid.program_guard(main, startup):
        x = layers.data("x", [4], dtype="float32")
        y = layers.data("y", [1], dtype="float32")
        pred = layers.fc(x, 1, param_attr=fluid.ParamAttr(name="w"),
                         bias_attr=fluid.ParamAttr(name="b"))
        loss = layers.mean(layers.square_error_cost(pred, y))
        fluid.optimizer.SGDOptimizer(0.05).minimize(loss)
    exe = fluid.Executor(fluid.CPUPlace())
    exe.run(startup)
    rng = np.random.RandomState(11)
    w_true = np.array([[1.0], [-2.0], [0.5], [3.0]], np.float32)
    losses = []
    for _ in range(steps):
        bx = rng.rand(16, 4).astype(np.float32)
        by = bx @ w_true + 0.25
        out = exe.run(main, feed={"x": bx, "y": by},
                      fetch_list=[loss.name])
        losses.append(float(np.asarray(out[0]).reshape(-1)[0]))
    final = float(np.mean(losses[-3:])) if losses else float("nan")
    print("CHAOS_LOSS " + json.dumps(final), flush=True)
    plan = faults.current()
    stats = {
        "role": "sentinel", "rank": 0,
        "faults": dict(plan.counts) if plan is not None else {},
        "retry": {},
        "stability": {
            k: exe._engine.counters.get(k, 0)
            for k in ("anomalies", "rollbacks", "ghost_snapshots",
                      "integrity_checks", "integrity_mismatches",
                      "integrity_rollbacks", "integrity_aborts")}}
    print("CHAOS_STATS " + json.dumps(stats), flush=True)


class _CursorStream:
    """Deterministic batch source speaking the train_state cursor
    protocol: batch ``i`` is a pure function of ``(seed, i)``, so a
    restored ``offset`` resumes bit-identically with no history
    replay — exactly the contract docs/RESILIENCE.md asks of real
    readers."""

    def __init__(self, seed: int):
        self.seed = int(seed)
        self.offset = 0

    def next_batch(self):
        import numpy as np
        r = np.random.RandomState(
            (self.seed * 100003 + self.offset) % (2 ** 31))
        bx = r.rand(16, 4).astype(np.float32)
        w_true = np.array([[1.0], [-2.0], [0.5], [3.0]], np.float32)
        by = bx @ w_true + 0.25
        self.offset += 1
        return bx, by

    def state_dict(self):
        return {"seed": self.seed, "offset": self.offset}

    def load_state_dict(self, state):
        self.seed = int(state.get("seed", self.seed))
        self.offset = int(state["offset"])


def _elastic_worker() -> None:
    """One rank of the elastic-topology probe: local SGD on the same
    4-feature regression, a CheckpointManager writing ``train_state``
    every step, and a rank-gated device-loss fault plan. Spawned by
    ``launch.supervise`` from ``_elastic_probe`` — and re-spawned at
    the SURVIVING world size after the supervisor's elastic shrink
    (``PT_ELASTIC_RESUME=1``), where ``maybe_restore`` takes the
    elastic path. With ``CHAOS_VERIFY_STEP`` set the worker instead
    restores exactly that step (elastically) and replays the remaining
    steps WITHOUT saving: the fresh same-world-size run the probe
    compares loss trajectories against bit-for-bit."""
    os.environ.setdefault("JAX_PLATFORMS", "cpu")
    os.environ.pop("XLA_FLAGS", None)
    sys.path.insert(0, REPO)

    import numpy as np

    import paddle_tpu as fluid
    from paddle_tpu import layers
    from paddle_tpu.checkpoint import CheckpointManager, register_reader
    from paddle_tpu.distributed import faults

    rank = int(os.environ.get("PADDLE_TRAINER_ID", "0"))
    world = int(os.environ.get("PADDLE_TRAINERS_NUM", "1"))
    steps = int(os.environ.get("CHAOS_STEPS", str(DEFAULT_STEPS)))
    ckpt_dir = os.environ["CHAOS_CKPT_DIR"]
    fault_rank = int(os.environ.get("CHAOS_FAULT_RANK", "-1"))
    verify_step = os.environ.get("CHAOS_VERIFY_STEP")

    if rank != fault_rank:
        # the fault plan rides the gang-wide env; only the designated
        # victim's device "burns out"
        faults.uninstall()

    fluid.framework.unique_name.reset()
    main, startup = fluid.Program(), fluid.Program()
    with fluid.program_guard(main, startup):
        x = layers.data("x", [4], dtype="float32")
        y = layers.data("y", [1], dtype="float32")
        pred = layers.fc(x, 1, param_attr=fluid.ParamAttr(name="w"),
                         bias_attr=fluid.ParamAttr(name="b"))
        loss = layers.mean(layers.square_error_cost(pred, y))
        fluid.optimizer.SGDOptimizer(0.05).minimize(loss)
    exe = fluid.Executor(fluid.CPUPlace())
    exe.run(startup)

    stream = _CursorStream(seed=11 + rank)
    register_reader("train", stream)
    # short commit barrier: when a rank dies mid-step, the survivors'
    # in-flight save must fail fast instead of stalling teardown
    manager = CheckpointManager(ckpt_dir, process_index=rank,
                                process_count=world,
                                commit_timeout=20.0)

    start = 0
    if verify_step is not None:
        start = manager.restore(step=int(verify_step),
                                scope=fluid.global_scope(),
                                vars=["w", "b"], elastic=True)
    else:
        restored = manager.maybe_restore(scope=fluid.global_scope(),
                                         vars=["w", "b"])
        if restored is not None:
            start = int(restored)
            print(f"CHAOS_RESUMED {start}", flush=True)
            info = manager.elastic_resume_info
            if info is not None:
                print("CHAOS_ELASTIC " + json.dumps({
                    "step": info["step"],
                    "saved_world": info["saved"].get("world_size"),
                    "world": info["current"].get("world_size"),
                    "reshard_seconds": info["reshard_seconds"],
                }), flush=True)

    losses = []
    for step in range(start + 1, steps + 1):
        bx, by = stream.next_batch()
        out = exe.run(main, feed={"x": bx, "y": by},
                      fetch_list=[loss.name])
        losses.append(float(np.asarray(out[0]).reshape(-1)[0]))
        if verify_step is None:
            # rank 0 owns the (replicated) tensors and the engine RNG
            # state; other ranks contribute only their train_state
            # worker entry — the shard layout a real data-parallel
            # gang writes (every rank writing its own RNG var would
            # over-cover it in the merged manifest)
            manager.save(step, scope=fluid.global_scope(),
                         vars=["w", "b"] if rank == 0 else [],
                         include_rng=(rank == 0),
                         sync=True, train_state=True)
    if verify_step is None:
        manager.close()
    print("CHAOS_LOSSES " + json.dumps(
        {"start": start, "losses": losses}), flush=True)


# ---------------------------------------------------------------------------
# orchestrator
# ---------------------------------------------------------------------------

def _free_port() -> int:
    import socket
    with socket.socket() as s:
        s.bind(("127.0.0.1", 0))
        return s.getsockname()[1]


def summarize_flight_dumps(directory: str, last_n: int = 8) -> list:
    """Ingest the flight-recorder postmortems the job's workers wrote
    into ``directory`` (PT_FLIGHT_DIR): a kill_at_step victim dumps its
    last-N step records inline before ``os._exit``, so the survival
    report can show WHAT the dead incarnation was doing — per-phase
    step latencies, fast-path state — not just that it died
    (docs/OBSERVABILITY.md)."""
    os.environ.setdefault("JAX_PLATFORMS", "cpu")
    if REPO not in sys.path:
        sys.path.insert(0, REPO)
    try:
        from paddle_tpu.observability import recorder
        return recorder.summarize_dumps(directory, last_n=last_n)
    except Exception as exc:  # a broken dump must not fail the report
        return [{"error": f"{type(exc).__name__}: {exc}"}]


def span_straggler_report(directory: str, top: int = 5,
                          stall_ms: float = 50.0) -> list:
    """Ingest the span dumps (``spans_*.jsonl``, docs/TRACING.md) the
    job's workers wrote next to their flight dumps and attribute each
    death to the RPC activity that preceded it: for every dump — a
    ``kill_at_step`` victim writes one inline before ``os._exit``, an
    evicted trainer's last dump shows what it was stuck on — list the
    client/server RPC spans that stalled (non-ok outcome, consumed
    retries, or duration >= ``stall_ms``), slowest first, with their
    endpoint and breaker state. The survival report then shows WHICH
    endpoint the dead incarnation was waiting on, not just that it
    died."""
    os.environ.setdefault("JAX_PLATFORMS", "cpu")
    if REPO not in sys.path:
        sys.path.insert(0, REPO)
    try:
        from paddle_tpu.observability import tracing
        out = []
        for path in tracing.find_span_dumps(directory):
            d = tracing.read_span_dump(path)
            hdr = d["header"]
            rpc = [s for s in d["spans"]
                   if str(s.get("kind", "")).startswith("rpc.")]
            stalls = []
            for s in rpc:
                ann = s.get("ann") or {}
                if (ann.get("outcome") not in (None, "ok")
                        or int(ann.get("retries") or 0) > 0
                        or float(s.get("dur_ms") or 0.0) >= stall_ms):
                    stalls.append(s)
            stalls.sort(key=lambda s: -float(s.get("dur_ms") or 0.0))
            out.append({
                "file": os.path.basename(path),
                "worker": hdr.get("worker"),
                "reason": hdr.get("reason"),
                "rpc_spans": len(rpc),
                "stalls": [{
                    "name": s.get("name"),
                    "endpoint": (s.get("ann") or {}).get("endpoint"),
                    "outcome": (s.get("ann") or {}).get("outcome"),
                    "retries": (s.get("ann") or {}).get("retries"),
                    "breaker": (s.get("ann") or {}).get("breaker"),
                    "dur_ms": s.get("dur_ms"),
                } for s in stalls[:top]],
            })
        return out
    except Exception as exc:  # a broken dump must not fail the report
        return [{"error": f"{type(exc).__name__}: {exc}"}]


def _spawn(role, rank, n_trainers, ep, steps, extra_env):
    env = dict(os.environ)
    env.pop("XLA_FLAGS", None)
    env.pop("PT_FAULT_PLAN", None)
    env.update({
        "JAX_PLATFORMS": "cpu",
        "PADDLE_TRAINER_ID": str(rank),
        "PADDLE_TRAINERS_NUM": str(n_trainers),
        "PADDLE_PSERVER_EP": ep,
        "CHAOS_STEPS": str(steps),
    })
    env.update(extra_env)
    return subprocess.Popen(
        [sys.executable, os.path.abspath(__file__), "--role", role],
        env=env, stdout=subprocess.PIPE, stderr=subprocess.PIPE,
        text=True)


def _parse_worker(out: str, agg: dict) -> None:
    for line in out.splitlines():
        if line.startswith("CHAOS_STATS "):
            st = json.loads(line[len("CHAOS_STATS "):])
            for k, v in st["faults"].items():
                agg["faults"][k] = agg["faults"].get(k, 0) + int(v)
            for k, v in st["retry"].items():
                agg["retry"][k] = agg["retry"].get(k, 0) + int(v)
            for k, v in st.get("stability", {}).items():
                agg["stability"][k] = (agg["stability"].get(k, 0)
                                       + int(v))
        elif line.startswith("CHAOS_LOSS "):
            agg["losses"].append(
                float(json.loads(line[len("CHAOS_LOSS "):])))
        elif line.startswith("CHAOS_RESUMED "):
            agg["resumed_at"] = int(line.split()[1])


def run_job(steps=DEFAULT_STEPS, fault_spec=None, max_restarts=1,
            timeout_s=JOB_TIMEOUT_S, stability=False,
            stability_policy=DEFAULT_STABILITY_POLICY) -> dict:
    """One 1-pserver + 2-trainer job; ``fault_spec`` (if any) is the
    PT_FAULT_PLAN for trainer 1 only. ``stability`` arms
    FLAGS_stability_guard in every trainer (for nan / grad_spike
    fault plans). Returns the per-run report."""
    ep = f"127.0.0.1:{_free_port()}"
    agg = {"faults": {}, "retry": {}, "stability": {}, "losses": [],
           "resumed_at": None}
    t0 = time.monotonic()
    # flight dumps outlive the job's ckpt tempdir: summarized after the
    # processes are reaped, removed by this function
    flight_dir = tempfile.mkdtemp(prefix="chaos_flight_")
    with tempfile.TemporaryDirectory(prefix="chaos_ckpt_") as ckpt:
        # liveness on: heartbeats (default interval) + a short eviction
        # timeout so a dead trainer can never hang serve()
        server = _spawn("pserver", 0, 2, ep, steps,
                        {"FLAGS_trainer_timeout_s": "8",
                         "PT_FLIGHT_DIR": flight_dir})
        trainers = {}
        attempts = {0: 0, 1: 0}
        outs = {0: [], 1: []}

        def spawn_trainer(rank):
            extra = {"PADDLE_RESTART_ATTEMPT": str(attempts[rank]),
                     "CHAOS_CKPT_DIR": os.path.join(ckpt, str(rank)),
                     "PT_FLIGHT_DIR": flight_dir}
            if stability:
                # guard on BOTH trainers (and on the clean run too, via
                # the caller) so the clean-vs-faulted comparison also
                # checks guard-on parity, not just recovery
                extra["CHAOS_STABILITY"] = "1"
                extra["PT_STABILITY_POLICY"] = stability_policy
                # async-PS tuning: ghost every 2 steps so a rollback
                # lands on a recent state; spike threshold above the
                # natural step-to-step norm variance of async pulled
                # params (injected grad_spike is x1e4, still caught);
                # no escalation — repeated clips must not degrade into
                # stale-ghost rollbacks that stall the whole cluster
                extra["PT_GHOST_EVERY"] = "2"
                extra["PT_GUARD_SPIKE_FACTOR"] = "100"
                extra["PT_GUARD_ESCALATE_AFTER"] = "1000000"
            if fault_spec and rank == 1:
                extra["PT_FAULT_PLAN"] = fault_spec
            trainers[rank] = _spawn("trainer", rank, 2, ep, steps,
                                    extra)

        for r in (0, 1):
            spawn_trainer(r)

        restarts = 0
        hung = False
        deadline = t0 + timeout_s
        live = dict(trainers)
        while live or server.poll() is None:
            if time.monotonic() > deadline:
                hung = True
                break
            for rank, p in list(live.items()):
                rc = p.poll()
                if rc is None:
                    continue
                out, err = p.communicate()
                outs[rank].append((rc, out, err))
                del live[rank]
                if rc != 0 and attempts[rank] < max_restarts:
                    # supervised relaunch: next incarnation resumes
                    # from its checkpoint; PADDLE_RESTART_ATTEMPT
                    # disarms one-shot kill_at_step plans
                    attempts[rank] += 1
                    restarts += 1
                    spawn_trainer(rank)
                    live[rank] = trainers[rank]
            if not live and server.poll() is None:
                # trainers done: the server exits via fanin (or
                # eviction, if an incarnation died unrecovered)
                try:
                    server.wait(timeout=max(
                        0.1, deadline - time.monotonic()))
                except subprocess.TimeoutExpired:
                    hung = True
                break
            time.sleep(0.1)

        for p in list(live.values()) + [server]:
            if p.poll() is None:
                p.kill()
        server_out, server_err = server.communicate()
        elapsed = time.monotonic() - t0

    trainer_codes = {r: [rc for rc, _, _ in outs[r]] for r in outs}
    for r in outs:
        for _, out, _ in outs[r]:
            _parse_worker(out, agg)
    _parse_worker(server_out, agg)
    # a kill_at_step victim dies via os._exit and never reports its own
    # counters — infer the injection from the exit code
    # (faults.KILL_EXIT_CODE == 43)
    kills = sum(1 for codes in trainer_codes.values()
                for rc in codes if rc == 43)
    if kills:
        agg["faults"]["kill"] = agg["faults"].get("kill", 0) + kills
    # likewise a device-loss victim (faults.DEVICE_LOSS_EXIT_CODE == 44)
    dlost = sum(1 for codes in trainer_codes.values()
                for rc in codes if rc == 44)
    if dlost:
        agg["faults"]["device_loss"] = (
            agg["faults"].get("device_loss", 0) + dlost)
    # final loss is taken from trainer 0 (never fault-injected) so the
    # clean-vs-faulted comparison measures the CLUSTER's recovery, not
    # the noise of the killed process
    loss0 = None
    for _, out, _ in outs[0]:
        for line in out.splitlines():
            if line.startswith("CHAOS_LOSS "):
                loss0 = float(json.loads(line[len("CHAOS_LOSS "):]))
    completed = (not hung and server.returncode == 0 and
                 all(codes and codes[-1] == 0
                     for codes in trainer_codes.values()))
    flight_records = summarize_flight_dumps(flight_dir)
    straggler = span_straggler_report(flight_dir)
    import shutil
    shutil.rmtree(flight_dir, ignore_errors=True)
    rep = {
        "final_loss": loss0,
        "restarts": restarts,
        "restart_attempts": {f"trainer{r}": attempts[r]
                             for r in sorted(attempts)},
        "trainer_exit_codes": trainer_codes,
        "pserver_clean_exit": (not hung and server.returncode == 0),
        "resumed_at_step": agg["resumed_at"],
        "faults_injected": agg["faults"],
        "retries_consumed": agg["retry"].get("retries", 0),
        "breaker_fast_fails": agg["retry"].get("breaker_fast_fails", 0),
        "stability": agg["stability"],
        "flight_records": flight_records,
        "straggler_attribution": straggler,
        "completed": completed,
        "elapsed_s": round(elapsed, 2),
    }
    if not completed:
        rep["stderr_tail"] = {
            "pserver": server_err[-800:],
            **{f"trainer{r}": outs[r][-1][2][-800:]
               for r in outs if outs[r]},
        }
    return rep


def _sentinel_probe(steps: int, fault_spec: str,
                    timeout_s=JOB_TIMEOUT_S) -> dict:
    """Run the single-process sentinel worker under ``fault_spec`` and
    fold its counters into ``{injected, detected, recovered, missed}``
    accounting (docs/RESILIENCE.md)."""
    env = dict(os.environ)
    env.pop("XLA_FLAGS", None)
    env.update({
        "JAX_PLATFORMS": "cpu",
        "CHAOS_STEPS": str(steps),
        "PT_FAULT_PLAN": fault_spec,
        # verdict every 2 steps so the injection's window closes well
        # inside the step budget
        "PT_INTEGRITY_EVERY": "2",
    })
    env.pop("PADDLE_RESTART_ATTEMPT", None)
    p = subprocess.Popen(
        [sys.executable, os.path.abspath(__file__),
         "--role", "sentinel"],
        env=env, stdout=subprocess.PIPE, stderr=subprocess.PIPE,
        text=True)
    try:
        out, err = p.communicate(timeout=timeout_s)
    except subprocess.TimeoutExpired:
        p.kill()
        out, err = p.communicate()
    agg = {"faults": {}, "retry": {}, "stability": {}, "losses": [],
           "resumed_at": None}
    _parse_worker(out, agg)
    f, st = agg["faults"], agg["stability"]
    injected = int(f.get("bitflip", 0)) + int(f.get("data_dup", 0))
    detected = int(st.get("integrity_mismatches", 0))
    rep = {
        "injected": injected,
        "detected": detected,
        "recovered": int(st.get("integrity_rollbacks", 0)),
        "missed": max(0, injected - detected),
        "aborted": int(st.get("integrity_aborts", 0)),
        "checks": int(st.get("integrity_checks", 0)),
        "faults_injected": f,
        "final_loss": (agg["losses"][0] if agg["losses"] else None),
        "completed": p.returncode == 0,
    }
    if p.returncode != 0:
        rep["stderr_tail"] = (err or "")[-800:]
    return rep


def _elastic_probe(steps: int, fault_spec: str,
                   timeout_s=JOB_TIMEOUT_S) -> dict:
    """Elastic-topology probe (docs/RESILIENCE.md "Elastic topology"):
    drive ``launch.supervise(nproc=2, elastic=True)`` over the elastic
    worker with ``fault_spec`` armed on rank 1, then audit the
    supervisor's attempt log and the surviving rank's markers for
    honest ``{injected, detected, resumed_elastic}`` accounting.
    Acceptance is a FRESH single-rank process restoring the same
    checkpoint step (elastically, no saving) and replaying the exact
    float-for-float loss trajectory the shrunk fleet produced."""
    if REPO not in sys.path:
        sys.path.insert(0, REPO)
    os.environ.setdefault("JAX_PLATFORMS", "cpu")
    from paddle_tpu.distributed import launch as pt_launch

    import shutil
    ckpt = tempfile.mkdtemp(prefix="chaos_elastic_ckpt_")
    log_dir = tempfile.mkdtemp(prefix="chaos_elastic_log_")
    attempt_log = []
    try:
        extra = {
            "JAX_PLATFORMS": "cpu",
            "CHAOS_STEPS": str(steps),
            "CHAOS_CKPT_DIR": ckpt,
            "CHAOS_FAULT_RANK": "1",    # rank 1's device burns out
            "PT_FAULT_PLAN": fault_spec,
        }
        code, restarts = pt_launch.supervise(
            [os.path.abspath(__file__), "--role", "elastic"],
            max_restarts=2, nproc=2, backend="cpu", log_dir=log_dir,
            extra_env=extra, grace_s=5.0, backoff_base_s=0.0,
            elastic=True, min_nproc=1, ckpt_dir=ckpt,
            attempt_log=attempt_log)

        # the surviving rank's (appended) workerlog carries the
        # continuation's markers; keep the LAST of each
        resumed_at = None
        elastic_marker = None
        cont = None
        try:
            with open(os.path.join(log_dir, "workerlog.0")) as f:
                for line in f:
                    if line.startswith("CHAOS_RESUMED "):
                        resumed_at = int(line.split()[1])
                    elif line.startswith("CHAOS_ELASTIC "):
                        elastic_marker = json.loads(
                            line[len("CHAOS_ELASTIC "):])
                    elif line.startswith("CHAOS_LOSSES "):
                        cont = json.loads(
                            line[len("CHAOS_LOSSES "):])
        except OSError:
            pass

        from paddle_tpu.distributed.faults import DEVICE_LOSS_EXIT_CODE
        injected = sum(1 for a in attempt_log
                       for c in a["codes"]
                       if c == DEVICE_LOSS_EXIT_CODE)
        detected = sum(1 for a in attempt_log if a.get("shrunk"))
        resumed_elastic = bool(
            elastic_marker is not None and cont is not None
            and resumed_at is not None
            and cont["start"] == resumed_at)

        verify = None
        if resumed_elastic:
            env = dict(os.environ)
            for k in ("XLA_FLAGS", "PT_FAULT_PLAN",
                      "PADDLE_RESTART_ATTEMPT", "PT_ELASTIC_RESUME"):
                env.pop(k, None)
            env.update({
                "JAX_PLATFORMS": "cpu",
                "PADDLE_TRAINER_ID": "0",
                "PADDLE_TRAINERS_NUM": "1",
                "CHAOS_STEPS": str(steps),
                "CHAOS_CKPT_DIR": ckpt,
                "CHAOS_VERIFY_STEP": str(resumed_at),
            })
            p = subprocess.Popen(
                [sys.executable, os.path.abspath(__file__),
                 "--role", "elastic"],
                env=env, stdout=subprocess.PIPE,
                stderr=subprocess.PIPE, text=True)
            try:
                out, _ = p.communicate(timeout=timeout_s)
            except subprocess.TimeoutExpired:
                p.kill()
                out, _ = p.communicate()
            for line in out.splitlines():
                if line.startswith("CHAOS_LOSSES "):
                    verify = json.loads(line[len("CHAOS_LOSSES "):])
        bit_identical = bool(
            cont is not None and verify is not None
            and len(cont["losses"]) > 0
            and cont["losses"] == verify["losses"])
        return {
            "injected": injected,
            "detected": detected,
            "resumed_elastic": resumed_elastic,
            "resumed_at_step": resumed_at,
            "world_sizes": [a["nproc"] for a in attempt_log],
            "restarts": restarts,
            "stitched_steps": len(cont["losses"]) if cont else 0,
            "bit_identical_vs_fresh": bit_identical,
            "completed": code == 0,
        }
    finally:
        shutil.rmtree(ckpt, ignore_errors=True)
        shutil.rmtree(log_dir, ignore_errors=True)


def chaos_report(steps=DEFAULT_STEPS, fault_spec=DEFAULT_FAULT,
                 max_restarts=1,
                 stability_policy=DEFAULT_STABILITY_POLICY) -> dict:
    # numeric-anomaly plans arm the stability guard in every trainer of
    # BOTH runs: the clean run doubles as a guard-on parity check
    stability = any(k in (fault_spec or "")
                    for k in ("nan=", "grad_spike="))
    clean = run_job(steps=steps, fault_spec=None, max_restarts=0,
                    stability=stability,
                    stability_policy=stability_policy)
    faulted = run_job(steps=steps, fault_spec=fault_spec,
                      max_restarts=max_restarts, stability=stability,
                      stability_policy=stability_policy)
    delta = None
    if clean["final_loss"] is not None and \
            faulted["final_loss"] is not None:
        delta = abs(clean["final_loss"] - faulted["final_loss"])
    rep = {
        "fault_plan": fault_spec,
        "clean": clean,
        "faulted": faulted,
        "loss_delta": delta,
        "loss_tolerance": LOSS_TOL,
        "survived": bool(
            clean["completed"] and faulted["completed"] and
            delta is not None and delta <= LOSS_TOL),
    }
    if stability:
        st = faulted["stability"]
        rep["anomalies"] = {
            "detected": st.get("anomalies", 0),
            "recovered_by_rollback": st.get("rollbacks", 0),
            "degraded_to_skip": st.get("rollback_reexec_failures", 0),
            "aborted": st.get("guard_aborts", 0),
        }
    # integrity-class chaos (bitflip / data_dup): single-process
    # sentinel probe with {injected, detected, recovered, missed}
    # accounting; an undetected bitflip (missed > 0) fails survival
    integrity = any(k in (fault_spec or "")
                    for k in ("bitflip", "data_dup"))
    if integrity:
        probe = _sentinel_probe(steps, fault_spec)
        rep["integrity"] = probe
        if "bitflip" in (fault_spec or ""):
            rep["survived"] = bool(
                rep["survived"] and probe["completed"]
                and probe["missed"] == 0 and probe["injected"] > 0)
    # device-loss chaos: elastic-topology probe — one rank of a
    # supervised gang permanently loses its device; the fleet must
    # shrink, resume elastically, and match a fresh same-world-size
    # run bit-for-bit (docs/RESILIENCE.md "Elastic topology")
    if "device_loss" in (fault_spec or ""):
        eprobe = _elastic_probe(steps, fault_spec)
        rep["elastic"] = eprobe
        rep["survived"] = bool(
            rep["survived"] and eprobe["completed"]
            and eprobe["injected"] > 0 and eprobe["detected"] > 0
            and eprobe["resumed_elastic"]
            and eprobe["bit_identical_vs_fresh"])
    return rep


def main(argv=None):
    ap = argparse.ArgumentParser(description=__doc__.splitlines()[0])
    ap.add_argument("--role", choices=["pserver", "trainer",
                                       "sentinel", "elastic"],
                    help=argparse.SUPPRESS)
    ap.add_argument("--steps", type=int, default=DEFAULT_STEPS)
    ap.add_argument("--fault", default=DEFAULT_FAULT,
                    help="PT_FAULT_PLAN spec for trainer 1")
    ap.add_argument("--max-restarts", type=int, default=1)
    ap.add_argument("--stability-policy",
                    default=DEFAULT_STABILITY_POLICY,
                    help="PT_STABILITY_POLICY for nan/grad_spike "
                         "fault plans (guard armed automatically)")
    args = ap.parse_args(argv)
    if args.role == "sentinel":
        _sentinel_worker()
        return
    if args.role == "elastic":
        _elastic_worker()
        return
    if args.role:
        _worker(args.role)
        return
    rep = chaos_report(steps=args.steps, fault_spec=args.fault,
                       max_restarts=args.max_restarts,
                       stability_policy=args.stability_policy)
    print(json.dumps(rep, indent=2))
    sys.exit(0 if rep["survived"] else 1)


if __name__ == "__main__":
    main()
