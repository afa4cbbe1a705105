"""Multi-process training launcher (VERDICT r3 missing #5).

Parity: reference python/paddle/distributed/launch.py — spawn N trainer
processes for a user script, each with the PADDLE_* environment the
fleet role makers read (PADDLE_TRAINER_ID / PADDLE_TRAINERS_NUM /
PADDLE_TRAINER_ENDPOINTS / TRAINING_ROLE), stream their logs, and
propagate the first failure.

TPU-native notes:
* On a TPU pod each HOST runs one process that owns its local chips
  (JAX multi-controller), so `--nproc_per_node` defaults to 1 on TPU
  (the reference defaults to the GPU count for the NCCL model). The
  gloo-style host bootstrap the collective fleet uses is selected with
  PADDLE_TPU_MULTIHOST=1 — the same contract the subprocess cluster
  tests exercise (tests/test_dist_fleet.py).
* A chip belongs to one process. With the TPU backend every child
  opens every local chip, so `--nproc > 1` on one TPU host is refused
  at launch (no gang of children fighting over the chips): drive the
  host's chips from ONE process over a mesh, or pass `--backend cpu`.
  The launcher itself never initialises a JAX backend, or it would
  hold the chips its children need; this is asserted before the spawn.
* `--backend cpu` forces JAX_PLATFORMS=cpu in the children (virtual
  multi-process clusters on one machine — CI, dry runs).

Resilience (docs/RESILIENCE.md):
* First failure kills the surviving gang with SIGTERM, waits
  `--grace` seconds (letting CheckpointManager's SIGTERM preemption
  hook finish a final save), then SIGKILLs stragglers — and the
  launcher exits with the ORIGINAL failing exit code, not a
  straggler's.
* `--max-restarts N` turns the launcher into a supervisor: a failed
  gang is torn down and relaunched up to N times, each incarnation
  seeing PADDLE_RESTART_ATTEMPT so the training script restores from
  the latest CheckpointManager snapshot (and fault plans with
  `kill_attempts` stop re-killing restarted runs).

Usage:
  python -m paddle_tpu.distributed.launch --nproc 2 train.py --lr 0.1
  python -m paddle_tpu.distributed.launch --ips host1,host2 \
      --started_port 6170 train.py       # one process per listed host
  python -m paddle_tpu.distributed.launch --nproc 2 --max-restarts 3 \
      train.py                           # elastic supervisor
"""
from __future__ import annotations

import argparse
import glob
import os
import signal
import socket
import subprocess
import sys
import time

__all__ = ["launch", "supervise", "main"]

# default seconds between SIGTERM and SIGKILL when tearing a gang down:
# long enough for a SIGTERM-hooked final checkpoint of a small model,
# short enough that a wedged worker cannot stall CI
DEFAULT_GRACE_S = 10.0


def _children_open_tpu(backend, env) -> bool:
    """Will a child started with *env* bring up the TPU backend? Decided
    without JAX (this process must never open the chips): the platform
    request that reaches the child, then the host's TPU device nodes."""
    if backend == "cpu":
        return False
    platforms = env.get("JAX_PLATFORMS", "").lower()
    if platforms and "tpu" not in platforms.split(","):
        return False
    return bool(glob.glob("/dev/accel*") or glob.glob("/dev/vfio/[0-9]*"))


def _free_ports(n, start=None):
    ports, socks = [], []
    try:
        for i in range(n):
            s = socket.socket()
            s.bind(("127.0.0.1", 0 if start is None else start + i))
            socks.append(s)
            ports.append(s.getsockname()[1])
    finally:
        for s in socks:
            s.close()
    return ports


def _terminate_gang(procs, grace_s=DEFAULT_GRACE_S):
    """SIGTERM every live worker, wait up to ``grace_s`` for them to
    exit (their checkpoint preemption hooks run in this window), then
    SIGKILL stragglers. Never returns with a live worker — stragglers
    outliving the launcher was the original first-failure bug."""
    alive = [p for _, p, _ in procs if p.poll() is None]
    for p in alive:
        try:
            p.send_signal(signal.SIGTERM)
        except OSError:
            pass
    deadline = time.monotonic() + max(0.0, grace_s)
    while time.monotonic() < deadline:
        if all(p.poll() is not None for p in alive):
            return
        time.sleep(0.05)
    for p in alive:
        if p.poll() is None:
            try:
                p.kill()
            except OSError:
                pass
    for p in alive:
        try:
            p.wait(timeout=10)
        except Exception:
            pass


def _run_once(script_args, nproc=1, ips=None, started_port=None,
              backend=None, log_dir=None, extra_env=None,
              grace_s=DEFAULT_GRACE_S):
    """One gang launch. Returns ``(codes, first_fail)``: exit codes in
    rank order, and the FIRST nonzero exit code observed (in failure
    order, not rank order) or 0 when every rank succeeded."""
    if ips:
        hosts = [h.strip() for h in ips.split(",") if h.strip()]
        # one process per host entry, rank ordered by list position;
        # this process only launches the LOCAL host's worker (reference
        # launch.py does the same: each host runs the launcher)
        local_names = {"127.0.0.1", "localhost", socket.gethostname()}
        try:
            hostname, aliases, addrs = socket.gethostbyname_ex(
                socket.gethostname())
            local_names.update([hostname, *aliases, *addrs])
        except OSError:
            pass
        local_ranks = [i for i, h in enumerate(hosts)
                       if h.split(":")[0] in local_names]
        if not local_ranks:
            raise SystemExit(
                f"paddle_tpu.distributed.launch: none of --ips {hosts} "
                f"matches this host ({sorted(local_names)}); refusing "
                f"to guess (launching every rank locally would create "
                f"duplicate trainers). Run the launcher on each listed "
                f"host, or use --nproc for a single-host cluster.")
        port0 = started_port or 6170
        endpoints = [f"{h}:{port0}" for h in hosts]
        ranks = local_ranks
    else:
        if nproc > 1 and _children_open_tpu(
                backend, {**os.environ, **(extra_env or {})}):
            raise SystemExit(
                f"paddle_tpu.distributed.launch: --nproc {nproc} on one "
                f"TPU host is refused: a chip belongs to one process "
                f"and every child would open every local chip. Run ONE "
                f"process over all local chips (a mesh / "
                f"with_data_parallel(places=fluid.tpu_places())), or "
                f"pass --backend cpu for a virtual cluster.")
        ports = _free_ports(nproc, started_port)
        endpoints = [f"127.0.0.1:{p}" for p in ports]
        ranks = list(range(nproc))

    # a parent that has brought the TPU backend up holds the chips its
    # children need (in-process callers with a CPU backend are fine)
    xb = sys.modules.get("jax._src.xla_bridge")
    assert xb is None or "tpu" not in xb._backends, \
        "launcher process initialised the TPU backend before spawning"

    eps = ",".join(endpoints)
    nranks = len(endpoints)
    procs = []
    if log_dir:
        os.makedirs(log_dir, exist_ok=True)
    for rank in ranks:
        env = dict(os.environ)
        env.pop("XLA_FLAGS", None)
        env.update({
            "PADDLE_TRAINER_ID": str(rank),
            "PADDLE_TRAINERS_NUM": str(nranks),
            "PADDLE_TRAINER_ENDPOINTS": eps,
            "PADDLE_CURRENT_ENDPOINT": endpoints[rank],
            "TRAINING_ROLE": "TRAINER",
            "PADDLE_TPU_MULTIHOST": "1" if nranks > 1 else "0",
        })
        if backend == "cpu":
            env["JAX_PLATFORMS"] = "cpu"
        if extra_env:
            env.update(extra_env)
        out = err = None
        if log_dir:
            out = open(os.path.join(log_dir,
                                    f"workerlog.{rank}"), "a")
            err = subprocess.STDOUT
        procs.append((rank, subprocess.Popen(
            [sys.executable] + list(script_args), env=env,
            stdout=out, stderr=err), out))

    codes = {}
    first_fail = 0
    try:
        while len(codes) < len(procs):
            for rank, p, _ in procs:
                if rank in codes:
                    continue
                rc = p.poll()
                if rc is not None:
                    codes[rank] = rc
                    if rc != 0 and first_fail == 0:
                        # first failure aborts the cluster; the
                        # escalating teardown guarantees no straggler
                        # outlives the launcher, and ITS exit code —
                        # the original failure — is what propagates
                        first_fail = rc
                        _terminate_gang(procs, grace_s)
            time.sleep(0.2)
    finally:
        _terminate_gang(procs, grace_s=0 if first_fail else grace_s)
        for _, p, f in procs:
            if f:
                f.close()
    for rank, p, _ in procs:
        codes.setdefault(rank, p.poll())
    return [codes[r] for r, _, _ in procs], first_fail


def launch(script_args, nproc=1, ips=None, started_port=None,
           backend=None, log_dir=None, extra_env=None,
           grace_s=DEFAULT_GRACE_S):
    """Spawn the trainer processes; returns the list of exit codes."""
    codes, _ = _run_once(script_args, nproc=nproc, ips=ips,
                         started_port=started_port, backend=backend,
                         log_dir=log_dir, extra_env=extra_env,
                         grace_s=grace_s)
    return codes


def _latest_ckpt_step(ckpt_dir):
    """Newest committed checkpoint step under ``ckpt_dir`` (the
    supervisor's view of training progress between incarnations), or
    None when unknown. Import is lazy: the supervisor stays light
    unless crash-loop step tracking is requested."""
    if not ckpt_dir:
        return None
    try:
        from ..checkpoint import manifest as _mf
        steps = _mf.list_steps(ckpt_dir)
        return steps[-1] if steps else _mf.read_latest(ckpt_dir)
    except Exception:
        return None


def _restart_backoff_s(attempt, base_s, cap_s):
    """Exponential backoff with full jitter in [0.5x, 1x]: a crashing
    gang must not hammer a shared checkpoint store / cluster scheduler
    at full speed, and jitter keeps multiple supervisors (one per host
    with --ips) from relaunching in lockstep. base_s <= 0 disables
    (tests)."""
    if base_s <= 0:
        return 0.0
    import random
    d = min(cap_s, base_s * (2.0 ** max(0, attempt - 1)))
    return d * (0.5 + random.random() / 2.0)


def supervise(script_args, max_restarts=0, nproc=1, ips=None,
              started_port=None, backend=None, log_dir=None,
              extra_env=None, grace_s=DEFAULT_GRACE_S,
              backoff_base_s=0.5, backoff_cap_s=15.0,
              elastic=False, min_nproc=1, ckpt_dir=None,
              attempt_log=None):
    """Elastic supervisor: relaunch a failed gang up to
    ``max_restarts`` times. Returns ``(exit_code, restarts_used)`` —
    exit_code is 0 when some incarnation finished clean, else the
    first-failure code of the final attempt.

    Every incarnation gets ``PADDLE_RESTART_ATTEMPT`` in its env; the
    training script pairs this with ``CheckpointManager.maybe_restore``
    to continue from the latest durable snapshot (PR 3's commit
    protocol guarantees the snapshot is complete or absent —
    docs/CHECKPOINTING.md).

    Hardening (docs/RESILIENCE.md): restarts are separated by
    exponential backoff with jitter (``backoff_base_s`` doubling up to
    ``backoff_cap_s``; 0 disables), and when ``started_port`` pins the
    port range, each incarnation shifts to a fresh range
    (``started_port + attempt * nproc``) so a dying worker's socket
    lingering in TIME_WAIT cannot make every restart fail on bind.

    **Elastic topology** (docs/RESILIENCE.md "Elastic topology"):
    with ``elastic=True`` — or whenever a worker exits with
    ``faults.DEVICE_LOSS_EXIT_CODE``, which declares its device
    PERMANENTLY gone — a failed gang is relaunched with the SURVIVING
    rank count (never below ``min_nproc``) instead of retrying the
    dead world size. The shrunk incarnation gets ``PT_ELASTIC_RESUME=1``
    so ``CheckpointManager.maybe_restore`` takes the elastic path:
    re-place, reshard, redistribute cursors. Shrinking applies to
    ``--nproc`` gangs; with ``--ips`` the host list is operator-owned,
    so the supervisor aborts with the failing code instead of guessing
    which host to drop.

    **Crash-loop detection**: ``PT_CRASH_LOOP_N`` (default 3)
    consecutive failures each faster than ``PT_CRASH_LOOP_WINDOW_S``
    (default 5s) after launch AND at the same checkpoint step
    (``ckpt_dir`` names the store to read it from; unknown steps
    compare equal) mean restarts cannot help — the supervisor aborts
    with a postmortem pointer instead of burning the remaining budget.
    In elastic mode a crash loop first tries one shrink (maybe a
    half-dead device keeps killing its rank); only a crash loop at
    ``min_nproc`` aborts.

    ``attempt_log``, when a list, receives one dict per incarnation
    ``{attempt, nproc, codes, first_fail, step, duration_s, shrunk}`` —
    the accounting ``tools/chaos_report.py``'s elastic probe audits."""
    attempt = 0
    loop_n = int(os.environ.get("PT_CRASH_LOOP_N", "3"))
    loop_window_s = float(os.environ.get("PT_CRASH_LOOP_WINDOW_S",
                                         "5.0"))
    fast_fails = 0           # consecutive immediate same-step failures
    last_fail_step = None
    elastic_now = bool(elastic)
    while True:
        env = dict(extra_env or {})
        env["PADDLE_RESTART_ATTEMPT"] = str(attempt)
        if elastic_now and attempt:
            env["PT_ELASTIC_RESUME"] = "1"
        port = started_port
        if port is not None and attempt:
            # fresh range per incarnation; ips-mode endpoints must be
            # identical on every host, so the shift is deterministic
            port = started_port + attempt * max(
                1, nproc if not ips else 1)
        t_launch = time.monotonic()
        codes, first_fail = _run_once(
            script_args, nproc=nproc, ips=ips,
            started_port=port, backend=backend,
            log_dir=log_dir, extra_env=env, grace_s=grace_s)
        duration = time.monotonic() - t_launch
        step = _latest_ckpt_step(ckpt_dir)
        shrunk = False
        if attempt_log is not None:
            attempt_log.append({
                "attempt": attempt, "nproc": len(codes),
                "codes": list(codes), "first_fail": first_fail,
                "step": step, "duration_s": duration,
                "shrunk": False})
        if first_fail == 0:
            return 0, attempt
        if attempt >= max_restarts:
            return first_fail, attempt

        # positive exit codes are ranks that died on their own; the
        # negative ones were torn down by the supervisor and survive
        # a shrink (their state is in the checkpoint either way)
        from .faults import DEVICE_LOSS_EXIT_CODE
        lost = [r for r, c in enumerate(codes)
                if c is not None and c > 0]
        device_lost = first_fail == DEVICE_LOSS_EXIT_CODE
        if device_lost:
            elastic_now = True

        # crash-loop accounting BEFORE deciding the next world size:
        # an immediate failure at an unchanged step means the restart
        # did nothing but burn budget
        immediate = duration < loop_window_s
        same_step = (attempt > 0 and step == last_fail_step)
        fast_fails = fast_fails + 1 if (immediate and
                                        (attempt == 0 or same_step)) \
            else (1 if immediate else 0)
        last_fail_step = step
        looping = fast_fails >= loop_n

        can_shrink = (not ips and len(lost) >= 1
                      and nproc - len(lost) >= min_nproc)
        if (elastic_now and can_shrink
                and (device_lost or looping or elastic)):
            new_nproc = nproc - len(lost)
            print(f"paddle_tpu.distributed.launch: elastic shrink — "
                  f"rank(s) {lost} lost "
                  f"(exit {first_fail}"
                  f"{', device loss' if device_lost else ''}); "
                  f"relaunching with {new_nproc} of {nproc} workers",
                  file=sys.stderr, flush=True)
            nproc = new_nproc
            shrunk = True
            fast_fails = 0   # the world changed; give it a fresh look
            if attempt_log is not None:
                attempt_log[-1]["shrunk"] = True
        elif looping:
            print(f"paddle_tpu.distributed.launch: crash loop — "
                  f"{fast_fails} consecutive failures within "
                  f"{loop_window_s:.1f}s of launch at checkpoint step "
                  f"{step}; aborting with {max_restarts - attempt} "
                  f"restarts unspent. Postmortem: flight-recorder "
                  f"dumps (PT_FLIGHT_DIR) and "
                  f"{log_dir or '--log_dir'}/workerlog.* "
                  f"(docs/RESILIENCE.md)",
                  file=sys.stderr, flush=True)
            return first_fail, attempt
        attempt += 1
        delay = _restart_backoff_s(attempt, backoff_base_s,
                                   backoff_cap_s)
        print(f"paddle_tpu.distributed.launch: gang failed "
              f"(exit {first_fail}); restart {attempt}/{max_restarts}"
              f"{f' at world size {nproc}' if shrunk else ''}"
              f" in {delay:.2f}s",
              file=sys.stderr, flush=True)
        if delay:
            time.sleep(delay)


def main(argv=None):
    ap = argparse.ArgumentParser(
        prog="paddle_tpu.distributed.launch",
        description=__doc__.splitlines()[0])
    ap.add_argument("--nproc", "--nproc_per_node", type=int, default=1,
                    dest="nproc",
                    help="local trainer processes (default 1: one "
                         "process per TPU host)")
    ap.add_argument("--ips", "--cluster_node_ips", default=None,
                    dest="ips",
                    help="comma-separated host list (one process per "
                         "host)")
    ap.add_argument("--started_port", type=int, default=None)
    ap.add_argument("--backend", choices=["tpu", "cpu"], default=None,
                    help="cpu forces JAX_PLATFORMS=cpu in children")
    ap.add_argument("--log_dir", default=None,
                    help="write per-rank workerlog.N files here")
    ap.add_argument("--max-restarts", "--max_restarts", type=int,
                    default=0, dest="max_restarts",
                    help="supervisor mode: relaunch a failed gang up "
                         "to N times (workers resume via "
                         "CheckpointManager; docs/RESILIENCE.md)")
    ap.add_argument("--grace", type=float, default=DEFAULT_GRACE_S,
                    dest="grace_s",
                    help="seconds between SIGTERM and SIGKILL when "
                         "tearing down a failed gang")
    ap.add_argument("--restart-backoff", type=float, default=0.5,
                    dest="backoff_base_s",
                    help="base seconds of the exponential backoff "
                         "between gang restarts (doubles per attempt, "
                         "jittered; 0 disables)")
    ap.add_argument("--restart-backoff-cap", type=float, default=15.0,
                    dest="backoff_cap_s",
                    help="ceiling seconds for the restart backoff")
    ap.add_argument("--elastic", action="store_true",
                    help="relaunch a failed gang with the SURVIVING "
                         "rank count instead of the dead world size; "
                         "workers resume via the elastic restore path "
                         "(docs/RESILIENCE.md 'Elastic topology')")
    ap.add_argument("--min-nproc", "--min_nproc", type=int, default=1,
                    dest="min_nproc",
                    help="never shrink the gang below this many ranks")
    ap.add_argument("--ckpt-dir", "--ckpt_dir", default=None,
                    dest="ckpt_dir",
                    help="checkpoint store the workers save into; lets "
                         "the crash-loop detector compare the global "
                         "step across restarts")
    ap.add_argument("script", help="training script")
    ap.add_argument("script_args", nargs=argparse.REMAINDER)
    args = ap.parse_args(argv)
    code, _restarts = supervise(
        [args.script] + args.script_args, max_restarts=args.max_restarts,
        nproc=args.nproc, ips=args.ips, started_port=args.started_port,
        backend=args.backend, log_dir=args.log_dir,
        grace_s=args.grace_s, backoff_base_s=args.backoff_base_s,
        backoff_cap_s=args.backoff_cap_s, elastic=args.elastic,
        min_nproc=args.min_nproc, ckpt_dir=args.ckpt_dir)
    sys.exit(code)


if __name__ == "__main__":
    main()
