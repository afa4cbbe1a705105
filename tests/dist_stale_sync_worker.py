"""Worker for the half-async (stale-update) 2-process cluster test.

Each process holds its OWN divergent copy of the parameters (the
defining property of half-async pserver training the SPMD global-view
path cannot express) and executes the StaleSyncSGD-transpiled program
under shard_map over a one-device-per-process "dp" mesh with
per-device collective semantics (collective_axis_guard), so the
program's c_allreduce_sum really crosses processes at sync rounds and
is a masked no-op during local steps.

Prints per-step loss and a parameter fingerprint so the driver can
assert convergence, mid-period divergence, and sync-round agreement.
"""
import json
import os
import sys

os.environ.setdefault("JAX_PLATFORMS", "cpu")
os.environ.pop("XLA_FLAGS", None)

import numpy as np
import jax
import jax.numpy as jnp

jax.config.update("jax_platforms", "cpu")
jax.config.update("jax_cpu_collectives_implementation", "gloo")

sys.path.insert(0, os.path.dirname(os.path.dirname(
    os.path.abspath(__file__))))

import paddle_tpu as fluid  # noqa: E402
from paddle_tpu import layers  # noqa: E402
from paddle_tpu.core.engine import run_block_ops  # noqa: E402
from paddle_tpu.core.registry import _RngCtx  # noqa: E402
from paddle_tpu.core.scope import Scope  # noqa: E402
from paddle_tpu.ops.collective import collective_axis_guard  # noqa: E402
from paddle_tpu.transpiler import DistributeTranspiler  # noqa: E402
from paddle_tpu.transpiler.distribute_transpiler import (  # noqa: E402
    DistributeTranspilerConfig)

K = 3  # staleness bound (avg every K steps)
STEPS = 12


def build():
    fluid.framework.unique_name.reset()
    main, startup = fluid.Program(), fluid.Program()
    with fluid.program_guard(main, startup):
        x = layers.data("x", [8], dtype="float32")
        y = layers.data("y", [1], dtype="float32")
        h = layers.fc(x, 32, act="relu",
                      param_attr=fluid.ParamAttr(name="w0"),
                      bias_attr=fluid.ParamAttr(name="b0"))
        pred = layers.fc(h, 1, param_attr=fluid.ParamAttr(name="w1"),
                         bias_attr=fluid.ParamAttr(name="b1"))
        loss = layers.mean(layers.square_error_cost(pred, y))
        fluid.optimizer.SGDOptimizer(0.05).minimize(loss)
    return main, startup, loss


def main():
    rank = int(os.environ["PADDLE_TRAINER_ID"])
    nranks = int(os.environ["PADDLE_TRAINERS_NUM"])
    eps = os.environ["PADDLE_TRAINER_ENDPOINTS"].split(",")
    jax.distributed.initialize(coordinator_address=eps[0],
                               num_processes=nranks, process_id=rank)
    assert jax.process_count() == nranks

    main_prog, startup, loss = build()
    cfg = DistributeTranspilerConfig()
    cfg.mode = "collective"
    cfg.stale_steps = K
    t = DistributeTranspiler(cfg)
    import warnings
    with warnings.catch_warnings():
        warnings.simplefilter("ignore")
        t.transpile(rank, program=main_prog, trainers=eps,
                    sync_mode=False, startup_program=startup)

    # run startup locally to materialize params + snapshots + counter
    scope = Scope()
    with fluid.scope_guard(scope):
        fluid.Executor(fluid.CPUPlace()).run(startup)

    block = main_prog.global_block()
    persist = sorted(
        n for n, v in block.vars.items()
        if v.persistable and scope.find_var(n) is not None
        and scope.find_var(n).is_initialized())
    state = {}
    for n in persist:
        v = scope.find_var(n).get_value()
        arr = np.asarray(v.array if hasattr(v, "array") else v)
        state[n] = arr

    from jax.sharding import Mesh, NamedSharding, PartitionSpec as P
    from jax import shard_map
    mesh = Mesh(np.array(jax.devices()), ("dp",))

    def to_global(local):
        # leading "dp" dim: each process contributes its own copy
        gshape = (nranks,) + local.shape
        return jax.make_array_from_process_local_data(
            NamedSharding(mesh, P("dp")), local[None], gshape)

    g_state = {n: to_global(a) for n, a in state.items()}

    def local_step(st, feeds):
        st = {n: a[0] for n, a in st.items()}       # drop local lead 1
        feeds = {n: a[0] for n, a in feeds.items()}
        env = dict(st)
        env.update(feeds)
        with collective_axis_guard("dp"):
            run_block_ops(block, env, _RngCtx(jnp.zeros(2, jnp.uint32)),
                          {}, None)
        new_st = {n: env[n][None] for n in st}
        return new_st, env[loss.name].reshape(1)

    stepped = jax.jit(shard_map(
        local_step, mesh=mesh,
        in_specs=(P("dp"), P("dp")), out_specs=(P("dp"), P("dp")),
        check_vma=False))

    rng = np.random.RandomState(7 + rank)   # DIFFERENT data per rank
    losses, prints = [], []
    for step in range(STEPS):
        gx = rng.rand(8, 8).astype(np.float32)
        gy = gx.sum(1, keepdims=True).astype(np.float32) / 4
        feeds = {"x": to_global(gx), "y": to_global(gy)}
        g_state, l = stepped(g_state, feeds)
        local_l = np.asarray(l.addressable_shards[0].data).reshape(-1)
        losses.append(float(local_l[0]))
        w_local = np.asarray(
            g_state["w1"].addressable_shards[0].data)
        # fingerprint of THIS rank's param copy after the step
        prints.append(float(np.abs(w_local).sum()))
    print("LOSSES " + json.dumps(losses), flush=True)
    print("WSUM " + json.dumps(prints), flush=True)


if __name__ == "__main__":
    main()
