"""Distributed API tests: transpiler structural goldens
(reference test_dist_transpiler.py pattern — assert op sequences without
running a cluster), collective op lowering under shard_map, fleet API.
"""
import numpy as np
import pytest

import jax
import jax.numpy as jnp
from jax.sharding import Mesh, PartitionSpec as P
from jax import shard_map

import paddle_tpu as fluid
from paddle_tpu.core.scope import Scope


def _simple_net():
    fluid.framework.unique_name.reset()
    main, startup = fluid.Program(), fluid.Program()
    with fluid.program_guard(main, startup):
        x = fluid.layers.data("x", [4], dtype="float32")
        y = fluid.layers.data("y", [1], dtype="float32")
        pred = fluid.layers.fc(x, 1)
        cost = fluid.layers.mean(
            fluid.layers.square_error_cost(pred, y))
        fluid.optimizer.SGDOptimizer(0.1).minimize(cost)
    return main, startup, cost


class TestTranspilerStructure:
    def test_collective_mode_inserts_bucketed_allreduce(self):
        """Default FLAGS_allreduce_bucket_mb (32MB) fuses every param
        grad of this small net into one c_allreduce_fused bucket."""
        main, startup, cost = _simple_net()
        cfg = fluid.DistributeTranspilerConfig()
        cfg.mode = "collective"
        t = fluid.DistributeTranspiler(config=cfg)
        t.transpile(trainer_id=0, program=main, trainers=2,
                    startup_program=startup)
        trainer = t.get_trainer_program()
        blk = trainer.global_block()
        ops = [op.type for op in blk.ops]
        assert "c_allreduce_sum" not in ops
        fused = [op for op in blk.ops if op.type == "c_allreduce_fused"]
        assert len(fused) == 1
        # bucket membership covers every param grad exactly once
        n_params = len(main.all_parameters())
        members = [n for op in fused for n in op.input("X")]
        assert len(members) == len(set(members)) == n_params
        assert all(m.endswith("@GRAD") for m in members)
        start_ops = [op.type for op in startup.global_block().ops]
        assert "c_gen_nccl_id" in start_ops
        assert "c_comm_init" in start_ops

    def test_collective_mode_per_tensor_with_bucketing_off(self):
        main, startup, cost = _simple_net()
        fluid.set_flags({"FLAGS_allreduce_bucket_mb": 0.0})
        try:
            cfg = fluid.DistributeTranspilerConfig()
            cfg.mode = "collective"
            t = fluid.DistributeTranspiler(config=cfg)
            t.transpile(trainer_id=0, program=main, trainers=2,
                        startup_program=startup)
            ops = [op.type for op in
                   t.get_trainer_program().global_block().ops]
        finally:
            fluid.set_flags({"FLAGS_allreduce_bucket_mb": 32.0})
        # every param grad gets scale + allreduce after its grad op
        n_params = len(main.all_parameters())
        assert ops.count("c_allreduce_sum") == n_params
        assert "c_allreduce_fused" not in ops

    def test_pserver_mode_transpiles_to_collective(self):
        main, startup, cost = _simple_net()
        t = fluid.DistributeTranspiler()
        with pytest.warns(UserWarning):
            t.transpile(trainer_id=0, program=main,
                        pservers="127.0.0.1:6174,127.0.0.1:6175",
                        trainers=2, startup_program=startup)
        ops = [op.type for op in
               t.get_trainer_program().global_block().ops]
        assert "c_allreduce_fused" in ops or "c_allreduce_sum" in ops
        assert "send" not in ops and "recv" not in ops
        ps = t.get_pserver_program("127.0.0.1:6174")
        assert [op.type for op in ps.global_block().ops] == \
            ["listen_and_serv"]

    def test_transpiled_program_still_runs_single_process(self):
        """world_size-1 semantics: c_* ops are identity; program trains."""
        main, startup, cost = _simple_net()
        cfg = fluid.DistributeTranspilerConfig()
        cfg.mode = "collective"
        t = fluid.DistributeTranspiler(config=cfg)
        t.transpile(trainer_id=0, program=main, trainers=1,
                    startup_program=startup)
        rng = np.random.default_rng(0)
        feed = {"x": rng.standard_normal((8, 4)).astype(np.float32),
                "y": rng.standard_normal((8, 1)).astype(np.float32)}
        scope = Scope()
        with fluid.scope_guard(scope):
            exe = fluid.Executor(fluid.CPUPlace())
            exe.run(startup)
            losses = [float(np.asarray(exe.run(
                main, feed=feed, fetch_list=[cost])[0]))
                for _ in range(5)]
        assert losses[-1] < losses[0]


class TestCollectiveOpsShardMap:
    def test_c_allreduce_sum_psum(self):
        """c_allreduce_sum lowers to a real psum under the axis guard."""
        from paddle_tpu.ops.collective import collective_axis_guard
        from paddle_tpu.core.registry import OPS, ExecContext

        mesh = Mesh(np.array(jax.devices()[:4]), ("dp",))

        class FakeOp:
            type = "c_allreduce_sum"

            def input(self, slot):
                return ["x"] if slot == "X" else []

            def output(self, slot):
                return ["out"] if slot == "Out" else []

            def attr(self, name, default=None):
                return default

            def has_attr(self, name):
                return False

        def f(x):
            env = {"x": x}
            with collective_axis_guard("dp"):
                OPS.get("c_allreduce_sum").lowering(
                    ExecContext(FakeOp(), env))
            return env["out"]

        fm = shard_map(f, mesh=mesh, in_specs=P("dp"), out_specs=P("dp"))
        x = jnp.arange(8, dtype=jnp.float32)
        out = jax.jit(fm)(x)
        # psum over 4 shards of [2] each -> every shard holds the sum
        expect = x.reshape(4, 2).sum(0)
        np.testing.assert_allclose(
            np.asarray(out), np.tile(expect, 4))


class TestCollectiveProd:
    def test_c_allreduce_prod_signs_and_zeros(self):
        """Product reduction must match ncclProd for negatives and
        zeros (not exp(psum(log)) which NaNs)."""
        from paddle_tpu.ops.collective import collective_axis_guard
        from paddle_tpu.core.registry import OPS, ExecContext

        mesh = Mesh(np.array(jax.devices()[:4]), ("dp",))

        class FakeOp:
            type = "c_allreduce_prod"

            def input(self, slot):
                return ["x"] if slot == "X" else []

            def output(self, slot):
                return ["out"] if slot == "Out" else []

            def attr(self, name, default=None):
                return default

            def has_attr(self, name):
                return False

        def f(x):
            env = {"x": x}
            with collective_axis_guard("dp"):
                OPS.get("c_allreduce_prod").lowering(
                    ExecContext(FakeOp(), env))
            return env["out"]

        fm = shard_map(f, mesh=mesh, in_specs=P("dp"),
                       out_specs=P("dp"))
        x = jnp.asarray([[2., -3., 0.5],    # col products:
                         [-1., -2., 4.],    # 2*-1*5*-0.5 = 5
                         [5., 1., 0.],      # -3*-2*1*2 = 12
                         [-0.5, 2., 8.]])   # 0.5*4*0*8 = 0
        out = jax.jit(fm)(x)
        expect = np.prod(np.asarray(x), axis=0)
        np.testing.assert_allclose(
            np.asarray(out).reshape(4, 3),
            np.tile(expect, (4, 1)), rtol=1e-6)


class TestMergeIds:
    def test_merge_ids_restores_original_order(self):
        from paddle_tpu.core.registry import OPS, ExecContext

        # 2 shards by id % 2; original ids deliberately unsorted + dup
        orig = np.array([5, 2, 9, 2, 4], np.int64)
        shard0 = np.array([2, 4], np.int64)   # even ids
        shard1 = np.array([5, 9], np.int64)   # odd ids
        table = np.arange(20, dtype=np.float32).reshape(10, 2)
        x0, x1 = table[shard0], table[shard1]

        class FakeOp:
            type = "merge_ids"

            def input(self, slot):
                return {"Ids": ["ids"], "Rows": ["r0", "r1"],
                        "X": ["x0", "x1"]}.get(slot, [])

            def output(self, slot):
                return ["out"] if slot == "Out" else []

            def attr(self, name, default=None):
                return default

            def has_attr(self, name):
                return False

        env = {"ids": orig, "r0": shard0, "r1": shard1,
               "x0": jnp.asarray(x0), "x1": jnp.asarray(x1)}
        OPS.get("merge_ids").lowering(ExecContext(FakeOp(), env))
        np.testing.assert_array_equal(np.asarray(env["out"]),
                                      table[orig])


class TestLocalSGD:
    def test_localsgd_identity_mode_preserves_training(self):
        """LocalSGD-transpiled program in identity (1-process) mode:
        param = snapshot - (snapshot - param) — training unchanged."""
        main, startup, cost = _simple_net()
        ref_main, ref_startup, ref_cost = _simple_net()

        from paddle_tpu.transpiler.collective import LocalSGD
        LocalSGD().transpile(startup, main, rank=0,
                             endpoints=["a:1", "b:2"],
                             current_endpoint="a:1")
        rng = np.random.default_rng(0)
        feed = {"x": rng.standard_normal((8, 4)).astype(np.float32),
                "y": rng.standard_normal((8, 1)).astype(np.float32)}

        param_names = [p.name for p in ref_main.all_parameters()]

        def run(mainp, startp, costv, init_from=None):
            scope = Scope()
            with fluid.scope_guard(scope):
                exe = fluid.Executor(fluid.CPUPlace())
                exe.run(startp)
                if init_from is not None:
                    for n, a in init_from.items():
                        scope.var(n).get_tensor().set(a)
                        snap = n + "@SNAPSHOT"
                        if scope.find_var(snap) is not None:
                            scope.var(snap).get_tensor().set(a)
                losses = [float(np.asarray(exe.run(
                    mainp, feed=feed, fetch_list=[costv])[0]))
                    for _ in range(4)]
                params = {n: np.asarray(
                    scope.var(n).get_tensor()._array)
                    for n in param_names}
                return losses, params

        init = {}
        scope0 = Scope()
        with fluid.scope_guard(scope0):
            exe = fluid.Executor(fluid.CPUPlace())
            exe.run(ref_startup)
            init = {n: np.asarray(scope0.var(n).get_tensor()._array)
                    for n in param_names}

        ref, _ = run(ref_main, ref_startup, ref_cost, init_from=init)
        got, _ = run(main, startup, cost, init_from=init)
        np.testing.assert_allclose(got, ref, rtol=1e-6)


class TestFleetCollective:
    def test_fleet_minimize_and_run(self, monkeypatch):
        from paddle_tpu.incubate.fleet.collective import fleet, \
            DistributedStrategy
        from paddle_tpu.incubate.fleet.base.role_maker import \
            UserDefinedCollectiveRoleMaker

        fleet.init(UserDefinedCollectiveRoleMaker(
            current_id=0, worker_endpoints=["127.0.0.1:6170"]))
        assert fleet.is_worker() and fleet.worker_num() == 1

        fluid.framework.unique_name.reset()
        main, startup = fluid.Program(), fluid.Program()
        with fluid.program_guard(main, startup):
            x = fluid.layers.data("x", [4], dtype="float32")
            y = fluid.layers.data("y", [1], dtype="float32")
            pred = fluid.layers.fc(x, 1)
            cost = fluid.layers.mean(
                fluid.layers.square_error_cost(pred, y))
            opt = fluid.optimizer.SGDOptimizer(0.1)
            opt = fleet.distributed_optimizer(opt,
                                              DistributedStrategy())
            opt.minimize(cost, startup_program=startup)

        rng = np.random.default_rng(0)
        feed = {"x": rng.standard_normal((8, 4)).astype(np.float32),
                "y": rng.standard_normal((8, 1)).astype(np.float32)}
        scope = Scope()
        with fluid.scope_guard(scope):
            exe = fluid.Executor(fluid.CPUPlace())
            exe.run(startup)
            losses = [float(np.asarray(exe.run(
                fleet.main_program, feed=feed,
                fetch_list=[cost.name])[0])) for _ in range(5)]
        assert losses[-1] < losses[0]

    def test_role_makers(self, monkeypatch):
        from paddle_tpu.incubate.fleet.base.role_maker import \
            PaddleCloudRoleMaker
        monkeypatch.setenv("PADDLE_TRAINER_ID", "2")
        monkeypatch.setenv("PADDLE_TRAINER_ENDPOINTS",
                           "a:1,b:2,c:3,d:4")
        rm = PaddleCloudRoleMaker()
        rm.generate_role()
        assert rm.worker_index() == 2
        assert rm.worker_num() == 4
        assert rm.is_worker()
