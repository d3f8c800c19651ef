"""Distributed stack tests on the 8-device virtual CPU mesh — the analog of
the reference's spawn-on-localhost fake cluster
(test/legacy_test/test_parallel_dygraph_dataparallel.py:30)."""

import numpy as np
import pytest

import jax
import jax.numpy as jnp

import paddle_tpu as paddle
import paddle_tpu.nn as nn
import paddle_tpu.nn.functional as F
import paddle_tpu.distributed as dist
from paddle_tpu.distributed import fleet
from paddle_tpu.distributed.collective import primitives


@pytest.fixture(autouse=True)
def reset_groups():
    yield
    dist.destroy_process_group()
    dist.env.set_global_mesh(None)


class TestTopology:
    def test_mesh_axes(self):
        mesh = dist.build_mesh(dp=2, mp=4)
        assert mesh.shape == {"dp": 2, "pp": 1, "sharding": 1, "sep": 1,
                              "ep": 1, "mp": 4}
        assert mesh.devices.size == 8

    def test_communicate_topology(self):
        from paddle_tpu.distributed.fleet.base.topology import CommunicateTopology

        topo = CommunicateTopology(dims=(2, 1, 1, 1, 4))
        assert topo.world_size() == 8
        assert topo.get_rank(data=1, pipe=0, sharding=0, sep=0, model=2) == 6
        assert topo.get_comm_list("model") == [[0, 1, 2, 3], [4, 5, 6, 7]]
        assert topo.get_comm_list("data") == [[0, 4], [1, 5], [2, 6], [3, 7]]

    def test_fleet_init_and_hcg(self):
        strategy = fleet.DistributedStrategy()
        strategy.hybrid_configs = {"dp_degree": 2, "mp_degree": 4, "pp_degree": 1}
        fleet.init(is_collective=True, strategy=strategy)
        hcg = fleet.get_hybrid_communicate_group()
        assert hcg.get_model_parallel_world_size() == 4
        assert hcg.get_data_parallel_world_size() == 2
        assert hcg.get_parallel_mode() == "tensor_parallel"
        assert hcg.mesh.shape["mp"] == 4


class TestEagerCollectives:
    def test_all_reduce_stacked(self):
        g = dist.new_group(list(range(4)))
        t = paddle.to_tensor(np.arange(8, dtype=np.float32).reshape(4, 2))
        dist.all_reduce(t, group=g)
        ref = np.broadcast_to(np.arange(8, dtype=np.float32).reshape(4, 2).sum(0), (4, 2))
        np.testing.assert_allclose(t.numpy(), ref)

    def test_all_gather(self):
        g = dist.new_group(list(range(4)))
        t = paddle.to_tensor(np.arange(4, dtype=np.float32).reshape(4, 1))
        out = []
        dist.all_gather(out, t, group=g)
        assert len(out) == 4
        np.testing.assert_allclose(out[2].numpy(), [2.0])

    def test_broadcast(self):
        g = dist.new_group(list(range(4)))
        t = paddle.to_tensor(np.arange(4, dtype=np.float32).reshape(4, 1))
        dist.broadcast(t, src=1, group=g)
        np.testing.assert_allclose(t.numpy(), np.ones((4, 1)))

    def test_alltoall(self):
        g = dist.new_group(list(range(2)))
        # in_list[j][i] = what rank i sends to slot j
        a = paddle.to_tensor(np.array([[0.0], [10.0]], np.float32))
        b = paddle.to_tensor(np.array([[1.0], [11.0]], np.float32))
        out = []
        dist.alltoall(out, [a, b], group=g)
        np.testing.assert_allclose(out[0].numpy(), [[0.0], [1.0]])
        np.testing.assert_allclose(out[1].numpy(), [[10.0], [11.0]])

    def test_reduce_op_variants(self):
        g = dist.new_group(list(range(2)))
        t = paddle.to_tensor(np.array([[1.0], [3.0]], np.float32))
        dist.all_reduce(t, op=dist.ReduceOp.MAX, group=g)
        np.testing.assert_allclose(t.numpy(), [[3.0], [3.0]])


class TestPrimitives:
    def test_psum_inside_shard_map(self):
        from jax.sharding import PartitionSpec as P
        mesh = dist.build_mesh(dp=8)
        x = jnp.arange(8.0)

        def body(v):
            return primitives.all_reduce(v, axis="dp")

        f = jax.shard_map(body, mesh=mesh, in_specs=P("dp"),
                          out_specs=P("dp"))
        out = f(x)
        np.testing.assert_allclose(np.asarray(out), np.full(8, 28.0))

    def test_ppermute_ring(self):
        from jax.sharding import PartitionSpec as P
        mesh = dist.build_mesh(pp=8)
        x = jnp.arange(8.0)
        perm = [(i, (i + 1) % 8) for i in range(8)]

        def body(v):
            return primitives.ppermute(v, "pp", perm)

        out = jax.shard_map(body, mesh=mesh, in_specs=P("pp"),
                            out_specs=P("pp"))(x)
        np.testing.assert_allclose(np.asarray(out), np.roll(np.arange(8.0), 1))


class TestTensorParallelLayers:
    def test_column_row_match_dense(self):
        paddle.seed(0)
        fleet_strategy = fleet.DistributedStrategy()
        fleet_strategy.hybrid_configs = {"mp_degree": 4, "dp_degree": 2}
        fleet.init(is_collective=True, strategy=fleet_strategy)
        from paddle_tpu.distributed.fleet.layers.mpu import (
            ColumnParallelLinear,
            RowParallelLinear,
        )

        col = ColumnParallelLinear(8, 16, gather_output=False)
        row = RowParallelLinear(16, 8, input_is_parallel=True)
        x = paddle.randn([2, 8])
        out = row(col(x))
        # dense oracle with the same weights
        ref = (x.numpy() @ col.weight.numpy() + col.bias.numpy()) @ row.weight.numpy() + row.bias.numpy()
        np.testing.assert_allclose(out.numpy(), ref, rtol=1e-4, atol=1e-5)
        # sharding metadata present for the compiled path
        from jax.sharding import PartitionSpec as P

        assert col.weight.dist_attr == P(None, "mp")
        assert row.weight.dist_attr == P("mp", None)

    def test_vocab_parallel_embedding(self):
        from paddle_tpu.distributed.fleet.layers.mpu import VocabParallelEmbedding

        dist.build_mesh(mp=4, dp=2)
        emb = VocabParallelEmbedding(16, 8)
        ids = paddle.to_tensor([[1, 5], [7, 3]], dtype="int32")
        out = emb(ids)
        assert out.shape == [2, 2, 8]
        np.testing.assert_allclose(out.numpy()[0, 0], emb.weight.numpy()[1], rtol=1e-6)


class TestDistributedTrainStep:
    def _mlp_with_tp(self):
        from paddle_tpu.distributed.fleet.layers.mpu import (
            ColumnParallelLinear,
            RowParallelLinear,
        )

        class MLP(nn.Layer):
            def __init__(self):
                super().__init__()
                self.fc1 = ColumnParallelLinear(8, 32, gather_output=False)
                self.fc2 = RowParallelLinear(32, 8, input_is_parallel=True)

            def forward(self, x):
                return self.fc2(F.relu(self.fc1(x)))

        return MLP()

    def test_dp_mp_train_step_runs_sharded(self):
        paddle.seed(0)
        mesh = dist.build_mesh(dp=2, mp=4)
        net = self._mlp_with_tp()
        opt = paddle.optimizer.AdamW(learning_rate=0.01, parameters=net.parameters())
        step = dist.DistributedTrainStep(net, F.mse_loss, opt, mesh=mesh)
        rng = np.random.RandomState(0)
        X = paddle.to_tensor(rng.rand(16, 8).astype(np.float32))
        y = paddle.to_tensor(rng.rand(16, 8).astype(np.float32))
        losses = [float(step(X, y).numpy()) for _ in range(10)]
        assert losses[-1] < losses[0]
        # fc1 weight must actually be sharded over mp
        sh = step.params["fc1.weight"].sharding
        assert "mp" in str(sh.spec)

    def test_matches_single_device_training(self):
        """Numeric parity: dp=2 x mp=4 vs single-device, same seeds/data —
        the hybrid_parallel_mp_model.py test pattern."""
        rng = np.random.RandomState(1)
        X = rng.rand(8, 8).astype(np.float32)
        y = rng.rand(8, 8).astype(np.float32)

        def run(distributed):
            paddle.seed(7)
            if distributed:
                mesh = dist.build_mesh(dp=2, mp=4)
            else:
                dist.env.set_global_mesh(None)
            net = self._mlp_with_tp()
            opt = paddle.optimizer.SGD(learning_rate=0.1, parameters=net.parameters())
            if distributed:
                step = dist.DistributedTrainStep(net, F.mse_loss, opt, mesh=mesh)
            else:
                step = paddle.jit.TrainStep(net, F.mse_loss, opt)
            out = [float(step(paddle.to_tensor(X), paddle.to_tensor(y)).numpy()) for _ in range(5)]
            step.sync_weights()
            return out, net.fc1.weight.numpy()

        dist_losses, dist_w = run(True)
        single_losses, single_w = run(False)
        np.testing.assert_allclose(dist_losses, single_losses, rtol=1e-4)
        np.testing.assert_allclose(dist_w, single_w, rtol=1e-4, atol=1e-5)

    def test_sharding_stage1_opt_states_sharded(self):
        paddle.seed(0)
        mesh = dist.build_mesh(sharding=8)
        net = nn.Sequential(nn.Linear(16, 64), nn.Tanh(), nn.Linear(64, 16))
        opt = paddle.optimizer.AdamW(learning_rate=0.01, parameters=net.parameters())
        step = dist.DistributedTrainStep(net, F.mse_loss, opt, mesh=mesh, sharding_stage=1)
        m_state = step.opt_states["0.weight"]["m"]
        assert "sharding" in str(m_state.sharding.spec)
        X = paddle.randn([8, 16])
        y = paddle.randn([8, 16])
        l0 = float(step(X, y).numpy())
        l1 = float(step(X, y).numpy())
        assert np.isfinite(l1)

    def test_sharding_stage3_params_sharded(self):
        paddle.seed(0)
        mesh = dist.build_mesh(sharding=8)
        net = nn.Sequential(nn.Linear(16, 64), nn.Tanh(), nn.Linear(64, 16))
        opt = paddle.optimizer.AdamW(learning_rate=0.01, parameters=net.parameters())
        step = dist.DistributedTrainStep(net, F.mse_loss, opt, mesh=mesh, sharding_stage=3)
        assert "sharding" in str(step.params["0.weight"].sharding.spec)
        l0 = float(step(paddle.randn([8, 16]), paddle.randn([8, 16])).numpy())
        assert np.isfinite(l0)

    def test_low_mem_recipe_trains(self):
        """bf16 params (amp.decorate O2) + bf16 AdamW moments + recompute:
        the recipe both training cells run (a 1.3B model on one 16 GB chip),
        on a tiny config."""
        import paddle_tpu.amp as amp
        from paddle_tpu.models import (GPTConfig, GPTForCausalLM,
                                       GPTPretrainingCriterion)

        paddle.seed(0)
        cfg = GPTConfig(hidden_size=64, num_layers=2, num_heads=2,
                        vocab_size=512, max_position_embeddings=64)
        cfg.use_recompute = True
        model = GPTForCausalLM(cfg)
        crit = GPTPretrainingCriterion(cfg)
        amp.decorate(model, level="O2", dtype="bfloat16")
        opt = paddle.optimizer.AdamW(learning_rate=1e-4,
                                     moment_dtype="bfloat16",
                                     parameters=model.parameters())
        step = dist.DistributedTrainStep(
            model, lambda lg, lb: crit(lg, lb), opt,
            mesh=dist.build_mesh(devices=jax.devices()[:1]))
        rng = np.random.default_rng(0)
        ids = paddle.to_tensor(rng.integers(0, cfg.vocab_size, (2, 16)))
        labels = paddle.to_tensor(rng.integers(0, cfg.vocab_size, (2, 16)))
        dts = {str(v.dtype) for v in step.params.values()}
        assert "bfloat16" in dts, dts
        mdts = {str(st["m"].dtype) for st in step.opt_states.values()
                if "m" in st}
        assert mdts == {"bfloat16"}, mdts
        losses = [float(step(ids, labels)) for _ in range(4)]
        assert all(np.isfinite(v) for v in losses), losses
        assert losses[-1] < losses[0], losses


class TestGroupShardedAPI:
    def test_levels(self):
        dist.build_mesh(sharding=8)
        net = nn.Linear(8, 8)
        opt = paddle.optimizer.AdamW(learning_rate=0.01, parameters=net.parameters())
        m, o, s = dist.group_sharded_parallel(net, opt, "p_g_os")
        assert o._sharding_stage == 3
        from jax.sharding import PartitionSpec as P

        assert net.weight.dist_attr is not None

    def test_bad_level_raises(self):
        net = nn.Linear(2, 2)
        opt = paddle.optimizer.SGD(0.1, parameters=net.parameters())
        with pytest.raises(ValueError):
            dist.group_sharded_parallel(net, opt, "bogus")


class TestRecompute:
    def test_eager_recompute_grads_match(self):
        from paddle_tpu.distributed.fleet.utils import recompute

        paddle.seed(0)
        net = nn.Sequential(nn.Linear(4, 8), nn.Tanh(), nn.Linear(8, 4))
        x = paddle.randn([2, 4])

        loss1 = net(x).sum()
        loss1.backward()
        g_ref = net[0].weight.grad.numpy().copy()
        net.clear_gradients()

        out = recompute(net, x)
        out.sum().backward()
        np.testing.assert_allclose(net[0].weight.grad.numpy(), g_ref, rtol=1e-4, atol=1e-5)

    def test_jit_recompute_in_train_step(self):
        from paddle_tpu.distributed.fleet.utils import recompute

        class Net(nn.Layer):
            def __init__(self):
                super().__init__()
                self.fc1 = nn.Linear(4, 8)
                self.fc2 = nn.Linear(8, 1)

            def forward(self, x):
                h = recompute(lambda v: F.relu(self.fc1(v)), x)
                return self.fc2(h)

        net = Net()
        opt = paddle.optimizer.SGD(learning_rate=0.1, parameters=net.parameters())
        step = paddle.jit.TrainStep(net, F.mse_loss, opt)
        loss = step(paddle.randn([4, 4]), paddle.randn([4, 1]))
        assert np.isfinite(float(loss.numpy()))


class TestDataParallel:
    def test_wrapper_api(self):
        net = nn.Linear(4, 2)
        dp = paddle.DataParallel(net)
        out = dp(paddle.ones([2, 4]))
        assert out.shape == [2, 2]
        assert len(dp.parameters()) == 2
        assert "weight" in dict(dp.named_parameters())


class TestCollectiveRegressions:
    """Fixes from review: p2p mailbox routing, alltoall_single transpose,
    reduce_scatter non-SUM axis, fused dp-sep group."""

    def test_send_recv_nonzero_dst(self):
        import paddle_tpu.distributed as dist

        g = dist.new_group(list(range(4)))
        t = paddle.to_tensor(np.arange(4, dtype="float32"))
        dist.send(t, dst=1, group=g)
        out = paddle.zeros([4])
        dist.recv(out, src=0, group=g)
        np.testing.assert_allclose(out.numpy(), t.numpy())

    def test_alltoall_single_transpose(self):
        import paddle_tpu.distributed as dist

        dist.init_parallel_env()
        n = dist.get_world_size()
        g = dist.new_group(list(range(2)))
        # stacked [src=2, dst=2, per=1] rows: a0 b0 / a1 b1 -> a0 a1 / b0 b1
        src = paddle.to_tensor(np.array([[0.0], [1.0], [2.0], [3.0]], "float32"))
        out = paddle.zeros([4, 1])
        dist.alltoall_single(out, src, group=g)
        np.testing.assert_allclose(out.numpy().ravel(), [0.0, 2.0, 1.0, 3.0])

    def test_reduce_scatter_max(self):
        import paddle_tpu.distributed as dist

        g = dist.new_group(list(range(2)))
        # entry j = per-source contributions for destination j
        t0 = paddle.to_tensor(np.array([[1.0], [8.0]], "float32"))
        t1 = paddle.to_tensor(np.array([[3.0], [2.0]], "float32"))
        out = paddle.zeros([2, 1])
        dist.reduce_scatter(out, [t0, t1], op=dist.ReduceOp.MAX, group=g)
        np.testing.assert_allclose(out.numpy().ravel(), [8.0, 3.0])

    def test_dp_sep_group_ranks(self):
        from paddle_tpu.distributed.fleet.base.topology import (
            CommunicateTopology,
            HybridCommunicateGroup,
        )

        topo = CommunicateTopology(dims=(2, 1, 1, 2, 2))  # dp=2, sep=2, mp=2
        hcg = HybridCommunicateGroup(topo)
        # rank 0's dp-sep peers: all ranks with the same mp coordinate
        ranks = hcg.get_dp_sep_parallel_group().ranks
        assert len(ranks) == 4
        assert 0 in ranks


class TestMixPrecisionUtils:
    def test_main_grad_accumulation_and_step(self):
        """fleet.utils.mix_precision_utils: bf16 grads accumulate into f32
        main_grad via hooks; the wrapped optimizer steps on them (reference
        mix_precision_utils.py MixPrecisionLayer :35 / MixPrecisionOptimizer
        :97)."""
        import numpy as np

        import paddle_tpu as paddle
        import paddle_tpu.nn as nn
        import paddle_tpu.optimizer as opt
        from paddle_tpu.distributed.fleet.utils.mix_precision_utils import (
            MixPrecisionLayer,
            MixPrecisionOptimizer,
        )

        paddle.seed(0)
        inner = nn.Linear(8, 4)
        for _, p in inner.named_parameters():
            p._value = p._value.astype("bfloat16")
        model = MixPrecisionLayer(inner, dtype="bfloat16")
        o = MixPrecisionOptimizer(
            opt.SGD(learning_rate=0.1, parameters=inner.parameters()))
        losses = []
        for _ in range(5):
            x = paddle.to_tensor(
                np.ones((4, 8), np.float32)).astype("bfloat16")
            loss = (model(x).astype("float32") ** 2).mean()
            loss.backward()
            assert str(inner.weight.main_grad._value.dtype) == "float32"
            o.step()
            o.clear_grad()
            assert inner.weight.main_grad is None  # cleared with grads
            losses.append(float(loss.numpy()))
        assert losses[-1] < losses[0]


class TestHybridParallelOptimizer:
    def test_distributed_clip_single_controller_matches_plain(self):
        """On a single-controller 2-mp mesh params hold global values, so the
        distributed clip must equal the plain ClipGradByGlobalNorm result
        (mp reduction is a placement no-op; replicated params counted once)."""
        from paddle_tpu.framework.core import Parameter
        import paddle_tpu.optimizer as opt

        strat = fleet.DistributedStrategy()
        strat.hybrid_configs = {"dp_degree": 1, "mp_degree": 2}
        fleet.init(is_collective=True, strategy=strat)

        def build():
            wd = Parameter(jnp.zeros((4,), jnp.float32))
            wd.is_distributed = True
            wr = Parameter(jnp.zeros((2,), jnp.float32))
            wd.grad = paddle.to_tensor(np.arange(4, dtype=np.float32))
            wr.grad = paddle.to_tensor(np.asarray([6.0, 8.0], np.float32))
            return wd, wr

        wd1, wr1 = build()
        inner = opt.SGD(learning_rate=1.0, parameters=[wd1, wr1],
                        grad_clip=nn.ClipGradByGlobalNorm(1.0))
        hpo = fleet.distributed_optimizer(inner)
        assert hpo._dist_clip is not None, "global-norm clip not wrapped"
        hpo.step()

        wd2, wr2 = build()
        plain = opt.SGD(learning_rate=1.0, parameters=[wd2, wr2],
                        grad_clip=nn.ClipGradByGlobalNorm(1.0))
        plain.step()
        np.testing.assert_allclose(wd1.numpy(), wd2.numpy(), rtol=1e-6)
        np.testing.assert_allclose(wr1.numpy(), wr2.numpy(), rtol=1e-6)

    def test_param_list_dedup(self):
        from paddle_tpu.framework.core import Parameter
        import paddle_tpu.optimizer as opt

        strat = fleet.DistributedStrategy()
        strat.hybrid_configs = {"dp_degree": 1, "mp_degree": 2}
        fleet.init(is_collective=True, strategy=strat)
        shared = Parameter(jnp.zeros((2,), jnp.float32))
        other = Parameter(jnp.zeros((2,), jnp.float32))
        inner = opt.SGD(learning_rate=1.0,
                        parameters=[shared, other, shared])
        hpo = fleet.distributed_optimizer(inner)
        assert len(hpo._obtain_optimizer_parameters_list()) == 2
        # the twice-listed (tied) param is updated exactly ONCE per step
        shared.grad = paddle.to_tensor(np.ones(2, np.float32))
        other.grad = paddle.to_tensor(np.ones(2, np.float32))
        hpo.step()
        np.testing.assert_allclose(shared.numpy(), -1.0)
        np.testing.assert_allclose(other.numpy(), -1.0)


class TestStoreKeyCleanup:
    """ADVICE round-3: group-communicator store keys must not leak for the
    job's life — destroy_process_group sweeps this rank's residual gar/
    keys (eager_multiproc.cleanup_group_keys)."""

    def test_rolling_and_destroy_cleanup(self, monkeypatch):
        from paddle_tpu.distributed import eager_multiproc as mp
        from paddle_tpu.distributed.store import TCPStore

        store = TCPStore(is_master=True, port=0)
        try:
            monkeypatch.setattr(mp, "rank", lambda: 0)
            monkeypatch.setattr(mp, "nprocs", lambda: 2)
            mp._group_seq.clear()
            for _ in range(4):
                out = mp.store_allreduce_group(
                    store, np.array([2.0]), [0], gid=7)
                assert float(out[0]) == 2.0
            tag = "0#g7"
            live = [s for s in range(4)
                    if store.tryget(f"gar/{tag}/{s}/0") is not None]
            # rolling cleanup keeps only the last two rounds
            assert live == [2, 3], live

            # destroy_process_group sweeps the rest
            import paddle_tpu.distributed as dist
            from paddle_tpu.distributed import store as store_mod

            monkeypatch.setattr(store_mod,
                                "create_or_get_global_tcp_store",
                                lambda *a, **k: store)
            dist.destroy_process_group()
            live = [s for s in range(4)
                    if store.tryget(f"gar/{tag}/{s}/0") is not None]
            assert live == [], live
            assert tag not in mp._group_seq
        finally:
            mp._group_seq.clear()
            store.close()
