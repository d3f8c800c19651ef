"""MoE / expert-parallel tests.

Oracle pattern follows the reference's OpTest + hybrid-parallel parity tests
(test/collective/fleet/...): dense-dispatch MoE vs an explicit per-token
python loop, and the expert-parallel path vs the replicated run.
"""

import os

import jax
import numpy as np
import pytest

import paddle_tpu as paddle
import paddle_tpu.distributed as dist
import paddle_tpu.nn as nn
import paddle_tpu.nn.functional as F
from paddle_tpu.incubate.distributed.models.moe import (
    ExpertFFN,
    GShardGate,
    MoELayer,
    NaiveGate,
    SwitchGate,
)


@pytest.fixture(autouse=True)
def _reset_mesh():
    yield
    dist.env.set_global_mesh(None)


def _ref_moe(x, gate_w, gate_b, w1, b1, w2, b2, topk, normalize=True):
    """Per-token loop oracle: out[t] = sum_j w_j * FFN_{e_j}(x[t])."""
    import jax

    T, M = x.shape
    logits = x @ gate_w + gate_b
    probs = np.asarray(jax.nn.softmax(logits.astype(np.float32), axis=-1))
    out = np.zeros_like(x)
    for t in range(T):
        idx = np.argsort(-probs[t])[:topk]
        w = probs[t][idx]
        if normalize:
            w = w / max(w.sum(), 1e-9)
        for j, e in enumerate(idx):
            h = np.asarray(jax.nn.gelu(x[t] @ w1[e] + b1[e][0]))
            out[t] += w[j] * (h @ w2[e] + b2[e][0])
    return out


class TestMoENumerics:
    def test_naive_gate_matches_loop_oracle(self):
        paddle.seed(0)
        E, M, H, T = 4, 16, 32, 24
        layer = MoELayer(M, ExpertFFN(E, M, H), gate={"type": "naive", "top_k": 2})
        layer.eval()
        rng = np.random.RandomState(0)
        x = rng.randn(T, M).astype(np.float32)
        got = layer(paddle.to_tensor(x)).numpy()
        ref = _ref_moe(
            x,
            np.asarray(layer.gate.gate.weight._value),
            np.asarray(layer.gate.gate.bias._value),
            np.asarray(layer.experts.w1._value), np.asarray(layer.experts.b1._value),
            np.asarray(layer.experts.w2._value), np.asarray(layer.experts.b2._value),
            topk=2,
        )
        np.testing.assert_allclose(got, ref, rtol=2e-4, atol=2e-4)

    def test_list_experts_match_stacked(self):
        """Reference-parity list-of-experts path == stacked ExpertFFN path
        when weights are copied across."""
        paddle.seed(1)
        E, M, H = 4, 8, 16
        stacked = MoELayer(M, ExpertFFN(E, M, H), gate={"type": "naive", "top_k": 2})
        stacked.eval()

        class Expert(nn.Layer):
            def __init__(self, e):
                super().__init__()
                self.fc1 = nn.Linear(M, H)
                self.fc2 = nn.Linear(H, M)
                self.fc1.weight.set_value(stacked.experts.w1[e])
                self.fc1.bias.set_value(stacked.experts.b1[e].reshape([H]))
                self.fc2.weight.set_value(stacked.experts.w2[e])
                self.fc2.bias.set_value(stacked.experts.b2[e].reshape([M]))

            def forward(self, x):
                return self.fc2(F.gelu(self.fc1(x), approximate=True))

        listed = MoELayer(M, [Expert(e) for e in range(E)],
                          gate=stacked.gate)
        listed.eval()
        x = paddle.to_tensor(np.random.RandomState(2).randn(12, M).astype(np.float32))
        np.testing.assert_allclose(stacked(x).numpy(), listed(x).numpy(),
                                   rtol=1e-5, atol=1e-5)

    def test_switch_capacity_drops_overflow(self):
        """Tokens beyond expert capacity produce zero rows (reference
        gshard_gate.py capacity pruning semantics)."""
        paddle.seed(0)
        M = 8
        gate = SwitchGate(M, num_expert=2, capacity=(0.5, 0.5))
        # force every token to expert 0
        gate.gate.weight.set_value(paddle.to_tensor(
            np.zeros((M, 2), np.float32)))
        gate.gate.bias.set_value(paddle.to_tensor(np.array([10.0, -10.0], np.float32)))
        layer = MoELayer(M, ExpertFFN(2, M, 16), gate=gate)
        layer.eval()
        T = 8
        x = paddle.to_tensor(np.random.RandomState(3).randn(T, M).astype(np.float32))
        out = layer(x).numpy()
        cap = gate.capacity(T)  # ceil(0.5 * 8 / 2) = 2
        nonzero_rows = (np.abs(out) > 1e-7).any(axis=-1).sum()
        assert nonzero_rows == cap

    def test_gshard_gate_l_aux_and_grads(self):
        paddle.seed(0)
        E, M, H = 4, 8, 16
        layer = MoELayer(M, ExpertFFN(E, M, H), gate={"type": "gshard", "top_k": 2})
        x = paddle.to_tensor(np.random.RandomState(4).randn(16, M).astype(np.float32))
        out = layer(x)
        assert layer.l_aux is not None
        (out.sum() + layer.l_aux).backward()
        assert float(np.abs(np.asarray(layer.experts.w1.grad._value)).sum()) > 0
        assert layer.gate.gate.weight.grad is not None


class TestExpertParallel:
    def test_ep_sharded_train_step(self):
        """Experts sharded over the dp axis (the reference's moe_group=data
        group), whole step jitted over the mesh."""
        paddle.seed(0)
        mesh = dist.build_mesh(dp=4, mp=2)

        class Net(nn.Layer):
            def __init__(self):
                super().__init__()
                self.moe = MoELayer(8, ExpertFFN(4, 8, 16, ep_axis="dp"),
                                    gate={"type": "naive", "top_k": 2},
                                    ep_axis="dp")

            def forward(self, x):
                return self.moe(x)

        net = Net()
        opt = paddle.optimizer.AdamW(learning_rate=0.01, parameters=net.parameters())
        step = dist.DistributedTrainStep(net, F.mse_loss, opt, mesh=mesh)
        rng = np.random.RandomState(0)
        X = paddle.to_tensor(rng.rand(16, 8).astype(np.float32))
        y = paddle.to_tensor(rng.rand(16, 8).astype(np.float32))
        losses = [float(step(X, y).numpy()) for _ in range(8)]
        assert losses[-1] < losses[0]
        sh = step.params["moe.experts.w1"].sharding
        assert "dp" in str(sh.spec)


class TestFusedMoE:
    def test_fused_moe_matches_oracle(self):
        import paddle_tpu.incubate.nn.functional as IF

        rng = np.random.RandomState(5)
        E, M, H, T = 4, 8, 16, 12
        x = rng.randn(T, M).astype(np.float32) * 0.5
        gw = rng.randn(M, E).astype(np.float32) * 0.1
        w1 = rng.randn(E, M, 2 * H).astype(np.float32) * 0.1
        w2 = rng.randn(E, H, M).astype(np.float32) * 0.1
        got = IF.fused_moe(paddle.to_tensor(x), paddle.to_tensor(gw),
                           paddle.to_tensor(w1), paddle.to_tensor(w2),
                           moe_topk=2).numpy()

        import jax
        logits = x @ gw
        probs = np.asarray(jax.nn.softmax(logits.astype(np.float32), axis=-1))
        ref = np.zeros_like(x)
        for t in range(T):
            idx = np.argsort(-probs[t])[:2]
            w = probs[t][idx]
            w = w / w.sum()
            for j, e in enumerate(idx):
                h = x[t] @ w1[e]
                u, g = h[:H], h[H:]
                h = np.asarray(jax.nn.silu(u)) * g
                ref[t] += w[j] * (h @ w2[e])
        np.testing.assert_allclose(got, ref, rtol=2e-4, atol=2e-4)

    def test_fused_moe_group_routing(self):
        """group_moe: per-group softmax + top-1 per group vs a numpy oracle."""
        import paddle_tpu.incubate.nn.functional as IF
        import jax

        rng = np.random.RandomState(7)
        E, M, H, T, K = 4, 8, 16, 12, 2
        x = rng.randn(T, M).astype(np.float32) * 0.5
        gw = rng.randn(M, E).astype(np.float32) * 0.5
        w1 = rng.randn(E, M, 2 * H).astype(np.float32) * 0.1
        w2 = rng.randn(E, H, M).astype(np.float32) * 0.1
        got = IF.fused_moe(paddle.to_tensor(x), paddle.to_tensor(gw),
                           paddle.to_tensor(w1), paddle.to_tensor(w2),
                           moe_topk=K, group_moe=True).numpy()

        Eg = E // K
        logits = (x @ gw).reshape(T, K, Eg)
        gp = np.asarray(jax.nn.softmax(logits.astype(np.float32), axis=-1))
        ref = np.zeros_like(x)
        for t in range(T):
            sel = [(g, int(np.argmax(gp[t, g]))) for g in range(K)]
            w = np.asarray([gp[t, g, e] for g, e in sel])
            w = w / w.sum()  # norm_topk_prob default True
            for wj, (g, e) in zip(w, sel):
                eid = g * Eg + e
                h = x[t] @ w1[eid]
                u, gg = h[:H], h[H:]
                h = np.asarray(jax.nn.silu(u)) * gg
                ref[t] += wj * (h @ w2[eid])
        np.testing.assert_allclose(got, ref, rtol=2e-4, atol=2e-4)
        with pytest.raises(ValueError):
            IF.fused_moe(paddle.to_tensor(x), paddle.to_tensor(gw),
                         paddle.to_tensor(w1), paddle.to_tensor(w2),
                         moe_topk=3, group_moe=True)

    def test_fused_moe_weight_only_int8(self):
        """weight_only_int8: int8 expert weights + per-out-channel scales
        reproduce the fp32 MoE within quantization error (reference cutlass
        weight-only grouped GEMM path)."""
        import paddle_tpu.incubate.nn.functional as IF

        rng = np.random.RandomState(7)
        E, M, H, T = 4, 8, 16, 12
        x = rng.randn(T, M).astype(np.float32) * 0.5
        gw = rng.randn(M, E).astype(np.float32) * 0.1
        w1 = rng.randn(E, M, 2 * H).astype(np.float32) * 0.1
        w2 = rng.randn(E, H, M).astype(np.float32) * 0.1

        def quant(w):
            scale = np.abs(w).max(axis=1) / 127.0  # [E, out]
            q = np.clip(np.round(w / scale[:, None, :]), -128, 127).astype(np.int8)
            return q, scale.astype(np.float32)

        q1, s1 = quant(w1)
        q2, s2 = quant(w2)
        ref = IF.fused_moe(paddle.to_tensor(x), paddle.to_tensor(gw),
                           paddle.to_tensor(w1), paddle.to_tensor(w2),
                           moe_topk=2).numpy()
        got = IF.fused_moe(paddle.to_tensor(x), paddle.to_tensor(gw),
                           paddle.to_tensor(q1), paddle.to_tensor(q2),
                           ffn1_scale=paddle.to_tensor(s1),
                           ffn2_scale=paddle.to_tensor(s2),
                           quant_method="weight_only_int8",
                           moe_topk=2).numpy()
        assert np.abs(got - ref).max() < 0.05 * np.abs(ref).max() + 1e-3


class TestGroupedGemm:
    """ops/pallas/grouped_gemm.py under the interpreter (the CUDA-vs-NumPy
    OpTest pattern): ragged forward semantics + VJP exactness."""

    def test_ragged_forward_and_dead_tiles(self, pallas_interpret_unless_hw):
        import jax.numpy as jnp
        from paddle_tpu.ops.pallas.grouped_gemm import (grouped_matmul,
                                                        row_stride)

        rng = np.random.RandomState(0)
        E, K, N = 4, 16, 24
        sizes = np.array([5, 0, 8, 3], np.int32)
        R = row_stride(8)
        lhs = np.zeros((E * R, K), np.float32)
        for e in range(E):
            lhs[e * R:e * R + sizes[e]] = rng.randn(sizes[e], K)
        rhs = rng.randn(E, K, N).astype(np.float32)
        out = np.asarray(grouped_matmul(jnp.asarray(lhs), jnp.asarray(rhs),
                                        jnp.asarray(sizes)))
        ref = np.stack([lhs.reshape(E, R, K)[e] @ rhs[e]
                        for e in range(E)]).reshape(E * R, N)
        # live groups to f32 accumulation order (a few ulp: the MXU and the
        # numpy matmul sum in another order — observed on the v5e 1.9e-6);
        # the all-dead group's tiles are exactly ZERO (skipped tiles write
        # zeros, never garbage)
        np.testing.assert_allclose(out, ref, rtol=1e-6, atol=2e-6)
        assert not out[R:2 * R].any()

    def test_vjp_matches_masked_einsum(self, pallas_interpret_unless_hw):
        import jax
        import jax.numpy as jnp
        from paddle_tpu.ops.pallas.grouped_gemm import grouped_matmul

        rng = np.random.RandomState(1)
        E, K, N, R, bm = 4, 16, 24, 8, 8
        sizes = np.array([5, 0, 8, 3], np.int32)
        lhs = rng.randn(E * R, K).astype(np.float32)  # garbage in dead rows
        rhs = rng.randn(E, K, N).astype(np.float32)
        co = rng.randn(E * R, N).astype(np.float32)
        computed = np.minimum(-(-sizes // bm) * bm, R)
        mask = (np.arange(R)[None, :] < computed[:, None]).reshape(E * R)

        def f(l, r):
            return (grouped_matmul(l, r, jnp.asarray(sizes)) * co).sum()

        def fref(l, r):
            o = jnp.einsum("erk,ekn->ern", l.reshape(E, R, K),
                           r).reshape(E * R, N)
            o = jnp.where(jnp.asarray(mask)[:, None], o, 0.0)
            return (o * co).sum()

        g = jax.grad(f, (0, 1))(jnp.asarray(lhs), jnp.asarray(rhs))
        gr = jax.grad(fref, (0, 1))(jnp.asarray(lhs), jnp.asarray(rhs))
        # f32, a few ulp: the kernel and the einsum accumulate in another
        # order (observed under jaxlib 0.9: 1 ulp, max abs 1.9e-6)
        for got, want in zip(g, gr):
            np.testing.assert_allclose(np.asarray(got), np.asarray(want),
                                       rtol=1e-6, atol=2e-6)

    def test_autotune_consult_recorded(self, pallas_interpret_unless_hw):
        import jax.numpy as jnp
        from paddle_tpu.ops.pallas import autotune
        from paddle_tpu.ops.pallas.grouped_gemm import grouped_matmul

        rng = np.random.RandomState(2)
        grouped_matmul(jnp.asarray(rng.randn(16, 8).astype(np.float32)),
                       jnp.asarray(rng.randn(2, 8, 16).astype(np.float32)),
                       jnp.asarray(np.array([8, 4], np.int32)))
        rec = autotune.chosen_tiles().get("grouped_gemm")
        assert rec is not None and rec["source"] in (
            "default", "tuned", "measured", "fixed")


# group sizes over `rows` rows of 8-row tiles; what is left behind the last
# group is the tail of pairs no held expert takes
_RAGGED = {
    "an-empty-group": ([5, 0, 8, 3], 32),
    "every-row-to-one-group": ([0, 32, 0, 0], 32),
    "no-multiple-of-the-tile": ([13, 7, 9, 2], 40),
    "two-groups-inside-one-tile": ([3, 2, 1, 1], 16),
    "a-tail-of-unheld-pairs": ([9, 4, 6, 0], 64),
    "zero-live-rows": ([0, 0, 0, 0], 24),
    "rows-no-multiple-of-the-tile": ([6, 5, 4, 3], 30),
    "one-row-a-group": ([1] * 8, 8),
}


class TestRaggedGroups:
    """`ragged_matmul`: groups that lie end to end, each starting where the
    one before ended, against `jax.lax.ragged_dot` and a per-group matmul."""

    @pytest.mark.parametrize("case", sorted(_RAGGED))
    def test_forward_against_ragged_dot(self, pallas_interpret_unless_hw,
                                        case):
        import jax
        import jax.numpy as jnp
        from paddle_tpu.ops.pallas.grouped_gemm import ragged_matmul

        sizes, rows = _RAGGED[case]
        sizes = np.array(sizes, np.int32)
        rng = np.random.RandomState(3)
        K, N = 16, 24
        lhs = rng.randn(rows, K).astype(np.float32)   # garbage in the tail
        rhs = rng.randn(len(sizes), K, N).astype(np.float32)
        out = np.asarray(ragged_matmul(
            jnp.asarray(lhs), jnp.asarray(rhs), jnp.asarray(sizes), (8, 128)))
        want = np.zeros((rows, N), np.float32)
        at = 0
        for g, n in enumerate(sizes):
            want[at:at + n] = lhs[at:at + n] @ rhs[g]
            at += n
        np.testing.assert_allclose(out, want, rtol=1e-6, atol=4e-6)
        # rows of no group are exactly ZERO, never what the tile held before
        assert not out[at:].any()
        ref = np.asarray(jax.lax.ragged_dot(
            jnp.asarray(lhs), jnp.asarray(rhs), jnp.asarray(sizes)))
        np.testing.assert_allclose(out, ref, rtol=1e-6, atol=4e-6)

    @pytest.mark.parametrize("case", sorted(_RAGGED))
    def test_the_visits_by_hand(self, case):
        """A group is visited in every tile it has a row in, a tile no group
        touches once (it is written as zeros), and nothing past
        tiles + groups - 1 visits is ever needed."""
        import jax.numpy as jnp
        from paddle_tpu.ops.pallas.grouped_gemm import end_to_end_visits

        sizes, rows = _RAGGED[case]
        bm = 8
        tiles = -(-rows // bm)
        v = end_to_end_visits(jnp.asarray(np.array(sizes, np.int32)), rows,
                              bm)
        live, at = [], 0
        for g, n in enumerate(sizes):
            live += [(g, t) for t in range(at // bm, -(-(at + n) // bm))
                     if n]
            at += n
        n_live, n_all = (int(x) for x in v.n)
        assert n_live == len(live)
        assert list(zip(np.asarray(v.group)[:n_live].tolist(),
                        np.asarray(v.out_tile)[:n_live].tolist())) == live
        assert np.array_equal(np.asarray(v.lhs_tile)[:n_live],
                              np.asarray(v.out_tile)[:n_live])
        untouched = sorted(set(range(tiles)) - {t for _, t in live})
        assert np.asarray(v.out_tile)[n_live:n_all].tolist() == untouched
        assert n_all <= tiles + len(sizes) - 1 == v.group.shape[0]
        # what is not live names the blocks already in VMEM: nothing to fetch
        if live:
            assert set(np.asarray(v.group)[n_live:].tolist()) <= {live[-1][0]}
            assert set(np.asarray(v.lhs_tile)[n_live:].tolist()) <= {
                live[-1][1]}
        assert len(set(np.asarray(v.out_tile)[n_all - 1:].tolist())) == 1

    def test_bf16_rows_accumulate_in_f32(self, pallas_interpret_unless_hw):
        import jax
        import jax.numpy as jnp
        from paddle_tpu.ops.pallas.grouped_gemm import ragged_matmul

        rng = np.random.RandomState(4)
        sizes = jnp.asarray(np.array([40, 0, 25, 31], np.int32))
        lhs = jnp.asarray(rng.randn(128, 256), jnp.bfloat16)
        rhs = jnp.asarray(rng.randn(4, 256, 128), jnp.bfloat16)
        out = ragged_matmul(lhs, rhs, sizes, (32, 128))
        ref = jax.lax.ragged_dot(lhs, rhs, sizes,
                                 preferred_element_type=jnp.float32)
        assert out.dtype == jnp.bfloat16
        # the f32 sums differ in their order by an ulp of f32, which rounds
        # to another bf16 only at a tie: at most one bf16 ulp, and seldom
        got, want = np.asarray(out, np.float32), np.asarray(ref)
        np.testing.assert_allclose(got, want, rtol=2 ** -7, atol=2 ** -7)
        rounded = np.asarray(ref.astype(jnp.bfloat16), np.float32)
        assert (got != rounded).mean() < 0.01

    def test_vjp_is_ragged_dots(self, pallas_interpret_unless_hw):
        import jax
        import jax.numpy as jnp
        from paddle_tpu.ops.pallas.grouped_gemm import ragged_matmul

        rng = np.random.RandomState(5)
        sizes = jnp.asarray(np.array([5, 0, 11, 3], np.int32))
        lhs = jnp.asarray(rng.randn(30, 16).astype(np.float32))
        rhs = jnp.asarray(rng.randn(4, 16, 24).astype(np.float32))
        co = jnp.asarray(rng.randn(30, 24).astype(np.float32))
        got = jax.grad(lambda l, r: (ragged_matmul(l, r, sizes, (8, 128))
                                     * co).sum(), (0, 1))(lhs, rhs)
        want = jax.grad(lambda l, r: (jax.lax.ragged_dot(l, r, sizes)
                                      * co).sum(), (0, 1))(lhs, rhs)
        for g, w in zip(got, want):
            np.testing.assert_allclose(np.asarray(g), np.asarray(w),
                                       rtol=1e-6, atol=4e-6)

    def test_the_uniform_stride_is_the_same_body(self,
                                                 pallas_interpret_unless_hw):
        """`grouped_matmul`'s stride layout is the ragged kernel with group e
        at row e * R: what it gives for groups packed end to end is what
        `ragged_matmul` gives for the same rows."""
        import jax.numpy as jnp
        from paddle_tpu.ops.pallas.grouped_gemm import (grouped_matmul,
                                                        ragged_matmul)

        rng = np.random.RandomState(6)
        E, R, K, N = 3, 16, 16, 24
        sizes = np.array([16, 8, 16], np.int32)        # whole 8-row tiles
        rhs = rng.randn(E, K, N).astype(np.float32)
        packed = rng.randn(int(sizes.sum()), K).astype(np.float32)
        strided = np.zeros((E * R, K), np.float32)
        at = 0
        for e, n in enumerate(sizes):
            strided[e * R:e * R + n] = packed[at:at + n]
            at += n
        a = np.asarray(grouped_matmul(jnp.asarray(strided), jnp.asarray(rhs),
                                      jnp.asarray(sizes), block=(8, 128)))
        b = np.asarray(ragged_matmul(jnp.asarray(packed), jnp.asarray(rhs),
                                     jnp.asarray(sizes), (8, 128)))
        at = 0
        for e, n in enumerate(sizes):
            assert np.array_equal(a[e * R:e * R + n], b[at:at + n])
            at += n
        assert not a[R + 8:2 * R].any()                # the dead tile


def _moe_with_grads(gate_cfg, fast, x, seed=7, E=4, M=16, H=32,
                    train=False, capacity=None):
    """(out, {param grads}) for one fresh seeded layer; fast/dense toggled
    via the captured-at-trace env (fresh dispatch per call)."""
    os.environ["PADDLE_TPU_MOE_FAST"] = "1" if fast else "0"
    from paddle_tpu.framework.core import clear_dispatch_cache

    clear_dispatch_cache()
    paddle.seed(seed)
    cfg = dict(gate_cfg)
    if capacity is not None:
        cfg["capacity"] = capacity
    layer = MoELayer(M, ExpertFFN(E, M, H), gate=cfg)
    layer.train() if train else layer.eval()
    xt = paddle.to_tensor(x)
    out = layer(xt)
    (out.sum() + layer.l_aux).backward()
    grads = {
        "w1": np.asarray(layer.experts.w1.grad._value),
        "w2": np.asarray(layer.experts.w2.grad._value),
        "gate_w": np.asarray(layer.gate.gate.weight.grad._value),
    }
    return out.numpy(), grads, float(np.asarray(layer.l_aux._value))


class TestFastPathParity:
    """Sorted-dispatch fast path vs the dense einsum oracle
    (PADDLE_TPU_MOE_FAST flipped either way): values + grads + l_aux.
    rtol=0; the tiny atol absorbs the one-FMA difference between XLA's
    fused einsum contraction and the explicit weighted sum (the products
    and routing are bit-identical — pinpointed in ISSUE-14 review)."""

    ATOL = 2e-6

    @pytest.fixture(autouse=True)
    def _restore_toggle(self):
        prev = os.environ.get("PADDLE_TPU_MOE_FAST")
        yield
        if prev is None:
            os.environ.pop("PADDLE_TPU_MOE_FAST", None)
        else:
            os.environ["PADDLE_TPU_MOE_FAST"] = prev

    @pytest.mark.parametrize("gate_cfg", [
        {"type": "naive", "top_k": 2},
        {"type": "gshard", "top_k": 2},
        {"type": "switch", "top_k": 1},
    ], ids=["naive_top2", "gshard_top2", "switch_top1"])
    def test_values_grads_laux_match_dense(self, gate_cfg):
        x = np.random.RandomState(0).randn(24, 16).astype(np.float32)
        out_d, g_d, l_d = _moe_with_grads(gate_cfg, fast=False, x=x)
        out_f, g_f, l_f = _moe_with_grads(gate_cfg, fast=True, x=x)
        np.testing.assert_allclose(out_f, out_d, rtol=0, atol=self.ATOL)
        np.testing.assert_allclose(l_f, l_d, rtol=1e-6, atol=self.ATOL)
        for k in g_d:
            np.testing.assert_allclose(g_f[k], g_d[k], rtol=0,
                                       atol=self.ATOL)

    def test_capacity_drop_parity(self):
        """Forced overflow (cap < routed tokens): the fast path's positional
        drop mask keeps exactly the rows the dense one-hot pruning keeps."""
        x = np.random.RandomState(1).randn(16, 16).astype(np.float32)
        cfg = {"type": "switch", "top_k": 1}
        out_d, g_d, _ = _moe_with_grads(cfg, fast=False, x=x,
                                        capacity=(0.5, 0.5))
        out_f, g_f, _ = _moe_with_grads(cfg, fast=True, x=x,
                                        capacity=(0.5, 0.5))
        np.testing.assert_allclose(out_f, out_d, rtol=0, atol=self.ATOL)
        nz = (np.abs(out_f) > 1e-7).any(-1).sum()
        assert 0 < nz < 16  # drops actually happened
        for k in g_d:
            np.testing.assert_allclose(g_f[k], g_d[k], rtol=0,
                                       atol=self.ATOL)

    def test_bf16_parity(self):
        import jax.numpy as jnp

        x32 = np.random.RandomState(2).randn(16, 16).astype(np.float32)
        for fast in (False, True):
            os.environ["PADDLE_TPU_MOE_FAST"] = "1" if fast else "0"
            from paddle_tpu.framework.core import clear_dispatch_cache

            clear_dispatch_cache()
            paddle.seed(3)
            layer = MoELayer(16, ExpertFFN(4, 16, 32),
                             gate={"type": "naive", "top_k": 2})
            layer.eval()
            x = paddle.to_tensor(x32).astype("bfloat16")
            out = layer(x)
            res = np.asarray(out.astype("float32").numpy())
            if fast:
                np.testing.assert_allclose(res, ref, rtol=0, atol=0.1)
            else:
                ref = res
        os.environ.pop("PADDLE_TPU_MOE_FAST", None)

    def test_kernel_path_parity(self, pallas_interpret_unless_hw):
        """One parity case with the Pallas grouped GEMM actually live
        (interpret mode) instead of the CPU einsum fallback."""
        from paddle_tpu.ops.pallas import kernels_available

        assert kernels_available()
        x = np.random.RandomState(3).randn(24, 16).astype(np.float32)
        cfg = {"type": "gshard", "top_k": 2}
        out_d, g_d, _ = _moe_with_grads(cfg, fast=False, x=x)
        out_f, g_f, _ = _moe_with_grads(cfg, fast=True, x=x)
        np.testing.assert_allclose(out_f, out_d, rtol=0, atol=self.ATOL)
        for k in g_d:
            np.testing.assert_allclose(g_f[k], g_d[k], rtol=0,
                                       atol=self.ATOL)


class TestGateAuxLoss:
    """ISSUE-14 satellite pin: the load-balance aux loss comes from
    PRE-capacity-drop router stats — post-drop stats are biased toward
    already-overflowed experts (the overflow is what the drop removed)."""

    @pytest.mark.parametrize("gtype,topk", [("switch", 1), ("gshard", 2)])
    def test_l_aux_invariant_to_capacity(self, gtype, topk):
        x = np.random.RandomState(4).randn(32, 8).astype(np.float32)
        vals = []
        for cap in ((0.25, 0.25), (10.0, 10.0)):
            paddle.seed(5)
            layer = MoELayer(8, ExpertFFN(4, 8, 16),
                             gate={"type": gtype, "top_k": topk,
                                   "capacity": cap})
            layer.eval()
            layer(paddle.to_tensor(x))
            vals.append(float(np.asarray(layer.l_aux._value)))
        assert vals[0] == vals[1]


class TestExpertParallelFast:
    """ep-sharded fast path on the 8-device CPU mesh: parity with the dense
    oracle through a jitted DistributedTrainStep, a2a chunk overlap
    schedule on, and the a2a accounting visible to the observability
    registry + comm_task observers."""

    def _losses(self, fast, chunks, steps=2):
        os.environ["PADDLE_TPU_MOE_FAST"] = "1" if fast else "0"
        os.environ["PADDLE_TPU_MOE_A2A_CHUNKS"] = str(chunks)
        from paddle_tpu.framework.core import clear_dispatch_cache

        clear_dispatch_cache()
        paddle.seed(0)
        mesh = dist.build_mesh(ep=4, mp=2)

        class Net(nn.Layer):
            def __init__(self):
                super().__init__()
                self.moe = MoELayer(8, ExpertFFN(4, 8, 16, ep_axis="ep"),
                                    gate={"type": "naive", "top_k": 2},
                                    ep_axis="ep")

            def forward(self, x):
                return self.moe(x)

        net = Net()
        opt = paddle.optimizer.AdamW(learning_rate=0.01,
                                     parameters=net.parameters())
        step = dist.DistributedTrainStep(net, F.mse_loss, opt, mesh=mesh,
                                         batch_axes=("dp", "ep"))
        rng = np.random.RandomState(0)
        X = paddle.to_tensor(rng.rand(16, 8).astype(np.float32))
        y = paddle.to_tensor(rng.rand(16, 8).astype(np.float32))
        losses = [float(step(X, y).numpy()) for _ in range(steps)]
        sh = step.params["moe.experts.w1"].sharding
        return losses, str(sh.spec)

    @pytest.fixture(autouse=True)
    def _restore(self):
        prev = {k: os.environ.get(k) for k in
                ("PADDLE_TPU_MOE_FAST", "PADDLE_TPU_MOE_A2A_CHUNKS")}
        yield
        for k, v in prev.items():
            if v is None:
                os.environ.pop(k, None)
            else:
                os.environ[k] = v
        dist.env.set_global_mesh(None)

    def test_ep_fast_matches_dense_with_overlap_on(self):
        from paddle_tpu.distributed import comm_watchdog
        from paddle_tpu.observability.metrics import default_registry

        dense, _ = self._losses(fast=False, chunks=2)
        seen = []
        obs = comm_watchdog.add_task_observer(
            lambda desc, t0, t1, kind: seen.append((desc, kind)))
        try:
            reg = default_registry()
            base = reg.snapshot()
            fast, spec = self._losses(fast=True, chunks=2)
            delta = reg.delta(base)
        finally:
            comm_watchdog.remove_task_observer(obs)
        for a, b in zip(dense, fast):
            np.testing.assert_allclose(a, b, rtol=0, atol=1e-6)
        assert "ep" in spec  # expert weights actually sharded on ep
        # a2a accounting: exact counters per executed step; the [est]
        # intervals are bytes over a chip's ICI bandwidth, and the CPU
        # backend has none to estimate with
        assert delta.get("collective_bytes_total{op=all_to_all}", 0) > 0
        assert delta.get("collective_calls_total{op=all_to_all}", 0) >= 2
        assert any(kind == "a2a" for _d, kind in seen) == (
            jax.default_backend() == "tpu")

    def test_emit_step_anchoring_follows_schedule(self):
        """Chunked records land behind now (covered by the open compute
        span); unchunked ones land ahead of it (counted exposed) — the
        instrument-side half of the PADDLE_TPU_MOE_A2A_CHUNKS A/B."""
        import time

        from paddle_tpu.distributed import comm_watchdog, moe_comm

        seen = []
        obs = comm_watchdog.add_task_observer(
            lambda d, t0, t1, k: seen.append((d, t0, t1, k)))
        try:
            now = time.perf_counter_ns()
            moe_comm.emit_step(
                ({"desc": "a", "bytes": 10 ** 9, "calls": 2,
                  "overlapped": True},
                 {"desc": "b", "bytes": 10 ** 9, "calls": 2,
                  "overlapped": False}), floor_ns=now, chip="TPU v4")
        finally:
            comm_watchdog.remove_task_observer(obs)
        (da, a0, a1, ka), (db, b0, b1, kb) = seen
        assert ka == kb == "a2a" and "[est]" in da
        assert a0 >= now and a1 <= time.perf_counter_ns()  # floored, behind
        assert b0 >= now and b1 > b0 and b1 > a1           # ahead: exposed

    @pytest.mark.slow
    def test_ep_fast_chunks_off_parity(self):
        """chunks=1 (overlap schedule off) must be numerically identical
        to chunks=2 — chunking only re-tiles, never re-routes."""
        one, _ = self._losses(fast=True, chunks=1)
        two, _ = self._losses(fast=True, chunks=2)
        np.testing.assert_allclose(one, two, rtol=0, atol=1e-6)


class TestGlobalScatterGather:
    def test_round_trip(self):
        import paddle_tpu.distributed as dist
        from paddle_tpu.distributed.utils import global_gather, global_scatter

        # the [src*dst*k, ...] stacked view needs the group size explicit —
        # alltoall_single now rejects shapes it cannot interpret instead of
        # silently returning the input
        grp = dist.new_group(list(range(4)))
        x = paddle.to_tensor(np.arange(16, dtype=np.float32).reshape(16, 1))
        cnt = paddle.to_tensor(np.full((4,), 4, np.int64))
        s = global_scatter(x, cnt, cnt, group=grp)
        assert not np.allclose(s.numpy(), x.numpy())  # exchange happened
        g = global_gather(s, cnt, cnt, group=grp)
        np.testing.assert_allclose(g.numpy(), x.numpy())
