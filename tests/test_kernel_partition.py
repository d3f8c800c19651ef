"""Pallas kernels under a multi-device mesh (ops/pallas/partition.py).

GSPMD cannot partition a Mosaic kernel — on a TPU jax refuses to lower one
inside a multi-device jit unless every mesh axis is manual — so each kernel
entry wraps itself in a shard_map over the axes that are not manual yet.
The refusal itself only exists on the chip (chip_smoke.py's four-chip phase
proves it there); these tests pin, on the CPU mesh under the interpreter,
what the wrapper must guarantee: the kernel body is traced with every axis
manual, on its local block, and values and gradients equal the unwrapped
single-device call.
"""

import jax
import jax.numpy as jnp
import numpy as np
import pytest
from jax.sharding import NamedSharding, PartitionSpec as P

import paddle_tpu.distributed as dist
from paddle_tpu.ops.pallas import flash_attention as fa
from paddle_tpu.ops.pallas import fused_norm as fnorm
from paddle_tpu.ops.pallas.partition import shard_plan


@pytest.fixture(autouse=True)
def _interpret_and_clear_mesh(pallas_interpret_unless_hw):
    dist.env.set_global_mesh(None)
    yield
    dist.env.set_global_mesh(None)


def _loss(q, x, w):
    o = fa.flash_attention_fwd(q, q, q, causal=True)
    y = fnorm.layer_norm_fwd(x, w, None)
    return (o * o).sum() + (y * jnp.arange(y.shape[-1])).sum()


def _needs(n):
    return pytest.mark.skipif(len(jax.devices()) < n,
                              reason=f"needs {n} devices")


@_needs(4)
def test_kernels_run_per_shard_with_every_axis_manual(monkeypatch):
    rng = np.random.default_rng(0)
    q = jnp.asarray(rng.standard_normal((4, 16, 4, 16)), jnp.float32)
    x = jnp.asarray(rng.standard_normal((4, 16, 32)), jnp.float32)
    w = jnp.asarray(rng.standard_normal((32,)), jnp.float32)
    want, want_g = jax.value_and_grad(_loss, (0, 1, 2))(q, x, w)

    seen = []
    real_fwd, real_norm = fa._fwd, fnorm._norm_fwd

    def spy_fwd(qq, *a, **kw):
        ctx = jax.sharding.get_abstract_mesh()
        seen.append(("flash", set(ctx.manual_axes), qq.shape[:2]))
        return real_fwd(qq, *a, **kw)

    def spy_norm(x2, *a, **kw):
        ctx = jax.sharding.get_abstract_mesh()
        seen.append(("norm", set(ctx.manual_axes), x2.shape))
        return real_norm(x2, *a, **kw)

    monkeypatch.setattr(fa, "_fwd", spy_fwd)
    monkeypatch.setattr(fnorm, "_norm_fwd", spy_norm)
    mesh = dist.build_mesh(dp=2, mp=2)
    put = lambda a, s: jax.device_put(a, NamedSharding(mesh, s))
    got, got_g = jax.jit(jax.value_and_grad(_loss, (0, 1, 2)))(
        put(q, P("dp", None, "mp", None)), put(x, P("dp")), put(w, P()))

    names = set(mesh.axis_names)
    assert seen and all(manual == names for _k, manual, _s in seen), seen
    # local blocks: batch 4 over dp=2; heads 4 over mp=2 ([B, H, ...] inside)
    assert ("flash", names, (2, 2)) in seen
    assert ("norm", names, (2 * 16, 32)) in seen
    np.testing.assert_allclose(got, want, rtol=1e-5)
    for g, wg in zip(got_g, want_g):
        np.testing.assert_allclose(np.asarray(g), np.asarray(wg),
                                   rtol=1e-4, atol=1e-5)


@_needs(2)
def test_plan_is_none_when_nothing_needs_partitioning():
    a = jnp.ones((4, 4))
    assert shard_plan(a) is None                      # no mesh
    dist.build_mesh(devices=jax.devices()[:1])
    assert jax.jit(lambda v: shard_plan(v) is None)(a)  # one-device mesh
    mesh = dist.build_mesh(dp=2)
    assert shard_plan(a) is None        # eager, concrete, on one device
    plan = jax.jit(lambda v: shard_plan(v) is not None)(a)
    assert plan                          # traced under a multi-device mesh

    def body(v):  # every axis manual already: call the kernel directly
        return v + (0.0 if shard_plan(v) is None else 1.0)

    out = jax.shard_map(body, mesh=mesh, in_specs=P("dp"), out_specs=P("dp"),
                        check_vma=False)(a)
    np.testing.assert_array_equal(np.asarray(out), np.ones((4, 4)))


@_needs(8)
def test_axes_split_only_what_they_divide():
    from paddle_tpu.ops.pallas.partition import _Plan

    mesh = dist.build_mesh(dp=2, sharding=2, mp=2)

    p = _Plan(mesh, tuple(mesh.axis_names))
    assert p.axes("batch", 8) == ("dp", "sharding")
    assert p.axes("batch", 6) == "dp"          # 6 % 4 != 0: sharding left out
    assert p.axes("heads", 3) is None
    assert p.axes("heads", 4) == "mp"
    p = _Plan(mesh, ("mp",))                   # dp/sharding manual already
    assert p.axes("batch", 8) is None and p.axes("heads", 4) == "mp"
