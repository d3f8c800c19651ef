"""The held-expert layer's compacting pass (`moe/held_moe.py`): a layer that
holds 1/16 of the experts or less gathers, multiplies and combines only the
first `_capacity` sorted places, and takes the whole buffer where its held
pairs overflow them. Held against the same layer built with no capacity
(today's whole-buffer pass) on the CPU, through the grouped GEMM in
interpret mode. The families' tiny configurations hold a quarter of their
experts and never compact, so the layers here hold 2 of 64 at top-4."""

import jax
import jax.numpy as jnp
import numpy as np
import pytest

import paddle_tpu as paddle
from paddle_tpu.incubate.distributed.models.moe import HeldExpertsMoE
from paddle_tpu.incubate.distributed.models.moe import held_moe
from paddle_tpu.inference.paged import PagedServingEngine
from paddle_tpu.models.afmoe import AfmoeForCausalLM, afmoe_tiny
from paddle_tpu.observability.metrics import default_registry

D, EXPERTS, TOP_K = 32, 64, 4


@pytest.fixture(autouse=True)
def _interpret_mode(pallas_interpret_unless_hw):
    pass


def _layer(gate, seed=3, bias=None):
    paddle.seed(seed)
    layer = HeldExpertsMoE(D, 16, EXPERTS, TOP_K, held=(10, 2), gate=gate,
                           route_scale=2.826)
    # a router wide enough that the tokens, not the bias, decide the picks
    layer.router._value = jnp.asarray(
        np.random.default_rng(seed).normal(0, 0.5, (D, EXPERTS)), jnp.float32)
    if gate == "sigmoid":
        layer.expert_bias._value = jnp.asarray(
            np.random.default_rng(seed).normal(0, 0.05, EXPERTS)
            if bias is None else bias, jnp.float32)
    return layer


def _call(layer, x, live, build=None, monkeypatch=None):
    """(out, stats as a dict) of a fresh build of `layer`. `build="whole"`
    builds it with no capacity: today's pass over every pair; `"inline"`
    with each `lax.cond` replaced by its first branch, so that the
    compacting pass is compiled as the same form of program as the whole
    buffer's (the CPU compiler contracts a multiply and an add into one
    rounding inside a conditional's branch where it does not outside: the
    last bit of an f32 sum, not its order)."""
    if build == "whole":
        monkeypatch.setattr(held_moe, "_capacity", lambda *a: None)
    elif build == "inline":
        monkeypatch.setattr(jax.lax, "cond", lambda p, yes, no: yes())
    layer._fns.clear()
    try:
        out, stats = layer(
            paddle.to_tensor(x),
            live=None if live is None else paddle.to_tensor(live),
            with_stats=True)
    finally:
        if build:
            monkeypatch.undo()
    return (np.asarray(out._value),
            dict(zip(held_moe.STAT_NAMES, np.asarray(stats._value).tolist())))


def _close(got, want):
    """Within the f32 rounding of the whole buffer's sums."""
    return np.abs(got - want).max() <= 1e-6 * np.abs(want).max()


def _x(tokens, seed=8):
    return np.random.default_rng(seed).normal(size=(tokens, D)).astype(
        np.float32)


@pytest.mark.parametrize("live", [None, "sparse"])
@pytest.mark.parametrize("gate", ["softmax", "sigmoid"])
def test_a_compacting_pass_gives_the_whole_buffers_bits(monkeypatch, gate,
                                                        live):
    """One pass of 512 tokens (2,048 pairs, capacity 256): the held pairs
    alone through both GEMMs and the combine, each token's gated terms
    summed in pick order as the whole buffer sums them: the same bits as
    one program, within f32 rounding under the capacity's cond."""
    tokens = 512
    layer = _layer(gate)
    mask = np.arange(tokens) % 7 != 0 if live else None
    assert held_moe._capacity(tokens * TOP_K, 2, EXPERTS) == 256
    got, stats = _call(layer, _x(tokens), mask)
    want, whole = _call(layer, _x(tokens), mask, "whole", monkeypatch)
    inlined, _ = _call(layer, _x(tokens), mask, "inline", monkeypatch)
    assert np.array_equal(inlined, want)
    assert _close(got, want)
    assert 0 < stats["routed_pairs_held"] <= 256
    assert (stats["passes"], stats["whole_passes"]) == (1, 0)
    assert (whole["passes"], whole["whole_passes"]) == (1, 1)
    for name in ("routed_pairs_held", "expert_rows_max", "expert_rows_sum",
                 "dropped_pairs"):
        assert stats[name] == whole[name]
    assert stats["dropped_pairs"] == 0
    if mask is not None:
        assert not got[~mask].any()


@pytest.mark.parametrize("gate", ["softmax", "sigmoid"])
def test_a_chunked_call_compacts_each_pass_and_skips_the_dead_tail(
        monkeypatch, gate):
    """A call of 700 tokens over passes of 256 (`CHUNK_TOKENS` lowered): the
    live rows end inside the second pass, so the third is skipped whole;
    the two it makes compact and give the whole buffer's bits, and the
    call is what one pass over all 700 gives."""
    tokens = 700
    layer = _layer(gate)
    x, mask = _x(tokens), np.arange(tokens) < 400
    one, _ = _call(layer, x, mask)
    monkeypatch.setattr(held_moe, "CHUNK_TOKENS", 256)
    assert held_moe.chunks_for(tokens) == 3
    got, stats = _call(layer, x, mask)
    with monkeypatch.context() as m:
        want, whole = _call(layer, x, mask, "whole", m)
    with monkeypatch.context() as m:
        inlined, _ = _call(layer, x, mask, "inline", m)
    assert np.array_equal(inlined, want)
    assert _close(got, want)
    assert np.abs(got - one).max() < 1e-6
    assert not got[400:].any()
    assert (stats["passes"], stats["whole_passes"]) == (2, 0)
    assert (whole["passes"], whole["whole_passes"]) == (2, 2)
    assert stats["routed_pairs_held"] == whole["routed_pairs_held"] > 0
    assert stats["dropped_pairs"] == 0


def test_held_pairs_past_the_capacity_take_the_whole_buffer(monkeypatch):
    """The router's bias sends two of every token's four picks to the two
    held experts: 1,024 held pairs against a capacity of 256. The pass
    takes the whole buffer, drops nothing, and is today's pass."""
    tokens = 512
    bias = np.zeros(EXPERTS, np.float32)
    bias[10:12] = 10.0
    layer = _layer("sigmoid", bias=bias)
    got, stats = _call(layer, _x(tokens), None)
    want, _ = _call(layer, _x(tokens), None, "whole", monkeypatch)
    assert _close(got, want)
    assert stats["routed_pairs_held"] == 2 * tokens
    assert (stats["passes"], stats["whole_passes"]) == (1, 1)
    assert stats["dropped_pairs"] == 0


def test_a_token_with_more_held_picks_than_slots_takes_the_whole_buffer(
        monkeypatch):
    """8 of 256 experts held at top-8 (capacity 512 of 4,096 pairs): one
    token's eight picks are all held, more than a compacting pass has
    slots for, while the pass's held pairs fit its capacity."""
    tokens, d = 512, 32
    rng = np.random.default_rng(4)
    paddle.seed(4)
    layer = HeldExpertsMoE(d, 16, 256, 8, held=(10, 8), gate="sigmoid")
    router = rng.normal(0, 0.5, (d, 256))
    router[0] = 0.0
    router[0, 10:18] = 1.0
    layer.router._value = jnp.asarray(router, jnp.float32)
    x = rng.normal(size=(tokens, d)).astype(np.float32)
    x[:, 0] = 0.0
    x[3, 0] = 50.0            # token 3 picks the eight held experts
    assert held_moe._capacity(tokens * 8, 8, 256) == 512
    got, stats = _call(layer, x, None)
    want, _ = _call(layer, x, None, "whole", monkeypatch)
    assert _close(got, want)
    assert 8 <= stats["routed_pairs_held"] <= 512
    assert (stats["passes"], stats["whole_passes"]) == (1, 1)
    # the same call with token 3 like the others compacts
    x[3, 0] = 0.0
    _, stats = _call(layer, x, None)
    assert (stats["passes"], stats["whole_passes"]) == (1, 0)


@pytest.mark.parametrize("pairs,held,experts,cap", [
    (128 * 8, 12, 384, 128),        # Kimi's decode tick
    (176 * 8, 8, 256, 256),         # MiMo's decode tick
    (4096 * 8, 12, 384, 4096),      # a 4096-token pass of Kimi
    (4096 * 8, 8, 256, 4096),       # and of MiMo
    (224 * 8, 64, 128, None),       # Trinity: half the experts held
    (4096 * 8, 64, 128, None),
    (128 * 10, 36, 72, None),       # Granite: half, at top-10
    (4096 * 10, 36, 72, None)])
def test_the_capacity_rule(pairs, held, experts, cap):
    assert held_moe._capacity(pairs, held, experts) == cap


def test_the_passes_counter_follows_the_stats_rows():
    """`moe_passes_total{path}`: a hand row, then a tiny model served (a
    quarter of its experts held: every pass takes the whole buffer), each
    read tick adding one pass an expert layer."""
    paddle.seed(7)
    model = AfmoeForCausalLM(afmoe_tiny(held_experts=(0, 2)))
    model.eval()
    eng = PagedServingEngine(model, max_batch_size=4, max_seq_len=96,
                             page_size=8)
    reg = default_registry()
    passes, ticks = reg.get("moe_passes_total"), reg.get(
        "serving_decode_ticks_total")

    def counts():
        return (passes.value(path="compact"), passes.value(path="whole"),
                ticks.value(path="overlapped") + ticks.value(path="drained"))

    before = counts()
    eng._note_routing(np.array([9, 3, 9, 0, 128, 12, 1], np.int32))
    assert counts() == (before[0] + 11, before[1] + 1, before[2])
    layers = sum(isinstance(s, HeldExpertsMoE) for s in model.sublayers())
    assert layers >= 1
    eng.add_request(np.arange(1, 21, dtype=np.int32), max_new_tokens=5)
    eng.add_request(np.arange(3, 14, dtype=np.int32), max_new_tokens=3)
    mid = counts()
    eng.run()
    after = counts()
    assert after[2] > mid[2]
    assert after[0] == mid[0]
    assert after[1] - mid[1] == layers * (after[2] - mid[2])
    assert reg.get("moe_dropped_pairs").value() == 0
