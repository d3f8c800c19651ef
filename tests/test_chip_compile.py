"""The decode kernels compiled for the chip at the serving cell's widths,
with no chip: the TPU's compiler is installed and compiles for a v5e that
is described, not attached. It refuses what the interpreter lets through (a
broadcast Mosaic does not implement, a block it cannot tile, a kernel over
its VMEM limit), at about two seconds a kernel and no chip time. Nothing
runs, so nothing here is a result or a time.

The topology is described inside a fixture, never at import: one process at
a time may load the TPU's library, and every xdist worker imports this file.
Keep such tests in this one file."""

import json
import os
import re

import pytest

import jax
import jax.numpy as jnp
from jax.sharding import SingleDeviceSharding

from paddle_tpu.ops.pallas import grouped_gemm, ssm_decode
from paddle_tpu.ops.pallas.decode_attention import (
    dense_decode_attention,
    paged_decode_attention,
    paged_kv_write,
)

# serve-xl-sat: 64 rows, 16 heads of 128, pages of 32, table 64 wide
B, H, D, PS, P, N_PAGES = 64, 16, 128, 32, 64, 1746


@pytest.fixture(scope="module")
def one_chip():
    from jax.experimental import topologies
    try:
        topo = topologies.get_topology_desc(platform="tpu",
                                            topology_name="v5e:2x2")
    except Exception as e:
        pytest.skip(f"no v5e:2x2 topology can be described here: {e}")
    return SingleDeviceSharding(topo.devices[0])


@pytest.fixture(autouse=True)
def _compiled_not_interpreted(monkeypatch):
    monkeypatch.delenv("PADDLE_TPU_PALLAS_INTERPRET", raising=False)


def _args(one_chip, *shapes):
    return [jax.ShapeDtypeStruct(s, d, sharding=one_chip) for s, d in shapes]


def _compile(fn, one_chip, *shapes):
    return jax.jit(fn).lower(*_args(one_chip, *shapes)).compile().as_text()


@pytest.mark.parametrize("q_dtype,pool_dtype,hkv", [
    (jnp.bfloat16, jnp.bfloat16, 16),   # the cell: bf16 operands, g = 1
    (jnp.float32, jnp.bfloat16, 4),     # f32 operands, GQA g = 4
    (jnp.bfloat16, jnp.int8, 16),       # the int8 pool, same grid
], ids=["bf16", "f32q-gqa4", "int8"])
def test_paged_decode_compiles_at_the_cells_shapes(one_chip, q_dtype,
                                                   pool_dtype, hkv):
    pool = ((N_PAGES, hkv, PS, D), pool_dtype)
    shapes = [((B, H, D), q_dtype), pool, pool, ((B, P), jnp.int32),
              ((B,), jnp.int32)]
    if pool_dtype == jnp.int8:
        shapes += [((N_PAGES, hkv), jnp.float32)] * 2

    def fn(q, kc, vc, tables, lengths, *scales):
        return paged_decode_attention(q, kc, vc, tables, lengths,
                                      kv_scales=scales or None)

    hlo = _compile(fn, one_chip, *shapes)
    # what benchmark/readers/decode_attn_roofline.py holds on to: a Mosaic
    # call under the kernel's name that takes the pool as it is
    name = "decode_paged_q8" if pool_dtype == jnp.int8 else "decode_paged"
    (call,) = [line for line in hlo.splitlines()
               if re.match(r"\s*%" + name + r"[.\w]* = ", line)]
    assert 'custom_call_target="tpu_custom_call"' in call
    assert "[" + ",".join(str(d) for d in pool[0]) + "]" in call


def test_dense_decode_compiles(one_chip):
    cache = ((8, 4, 2048, D), jnp.bfloat16)
    hlo = _compile(dense_decode_attention, one_chip,
                   ((8, H, D), jnp.bfloat16), cache, cache, ((8,), jnp.int32))
    assert re.search(r"%decode_dense[.\w]* = .*tpu_custom_call", hlo)


# serve-granite-h-sat: 128 rows; state [128, 8192] a row and Mamba layer;
# 36 held experts of 4096 x (2 x 768) and 768 x 4096; 4 query heads a KV head


def test_ssm_decode_compiles_in_place_at_the_cells_shapes(one_chip,
                                                          monkeypatch):
    # on the CPU backend the entry point takes its jax.numpy form; here the
    # kernel itself is what is compiled
    monkeypatch.setattr(ssm_decode, "kernels_available", lambda: True)
    rows, n, lanes = 128, 128, 8192
    args = _args(
        one_chip, ((rows, n, lanes), jnp.bfloat16), ((rows, lanes), jnp.float32),
        ((rows, lanes), jnp.float32), ((rows, n), jnp.float32),
        ((rows, n), jnp.float32), ((rows,), jnp.bool_))
    compiled = jax.jit(ssm_decode.ssm_decode, donate_argnums=(0,)).lower(
        *args).compile()
    assert re.search(r"%ssm_decode[.\w]* = .*tpu_custom_call",
                     compiled.as_text())
    # the state goes in and comes out as one buffer: no second copy of it
    memory = compiled.memory_analysis()
    state_bytes = rows * n * lanes * 2
    assert memory.alias_size_in_bytes >= state_bytes
    assert memory.temp_size_in_bytes < state_bytes // 8


def _held_experts_call(one_chip, held, experts, tokens, top_k, k, n):
    """The held layer's `grouped_gemm` call over `tokens x top_k` routed rows
    laid end to end, at the tiles the layer takes from those shapes, compiled
    for the chip: the HLO line of the kernel's call."""
    from paddle_tpu.incubate.distributed.models.moe import held_moe

    pairs = tokens * top_k
    bm = held_moe._row_tile(pairs, experts)
    block = (bm, held_moe._column_tile(n, k, bm))
    hlo = _compile(
        lambda rows, w, sizes: grouped_gemm.ragged_matmul(rows, w, sizes,
                                                          block),
        one_chip, ((pairs, k), jnp.bfloat16), ((held, k, n), jnp.bfloat16),
        ((held,), jnp.int32))
    (call,) = [line for line in hlo.splitlines()
               if re.match(r"\s*(ROOT )?%grouped_gemm[.\w]* = ", line)]
    assert 'custom_call_target="tpu_custom_call"' in call
    return call


@pytest.mark.parametrize("tokens,k,n", [
    (128, 4096, 1536), (128, 768, 4096), (1024, 4096, 1536),
    (1024, 768, 4096)],
    ids=["decode-in", "decode-out", "prefill-in", "prefill-out"])
def test_grouped_gemm_compiles_at_the_held_experts_shapes(one_chip, tokens,
                                                          k, n):
    """serve-granite-h-sat: 36 of 72 experts held, 10 picks a token; a decode
    tick's 128 rows and a prefill's largest bucket, each one pass."""
    call = _held_experts_call(one_chip, 36, 72, tokens, 10, k, n)
    # what benchmark/readers/kernel_roofline_hybrid.py holds on to
    assert f"bf16[36,{k},{n}]" in call


# serve-trinity-mixed-sat: 160 rows, 32 query heads on 4 KV heads of 128,
# pages of 32, a pool of about 41k pages in two arrays; the full layers'
# table 512 wide, the window layers' 65 (window 2048); prefill buckets to 16k;
# 64 held experts of 2048 x (2 x 1024) and 1024 x 2048

T_POOL = ((41000, 4, PS, D), jnp.bfloat16)


@pytest.mark.parametrize("width,window,name", [
    (512, None, "decode_paged"), (65, 2048, "decode_window")])
def test_trinity_decode_kernels_compile_at_the_cells_shapes(one_chip, width,
                                                            window, name):
    from paddle_tpu.ops.pallas.decode_attention import pages_per_step

    hlo = _compile(
        lambda q, kc, vc, tables, lengths: paged_decode_attention(
            q, kc, vc, tables, lengths, window=window),
        one_chip, ((160, 32, D), jnp.bfloat16), T_POOL, T_POOL,
        ((160, width), jnp.int32), ((160,), jnp.int32))
    # what benchmark/readers/kernel_roofline_afmoe.py holds on to
    assert re.search(r"%" + name + r"[.\w]* = .*tpu_custom_call", hlo)
    # the window's table is 65 wide whatever max_seq_len is: 5 grid steps a
    # row at 16 pages a step, where the full-width table takes 32
    assert pages_per_step(4, PS, D, width, 2) == 16


@pytest.mark.parametrize("seq", [512, 16384])
def test_window_prefill_compiles_at_the_cells_buckets(one_chip, seq):
    from paddle_tpu.ops.pallas.flash_attention import flash_window_fwd

    kv = ((1, seq, 4, D), jnp.bfloat16)
    hlo = _compile(lambda q, k, v: flash_window_fwd(q, k, v, 2048), one_chip,
                   ((1, seq, 32, D), jnp.bfloat16), kv, kv)
    assert re.search(r"%flash_fwd_window[.\w]* = .*tpu_custom_call", hlo)


@pytest.mark.parametrize("tokens,k,n", [
    (224, 2048, 2048), (224, 1024, 2048), (1024, 2048, 2048),
    (1024, 1024, 2048), (4096, 2048, 2048), (4096, 1024, 2048)],
    ids=["decode-in", "decode-out", "1024-in", "1024-out", "pass-in",
         "pass-out"])
def test_grouped_gemm_compiles_at_trinitys_held_experts(one_chip, tokens, k,
                                                        n):
    """64 of 128 experts held, 8 picks a token: a decode tick's 224 rows, a
    1024-token bucket and a whole pass of 4096 tokens (`CHUNK_TOKENS`; the
    8192 and 16384 buckets are scans of it)."""
    from paddle_tpu.incubate.distributed.models.moe import held_moe

    assert held_moe.CHUNK_TOKENS == 4096
    call = _held_experts_call(one_chip, 64, 128, tokens, 8, k, n)
    assert f"bf16[64,{k},{n}]" in call


def test_paged_decode_compiles_for_four_query_heads_a_kv_head(one_chip):
    pool = ((9700, 8, PS, D), jnp.bfloat16)
    hlo = _compile(
        lambda q, kc, vc, t, n: paged_decode_attention(q, kc, vc, t, n,
                                                       scale=0.0078125),
        one_chip, ((128, 32, D), jnp.bfloat16), pool, pool,
        ((128, P), jnp.int32), ((128,), jnp.int32))
    assert re.search(r"%decode_paged[.\w]* = .*tpu_custom_call", hlo)


# the KV append of a decode tick: the pool goes in and comes out as one
# buffer and the compiler lays no second pool array beside it (S15: a form of
# the write that scatters pages AND slots has it copy the whole pool into
# another layout and back, 229 MB and 639 MB at these shapes)
POOLS = {"serve-xl-sat": ((1746, 16, PS, D), 64, 16),
         "serve-granite-h-sat": ((9749, 8, PS, D), 128, 32)}


def _pool_text(pool):
    return "bf16[" + ",".join(str(d) for d in pool) + "]"


def _assert_pool_stays_where_it_lies(compiled, pool, n_pools):
    hlo = compiled.as_text()
    assert not re.search(r"%copy[.\w]* = " + re.escape(_pool_text(pool)), hlo)
    memory = compiled.memory_analysis()
    pool_bytes = 2 * pool[0] * pool[1] * pool[2] * pool[3]
    assert memory.alias_size_in_bytes >= n_pools * pool_bytes
    assert memory.temp_size_in_bytes < pool_bytes // 8
    return hlo


@pytest.mark.parametrize("cell", sorted(POOLS))
def test_paged_kv_write_compiles_in_place(one_chip, cell):
    pool, rows, _ = POOLS[cell]
    args = _args(one_chip, (pool, jnp.bfloat16),
                 ((rows, pool[1], D), jnp.bfloat16), ((rows, P), jnp.int32),
                 ((rows,), jnp.int32))
    compiled = jax.jit(paged_kv_write, donate_argnums=(0,)).lower(
        *args).compile()
    _assert_pool_stays_where_it_lies(compiled, pool, 1)


@pytest.mark.parametrize("cell", sorted(POOLS))
def test_kv_writes_then_paged_decode_leave_the_pool_in_place(one_chip, cell):
    """The model's own order in one program, both pool arrays donated."""
    pool, rows, heads = POOLS[cell]

    def layer(kc, vc, k, v, q, tables, lengths):
        kc = paged_kv_write(kc, k, tables, lengths)
        vc = paged_kv_write(vc, v, tables, lengths)
        return paged_decode_attention(q, kc, vc, tables, lengths + 1), kc, vc

    new = ((rows, pool[1], D), jnp.bfloat16)
    args = _args(one_chip, (pool, jnp.bfloat16), (pool, jnp.bfloat16), new, new,
                 ((rows, heads, D), jnp.bfloat16), ((rows, P), jnp.int32),
                 ((rows,), jnp.int32))
    compiled = jax.jit(layer, donate_argnums=(0, 1)).lower(*args).compile()
    hlo = _assert_pool_stays_where_it_lies(compiled, pool, 2)
    # what benchmark/readers/decode_attn_roofline.py holds on to
    (call,) = [line for line in hlo.splitlines()
               if re.match(r"\s*(ROOT )?%decode_paged[.\w]* = ", line)]
    assert 'custom_call_target="tpu_custom_call"' in call
    assert _pool_text(pool) + "{3,2,1,0" in call


def _xl_engine(monkeypatch):
    """The paged engine at `serve-xl-sat`'s widths over two layers of the 24,
    from shapes alone: nothing runs, so no value is ever read and the
    parameters stay the zeros they are made as."""
    import paddle_tpu.amp as amp
    from paddle_tpu.inference.paged import PagedServingEngine
    from paddle_tpu.models import GPTConfig, GPTForCausalLM
    from paddle_tpu.nn import initializer

    for cls in (initializer.Normal, initializer.XavierUniform):
        monkeypatch.setattr(cls, "__call__", lambda self, param, block=None: param)
    root = os.path.dirname(os.path.dirname(os.path.abspath(__file__)))
    with open(os.path.join(root, "benchmark", "configs", "gpt3-xl.json")) as f:
        cell = json.load(f)
    serve = cell["serve"]
    model = GPTForCausalLM(GPTConfig(
        vocab_size=cell["vocab_size"], hidden_size=cell["hidden_size"],
        num_layers=2, num_heads=cell["num_heads"],
        max_position_embeddings=cell["max_position_embeddings"]))
    amp.decorate(model, level="O2", dtype="bfloat16")
    return PagedServingEngine(
        model, max_batch_size=serve["max_batch_size"],
        max_seq_len=serve["max_seq_len"], page_size=serve["page_size"],
        num_pages=2)


def test_the_decode_program_samples_under_one_conditional(one_chip,
                                                          monkeypatch):
    """The engine's whole decode program at `serve-xl-sat`'s widths (64 rows,
    vocabulary 50304, the cell's pool; two layers of the 24, from shapes
    alone): the sampler is ONE `conditional` that yields the tokens and the
    advanced keys, so an all-greedy tick skips the draw; the program returns
    both beside the logits; and no second pool array is laid beside the pool
    (S15), sampler or not."""
    eng = _xl_engine(monkeypatch)
    pool, rows, _ = POOLS["serve-xl-sat"]
    assert (eng.B, eng.P, eng.pool.kv[0][0].shape[1:]) == (rows, P, pool[1:])

    def shape(x, dims=None):
        return jax.ShapeDtypeStruct(dims or x.shape, x.dtype,
                                    sharding=one_chip)

    args = (jax.tree.map(shape, eng.params), jax.tree.map(shape, eng.buffers),
            *_args(one_chip, ((rows,), jnp.int32), ((rows,), jnp.int32),
                   ((rows, P), jnp.int32), ((rows,), jnp.float32),
                   ((rows, 2), jnp.uint32)),
            [tuple(shape(x, pool) for x in layer) for layer in eng.pool.kv])
    compiled = eng._decode_program().lower(*args).compile()
    hlo = _assert_pool_stays_where_it_lies(compiled, pool, 4)
    (cond,) = [line for line in hlo.splitlines() if " conditional(" in line]
    assert re.search(r"= \(s32\[64\]\S*, u32\[64,2\]\S*\) conditional\(", cond)
    # the draw (the generator's bits) is inside the branches only
    entry = hlo[hlo.index("\nENTRY "):]
    assert "rng-bit-generator" not in entry and "threefry" not in entry
    (out,) = [line for line in entry.splitlines()
              if re.match(r"\s*ROOT %\S+ = \(", line)]
    assert re.search(r"= \(s32\[64\]\S*, u32\[64,2\]\S*, "
                     r"bf16\[64,50304\]\S*, " + re.escape(_pool_text(pool)),
                     out)
    assert len(re.findall(r"%decode_paged[.\w]* = ", entry)) == 2


@pytest.mark.parametrize("bucket", [1024, 2048])
def test_the_gpt_prefill_program_is_flash_over_the_prompt_alone(
        one_chip, monkeypatch, bucket):
    """The engine's whole prefill program at `serve-xl-sat`'s two buckets
    (batch 1, 16 heads of 128, two layers of the 24, from shapes alone): its
    inputs beside the weights are the tokens and the length, every layer's
    attention is the `flash_fwd` kernel the training cell runs, and no
    `f32[16, Sp, Sp]` array of scores is written (S4(c))."""
    import paddle_tpu.ops.pallas as pallas

    # the dispatch rule of a TPU backend, which this process does not have
    monkeypatch.setattr(pallas, "kernels_available", lambda: True)
    eng = _xl_engine(monkeypatch)
    programs = []
    compile_prefill = eng._prefill_programs.get_or_compile
    eng._prefill_programs.get_or_compile = lambda b, fn: programs.append(
        compile_prefill(b, fn)) or (lambda *args: (None, None))
    eng._run_prefill(type("Req", (), {"prompt": [1] * (bucket - 5),
                                      "req_id": 0}))

    def shape(x):
        return jax.ShapeDtypeStruct(x.shape, x.dtype, sharding=one_chip)

    (program,) = programs
    hlo = program.lower(
        jax.tree.map(shape, eng.params), jax.tree.map(shape, eng.buffers),
        *_args(one_chip, ((1, bucket), jnp.int32), ((), jnp.int32))
    ).compile().as_text()
    calls = [line for line in hlo.splitlines()
             if re.match(r"\s*(ROOT )?%flash_fwd[.\w]* = ", line)]
    assert len(calls) == 2
    for call in calls:
        assert 'custom_call_target="tpu_custom_call"' in call
        assert f"bf16[1,{H},{bucket},{D}]" in call
    assert not re.search(rf"f32\[(1,)?{H},{bucket},{bucket}\]", hlo)
    (out,) = [line for line in hlo[hlo.index("\nENTRY "):].splitlines()
              if re.match(r"\s*ROOT %\S+ = \(", line)]
    # the last position's logits row, and K and V a layer as the pages take
    assert re.search(r"= \(bf16\[50304\]\S*(, bf16\[1,%d,%d,%d\]\S*){4}\) "
                     % (bucket, H, D), out)


# serve-kimi-k2-reason-sat: 256 rows, 64 heads; a token's cache in a layer is
# ONE row of 640 (latent 512, rotated key 64, zeros), pages of 256, a pool of
# about 4k pages in five arrays, the table 64 wide; prefill buckets 1k-8k
# with q and k 192 wide and v 128; 12 held experts of 384, 7168 x (2 x 2048)
# and 2048 x 7168

K_POOL, K_ROWS, K_HEADS, K_TABLE = (4100, 256, 640), 96, 64, 64


def test_latent_decode_compiles_in_place_at_the_cells_shapes(one_chip):
    """The model's own order in one program: the token's row written into
    its page, then `decode_latent` over the pool as it lies, donated."""
    from paddle_tpu.ops.pallas.decode_attention import (
        latent_decode_attention, latent_kv_write, latent_pages_per_step)

    def layer(pages, new, q, tables, lengths):
        pages = latent_kv_write(pages, new, tables, lengths)
        return latent_decode_attention(q, pages, tables, lengths + 1, 512,
                                       0.13087), pages

    args = _args(one_chip, (K_POOL, jnp.bfloat16),
                 ((K_ROWS, 640), jnp.bfloat16),
                 ((K_ROWS, K_HEADS, 640), jnp.bfloat16),
                 ((K_ROWS, K_TABLE), jnp.int32), ((K_ROWS,), jnp.int32))
    compiled = jax.jit(layer, donate_argnums=(0,)).lower(*args).compile()
    hlo = compiled.as_text()
    pool = "bf16[" + ",".join(str(d) for d in K_POOL) + "]"
    assert not re.search(r"%copy[.\w]* = " + re.escape(pool), hlo)
    memory = compiled.memory_analysis()
    pool_bytes = 2 * K_POOL[0] * K_POOL[1] * K_POOL[2]
    assert memory.alias_size_in_bytes >= pool_bytes
    assert memory.temp_size_in_bytes < pool_bytes // 8
    # what benchmark/readers/kernel_roofline_kimi_k2.py holds on to
    (call,) = [line for line in hlo.splitlines()
               if re.match(r"\s*(ROOT )?%decode_latent[.\w]* = ", line)]
    assert 'custom_call_target="tpu_custom_call"' in call and pool in call
    # a kilotoken a grid step, whatever the page size
    assert latent_pages_per_step(256, 640, K_TABLE, 2) == 4
    assert latent_pages_per_step(64, 640, 256, 2) == 16


# the paged decode kernels' grid is the work list of live steps, its length a
# traced scalar: the cells' rows and table widths as the cells run them
WORK_LIST_CELLS = {
    # cell: (kernel, rows, table width, pool, q, window, pages a step)
    "trinity-full": ("decode_paged", 224, 512, T_POOL[0], (32, D), None, 16),
    "trinity-window": ("decode_window", 224, 65, T_POOL[0], (32, D), 2048,
                       16),
    "xl": ("decode_paged", 64, 64, (N_PAGES, 16, PS, D), (16, D), None, 8),
    "granite": ("decode_paged", 128, 64, (9749, 8, PS, D), (32, D), None,
                16),
    "kimi": ("decode_latent", 128, 64, K_POOL, (K_HEADS, 640), None, 4),
}


@pytest.mark.parametrize("cell", sorted(WORK_LIST_CELLS))
def test_the_work_list_grid_compiles_at_the_cells_shapes(one_chip, cell):
    """Mosaic takes the dynamic grid bound for all three bodies; two layers
    that share a table share ONE work list (one scatter of
    `[rows x steps, n + 1]` integers in the program, no gather of the
    table's scalars), and the prefetched operands fit SMEM."""
    from paddle_tpu.ops.pallas.decode_attention import (
        latent_decode_attention, work_list)

    name, rows, width, pool, q_shape, window, n = WORK_LIST_CELLS[cell]

    def attend(q, pages, tables, lengths):
        if name == "decode_latent":
            return latent_decode_attention(q, pages, tables, lengths, 512,
                                           0.13087)
        return paged_decode_attention(q, pages, pages, tables, lengths,
                                      window=window)

    def two_layers(q, pages0, pages1, tables, lengths):
        out = attend(q, pages0, tables, lengths + 1)
        if name == "decode_latent":   # [rows, H, 512] back to the q's width
            out = jnp.pad(out, ((0, 0), (0, 0), (0, 128)))
        return attend(q + out, pages1, tables, lengths + 1)

    pool = (pool, jnp.bfloat16)
    hlo = _compile(two_layers, one_chip, ((rows,) + q_shape, jnp.bfloat16),
                   pool, pool, ((rows, width), jnp.int32),
                   ((rows,), jnp.int32))
    calls = [line for line in hlo.splitlines()
             if re.match(r"\s*(ROOT )?%" + name + r"[.\w]* = ", line)]
    assert len(calls) == 2
    steps = rows * -(-width // n)
    for call in calls:
        assert 'custom_call_target="tpu_custom_call"' in call
        # the grid's bound is the call's first operand, a scalar; then the
        # fetch table as one row and the list's four arrays
        assert (f"operand_layout_constraints={{s32[], s32[{steps * n}]{{0}}, "
                f"s32[{steps}]{{0}}") in call
    assert len(re.findall(rf" = s32\[{steps},{n + 1}\]\S* scatter\(",
                          hlo)) == 1
    assert not re.search(rf" = s32\[{steps * n}\]\S* gather\(", hlo)
    shapes = jax.eval_shape(
        lambda t, l: work_list(t, l, pool[0][-2] if name == "decode_latent"
                               else PS, n, window),
        jax.ShapeDtypeStruct((rows, width), jnp.int32),
        jax.ShapeDtypeStruct((rows,), jnp.int32))
    assert shapes.fetch.shape == (steps, n) and shapes.count.shape == ()


@pytest.mark.parametrize("seq", [1024, 8192])
def test_flash_fwd_compiles_with_a_value_width_of_its_own(one_chip, seq):
    """The latent prefill's expanded heads: q and k 192 wide, v 128."""
    from paddle_tpu.ops.pallas.flash_attention import flash_attention_fwd

    qk = ((1, seq, K_HEADS, 192), jnp.bfloat16)
    hlo = _compile(
        lambda q, k, v: flash_attention_fwd(q, k, v, causal=True,
                                            scale=0.13087),
        one_chip, qk, qk, ((1, seq, K_HEADS, 128), jnp.bfloat16))
    (call,) = [line for line in hlo.splitlines()
               if re.match(r"\s*(ROOT )?%flash_fwd[.\w]* = ", line)]
    assert 'custom_call_target="tpu_custom_call"' in call
    assert f"bf16[1,{K_HEADS},{seq},128]" in call


@pytest.mark.parametrize("tokens,k,n", [
    (256, 7168, 4096), (256, 2048, 7168), (4096, 7168, 4096),
    (4096, 2048, 7168)],
    ids=["decode-in", "decode-out", "pass-in", "pass-out"])
def test_grouped_gemm_compiles_at_kimis_held_experts(one_chip, tokens, k, n):
    """12 of 384 experts held, 8 picks a token: a decode tick's 256 rows and
    a whole pass of 4096 tokens."""
    call = _held_experts_call(one_chip, 12, 384, tokens, 8, k, n)
    # what benchmark/readers/kernel_roofline_kimi_k2.py holds on to
    assert f"bf16[12,{k},{n}]" in call


# serve-mimo-v2-agent-sat: 176 rows, 64 query heads; full layers of 4 KV
# heads, sliding layers of 8, keys 192 wide STORED in 256 lanes and values
# of 128, pages of 32; ONE pool of about 60k units [units, 4, 32, W] that a
# sliding layer reads as [units / 2, 8, 32, W]; the full layers' table 512
# wide, the sliding layers' 5 (window 128); prefill buckets to 16k; 8 held
# experts of 4096 x (2 x 2048) and 2048 x 4096

M_UNITS, M_ROWS, M_HEADS = 60000, 176, 64
M_K = ((M_UNITS, 4, PS, 256), jnp.bfloat16)
M_V = ((M_UNITS, 4, PS, 128), jnp.bfloat16)


def _view(pool, heads):
    shape, dtype = pool
    return ((shape[0] * shape[1] // heads, heads) + shape[2:], dtype)


@pytest.mark.parametrize("heads,width,window,name,n", [
    (4, 512, None, "decode_paged", 16), (8, 5, 128, "decode_window", 4)])
def test_mimo_decode_kernels_compile_at_two_widths(one_chip, heads, width,
                                                   window, name, n):
    from paddle_tpu.ops.pallas.decode_attention import pages_per_step

    sink = [((M_HEADS,), jnp.bfloat16)] if window else []
    hlo = _compile(
        lambda q, kc, vc, tables, lengths, *b: paged_decode_attention(
            q, kc, vc, tables, lengths, scale=192 ** -0.5, window=window,
            sink=b[0] if b else None),
        one_chip, ((M_ROWS, M_HEADS, 256), jnp.bfloat16), _view(M_K, heads),
        _view(M_V, heads), ((M_ROWS, width), jnp.int32),
        ((M_ROWS,), jnp.int32), *sink)
    (call,) = [line for line in hlo.splitlines()
               if re.match(r"\s*(ROOT )?%" + name + r"[.\w]* = ", line)]
    assert 'custom_call_target="tpu_custom_call"' in call
    # the output is as wide as the values
    assert f"bf16[{M_ROWS},{heads},{M_HEADS // heads},128]" in call
    assert pages_per_step(heads, PS, 256, width, 2, 128) == n


@pytest.mark.parametrize("seq", [1024, 16384])
def test_mimo_window_prefill_compiles_with_a_sink(one_chip, seq):
    """q and k 192 wide as the projections give them, v 128, 8 KV heads, a
    sink a query head; the key block follows the window of 128."""
    from paddle_tpu.ops.pallas.flash_attention import flash_window_fwd

    hlo = _compile(
        lambda q, k, v, b: flash_window_fwd(q, k, v, 128, scale=192 ** -0.5,
                                            sink=b),
        one_chip, ((1, seq, M_HEADS, 192), jnp.bfloat16),
        ((1, seq, 8, 192), jnp.bfloat16), ((1, seq, 8, 128), jnp.bfloat16),
        ((M_HEADS,), jnp.bfloat16))
    (call,) = [line for line in hlo.splitlines()
               if re.match(r"\s*(ROOT )?%flash_fwd_window[.\w]* = ", line)]
    assert 'custom_call_target="tpu_custom_call"' in call
    assert f"bf16[1,{M_HEADS},{seq},128]" in call
    assert "bf16[1,8,192,%d]" % seq in hlo       # K transposed, 192 wide


def _two_shapes_layers(kc, vc, k4, v4, k8, v8, q, t4, t8, n4, n8, sink):
    """A full layer, a sliding layer through the coarser view, a full layer
    again: the model's own order over the ONE pool."""
    def layer(kc, vc, k, v, tables, lengths, **kw):
        heads = k.shape[1]
        ks, vs = kc.shape, vc.shape
        kc = kc.reshape((-1, heads) + ks[2:])
        vc = vc.reshape((-1, heads) + vs[2:])
        kc = paged_kv_write(kc, k, tables, lengths)
        vc = paged_kv_write(vc, v, tables, lengths)
        out = paged_decode_attention(q, kc, vc, tables, lengths + 1,
                                     scale=192 ** -0.5, **kw)
        return out, kc.reshape(ks), vc.reshape(vs)

    o1, kc, vc = layer(kc, vc, k4, v4, t4, n4)
    o2, kc, vc = layer(kc, vc, k8, v8, t8, n8, window=128, sink=sink)
    o3, kc, vc = layer(kc, vc, k4, v4, t4, n4)
    return o1 + o2 + o3, kc, vc


@pytest.mark.parametrize("key_lanes", [256, 192])
def test_the_pool_of_two_shapes_stays_where_it_lies(one_chip, key_lanes):
    """Keys stored in whole lane tiles (256 for 192): the append and both
    decode kernels, through both views, leave the donated pool in place and
    the views are no copy. Stored 192 wide, the compiler lays the pool out
    its own way and copies ALL of it round the kernels: why
    `block_pool.stored_width` pads."""
    k_pool = (M_K[0][:3] + (key_lanes,), jnp.bfloat16)
    args = _args(
        one_chip, k_pool, M_V, ((M_ROWS, 4, key_lanes), jnp.bfloat16),
        ((M_ROWS, 4, 128), jnp.bfloat16),
        ((M_ROWS, 8, key_lanes), jnp.bfloat16),
        ((M_ROWS, 8, 128), jnp.bfloat16),
        ((M_ROWS, M_HEADS, key_lanes), jnp.bfloat16),
        ((M_ROWS, 512), jnp.int32), ((M_ROWS, 5), jnp.int32),
        ((M_ROWS,), jnp.int32), ((M_ROWS,), jnp.int32),
        ((M_HEADS,), jnp.bfloat16))
    compiled = jax.jit(_two_shapes_layers, donate_argnums=(0, 1)).lower(
        *args).compile()
    copies = re.findall(r"%copy[.\w]* = bf16\[(?:60000,4|30000,8),32,",
                        compiled.as_text())
    memory = compiled.memory_analysis()
    k_bytes = 2 * M_UNITS * 4 * PS * 256
    if key_lanes == 256:
        assert not copies
        assert memory.alias_size_in_bytes >= k_bytes + k_bytes // 2
        assert memory.temp_size_in_bytes < k_bytes // 100
    else:
        assert copies and memory.temp_size_in_bytes >= k_bytes


@pytest.mark.parametrize("tokens,k,n", [
    (176, 4096, 4096), (176, 2048, 4096), (4096, 4096, 4096),
    (4096, 2048, 4096)],
    ids=["decode-in", "decode-out", "pass-in", "pass-out"])
def test_grouped_gemm_compiles_at_mimos_held_experts(one_chip, tokens, k, n):
    """8 of 256 experts held, 8 picks a token: a decode tick's 176 rows and
    a whole pass of 4096 tokens."""
    call = _held_experts_call(one_chip, 8, 256, tokens, 8, k, n)
    assert f"bf16[8,{k},{n}]" in call


@pytest.mark.parametrize("tokens,d,experts,held,cap", [
    (128, 7168, 384, 12, 128), (4096, 4096, 256, 8, 4096)],
    ids=["kimi-decode", "mimo-pass"])
def test_the_compacting_pass_compiles_at_kimis_and_mimos_shapes(
        one_chip, monkeypatch, tokens, d, experts, held, cap):
    """The held layer WHOLE (route, sort, both branches of the capacity's
    cond) at Kimi's decode tick (1,024 pairs of 7,168-wide rows, 12 held)
    and MiMo's 4096-token pass (32,768 pairs of 4,096, 8 held), beside the
    kernel alone above: the compacting branch's two `grouped_gemm` calls
    take `cap` rows, the whole buffer's two every pair."""
    import paddle_tpu.ops.pallas as pallas
    from paddle_tpu.incubate.distributed.models.moe import held_moe

    # on the CPU backend the layer takes `ragged_dot`; here the kernel
    monkeypatch.setattr(pallas, "kernels_available", lambda: True)
    f, k, pairs = 2048, 8, tokens * 8
    assert held_moe._capacity(pairs, held, experts) == cap
    layer = held_moe.HeldExpertsMoE.__new__(held_moe.HeldExpertsMoE)
    for name, v in dict(gate="sigmoid", route_scale=2.5, held=(0, held),
                        top_k=k, d_expert=f, num_experts=experts,
                        d_model=d).items():
        object.__setattr__(layer, name, v)
    hlo = _compile(layer._build_fn(tokens, True), one_chip,
                   ((tokens, d), jnp.bfloat16), ((d, experts), jnp.bfloat16),
                   ((held, d, 2 * f), jnp.bfloat16),
                   ((held, f, d), jnp.bfloat16), ((experts,), jnp.float32),
                   ((tokens,), jnp.bool_))
    calls = [line for line in hlo.splitlines()
             if re.match(r"\s*(ROOT )?%grouped_gemm[.\w]* = ", line)]
    assert all('custom_call_target="tpu_custom_call"' in c for c in calls)
    for rows in (cap, pairs):
        assert any(f"bf16[{rows},{d}]" in c and f"bf16[{held},{d},{2 * f}]"
                   in c for c in calls)
        assert any(f"bf16[{rows},{f}]" in c and f"bf16[{held},{f},{d}]"
                   in c for c in calls)
