"""Continuous-batching generation engine (reference L13 serving depth:
dynamic batching scheduler; here admit-while-decoding over a slotted KV
cache with one fixed-shape compiled decode program)."""

import jax
import jax.numpy as jnp
import numpy as np
import pytest

import paddle_tpu as paddle
from paddle_tpu.inference.serving import (
    ContinuousBatchingEngine,
    _ServingEngineBase,
)
from paddle_tpu.models import GPTForCausalLM, gpt3_tiny
from paddle_tpu.models.generation import generate


@pytest.fixture(scope="module")
def model():
    paddle.seed(0)
    return GPTForCausalLM(gpt3_tiny())


class TestContinuousBatching:
    def test_single_request_matches_generate(self, model):
        eng = ContinuousBatchingEngine(model, max_batch_size=4,
                                       max_seq_len=64)
        prompt = np.array([5, 7, 11, 13], np.int32)
        eng.add_request(prompt, max_new_tokens=8, temperature=0.0)
        done = eng.run()
        ref = generate(model, prompt[None], max_new_tokens=8,
                       temperature=0.0).numpy()[0]
        np.testing.assert_array_equal(done[0].output_ids,
                                      ref[: len(done[0].output_ids)])

    def test_staggered_admission_parity(self, model):
        """More requests than slots, different prompt lengths and budgets:
        every output equals its standalone greedy generation."""
        eng = ContinuousBatchingEngine(model, max_batch_size=4,
                                       max_seq_len=64)
        prompts = [np.arange(2 + i, dtype=np.int32) + 3 for i in range(6)]
        ids = [eng.add_request(p, max_new_tokens=4 + i % 3,
                               temperature=0.0)
               for i, p in enumerate(prompts)]
        done = eng.run()
        assert len(done) == 6
        by_id = {r.req_id: r for r in done}
        for p, rid in zip(prompts, ids):
            got = by_id[rid]
            ref = generate(model, p[None],
                           max_new_tokens=len(got.generated),
                           temperature=0.0).numpy()[0]
            np.testing.assert_array_equal(got.output_ids, ref)

    def test_eos_stops_request(self, model):
        eng = ContinuousBatchingEngine(model, max_batch_size=2,
                                       max_seq_len=64)
        prompt = np.array([5, 7, 11, 13], np.int32)
        ref = generate(model, prompt[None], max_new_tokens=8,
                       temperature=0.0).numpy()[0]
        eos = int(ref[len(prompt)])  # first generated token acts as EOS
        eng.add_request(prompt, max_new_tokens=8, eos_token_id=eos,
                        temperature=0.0)
        done = eng.run()
        assert done[0].generated == [eos]

    def test_prompt_too_long_rejected(self, model):
        eng = ContinuousBatchingEngine(model, max_batch_size=2,
                                       max_seq_len=16)
        with pytest.raises(ValueError):
            eng.add_request(np.zeros(16, np.int32))

    def test_admission_is_online(self, model):
        """step() output only contains live requests; new arrivals join
        later ticks without recompilation (same decode program)."""
        eng = ContinuousBatchingEngine(model, max_batch_size=2,
                                       max_seq_len=64)
        a = eng.add_request(np.array([3, 4], np.int32), max_new_tokens=6,
                            temperature=0.0)
        first = eng.step()
        assert set(first) == {a}
        b = eng.add_request(np.array([9, 8, 7], np.int32),
                            max_new_tokens=3, temperature=0.0)
        second = eng.step()
        assert b in second and a in second
        done = eng.run()
        assert {r.req_id for r in done} == {a, b}


class TestServingSatellites:
    def test_sampled_rows_leave_greedy_rows_untouched(self, model):
        """One sampled-temperature request must not perturb the greedy
        requests batched with it (the old path materialized the whole
        [B, vocab] logits on host for everyone; now each sampled row
        gathers only its own slice, and greedy stays on device)."""
        prompts = [np.array([5, 7, 11], np.int32),
                   np.array([2, 3], np.int32)]
        eng = ContinuousBatchingEngine(model, max_batch_size=2,
                                       max_seq_len=64, seed=0)
        g_only = eng.add_request(prompts[0], max_new_tokens=4,
                                 temperature=0.0)
        ref = {r.req_id: r.generated for r in eng.run()}[g_only]

        eng = ContinuousBatchingEngine(model, max_batch_size=2,
                                       max_seq_len=64, seed=0)
        g = eng.add_request(prompts[0], max_new_tokens=4, temperature=0.0)
        eng.add_request(prompts[1], max_new_tokens=4, temperature=0.9)
        out = {r.req_id: r.generated for r in eng.run()}
        assert out[g] == ref

    def test_sampled_stream_deterministic_per_seed_and_arrival(self, model):
        """Per-request sampling keys fold (engine seed, arrival index):
        the same workload on the same seed reproduces exactly."""
        prompt = np.array([9, 8, 7], np.int32)

        def run_once():
            eng = ContinuousBatchingEngine(model, max_batch_size=2,
                                           max_seq_len=64, seed=5)
            eng.add_request(prompt, max_new_tokens=5, temperature=0.8)
            return eng.run()[0].generated

        assert run_once() == run_once()

    def test_truncated_flag_on_capacity_retirement(self, model):
        eng = ContinuousBatchingEngine(model, max_batch_size=2,
                                       max_seq_len=16)
        eng.add_request(np.arange(1, 11, dtype=np.int32),
                        max_new_tokens=100)
        done = eng.run()
        assert done[0].truncated and len(done[0].generated) == 6
        # a request that finishes inside its budget is NOT flagged
        eng.add_request(np.array([1, 2, 3], np.int32), max_new_tokens=2)
        assert not eng.run()[0].truncated

    def test_prefill_compile_cache_capped(self, model):
        """Live prefill buckets are capped (oldest evicted) and every real
        compile — including a re-compile after eviction — lands in
        serving_prefill_compiles_total{engine=,bucket=}."""
        from paddle_tpu.observability.metrics import default_registry

        def compiles(bucket):
            m = default_registry().get("serving_prefill_compiles_total")
            return m.value(engine="dense", bucket=bucket) if m else 0.0

        c16 = compiles("16")
        eng = ContinuousBatchingEngine(model, max_batch_size=2,
                                       max_seq_len=128,
                                       max_prefill_buckets=2)
        for n in (5, 20, 40):  # buckets 16, 32, 64 -> 16 evicted
            eng.add_request(np.arange(1, n + 1, dtype=np.int32),
                            max_new_tokens=1)
            eng.run()
        assert len(eng._prefill_programs) == 2
        assert 16 not in eng._prefill_programs and 64 in eng._prefill_programs
        eng.add_request(np.arange(1, 6, dtype=np.int32), max_new_tokens=1)
        eng.run()
        assert compiles("16") == c16 + 2  # eviction made the recompile visible


# the decode program's sampler against the per-row chain it replaced, kept
# here as the reference: an eager split, then categorical(sub, row / T)
TEMPS = {"all-greedy": [0.0] * 8,
         "mixed": [0.7, 0.0, 0.0, 1.3, 0.0, 0.7, 0.2, 0.0],
         "all-sampled": [0.7, 1.0, 0.2, 1.3, 0.7, 0.5, 2.0, 0.9]}


def _per_row_chain(logits, temps, keys):
    tokens, keys = [], [jnp.asarray(k) for k in keys]
    for i, t in enumerate(temps):
        if t == 0.0:
            tokens.append(int(jnp.argmax(logits[i])))
            continue
        keys[i], sub = jax.random.split(keys[i])
        tokens.append(int(jax.random.categorical(sub, logits[i] / t)))
    return np.asarray(tokens, np.int32), np.stack([np.asarray(k)
                                                   for k in keys])


@pytest.mark.parametrize("dtype", [jnp.float32, jnp.bfloat16],
                         ids=["float32", "bfloat16"])
@pytest.mark.parametrize("mix", sorted(TEMPS))
def test_in_program_sampler_equals_the_per_row_chain(mix, dtype):
    """Tokens and advanced keys bit for bit, at the serving cell's
    vocabulary, over two steps of the stream; a greedy row's key comes back
    as it went in."""
    temps = TEMPS[mix]
    rng = np.random.default_rng(5)
    keys = np.stack([np.asarray(jax.random.fold_in(jax.random.PRNGKey(3), i))
                     for i in range(8)])
    choose = jax.jit(_ServingEngineBase._choose_tokens)
    for _ in range(2):
        logits = jnp.asarray(rng.normal(0, 2, (8, 50304)), dtype)
        want_tok, want_keys = _per_row_chain(logits, temps, keys)
        tok, new_keys = jax.device_get(
            choose(logits, jnp.asarray(temps, jnp.float32), jnp.asarray(keys)))
        assert tok.dtype == np.int32 and new_keys.dtype == np.uint32
        np.testing.assert_array_equal(tok, want_tok)
        np.testing.assert_array_equal(new_keys, want_keys)
        greedy = np.asarray(temps) == 0.0
        np.testing.assert_array_equal(new_keys[greedy], keys[greedy])
        assert (new_keys[~greedy] != keys[~greedy]).any(axis=1).all()
        keys = new_keys


def test_in_program_sampler_draws_only_under_a_cond_on_the_temperatures():
    """One program for every mix: the draw sits in a branch that the
    program picks from `temps`, so an all-greedy tick pays an argmax."""
    jaxpr = jax.make_jaxpr(_ServingEngineBase._choose_tokens)(
        jnp.zeros((4, 64), jnp.bfloat16), jnp.zeros(4, jnp.float32),
        jnp.zeros((4, 2), jnp.uint32))
    (cond,) = [e for e in jaxpr.eqns if e.primitive.name == "cond"]
    outside = {e.primitive.name for e in jaxpr.eqns} - {"cond"}
    assert not any("random" in n or "threefry" in n for n in outside)
    branches = [str(b) for b in cond.params["branches"]]
    assert sum("threefry" in b or "random_bits" in b for b in branches) == 1


class TestQuantizedServing:
    def test_weight_only_generation_and_serving(self):
        """quantize_for_inference converts Linear (incl. degenerate
        parallel Linear) layers to int8 weight-only buffers; generation and
        the batching engine keep working with near-identical tokens."""
        from paddle_tpu.nn.quant import quantize_for_inference

        paddle.seed(0)
        m = GPTForCausalLM(gpt3_tiny())
        prompt = np.array([5, 7, 11, 13], np.int32)
        ref = generate(m, prompt[None], max_new_tokens=8,
                       temperature=0.0).numpy()[0]
        n = quantize_for_inference(m)
        assert n > 0
        # the fp weight params are gone from state (HBM saving is real)
        assert not any(k.endswith("q_proj.weight")
                       for k, _ in m.named_parameters())
        got = generate(m, prompt[None], max_new_tokens=8,
                       temperature=0.0).numpy()[0]
        assert (ref == got).mean() >= 0.7
        eng = ContinuousBatchingEngine(m, max_batch_size=2, max_seq_len=48)
        eng.add_request(prompt, max_new_tokens=5, temperature=0.0)
        done = eng.run()
        np.testing.assert_array_equal(
            done[0].output_ids, got[: len(done[0].output_ids)])


class TestLlamaServing:
    def test_llama_gqa_through_engine(self):
        """GQA models (kv_heads < num_heads) run through the slotted cache
        and match plain generate()."""
        from paddle_tpu.models.llama import LlamaForCausalLM, llama_tiny

        paddle.seed(0)
        m = LlamaForCausalLM(llama_tiny())
        prompt = np.array([3, 5, 7], np.int32)
        ref = generate(m, prompt[None], max_new_tokens=6,
                       temperature=0.0).numpy()[0]
        eng = ContinuousBatchingEngine(m, max_batch_size=2, max_seq_len=48)
        eng.add_request(prompt, max_new_tokens=6, temperature=0.0)
        done = eng.run()
        np.testing.assert_array_equal(
            done[0].output_ids, ref[: len(done[0].output_ids)])
