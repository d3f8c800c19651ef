#!/usr/bin/env python
"""Decode/serving throughput bench: tokens/s for the continuous-batching
engine (inference/serving.py) on gpt3-125M-shaped decode.

Prints one JSON line per configuration: prefill + steady-state decode
tokens/s at several batch sizes, with and without weight-only int8.
Meant for the real chip (one command of a chip-tool call); CPU runs are
smoke only."""

import json
import os
import sys
import time

sys.path.insert(0, os.path.dirname(os.path.dirname(os.path.abspath(__file__))))

import numpy as np


def main():
    import jax

    import paddle_tpu as paddle
    from paddle_tpu.inference.serving import ContinuousBatchingEngine
    from paddle_tpu.models import GPTForCausalLM, gpt3_125m, gpt3_tiny

    on_tpu = jax.devices()[0].platform == "tpu"
    cfg_fn = gpt3_125m if on_tpu else gpt3_tiny
    seq_len = 1024 if on_tpu else 64
    new_tokens = 128 if on_tpu else 8

    for quantized in (False, True):
        paddle.seed(0)
        model = GPTForCausalLM(cfg_fn())
        if quantized:
            from paddle_tpu.nn.quant import quantize_for_inference

            quantize_for_inference(model)
        for B in (1, 8) if on_tpu else (2,):
            eng = ContinuousBatchingEngine(model, max_batch_size=B,
                                           max_seq_len=seq_len)
            rng = np.random.default_rng(0)
            for _ in range(B):
                eng.add_request(
                    rng.integers(0, model.config.vocab_size, 32)
                    .astype(np.int32),
                    max_new_tokens=new_tokens, temperature=0.0)
            eng.step()  # admit + compile
            t0 = time.perf_counter()
            n_tokens = 0
            while any(r is not None for r in eng.active):
                n_tokens += len(eng.step())
            dt = time.perf_counter() - t0
            print(json.dumps({
                "metric": "decode_tokens_per_sec",
                "batch": B,
                "quantized": quantized,
                "value": round(n_tokens / max(dt, 1e-9), 1),
                "unit": "tok/s",
                "platform": jax.devices()[0].platform,
            }), flush=True)


if __name__ == "__main__":
    main()
