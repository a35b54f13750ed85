"""Decode-throughput benchmark: static-cache `generate()` on GPT-medium.

Two compiled programs regardless of length (prefill + scanned decode);
sampling (top-k) runs on device inside the scan. On a local chip
block_until_ready is the barrier; the np.asarray fetch serves as one
too.

Builder-reported on an earlier JAX, in no driver record — not measured on
today's code: on a v5e-class chip (355M params, bf16, prompt 32, 128 new;
top-k threshold via lax.approx_max_k — 29x faster than exact top_k over
the 50k vocab):
  batch  1:  ~680 tok/s  (1.5 ms/token — weight-bandwidth bound)
  batch  8: ~2200 tok/s
  batch 32: ~3300 tok/s
For ragged many-request serving use `GPTForCausalLM.paged_decode_step`
(continuous batching over a shared paged KV pool) instead.
"""
import json
import time

import numpy as np

import paddle_tpu as paddle
from paddle_tpu.models.gpt import GPTForCausalLM, gpt_medium, gpt_tiny


def main():
    import jax
    on_tpu = jax.default_backend() == "tpu"
    cfg = gpt_medium() if on_tpu else gpt_tiny()
    paddle.seed(0)
    model = GPTForCausalLM(cfg)
    if on_tpu:
        model.bfloat16()
    model.eval()
    rng = np.random.RandomState(0)
    batches = (1, 8, 32) if on_tpu else (2,)
    prompt, new = (32, 128) if on_tpu else (8, 8)
    for B in batches:
        ids = paddle.to_tensor(
            rng.randint(0, cfg.vocab_size, (B, prompt)).astype(np.int32))
        t0 = time.perf_counter()
        out = model.generate(ids, max_new_tokens=new, top_k=50)
        np.asarray(out.value)
        compile_s = time.perf_counter() - t0
        t0 = time.perf_counter()
        out = model.generate(ids, max_new_tokens=new, top_k=50)
        np.asarray(out.value)
        dt = time.perf_counter() - t0
        # dt covers prefill + all decode steps; with a short prompt the
        # prefill share is negligible, but the metric is end-to-end
        print(json.dumps({
            "batch": B, "prompt": prompt, "new": new,
            "compile_s": round(compile_s, 1),
            "decode_tok_per_s": round(B * new / dt, 1),
            "e2e_ms_per_new_token": round(dt / new * 1e3, 2)}), flush=True)


if __name__ == "__main__":
    main()
