"""Autoregressive generation demo: KV-cache decode + continuous batching.

Builds a small decoder-only transformer, serves it through the
generation engine, and shows the three entry points:

  1. engine.generate        — batch API (private scheduler)
  2. scheduler streaming    — per-token iteration with mixed sampling
  3. HTTP serving           — POST /v2/models/lm/generate (JSON + SSE)
                              and GET /v2/stats

Run:  JAX_PLATFORMS=cpu python examples/generation_demo.py
"""
import json
import sys
import urllib.request

sys.path.insert(0, ".")

import jax

from flexflow_tpu.generation import (
    ContinuousBatchingScheduler,
    GenerationEngine,
    SamplingParams,
    init_decoder_params,
)
from flexflow_tpu.models.transformer import TransformerConfig
from flexflow_tpu.serving import InferenceServer
from flexflow_tpu.serving.generation import GenerationModel


def main():
    from flexflow_tpu.device import enable_compile_cache

    enable_compile_cache()
    cfg = TransformerConfig(
        num_layers=2, hidden_size=64, num_heads=4, ff_size=256,
        seq_length=128, vocab_size=256, causal=True,
    )
    params = init_decoder_params(jax.random.key(0), cfg)
    engine = GenerationEngine(
        params, cfg,
        max_batch_slots=4,
        block_size=16,
        # alternatively: cache_budget_bytes=64 << 20 sizes the cache
        # from a memory budget (see README "Generation")
    )

    # --- 1. batch API: mixed prompt lengths, one call -------------------
    prompts = [[1, 2, 3], list(range(10, 30)), [42] * 7]
    outs = engine.generate(prompts, SamplingParams(max_new_tokens=8))
    for p, o in zip(prompts, outs):
        print(f"prompt[{len(p)} toks] -> {o}")
    print("jit traces (one per bucket + one decode):", engine.trace_counts)

    # --- 2. streaming: tokens as they decode, per-request sampling ------
    sched = ContinuousBatchingScheduler(engine)
    sched.start()
    try:
        handle = sched.submit(
            [5, 6, 7],
            SamplingParams(max_new_tokens=6, temperature=0.8, top_k=20, seed=123),
        )
        print("stream:", end=" ", flush=True)
        for tok in handle.tokens(timeout=60):
            print(tok, end=" ", flush=True)
        print()
    finally:
        sched.stop()

    # --- 3. HTTP serving: JSON, SSE, and /v2/stats ----------------------
    server = InferenceServer(port=0)
    server.register_generation(GenerationModel(engine, name="lm"))
    with server:
        base = f"http://127.0.0.1:{server.port}"
        body = json.dumps({"prompt": [1, 2, 3], "max_new_tokens": 5}).encode()
        resp = json.load(
            urllib.request.urlopen(
                urllib.request.Request(f"{base}/v2/models/lm/generate", data=body)
            )
        )
        print("HTTP generate:", resp)
        body = json.dumps({"prompt": [9, 9], "max_new_tokens": 4, "stream": True}).encode()
        sse = urllib.request.urlopen(
            urllib.request.Request(f"{base}/v2/models/lm/generate", data=body)
        ).read().decode()
        print("SSE events:", [json.loads(l[6:]) for l in sse.strip().split("\n\n")])
        stats = json.load(urllib.request.urlopen(f"{base}/v2/stats"))
        print("stats:", json.dumps(stats["generation"]["lm"], indent=2))


if __name__ == "__main__":
    main()
