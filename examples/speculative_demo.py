"""Speculative decoding demo: drafters + fixed-shape batched
verification over the block KV cache.

Shows the subsystem end to end:

  1. exactness        — speculative greedy output is token-for-token
                        identical to plain decoding (any drafter)
  2. throughput       — tokens per engine step vs the baseline, with
                        acceptance stats and adaptive k
  3. HTTP serving     — the "speculation" request block on
                        POST /v2/models/lm/generate and the spec_*
                        counters on GET /v2/stats

Run:  JAX_PLATFORMS=cpu python examples/speculative_demo.py
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
    SpeculationConfig,
    init_decoder_params,
)
from flexflow_tpu.models.transformer import TransformerConfig
from flexflow_tpu.serving import InferenceServer
from flexflow_tpu.serving.generation import GenerationModel


def make_engine(params, cfg):
    return GenerationEngine(
        params, cfg, max_batch_slots=4, block_size=16, max_spec_tokens=4
    )


def main():
    from flexflow_tpu.device import enable_compile_cache

    enable_compile_cache()
    cfg = TransformerConfig(
        num_layers=2, hidden_size=64, num_heads=4, ff_size=256,
        seq_length=128, vocab_size=64, causal=True,
    )
    params = init_decoder_params(jax.random.key(0), cfg)

    # repetitive prompts: the n-gram (prompt-lookup) drafter's home turf
    prompts = [[7, 3, 9] * 8, [5, 5, 2, 5, 5, 2, 5, 5, 2], list(range(1, 20))]
    sampling = SamplingParams(max_new_tokens=32)
    spec = SpeculationConfig(k=4, method="ngram")

    # --- 1. exactness ---------------------------------------------------
    plain = make_engine(params, cfg).generate(prompts, sampling)
    spec_eng = make_engine(params, cfg)
    spec_out = spec_eng.generate(prompts, sampling, speculation=spec)
    assert plain == spec_out, "speculative greedy must be exact"
    print("exact: speculative greedy == plain greedy on", len(prompts), "prompts")

    # --- 2. throughput + acceptance ------------------------------------
    base_eng = make_engine(params, cfg)
    base_eng.generate(prompts, sampling)
    base_steps = base_eng.step_counts["decode"]
    eng = make_engine(params, cfg)
    sched = ContinuousBatchingScheduler(eng)
    handles = [sched.submit(p, sampling, speculation=spec) for p in prompts]
    while any(not h.done() for h in handles):
        if not sched.step():
            break
    spec_steps = eng.step_counts["verify"] + eng.step_counts["decode"]
    total = sum(len(h.result(timeout=0)) for h in handles)
    ss = sched.spec_stats
    print(f"decode steps: {base_steps} plain vs {spec_steps} speculative "
          f"for {total} tokens ({base_steps / max(1, spec_steps):.2f}x fewer)")
    print(f"acceptance rate {ss.acceptance_rate():.2f}, "
          f"mean accepted run {ss.mean_accepted_len():.2f}, "
          f"mean emitted/window {ss.mean_emitted_len():.2f}")
    print("verify program compiled", eng.trace_counts.get("verify"), "time(s)")

    # --- 3. HTTP: speculation request block + /v2/stats -----------------
    server = InferenceServer(port=0)
    server.register_generation(GenerationModel(make_engine(params, cfg), name="lm"))
    with server:
        base = f"http://127.0.0.1:{server.port}"
        body = json.dumps({
            "prompt": prompts[0], "max_new_tokens": 16,
            "speculation": {"k": 4, "method": "ngram", "max_ngram": 3},
        }).encode()
        resp = json.load(urllib.request.urlopen(
            urllib.request.Request(f"{base}/v2/models/lm/generate", data=body)))
        assert resp["tokens"] == plain[0][:16]
        print("HTTP speculative generate:", resp["tokens"][:8], "...")
        stats = json.load(urllib.request.urlopen(f"{base}/v2/stats"))
        lm = stats["generation"]["lm"]
        print("stats:", {k: v for k, v in lm.items() if k.startswith("spec_")})


if __name__ == "__main__":
    main()
