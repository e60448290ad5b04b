"""Fleet serving demo: replica failover as a routing event, not an
outage.

Builds a 2-replica Fleet of small decoder-only transformers, murders
replica r0 mid-stream with a scoped fault plan (``replica_kill``), and
shows every stream completing byte-identically on the survivor while
the fleet spawns a warm replacement. Then serves the fleet over HTTP
and reads the new ``GET /v2/fleet`` debug endpoint plus the
replica-labeled ``/metrics`` families.

Run:  JAX_PLATFORMS=cpu python examples/fleet_demo.py
"""
import json
import sys
import urllib.request

sys.path.insert(0, ".")

import jax

from flexflow_tpu.generation import (
    GenerationEngine,
    RecoveryPolicy,
    SamplingParams,
    init_decoder_params,
)
from flexflow_tpu.models.transformer import TransformerConfig
from flexflow_tpu.runtime.faults import FaultPlan, replica_kill
from flexflow_tpu.serving import InferenceServer
from flexflow_tpu.serving.fleet import Fleet


def main():
    from flexflow_tpu.device import enable_compile_cache

    enable_compile_cache()
    cfg = TransformerConfig(
        num_layers=2, hidden_size=64, num_heads=4, ff_size=256,
        seq_length=128, vocab_size=256, causal=True,
    )
    params = init_decoder_params(jax.random.key(0), cfg)

    def engine_factory():
        return GenerationEngine(
            params, cfg, max_batch_slots=4, block_size=16,
            prompt_buckets=(16, 64, 128),
        )

    prompts = [[1, 2, 3], [4, 5, 6, 7], [9, 8, 7, 6], [1, 2, 3, 4, 5]]
    sampling = SamplingParams(max_new_tokens=16)

    # ---------------------------------------------- fault-free reference
    ref_engine = engine_factory()
    reference = [ref_engine.generate([p], sampling)[0] for p in prompts]

    # -------------------------------- 1. kill a replica mid-stream
    print("== 1. replica murder -> cross-replica journal-replay failover ==")
    fleet = Fleet(
        engine_factory, 2, name="lm",
        scheduler_kwargs=dict(
            recovery=RecoveryPolicy(max_restarts=1, sleep=lambda _s: None)
        ),
    )
    plan = FaultPlan(seed=0)
    replica_kill(plan, "r0", every=1)  # every decode step on r0 crashes
    with plan.active():
        handles = [fleet.submit(p, sampling) for p in prompts]
        while not all(h.done() for h in handles):
            fleet.step()
    results = [h.result(timeout=0) for h in handles]
    print("   streams byte-identical to fault-free run:",
          results == reference)
    print("   fleet counters:", json.dumps(fleet.fleet_stats.snapshot()))
    print("   replicas now:", [(r.id, r.state) for r in fleet.replicas])

    # ------------------------------------- 2. HTTP serving + /v2/fleet
    print("== 2. HTTP serving: /v2/fleet + replica-labeled /metrics ==")
    server = InferenceServer(port=0)
    server.register_generation(fleet)
    server.start()
    base = f"http://127.0.0.1:{server.port}"
    try:
        body = json.dumps({
            "prompt": prompts[0], "max_new_tokens": 8,
        }).encode()
        req = urllib.request.Request(
            f"{base}/v2/models/lm/generate", data=body,
            headers={"Content-Type": "application/json"},
        )
        with urllib.request.urlopen(req) as resp:
            print("   generate:", json.loads(resp.read())["tokens"])
        with urllib.request.urlopen(f"{base}/v2/fleet") as resp:
            fr = json.loads(resp.read())["models"]["lm"]
            print("   /v2/fleet replicas:",
                  [(r["id"], r["state"], r["load_score"]) for r in fr["replicas"]])
            print("   /v2/fleet failovers:", fr["failovers"],
                  "migrated:", fr["migrated_streams"],
                  "router:", fr["router_decisions"])
        with urllib.request.urlopen(f"{base}/metrics") as resp:
            fleet_lines = [
                line for line in resp.read().decode().splitlines()
                if ("fleet" in line or 'replica="' in line)
                and not line.startswith("#")
            ]
            print("   /metrics fleet families (sample):")
            for line in fleet_lines[:8]:
                print("     ", line)
    finally:
        server.stop()
    print("done.")


if __name__ == "__main__":
    main()
