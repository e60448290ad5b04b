"""Driver ``serve``: the generation server behind HTTP, loaded by a
child process.

Builds the benchmark's weights (``reference.init_params``: one jitted
call from the seed) -> ``GenerationEngine`` -> ``GenerationModel`` -> ``InferenceServer`` from
the configuration and the cell's deployment settings, warms exactly the
prefill buckets the cell's schedule uses plus the decode program through
``engine.generate`` BEFORE the scheduler thread starts (a cold compile
on that thread outlasts the 30 s step watchdog), resets the engine, and
serves. Server and scheduler options stay at their defaults (overload
control on): a refused request is a failed request.

The window: the load generator (``benchmark/loadgen.py``, a child that
never imports JAX) starts the cell's traffic at ``t0``; after
``lead_in_s`` unmeasured seconds the window opens and the counters are
snapshot; after ``--seconds`` more it closes and they are snapshot
again; in-flight requests then drain. Latencies are over the requests
DUE inside the window; throughput counts what COMPLETED inside it.
"""
from __future__ import annotations

import json
import subprocess
import sys
import time
from typing import Dict, List

import numpy as np

from benchmark import layer_metrics, spec, stats, traffic
from benchmark.reference import decoder as reference

# the client-side metrics shown in the log of every run, judged or not
LOGGED = (
    "ttft_p50_ms", "ttft_p90_ms", "itl_p50_ms", "itl_p95_ms", "itl_p99_ms", "slow_gap_share",
    "served_tokens_per_s", "generator_lag_p99_ms",
)

# self-healing that must not run in a measured window: each of these
# means the numbers describe a recovery, not the steady system
ZERO_COUNTERS = (
    "recoveries", "step_retries", "replayed_tokens", "quarantined",
    "watchdog_trips", "engine_failures", "recompiles",
)


def build_engine(cell: spec.Cell, seed: int):
    import jax

    from flexflow_tpu.generation import GenerationEngine
    from flexflow_tpu.models.transformer import TransformerConfig

    c, d = cell.config, cell.workload["deployment"]
    cfg = TransformerConfig(
        num_layers=c["n_layer"], hidden_size=c["n_embd"], num_heads=c["n_head"],
        ff_size=c["n_inner"], seq_length=c["n_positions"], vocab_size=c["vocab_size"],
        causal=True,
    )
    # the weights: the benchmark's, on the device, from the seed, in one
    # jitted call, in the type they are served in (float32)
    params = reference.init_params(seed, c)
    engine = GenerationEngine(
        params, cfg, max_batch_slots=int(d["slots"]), block_size=int(d["block_size"]),
        prompt_buckets=list(d["prompt_buckets"]), max_seq_len=int(d["max_seq_len"]),
    )
    jax.block_until_ready(engine.cache.k)
    return params, cfg, engine


def warm(engine, requests: List[Dict], vocab: int, seed: int, log) -> None:
    """Compile (or load from the cache) the decode program and one
    prefill program per bucket that the schedule's prompts fall in."""
    from flexflow_tpu.generation.engine import SamplingParams

    rs = np.random.RandomState(seed + 1)
    buckets = sorted({engine.bucket_for(len(r["prompt"])) for r in requests})
    prompts = []
    for b in buckets:
        n = min(b, engine.max_seq_len - 4)
        if engine.bucket_for(n) != b:
            raise ValueError(f"no prompt length warms bucket {b} of {engine.buckets}")
        prompts.append([int(t) for t in rs.randint(0, vocab, size=n)])
    # ... and then more of the longest, until the prompts together
    # overflow the block pool: finished requests leave their blocks to
    # the prefix cache, and the programs that evict them (block reads to
    # the host tier) must have run before the window does
    n = len(prompts[-1])
    pool = engine.cache_config.num_blocks * engine.cache_config.block_size
    while sum(len(p) for p in prompts) <= pool + n:
        prompts.append([int(t) for t in rs.randint(0, vocab, size=n)])
    t0 = time.monotonic()
    engine.generate(prompts, SamplingParams(max_new_tokens=3))
    engine.reset()
    log(f"warmed decode + prefill{buckets} with {len(prompts)} prompts in {time.monotonic() - t0:.1f}s; "
        f"programs traced: {dict(engine.trace_counts)}; compile+first-run seconds: "
        f"{ {p['name']: round(p['compile_s'], 2) for p in engine.programs.snapshot() if p.get('compile_s')} }")


def engine_snapshot(engine) -> Dict:
    return {
        "phase_time_s": {k: dict(v) for k, v in engine.phase_time_s.items()},
        "step_counts": dict(engine.step_counts),
        "trace_counts": dict(engine.trace_counts),
    }


def sleep_until(t: float) -> None:
    while True:
        left = t - time.monotonic()
        if left <= 0:
            return
        time.sleep(min(left, 0.05))


def start_loadgen(url: str, sched: Dict, w: Dict, traffic_s: float) -> tuple:
    """Start the child on ``sched`` and return (process, t0): traffic
    starts at ``t0``, which leaves the child time to start and to parse
    the job; it hands out requests for ``traffic_s`` seconds."""
    job = {
        "url": url, "model": "lm", "mode": sched["mode"], "requests": sched["requests"],
        "clients": sched.get("clients", 0), "workers": int(w.get("workers", 64)),
        "stop_s": traffic_s, "drain_s": float(w["drain_s"]), "timeout_s": float(w["request_timeout_s"]),
    }
    child = subprocess.Popen(
        [sys.executable, str(spec.HERE / "loadgen.py")], stdin=subprocess.PIPE, stdout=subprocess.PIPE,
    )
    job["t0"] = time.monotonic() + 1.0 + 2e-6 * sum(len(r["prompt"]) for r in sched["requests"])
    child.stdin.write(json.dumps(job).encode())
    child.stdin.close()
    return child, job["t0"]


def finish_loadgen(child, w: Dict) -> Dict:
    """Wait for the child's drain and return what it recorded."""
    try:
        out = child.stdout.read()
        child.wait(timeout=float(w["drain_s"]) + float(w["request_timeout_s"]) + 60.0)
    finally:
        if child.poll() is None:
            child.kill()
            child.wait()
    if child.returncode != 0:
        raise RuntimeError(f"load generator exited {child.returncode}")
    gen = json.loads(out)
    gen["records"].sort(key=lambda r: r["id"])
    if gen["exhausted"]:
        raise RuntimeError(
            f"the closed loop ran out of requests: its list of {gen['listed']} lasted {gen['exhausted_after_s']:.1f} s of the "
            f"traffic's seconds. The list is sized by max_rate_per_s in the traffic file for a server about twice as fast as the "
            f"one it was sized on; a program that outruns it needs a `benchmark` issue that re-sizes the list (PERF.md, section 4)"
        )
    return gen


def sent_of_listed(gen: Dict, sched: Dict) -> str:
    """How much of its list a run used, for the window's log line: a
    closed loop draws from a list sized beforehand (``closed_clients``),
    and a run that comes near its end says so before one crosses it."""
    if sched["mode"] != "closed":
        return f"{gen['sent']} sent in all"
    return f"{gen['sent']} sent of {gen['listed']} listed ({100.0 * gen['sent'] / gen['listed']:.0f} %)"


def run(cell: spec.Cell, rt, peaks) -> Dict:
    import jax

    from flexflow_tpu.serving import InferenceServer
    from flexflow_tpu.serving.generation import GenerationModel

    args, w = rt.args, cell.workload
    seconds, lead_in = float(args.seconds), float(w["lead_in_s"])
    params, cfg, engine = build_engine(cell, args.seed)
    cc = engine.cache_config
    rt.log(f"engine: {cfg.num_layers} L / {cfg.hidden_size} / {cfg.num_heads} heads / {cfg.ff_size}, "
           f"vocab {cfg.vocab_size}, {engine.max_batch_slots} slots, buckets {engine.buckets}, "
           f"cache {cc.num_blocks} x {cc.block_size} = {cc.total_bytes / 2**30:.2f} GiB")

    sched = traffic.schedule(
        cell.traffic["generator"], args.seed, lead_in + seconds, cell.traffic["params"],
        {"vocab_size": cfg.vocab_size},
    )
    requests = sched["requests"]
    too_long = [r["id"] for r in requests if len(r["prompt"]) + r["max_new_tokens"] > engine.max_seq_len]
    if too_long:
        raise ValueError(f"requests {too_long[:5]} exceed max_seq_len {engine.max_seq_len}")
    warm(engine, requests, cfg.vocab_size, args.seed, rt.log)

    server = InferenceServer(port=0)
    model = GenerationModel(engine, name="lm")
    server.register_generation(model)
    lm_stats = lambda: server.stats()["generation"]["lm"]
    samples: List[Dict] = []
    with server:
        child, t0 = start_loadgen(f"http://127.0.0.1:{server.port}", sched, w, lead_in + seconds)
        try:
            t_open, t_close = t0 + lead_in, t0 + lead_in + seconds
            sleep_until(t_open)
            t_open_real = time.monotonic()
            stats_open, eng_open = lm_stats(), engine_snapshot(engine)
            if args.trace:
                # the trace covers the LAST trace_s seconds of the window
                # and is stopped (a stall of seconds) only after the
                # in-flight requests have drained
                trace_s = min(float(w["trace_s"]), seconds)
                next_sample = t_open
                while time.monotonic() < t_close:
                    now = time.monotonic()
                    if rt.trace_t0 is None and now >= t_close - trace_s:
                        rt.trace_start()
                    if now >= next_sample:  # once a second
                        samples.append(lm_stats())
                        next_sample += 1.0
                    time.sleep(0.02)
            else:
                sleep_until(t_close)
            t_close_real = time.monotonic()
            stats_close, eng_close = lm_stats(), engine_snapshot(engine)
            if args.trace:
                traced_s = t_close_real - rt.trace_t0
                deadline = t_close_real + 8.0
                while child.poll() is None and time.monotonic() < deadline:
                    time.sleep(0.05)
        except BaseException:
            child.kill()
            raise
        finally:
            rt.trace_stop()
        gen = finish_loadgen(child, w)
        stats_end = lm_stats()
        breaker = model.breaker.state
    memory_peak = stats.memory_peak_bytes(jax.devices()[:1])
    records = gen["records"]

    # ------------------------------------------------------- reduction
    # every number below is a reader's (benchmark/layer_metrics/): the
    # result line takes the same ones
    ctx = {
        "cell": cell, "records": records, "window": (t_open, t_close),
        "setup_s": t_open_real - rt.t_start, "memory_peak_bytes": memory_peak,
        "trace_abs": (rt.trace_t0, rt.trace_t0 + traced_s) if args.trace else None,
        "traced_s": traced_s if args.trace else None, "stats_open": stats_open, "stats_close": stats_close,
        "stats_samples": samples, "engine_open": eng_open, "engine_close": eng_close,
        "slots": engine.max_batch_slots,
        "model": {"num_layers": cfg.num_layers, "num_heads": cfg.num_heads,
                  "head_dim": cfg.hidden_size // cfg.num_heads, "cache_itemsize": 4},
    }
    due = stats.due_in_window(records, t_open, t_close)
    done = stats.completed_in_window(records, t_open, t_close)
    ok_due = stats.window_ok(ctx)
    attempted = len(due) + gen["undrained"] if sched["mode"] == "open" else len(due)
    n_gaps = len(stats.window_gaps_ms(ctx))
    rt.log(f"window {t_close_real - t_open_real:.3f}s: {len(due)} requests due ({len(ok_due)} ok), "
           f"{len(done)} completed inside ({len(done) / seconds:.2f}/s), {gen['undrained']} undrained; "
           f"{sent_of_listed(gen, sched)}")
    rt.log(f"first tokens: {len(ok_due)} ({stats.samples_beyond(len(ok_due), 90)} beyond p90); "
           f"gaps: {n_gaps} ({stats.samples_beyond(n_gaps, 95)} beyond p95, "
           f"{stats.samples_beyond(n_gaps, 99)} beyond p99)")
    rt.log("at the client: " + ", ".join(
        f"{name} {value:.2f}" for name in LOGGED if (value := layer_metrics.read(name, ctx)) is not None
    ))

    # --------------------------------------------------------- correct
    why = []
    bad = [r["id"] for r in records if not stats.request_ok(r)]
    if bad or gen["undrained"]:
        errs = sorted({str(r.get("error") or r.get("status"))[:120] for r in records if not stats.request_ok(r)})
        why.append(f"{len(bad)} requests failed or were refused, {gen['undrained']} never drained: {errs[:3]}")
    if any(not 0 <= t < cfg.vocab_size for r in records for t in r["tokens"]):
        why.append("a token outside the vocabulary")
    new_traces = {
        k: v - eng_open["trace_counts"].get(k, 0) for k, v in eng_close["trace_counts"].items()
        if v != eng_open["trace_counts"].get(k, 0)
    }
    if new_traces:
        why.append(f"programs traced inside the window: {new_traces}")
    n_compiles = rt.compiles_between(t_open_real, t_close_real)
    if n_compiles:
        why.append(f"{n_compiles} XLA compiles inside the window")
    healing = {k: stats_end[k] for k in ZERO_COUNTERS if stats_end.get(k)}
    if healing or breaker != "closed":
        why.append(f"self-healing ran: {healing}, breaker {breaker}")
    # a seeded sample of completed greedy requests, every served token
    # judged given its prefix by the benchmark's own float32 reference
    # (benchmark/reference/decoder.py: `reading` says what is compared
    # and why; the cell's file and PERF.md give the sound runs' and the
    # control's readings that the limit lies between)
    good = [r for r in records if stats.request_ok(r)]
    rs = np.random.RandomState(args.seed + 2)
    picked = [good[i] for i in rs.choice(len(good), size=min(int(w["reference_sample"]), len(good)), replace=False)]
    by_id = {r["id"]: r for r in requests}
    limit = float(w["near_tie_gap_limit"])
    if picked:
        t0 = time.monotonic()
        # a shape fixed by the mix, not by the seed's draw: one reference
        # program per cell in the compile cache
        lay = reference.layout([by_id[r["id"]]["prompt"] for r in picked], [r["tokens"] for r in picked],
                               pad_to=engine.max_seq_len, max_new=int(cell.traffic["params"]["output"]["max"]))
        read = reference.reading(reference.judge(params, lay["tokens"], lay["at"], lay["chosen"], lay["valid"]))
        rt.log(f"reference: near_tie_gap {read['near_tie_gap']:.3e} (limit {limit:.1e}) over {read['tokens']} greedy "
               f"tokens of {len(picked)} requests, {read['near_ties']} at near-ties of the reference, "
               f"{read['off_argmax']} off its argmax, the worst {read['worst_gap']:.6f} logits below; "
               f"{time.monotonic() - t0:.1f}s")
    if not picked or read["near_tie_gap"] > limit:
        why.append(f"near_tie_gap {read['near_tie_gap'] if picked else None} over the limit {limit}")
    # (what run.py prints last, in the result line and on standard error: each number compared beside its limit)
    ctx["compared"] = {"near_tie_gap": [read["near_tie_gap"] if picked else None, limit]}

    ctx.update(correct=not why, why_incorrect=why, attempted=attempted, failed=attempted - len(ok_due))
    return ctx
