"""Driver ``serve_lfm2``: LFM2-8B-A1B behind the same HTTP server, loaded
by the same child process, as driver ``serve`` runs GPT-2-medium.

What is the same is ``serve``'s, imported and not copied: ``warm``,
``start_loadgen``, ``finish_loadgen``, ``engine_snapshot``,
``sleep_until``, the window and every check of ``correct`` but the last.
What differs: the weights and the configuration are
``reference/lfm2.py``'s (bfloat16 weights made from the seed, a
``DecoderConfig`` whose layers are gated convolutions, grouped-query
attention and routed experts), the reference that judges the served
tokens is that file's float32 one, ``ctx["model"]`` carries the
sizes the readers of the new layers need (``moe_model.py``), and set-up
goes on after ``warm`` until the prefix cache is where this traffic
keeps it (``age_prefix_cache``).
"""
from __future__ import annotations

import time
from typing import Dict, List

import numpy as np

from benchmark import layer_metrics, spec, stats, traffic
from benchmark.drivers.serve import (
    LOGGED, ZERO_COUNTERS, engine_snapshot, finish_loadgen, sent_of_listed, sleep_until, start_loadgen, warm,
)
from benchmark.reference import lfm2 as reference


def build_engine(cell: spec.Cell, seed: int):
    import jax

    from flexflow_tpu.generation import GenerationEngine

    c, d = cell.config, cell.workload["deployment"]
    cfg = reference.engine_config(c, int(d["max_seq_len"]))
    # the weights: the benchmark's, on the device, from the seed, a
    # jitted call a layer, in the type they are served in
    params = reference.init_params(seed, c)
    if c.get("serving_dtype", "bfloat16") != "bfloat16":  # the rehearsal on the CPU
        params = reference.cast_params(params, cfg.dtype.jnp)
    engine = GenerationEngine(
        params, cfg, max_batch_slots=int(d["slots"]), block_size=int(d["block_size"]),
        prompt_buckets=list(d["prompt_buckets"]), max_seq_len=int(d["max_seq_len"]),
    )
    jax.block_until_ready((engine.cache.k, engine.cache.state))
    return params, cfg, engine


def model_sizes(cfg, engine) -> Dict:
    """What the readers of the kernels' and the step's rooflines need."""
    return {
        "num_layers": cfg.num_layers, "num_heads": cfg.num_heads, "kv_heads": cfg.kv_heads,
        "head_dim": cfg.dim_per_head, "hidden_size": cfg.hidden_size, "ff_size": cfg.ff_size,
        "moe_ff_size": cfg.moe_ff_size, "num_experts": cfg.num_experts, "experts_per_token": cfg.experts_per_token,
        "conv_kernel": cfg.conv_kernel, "vocab_size": cfg.vocab_size,
        "attention_layers": len(cfg.attention_layers), "conv_layers": len(cfg.conv_layers),
        "expert_layers": len(cfg.expert_layers), "dense_layers": cfg.num_layers - len(cfg.expert_layers),
        "cache_itemsize": engine.cache.k.dtype.itemsize, "weight_itemsize": cfg.dtype.size_bytes,
    }


def age_prefix_cache(engine, prompt_len: int, vocab: int, seed: int, log) -> None:
    """Bring the prefix cache to where this traffic leaves it for all
    but a deployment's first minutes: the block pool full of finished
    requests' blocks and the host tier at its budget. ``warm`` ends in a
    reset, so a server starts with both empty; unshared traffic then
    fills the pool, after that every eviction reads a block out to the
    host tier (and waits for the step in flight), and once the tier is
    at its budget ``PrefixCache.reclaim`` finds no room, reads nothing
    and drops its victims, from then on. At this cell's rates that point
    lies ~2 minutes after the load starts (the cell's file,
    ``lead_in_why``): a window before it measures a transient, one
    across it two regimes. So set-up serves unshared prompts of the
    traffic's longest length, in a shape ``warm`` has run, until the
    tier is full; what the window then evicts, it drops."""
    from flexflow_tpu.generation.engine import SamplingParams

    pc, cc = engine.prefix_cache, engine.cache_config
    if not pc.enabled or pc.host_budget_bytes < pc.bytes_per_block:
        return
    full = lambda: pc.host_bytes + pc.bytes_per_block > pc.host_budget_bytes  # noqa: E731
    rs = np.random.RandomState(seed + 3)
    per_prompt = max(1, prompt_len // cc.block_size)
    # the pool's blocks and the tier's, at a prompt's full blocks each: twice that is the cap
    cap = 2 * (cc.num_blocks + pc.host_budget_bytes // pc.bytes_per_block) // per_prompt + engine.max_batch_slots
    t0, served = time.monotonic(), 0
    while not full() and served < cap:
        prompts = [[int(t) for t in rs.randint(0, vocab, size=prompt_len)] for _ in range(engine.max_batch_slots)]
        engine.generate(prompts, SamplingParams(max_new_tokens=3))
        served += len(prompts)
    if not full():
        raise RuntimeError(f"{served} prompts of {prompt_len} tokens left the host tier at {pc.host_bytes} of "
                           f"{pc.host_budget_bytes} bytes: the window would not lie in the state the cell states")
    log(f"aged the prefix cache with {served} unshared prompts of {prompt_len} tokens in {time.monotonic() - t0:.1f}s: "
        f"{pc.resident_blocks} of {cc.num_blocks} blocks hold cached content, the host tier {pc.host_bytes} of "
        f"{pc.host_budget_bytes} bytes ({pc.swaps_out_total} blocks read out)")


def host_tier(engine) -> Dict:
    """The prefix cache's host tier as it stands (plain ints, no lock)."""
    pc = engine.prefix_cache
    return {"host_bytes": pc.host_bytes, "host_budget_bytes": pc.host_budget_bytes,
            "swaps_out_total": pc.swaps_out_total, "evicted_total": pc.evicted_total}


def run(cell: spec.Cell, rt, peaks) -> Dict:
    import jax

    from flexflow_tpu.serving import InferenceServer
    from flexflow_tpu.serving.generation import GenerationModel

    args, w = rt.args, cell.workload
    seconds, lead_in = float(args.seconds), float(w["lead_in_s"])
    t0 = time.monotonic()
    params, cfg, engine = build_engine(cell, args.seed)
    cc, sc = engine.cache_config, engine.state_config
    weight_bytes = sum(a.size * a.dtype.itemsize for a in jax.tree.leaves(params))
    rt.log(f"engine: {cfg.num_layers} L ({len(cfg.conv_layers)} conv + {len(cfg.attention_layers)} attention; "
           f"{len(cfg.expert_layers)} expert layers of {cfg.num_experts} x {cfg.moe_ff_size} top-{cfg.experts_per_token}) / "
           f"{cfg.hidden_size} / {cfg.num_heads} over {cfg.kv_heads} heads of {cfg.dim_per_head}, vocab {cfg.vocab_size}, "
           f"{cfg.dtype.name}: weights {weight_bytes / 1e9:.2f} GB in {time.monotonic() - t0:.1f}s; "
           f"{engine.max_batch_slots} slots, buckets {engine.buckets}, K/V {cc.num_blocks} x {cc.block_size} = "
           f"{cc.total_bytes / 2**30:.2f} GiB, convolution state {sc.total_bytes(cc.num_blocks) / 2**30:.2f} GiB")

    sched = traffic.schedule(
        cell.traffic["generator"], args.seed, lead_in + seconds, cell.traffic["params"],
        {"vocab_size": cfg.vocab_size},
    )
    requests = sched["requests"]
    too_long = [r["id"] for r in requests if len(r["prompt"]) + r["max_new_tokens"] > engine.max_seq_len]
    if too_long:
        raise ValueError(f"requests {too_long[:5]} exceed max_seq_len {engine.max_seq_len}")
    warm(engine, requests, cfg.vocab_size, args.seed, rt.log)
    age_prefix_cache(engine, int(cell.traffic["params"]["prompt"]["max"]), cfg.vocab_size, args.seed, rt.log)

    server = InferenceServer(port=0)
    model = GenerationModel(engine, name="lm")
    server.register_generation(model)
    lm_stats = lambda: server.stats()["generation"]["lm"]  # noqa: E731
    samples: List[Dict] = []
    with server:
        child, t0 = start_loadgen(f"http://127.0.0.1:{server.port}", sched, w, lead_in + seconds)
        try:
            t_open, t_close = t0 + lead_in, t0 + lead_in + seconds
            sleep_until(t_open)
            t_open_real = time.monotonic()
            stats_open, eng_open, tier_open = lm_stats(), engine_snapshot(engine), host_tier(engine)
            if args.trace:
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
            stats_close, eng_close, tier_close = lm_stats(), engine_snapshot(engine), host_tier(engine)
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
        try:
            gen = finish_loadgen(child, w)
        except RuntimeError:
            # (the closed loop ran out of requests: they were failing as
            # fast as they were sent. What the server says it did to them)
            rt.log(f"server counters: { {k: v for k, v in lm_stats().items() if isinstance(v, (int, float, str)) and v} }")
            raise
        stats_end = lm_stats()
        breaker = model.breaker.state
    memory_peak = stats.memory_peak_bytes(jax.devices()[:1])
    records = gen["records"]

    ctx = {
        "cell": cell, "records": records, "window": (t_open, t_close),
        "setup_s": t_open_real - rt.t_start, "memory_peak_bytes": memory_peak,
        "trace_abs": (rt.trace_t0, rt.trace_t0 + traced_s) if args.trace else None,
        "traced_s": traced_s if args.trace else None, "stats_open": stats_open, "stats_close": stats_close,
        "stats_samples": samples, "engine_open": eng_open, "engine_close": eng_close,
        "slots": engine.max_batch_slots, "model": model_sizes(cfg, engine),
    }
    due = stats.due_in_window(records, t_open, t_close)
    done = stats.completed_in_window(records, t_open, t_close)
    ok_due = stats.window_ok(ctx)
    attempted = len(due)
    n_gaps = len(stats.window_gaps_ms(ctx))
    rt.log(f"window {t_close_real - t_open_real:.3f}s: {len(due)} requests due ({len(ok_due)} ok), "
           f"{len(done)} completed inside ({len(done) / seconds:.2f}/s), {gen['undrained']} undrained; "
           f"{sent_of_listed(gen, sched)}; gaps: {n_gaps}")
    rt.log("at the client: " + ", ".join(
        f"{name} {value:.2f}" for name in LOGGED if (value := layer_metrics.read(name, ctx)) is not None
    ))
    ex = stats_close.get("experts") or {}
    rt.log(f"inside: decode steps {eng_close['step_counts']['decode'] - eng_open['step_counts']['decode']}, "
           f"decode_step_ms {layer_metrics.read('decode_step_ms', ctx)}, experts section "
           f"{ {k: ex.get(k) for k in ('decode_calls_total', 'prefill_calls_total')} }, "
           f"conv_state {stats_close.get('conv_state')}")
    # the window is meant to lie after the host tier's fill
    # (age_prefix_cache): blocks read out to it inside the window
    rt.log(f"host tier: {tier_close['swaps_out_total'] - tier_open['swaps_out_total']} blocks read out to it inside the "
           f"window of {tier_close['evicted_total'] - tier_open['evicted_total']} evicted; it held "
           f"{tier_open['host_bytes']} of {tier_open['host_budget_bytes']} bytes at the open, {tier_close['host_bytes']} at the close")

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
    # a seeded sample of the requests COMPLETED INSIDE the window (what
    # the timed window itself served), every served token judged given
    # its prefix by the benchmark's own float32 reference
    # (benchmark/reference/lfm2.py), logits not tokens, and held to the
    # distance at which the same equations lie from that reference when
    # computed in the arithmetic the configuration states (`gap_ratio`
    # says why; the cell's file gives the readings the limit lies between)
    good = [r for r in done if stats.request_ok(r)] or [r for r in records if stats.request_ok(r)]
    rs = np.random.RandomState(args.seed + 2)
    picked = [good[i] for i in rs.choice(len(good), size=min(int(w["reference_sample"]), len(good)), replace=False)]
    by_id = {r["id"]: r for r in requests}
    limit, request_limit = float(w["gap_ratio_limit"]), float(w["request_gap_ratio_limit"])
    ratio = worst = None
    if picked:
        t0 = time.monotonic()
        # the engine's caches are not needed any more: their room is the reference's
        engine.cache.k = engine.cache.v = None
        engine.cache.state = {}
        lay = reference.layout([by_id[r["id"]]["prompt"] for r in picked], [r["tokens"] for r in picked],
                               pad_to=engine.max_seq_len, max_new=int(cell.traffic["params"]["output"]["max"]))
        stated = reference.choices(params, cell.config, lay["tokens"], lay["at"], "bfloat16")
        judged = reference.judge(params, cell.config, lay["tokens"], lay["at"],
                                 {"program": lay["chosen"], "stated": stated}, lay["valid"])
        ratio = reference.gap_ratio(judged["program"], judged["stated"])
        # the same ratio request by request, the largest: one garbled stream
        worst = reference.worst_request_ratio(judged["program"], judged["stated"], lay["valid"])
        read, ref = reference.reading(judged["program"]), reference.reading(judged["stated"])
        rt.log(f"reference: gap_ratio {ratio:.4f} (limit {limit}), the worst request's {worst:.4f} (limit {request_limit}), "
               f"over {read['tokens']} greedy tokens of {len(picked)} requests: the served tokens lie {judged['program']['gap'].mean():.4f} logits below the float32 "
               f"reference's best in the mean ({read['off_argmax']} off its argmax, near_tie_gap {read['near_tie_gap']:.3e}), "
               f"the stated arithmetic's own choices {judged['stated']['gap'].mean():.4f} ({ref['off_argmax']}, "
               f"{ref['near_tie_gap']:.3e}); {read['near_ties']} positions at near-ties; {time.monotonic() - t0:.1f}s")
    if ratio is None or not ratio <= limit:
        why.append(f"gap_ratio {ratio} over the limit {limit}")
    if worst is None or not worst <= request_limit:
        why.append(f"the worst request's gap_ratio {worst} over the limit {request_limit}")

    ctx["compared"] = {"gap_ratio": [ratio, limit], "worst_request_gap_ratio": [worst, request_limit]}
    ctx.update(correct=not why, why_incorrect=why, attempted=attempted, failed=attempted - len(ok_due))
    return ctx
