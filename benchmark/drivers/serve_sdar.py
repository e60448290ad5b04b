"""Driver ``serve_sdar``: SDAR-30B-A3B-Chat, generated by block
diffusion, behind the same HTTP server, loaded by the same child
process, as the other serving drivers run their models.

What is the same is theirs, imported and not copied: ``serve``'s
``warm``, ``start_loadgen``, ``finish_loadgen``, ``engine_snapshot``,
``sleep_until``, and ``serve_lfm2``'s ``age_prefix_cache`` and
``host_tier``. What differs: the weights, the configuration and the
generation rule are ``reference/sdar.py``'s (bfloat16 weights made from
the seed; a ``DecoderConfig`` with ``block_mask`` 4 and the engine's
``BlockDiffusion`` rule at the cell's steps), ``ctx["model"]`` carries
the sizes ``sdar_model.py``'s readers need, the ``diffusion`` section of
``/v2/stats`` is sampled with the rest, and ``correct`` also holds the
program to the rule's own identities (every block run whole and
committed exactly once, the tokens fixed those that left plus those the
budgets cut) and judges a seeded sample of requests ALONG THEIR OWN
TRAJECTORIES: each is served once more in this process, non-streamed,
for the forward that fixed every token (``fixed_at``: greedy, so the
tokens have to be the served ones again), and every denoising state is
then scored by the float32 reference (``reference/sdar.py::judge``,
``pooled_gap_ratio``).
"""
from __future__ import annotations

import time
from typing import Dict, List

import numpy as np

from benchmark import layer_metrics, spec, stats, traffic
from benchmark.drivers.serve import (
    LOGGED, ZERO_COUNTERS, engine_snapshot, finish_loadgen, sent_of_listed, sleep_until, start_loadgen, warm,
)
from benchmark.drivers.serve_lfm2 import age_prefix_cache, host_tier
from benchmark.reference import sdar as reference


def build_engine(cell: spec.Cell, seed: int):
    import jax

    from flexflow_tpu.generation import GenerationEngine

    c, d = cell.config, cell.workload["deployment"]
    cfg = reference.engine_config(c, int(d["max_seq_len"]))
    params = reference.init_params(seed, c)
    if c.get("serving_dtype", "bfloat16") != "bfloat16":  # the rehearsal on the CPU
        params = reference.cast_params(params, cfg.dtype.jnp)
    engine = GenerationEngine(
        params, cfg, max_batch_slots=int(d["slots"]), block_size=int(d["block_size"]),
        prompt_buckets=list(d["prompt_buckets"]), max_seq_len=int(d["max_seq_len"]),
        diffusion=reference.diffusion_rule(c, denoising_steps=int(d["denoising_steps"]), remasking=d["remasking"]),
    )
    jax.block_until_ready(engine.cache.k)
    return params, cfg, engine


def model_sizes(cfg, engine) -> Dict:
    """What the readers of the step's and the paged call's rooflines need."""
    return {
        "num_layers": cfg.num_layers, "num_heads": cfg.num_heads, "kv_heads": cfg.kv_heads,
        "head_dim": cfg.dim_per_head, "hidden_size": cfg.hidden_size, "moe_ff_size": cfg.moe_ff_size,
        "num_experts": cfg.num_experts, "experts_per_token": cfg.experts_per_token, "vocab_size": cfg.vocab_size,
        "block_length": engine.diffusion.block_length, "block_size": engine.cache_config.block_size,
        "cache_itemsize": engine.cache.k.dtype.itemsize, "weight_itemsize": cfg.dtype.size_bytes,
    }


def judged_shape(cell: spec.Cell, block: int, steps: int):
    """(the length the reference runs a judged request at, the denoising
    states it has room for): fixed by the traffic's longest prompt and
    reply, so that one compiled reference serves every run."""
    p = cell.traffic["params"]
    pad_to = -(-(int(p["prompt"]["max"]) + int(p["output"]["max"])) // block) * block
    states = (int(p["output"]["max"]) // block + 2) * steps
    return pad_to, -(-states // reference.STATES) * reference.STATES


def whole_blocks(prompt_len: int, reply_len: int, block: int):
    """(blocks a request's generation runs, tokens its budget cuts from
    the last one): every block is run whole."""
    end = -(-(prompt_len + reply_len) // block) * block
    return (end - prompt_len // block * block) // block, end - prompt_len - reply_len


def replay(model, requests: List[Dict], timeout_s: float):
    """Serve ``requests`` once more, in this process and non-streamed:
    their tokens and the forward that fixed each. The window's prompts
    were unshared and prefilled whole; the same prompt sent again would
    hit its own blocks in the prefix index and take the suffix prefill,
    another program with other roundings. So every unreferenced cached
    block is evicted first (all requests have drained; the host tier is
    at its budget since set-up, so what is evicted is dropped), and a
    replay that hit the index all the same is reported (third result)."""
    from flexflow_tpu.generation.engine import SamplingParams

    engine = model.engine
    engine.reclaim_cached(engine.cache_config.num_blocks)
    hits = engine.prefix_cache.hits
    handles = [model.submit(r["prompt"], SamplingParams(max_new_tokens=int(r["max_new_tokens"]))) for r in requests]
    served = [(h.result(timeout=timeout_s), list(h._request.fixed_at)) for h in handles]
    return served, engine.prefix_cache.hits - hits, sum(h._request.preemptions for h in handles)


def judge_sample(params, cell: spec.Cell, engine, picked: List[Dict], replayed, log) -> Dict:
    """The sample's requests along their own trajectories, pooled: the
    program's and the stated arithmetic's readings, and the two ratios."""
    d = engine.diffusion
    n_fix, threshold = d.rows_per_forward(), d.threshold_of()
    pad_to, states = judged_shape(cell, d.block_length, d.denoising_steps)
    pooled = {arm: {"gap": [], "row_gap": []} for arm in ("program", "stated")}
    for request, (tokens, fixed_at) in zip(picked, replayed):
        lay = reference.trajectory(request["prompt"], tokens, fixed_at, d.block_length, pad_to, states)
        stated = reference.choices(params, cell.config, lay, n_fix, threshold, "bfloat16")
        judged = reference.judge(
            params, cell.config, lay, {"program": {"chosen": lay["chosen"], "picked": lay["picked"]}, "stated": stated},
            n_fix, threshold,
        )
        for arm, parts in judged.items():
            for key, values in parts.items():
                pooled[arm][key].append(values)
    pooled = {arm: {k: np.concatenate(v) for k, v in parts.items()} for arm, parts in pooled.items()}
    out = {
        "gap_ratio": reference.pooled_gap_ratio(pooled["program"], pooled["stated"]),
        "token_gap_ratio": reference.gap_ratio(pooled["program"], pooled["stated"]),
        "row_gap_ratio": reference.row_gap_ratio(pooled["program"], pooled["stated"]),
        "program": reference.reading(pooled["program"]), "stated": reference.reading(pooled["stated"]),
    }
    log(f"reference: gap_ratio {out['gap_ratio']:.4f} (the tokens' part alone {out['token_gap_ratio']:.4f}, the rows' "
        f"{out['row_gap_ratio']:.4f}) over {out['program']['rows']} fixed rows "
        f"of {len(picked)} requests: the program's tokens lie {out['program']['mean_gap']:.6f} logits below the float32 "
        f"reference's best candidate in the mean ({out['program']['off_argmax']} off it), the stated arithmetic's own "
        f"{out['stated']['mean_gap']:.6f} ({out['stated']['off_argmax']}); rows short of the reference's least confidence: "
        f"{out['program']['off_rows']} by {out['program']['mean_row_gap']:.2e} in the mean, the stated arithmetic's "
        f"{out['stated']['off_rows']} by {out['stated']['mean_row_gap']:.2e}")
    return out


def run(cell: spec.Cell, rt, peaks) -> Dict:
    import jax

    from flexflow_tpu.serving import InferenceServer
    from flexflow_tpu.serving.generation import GenerationModel

    args, w = rt.args, cell.workload
    seconds, lead_in = float(args.seconds), float(w["lead_in_s"])
    t0 = time.monotonic()
    params, cfg, engine = build_engine(cell, args.seed)
    cc, rule = engine.cache_config, engine.diffusion
    weight_bytes = sum(a.size * a.dtype.itemsize for a in jax.tree.leaves(params))
    rt.log(f"engine: {cfg.num_layers} L ({cfg.num_experts} x {cfg.moe_ff_size} experts top-{cfg.experts_per_token} in each) / "
           f"{cfg.hidden_size} / {cfg.num_heads} over {cfg.kv_heads} heads of {cfg.dim_per_head}, vocab {cfg.vocab_size}, "
           f"{cfg.dtype.name}: weights {weight_bytes / 1e9:.2f} GB in {time.monotonic() - t0:.1f}s; block diffusion over blocks of "
           f"{rule.block_length}, {rule.denoising_steps} steps, {rule.remasking}; {engine.max_batch_slots} slots, buckets "
           f"{engine.buckets}, cache {cc.num_blocks} x {cc.block_size} = {cc.total_bytes / 2**30:.2f} GiB; kernels "
           f"{engine.kernel_stats()}, experts {engine.expert_lowerings()}")

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
    by_id = {r["id"]: r for r in requests}
    with server:
        stats_start = lm_stats()
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
            rt.log(f"server counters: { {k: v for k, v in lm_stats().items() if isinstance(v, (int, float, str)) and v} }")
            raise
        stats_end = lm_stats()
        breaker = model.breaker.state
        records = gen["records"]
        # the judged sample, served once more here for its trajectories (module docstring)
        done = stats.completed_in_window(records, t_open, t_close)
        good = [r for r in done if stats.request_ok(r)] or [r for r in records if stats.request_ok(r)]
        rs = np.random.RandomState(args.seed + 2)
        picked = [good[i] for i in rs.choice(len(good), size=min(int(w["reference_sample"]), len(good)), replace=False)]
        t0 = time.monotonic()
        replayed, replay_hits, replay_preemptions = replay(model, [by_id[r["id"]] for r in picked], float(w["request_timeout_s"]))
        rt.log(f"served the {len(picked)} judged requests once more in {time.monotonic() - t0:.1f}s "
               f"({replay_hits} prefix hits, {replay_preemptions} preemptions)")
    memory_peak = stats.memory_peak_bytes(jax.devices()[:1])

    ctx = {
        "cell": cell, "records": records, "window": (t_open, t_close),
        "setup_s": t_open_real - rt.t_start, "memory_peak_bytes": memory_peak,
        "trace_abs": (rt.trace_t0, rt.trace_t0 + traced_s) if args.trace else None,
        "traced_s": traced_s if args.trace else None, "stats_open": stats_open, "stats_close": stats_close,
        "stats_samples": samples, "engine_open": eng_open, "engine_close": eng_close,
        "slots": engine.max_batch_slots, "model": model_sizes(cfg, engine),
    }
    due = stats.due_in_window(records, t_open, t_close)
    ok_due = stats.window_ok(ctx)
    attempted = len(due)
    rt.log(f"window {t_close_real - t_open_real:.3f}s: {len(due)} requests due ({len(ok_due)} ok), "
           f"{len(done)} completed inside ({len(done) / seconds:.2f}/s), {gen['undrained']} undrained; "
           f"{sent_of_listed(gen, sched)}; gaps: {len(stats.window_gaps_ms(ctx))}")
    emitted = sum(t_open <= t < t_close for r in records for t in r.get("token_times") or [])
    prefilled = [r["prompt_len"] for r in records if r.get("token_times") and t_open <= r["token_times"][0] < t_close]
    rt.log(f"by the token: {emitted} reply tokens emitted inside the window ({emitted / seconds:.2f}/s), {len(prefilled)} prompts "
           f"prefilled inside it ({sum(prefilled)} tokens, {sum(prefilled) / seconds:.2f}/s)")
    rt.log("at the client: " + ", ".join(
        f"{name} {value:.2f}" for name in LOGGED if (value := layer_metrics.read(name, ctx)) is not None
    ))
    dif = {k: v - stats_open["diffusion"][k] for k, v in stats_close["diffusion"].items() if k.endswith("_total")}
    rt.log(f"inside: block steps {eng_close['step_counts']['block_step'] - eng_open['step_counts']['block_step']}, "
           f"block_step_ms {layer_metrics.read('block_step_ms', ctx)}, diffusion over the window {dif}, tokens a forward "
           f"{layer_metrics.read('tokens_per_forward', ctx)}, commit share {layer_metrics.read('commit_forward_share', ctx)}, "
           f"forwards by tokens fixed {stats_close['diffusion']['fixed_per_forward_histogram']}, uploads "
           f"{ {k: v - (stats_open.get('uploads') or {}).get(k, 0) for k, v in (stats_close.get('uploads') or {}).items()} }")
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
    if any(t == rule.mask_token_id for r in records for t in r["tokens"]):
        why.append("the mask token was emitted")
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
    # the rule's identities over everything the server served (the load generator's requests, all drained):
    # every block run whole and committed exactly once, the tokens fixed those that left plus those a budget cut
    if not bad and not gen["undrained"]:
        grew = {k: stats_end["diffusion"][k] - stats_start["diffusion"][k] for k in dif}
        runs = [whole_blocks(r["prompt_len"], len(r["tokens"]), rule.block_length) for r in records]
        blocks, cut = sum(b for b, _ in runs), sum(c for _, c in runs)
        left = sum(len(r["tokens"]) for r in records)
        if grew["tokens_fixed_total"] != left + cut:
            why.append(f"{grew['tokens_fixed_total']} tokens fixed, {left} left and the budgets cut {cut}")
        if grew["blocks_committed_total"] != blocks or grew["commit_forwards_total"] != blocks:
            why.append(f"{blocks} blocks were run: {grew['blocks_committed_total']} committed in {grew['commit_forwards_total']} commit forwards")
        rt.log(f"identities: {grew}; {len(records)} requests ran {blocks} blocks, {left} tokens left and {cut} were cut")
    limit = float(w["gap_ratio_limit"])
    read = None
    differ = [r["id"] for r, (tokens, _) in zip(picked, replayed) if list(tokens) != list(r["tokens"])]
    if differ:
        why.append(f"requests {differ[:5]} served once more gave other tokens ({replay_hits} prefix hits and {replay_preemptions} "
                   f"preemptions among the {len(picked)}: with neither, a slot's stream depends on its neighbours)")
    elif picked:
        t0 = time.monotonic()
        # the engine's cache is not needed any more: its room is the reference's
        engine.cache.k = engine.cache.v = None
        read = judge_sample(params, cell, engine, [by_id[r["id"]] for r in picked], replayed, rt.log)
        rt.log(f"judged in {time.monotonic() - t0:.1f}s (limit {limit})")
        if len(picked) < int(w["reference_sample"]) or read["program"]["rows"] < int(w["reference_tokens_least"]):
            why.append(f"the reference judged {read['program']['rows']} rows of {len(picked)} requests: fewer than the cell asks")
    if read is None or not read["gap_ratio"] <= limit:
        why.append(f"gap_ratio {read and read['gap_ratio']} over the limit {limit}")

    ctx["compared"] = {"gap_ratio": [read and read["gap_ratio"], limit]}
    ctx.update(correct=not why, why_incorrect=why, attempted=attempted, failed=attempted - len(ok_due))
    return ctx
