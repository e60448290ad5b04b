"""Driver ``serve_joyai``: JoyAI-LLM-Flash (one chip's share: 20 of 40
layers, 16 of each layer's 256 routed experts) behind the same HTTP
server, loaded by the same child process, as the other serving drivers.

What is the same is theirs, imported and not copied: ``serve``'s
``warm``, ``start_loadgen``, ``finish_loadgen``, ``engine_snapshot``,
``sleep_until``, ``serve_lfm2``'s ``age_prefix_cache`` and ``host_tier``,
``serve_mellum2``'s ``pad_to``. What differs: the weights and the
configuration are ``reference/joyai.py``'s (bfloat16 weights made from
the seed; a ``DecoderConfig`` whose layers are latent attention, a dense
feed-forward then routed experts beside a shared one, of which this
engine holds a share), the reference that judges the served tokens is
that file's float32 one, ``ctx["model"]`` carries the sizes the readers
of the new layers need (``joyai_model.py``), the ``cache.latent`` section
of ``/v2/stats`` is sampled with the rest, and, traced, the latent
kernel's device seconds are read out of the trace under its own name
(``ctx["latent_kernels"]``: the harness reduces with the names it had).
"""
from __future__ import annotations

import time
from typing import Dict, List

import numpy as np

from benchmark import layer_metrics, spec, stats, trace_reduce, traffic
from benchmark.stalls import Watch
from benchmark.drivers.serve import (
    LOGGED, ZERO_COUNTERS, engine_snapshot, finish_loadgen, sent_of_listed, sleep_until, start_loadgen, warm,
)
from benchmark.drivers.serve_lfm2 import age_prefix_cache, host_tier
from benchmark.drivers.serve_mellum2 import pad_to
from benchmark.reference import joyai as reference

LATENT_KERNELS = ("paged_latent_attention",)


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
    )
    jax.block_until_ready((engine.cache.k, engine.cache.state))
    return params, cfg, engine


def model_sizes(cfg, engine) -> Dict:
    """What the readers of the kernel's and the step's rooflines need."""
    return {
        "num_layers": cfg.num_layers, "num_heads": cfg.num_heads, "hidden_size": cfg.hidden_size,
        "ff_size": cfg.ff_size, "moe_ff_size": cfg.moe_ff_size, "num_experts": cfg.num_experts,
        "experts_held": cfg.held_experts, "shared_experts": cfg.num_shared_experts,
        "experts_per_token": cfg.experts_per_token, "vocab_size": cfg.vocab_size,
        "q_lora_rank": cfg.q_lora_rank, "kv_lora_rank": cfg.kv_lora_rank, "qk_nope_head_dim": cfg.qk_nope_head_dim,
        "qk_rope_head_dim": cfg.qk_rope_head_dim, "v_head_dim": cfg.v_head_dim,
        "latent_layers": len(cfg.latent_layers), "expert_layers": len(cfg.expert_layers),
        "block_size": engine.cache_config.block_size,
        "cache_itemsize": engine.cache.k.dtype.itemsize, "weight_itemsize": cfg.dtype.size_bytes,
    }


def latent_kernels(rt, seconds: float):
    """The latent kernel's device seconds and calls over the traced part
    of the window, from the trace the harness is about to reduce."""
    files = sorted(rt.trace_dir.glob("plugins/profile/*/*.xplane.pb"))
    if not files:
        return None
    reduced = trace_reduce.reduce_trace(
        trace_reduce.read_xplane(str(files[-1])), LATENT_KERNELS, window_ns=(0.0, seconds * 1e9)
    )
    return {"kernel_s": reduced["kernel_s"], "kernel_calls": reduced["kernel_calls"]}


def run(cell: spec.Cell, rt, peaks) -> Dict:
    import jax

    from flexflow_tpu.serving import InferenceServer
    from flexflow_tpu.serving.generation import GenerationModel

    args, w = rt.args, cell.workload
    seconds, lead_in = float(args.seconds), float(w["lead_in_s"])
    t0 = time.monotonic()
    params, cfg, engine = build_engine(cell, args.seed)
    cc = engine.cache_config
    weight_bytes = sum(a.size * a.dtype.itemsize for a in jax.tree.leaves(params))
    rt.log(f"engine: {cfg.num_layers} latent L (rows of {cfg.latent_width} stored at {cc.row_shape[0]}; {cfg.num_heads} heads, "
           f"scores {cfg.qk_nope_head_dim}+{cfg.qk_rope_head_dim}, values {cfg.v_head_dim}; {len(cfg.expert_layers)} expert layers: "
           f"{cfg.held_experts} of {cfg.num_experts} x {cfg.moe_ff_size} held, top-{cfg.experts_per_token}, {cfg.num_shared_experts} "
           f"shared) / {cfg.hidden_size}, vocab {cfg.vocab_size}, {cfg.dtype.name}: weights {weight_bytes / 1e9:.2f} GB in "
           f"{time.monotonic() - t0:.1f}s; {engine.max_batch_slots} slots, buckets {engine.buckets}, latent cache {cc.num_blocks} x "
           f"{cc.block_size} x {cc.bytes_per_token} B = {cc.total_bytes / 2**30:.2f} GiB (k {engine.cache.k.shape}, v "
           f"{engine.cache.v.shape}); kernels {engine.attention_kernels}")

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
    watch = Watch(model.anatomy)
    with server:
        child, t0 = start_loadgen(f"http://127.0.0.1:{server.port}", sched, w, lead_in + seconds)
        try:
            t_open, t_close = t0 + lead_in, t0 + lead_in + seconds
            sleep_until(t_open)
            t_open_real = time.monotonic()
            stats_open, eng_open, tier_open = lm_stats(), engine_snapshot(engine), host_tier(engine)
            watch.open()
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
                    watch.sleep(now + 0.02, 0.02)
            else:
                watch.sleep(t_close)
            t_close_real = time.monotonic()
            watch.close()
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
    memory_peak = stats.memory_peak_bytes(jax.devices()[:1])
    records = gen["records"]

    ctx = {
        "cell": cell, "records": records, "window": (t_open, t_close),
        "setup_s": t_open_real - rt.t_start, "memory_peak_bytes": memory_peak,
        "trace_abs": (rt.trace_t0, rt.trace_t0 + traced_s) if args.trace else None,
        "traced_s": traced_s if args.trace else None, "stats_open": stats_open, "stats_close": stats_close,
        "stats_samples": samples, "engine_open": eng_open, "engine_close": eng_close,
        "slots": engine.max_batch_slots, "model": model_sizes(cfg, engine),
        "latent_kernels": latent_kernels(rt, traced_s) if args.trace else None,
    }
    due = stats.due_in_window(records, t_open, t_close)
    done = stats.completed_in_window(records, t_open, t_close)
    ok_due = stats.window_ok(ctx)
    attempted = len(due)
    rt.log(f"window {t_close_real - t_open_real:.3f}s: {len(due)} requests due ({len(ok_due)} ok), "
           f"{len(done)} completed inside ({len(done) / seconds:.2f}/s), {gen['undrained']} undrained; "
           f"{sent_of_listed(gen, sched)}; gaps: {len(stats.window_gaps_ms(ctx))}; memory peak {memory_peak}")
    # the same window by the token and not by the request (no metric: what served_tokens_per_s's spread is held against)
    emitted = sum(t_open <= t < t_close for r in records for t in r.get("token_times") or [])
    prefilled = [r["prompt_len"] for r in records if r.get("token_times") and t_open <= r["token_times"][0] < t_close]
    rt.log(f"by the token: {emitted} reply tokens emitted inside the window ({emitted / seconds:.2f}/s), {len(prefilled)} prompts "
           f"prefilled inside it ({sum(prefilled)} tokens, {sum(prefilled) / seconds:.2f}/s)")
    rt.log("at the client: " + ", ".join(
        f"{name} {value:.2f}" for name in LOGGED if (value := layer_metrics.read(name, ctx)) is not None
    ))
    ex, latent = stats_close.get("experts") or {}, (stats_close.get("cache") or {}).get("latent") or {}
    rt.log(f"inside: decode steps {eng_close['step_counts']['decode'] - eng_open['step_counts']['decode']}, "
           f"decode_step_ms {layer_metrics.read('decode_step_ms', ctx)}, pipeline {stats_close.get('pipeline')}, experts section "
           f"{ {k: ex.get(k) for k in ('decode_calls_total', 'prefill_calls_total', 'unrouted_here_total')} }, held tokens "
           f"{sum(ex.get('tokens_total') or [])}")
    rt.log(f"cache.latent: {latent}; kernels {stats_close.get('kernels')}")
    rt.log(f"host tier: {tier_close['swaps_out_total'] - tier_open['swaps_out_total']} blocks read out to it inside the "
           f"window of {tier_close['evicted_total'] - tier_open['evicted_total']} evicted; it held "
           f"{tier_open['host_bytes']} of {tier_open['host_budget_bytes']} bytes at the open, {tier_close['host_bytes']} at the close")
    for line in watch.report(records, t_open, t_close):
        rt.log(line)

    # --------------------------------------------------------- correct
    why = []
    bad = [r["id"] for r in records if not stats.request_ok(r)]
    if bad or gen["undrained"]:
        failed = [r for r in records if not stats.request_ok(r)]
        errs = sorted({str(r.get("error") or r.get("status"))[:120] for r in failed})
        when = [(round(r.get("sent", r["due"]) - t_open, 2), len(r.get("token_times") or [])) for r in failed]  # (sent, seconds after the open; tokens it got)
        why.append(f"{len(bad)} requests failed or were refused, {gen['undrained']} never drained: {errs[:3]}, sent and tokens got {when[:16]}")
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
    # one row a token a layer, at the stored width, and nothing else held for a position
    stored = len(cfg.latent_layers) * cc.row_shape[0] * engine.cache_config.dtype.size_bytes
    if latent.get("bytes_per_token") != stored or engine.cache_config.value_row_shape != (0,):
        why.append(f"the latent cache holds {latent.get('bytes_per_token')} B a token, not one row a layer ({stored} B)")
    body = ((stats_close.get("kernels") or {}).get("latent") or {}).get("body")
    if body != ("mxu" if jax.default_backend() == "tpu" else "reference"):
        why.append(f"the latent layers' decode call lowered to {body!r}")
    # a seeded sample of the requests COMPLETED INSIDE the window, every
    # served token judged given its prefix by the benchmark's float32
    # reference (benchmark/reference/joyai.py: the expanded form), logits
    # not tokens, held to the distance at which the same equations lie
    # from that reference when computed in the arithmetic the
    # configuration states (reference/lfm2.py::gap_ratio says why a ratio)
    good = [r for r in done if stats.request_ok(r)] or [r for r in records if stats.request_ok(r)]
    rs = np.random.RandomState(args.seed + 2)
    picked = [good[i] for i in rs.choice(len(good), size=min(int(w["reference_sample"]), len(good)), replace=False)]
    by_id = {r["id"]: r for r in requests}
    limit, request_limit = float(w["gap_ratio_limit"]), float(w["request_excess_limit"])
    ratio = worst = None
    if picked:
        t0 = time.monotonic()
        # the engine's caches are not needed any more: their room is the reference's
        engine.cache.k = engine.cache.v = None
        engine.cache.state = {}
        lay = reference.layout([by_id[r["id"]]["prompt"] for r in picked], [r["tokens"] for r in picked],
                               pad_to=pad_to(cell), max_new=int(cell.traffic["params"]["output"]["max"]))
        stated = reference.choices(params, cell.config, lay["tokens"], lay["at"], "bfloat16")
        judged = reference.judge(params, cell.config, lay["tokens"], lay["at"],
                                 {"program": lay["chosen"], "stated": stated}, lay["valid"])
        ratio = reference.gap_ratio(judged["program"], judged["stated"])
        by_request = reference.worst_request_excess(judged["program"], judged["stated"], lay["valid"])
        worst = by_request["excess"]
        read, ref = reference.reading(judged["program"]), reference.reading(judged["stated"])
        rt.log(f"reference: gap_ratio {ratio:.4f} (limit {limit}), the worst request's excess {worst:.4f} mean requests (limit "
               f"{request_limit}; its {by_request['tokens']} tokens: {by_request['own']:.5f} against {by_request['stated']:.5f}, the "
               f"mean request {by_request['mean_stated']:.5f}; the largest ratio of a request "
               f"{reference.worst_request_ratio(judged['program'], judged['stated'], lay['valid']):.3f}), "
               f"over {read['tokens']} greedy tokens of {len(picked)} requests: the served tokens lie {judged['program']['gap'].mean():.5f} "
               f"logits below the float32 reference's best in the mean ({read['off_argmax']} off its argmax), the stated "
               f"arithmetic's own choices {judged['stated']['gap'].mean():.5f} ({ref['off_argmax']}); median margin "
               f"{float(np.median(judged['program']['margin'])):.4f}; {read['near_ties']} positions at near-ties; "
               f"{time.monotonic() - t0:.1f}s")
        if len(picked) < int(w["reference_sample"]) or read["tokens"] < int(w["reference_tokens_least"]):
            why.append(f"the reference judged {read['tokens']} tokens of {len(picked)} requests: fewer than the cell asks")
    if ratio is None or not ratio <= limit:
        why.append(f"gap_ratio {ratio} over the limit {limit}")
    if worst is None or not worst <= request_limit:
        why.append(f"the worst request's excess {worst} over the limit {request_limit}")

    ctx["compared"] = {"gap_ratio": [ratio, limit], "worst_request_excess": [worst, request_limit]}
    ctx.update(correct=not why, why_incorrect=why, attempted=attempted, failed=attempted - len(ok_due))
    return ctx
