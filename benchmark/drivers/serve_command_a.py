"""Driver ``serve_command_a``: Command A+ (one chip's share: 4 of 32
layers, 16 of each layer's 128 routed experts, an eighth of the
vocabulary) behind the same HTTP server, loaded by the same child process,
as the other serving drivers.

What is the same is theirs, imported and not copied: ``serve``'s ``warm``,
``start_loadgen``, ``finish_loadgen``, ``engine_snapshot``,
``sleep_until``, ``serve_lfm2``'s ``age_prefix_cache`` and ``host_tier``,
``serve_mellum2``'s ``pad_to``, ``stalls.Watch``. What differs: the
weights and the configuration are ``reference/command_a_plus.py``'s
(bfloat16 weights made from the seed; a ``DecoderConfig`` whose block is
parallel, with window and full layers over grouped queries, a held share
of the experts beside averaged shared ones, a tied head over the
vocabulary's slice), the reference that judges the served tokens is that
file's float32 one, ``ctx["model"]`` carries the sizes the readers of the
new layers need (``command_a_model.py``), the ``cache`` and
``prefill_attention`` sections of ``/v2/stats`` are sampled with the rest,
and, traced, the kernels' device seconds are read out of the trace under
their own names: the two paged calls (``ctx["paged_kernels"]``) and, run by
run of the prefill program, the streamed prefill's (``ctx["prefill_runs"]``;
the harness reduces with the kernel names it had).
"""
from __future__ import annotations

import time
from typing import Dict, List, Optional

import numpy as np

from benchmark import layer_metrics, spec, stats, trace_reduce, traffic
from benchmark.drivers.serve import (
    LOGGED, ZERO_COUNTERS, engine_snapshot, finish_loadgen, sent_of_listed, sleep_until, start_loadgen, warm,
)
from benchmark.drivers.serve_lfm2 import age_prefix_cache, host_tier
from benchmark.drivers.serve_mellum2 import pad_to
from benchmark.reference import command_a_plus as reference
from benchmark.stalls import Watch

PAGED_KERNELS = ("paged_window_attention", "paged_window_attention_split", "paged_append_attention", "paged_append_attention_split")
PREFILL_KERNEL = "prefill_stream_attention"


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
    """What the readers of the kernels' and the step's rooflines need."""
    return {
        "num_layers": cfg.num_layers, "num_heads": cfg.num_heads, "kv_heads": cfg.kv_heads,
        "head_dim": cfg.dim_per_head, "hidden_size": cfg.hidden_size, "moe_ff_size": cfg.moe_ff_size,
        "num_experts": cfg.num_experts, "experts_held": cfg.held_experts, "shared_experts": cfg.num_shared_experts,
        "experts_per_token": cfg.experts_per_token, "vocab_size": cfg.vocab_size,
        "full_layers": len(cfg.full_layers), "window_layers": len(cfg.window_layers), "window": cfg.window,
        "parallel_block": cfg.block == "parallel", "block_size": engine.cache_config.block_size,
        "cache_itemsize": engine.cache.k.dtype.itemsize, "weight_itemsize": cfg.dtype.size_bytes,
    }


def _trace(rt) -> Optional[trace_reduce.Trace]:
    files = sorted(rt.trace_dir.glob("plugins/profile/*/*.xplane.pb"))
    return trace_reduce.read_xplane(str(files[-1])) if files else None


def paged_kernels(trace, seconds: float):
    """The paged kernels' device seconds and calls over the traced part of
    the window, each under its own name."""
    reduced = trace_reduce.reduce_trace(trace, PAGED_KERNELS, window_ns=(0.0, seconds * 1e9))
    return {"kernel_s": reduced["kernel_s"], "kernel_calls": reduced["kernel_calls"]}


def prefill_runs(trace, seconds: float, records: List[Dict], trace_t0: float) -> List[Dict]:
    """The runs of the prefill program that lie wholly inside the traced
    part of the window, each with the device seconds of its streamed
    attention kernel's events, its own device seconds and the prompt it
    prefilled: the runs in order are the first tokens in order (an
    admission's prefill ends at its first token), matched from the last
    backwards, so that a run cut by the trace's start costs only itself."""
    if not trace.devices:
        return []
    dev, hi = trace.devices[0], seconds * 1e9
    runs = sorted((a, b) for n, a, b in dev.modules if "prefill_impl" in n and "prefix" not in n and a >= 0.0 and b <= hi)
    events = [(a, b) for n, a, b in dev.ops if PREFILL_KERNEL in trace_reduce.op_name(n)]
    firsts = sorted(
        (r["token_times"][0] - trace_t0, r["prompt_len"]) for r in records
        if r.get("token_times") and 0.0 <= r["token_times"][0] - trace_t0 < seconds + 1.0
    )
    out = []
    while runs and firsts:
        (a, b), (t, prompt_len) = runs[-1], firsts[-1]
        if t < b / 1e9 - 0.05:  # a first token from before this run ended: the run's own came after the trace
            runs.pop()
            continue
        runs.pop()
        firsts.pop()
        inside = [e1 - e0 for e0, e1 in events if a <= e0 and e1 <= b]
        out.append({"prompt_len": int(prompt_len), "kernel_s": sum(inside) / 1e9, "kernel_calls": len(inside),
                    "program_s": (b - a) / 1e9, "first_token_after_s": t - b / 1e9})
    return out[::-1]


# Arms beside the program's and the stated arithmetic's, for
# ``tools/command_a_check.py control`` alone (a run has none): a control of
# ``reference.CONTROLS`` -> over how many of the judged requests its choices
# are computed (None: all of them)
CONTROL_ARMS: Dict[str, Optional[int]] = {}


def judge_sample(params, cell: spec.Cell, prompts, streams, controls: Dict[str, Optional[int]]) -> Dict:
    """The served ``streams`` after their ``prompts``, the stated
    arithmetic's choices after the same prefixes and each control's, all
    judged by the float32 reference in one pass over its logits:
    ``judged[arm]`` flat over the ``valid`` tokens, ``of`` the request of
    each. A control computed over its first ``n`` requests alone holds the
    stated arithmetic's choices in the others: :func:`arm_of` cuts it out."""
    lay = reference.layout(prompts, streams, pad_to=pad_to(cell), max_new=int(cell.traffic["params"]["output"]["max"]))
    arms = {"program": lay["chosen"], "stated": reference.choices(params, cell.config, lay["tokens"], lay["at"], "bfloat16")}
    for name, n in controls.items():
        rows = slice(0, len(prompts) if n is None else n)
        arms[name] = arms["stated"].copy()
        arms[name][rows] = reference.choices(params, cell.config, lay["tokens"][rows], lay["at"][rows], name)
    judged = reference.judge(params, cell.config, lay["tokens"], lay["at"], arms, lay["valid"])
    return {"judged": judged, "valid": lay["valid"], "of": np.nonzero(np.asarray(lay["valid"]))[0]}


def arm_of(sample: Dict, name: str, n: Optional[int]):
    """``(judged arm, judged stated, valid)`` over the first ``n`` requests
    of a judged sample: what :func:`verdict` takes."""
    keep = slice(None) if n is None else sample["of"] < n
    cut = lambda arm: {k: v[keep] for k, v in sample["judged"][arm].items()}  # noqa: E731
    return cut(name), cut("stated"), sample["valid"][:n]


def verdict(judged: Dict, stated: Dict, valid, w: Dict):
    """THE comparison that decides ``correct`` for an arm's judged tokens
    against the stated arithmetic's after the same prefixes: the readings,
    and for each of the cell's limits that the arm passes over, a line.
    The run holds the served tokens to it, and ``command_a_check.py
    control`` every control: one function, so that a control that comes
    out correct here would have come out correct in a run."""
    by_request = reference.worst_request_excess(judged, stated, valid)
    capped = reference.capped_gap_ratio(judged, stated, valid)
    of = np.nonzero(np.asarray(valid))[0]
    count = lambda weights: np.bincount(of, weights=weights, minlength=len(valid))  # noqa: E731
    read = {
        "gap_ratio": reference.gap_ratio(judged, stated), "worst_request_excess": by_request["excess"],
        "capped_gap_ratio": capped["ratio"], "tokens": int(judged["gap"].size), "requests": int(len(valid)),
        "worst_request": by_request, "capped": capped,
        "mean_gap": float(judged["gap"].mean()), "mean_gap_stated": float(stated["gap"].mean()),
        "median_margin": float(np.median(judged["margin"])), "near_ties": reference.reading(judged)["near_ties"],
        "by_request": {"own": count(judged["gap"]).round(5).tolist(), "stated": count(stated["gap"]).round(5).tolist(),
                       "off_own": count(judged["gap"] > 0).astype(int).tolist(),
                       "off_stated": count(stated["gap"] > 0).astype(int).tolist()},
    }
    failures = [f"{name} {read[name]} over the limit {float(w[key])}" for name, key in HELD if not read[name] <= float(w[key])]
    return read, failures


HELD = (("gap_ratio", "gap_ratio_limit"), ("worst_request_excess", "request_excess_limit"), ("capped_gap_ratio", "capped_gap_ratio_limit"))


def compared(read: Dict, w: Dict) -> Dict:
    """Every number :func:`verdict` held, beside its limit (``run.py``
    prints them last, in the result line and on standard error)."""
    return {name: [read[name], float(w[key])] for name, key in HELD}


def describe(read: Dict, w: Dict) -> str:
    worst, capped = read["worst_request"], read["capped"]
    return (f"gap_ratio {read['gap_ratio']:.4f} (limit {w['gap_ratio_limit']}), capped_gap_ratio {read['capped_gap_ratio']:.4f} "
            f"(limit {w['capped_gap_ratio_limit']}; at most {reference.GAP_CAP} logits a token: {capped['own']:.4f} against the stated "
            f"arithmetic's {capped['stated']:.4f}, request {capped['left_out']} left out with {capped['left_out_own']:.4f} against "
            f"{capped['left_out_stated']:.4f}; {capped['off_argmax']} served tokens off the float32 argmax against "
            f"{capped['off_argmax_stated']}), the worst request's excess {read['worst_request_excess']:.4f} mean requests (limit "
            f"{w['request_excess_limit']}; its {worst['tokens']} tokens: {worst['own']:.5f} against {worst['stated']:.5f}, the mean request "
            f"{worst['mean_stated']:.5f}), over {read['tokens']} greedy tokens of {read['requests']} requests: the served tokens lie "
            f"{read['mean_gap']:.5f} logits below the float32 reference's best in the mean, the stated arithmetic's own choices "
            f"{read['mean_gap_stated']:.5f}; median margin {read['median_margin']:.4f}; {read['near_ties']} positions at near-ties")


def run(cell: spec.Cell, rt, peaks) -> Dict:
    import jax

    from flexflow_tpu.serving import InferenceServer
    from flexflow_tpu.serving.generation import GenerationModel

    args, w = rt.args, cell.workload
    seconds, lead_in = float(args.seconds), float(w["lead_in_s"])
    t0 = time.monotonic()
    params, cfg, engine = build_engine(cell, args.seed)
    cc, wc = engine.cache_config, engine.window_config
    weight_bytes = sum(a.size * a.dtype.itemsize for a in jax.tree.leaves(params))
    rt.log(f"engine: {cfg.num_layers} L, block {cfg.block}, norm {cfg.norm} ({len(cfg.window_layers)} window of {cfg.window} + "
           f"{len(cfg.full_layers)} full without rotation; {cfg.held_experts} of {cfg.num_experts} x {cfg.moe_ff_size} experts held, "
           f"top-{cfg.experts_per_token}, {cfg.num_shared_experts} shared {cfg.shared_experts}) / {cfg.hidden_size} / {cfg.num_heads} "
           f"over {cfg.kv_heads} heads of {cfg.dim_per_head}, vocab {cfg.vocab_size} (tied), {cfg.dtype.name}: weights "
           f"{weight_bytes / 1e9:.2f} GB in {time.monotonic() - t0:.1f}s; {engine.max_batch_slots} slots, buckets {engine.buckets}, "
           f"full pool {cc.num_blocks} x {cc.block_size} = {cc.total_bytes / 2**30:.2f} GiB, window pool {wc.num_blocks} x "
           f"{wc.block_size} = {wc.total_bytes / 2**30:.2f} GiB ({wc.blocks_per_sequence(engine.max_seq_len)} blocks a sequence); "
           f"kernels {engine.kernel_stats()}, prefill {engine.prefill_attention_stats()['programs']}")

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

    trace = _trace(rt) if args.trace else None
    ctx = {
        "cell": cell, "records": records, "window": (t_open, t_close),
        "setup_s": t_open_real - rt.t_start, "memory_peak_bytes": memory_peak,
        "trace_abs": (rt.trace_t0, rt.trace_t0 + traced_s) if args.trace else None,
        "traced_s": traced_s if args.trace else None, "stats_open": stats_open, "stats_close": stats_close,
        "stats_samples": samples, "engine_open": eng_open, "engine_close": eng_close,
        "slots": engine.max_batch_slots, "model": model_sizes(cfg, engine),
        "paged_kernels": paged_kernels(trace, traced_s) if trace else None,
        "prefill_runs": prefill_runs(trace, traced_s, records, rt.trace_t0) if trace else None,
    }
    del trace
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
    ex, cache = stats_close.get("experts") or {}, stats_close.get("cache") or {}
    prefill_open, prefill_close = (s["phase_time_s"].get("prefill", {}) for s in (eng_open, eng_close))
    rt.log(f"inside: decode steps {eng_close['step_counts']['decode'] - eng_open['step_counts']['decode']}, "
           f"decode_step_ms {layer_metrics.read('decode_step_ms', ctx)}, prefills "
           f"{eng_close['step_counts']['prefill'] - eng_open['step_counts']['prefill']} in "
           f"{sum(prefill_close.values()) - sum(prefill_open.values()):.2f}s of phases, pipeline {stats_close.get('pipeline')}, "
           f"experts section { {k: ex.get(k) for k in ('decode_calls_total', 'prefill_calls_total', 'unrouted_here_total', 'forms')} }, "
           f"held tokens {sum(ex.get('tokens_total') or [])}")
    rt.log(f"cache: {cache}; window blocks released inside the window "
           f"{cache.get('window_released_total', 0) - (stats_open.get('cache') or {}).get('window_released_total', 0)}")
    rt.log(f"prefill_attention: {stats_close.get('prefill_attention')}; kernels {stats_close.get('kernels')}; traced: paged "
           f"{ctx['paged_kernels']}, prefill runs {ctx['prefill_runs']}")
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
        when = [(round(r.get("sent", r["due"]) - t_open, 2), len(r.get("token_times") or [])) for r in failed]
        why.append(f"{len(bad)} requests failed or were refused, {gen['undrained']} never drained: {errs[:3]}, sent and tokens got {when[:16]}")
    if any(not 0 <= t < cfg.vocab_size for r in records for t in r["tokens"]):
        why.append("a token outside the vocabulary's slice")
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
    held, bound = (cache.get("window") or {}).get("held_by_a_sequence_peak"), wc.blocks_per_sequence(engine.max_seq_len)
    if held is None or held > bound:
        why.append(f"a live sequence held {held} blocks of the window pool, over the {bound} the window allows")
    # no prefill of the window materialised its scores, and on the chip each ran the kernel
    pa_open, pa = stats_open.get("prefill_attention") or {}, stats_close.get("prefill_attention") or {}
    on_chip = jax.default_backend() == "tpu"
    if on_chip and pa.get("materialised_calls_total", 0) != pa_open.get("materialised_calls_total", 0):
        why.append(f"prefills inside the window materialised their scores: {pa}")
    if on_chip and any(k.startswith("prefill_stream_attention") for k in pa.get("refused_total") or {}):
        why.append(f"the streamed prefill's kernel was refused: {pa['refused_total']}")
    bodies = {k: (stats_close.get("kernels") or {}).get(k, {}).get("body") for k in ("full", "window")}
    if set(bodies.values()) != {"mxu" if on_chip else "reference"}:
        why.append(f"the decode calls lowered to {bodies}")
    # every request COMPLETED INSIDE the window (a seeded choice of
    # reference_sample of them where there are more), every served token
    # judged given its prefix by the benchmark's float32 reference
    # (benchmark/reference/command_a_plus.py), logits not tokens, held to
    # the distance at which the same equations lie from that reference when
    # computed in the arithmetic the configuration states
    # (reference/lfm2.py::gap_ratio says why a ratio)
    good = [r for r in done if stats.request_ok(r)] or [r for r in records if stats.request_ok(r)]
    rs = np.random.RandomState(args.seed + 2)
    picked = [good[i] for i in sorted(rs.choice(len(good), size=min(int(w["reference_sample"]), len(good)), replace=False))]
    by_id = {r["id"]: r for r in requests}
    if picked:
        t0 = time.monotonic()
        # the engine's caches are not needed any more: their room is the reference's
        engine.cache.k = engine.cache.v = None
        engine.cache.state = {}
        sample = judge_sample(params, cell, [by_id[r["id"]]["prompt"] for r in picked], [r["tokens"] for r in picked], CONTROL_ARMS)
        read, failures = verdict(sample["judged"]["program"], sample["judged"]["stated"], sample["valid"], w)
        rt.log(f"reference: {describe(read, w)}; {time.monotonic() - t0:.1f}s")
        rt.log(f"reference, request by request: served {read['by_request']['own']}, stated {read['by_request']['stated']}; "
               f"off the argmax {read['by_request']['off_own']}, stated {read['by_request']['off_stated']}")
        why += failures
        ctx["compared"] = compared(read, w)
        if len(picked) < int(w["reference_requests_least"]) or read["tokens"] < int(w["reference_tokens_least"]):
            why.append(f"the reference judged {read['tokens']} tokens of {len(picked)} requests: fewer than the cell asks")
        ctx["reference"] = dict(sample, read=read, params=params, picked=[r["id"] for r in picked])
    else:
        why.append("no request for the reference to judge")

    ctx.update(correct=not why, why_incorrect=why, attempted=attempted, failed=attempted - len(ok_due))
    return ctx
