"""Driver ``serve_longcat``: LongCat-Flash-Chat (one chip's share: 4 of 28
published layers = 8 sub-layers, 16 of each layer's 512 routed experts
beside its 256 identity experts, an eighth of the vocabulary) behind the
same HTTP server, loaded by the same child process, as the other serving
drivers.

What is the same is theirs, imported and not copied: ``serve``'s ``warm``,
``start_loadgen``, ``finish_loadgen``, ``engine_snapshot``,
``sleep_until``, ``serve_lfm2``'s ``host_tier``, ``serve_mellum2``'s
``pad_to``, ``serve_joyai``'s ``latent_kernels``, ``serve_command_a``'s
``verdict`` / ``arm_of`` / ``describe`` (THE comparison that decides
``correct``, as ``long-doc`` has it), ``stalls.Watch``. What differs: the
weights and the configuration are ``reference/longcat_flash.py``'s
(bfloat16 weights made from the seed; a ``DecoderConfig`` of latent
sub-layers with dense feed-forwards, every second one carrying a routed
branch on a shortcut), the reference that judges the served tokens is that
file's float32 one, ``ctx["model"]`` carries the sizes the readers of the
new layers need (``longcat_model.py``), and the run is held to what the
issue asks of the program: every prefill's latent attention streamed (no
``[64, S, S]`` scores), the absorbed kernel taken at 64 heads, one row a
token a sub-layer in the cache, the identity experts counted.
"""
from __future__ import annotations

import time
from typing import Dict, List, Optional

import numpy as np

from benchmark import layer_metrics, spec, stats, traffic
from benchmark.drivers.serve import (
    LOGGED, ZERO_COUNTERS, engine_snapshot, finish_loadgen, sent_of_listed, sleep_until, start_loadgen, warm,
)
from benchmark.drivers.serve_command_a import arm_of, compared, describe, verdict  # noqa: F401  (arm_of: tools/longcat_check.py)
from benchmark.drivers.serve_joyai import latent_kernels
from benchmark.drivers.serve_lfm2 import host_tier
from benchmark.drivers.serve_mellum2 import pad_to
from benchmark.reference import longcat_flash as reference
from benchmark.stalls import Watch


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
    """What the readers of the kernel's and the step's rooflines need
    (``joyai_model.paged_latent_attention_call``, ``longcat_model``)."""
    return {
        "num_layers": cfg.num_layers, "sub_layers": cfg.num_layers, "routed_branches": len(cfg.expert_layers),
        "num_heads": cfg.num_heads, "hidden_size": cfg.hidden_size, "ff_size": cfg.ff_size, "moe_ff_size": cfg.moe_ff_size,
        "num_experts": cfg.num_experts, "zero_experts": cfg.zero_experts, "router_outputs": cfg.router_outputs,
        "experts_held": cfg.held_experts, "experts_per_token": cfg.experts_per_token, "vocab_size": cfg.vocab_size,
        "q_lora_rank": cfg.q_lora_rank, "kv_lora_rank": cfg.kv_lora_rank, "qk_nope_head_dim": cfg.qk_nope_head_dim,
        "qk_rope_head_dim": cfg.qk_rope_head_dim, "v_head_dim": cfg.v_head_dim,
        "latent_layers": len(cfg.latent_layers), "block_size": engine.cache_config.block_size,
        "cache_itemsize": engine.cache.k.dtype.itemsize, "weight_itemsize": cfg.dtype.size_bytes,
    }


# Arms beside the program's and the stated arithmetic's, for
# ``tools/longcat_check.py control`` alone (a run has none): a control of
# ``reference.CONTROLS`` -> over how many of the judged requests its choices
# are computed (None: all of them)
CONTROL_ARMS: Dict[str, Optional[int]] = {}


def judge_sample(params, cell: spec.Cell, prompts, streams, controls: Dict[str, Optional[int]]) -> Dict:
    """The served ``streams`` after their ``prompts``, the stated
    arithmetic's choices after the same prefixes and each control's, all
    judged by the float32 reference in one pass over its logits
    (``serve_command_a.judge_sample``'s contract, with this model's
    reference)."""
    lay = reference.layout(prompts, streams, pad_to=pad_to(cell), max_new=int(cell.traffic["params"]["output"]["max"]))
    arms = {"program": lay["chosen"], "stated": reference.choices(params, cell.config, lay["tokens"], lay["at"], "bfloat16")}
    for name, n in controls.items():
        rows = slice(0, len(prompts) if n is None else n)
        arms[name] = arms["stated"].copy()
        arms[name][rows] = reference.choices(params, cell.config, lay["tokens"][rows], lay["at"][rows], name)
    judged = reference.judge(params, cell.config, lay["tokens"], lay["at"], arms, lay["valid"])
    return {"judged": judged, "valid": lay["valid"], "of": np.nonzero(np.asarray(lay["valid"]))[0]}


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
    rt.log(f"engine: {cfg.num_layers} latent sub-layers (rows of {cfg.latent_width} stored at {cc.row_shape[0]}; {cfg.num_heads} heads, "
           f"scores {cfg.qk_nope_head_dim}+{cfg.qk_rope_head_dim}, values {cfg.v_head_dim}, scales {cfg.latent_q_scale:.4f} / "
           f"{cfg.latent_kv_scale:.4f}), each with a dense SwiGLU of {cfg.ff_size}; routed branches on sub-layers {cfg.expert_layers}: "
           f"{cfg.held_experts} of {cfg.num_experts} x {cfg.moe_ff_size} held + {cfg.zero_experts} identity, top-{cfg.experts_per_token} "
           f"of {cfg.router_outputs}, gates x {cfg.routed_scaling_factor} / {cfg.hidden_size}, vocab {cfg.vocab_size}, {cfg.dtype.name}: "
           f"weights {weight_bytes / 1e9:.2f} GB in {time.monotonic() - t0:.1f}s; {engine.max_batch_slots} slots, buckets "
           f"{engine.buckets}, latent cache {cc.num_blocks} x {cc.block_size} x {cc.bytes_per_token} B = {cc.total_bytes / 2**30:.2f} GiB; "
           f"kernels {engine.kernel_stats()}, prefill {engine.prefill_attention_stats()['programs']}, experts "
           f"{engine.expert_lowerings()}; refused by name: {sorted(engine.unsupported)}")

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
    prefill_open, prefill_close = (s["phase_time_s"].get("prefill", {}) for s in (eng_open, eng_close))
    rt.log(f"inside: decode steps {eng_close['step_counts']['decode'] - eng_open['step_counts']['decode']}, "
           f"decode_step_ms {layer_metrics.read('decode_step_ms', ctx)}, prefills "
           f"{eng_close['step_counts']['prefill'] - eng_open['step_counts']['prefill']} in "
           f"{sum(prefill_close.values()) - sum(prefill_open.values()):.2f}s of phases, pipeline {stats_close.get('pipeline')}")
    rt.log(f"experts: { {k: ex.get(k) for k in ('decode_calls_total', 'prefill_calls_total', 'grouped_calls_total', 'forms', 'unrouted_here_total', 'zero_picks_total', 'zero_pick_share', 'real_experts_per_token_total')} }, "
           f"held tokens {sum(ex.get('tokens_total') or [])}; in the window: zero_expert_pick_share "
           f"{layer_metrics.read('zero_expert_pick_share', ctx)}, real_experts_per_token_p95 {layer_metrics.read('real_experts_per_token_p95', ctx)}")
    rt.log(f"cache.latent: {latent}; kernels {stats_close.get('kernels')}; prefill_attention {stats_close.get('prefill_attention')}; "
           f"traced: latent kernel {ctx['latent_kernels']}")
    rt.log(f"host tier: {tier_close['swaps_out_total'] - tier_open['swaps_out_total']} blocks read out to it inside the "
           f"window of {tier_close['evicted_total'] - tier_open['evicted_total']} evicted")
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
    # one row a token a sub-layer, at the stored width, and nothing else held for a position
    stored = len(cfg.latent_layers) * cc.row_shape[0] * cc.dtype.size_bytes
    if latent.get("bytes_per_token") != stored or cc.value_row_shape != (0,):
        why.append(f"the latent cache holds {latent.get('bytes_per_token')} B a token, not one row a sub-layer ({stored} B)")
    on_chip = jax.default_backend() == "tpu"
    body = (stats_close.get("kernels") or {}).get("latent") or {}
    if body.get("body") != ("mxu" if on_chip else "reference") or body.get("group") != cfg.num_heads:
        why.append(f"the latent layers' decode call lowered to {body}")
    # no prefill of the window materialised [heads, S, S] scores: at the cell's
    # size every bucket's calls are past the bound and take the streamed form
    pa_open, pa = stats_open.get("prefill_attention") or {}, stats_close.get("prefill_attention") or {}
    past = [b for b in engine.buckets if 4 * cfg.num_heads * b * b > pa.get("score_bytes_bound", 0)]
    if any(pa["programs"][f"prefill[{b}]"]["form"] != "streamed" for b in past):
        why.append(f"a prefill past the bound materialises its scores: {pa['programs']}")
    if len(past) == len(engine.buckets) and pa.get("materialised_calls_total", 0) != pa_open.get("materialised_calls_total", 0):
        why.append(f"prefills inside the window materialised their scores: {pa}")
    if "zero_picks_total" not in ex or ex["zero_picks_total"] <= (stats_open.get("experts") or {}).get("zero_picks_total", 0):
        why.append(f"no pick of an identity expert was counted inside the window: {ex.get('zero_picks_total')}")
    # every request COMPLETED INSIDE the window (a seeded choice of
    # reference_sample of them where there are more), every served token
    # judged given its prefix by the benchmark's float32 reference
    # (benchmark/reference/longcat_flash.py), logits not tokens, held to the
    # distance at which the same equations lie from that reference when
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
