"""Driver ``serve_nemotron``: NVIDIA-Nemotron-3-Super-120B-A12B (one chip's
share: the first 11 of 88 layers, 128 of each expert layer's 512 routed
experts, a quarter of the vocabulary) behind the same HTTP server, loaded by
the same child process, as the other serving drivers.

What is the same is theirs, imported and not copied: ``serve``'s
``start_loadgen``, ``finish_loadgen``, ``engine_snapshot``, ``sleep_until``,
``serve_mellum2``'s ``pad_to``, ``stalls.Watch``. What differs: the weights
and the configuration are ``reference/nemotron_h.py``'s (bfloat16 weights
made from the seed; a ``DecoderConfig`` whose block is ``single``:
state-space mixers, one attention layer without positions, ungated experts
in a latent, of which this engine holds a share), the reference that judges
the served tokens is that file's float32 one, ``ctx["model"]`` carries the
sizes the readers of the new layers need (``nemotron_model.py``), the
``cache.ssm`` section of ``/v2/stats`` is sampled with the rest, ``warm`` is its own (no pool to overflow), and,
traced, the update kernel's device seconds are read out of the trace under
its own name (``ctx["ssm_kernels"]``: the harness reduces with the names it
had). No prefix index exists for such a configuration, so nothing is aged.
And ``correct`` holds two numbers that no served token shows
(:func:`probe_engine`, after the window, on the idle engine): the state the
first state-space layer STORES against the stated arithmetic's, and what
rounding the router's matrices to bfloat16 moves of the first expert
layer's picks: a bfloat16 state and a bfloat16 router read inside the
program's own band of served tokens, and far outside these two.
"""
from __future__ import annotations

import time
from typing import Dict, List

import numpy as np

from benchmark import layer_metrics, spec, stats, trace_reduce, traffic
from benchmark.stalls import Watch
from benchmark.drivers.serve import (
    LOGGED, ZERO_COUNTERS, engine_snapshot, finish_loadgen, sent_of_listed, sleep_until, start_loadgen,
)
from benchmark.drivers.serve_mellum2 import pad_to
from benchmark.reference import nemotron_h as reference

SSM_KERNELS = ("ssm_state_update",)
SHARES = (0.5, 0.9, 0.99, 1.0)  # of the first layer's (request, head) pairs: those the cell's file gives a limit are held (state_error_limits), all are logged

# Arms beside the program's and the stated arithmetic's, for ``tools/nemotron_check.py control`` alone (a run has
# none): a control of ``reference.CONTROLS`` each
CONTROL_ARMS: tuple = ()


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


def warm(engine, requests: List[Dict], vocab: int, seed: int, log) -> None:
    """Compile (or load from the cache) the decode program, one prefill
    program per bucket that the schedule's prompts fall in and the state's
    hand-over. (``serve.warm`` goes on until its prompts overflow the block
    pool, for the programs that evict cached prefixes: this engine keeps no
    prefix index, and its pool holds more prompts than the queue does.)"""
    from flexflow_tpu.generation.engine import SamplingParams

    rs = np.random.RandomState(seed + 1)
    buckets = sorted({engine.bucket_for(len(r["prompt"])) for r in requests})
    prompts = [[int(t) for t in rs.randint(0, vocab, size=min(b, engine.max_seq_len - 4))] for b in buckets]
    t0 = time.monotonic()
    engine.generate(prompts + prompts, SamplingParams(max_new_tokens=3))
    engine.reset()
    log(f"warmed decode + prefill{buckets} with {2 * len(prompts)} prompts in {time.monotonic() - t0:.1f}s; "
        f"programs traced: {dict(engine.trace_counts)}; compile+first-run seconds: "
        f"{ {p['name']: round(p['compile_s'], 2) for p in engine.programs.snapshot() if p.get('compile_s')} }")


def model_sizes(cfg, engine) -> Dict:
    """What the readers of the kernel's and the step's rooflines need."""
    return {
        "num_layers": cfg.num_layers, "ssm_layers": len(cfg.ssm_layers), "attention_layers": len(cfg.attention_layers),
        "expert_layers": len(cfg.expert_layers), "hidden_size": cfg.hidden_size, "num_heads": cfg.num_heads,
        "kv_heads": cfg.kv_heads, "head_dim": cfg.dim_per_head, "ssm_heads": cfg.ssm_heads, "ssm_head_dim": cfg.ssm_head_dim,
        "ssm_groups": cfg.ssm_groups, "ssm_state_size": cfg.ssm_state_size, "ssm_conv_kernel": cfg.ssm_conv_kernel,
        "moe_ff_size": cfg.moe_ff_size, "moe_latent_size": cfg.moe_latent_size, "shared_ff_size": cfg.shared_ff_size,
        "num_experts": cfg.num_experts, "experts_held": cfg.held_experts, "experts_per_token": cfg.experts_per_token,
        "vocab_size": cfg.vocab_size, "block_size": engine.cache_config.block_size,
        "cache_itemsize": engine.cache.k.dtype.itemsize, "weight_itemsize": cfg.dtype.size_bytes,
    }


def ssm_kernels(rt, seconds: float):
    """The update kernel's device seconds and calls over the traced part
    of the window, from the trace the harness is about to reduce."""
    files = sorted(rt.trace_dir.glob("plugins/profile/*/*.xplane.pb"))
    if not files:
        return None
    reduced = trace_reduce.reduce_trace(
        trace_reduce.read_xplane(str(files[-1])), SSM_KERNELS, window_ns=(0.0, seconds * 1e9)
    )
    return {"kernel_s": reduced["kernel_s"], "kernel_calls": reduced["kernel_calls"]}


def judge_sample(params, cell: spec.Cell, prompts, streams, controls=()) -> Dict:
    """The served ``streams`` after their ``prompts``, the stated
    arithmetic's choices after the same prefixes and each control's, all
    judged by the float32 reference in one pass over its logits:
    ``judged[arm]`` flat over the ``valid`` tokens."""
    lay = reference.layout(prompts, streams, pad_to=pad_to(cell), max_new=int(cell.traffic["params"]["output"]["max"]))
    arms = {"program": lay["chosen"], "stated": reference.choices(params, cell.config, lay["tokens"], lay["at"], "bfloat16")}
    for name in controls:
        arms[name] = reference.choices(params, cell.config, lay["tokens"], lay["at"], name)
    return {"judged": reference.judge(params, cell.config, lay["tokens"], lay["at"], arms, lay["valid"]), "valid": lay["valid"]}


def probe_engine(engine, params, prompts, steps: int) -> Dict:
    """The ``prompts`` served once more by the engine of the run, idle
    now, through the programs the window ran (a prefill each, the state
    handed to a slot, ``steps`` - 1 decode steps of all the slots), for
    what a served token does not show:

    * ``state`` [M layers, N, H, P, N]: what every state-space layer
      STORES for each of them after its last step, as it lies on the
      device; ``tokens`` [N, S] are the positions they were fed (a prompt,
      then its reply but the last token, which no step was fed),
      ``lengths`` how many a row has;
    * ``picks`` [E layers, held]: the router's picks by held expert over
      those positions, off the program's own counters;
    * ``prefill_picks``: the same counters over the prompts' prefills
      alone, with the weights as they are and, ``rounded``, with every
      router matrix rounded to bfloat16 beforehand
      (``reference.round_router``): the same program on the same
      activations, so what differs is what the router's product sees of
      its float32 weights below bfloat16, and a product over bfloat16
      operands sees nothing."""
    import jax.numpy as jnp

    from flexflow_tpu.generation.engine import SamplingParams
    from flexflow_tpu.ops import ssm

    counters = lambda: np.asarray(engine.expert_stats()["tokens_total_by_layer"], np.int64)  # noqa: E731

    def serve(weights, new_tokens: int):
        engine.reset()
        engine.params = weights
        before = counters()
        replies = engine.generate(prompts, SamplingParams(max_new_tokens=new_tokens))
        return replies, counters() - before

    try:
        given, rounded = serve(params, 1)[1], serve(reference.round_router(params), 1)[1]
        replies, picks = serve(params, steps)
    finally:
        engine.params = params
    lengths = np.asarray([len(p) + len(r) - 1 for p, r in zip(prompts, replies)], np.int32)
    tokens = np.zeros((len(prompts), max(len(p) for p in prompts) + steps - 1), np.int32)
    for row, p, r in zip(tokens, prompts, replies):
        row[: len(p) + len(r) - 1] = list(p) + list(r)[:-1]
    # a private scheduler on an engine just reset seats its requests in the order they came: request i in slot i
    # (a state read off another slot lies a whole norm from the reference's, and the run is not correct)
    state = ssm.unpack_state(jnp.array(engine.cache.state["ssm"][:, : len(prompts)]), engine.dcfg.ssm_head_dim)
    return {"tokens": tokens, "lengths": lengths, "prompt_lengths": np.asarray([len(p) for p in prompts], np.int32), "state": state,
            "picks": picks, "prefill_picks": {"given": given, "rounded": rounded}, "replies": [list(map(int, r)) for r in replies]}


def probe_sample(params, cell: spec.Cell, probed: Dict, controls=()) -> Dict:
    """What :func:`verdict` holds of the probe, for the program and for
    each control's arithmetic over the same positions
    (``reference.probe``), by layer: ``state_error``, the stored state's
    distance from the yardstick's (the arithmetic the configuration
    states) by (request, head), the one that 0.9 of the pairs lie under
    (``reference.state_error`` says why not pooled; ``state_error_pooled``
    is logged), ``state_error_at``, the first layer's at each of ``SHARES``
    (:func:`verdict` holds those the cell's file gives a limit), and
    ``state_error_pairs``, its every pair, request by request (what
    ``nemotron_check.py control`` keeps: a limit's room is taken from what
    the pairs CAN do); ``pick_error``, the picks not shared with the yardstick's;
    ``router_shift``, the picks of the prompts' positions that move when
    the router matrices are rounded to bfloat16 beforehand, beside the
    yardstick's own (``router_shift_stated``)."""
    stated = cell.config.get("serving_dtype", "bfloat16")  # (the rehearsal on the CPU serves float32)
    rounded, at, first = reference.round_router(params), int(probed["prompt_lengths"].max()), int(probed["prompt_lengths"][0])

    def shift(arithmetic: str):
        given, moved = (reference.probe(p, cell.config, probed["tokens"][:, :at], probed["prompt_lengths"], arithmetic, first)["picks"]
                        for p in (params, rounded))
        return reference.pick_error(moved, given)

    yardstick = reference.probe(params, cell.config, probed["tokens"], probed["lengths"], stated)
    shift_stated = shift(stated)

    def of_the_state(state) -> Dict:
        pairs = reference.state_distances(state[:1], yardstick["state"][:1])[0]  # [requests, heads] of the first layer
        return {"state_error": reference.state_error(state, yardstick["state"]),
                "state_error_pooled": reference.state_error_pooled(state, yardstick["state"]),
                "state_error_at": np.quantile(pairs, SHARES), "state_error_pairs": pairs.reshape(-1)}

    out = {"program": {**of_the_state(probed["state"]), "pick_error": reference.pick_error(probed["picks"], yardstick["picks"]),
                       "router_shift": reference.pick_error(probed["prefill_picks"]["rounded"], probed["prefill_picks"]["given"])}}
    for name in controls:
        arm = reference.probe(params, cell.config, probed["tokens"], probed["lengths"], name, first)
        out[name] = {**of_the_state(arm["state"]), "pick_error": reference.pick_error(arm["picks"], yardstick["picks"]), "router_shift": shift(name)}
    return {arm: {k: [float(x) for x in v] for k, v in dict(read, router_shift_stated=shift_stated).items()} for arm, read in out.items()}


def verdict(judged: Dict, stated: Dict, valid, w: Dict, probed: Dict):
    """THE comparison that decides ``correct`` for an arm: its judged
    tokens against the stated arithmetic's after the same prefixes, and
    of its ``probed`` readings (:func:`probe_sample`) two, in the FIRST
    layer of each kind: the stored state's distance from the stated
    arithmetic's (what goes into that layer is the same numbers on both
    sides but for a rounding that fell the other way; deeper, the
    bfloat16 activations' own noise is all one reads) at each share of
    the (request, head) pairs that the cell's file names
    (``state_error_limits``: the median, which a state stored coarser
    moves and a skipped slot does not, and 0.9, the other way round; one
    request's heads moved a little by the program's own rounding pass
    both), each under its limit; and the picks that rounding the router's
    weights moves, held OVER a share of what it moves in the stated
    arithmetic. The readings,
    and for each of the cell's limits that the arm does not keep, a line. The run holds
    the program to it, and ``nemotron_check.py control`` every control:
    one function, so that a control that comes out correct here would
    have come out correct in a run."""
    by_request = reference.worst_request_excess(judged, stated, valid)
    own, ref = reference.reading(judged), reference.reading(stated)
    read = {
        "gap_ratio": reference.gap_ratio(judged, stated), "worst_request_excess": by_request["excess"],
        "tokens": own["tokens"], "requests": int(len(valid)), "worst_request": by_request,
        "mean_gap": float(judged["gap"].mean()), "mean_gap_stated": float(stated["gap"].mean()),
        "off_argmax": own["off_argmax"], "off_argmax_stated": ref["off_argmax"],
        "median_margin": float(np.median(judged["margin"])), "near_ties": own["near_ties"],
        "worst_request_ratio": reference.worst_request_ratio(judged, stated, valid),
        "state_error": probed["state_error"][0], "router_shift": probed["router_shift"][0],
        "router_shift_stated": probed["router_shift_stated"][0], "state_error_at": dict(zip(map(str, SHARES), probed["state_error_at"])),
        "state_error_pairs": probed.get("state_error_pairs"),
        **{f"{k}_by_layer": v for k, v in probed.items() if k not in ("state_error_at", "state_error_pairs")},
    }
    failures = [
        f"{name} {value} over the limit {limit}"
        for name, (value, limit) in compared(read, w).items() if name != "router_shift_at_least" and not value <= limit
    ]
    if not read["router_shift"] >= float(w["router_shift_least"]) * read["router_shift_stated"]:
        failures.append(f"router_shift {read['router_shift']} under {float(w['router_shift_least'])} of the stated arithmetic's "
                        f"{read['router_shift_stated']}")
    return read, failures


def compared(read: Dict, w: Dict) -> Dict:
    """Every number :func:`verdict` holds, beside its limit (the stored
    state's distance at each share of the pairs that the cell's file gives
    one); also the result line's last key and the run's last lines on
    standard error."""
    return {
        "gap_ratio": [read["gap_ratio"], float(w["gap_ratio_limit"])],
        "worst_request_excess": [read["worst_request_excess"], float(w["request_excess_limit"])],
        **{f"state_error_at_{share}": [read["state_error_at"][share], float(limit)] for share, limit in w["state_error_limits"].items()},
        "router_shift_at_least": [read["router_shift"], float(w["router_shift_least"]) * read["router_shift_stated"]],
    }


def describe(read: Dict, w: Dict) -> str:
    worst = read["worst_request"]
    return (f"gap_ratio {read['gap_ratio']:.4f} (limit {w['gap_ratio_limit']}), the worst request's excess "
            f"{read['worst_request_excess']:.4f} mean requests (limit {w['request_excess_limit']}; its {worst['tokens']} tokens: "
            f"{worst['own']:.5f} against {worst['stated']:.5f}, the mean request {worst['mean_stated']:.5f}; the largest ratio of a "
            f"request {read['worst_request_ratio']:.3f}), over {read['tokens']} greedy tokens of {read['requests']} requests: the "
            f"served tokens lie {read['mean_gap']:.5f} logits below the float32 reference's best in the mean ({read['off_argmax']} off "
            f"its argmax), the stated arithmetic's own choices {read['mean_gap_stated']:.5f} ({read['off_argmax_stated']}); median "
            f"margin {read['median_margin']:.4f}; {read['near_ties']} positions at near-ties; probed: the first state-space layer's "
            f"stored state {read['state_error']:.3e} of a head's norm from the stated arithmetic's at {reference.STATE_SHARE} of the (request, head) "
            f"pairs (the first layer's at shares { {k: float(f'{x:.3g}') for k, x in read['state_error_at'].items()} }, limits "
            f"{w['state_error_limits']}; at {reference.STATE_SHARE} by layer {[float(f'{x:.3g}') for x in read['state_error_by_layer']]}; all pairs pooled "
            f"{[float(f'{x:.3g}') for x in read.get('state_error_pooled_by_layer', [])]}); router matrices rounded to bfloat16 move {read['router_shift']:.3e} "
            f"of the first expert layer's picks over the prompts, in the stated arithmetic {read['router_shift_stated']:.3e} (at least "
            f"{w['router_shift_least']} of that; by layer {[float(f'{x:.3g}') for x in read['router_shift_by_layer']]} against "
            f"{[float(f'{x:.3g}') for x in read['router_shift_stated_by_layer']]}); picks not the stated arithmetic's, by layer "
            f"{[float(f'{x:.3g}') for x in read['pick_error_by_layer']]}")


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
    ss = engine.slot_state
    rt.log(f"engine: {cfg.num_layers} L, block {cfg.block} ({len(cfg.ssm_layers)} ssm: {cfg.ssm_heads} heads of {cfg.ssm_head_dim}, "
           f"{cfg.ssm_groups} groups, state {cfg.ssm_state_size}; {len(cfg.attention_layers)} attention: {cfg.num_heads} over "
           f"{cfg.kv_heads} heads of {cfg.dim_per_head}, no positions; {len(cfg.expert_layers)} expert layers: {cfg.held_experts} of "
           f"{cfg.num_experts} x {cfg.moe_ff_size} {cfg.expert_activation} held in a latent of {cfg.moe_latent_size}, "
           f"top-{cfg.experts_per_token}, shared {cfg.shared_ff_size}) / {cfg.hidden_size}, vocab {cfg.vocab_size}, {cfg.dtype.name}: "
           f"weights {weight_bytes / 1e9:.2f} GB in {time.monotonic() - t0:.1f}s; {engine.max_batch_slots} slots, buckets "
           f"{engine.buckets}, state {ss.bytes_per_sequence} B a slot = {ss.total_bytes / 2**30:.2f} GiB "
           f"({ {k: tuple(v.shape) for k, v in engine.cache.state.items()} }), K/V {cc.num_blocks} x {cc.block_size} = "
           f"{cc.total_bytes / 2**30:.2f} GiB; kernels {engine.attention_kernels}; experts {engine.expert_lowerings()}; "
           f"refused {sorted(engine.unsupported)}")

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
    # every server option at its default but the queue's bound where the cell's file names one (the default
    # holds 256 waiting requests: a closed loop of more clients than that would be refused at the door)
    d = w["deployment"]
    model = GenerationModel(engine, name="lm", **({"max_queue": int(d["max_queue"])} if "max_queue" in d else {}))
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
            stats_open, eng_open = lm_stats(), engine_snapshot(engine)
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
        "ssm_kernels": ssm_kernels(rt, traced_s) if args.trace else None,
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
    ex, ssm = stats_close.get("experts") or {}, (stats_close.get("cache") or {}).get("ssm") or {}
    prefill_open, prefill_close = (s["phase_time_s"].get("prefill", {}) for s in (eng_open, eng_close))
    rt.log(f"inside: decode steps {eng_close['step_counts']['decode'] - eng_open['step_counts']['decode']}, "
           f"decode_step_ms {layer_metrics.read('decode_step_ms', ctx)}, prefills "
           f"{eng_close['step_counts']['prefill'] - eng_open['step_counts']['prefill']} in "
           f"{sum(prefill_close.values()) - sum(prefill_open.values()):.2f}s of phases, pipeline {stats_close.get('pipeline')}, "
           f"experts section { {k: ex.get(k) for k in ('decode_calls_total', 'prefill_calls_total', 'unrouted_here_total', 'forms')} }, "
           f"held tokens {sum(ex.get('tokens_total') or [])}")
    rt.log(f"cache.ssm: {ssm}; kernels {stats_close.get('kernels')}; traced: {ctx['ssm_kernels']}")
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
    # the state-space state: per slot, float32, at the published size, and no prefix index beside it
    want = len(cfg.ssm_layers) * 4 * cfg.ssm_inner * cfg.ssm_state_size
    if ssm.get("state_bytes_per_slot") != want or engine.cache.state.get("ssm") is None or engine.cache.state["ssm"].dtype != np.float32:
        why.append(f"the state-space state holds {ssm.get('state_bytes_per_slot')} B a slot, not float32 S of every layer ({want} B)")
    if engine.prefix_cache.enabled or "prefix_reuse" not in engine.unsupported:
        why.append("a prefix index is kept beside per-slot state")
    on_chip = jax.default_backend() == "tpu"
    if on_chip and not eng_close["trace_counts"].get("decode"):
        why.append("no decode program was traced")
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
    if picked:
        t0 = time.monotonic()
        prompts = [by_id[r["id"]]["prompt"] for r in picked]
        probed = probe_engine(engine, params, prompts[: int(w["probe_sample"])], int(w["probe_steps"]))
        same = sum(a == b for r, got in zip(picked, probed["replies"]) for a, b in zip(r["tokens"], got))
        rt.log(f"probe: {len(probed['replies'])} judged prompts served once more for {w['probe_steps']} tokens in {time.monotonic() - t0:.1f}s; "
               f"{same} of {sum(map(len, probed['replies']))} tokens are the ones served in the window")
        # the engine's caches and state are not needed any more: their room is the reference's
        engine.cache.k = engine.cache.v = None
        engine.cache.state = {}
        sample = judge_sample(params, cell, prompts, [r["tokens"] for r in picked], CONTROL_ARMS)
        sample["probed"] = probe_sample(params, cell, probed, CONTROL_ARMS)
        read, failures = verdict(sample["judged"]["program"], sample["judged"]["stated"], sample["valid"], w, sample["probed"]["program"])
        rt.log(f"reference: {describe(read, w)}; {time.monotonic() - t0:.1f}s")
        why += failures
        ctx["compared"] = compared(read, w)
        if len(picked) < int(w["reference_sample"]) or read["tokens"] < int(w["reference_tokens_least"]):
            why.append(f"the reference judged {read['tokens']} tokens of {len(picked)} requests: fewer than the cell asks")
        ctx["reference"] = dict(sample, read=read, picked=[r["id"] for r in picked])
    else:
        why.append("no request for the reference to judge")

    ctx.update(correct=not why, why_incorrect=why, attempted=attempted, failed=attempted - len(ok_due))
    return ctx
