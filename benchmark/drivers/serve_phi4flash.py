"""Driver ``serve_phi4flash``: Phi-4-mini-flash-reasoning, WHOLE (32 layers, the
whole vocabulary, nothing cut) behind the same HTTP server, loaded by the same
child process, as the other serving drivers.

What is the same is theirs, imported and not copied: ``serve``'s
``start_loadgen``, ``finish_loadgen``, ``engine_snapshot``, ``sleep_until``,
``serve_mellum2``'s ``pad_to``, ``serve_nemotron``'s ``warm``, ``stalls.Watch``.
What differs: the weights and the configuration are ``reference/phi4flash.py``'s
(bfloat16 weights made from the seed; a ``DecoderConfig`` of Mamba-1, window,
attention, gmu and cross layers under differential attention), the reference
that judges the served tokens is that file's float32 one (EVERY layer over
EVERY row, where the program runs the cross-decoder of a prompt on one),
``ctx["model"]`` carries the sizes the readers of the new layers need
(``phi4flash_model.py``), the ``cache`` and ``prefill`` sections of
``/v2/stats`` are sampled with the rest and, traced, the update kernel's
device seconds are read out of the trace under its own name
(``ctx["ssm_kernels"]``) and the decode program's device seconds by the scope
names of its own compiled text (``ctx["samba_scopes"]``: the harness reduces
with the names it had). No prefix index exists for such a configuration.
And ``correct`` holds what no served token shows (:func:`probe_engine`,
after the window, on the idle engine): the state the first Mamba layer STORES
against the stated arithmetic's, by (request, channel), at a median AND a high
share: a bfloat16 state reads inside the program's own band of served tokens,
and far outside these.
"""
from __future__ import annotations

import bisect
import re
import time
from typing import Dict, List

import numpy as np

from benchmark import layer_metrics, spec, stats, trace_reduce, traffic
from benchmark.stalls import Watch
from benchmark.drivers.serve import (
    LOGGED, ZERO_COUNTERS, engine_snapshot, finish_loadgen, sent_of_listed, sleep_until, start_loadgen,
)
from benchmark.drivers.serve_mellum2 import pad_to
from benchmark.drivers.serve_nemotron import warm
from benchmark.reference import phi4flash as reference

SSM_KERNELS = ("selective_state_update",)
PAGED = ("paged_append_attention", "paged_window_attention")  # the paged calls' names in a trace, longest match first
# the scopes of generation/decoder.py::_layers that a decode program of this configuration has, innermost wins
SCOPES = ("attention.full", "attention.cross", "attention.window", "gmu", "ssm.conv", "ssm.update", "ssm", "mlp",
          "cache_write", "embed", "head", "sample")
SHARES = (0.5, 0.9, 0.99, 1.0)  # of the first layer's (request, channel) pairs: those the cell's file gives a limit are held, all are logged

# Arms beside the program's and the stated arithmetic's, for ``tools/phi4flash_check.py control`` alone (a run has
# none): a control of ``reference.CONTROLS`` each
CONTROL_ARMS: tuple = ()


def build_engine(cell: spec.Cell, seed: int, slots: int = 0):
    import jax

    from flexflow_tpu.generation import GenerationEngine

    c, d = cell.config, cell.workload["deployment"]
    cfg = reference.engine_config(c, int(d["max_seq_len"]))
    params = reference.init_params(seed, c)
    if c.get("serving_dtype", "bfloat16") != "bfloat16":  # the rehearsal on the CPU
        params = reference.cast_params(params, cfg.dtype.jnp)
    engine = GenerationEngine(
        params, cfg, max_batch_slots=int(slots or d["slots"]), block_size=int(d["block_size"]),
        prompt_buckets=list(d["prompt_buckets"]), max_seq_len=int(d["max_seq_len"]),
    )
    jax.block_until_ready((engine.cache.k, engine.cache.state))
    return params, cfg, engine


def model_sizes(cfg, engine) -> Dict:
    """What the readers of the kernels' and the step's rooflines need: the
    PUBLISHED sizes (heads as the configuration's file has them, not the
    padded form the attention calls are handed)."""
    return {
        "num_layers": cfg.num_layers, "mamba_layers": len(cfg.mamba_layers), "window_layers": len(cfg.window_layers),
        "gmu_layers": len(cfg.gmu_layers), "cross_layers": len(cfg.cross_layers), "hidden_size": cfg.hidden_size,
        "ff_size": cfg.ff_size, "num_heads": cfg.num_heads, "kv_heads": cfg.kv_heads, "head_dim": cfg.dim_per_head,
        "mamba_inner": cfg.ssm_inner, "state_size": cfg.ssm_state_size, "dt_rank": cfg.dt_rank, "conv_kernel": cfg.ssm_conv_kernel,
        "window": cfg.window, "vocab_size": cfg.vocab_size, "block_size": engine.cache_config.block_size,
        "cache_itemsize": engine.cache.k.dtype.itemsize, "weight_itemsize": cfg.dtype.size_bytes,
    }


def _trace_file(rt):
    files = sorted(rt.trace_dir.glob("plugins/profile/*/*.xplane.pb"))
    return str(files[-1]) if files else None


def ssm_kernels(rt, seconds: float):
    """The update kernel's device seconds and calls over the traced part
    of the window, from the trace the harness is about to reduce."""
    path = _trace_file(rt)
    if path is None:
        return None
    reduced = trace_reduce.reduce_trace(trace_reduce.read_xplane(path), SSM_KERNELS, window_ns=(0.0, seconds * 1e9))
    return {"kernel_s": reduced["kernel_s"], "kernel_calls": reduced["kernel_calls"]}


def scope_of(text: str) -> Dict[str, str]:
    """``{instruction name: scope}`` of a compiled program's text: the
    innermost of :data:`SCOPES` in the instruction's ``op_name``."""
    out = {}
    for name, op_name in re.findall(r"^\s*(?:ROOT )?%?([\w.\-]+) = .*?op_name=\"([^\"]*)\"", text, flags=re.M):
        found = [(op_name.rfind(s), len(s), s) for s in SCOPES if s in op_name]
        if found:
            out[name] = max(found)[2]
    return out


def decode_text(engine) -> str:
    """The compiled decode program's text, lowered again from the shapes
    the engine runs it at (the persistent cache holds the executable; a
    traced run pays the lowering, after the window)."""
    import jax
    import jax.numpy as jnp

    sds = lambda a: jax.ShapeDtypeStruct(a.shape, a.dtype)  # noqa: E731
    b, mb, v = engine.max_batch_slots, engine.max_blocks_per_seq, engine.cfg.vocab_size
    i32, f32 = jnp.int32, jnp.float32
    vec = lambda dt: jax.ShapeDtypeStruct((b,), dt)  # noqa: E731
    wtables = {"tables": jax.ShapeDtypeStruct((b, engine.window_columns), i32), "first": vec(i32)}
    before = dict(engine.trace_counts)
    try:
        lowered = engine._decode_jit.lower(
            jax.tree.map(sds, engine.params), vec(i32), vec(i32), sds(engine.cache.k), sds(engine.cache.v),
            jax.ShapeDtypeStruct((b, mb), i32), vec(i32), vec(f32), vec(i32), vec(f32), vec(jnp.uint32), vec(i32),
            jax.ShapeDtypeStruct((b, v), f32), jax.tree.map(sds, engine._step_state()), jax.tree.map(sds, engine.expert_counts), wtables,
        )
        return lowered.compile().as_text()
    finally:
        engine.trace_counts.clear()
        engine.trace_counts.update(before)  # (this lowering is the benchmark's, not a retrace of the program's)


def samba_scopes(rt, seconds: float, text: str):
    """The decode program's device seconds over the traced part of the
    window, by the scope its compiled ``text`` names for each instruction:
    ``scope_s`` (every instruction's own time), ``kernel_s`` (of that, the
    paged attention calls'), ``decode_s`` (all of it) and ``steps``, the
    decode programs that ran there."""
    path = _trace_file(rt)
    if path is None or not text:
        return None
    trace = trace_reduce.read_xplane(path)
    if not trace.devices:
        return None
    dev, hi = trace.devices[0], seconds * 1e9
    scopes = scope_of(text)
    steps = sorted((a, b) for n, a, b in dev.modules if "decode_impl" in n and a < hi)
    starts = [a for a, _ in steps]
    ops = [(n, a, min(b, hi)) for n, a, b in dev.ops if a < hi]
    scope_s: Dict[str, float] = {}
    kernel_s: Dict[str, float] = {}
    decode_ns = 0.0
    for (n, a, b), own in zip(ops, trace_reduce.self_times(ops)):
        i = bisect.bisect_right(starts, a) - 1
        if i < 0 or a >= steps[i][1]:
            continue  # (another program's instruction: a prefill's, the hand-over's)
        name = trace_reduce.op_name(n)
        scope = scopes.get(name, "other")
        decode_ns += own
        scope_s[scope] = scope_s.get(scope, 0.0) + own / 1e9
        if any(k in name for k in PAGED):
            kernel_s[scope] = kernel_s.get(scope, 0.0) + (b - a) / 1e9
    return {"scope_s": scope_s, "kernel_s": kernel_s, "decode_s": decode_ns / 1e9, "steps": len(steps)}


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


def probe_engine(engine, prompts, steps: int) -> Dict:
    """The ``prompts`` served once more by the engine of the run, idle
    now, through the programs the window ran (a prefill each, the state
    handed to a slot, ``steps`` - 1 decode steps of all the slots), for
    what a served token does not show: ``state`` [9 layers, N, D, N']: what
    every Mamba layer STORES for each of them after its last step, as it
    lies on the device; ``tokens`` [N, S] are the positions they were fed
    (a prompt, then its reply but the last token, which no step was fed),
    ``lengths`` how many a row has."""
    import jax.numpy as jnp

    from flexflow_tpu.generation.engine import SamplingParams

    engine.reset()
    replies = engine.generate(prompts, SamplingParams(max_new_tokens=steps))
    lengths = np.asarray([len(p) + len(r) - 1 for p, r in zip(prompts, replies)], np.int32)
    tokens = np.zeros((len(prompts), max(len(p) for p in prompts) + steps - 1), np.int32)
    for row, p, r in zip(tokens, prompts, replies):
        row[: len(p) + len(r) - 1] = list(p) + list(r)[:-1]
    # a private scheduler on an engine just reset seats its requests in the order they came: request i in slot i
    # (a state read off another slot lies a whole norm from the reference's, and the run is not correct)
    state = jnp.swapaxes(jnp.array(engine.cache.state["ssm"][:, : len(prompts)]), -1, -2)
    return {"tokens": tokens, "lengths": lengths, "state": state, "replies": [list(map(int, r)) for r in replies]}


def probe_sample(params, cell: spec.Cell, probed: Dict, controls=()) -> Dict:
    """What :func:`verdict` holds of the probe, for the program and for
    each control's arithmetic over the same positions
    (``reference.probe``): ``state_error`` by layer, the stored state's
    distance from the yardstick's (the arithmetic the configuration
    states) by (request, channel), the one that 0.9 of the pairs lie under;
    ``state_error_at``, the FIRST layer's at each of ``SHARES``."""
    stated = cell.config.get("serving_dtype", "bfloat16")  # (the rehearsal on the CPU serves float32)
    yardstick = reference.probe(params, cell.config, probed["tokens"], probed["lengths"], stated)

    def of_the_state(state) -> Dict:
        pairs = reference.state_distances(state[:1], yardstick["state"][:1])[0]  # [requests, channels] of the first layer
        return {"state_error": reference.state_error(state, yardstick["state"]), "state_error_at": np.quantile(pairs, SHARES)}

    out = {"program": of_the_state(probed["state"])}
    for name in controls:
        out[name] = of_the_state(reference.probe(params, cell.config, probed["tokens"], probed["lengths"], name)["state"])
    return {arm: {k: [float(x) for x in v] for k, v in read.items()} for arm, read in out.items()}


def verdict(judged: Dict, stated: Dict, valid, w: Dict, probed: Dict):
    """THE comparison that decides ``correct`` for an arm: its judged
    tokens against the stated arithmetic's after the same prefixes
    (``gap_ratio`` pooled, ``worst_request_excess`` request by request),
    and of its ``probed`` readings the FIRST Mamba layer's stored state's
    distance from the stated arithmetic's (what goes into that layer is
    the same numbers on both sides; deeper, the bfloat16 activations' own
    noise is all one reads) at each share of the (request, channel) pairs
    that the cell's file names (``state_error_limits``: the median, which a
    state stored coarser moves and a skipped slot does not, and 0.9, the
    other way round), each under its limit. The readings, and for each of
    the cell's limits that the arm does not keep, a line. The run holds
    the program to it, and ``phi4flash_check.py control`` every control:
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
        "state_error_by_layer": probed["state_error"], "state_error_at": dict(zip(map(str, SHARES), probed["state_error_at"])),
    }
    failures = [f"{name} {value} over the limit {limit}" for name, (value, limit) in compared(read, w).items() if not value <= limit]
    return read, failures


def compared(read: Dict, w: Dict) -> Dict:
    """Every number :func:`verdict` holds, beside its limit; also the
    result line's last key and the run's last lines on standard error."""
    return {
        "gap_ratio": [read["gap_ratio"], float(w["gap_ratio_limit"])],
        "worst_request_excess": [read["worst_request_excess"], float(w["request_excess_limit"])],
        **{f"state_error_at_{share}": [read["state_error_at"][share], float(limit)] for share, limit in w["state_error_limits"].items()},
    }


def describe(read: Dict, w: Dict) -> str:
    worst = read["worst_request"]
    return (f"gap_ratio {read['gap_ratio']:.4f} (limit {w['gap_ratio_limit']}), the worst request's excess "
            f"{read['worst_request_excess']:.4f} mean requests (limit {w['request_excess_limit']}; its {worst['tokens']} tokens: "
            f"{worst['own']:.5f} against {worst['stated']:.5f}, the mean request {worst['mean_stated']:.5f}; the largest ratio of a "
            f"request {read['worst_request_ratio']:.3f}), over {read['tokens']} greedy tokens of {read['requests']} requests: the "
            f"served tokens lie {read['mean_gap']:.5f} logits below the float32 reference's best in the mean ({read['off_argmax']} off "
            f"its argmax), the stated arithmetic's own choices {read['mean_gap_stated']:.5f} ({read['off_argmax_stated']}); median "
            f"margin {read['median_margin']:.4f}; {read['near_ties']} positions at near-ties; probed: the first Mamba layer's "
            f"stored state from the stated arithmetic's by (request, channel), at shares "
            f"{ {k: float(f'{x:.3g}') for k, x in read['state_error_at'].items()} } (limits {w['state_error_limits']}); at 0.9 by layer "
            f"{[float(f'{x:.3g}') for x in read['state_error_by_layer']]}")


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
    rt.log(f"engine: {cfg.num_layers} L ({len(cfg.mamba_layers)} mamba: inner {cfg.ssm_inner}, state {cfg.ssm_state_size}, dt rank "
           f"{cfg.dt_rank}; {len(cfg.window_layers)} window of {cfg.window}; the K/V layer {cfg.kv_source}, memory from {cfg.memory_source}; "
           f"{len(cfg.gmu_layers)} gmu, {len(cfg.cross_layers)} cross; differential {cfg.num_heads} over {cfg.kv_heads} heads of "
           f"{cfg.dim_per_head}, stored as {cfg.cache_kv_heads} rows of {cfg.cache_head_dim} ({cfg.kv_heads // 2} pairs), no positions; SwiGLU {cfg.ff_size}) / "
           f"{cfg.hidden_size}, vocab {cfg.vocab_size} tied, {cfg.dtype.name}: weights {weight_bytes / 1e9:.2f} GB in "
           f"{time.monotonic() - t0:.1f}s; {engine.max_batch_slots} slots, buckets {engine.buckets}, state {ss.bytes_per_sequence} B "
           f"a slot = {ss.total_bytes / 2**30:.2f} GiB ({ {k: tuple(v.shape) for k, v in engine.cache.state.items()} }), full K/V "
           f"{cc.num_blocks} x {cc.block_size} = {cc.total_bytes / 2**30:.2f} GiB, window K/V {engine.window_config.num_blocks} x "
           f"{cc.block_size} = {engine.window_config.total_bytes / 2**30:.2f} GiB; kernels {engine.attention_kernels}; "
           f"prefill {engine.prefill_attention_stats()['programs']}; refused {sorted(engine.unsupported)}")

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
        "samba_scopes": samba_scopes(rt, traced_s, decode_text(engine)) if args.trace else None,
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
    cache = stats_close.get("cache") or {}
    ssm = cache.get("ssm") or {}
    prefill_open, prefill_close = (s["phase_time_s"].get("prefill", {}) for s in (eng_open, eng_close))
    rt.log(f"inside: decode steps {eng_close['step_counts']['decode'] - eng_open['step_counts']['decode']}, "
           f"decode_step_ms {layer_metrics.read('decode_step_ms', ctx)}, prefills "
           f"{eng_close['step_counts']['prefill'] - eng_open['step_counts']['prefill']} in "
           f"{sum(prefill_close.values()) - sum(prefill_open.values()):.2f}s of phases, pipeline {stats_close.get('pipeline')}")
    rt.log(f"cache: ssm {ssm}; shared_kv {cache.get('shared_kv')}; full {cache.get('full')}; window {cache.get('window')}; prefill "
           f"{stats_close.get('prefill')}; kernels {stats_close.get('kernels')}; traced: {ctx['ssm_kernels']}; scopes {ctx['samba_scopes']}")
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
    # the Mamba layers' state: per slot, float32, at the published size, ONE layer's full K/V, and no prefix index
    want = len(cfg.mamba_layers) * 4 * cfg.ssm_inner * cfg.ssm_state_size
    if ssm.get("state_bytes_per_slot") != want or engine.cache.state.get("ssm") is None or engine.cache.state["ssm"].dtype != np.float32:
        why.append(f"the state-space state holds {ssm.get('state_bytes_per_slot')} B a slot, not float32 S of every layer ({want} B)")
    if engine.prefix_cache.enabled or "prefix_reuse" not in engine.unsupported:
        why.append("a prefix index is kept beside per-slot state")
    if engine.cache_config.num_layers != 1 or (cache.get("shared_kv") or {}).get("reader_layers") != list(cfg.cross_layers):
        why.append(f"the full K/V holds {engine.cache_config.num_layers} layers, read by {(cache.get('shared_kv') or {}).get('reader_layers')}: not ONE, read by every cross layer")
    on_chip = jax.default_backend() == "tpu"
    if on_chip and not eng_close["trace_counts"].get("decode"):
        why.append("no decode program was traced")
    # a seeded sample of the requests COMPLETED INSIDE the window, every
    # served token judged given its prefix by the benchmark's float32
    # reference (benchmark/reference/phi4flash.py: every layer over every row), logits
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
        probed = probe_engine(engine, prompts[: int(w["probe_sample"])], int(w["probe_steps"]))
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
