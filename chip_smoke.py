#!/usr/bin/env python
"""The quickest proof that the system still starts on the chip.

    python chip_smoke.py                 # one chip: kernels, server, trainer
    python chip_smoke.py --four-chips    # one four-chip host: the sharded legs
    python chip_smoke.py --latent-kernel     # the latent paged kernel alone
    python chip_smoke.py --latent 64         # ... at the sizes of the cell whose latent layers have 64 heads
    python chip_smoke.py --expert-product    # the routed experts' sum alone: dense against grouped
    python chip_smoke.py --release-probe     # what the drop of a consumed step's device arrays waits for
    python chip_smoke.py --dispatch-probe    # ms a decode dispatch (args / upload / call), steady and after a change
    python chip_smoke.py --evict-probe       # ms an eviction and ms a pressure tick of the prefix index, beside the plain scan
    python chip_smoke.py --group16           # the group-16 paged calls and the streamed prefill kernel alone
    python chip_smoke.py --grouped-kernels   # every grouped paged call of the cells alone (``--tree .parent``: a parent commit's)
    python chip_smoke.py --walk-sweep        # ... at 1 to 32 table columns a grid step
    python chip_smoke.py --latent-prefill    # the streamed prefill kernel alone at the 64-head latent cell's call (192 / 128, group 1)
    python chip_smoke.py --flash-kernels     # the three training flash kernels alone at the bert-large cells' call (``--tree .parent`` too)
    python chip_smoke.py --flash-sweep       # ... at every block pair of {128, 256, 512}: ms a call and compile seconds

ONE process. It refuses to start unless JAX's first device is a TPU, and
any failed check raises: the exit code is non-zero and no result line is
printed. On success the LAST line of stdout is one JSON object,
``{"ok": true, "device": {"platform": "tpu", "kind": ..., "count": ...}}``.

What the default run does, at 24 layers / hidden 1024 / 16 heads of 64 /
FFN 4096 (BERT-Large read as an encoder, GPT-2-medium read as a causal
decoder), weights random from a seed:

* kernels — the paged decode/append kernel at group 1 (float32 rows of
  8 x 128; W = 1, 5, 32; kv_splits 1 and 4) and at the grouped-query
  cells' own decode calls (``GROUPED_CALLS``: LFM2's and Mellum2's full
  and windowed, bfloat16, against the XLA composition in the stated
  arithmetic, error and milliseconds a call logged), and the flash
  kernel (seq 512, forward and both backward kernels) against their
  plain-XLA references, compiled by Mosaic;
* server — ``GenerationEngine`` (8 slots, block 16, 1,024 positions,
  vocabulary 50,257, float32) behind ``InferenceServer``: the program
  family is warmed through ``engine.generate`` BEFORE the scheduler
  thread starts (a cold compile on that thread would trip the 30 s step
  watchdog), then concurrent HTTP requests (JSON and SSE, four prompt
  buckets up to 1,024, greedy and seeded sampling, one prompt that
  reuses a cached prefix) must return exactly their ``max_new_tokens``
  in-vocabulary tokens, equal to the direct ``engine.generate`` streams,
  reproduce under teacher forcing through ``forward_full`` within
  ``LOGIT_MARGIN``, add zero jit traces, and leave every self-healing
  counter in ``/v2/stats`` at zero;
* trainer — ``build_transformer`` in bf16 at sequence 512,
  ``FFModel.compile`` then ``executor.train_batch`` on one fixed batch,
  once data-parallel and once searched: loss finite and falling;
* HLO — the decode, suffix-prefill and train-step programs must hold the
  Mosaic custom call. Serving through the XLA reference is a FAILURE.

Compile seconds are reported apart from run seconds, and the persistent
compile cache (``flexflow_tpu.device.enable_compile_cache``) is reported
cold or warm by its entry count. ``flexflow_tpu/_native/libffcore.so`` is
removed first so the native library is rebuilt from ``native/src``.
"""
from __future__ import annotations

import argparse
import concurrent.futures
import functools
import gc
import importlib.metadata
import json
import os
import pathlib
import sys
import time
import urllib.request

REPO = pathlib.Path(__file__).resolve().parent
sys.path.insert(0, str(REPO))

SEED = 0
SLOTS = 8
# four prefill buckets (16, 128, 512, 1024) shared by six prompts: every
# extra bucket is another 24-layer program to compile cold
PROMPT_LENS = (12, 100, 300, 600, 9, 120)
NEW_TOKENS = 32
# Greedy tokens must be forward_full's argmax or within this many logits
# of it. The two paths differ in attention arithmetic only (the paged
# kernel accumulates scores in float32 on the VPU, forward_full's einsum
# runs XLA's default single bf16 pass). On the v5e 187 of 192 greedy
# tokens were the argmax and the worst gap was 0.0006, on logits whose
# spread is ~0.2 and whose top-two gap averages ~0.04 at this vocabulary;
# the margin is ten times that worst gap.
LOGIT_MARGIN = 0.005
MOSAIC_CALL = "tpu_custom_call"

_T0 = time.monotonic()


def log(msg: str) -> None:
    print(f"[smoke +{time.monotonic() - _T0:7.1f}s] {msg}", flush=True)


def check(ok: bool, what: str) -> None:
    """A failed check ends the run: raise, never print-and-continue."""
    if not ok:
        raise RuntimeError(f"chip_smoke check failed: {what}")


def ssm_slots_check(slots_pair=(64, 192), new_tokens: int = 12, config: dict = None) -> dict:
    """An engine with state-space layers at the Nemotron cell's sizes
    serving the SAME prompts (as many as the smaller count has slots) at
    two slot counts: the streams, and what every state-space layer stores
    for each of them after its last step (a fresh scheduler seats request
    i in slot i at both counts). The two programs differ in shape alone,
    so a stream leaves the other count's only where a near-tie of these
    seeded weights' logits falls the other way (a few in a hundred
    tokens), and for the streams that stay TOGETHER the first layer's
    stored parts differ by roundings that fell the other way (1e-4 of
    their norm) and deeper ones by the bfloat16 activations' noise. What a compiled decode program does to a state it
    updates in place under memory pressure shows in no kernel check and
    on no CPU (from 176 slots on the convolution's rows were
    rematerialised after their update and left shifted twice: PERF.md
    section 6, PR 48): it shows here, as tenths."""
    import jax.numpy as jnp
    import numpy as np

    from benchmark import spec
    from benchmark.reference import nemotron_h
    from flexflow_tpu.generation import GenerationEngine
    from flexflow_tpu.generation.engine import SamplingParams

    cell = spec.load_cell("nemotron-3-super-120b-a12b.reason-gen")
    config, d = config or cell.config, cell.workload["deployment"]
    cfg = nemotron_h.engine_config(config, int(d["max_seq_len"]))
    params = nemotron_h.init_params(SEED + 11, config)
    if cfg.dtype.jnp != jnp.bfloat16:
        params = nemotron_h.cast_params(params, cfg.dtype.jnp)
    rs = np.random.RandomState(SEED + 5)
    n, buckets = min(slots_pair), sorted(d["prompt_buckets"])
    prompts = [rs.randint(0, cfg.vocab_size, rs.randint(buckets[0] // 2, buckets[0])).tolist() for _ in range(n)]
    served = {}
    for slots in slots_pair:
        t0 = time.monotonic()
        eng = GenerationEngine(params, cfg, max_batch_slots=slots, block_size=int(d["block_size"]), prompt_buckets=buckets,
                               max_seq_len=int(d["max_seq_len"]))
        streams = [list(map(int, s)) for s in eng.generate(prompts, SamplingParams(max_new_tokens=new_tokens))]
        # on the host: at the larger count the chip has no room for a second state (the cell peaks at 90 % of it)
        served[slots] = (streams, {name: np.asarray(part[:, :n].astype(jnp.float32)) for name, part in eng.cache.state.items()})
        eng.cache.k = eng.cache.v = None
        eng.cache.state = {}
        del eng
        log(f"ssm engine at {slots} slots: {n} prompts x {new_tokens} tokens in {time.monotonic() - t0:.0f}s")
    (a_streams, a_state), (b_streams, b_state) = (served[s] for s in slots_pair)
    together = np.flatnonzero([a == b for a, b in zip(a_streams, b_streams)])  # (another last token is another state)
    left_at = sorted(next(j for j in range(new_tokens) if a[j] != b[j]) for a, b in zip(a_streams, b_streams) if a != b)
    apart = {name: [float(np.linalg.norm((a_state[name][l, together] - b_state[name][l, together]).ravel())
                          / np.linalg.norm(a_state[name][l, together].ravel())) for l in range(a_state[name].shape[0])] for name in a_state}
    out = {"slots": list(slots_pair), "prompts": n, "tokens_a_stream": new_tokens, "streams_together": int(len(together)),
           "the_others_left_at_token": left_at, "stored_state_apart_by_layer_of_those_together": apart}
    log(f"ssm engine at {slots_pair[0]} against {slots_pair[1]} slots: {out}")
    check(2 * len(together) >= n, f"{n - len(together)} of {n} streams differ between {slots_pair} slots")
    for name, by_layer in apart.items():
        check(by_layer[0] <= 2e-3, f"the first state-space layer's stored {name} lies {by_layer[0]} of its norm apart between {slots_pair} slots")
    return out


# ----------------------------------------------------------------- set-up


def rebuild_native() -> str:
    """Remove the (gitignored, possibly stale) native library so it is
    rebuilt from native/src, and say which host path the run uses."""
    lib = REPO / "flexflow_tpu" / "_native" / "libffcore.so"
    existed = lib.exists()
    if existed:
        lib.unlink()
    try:
        import flexflow_tpu._native as native
    except ImportError:
        return f"pure-Python fallback (native build failed; stale .so removed: {existed})"
    return f"{native.version()} rebuilt from native/src (stale .so removed: {existed})"


def cache_entries(cache_dir: str) -> int:
    p = pathlib.Path(cache_dir)
    return sum(1 for f in p.iterdir() if f.is_file()) if p.is_dir() else 0


def mosaic_calls(fn, *args) -> int:
    """Mosaic custom calls in the program ``fn`` lowers to for ``args``."""
    import jax

    return jax.jit(fn).lower(*args).as_text().count(MOSAIC_CALL)


# ---------------------------------------------------------------- kernels

# The decode calls of the benchmark's grouped-query cells, at the cells'
# sizes (benchmark/workloads/lfm2-8b-a1b.gen-batch.json, mellum2-12b.code-gen.json):
# query heads over K/V heads of ``head_dim``, bfloat16, ``slots`` rows over
# ``columns`` table columns of ``block`` positions, contexts drawn from
# ``contexts``. Mellum2's window layers call with a table that starts at
# the first block a sequence still holds.
GROUPED_CALLS = {
    "lfm2": dict(heads=32, kv_heads=8, head_dim=64, block=16, slots=64, columns=64,
                 layers=4, contexts=(64, 1000), window=0),
    "mellum2_full": dict(heads=32, kv_heads=4, head_dim=128, block=64, slots=48, columns=48,
                         layers=3, contexts=(1100, 2700), window=0),
    "mellum2_window": dict(heads=32, kv_heads=4, head_dim=128, block=64, slots=48, columns=17,
                           layers=9, contexts=(1100, 2700), window=1024),
}


# The decode calls of the long-document cell (benchmark/workloads/
# command-a-plus.long-doc.json): 128 query heads over 8 K/V heads of 128,
# a group of 16, contexts past the window of 4,096; and its prefill's
# streamed attention call at the longest bucket.
GROUP16_CALLS = {
    "command_a_full": dict(heads=128, kv_heads=8, head_dim=128, block=64, slots=16, columns=128,
                           layers=1, contexts=(4700, 7100), window=0),
    "command_a_window": dict(heads=128, kv_heads=8, head_dim=128, block=64, slots=16, columns=65,
                             layers=3, contexts=(4700, 7100), window=4096),
}
# A block-diffusion cell's paged call (benchmark/workloads/sdar-30b-a3b-chat.block-gen.json): a block of 4
# rows a sequence, each at its own position and all attending to the block's last one: 4 window queries x
# group 8 x 4 K/V heads = 128 query rows a sequence.
BLOCK_CALLS = {
    "sdar_block": dict(heads=32, kv_heads=4, head_dim=128, block=64, slots=64, columns=32, layers=7,
                       contexts=(300, 1800), window=0, rows=4),
}
STREAM_CALL = dict(heads=128, kv_heads=8, head_dim=128, seq=6144, length=5900, window=4096)
# The older served cells' longest prefill calls, which stay under the bound and materialise their scores
# (ops/attention.py STREAM_SCORE_BYTES): the streamed kernel's gate takes none of them (head_dim 64, or a
# group that is no whole tile of 16 query heads), so what they could stream through is the XLA chunk scan.
MATERIALISED_CALLS = {
    "prompt_batch": dict(heads=16, kv_heads=16, head_dim=64, seq=1024, length=1000, window=0),
    "gen_batch": dict(heads=32, kv_heads=8, head_dim=64, seq=512, length=500, window=0),
    "code_gen_full": dict(heads=32, kv_heads=4, head_dim=128, seq=2048, length=2000, window=0),
    "code_gen_window": dict(heads=32, kv_heads=4, head_dim=128, seq=2048, length=2000, window=1024),
}


def grouped_call(name: str, seed: int, calls=None, half=False):
    """The arguments of one of ``GROUPED_CALLS`` as the engine's decode
    step would pass them: ``(q, k_cache, v_cache, layer, tables,
    context_lens, first_positions)``. Row 0 is inactive (context 0), row
    1 ends one position into a block. ``half``: contexts of at most half
    the table (a block and up), whose other columns are dead."""
    import jax
    import jax.numpy as jnp
    import numpy as np

    from flexflow_tpu.ops.kernels.decode_attention import cache_row_shape

    c = (calls or GROUPED_CALLS)[name]
    rs = np.random.RandomState(seed)
    b, bs, cols = c["slots"], c["block"], c["columns"]
    nb = b * cols + 1
    shape = (c["layers"], nb, bs, *cache_row_shape(c["kv_heads"], c["head_dim"]))
    kq, kk, kv = jax.random.split(jax.random.key(seed), 3)
    k_cache = jax.random.normal(kk, shape, jnp.bfloat16)
    v_cache = jax.random.normal(kv, shape, jnp.bfloat16)
    q = jax.random.normal(kq, (b, c["heads"], c["head_dim"]), jnp.bfloat16)
    ctx = rs.randint(*((bs, cols * bs // 2) if half else c["contexts"]), size=b)
    ctx[0], ctx[1] = 0, (ctx[1] // bs) * bs + 1
    first = np.zeros(b, np.int64)
    if c["window"]:
        # the blocks wholly behind the window of the LAST position are released
        first = np.maximum(ctx - c["window"], 0) // bs * bs
        check(int(np.max((ctx - 1 - first) // bs)) < cols, f"{name}: a window past its table")
    tables = jnp.asarray(1 + rs.permutation(nb - 1).reshape(b, cols), jnp.int32)
    return (q, k_cache, v_cache, c["layers"] - 1, tables, jnp.asarray(ctx, jnp.int32),
            jnp.asarray(first, jnp.int32))


def stated_paged_attention(q, k_cache, v_cache, layer, tables, ctx, window=0, first_positions=None):
    """The grouped decode call in the arithmetic a bfloat16 configuration
    states (benchmark/reference/mellum2.py ``_attention``, lfm2.py):
    scores summed in float32, a float32 softmax, the probabilities
    rounded to the cache's dtype times V with float32 accumulation. In
    plain XLA over gathered blocks. Returns the float32 result and, per
    element, what the roundings of a bfloat16 computation (8 significant
    bits: unit roundoff 2^-8) can move a kernel's result from it by:
    2^-8 of ``sum p |v|`` for the probabilities' rounding, the kernel's
    (before the normalisation) and this one's (after), and 2^-8 of the
    result for each side's rounding of it."""
    import jax
    import jax.numpy as jnp

    b, h, d = q.shape
    bs = k_cache.shape[2]
    s_max = tables.shape[1] * bs
    k = k_cache[layer, tables].reshape(b, s_max, -1, d)
    v = v_cache[layer, tables].reshape(b, s_max, -1, d)
    qg = q.reshape(b, k.shape[2], -1, d)
    hi = jax.lax.Precision.HIGHEST
    s = jnp.einsum("bhgd,bkhd->bhgk", qg, k, preferred_element_type=jnp.float32, precision=hi) * d ** -0.5
    pos = jnp.arange(s_max)[None, :] + (0 if first_positions is None else first_positions[:, None])
    valid = pos < ctx[:, None]
    if window:
        valid &= pos > ctx[:, None] - 1 - window
    valid = valid[:, None, None, :]
    p = jnp.where(valid, jnp.exp(s - jnp.max(jnp.where(valid, s, -1e30), axis=-1, keepdims=True)), 0.0)
    p = p / jnp.maximum(jnp.sum(p, axis=-1, keepdims=True), 1e-30)
    pv = lambda p_, v_: jnp.einsum("bhgk,bkhd->bhgd", p_, v_, preferred_element_type=jnp.float32, precision=hi)
    out = pv(p.astype(v.dtype), v)
    room = 2.0 ** -7 * (pv(p, jnp.abs(v).astype(jnp.float32)) + jnp.abs(out)) + 1e-6
    return out.reshape(b, h, d), room.reshape(b, h, d)


def _timed_ms(call, args, reps=20) -> float:
    """Milliseconds a call of a compiled ``call`` on the host's clock:
    ``reps`` calls, one wait."""
    import jax

    jax.block_until_ready(call(*args))
    t0 = time.perf_counter()
    for _ in range(reps):
        r = call(*args)
    jax.block_until_ready(r)
    return round((time.perf_counter() - t0) / reps * 1e3, 4)


def _device_trace(call, reps: int, tag: str):
    """``reps`` calls of ``call()`` under the profiler: the trace as
    ``benchmark/trace_reduce.py`` reads it (times on the device's clock)."""
    import shutil

    import jax

    from benchmark import trace_reduce

    trace_dir = REPO / ".bench_trace" / tag
    shutil.rmtree(trace_dir, ignore_errors=True)
    opts = jax.profiler.ProfileOptions()
    opts.python_tracer_level = 0
    jax.profiler.start_trace(str(trace_dir), profiler_options=opts)
    for _ in range(reps):
        r = call()
    jax.block_until_ready(r)
    jax.profiler.stop_trace()
    (xplane,) = sorted(trace_dir.glob("plugins/profile/*/*.xplane.pb"))
    trace = trace_reduce.read_xplane(str(xplane))
    shutil.rmtree(trace_dir, ignore_errors=True)
    return trace


def grouped_kernels_check(calls=None, columns=None) -> dict:
    """The grouped-query decode calls of ``calls`` (``GROUPED_CALLS``), compiled by
    Mosaic, against :func:`stated_paged_attention`: every element inside
    the room the arithmetic leaves, an inactive row exact zeros; the
    kernel's time a call on the host's clock (20 calls, one wait), at the
    cell's contexts and at contexts of half the table and less
    (``<name>_half_table``), with the walk the call ran (table columns a
    grid step, grid steps a call). ``columns``: that many columns a step
    and not the rule's (the sweep's; nothing in the program sets it)."""
    import jax
    import jax.numpy as jnp
    import numpy as np

    from flexflow_tpu.ops.kernels import decode_attention as kernels

    out = {}
    calls = calls or GROUPED_CALLS
    rule = getattr(kernels, "grouped_columns_per_step", None)  # a tree from before PR 43 walks one column a step
    if columns is not None:
        kernels.grouped_columns_per_step = lambda *a, **kw: columns
    try:
        for name, c in calls.items():
            for half in (False, True):
                q, k_cache, v_cache, layer, tables, ctx, first = args = grouped_call(name, SEED, calls, half=half)
                group = kernels.query_group(c["heads"], c["head_dim"], k_cache.shape[3:])
                check(group == c["heads"] // c["kv_heads"], f"{name}: group {group}")
                reason = kernels.paged_kernel_refusal(c["kv_heads"], c["head_dim"], c["block"], group, 2, group=group)
                check(reason is None or columns is not None, f"{name}: the gate refuses the cell's own call: {reason}")

                def over(attend):
                    def run(q, k, v, layer, t, n, first):
                        bounds = {"window": c["window"], "first_positions": first} if c["window"] else {}
                        return attend(q, k, v, layer, t, n, **bounds)
                    return jax.jit(run, static_argnums=3)

                call = over(kernels.paged_decode_attention)
                got = call(*args).astype(jnp.float32)
                want, room = over(stated_paged_attention)(*args)
                err = jnp.abs(got - want)
                worst, of_room = float(jnp.max(err)), float(jnp.max(err / room))
                check(np.isfinite(worst) and of_room <= 1.0,
                      f"{name}: max err {worst}, {of_room:.2f} of the room bfloat16 leaves")
                check(bool(jnp.all(got[0] == 0.0)), f"{name}: an inactive row must emit zeros")
                steps = c["slots"] * c["columns"]  # a tree from before PR 43: a grid step a column
                walk = {"columns_per_step": 1, "grid_steps": steps, "walk_steps_at_most": steps}
                if rule is not None:
                    walk = kernels.paged_walk(c["kv_heads"], c["head_dim"], c["block"], group, 2, group, c["slots"], c["columns"],
                                              kernels.default_kv_splits(c["slots"], c["columns"]))
                key = name + ("_half_table" if half else "")
                out[key] = {
                    "body": kernels.kernel_body(group), "group": group, **walk, "mean_context": float(np.mean(np.asarray(ctx))),
                    "max_abs_err": worst, "err_of_room": round(of_room, 3), "ms_a_call": _timed_ms(call, args),
                }
                log(f"grouped paged kernel {key}: {out[key]}")
                del q, k_cache, v_cache, args, got, want, room, err
    finally:
        if rule is not None:
            kernels.grouped_columns_per_step = rule
    return out


def block_kernel_check(calls=None) -> dict:
    """The grouped paged call at a block's rows (``BLOCK_CALLS``: W = 4
    window queries a sequence, every row's attend bound the block's last
    position), compiled by Mosaic, against ``reference_paged_append_attention``
    in float32 from the same bfloat16 operands: the largest difference
    (bfloat16's roundings of the probabilities and of the result bound
    it), an inactive sequence exact zeros, the gate's answer at the
    cell's shapes, and ms a call."""
    import jax
    import jax.numpy as jnp
    import numpy as np

    from flexflow_tpu.ops.kernels import decode_attention as kernels

    out = {}
    for name, c in (calls or BLOCK_CALLS).items():
        q1, k_cache, v_cache, layer, tables, ctx, _ = grouped_call(name, SEED, calls or BLOCK_CALLS)
        w, group = c["rows"], c["heads"] // c["kv_heads"]
        reason = kernels.paged_kernel_refusal(c["kv_heads"], c["head_dim"], c["block"], w * group, 2, group=group)
        check(reason is None, f"{name}: the gate refuses the cell's own call: {reason}")
        q = jax.random.normal(jax.random.key(SEED + 1), (c["slots"], w, c["heads"], c["head_dim"]), jnp.bfloat16)
        # a block ends at a multiple of the block length; every row attends up to its last position
        last = (jnp.maximum(ctx, w) // w) * w - 1
        bound = jnp.where(ctx[:, None] > 0, jnp.broadcast_to(last[:, None], (c["slots"], w)), -1).astype(jnp.int32)
        args = (q, k_cache, v_cache, tables, bound)
        call = jax.jit(lambda q, k, v, t, p: kernels.paged_append_attention(q, k, v, layer, t, p))
        ref = jax.jit(lambda q, k, v, t, p: kernels.reference_paged_append_attention(
            q.astype(jnp.float32), k.astype(jnp.float32), v.astype(jnp.float32), layer, t, p))
        got, want = call(*args).astype(jnp.float32), ref(*args)
        worst = float(jnp.max(jnp.abs(got - want)))
        scale = float(jnp.max(jnp.abs(want)))
        check(np.isfinite(worst) and worst <= 2.0 ** -6 * max(scale, 1.0), f"{name}: max err {worst} against values up to {scale}")
        check(bool(jnp.all(got[0] == 0.0)), f"{name}: an inactive sequence must emit zeros")
        walk = kernels.paged_walk(c["kv_heads"], c["head_dim"], c["block"], w * group, 2, group, c["slots"], c["columns"], 1)
        out[name] = {"body": kernels.kernel_body(group), "group": group, "query_rows_a_sequence": w * c["heads"], **walk,
                     "mean_context": float(np.mean(np.asarray(ctx))), "max_abs_err": worst, "largest_value": scale,
                     "ms_a_call": _timed_ms(call, args)}
        log(f"block paged kernel {name}: {out[name]}")
    return out


def walk_sweep() -> dict:
    """Every grouped call of the cells at 1, 2, 4 ... 32 table columns a
    grid step (those the compiler takes: VMEM bounds the widest), ms a
    call: what ``GROUPED_STEP_POSITIONS`` and the rule beside it
    (ops/kernels/decode_attention.py) were read from."""
    out = {}
    every = {**GROUPED_CALLS, **GROUP16_CALLS}
    for name, c in every.items():
        for columns in (1, 2, 4, 8, 16, 32):
            if columns > c["columns"]:
                continue
            try:
                got = grouped_kernels_check({name: c}, columns=columns)
            except Exception as e:  # the compiler's refusal (VMEM) is a reading, a failed check is not
                if "chip_smoke check failed" in str(e):
                    raise
                got = {name: {"ms_a_call": "refused: " + str(e)[:120]}}
            for key, v in got.items():
                out.setdefault(key, {})[str(columns)] = v["ms_a_call"]
    for key, v in out.items():
        log(f"walk sweep {key}: ms a call by columns a step {v}")
    return out


# Largest error of a flash kernel's bfloat16 result or gradient over the largest value of float32 attention's on
# the same operands: four roundings of 2^-8 meet on the way (the probabilities and dS as operands, delta's O, the
# result; `flash_public_check` rounds dO too). The v5e reads 0.0022-0.0058 there, causal and not, dv the largest
# (PR 47); at the cells' call 0.0021 (forward), 0.0037 (dq), 0.0035 (dk), 0.0032 (dv).
FLASH_REL_ERR = 2.0 ** -6
FLASH_CALL = dict(batch=16, seq=512, heads=16, head_dim=64)  # a chip's call in both bert-large cells, bfloat16, not causal


def flash_public_check(rs) -> dict:
    """``flash_attention`` as the trainer calls it ([2, 512, 16, 64]
    bfloat16, causal and not, the tree's own blocks), compiled by Mosaic:
    the result and the three gradients within :data:`FLASH_REL_ERR` of
    float32 attention at ``highest``."""
    import jax
    import jax.numpy as jnp
    import numpy as np

    from flexflow_tpu.ops.attention import reference_attention
    from flexflow_tpu.ops.kernels.flash_attention import flash_attention

    out = {}
    q, k, v = (jnp.asarray(rs.randn(2, 512, 16, 64), jnp.bfloat16) for _ in range(3))
    wgt = jnp.asarray(rs.randn(2, 512, 16, 64), jnp.float32)
    for causal in (False, True):
        def loss(attn, q, k, v):
            return jnp.sum(attn(q, k, v, causal=causal).astype(jnp.float32) * wgt)

        def ref_attn(q, k, v, causal):
            with jax.default_matmul_precision("highest"):
                return reference_attention(
                    *(x.astype(jnp.float32) for x in (q, k, v)), causal=causal
                )

        got = jax.jit(jax.value_and_grad(functools.partial(loss, flash_attention), (0, 1, 2)))(q, k, v)
        ref = jax.jit(jax.value_and_grad(functools.partial(loss, ref_attn), (0, 1, 2)))(q, k, v)
        for name, g, r in zip(("dq", "dk", "dv"), got[1], ref[1]):
            r = r.astype(jnp.float32)
            rel = float(jnp.max(jnp.abs(g.astype(jnp.float32) - r)) / jnp.max(jnp.abs(r)))
            check(np.isfinite(rel) and rel <= FLASH_REL_ERR, f"flash causal={causal} {name}: rel err {rel}")
            out[f"flash_causal{int(causal)}_{name}_rel_err"] = rel
        o = flash_attention(q, k, v, causal=causal).astype(jnp.float32)
        want = ref_attn(q, k, v, causal)
        rel = float(jnp.max(jnp.abs(o - want)) / jnp.max(jnp.abs(want)))
        check(np.isfinite(rel) and rel <= FLASH_REL_ERR, f"flash causal={causal} fwd: rel err {rel}")
        out[f"flash_causal{int(causal)}_fwd_rel_err"] = rel
    log(f"flash kernel (seq 512, fwd + dq + dkv) within {FLASH_REL_ERR} of the reference: { {k: round(v, 5) for k, v in out.items()} }")
    return out


def flash_kernels_check(block_pairs=(None,)) -> dict:
    """The three training flash kernels at :data:`FLASH_CALL`, compiled
    by Mosaic, in ONE program (the gradient of the attention of a layer:
    forward, ``delta``, both backward calls, as a train step holds
    them): milliseconds a call of each kernel on the device's clock, out
    of a profiler trace reduced as the benchmark reduces a cell's
    (``benchmark/trace_reduce.py``: what ``flash_attention_roofline``
    reads), the program's milliseconds on the host's clock and the
    seconds it took to lower and compile, at each of ``block_pairs``
    (``None``: the pair the tree's own policy takes). Through
    ``_flash_bhsd``, which a parent commit has under the same name."""
    import jax
    import jax.numpy as jnp
    import numpy as np

    from benchmark import kernel_model, trace_reduce
    from flexflow_tpu.ops.kernels import flash_attention as fa

    c = FLASH_CALL
    rs = np.random.RandomState(SEED)
    q, k, v, do = (jnp.asarray(rs.randn(c["batch"], c["heads"], c["seq"], c["head_dim"]), jnp.bfloat16) for _ in range(4))
    scale = c["head_dim"] ** -0.5
    kernels = kernel_model.FLASH_KERNELS
    out = {"call": c, "policy_blocks": list(fa.effective_blocks(c["seq"], c["seq"]))}
    reps = 20
    for pair in block_pairs:
        bq, bk = pair or out["policy_blocks"]

        def layer(q, k, v):
            return jnp.sum(fa._flash_bhsd(q, k, v, scale, False, bq, bk, False).astype(jnp.float32) * do.astype(jnp.float32))

        t0 = time.perf_counter()
        call = jax.jit(jax.grad(layer, (0, 1, 2))).lower(q, k, v).compile()
        row = {"compile_s": round(time.perf_counter() - t0, 2), "program_ms": _timed_ms(call, (q, k, v), reps=reps)}
        reduced = trace_reduce.reduce_trace(_device_trace(lambda: call(q, k, v), reps, "chip_smoke_flash"), kernels)
        check(all(reduced["kernel_calls"][name] == reps for name in kernels), f"the trace shows {reduced['kernel_calls']}, not {reps} calls of each")
        row.update({name: round(reduced["kernel_s"][name] / reps * 1e3, 4) for name in kernels})
        row["three_ms"] = round(sum(row[name] for name in kernels), 4)
        out[f"{bq}x{bk}"] = row
        log(f"flash kernels at blocks {bq} x {bk}, ms a call on the device's clock: {row}")
    return out


LOSS_CHAIN_CALL = dict(rows=16 * 512, classes=30522)  # a chip's logits in both bert-large cells, bfloat16, a label a row


def loss_chain_check() -> dict:
    """The trainer's vocabulary loss chain alone at :data:`LOSS_CHAIN_CALL`,
    value and gradient in one program, composed
    (``sparse_categorical_crossentropy(softmax(logits))`` under autodiff)
    against fused (``losses.softmax_crossentropy``): milliseconds a call
    on the device's clock (a profiler trace reduced as the benchmark
    reduces a cell's) and on the host's, what share of HBM's peak the
    LEAST traffic of the chain would be at that time (two reads of the
    bfloat16 logits, one write of their gradient), and the errors of the
    value and the gradient against the composed chain on the same logits
    in float32. Alone, the logits and their gradient cross the program's
    boundary in the default layout; a train step picks its own."""
    import jax
    import jax.numpy as jnp
    import numpy as np

    from benchmark import trace_reduce
    from flexflow_tpu.runtime import losses

    c = LOSS_CHAIN_CALL
    rs = np.random.RandomState(SEED)
    logits = jnp.asarray(rs.randn(c["rows"], c["classes"]) * 2.0, jnp.bfloat16)
    labels = jnp.asarray(rs.randint(0, c["classes"], c["rows"]), jnp.int32)
    forms = {
        "composed": lambda x, y: losses.sparse_categorical_crossentropy(jax.nn.softmax(x, axis=-1), y),
        "fused": lambda x, y: losses.softmax_crossentropy(x, y, True),
    }
    ref_value, ref_grad = jax.jit(jax.value_and_grad(forms["composed"]))(logits.astype(jnp.float32), labels)
    least_bytes = 3 * logits.size * logits.dtype.itemsize
    out = {"call": c, "least_bytes": least_bytes}
    reps = 20
    for form, fn in forms.items():
        t0 = time.perf_counter()
        call = jax.jit(jax.value_and_grad(fn)).lower(logits, labels).compile()
        row = {"compile_s": round(time.perf_counter() - t0, 2), "host_ms": _timed_ms(call, (logits, labels), reps=reps)}
        reduced = trace_reduce.reduce_trace(_device_trace(lambda: call(logits, labels), reps, "chip_smoke_loss_chain"), ())
        value, grad = call(logits, labels)
        row["device_ms"] = round(reduced["busy_s"] / reps * 1e3, 4)
        row["device_ops_ms"] = {k: round(v / reps * 1e3, 4) for k, v in reduced["device_ops"][:6]}
        row["least_traffic_gb_per_s"] = round(least_bytes / (row["device_ms"] * 1e-3) / 1e9, 1)
        row["temp_bytes"] = call.memory_analysis().temp_size_in_bytes
        row["value_rel_err"] = float(abs(value - ref_value) / abs(ref_value))
        row["grad_dtype"] = str(grad.dtype)
        row["grad_err_over_max"] = float(jnp.max(jnp.abs(grad.astype(jnp.float32) - ref_grad)) / jnp.max(jnp.abs(ref_grad)))
        out[form] = row
        log(f"loss chain {form} at {c}: {row}")
    # the fused value is float32 from the logits; the composed one rounds the probabilities to bfloat16 first
    check(out["fused"]["value_rel_err"] <= out["composed"]["value_rel_err"] + 1e-7,
          f"the fused value departs further from float32 than the composed one: {out['fused']['value_rel_err']} > {out['composed']['value_rel_err']}")
    check(out["fused"]["grad_err_over_max"] <= 2.0 ** -8, f"the fused gradient is {out['fused']['grad_err_over_max']} of its largest entry from float32")
    check(out["fused"]["grad_dtype"] == "bfloat16", f"the fused gradient is {out['fused']['grad_dtype']}")
    return out


def stream_kernel_check() -> dict:
    """``prefill_stream_attention`` at :data:`STREAM_CALL`, compiled by
    Mosaic, against the XLA composition of the same arithmetic
    (``reference_prefill_stream_attention``) computed in float32 from the
    same bfloat16 operands: windowed and full, the live rows' largest
    error (bfloat16 leaves 2^-8 of a value each side), and both lowerings'
    milliseconds a call on the host's clock."""
    import jax
    import jax.numpy as jnp

    from flexflow_tpu.ops.kernels.flash_attention import (
        prefill_stream_attention, prefill_stream_refusal, reference_prefill_stream_attention,
    )

    c = STREAM_CALL
    kq, kk, kv = jax.random.split(jax.random.key(SEED), 3)
    q = jax.random.normal(kq, (1, c["seq"], c["heads"], c["head_dim"]), jnp.bfloat16)
    k = jax.random.normal(kk, (1, c["seq"], c["kv_heads"], c["head_dim"]), jnp.bfloat16)
    v = jax.random.normal(kv, (1, c["seq"], c["kv_heads"], c["head_dim"]), jnp.bfloat16)
    lens = jnp.asarray([c["length"]], jnp.int32)
    check(prefill_stream_refusal(q.shape, k.shape, 2) is None, "the gate refuses the cell's own prefill call")
    out = {}
    for name, window in (("full", 0), ("window", c["window"])):
        kernel = jax.jit(lambda q, k, v, n, w=window: prefill_stream_attention(q, k, v, n, window=w))
        plain = jax.jit(lambda q, k, v, n, w=window: reference_prefill_stream_attention(q, k, v, n, window=w))
        exact = jax.jit(lambda q, k, v, n, w=window: reference_prefill_stream_attention(
            q.astype(jnp.float32), k.astype(jnp.float32), v.astype(jnp.float32), n, window=w))
        got = kernel(q, k, v, lens)[0, : c["length"]].astype(jnp.float32)
        with jax.default_matmul_precision("highest"):
            want = exact(q, k, v, lens)[0, : c["length"]]
        worst = float(jnp.max(jnp.abs(got - want)))
        check(worst == worst and worst <= 0.05, f"prefill_stream_attention {name}: max err {worst}")
        row = {"max_abs_err": worst}
        for label, call in (("kernel", kernel), ("xla_chunks", plain)):
            jax.block_until_ready(call(q, k, v, lens))
            t0 = time.perf_counter()
            for _ in range(5):
                r = call(q, k, v, lens)
            jax.block_until_ready(r)
            row[f"{label}_ms_a_call"] = round((time.perf_counter() - t0) / 5 * 1e3, 3)
        out[name] = row
        log(f"streamed prefill kernel {name}: {row}")
        del got, want, r
    return out


# The latent layers' expanded prefill call of longcat-flash-chat.agent-turns, at the cell's two buckets past the
# bound: 64 heads with K/V of their own, scores at 192 (qk_nope 128 + qk_rope 64) beside values of 128, bfloat16.
LATENT_STREAM_CALLS = {"prefill[3072]": dict(heads=64, score_width=192, value_width=128, seq=3072, length=2900),
                       "prefill[4096]": dict(heads=64, score_width=192, value_width=128, seq=4096, length=3800)}


def latent_stream_check() -> dict:
    """``prefill_stream_attention`` at :data:`LATENT_STREAM_CALLS` (one
    query head a K/V head, head-major, two widths), compiled by Mosaic,
    against the XLA chunks computed in float32 at highest precision from
    the same bfloat16 operands: the live rows' largest error, and ms a
    call of the kernel as the program calls it (``kernel``: the
    contraction over 192 whole), of the same kernel over q and k
    zero-filled to 256 lanes by the caller (``kernel_padded``: a third
    more score arithmetic, the same sums) and of the XLA chunks."""
    import jax
    import jax.numpy as jnp

    from flexflow_tpu.ops.kernels.flash_attention import (
        prefill_stream_attention, prefill_stream_refusal, reference_prefill_stream_attention,
    )

    out = {}
    for name, c in LATENT_STREAM_CALLS.items():
        kq, kk, kv = jax.random.split(jax.random.key(SEED), 3)
        q = jax.random.normal(kq, (1, c["seq"], c["heads"], c["score_width"]), jnp.bfloat16)
        k = jax.random.normal(kk, (1, c["seq"], c["heads"], c["score_width"]), jnp.bfloat16)
        v = jax.random.normal(kv, (1, c["seq"], c["heads"], c["value_width"]), jnp.bfloat16)
        lens = jnp.asarray([c["length"]], jnp.int32)
        reason = prefill_stream_refusal(q.shape, k.shape, 2, v.shape)
        check(reason is None, f"{name}: the gate refuses the cell's own prefill call: {reason}")

        def padded(q, k, v, n, fill=256 - c["score_width"], scale=c["score_width"] ** -0.5):
            wide = lambda a: jnp.pad(a, ((0, 0), (0, 0), (0, 0), (0, fill)))  # noqa: E731
            return prefill_stream_attention(wide(q), wide(k), v, n, scale=scale)

        calls = {"kernel": jax.jit(prefill_stream_attention), "kernel_padded": jax.jit(padded),
                 "xla_chunks": jax.jit(reference_prefill_stream_attention)}
        exact = jax.jit(lambda q, k, v, n: reference_prefill_stream_attention(
            q.astype(jnp.float32), k.astype(jnp.float32), v.astype(jnp.float32), n))
        with jax.default_matmul_precision("highest"):
            want = exact(q, k, v, lens)[0, : c["length"]]
        row = {}
        for label, call in calls.items():
            got = call(q, k, v, lens)[0, : c["length"]].astype(jnp.float32)
            worst = float(jnp.max(jnp.abs(got - want)))
            check(got.shape == want.shape and worst == worst and worst <= 0.05, f"{name} {label}: max err {worst}")
            row[f"{label}_max_abs_err"] = worst
            t0 = time.perf_counter()
            for _ in range(10):
                r = call(q, k, v, lens)
            jax.block_until_ready(r)
            row[f"{label}_ms_a_call"] = round((time.perf_counter() - t0) / 10 * 1e3, 3)
        out[name] = row
        log(f"streamed latent prefill call {name}: {row}")
        del got, want, r
    return out


def materialised_calls_check() -> dict:
    """The older cells' prefill calls at :data:`MATERIALISED_CALLS`:
    ``masked_attention`` (what they take) against the XLA chunk scan (what
    they would stream through: the kernel's gate refuses each, and the
    line says why), ms a call on the host's clock."""
    import jax
    import jax.numpy as jnp

    from flexflow_tpu.ops.attention import masked_attention
    from flexflow_tpu.ops.kernels.flash_attention import prefill_stream_refusal, reference_prefill_stream_attention

    out = {}
    for name, c in MATERIALISED_CALLS.items():
        kq, kk, kv = jax.random.split(jax.random.key(SEED), 3)
        q = jax.random.normal(kq, (1, c["seq"], c["heads"], c["head_dim"]), jnp.bfloat16)
        k = jax.random.normal(kk, (1, c["seq"], c["kv_heads"], c["head_dim"]), jnp.bfloat16)
        v = jax.random.normal(kv, (1, c["seq"], c["kv_heads"], c["head_dim"]), jnp.bfloat16)
        lens = jnp.asarray([c["length"]], jnp.int32)
        row = {"kernel_refuses": prefill_stream_refusal(q.shape, k.shape, 2), "score_mib": 4 * c["heads"] * c["seq"] ** 2 >> 20}
        check(row["kernel_refuses"] is not None, f"{name}: the streamed kernel takes this shape, and the table says it does not")
        calls = {"masked_attention": jax.jit(lambda q, k, v, n, w=c["window"]: masked_attention(q, k, v, n, causal=True, window=w)),
                 "xla_chunks": jax.jit(lambda q, k, v, n, w=c["window"]: reference_prefill_stream_attention(q, k, v, n, window=w))}
        for label, call in calls.items():
            jax.block_until_ready(call(q, k, v, lens))
            t0 = time.perf_counter()
            for _ in range(20):
                r = call(q, k, v, lens)
            jax.block_until_ready(r)
            row[f"{label}_ms_a_call"] = round((time.perf_counter() - t0) / 20 * 1e3, 3)
        out[name] = row
        log(f"materialised prefill call {name}: {row}")
    return out


# The latent layers' decode call of the benchmark's latent cell, at the
# cell's sizes (benchmark/workloads/joyai-llm-flash.long-gen.json): 32
# heads over ONE row a position, 576 values stored at 640 lanes, the
# values its first 512 columns, bfloat16.
LATENT_CALL = dict(heads=32, width=576, value_width=512, score_width=192, block=64, slots=32, columns=48,
                   layers=4, contexts=(1280, 2816))
# ... and of the cell whose latent layers have 64 heads (longcat-flash-chat.agent-turns: 24 slots, tables of 72
# columns, contexts 2,176-4,608, 8 sub-layers' rows in the array): `--latent 64`
LATENT_CALLS = {32: LATENT_CALL, 64: dict(LATENT_CALL, heads=64, slots=24, columns=72, layers=8, contexts=(2176, 4608))}


def latent_kernel_check(c=None) -> dict:
    """``paged_latent_attention`` at :data:`LATENT_CALL` (or the call ``c``), compiled by
    Mosaic, against the XLA composition in the stated arithmetic
    (``reference_paged_latent_attention``: bfloat16 queries and rows,
    float32 scores and softmax, the probabilities rounded to bfloat16
    times the rows' first 512 columns, float32 accumulation), computed
    here in float32 with the room bfloat16's roundings leave as
    :func:`stated_paged_attention` reckons it; an inactive row exact
    zeros; both lowerings' time a call on the host's clock."""
    import jax
    import jax.numpy as jnp
    import numpy as np

    from flexflow_tpu.ops.kernels.decode_attention import (
        latent_kernel_refusal, latent_row_width, paged_latent_attention, reference_paged_latent_attention,
    )

    c = c or LATENT_CALL
    rs = np.random.RandomState(SEED)
    b, bs, cols, rw, vw = c["slots"], c["block"], c["columns"], latent_row_width(c["width"]), c["value_width"]
    nb = b * cols + 1
    check(latent_kernel_refusal(c["heads"], rw, bs, 2) is None, "latent: the gate refuses the cell's own call")
    kq, kc = jax.random.split(jax.random.key(SEED))
    live = (jnp.arange(rw) < c["width"])  # the fill is zero, in the rows and in the queries
    cache = jnp.where(live, jax.random.normal(kc, (c["layers"], nb, bs, rw), jnp.bfloat16), 0).astype(jnp.bfloat16)
    q = jnp.where(live, jax.random.normal(kq, (b, 1, c["heads"], rw), jnp.bfloat16), 0).astype(jnp.bfloat16)
    ctx = rs.randint(*c["contexts"], size=b)
    ctx[0], ctx[1] = 0, (ctx[1] // bs) * bs + 1
    tables = jnp.asarray(1 + rs.permutation(nb - 1).reshape(b, cols), jnp.int32)
    positions = jnp.asarray(ctx - 1, jnp.int32)[:, None]
    layer, scale = c["layers"] - 1, c["score_width"] ** -0.5

    def stated(q, cache, tables, positions):
        rows = cache[layer, tables].reshape(b, cols * bs, rw)
        hi = jax.lax.Precision.HIGHEST
        s = jnp.einsum("bwhc,bkc->bhwk", q, rows, preferred_element_type=jnp.float32, precision=hi) * scale
        valid = jnp.arange(cols * bs)[None, None, None, :] <= positions[:, None, :, None]
        p = jnp.where(valid, jnp.exp(s - jnp.max(jnp.where(valid, s, -1e30), axis=-1, keepdims=True)), 0.0)
        p = p / jnp.maximum(jnp.sum(p, axis=-1, keepdims=True), 1e-30)
        pv = lambda p_, v_: jnp.einsum("bhwk,bkc->bwhc", p_, v_, preferred_element_type=jnp.float32, precision=hi)  # noqa: E731
        out = pv(p.astype(rows.dtype), rows[..., :vw])
        return out, 2.0 ** -7 * (pv(p, jnp.abs(rows[..., :vw]).astype(jnp.float32)) + jnp.abs(out)) + 1e-6

    kernel = jax.jit(lambda q, c_, t, p: paged_latent_attention(q, c_, layer, t, p, vw, scale))
    composed = jax.jit(lambda q, c_, t, p: reference_paged_latent_attention(q, c_, layer, t, p, vw, scale))
    args = (q, cache, tables, positions)
    got = kernel(*args).astype(jnp.float32)
    want, room = jax.jit(stated)(*args)
    err = jnp.abs(got - want)
    worst, of_room = float(jnp.max(err)), float(jnp.max(err / room))
    check(np.isfinite(worst) and of_room <= 1.0, f"latent: max err {worst}, {of_room:.2f} of the room bfloat16 leaves")
    check(bool(jnp.all(got[0] == 0.0)), "latent: an inactive row must emit zeros")
    text = kernel.lower(*args).compile().as_text()
    check("paged_latent_attention" in text, "the latent call's Mosaic custom call is not in its program")
    ms = {}
    for name, call in (("kernel", kernel), ("xla_composition", composed)):
        jax.block_until_ready(call(*args))
        t0 = time.perf_counter()
        for _ in range(20):
            r = call(*args)
        jax.block_until_ready(r)
        ms[name] = round((time.perf_counter() - t0) / 20 * 1e3, 4)
    read = float(np.sum(ctx)) * c["width"] * 2
    out = {"max_abs_err": worst, "err_of_room": round(of_room, 3), "ms_a_call": ms,
           "rows_read_gb": round(read / 1e9, 4), "gb_per_s": round(read / 1e9 / (ms["kernel"] / 1e3), 1)}
    log(f"latent paged kernel at the cell's sizes ({c['heads']} heads, {b} slots x {cols} columns): {out}")
    return out


def ssm_kernel_check(slots_list=(64, 128, 192)) -> dict:
    """The state-space decode update (``ops/ssm.py::update``) at the
    Nemotron cell's sizes (5 layers of state [slots, 64, 128, 128]
    float32): the Pallas call ``ssm_state_update`` against its XLA
    composition (results and the state, which both update in place on a
    donated array), ms a call of each on the host's clock over 20 calls
    of ONE layer, and GB/s of state moved (read + written)."""
    import jax
    import jax.numpy as jnp

    from flexflow_tpu.ops import ssm

    h, p, g, n, layers = 128, 64, 8, 128, 5
    out = {}
    for slots in slots_list:
        keys = jax.random.split(jax.random.key(SEED + slots), 6)
        shape = (layers, slots) + ssm.state_shape(h, p, g, n)
        x = jax.random.normal(keys[0], (slots, h, p), jnp.bfloat16)
        dt = jax.nn.softplus(jax.random.normal(keys[1], (slots, h)) - 4.0)
        a = -jnp.arange(1, h + 1, dtype=jnp.float32)
        b, c = (jax.random.normal(k, (slots, g, n), jnp.bfloat16) for k in keys[2:4])
        calls = {
            "kernel": jax.jit(lambda st, *r: ssm.update(st, 2, *r, backend="tpu"), donate_argnums=(0,)),
            "xla_composition": jax.jit(lambda st, *r: ssm.update_reference(st, 2, *r), donate_argnums=(0,)),
        }
        got, ms = {}, {}
        for name, call in calls.items():
            state = jax.random.normal(keys[4], shape, jnp.float32)
            y, state = jax.block_until_ready(call(state, x, dt, a, b, c))
            got[name] = (y, state[2], state[1])
            t0 = time.perf_counter()
            for _ in range(20):
                y, state = call(state, x, dt, a, b, c)
            jax.block_until_ready(state)
            ms[name] = round((time.perf_counter() - t0) / 20 * 1e3, 4)
            del state
        err_y = float(jnp.max(jnp.abs(got["kernel"][0] - got["xla_composition"][0])))
        err_s = float(jnp.max(jnp.abs(got["kernel"][1] - got["xla_composition"][1])))
        untouched = bool(jnp.all(got["kernel"][2] == got["xla_composition"][2]))
        scale = float(jnp.max(jnp.abs(got["xla_composition"][0])))
        check(err_y <= 1e-4 * scale + 1e-5 and err_s <= 1e-5, f"ssm update at {slots} slots: y differs by {err_y} (of {scale}), the state by {err_s}")
        check(untouched, "ssm update: another layer's state moved")
        text = calls["kernel"].lower(jax.ShapeDtypeStruct(shape, jnp.float32), x, dt, a, b, c).compile().as_text()
        check("ssm_state_update" in text, "the update's Mosaic custom call is not in its program")
        moved = 2 * 4 * slots * h * p * n
        out[str(slots)] = {"ms_a_call": ms, "max_abs_err_y": err_y, "max_abs_err_state": err_s, "state_moved_gb": round(moved / 1e9, 4),
                           "gb_per_s": {k: round(moved / 1e9 / (v / 1e3), 1) for k, v in ms.items()}}
        log(f"ssm state update at {slots} slots: {out[str(slots)]}")
        del got
    return out


# One expert layer of each expert configuration as its cell holds it
# (benchmark/configs/*.json): the routed sum's two lowerings are timed over
# these, and `expert_form`'s constants come from the table this prints.
EXPERT_LAYERS = {
    "lfm2": dict(hidden=2048, width=1792, experts=32, held=32, k=4, router="sigmoid"),
    "mellum2": dict(hidden=2304, width=896, experts=64, held=64, k=8, router="softmax"),
    "joyai": dict(hidden=2048, width=768, experts=256, held=16, k=8, router="sigmoid"),
    "command_a": dict(hidden=4096, width=4096, experts=128, held=16, k=8, router="sigmoid", rows=(16, 5120, 6144)),
    # JoyAI's share at the row counts the rule's second entry moves (no bucket of its cell is that long)
    "joyai_long": dict(hidden=2048, width=768, experts=256, held=16, k=8, router="sigmoid", rows=(4096, 6144)),
    # LongCat's routed branch: 16 held of 512 experts beside 256 identity experts (768 outputs), gates 6 p_j unrenormalised
    "longcat": dict(hidden=6144, width=2048, experts=512, zero=256, held=16, k=12, router="softmax", rows=(16, 64, 1024, 4096)),
    # SDAR's layer, every expert held: a block step of 32 / 48 / 64 slots has 128 / 192 / 256 rows, a prefill 1,024
    "sdar": dict(hidden=2048, width=768, experts=128, held=128, k=8, router="softmax", rows=(128, 192, 256, 1024)),
    # Nemotron-3-Super's routed experts as they lie in the latent: ungated relu^2, 1,024 -> 2,688 -> 1,024, 128 held of 512,
    # k 22 (5.5 of a row's picks land here); a decode step of 64 / 128 / 192 slots, a prefill of 512 / 1,024 rows. (The
    # router here reads the 1,024-wide rows too; the model's reads the 4,096-wide ones: 2 M more weights in both forms)
    "nemotron": dict(hidden=1024, width=2688, experts=512, held=128, k=22, router="sigmoid", ungated=True, rows=(64, 128, 192, 512, 1024)),
}
EXPERT_ROWS = (32, 64, 256, 512, 1024, 1536, 2048)


def expert_product_check(names=()) -> dict:
    """The routed experts' sum of ONE layer (``decoder.expert_ffn``) at
    :data:`EXPERT_LAYERS` over :data:`EXPERT_ROWS` rows, compiled: the
    dense form, the grouped form with the product the program keeps
    (``megablox.gmm``) and with the other candidate (``lax.ragged_dot``),
    ms a call each on the host's clock, and each one's largest
    difference from the float32 composition (the same sum at ``highest``
    from the same bfloat16 weights), which bfloat16's roundings of the
    hidden product and of the result bound, and from the dense form's
    result (``from_dense``: the largest, and the share of elements that
    differ at all). Where the rule
    (``ops/expert_product.py::expert_form``) picks the grouped form it
    must not be more than a tenth slower than the dense one; where the
    rule keeps the dense form and the grouped one reads ahead, the line
    says so (``left``) and nothing fails: the rule's row count is the
    lowest at which every configuration's grouped form is well ahead."""
    import jax
    import jax.numpy as jnp

    from flexflow_tpu.generation import decoder
    from flexflow_tpu.ops import expert_product

    rule = expert_product.expert_form
    hi = jax.lax.Precision.HIGHEST

    def ragged(lhs, rhs, sizes):
        return jax.lax.ragged_dot(lhs, rhs, sizes, preferred_element_type=jnp.float32)

    out = {}
    for name, c in EXPERT_LAYERS.items():
        if names and name not in names:
            continue
        held = None if c["held"] == c["experts"] else tuple(range(c["held"]))
        cfg = decoder.DecoderConfig(
            num_layers=1, hidden_size=c["hidden"], num_heads=16, ff_size=c["width"], seq_length=64, vocab_size=128,
            num_dense_layers=0, num_experts=c["experts"], experts_per_token=c["k"], moe_ff_size=c["width"],
            router=c["router"], experts_held=held or (), expert_activation="relu2" if c.get("ungated") else "swiglu",
            **(dict(zero_experts=c["zero"], router_softmax_bias=True, router_renormalise=False, routed_scaling_factor=6.0)
               if c.get("zero") else {}))
        outputs = cfg.router_outputs
        shapes = dict(router=(c["hidden"], outputs), router_bias=(outputs,),
                      ew1=(c["held"], c["hidden"], c["width"]), ew3=(c["held"], c["hidden"], c["width"]),
                      ew2=(c["held"], c["width"], c["hidden"]))
        if c.get("ungated"):  # W2 relu(W1 v)^2: no third matrix
            del shapes["ew3"]
        keys = dict(zip(shapes, jax.random.split(jax.random.key(SEED), len(shapes))))
        layer = {k: jax.random.normal(keys[k], s, jnp.float32) * (s[-2] if len(s) > 1 else 2500.0) ** -0.5
                 for k, s in shapes.items()}
        layer = {k: a if k.startswith("router") else a.astype(jnp.bfloat16) for k, a in layer.items()}

        def traced_as(form):
            def call(layer, v, live=None):
                expert_product.expert_form = lambda *shape: form  # the rule, held still while this traces
                try:
                    return decoder.expert_ffn(cfg, layer, v, held=held, live=live)[0]
                finally:
                    expert_product.expert_form = rule
            return jax.jit(call)

        def other_candidate(layer, v):
            gates, chosen = decoder.route(cfg, layer, v)
            identity = jnp.sum(gates[:, cfg.num_experts:], axis=1) if cfg.zero_experts else None
            return expert_product.grouped_expert_sum(
                v, gates, chosen, layer["ew1"], layer.get("ew3"), layer["ew2"], held=held, product=ragged, identity=identity)

        def composition(layer, v):
            gates, _ = decoder.route(cfg, layer, v)
            mine = gates if held is None else gates[:, jnp.asarray(held)]
            x = v.astype(jnp.float32)
            start = jnp.sum(gates[:, cfg.num_experts:], axis=1)[:, None] * x  # the identity experts' term (none: zeros)

            def one(acc, w):
                w1, w3, w2, g = w
                up, gate_up = (jnp.dot(x, m.astype(jnp.float32), precision=hi) for m in (w1, w3))
                return acc + jnp.dot(jax.nn.silu(up) * gate_up * g[:, None], w2.astype(jnp.float32), precision=hi), None

            def one_ungated(acc, w):
                w1, w2, g = w
                up = jnp.dot(x, w1.astype(jnp.float32), precision=hi)
                return acc + jnp.dot(jnp.square(jax.nn.relu(up)) * g[:, None], w2.astype(jnp.float32), precision=hi), None
            if "ew3" not in layer:
                return jax.lax.scan(one_ungated, start, (layer["ew1"], layer["ew2"], mine.T))[0]
            return jax.lax.scan(one, start, (layer["ew1"], layer["ew3"], layer["ew2"], mine.T))[0]

        forms = {"dense": traced_as("dense"), "grouped": traced_as("grouped"), "grouped_ragged_dot": jax.jit(other_candidate)}
        for rows in c.get("rows", EXPERT_ROWS):
            v = jax.random.normal(jax.random.key(rows), (rows, c["hidden"]), jnp.bfloat16)
            want = jax.jit(composition)(layer, v)
            line = {"rule": rule(rows, c["held"], c["k"], outputs), "largest_value": float(jnp.max(jnp.abs(want)))}
            for form, call in forms.items():
                try:
                    got = jax.block_until_ready(call(layer, v))
                except Exception as e:  # noqa: BLE001 - a candidate the compiler refuses is a finding, not a failure
                    check(form != line["rule"], f"experts {name} rows {rows}: the form the rule picks does not compile: {e}")
                    line[form] = f"refused: {str(e)[:160]}"
                    continue
                t0 = time.perf_counter()
                for _ in range(20):
                    got = call(layer, v)
                jax.block_until_ready(got)
                took = round((time.perf_counter() - t0) / 20 * 1e3, 4)
                got = got.astype(jnp.float32)
                dense = got if form == "dense" else dense
                line[form] = {"ms": took,
                              "max_abs_err": float(jnp.max(jnp.abs(got - want))),
                              # (the same roundings at the same points: the last cast lands one step apart at most)
                              "from_dense": {"max_abs": float(jnp.max(jnp.abs(got - dense))), "share_differing": float(jnp.mean(got != dense))}}
                # two bfloat16 roundings (the hidden product, the result): 2**-7 of the largest value, with room
                check(line[form]["max_abs_err"] <= 2.0 ** -6 * line["largest_value"] + 1e-6,
                      f"experts {name} rows {rows} {form}: {line[form]['max_abs_err']} from the float32 composition")
            if isinstance(line["grouped"], dict):
                # a prompt that fills 85 % of the bucket: the grouped form skips the rows behind it and hands back zeros
                # there; the rows ahead of it are the dense form's (the kernel then stops short of its last row tiles)
                n = int(0.85 * rows)
                got = forms["grouped"](layer, v, jnp.arange(rows) < n).astype(jnp.float32)
                line["grouped"]["padded"] = {"max_abs_from_dense": float(jnp.max(jnp.abs(got[:n] - dense[:n]))),
                                             "share_differing": float(jnp.mean(got[:n] != dense[:n])),
                                             "zeros_behind": not bool(jnp.any(got[n:]))}
                check(line["grouped"]["padded"]["zeros_behind"] and
                      line["grouped"]["padded"]["max_abs_from_dense"] <= 2.0 ** -6 * line["largest_value"] + 1e-6,
                      f"experts {name} rows {rows} grouped, {n} rows live: {line['grouped']['padded']}")
            ms = {form: line[form]["ms"] for form in ("dense", "grouped") if isinstance(line[form], dict)}
            check(line["rule"] == "dense" or ms["grouped"] <= 1.1 * ms["dense"], f"experts {name} rows {rows}: the rule picks grouped at {ms}")
            line["left"] = round(ms["dense"] - min(ms.values()), 4)  # ms a layer the rule leaves where it keeps the dense form
            out[f"{name}[{rows}]"] = line
            log(f"expert product {name} rows {rows}: {line}")
    return out


# The drop of a consumed decode step's device arrays (PERF.md §6, PR 37 and
# PR 38): a stand-in step program of about a decode step's device time, its
# small results dropped where the scheduler drops them, and beside them what
# a serving process adds: one thread a stream, woken by the step's tokens.
RELEASE_PROBE = dict(width=4096, rows=2048, step_ms=20.0, reps=40, streams=64, stream_ms=0.17)


def release_probe(width=None, rows=None, step_ms=None, reps=None, streams=None, stream_ms=None) -> dict:
    """What the scheduler thread waits for when it drops a consumed
    step's ``InFlightDecode``. A step program of ``step_ms`` of device
    time (matrix products in a loop; a donated ``cache``; results
    ``out`` int32 and ``ok`` bool of one element a row, ``out`` carried
    into the next call as the engine carries its tokens) runs back to
    back, and the drop of a FINISHED step's ``out`` and ``ok`` (host
    copies started at dispatch and read, as ``consume_decode`` does) is
    timed, ms a drop over ``reps`` drops:

    * ``a_idle``: nothing in flight;
    * ``b_in_flight``: its successor, which reads ``out``, in flight, on
      the dispatching thread; ``b_held_longer``: the successor's
      successor in flight, which does not read it (PR 37's experiment);
    * ``c_second_thread``: as (b), the arrays handed to a second thread
      through a ``SimpleQueue`` while the first spins in Python:
      the second thread's drop, ``c_first_thread_stall`` the first's
      longest stall between two clock reads, ``c_first_thread_put`` its
      hand-over;
    * ``d_donated``: the reference to the ``cache`` that was donated to
      the program in flight.

    And with ``streams`` threads parked each on a ``queue.Queue`` of its
    own, as the server's stream handlers are, each of which does
    ``stream_ms`` of Python a token: every thread is given a token (the
    bookkeeping's ``_emit``) and then the same drop is timed,
    ``e_streams_in_flight`` beside a program in flight and
    ``f_streams_idle`` beside none; ``g_streams_sleep0`` times
    ``time.sleep(0)`` there instead of a drop (the interpreter's lock
    given up and taken back, no device array in sight), ``h_streams_numpy``
    the drop of two numpy arrays (the lock is kept), and
    ``i_streams_spin`` how long the dropping thread runs on in pure
    Python, holding its references, before the streams' threads have
    all had their turn (the interpreter's switch interval at work).

    Last, threads that do for a token what the server's stream handler
    does (``serving/server.py``: a ``get`` with a timeout on the
    handle's queue, the event's JSON and text, its bytes to a socket)
    and the same drop after a token to each, nothing in flight:
    ``j_handlers_queue`` on a ``queue.Queue`` with the event's ``id:``
    and ``data:`` lines in a ``sendall`` each (the server up to PR 37),
    ``k_handlers_simple_queue`` the same on a ``queue.SimpleQueue``
    (since PR 38), ``l_handlers_simple_queue_one_write`` with one
    ``sendall`` an event besides (not taken: it read no gain in the
    cell): what a token costs the thread that gives up the lock is its
    handlers' turns at it."""
    import queue
    import statistics
    import threading

    import jax
    import jax.numpy as jnp
    import numpy as np

    c = dict(RELEASE_PROBE)
    c.update({k: v for k, v in dict(width=width, rows=rows, step_ms=step_ms, reps=reps, streams=streams,
                                    stream_ms=stream_ms).items() if v is not None})
    n_rows, wd = c["rows"], c["width"]
    w = (jax.random.normal(jax.random.key(SEED), (wd, wd), jnp.float32) * wd ** -0.5).astype(jnp.bfloat16)

    @functools.partial(jax.jit, donate_argnums=(1,))
    def step(w, cache, tok, n):
        x = jnp.broadcast_to(((tok % 13).astype(jnp.bfloat16) * 0.1)[:, None], (n_rows, wd))
        x = jax.lax.fori_loop(0, n, lambda _, x: jnp.tanh(jnp.dot(x, w, preferred_element_type=jnp.float32)).astype(jnp.bfloat16), x)
        out = jnp.argmax(x, axis=-1).astype(jnp.int32)
        return out, jnp.isfinite(x.astype(jnp.float32)).all(axis=-1), cache.at[0].add(1)

    cache = jnp.zeros((256, 1024, 128), jnp.bfloat16)  # 64 MiB, donated and aliased call after call
    tok0 = jnp.zeros((n_rows,), jnp.int32)

    def dispatch(tok, n):
        nonlocal cache
        old = cache
        out, ok, cache = step(w, cache, tok, n)
        out.copy_to_host_async()
        ok.copy_to_host_async()
        return [out, ok], old

    def consume(h):
        jax.block_until_ready(h)
        np.asarray(h[1]), np.asarray(h[0])

    # the loop count that gives step_ms of device time
    n = jnp.int32(16)
    consume(dispatch(tok0, n)[0])
    t0 = time.perf_counter()
    consume(dispatch(tok0, n)[0])
    n = jnp.int32(max(1, round(16 * c["step_ms"] / ((time.perf_counter() - t0) * 1e3))))
    t0 = time.perf_counter()
    for _ in range(5):
        consume(dispatch(tok0, n)[0])
    program_ms = (time.perf_counter() - t0) / 5 * 1e3

    def timed_drop(h):
        t0 = time.perf_counter()
        h.clear()
        return (time.perf_counter() - t0) * 1e3

    times = {}

    def note(name, ms):
        times.setdefault(name, []).append(ms)

    # ---- the streams' threads: parked on a queue each, woken with a token each
    body = {"token": 12345, "text": "x" * 24, "index": 7}
    t0 = time.perf_counter()
    for _ in range(2000):
        json.dumps(body)
    per_token = max(1, round(c["stream_ms"] * 1e-3 / ((time.perf_counter() - t0) / 2000)))

    def streams(queue_kind=queue.Queue, writes=0):
        """``streams`` threads, each parked on a queue of ``queue_kind``;
        for a token one does ``stream_ms`` of Python (``writes`` 0) or what
        the server's handler does, the event's lines in ``writes`` calls
        of ``sendall``. Returns (a token to each, wait for every turn, stop)."""
        import socket

        boxes, done, socks = [queue_kind() for _ in range(c["streams"])], threading.Semaphore(0), []

        def serve(box, sock):
            count = 0
            while (tok := box.get(timeout=600)) is not None:
                if writes == 0:
                    for _ in range(per_token):
                        json.dumps(body)
                else:
                    lines = [f"id: {count}\n", f"data: {json.dumps({'token': int(tok), 'index': count})}\n\n"]
                    for part in (lines if writes == 2 else ["".join(lines)]):
                        sock.sendall(part.encode())
                count += 1
                done.release()

        mine = []
        for box in boxes:
            a, b = socket.socketpair()  # the far end is never read: a run's events fit its buffer
            socks += [a, b]
            mine.append(threading.Thread(target=serve, args=(box, a), daemon=True))
            mine[-1].start()

        def emit_all():
            for box in boxes:
                box.put(12345)

        def wait_all():
            for _ in boxes:
                done.acquire()

        def stop():
            for box in boxes:
                box.put(None)
            for t in mine:
                t.join(5)
            for sock in socks:
                sock.close()

        return emit_all, wait_all, stop

    emit, all_had_their_turn, stop_streams = streams()

    reaper_in, reaper_out = queue.SimpleQueue(), queue.SimpleQueue()

    def reaper():
        while True:
            h = reaper_in.get()
            if h is None:
                return
            reaper_out.put(timed_drop(h))

    second = threading.Thread(target=reaper, daemon=True)
    second.start()

    for rep in range(c["reps"]):
        # (a) nothing in flight
        h0, _ = dispatch(tok0, n)
        consume(h0)
        note("a_idle", timed_drop(h0))
        # (b) the successor, which reads out, in flight
        h0, _ = dispatch(tok0, n)
        h1, _ = dispatch(h0[0], n)
        consume(h0)
        note("b_in_flight", timed_drop(h0))
        # (b') held one step longer: the successor's successor in flight
        h2, _ = dispatch(h1[0], n)
        consume(h1)
        h3, _ = dispatch(h2[0], n)
        consume(h2)
        note("b_held_longer", timed_drop(h1))
        # (c) on a second thread, the first spinning
        h4, _ = dispatch(h3[0], n)
        consume(h3)
        t0 = time.perf_counter()
        reaper_in.put(h3)
        del h3
        last = time.perf_counter()
        note("c_first_thread_put", (last - t0) * 1e3)
        stall = 0.0
        while reaper_out.empty():
            now = time.perf_counter()
            stall, last = max(stall, now - last), now
        note("c_second_thread", reaper_out.get())
        note("c_first_thread_stall", stall * 1e3)
        # (d) the donated cache's old reference, its program in flight
        h5, old = dispatch(h4[0], n)
        old = [old]
        note("d_donated", timed_drop(old))
        consume(h4), consume(h5)
        del h2, h4
        # (e) the streams woken, a program in flight
        h6, _ = dispatch(h5[0], n)
        h7, _ = dispatch(h6[0], n)
        consume(h6)
        emit()
        note("e_streams_in_flight", timed_drop(h6))
        all_had_their_turn()
        consume(h7)
        # (f) the streams woken, nothing in flight
        emit()
        note("f_streams_idle", timed_drop(h7))
        all_had_their_turn()
        # (g) the lock given up without a device array, (h) a drop that keeps it
        emit()
        t0 = time.perf_counter()
        time.sleep(0)
        note("g_streams_sleep0", (time.perf_counter() - t0) * 1e3)
        all_had_their_turn()
        arrays = [np.zeros((n_rows,), np.int32), np.zeros((n_rows,), bool)]
        emit()
        note("h_streams_numpy", timed_drop(arrays))
        all_had_their_turn()
        # (i) pure Python after the tokens went out: until the first stall of over a millisecond
        emit()
        t0 = last = time.perf_counter()
        ran = None
        while time.perf_counter() - t0 < 0.05:
            now = time.perf_counter()
            if ran is None and now - last > 1e-3:
                ran = (last - t0) * 1e3
            last = now
        note("i_streams_spin", 50.0 if ran is None else ran)
        all_had_their_turn()
        del h0, h1, h5, h6, h7
    reaper_in.put(None)
    second.join(5)
    stop_streams()
    # (j, k, l) the server's handlers, one kind awake at a time
    for name, queue_kind, writes in (("j_handlers_queue", queue.Queue, 2), ("k_handlers_simple_queue", queue.SimpleQueue, 2),
                                     ("l_handlers_simple_queue_one_write", queue.SimpleQueue, 1)):
        emit_all, wait_all, stop = streams(queue_kind, writes)
        for rep in range(c["reps"]):
            h, _ = dispatch(tok0, n)
            consume(h)
            emit_all()
            note(name, timed_drop(h))
            wait_all()
        stop()

    def summary(v):
        v = sorted(v)
        return {"median": round(statistics.median(v), 4), "p90": round(v[int(0.9 * (len(v) - 1))], 4),
                "max": round(v[-1], 4), "n": len(v)}

    out = {"program_ms": round(program_ms, 3), "loop_count": int(n), "streams": c["streams"],
           "stream_python_ms_a_token": c["stream_ms"], "switch_interval_ms": sys.getswitchinterval() * 1e3,
           "ms_a_drop": {k: summary(v) for k, v in times.items()}}
    log(f"release probe: {json.dumps(out)}")
    return out


def evict_probe(sizes=(1000, 7400, 15000), evictions: int = 100, ticks: int = 200) -> dict:
    """What the prefix index costs the scheduler's thread on this host
    (no device work): ms an eviction (``reclaim(1)``, the victim
    dropped) and ms a pressure tick (``CacheTelemetry.tick``, once a
    scheduler iteration) at ``sizes`` entries, every one resident and
    unreferenced — ``heap``, the index as it is, beside ``scan``, the
    same index finding its victim and its count by a walk over every
    entry, written out here."""
    from flexflow_tpu.generation.cache import BlockAllocator, CacheConfig
    from flexflow_tpu.generation.prefix import PrefixCache
    from flexflow_tpu.obs.capacity import CacheTelemetry

    class Scan(PrefixCache):
        @property
        def evictable_blocks(self):
            with self._lock:
                return sum(1 for e in self._by_id.values() if e.resident and e.refs == 0)

        def _pop_victim(self):
            cands = [e for e in self._by_id.values() if e.resident and e.refs == 0]
            return min(cands, key=lambda e: (e.last_touch, -e.depth)) if cands else None

    out = {}
    for n in sizes:
        out[str(n)] = {}
        for name, index in (("scan", Scan), ("heap", PrefixCache)):
            config = CacheConfig(num_layers=1, num_heads=1, head_dim=64, num_blocks=n + 1, block_size=16)
            alloc = BlockAllocator(config)
            pc = index(alloc, config)
            held = []
            for i in range(n // 4):  # chains of four blocks, as a 64-token prompt leaves
                prompt = [i] * 16 + list(range(49))
                pc.register_chain(prompt, alloc.allocate(4), set(), held, len(prompt))
            pc.release(held)
            telemetry = CacheTelemetry(alloc, reclaimable=lambda pc=pc: pc.evictable_blocks)
            t0 = time.perf_counter()
            for _ in range(ticks):
                telemetry.tick()
            tick_ms = (time.perf_counter() - t0) * 1e3 / ticks
            t0 = time.perf_counter()
            for _ in range(evictions):
                check(pc.reclaim(1) == 1, f"evict probe: {name} at {n} entries evicts")
            evict_ms = (time.perf_counter() - t0) * 1e3 / evictions
            check(pc.evictable_blocks == pc.resident_blocks == 4 * (n // 4) - evictions, f"evict probe: {name} at {n} entries counts")
            out[str(n)][name] = {"tick_ms": round(tick_ms, 5), "evict_ms": round(evict_ms, 5)}
        log(f"evict probe: {n} entries: {json.dumps(out[str(n)])}")
    return out


def dispatch_probe(slots_list=(8, 64), num_layers: int = 24, short: int = 40, long: int = 100) -> dict:
    """What a decode dispatch costs the scheduler's thread, by child
    span (``args`` / ``upload`` / ``call``, ms, medians), at each of
    ``slots_list`` batch slots of the GPT-2-medium decoder: ``slots``
    requests of 12 tokens through a pipelined scheduler stepped by hand,
    half of them replies of ``long`` tokens and half shorter ones, each
    of a length of its own from ``short`` up (a finish apiece), every dispatch of
    ``engine.decode`` / ``decode_async`` recorded with what it added to
    ``engine.uploads``:

    * ``steady``: dispatched on the token array of the step in flight,
      in the composition of the step before it (its positions are that
      step's plus one in every active slot, its mask that step's), and
      staging missed nothing (no block table grew);
    * ``changed``: a step in another composition than the one before it
      (an admission, a finish, a stream left out of the step after its
      last);
    * ``other``: the rest (a table that grew, a pipeline's first step).

    It asks the engine nothing a parent commit lacks, so ``--tree DIR``
    runs it over that tree's ``flexflow_tpu`` for the other side."""
    import statistics

    import jax
    import numpy as np

    from flexflow_tpu.generation import CacheConfig, ContinuousBatchingScheduler, GenerationEngine, SamplingParams, init_decoder_params

    cfg = decoder_config(num_layers)
    params = init_decoder_params(jax.random.key(SEED), cfg)
    out = {}
    for slots in slots_list:
        blocks = 1 + slots * (-(-(12 + long) // 16) + 1)
        cache = CacheConfig(num_layers=cfg.num_layers, num_heads=cfg.num_heads, head_dim=cfg.hidden_size // cfg.num_heads,
                            block_size=16, num_blocks=blocks)
        engine = GenerationEngine(params, cfg, max_batch_slots=slots, cache_config=cache, prompt_buckets=[16], max_seq_len=128,
                                  prefix_cache=False)
        t0 = time.monotonic()
        engine.generate([[7] * 12], SamplingParams(max_new_tokens=3))  # compiles prefill[16] and the decode program
        log(f"dispatch probe: {slots} slots, {blocks} blocks, programs ready in {time.monotonic() - t0:.1f}s")
        sched = ContinuousBatchingScheduler(engine, overlap=True)
        records, last = [], [None]

        def recorded(fn):
            def call(tokens, positions, tables, active, *a, **kw):
                before = dict(engine.uploads)
                # (the scheduler bumps these arrays in place for the next step)
                expected, last[0] = last[0], (positions + active, active.copy())
                same = expected is not None and all(np.array_equal(x, y) for x, y in zip(expected, (positions, active)))
                result = fn(tokens, positions, tables, active, *a, **kw)
                grew = {k: v - before.get(k, 0) for k, v in engine.uploads.items()}
                kind = ("changed" if not same else
                        "steady" if kw.get("tokens_dev") is not None and not grew["staged_misses_total"] else "other")
                records.append((kind, {name: (t1 - t0) * 1e3 for name, t0, t1 in engine._children}, grew))
                return result
            return call

        engine.decode, engine.decode_async = recorded(engine.decode), recorded(engine.decode_async)
        rs = np.random.RandomState(slots)
        handles = [sched.submit([int(t) for t in rs.randint(0, cfg.vocab_size, size=12)],
                                SamplingParams(max_new_tokens=min(short + i, long) if i % 2 else long)) for i in range(slots)]
        while any(not h.done() for h in handles):
            check(sched.step() is not False, "the probe's scheduler ran out of work with requests open")
        check(all(short <= len(h.result(timeout=0)) <= long for h in handles), "a probe request came back short")
        check(engine.trace_counts["decode"] == 1, "the decode program was traced again")
        table = {}
        for kind in ("steady", "changed", "other"):
            rows = [(parts, grew) for k, parts, grew in records if k == kind]
            if rows:
                table[kind] = {
                    "n": len(rows),
                    **{f"{part}_ms": round(statistics.median(parts[part] for parts, _ in rows), 4) for part in ("args", "upload", "call")},
                    "dispatch_ms": round(statistics.median(sum(parts.values()) for parts, _ in rows), 4),
                    "uploads_a_step": round(statistics.mean(grew["uploads_total"] for _, grew in rows), 2),
                    "upload_bytes_a_step": round(statistics.mean(grew["upload_bytes_total"] for _, grew in rows), 1),
                }
        out[str(slots)] = table
        log(f"dispatch probe: {slots} slots: {json.dumps(table)}")
        del engine, sched
        gc.collect()
    return out


def kernels_phase(rs) -> dict:
    """Both Pallas kernels against their XLA references at the shapes
    the server and the trainer run, compiled (never interpreted)."""
    import jax
    import jax.numpy as jnp
    import numpy as np

    from flexflow_tpu.ops.kernels.decode_attention import (
        cache_row_shape,
        paged_append_attention,
        paged_kernel_refusal,
        reference_paged_append_attention,
    )

    out = {}
    b, h, d, bs, max_blocks = 8, 16, 64, 16, 64
    nb = b * max_blocks + 1
    # a whole cache of three layers, stored as the engine stores it (two
    # heads of 64 to a 128-lane row): the kernel takes all of it and a
    # static layer index
    layers, rows = 3, cache_row_shape(h, d)
    check(rows == (8, 128), f"16 heads of 64 should be stored as 8 x 128, not {rows}")
    k_cache = jnp.asarray(rs.randn(layers, nb, bs, *rows), jnp.float32)
    v_cache = jnp.asarray(rs.randn(layers, nb, bs, *rows), jnp.float32)
    tables = jnp.asarray(1 + rs.permutation(nb - 1).reshape(b, max_blocks), jnp.int32)
    for layer, w in enumerate((1, 5, 32)):
        check(paged_kernel_refusal(h, d, bs, w) is None, f"gate refuses W={w}")
        q = jnp.asarray(rs.randn(b, w, h, d), jnp.float32)
        base = rs.randint(0, max_blocks * bs - w, size=b)
        qpos = base[:, None] + np.arange(w)[None, :]
        qpos[-1, w // 2 + 1:] = -1  # padding queries on one row
        if w > 1:
            qpos[0, :] = -1  # one wholly inactive row
        qpos = jnp.asarray(qpos, jnp.int32)
        with jax.default_matmul_precision("highest"):
            ref = reference_paged_append_attention(q, k_cache, v_cache, layer, tables, qpos)
        for splits in (1, 4):
            got = jax.jit(
                lambda q, k, v, t, p: paged_append_attention(q, k, v, layer, t, p, kv_splits=splits)
            )(q, k_cache, v_cache, tables, qpos)
            err = float(jnp.max(jnp.abs(got - ref)))
            check(np.isfinite(err) and err <= 1e-3, f"paged W={w} splits={splits}: err {err}")
            check(
                bool(jnp.all(jnp.where(qpos[:, :, None, None] < 0, got == 0.0, True))),
                f"paged W={w} splits={splits}: padding queries must emit zeros",
            )
            out[f"paged_w{w}_s{splits}_max_abs_err"] = err
    log(f"paged kernel on layers 0-2 of a 5-D cache of 8 x 128 rows matches the reference: {out}")
    out["grouped"] = grouped_kernels_check()
    out["latent"] = latent_kernel_check()

    out.update(flash_public_check(rs))
    return out


# ----------------------------------------------------------------- server


def decoder_config(num_layers: int):
    from flexflow_tpu.models.transformer import TransformerConfig

    return TransformerConfig(
        num_layers=num_layers, hidden_size=1024, num_heads=16, ff_size=4096,
        seq_length=1024, vocab_size=50257, causal=True,
    )


def suffix_prefill_mosaic_calls(engine, window: int) -> int:
    """Mosaic custom calls in the one-sequence append program at
    ``window`` (suffix prefill), lowered through the dispatch the
    engine's own jits take."""
    import jax.numpy as jnp

    from flexflow_tpu.generation.decoder import verify_step

    zi = lambda *s: jnp.zeros(s, jnp.int32)
    return mosaic_calls(
        functools.partial(verify_step, backend=engine.backend, mesh=engine._kernel_mesh),
        engine.params, zi(1, window), zi(1, window), engine.cache.k, engine.cache.v,
        zi(1, engine.max_blocks_per_seq),
    )


def decode_program_facts(engine) -> dict:
    """The engine's OWN decode jit (both cache arrays donated), compiled
    for the shapes it serves: Mosaic calls, and what
    ``memory_analysis()`` says it holds beside its arguments. The
    program is in the compile cache by now."""
    import numpy as np

    b, mb, v = engine.max_batch_slots, engine.max_blocks_per_seq, engine.cfg.vocab_size
    z = lambda dtype, *s: engine._dev(np.zeros(s, dtype))
    i32, f32 = np.int32, np.float32
    compiled = engine._decode_jit.lower(
        engine.params, z(i32, b), z(i32, b), engine.cache.k, engine.cache.v, z(i32, b, mb),
        z(i32, b), z(f32, b), z(i32, b), z(f32, b), z(np.uint32, b), z(i32, b), z(f32, b, v),
    ).compile()
    m = compiled.memory_analysis()
    return {
        "mosaic_calls": compiled.as_text().count(MOSAIC_CALL),
        "argument_bytes": int(m.argument_size_in_bytes),
        "temp_bytes": int(m.temp_size_in_bytes),
        "alias_bytes": int(m.alias_size_in_bytes),
    }


def cache_device_bytes(engine) -> int:
    """What one shard of K plus V holds on its device, padding and all."""
    return sum(a.addressable_shards[0].data.on_device_size_in_bytes()
               for a in (engine.cache.k, engine.cache.v))


def make_requests(rs, vocab: int, prompt_lens) -> list:
    """Greedy requests over ``prompt_lens`` (JSON and SSE alternating),
    the last one sampled from a seed."""
    reqs = [
        {
            "prompt": [int(t) for t in rs.randint(0, vocab, size=n)],
            "max_new_tokens": NEW_TOKENS,
            "stream": bool(i % 2),
        }
        for i, n in enumerate(prompt_lens)
    ]
    reqs[-1].update(temperature=0.8, top_k=50, seed=7)
    return reqs


def direct_generate(engine, bodies: list) -> list:
    """The synchronous path (``engine.generate``: a private scheduler
    stepped on this thread), one call per sampling configuration."""
    from flexflow_tpu.serving.generation import GenerationModel

    out = [None] * len(bodies)
    groups: dict = {}
    for i, body in enumerate(bodies):
        groups.setdefault(GenerationModel.sampling_from(body), []).append(i)
    for sampling, idx in groups.items():
        for i, toks in zip(idx, engine.generate([bodies[i]["prompt"] for i in idx], sampling)):
            out[i] = [int(t) for t in toks]
    return out


def http_generate(base: str, name: str, body: dict) -> list:
    req = urllib.request.Request(
        f"{base}/v2/models/{name}/generate", data=json.dumps(body).encode()
    )
    with urllib.request.urlopen(req, timeout=600) as resp:
        raw = resp.read().decode()
    if not body["stream"]:
        return [int(t) for t in json.loads(raw)["tokens"]]
    events = [
        json.loads(line[len("data: "):])
        for chunk in raw.strip().split("\n\n")
        for line in chunk.split("\n")
        if line.startswith("data: ")
    ]
    check(events and events[-1].get("done") is True, f"SSE stream did not finish: {events[-1:]}")
    check("error" not in events[-1], f"SSE stream failed: {events[-1]}")
    streamed = [int(e["token"]) for e in events[:-1]]
    check(streamed == events[-1]["tokens"], "SSE token events disagree with the done event")
    return streamed


def teacher_force(params, bodies: list, streams: list):
    """Per request: forward_full's argmax at every generated position,
    and how far below the maximum the produced token's logit is."""
    import jax
    import jax.numpy as jnp
    import numpy as np

    from flexflow_tpu.generation.decoder import forward_full

    n_new = len(streams[0])
    lens = [len(b["prompt"]) for b in bodies]
    pad = -(-(max(lens) + n_new) // 128) * 128
    tokens = np.zeros((len(bodies), pad), np.int32)
    for i, (b, s) in enumerate(zip(bodies, streams)):
        tokens[i, : lens[i]] = b["prompt"]
        tokens[i, lens[i] : lens[i] + n_new] = s

    @jax.jit
    def run(params, tokens, lengths, prompt_lens, produced):
        logits = forward_full(params, tokens, lengths)  # [N, S, V]
        # the logits at position p predict the token at p + 1
        at = prompt_lens[:, None] - 1 + jnp.arange(n_new)[None, :]
        sel = jnp.take_along_axis(logits, at[:, :, None], axis=1)  # [N, n_new, V]
        got = jnp.take_along_axis(sel, produced[:, :, None], axis=2)[..., 0]
        return jnp.argmax(sel, axis=-1), jnp.max(sel, axis=-1) - got, jnp.all(jnp.isfinite(sel))

    argmax, gap, finite = run(
        params, jnp.asarray(tokens), jnp.asarray(np.asarray(lens) + n_new, jnp.int32),
        jnp.asarray(lens, jnp.int32), jnp.asarray(np.asarray(streams, np.int32)),
    )
    check(bool(finite), "forward_full produced non-finite logits")
    return np.asarray(argmax), np.asarray(gap)


def check_streams(bodies, streams, vocab, what):
    for body, toks in zip(bodies, streams):
        check(
            len(toks) == body["max_new_tokens"],
            f"{what}: prompt[{len(body['prompt'])}] returned {len(toks)} tokens, "
            f"asked {body['max_new_tokens']}",
        )
        check(all(0 <= t < vocab for t in toks), f"{what}: token outside the vocabulary")


def check_teacher_forcing(params, bodies, streams, what) -> dict:
    import numpy as np

    greedy = [i for i, b in enumerate(bodies) if b.get("temperature", 0.0) <= 0.0]
    argmax, gap = teacher_force(
        params, [bodies[i] for i in greedy], [streams[i] for i in greedy]
    )
    produced = np.asarray([streams[i] for i in greedy])
    off = int(np.sum(argmax != produced))
    worst = float(gap.max())
    log(
        f"{what}: teacher forcing through forward_full: {produced.size - off}/"
        f"{produced.size} greedy tokens are the argmax, worst logit gap {worst:.5f} "
        f"(margin {LOGIT_MARGIN})"
    )
    check(worst <= LOGIT_MARGIN, f"{what}: a greedy token is {worst} logits below forward_full's best")
    return {"greedy_tokens": int(produced.size), "not_argmax": off, "worst_logit_gap": worst}


ZERO_COUNTERS = (
    "rejected", "expired", "failed", "cancelled", "preemptions", "recompiles",
    "retraces_blamed", "recoveries", "step_retries", "replayed_tokens",
    "quarantined", "watchdog_trips", "engine_failures", "degrade_level",
    "overload_sheds_total", "overload_throttled_total",
)


def server_phase(rs, num_layers: int = 24) -> dict:
    import jax
    import numpy as np

    from flexflow_tpu.generation import GenerationEngine, init_decoder_params
    from flexflow_tpu.ops.kernels.decode_attention import paged_kernel_refusal
    from flexflow_tpu.serving import InferenceServer
    from flexflow_tpu.serving.generation import GenerationModel

    out: dict = {}
    cfg = decoder_config(num_layers)
    t0 = time.monotonic()
    params = init_decoder_params(jax.random.key(SEED), cfg)
    engine = GenerationEngine(params, cfg, max_batch_slots=SLOTS, block_size=16)
    jax.block_until_ready(engine.cache.k)
    cc = engine.cache_config
    log(
        f"engine: {num_layers} L / {cfg.hidden_size} / {cfg.num_heads} x "
        f"{cfg.hidden_size // cfg.num_heads} / {cfg.ff_size}, vocab {cfg.vocab_size}, "
        f"{SLOTS} slots, buckets {engine.buckets}, cache {cc.num_blocks} x {cc.block_size} "
        f"= {cc.total_bytes / 2**30:.2f} GiB {cc.dtype.name}, donate={engine.donate}, "
        f"built in {time.monotonic() - t0:.1f}s"
    )
    check(engine.donate, "cache donation must be on for a TPU backend")
    check(engine.cache.k is not engine.cache.v, "K and V must not share one buffer")
    # stored as [L, nb, bs, 8, 128], the cache's default layout on the
    # chip is the row-major one the paged kernel reads, and pads nothing
    cache_dev = cache_device_bytes(engine)
    log(f"cache on the device: {engine.cache.k.shape} x 2 = {cache_dev / 2**30:.2f} GiB, "
        f"layout {engine.cache.k.format.layout}")
    check(cache_dev == cc.total_bytes, f"the cache holds {cache_dev} B on the device, nominal {cc.total_bytes}")
    check(engine.cache.k.format.layout.major_to_minor == (0, 1, 2, 3, 4),
          f"the cache's default layout is not row-major: {engine.cache.k.format.layout}")
    out["cache_device_bytes"] = cache_dev

    bodies = make_requests(rs, cfg.vocab_size, PROMPT_LENS)
    # a follow-up that shares request 2's prompt: its full blocks are in
    # the prefix cache by then, so only a <=32-token suffix is computed —
    # the append kernel's W > 1 window inside a serving program
    shared = len(bodies[2]["prompt"]) // 16 * 16
    suffix = [int(t) for t in rs.randint(0, cfg.vocab_size, size=22)]
    follow = {"prompt": bodies[2]["prompt"][:shared] + suffix,
              "max_new_tokens": NEW_TOKENS, "stream": True}
    buckets = sorted({engine.bucket_for(len(b["prompt"])) for b in bodies})
    check(len(buckets) >= 3 and buckets[-1] >= 512, f"prompts must span buckets: {buckets}")

    # ---- warm: compile the whole family on THIS thread, before start()
    t0 = time.monotonic()
    direct = direct_generate(engine, bodies)
    direct_follow = direct_generate(engine, [follow])
    warm_s = time.monotonic() - t0
    compile_s = {p["name"]: round(p["compile_s"], 2) for p in engine.programs.snapshot()
                 if p.get("compile_s")}
    log(f"warm-up through engine.generate: {warm_s:.1f}s; compile+first-run seconds by program: {compile_s}")
    out.update(warm_s=round(warm_s, 1), compile_s=compile_s)
    check(engine.prefix_cache.hits >= 1, "the follow-up prompt must reuse a cached prefix")
    check(
        f"prefix_prefill[{engine.bucket_for(len(suffix))}]" in engine.trace_counts,
        f"suffix prefill did not run: {engine.trace_counts}",
    )
    check_streams(bodies + [follow], direct + direct_follow, cfg.vocab_size, "engine.generate")

    # a clean cache and prefix index, so the served run takes the same
    # programs in the same order as the direct one (no recompiles)
    engine.reset()
    traces_before = dict(engine.trace_counts)

    # ---- serve: real HTTP, concurrent, JSON and SSE
    server = InferenceServer(port=0)
    model = GenerationModel(engine, name="lm")
    server.register_generation(model)
    t0 = time.monotonic()
    with server:
        base = f"http://127.0.0.1:{server.port}"
        with concurrent.futures.ThreadPoolExecutor(len(bodies)) as pool:
            futures = [pool.submit(http_generate, base, "lm", b) for b in bodies]
            served = [f.result(timeout=900) for f in futures]
        served_follow = [http_generate(base, "lm", follow)]
        serve_s = time.monotonic() - t0
        stats = json.load(urllib.request.urlopen(f"{base}/v2/stats"))["generation"]["lm"]
        ready = json.load(urllib.request.urlopen(f"{base}/v2/health/ready"))
    n_tok = sum(len(s) for s in served + served_follow)
    log(
        f"served {len(bodies) + 1} HTTP requests ({n_tok} tokens) in {serve_s:.1f}s; "
        f"steps {engine.step_counts}; decode execute {engine.phase_time_s['decode']['execute']:.2f}s"
    )
    out.update(serve_s=round(serve_s, 2), tokens=n_tok, step_counts=dict(engine.step_counts))

    check_streams(bodies + [follow], served + served_follow, cfg.vocab_size, "HTTP")
    check(served == direct, "HTTP streams differ from engine.generate of the same prompts")
    check(served_follow == direct_follow, "HTTP prefix-reuse stream differs from engine.generate")
    new_traces = {
        k: v - traces_before.get(k, 0) for k, v in engine.trace_counts.items()
        if v != traces_before.get(k, 0)
    }
    check(not new_traces, f"jit traces while serving (steady state must have none): {new_traces}")
    nonzero = {k: stats[k] for k in ZERO_COUNTERS if stats[k]}
    check(not nonzero, f"self-healing ran while serving: {nonzero}")
    check(stats["completed"] == len(bodies) + 1, f"completed {stats['completed']}")
    check(model.breaker.state == "closed", f"breaker {model.breaker.state}")
    check(stats["prefix_cache_tokens_reused_total"] >= shared, "served follow-up missed the prefix cache")
    # plain multi-head attention: the kernel's VPU body, not the XLA composition
    check(stats["kernels"] == {"full": {"body": "vpu", "group": 1}}, f"/v2/stats kernels: {stats.get('kernels')}")
    log(f"zero new traces, zero recoveries/retries/quarantines/watchdog trips; readiness {ready.get('ready')}")

    out["teacher_forcing"] = check_teacher_forcing(
        params, bodies + [follow], served + served_follow, "server"
    )

    # ---- which attention ran: the programs' own lowering
    head_dim = cfg.hidden_size // cfg.num_heads
    for window in (1, engine.bucket_for(len(suffix))):
        check(
            paged_kernel_refusal(cfg.num_heads, head_dim, cc.block_size, window) is None,
            f"the kernel gate refuses the served shape (window {window})",
        )
    w = engine.bucket_for(len(suffix))
    facts = decode_program_facts(engine)
    n_decode = facts["mosaic_calls"]
    n_suffix = suffix_prefill_mosaic_calls(engine, w)
    log(f"Mosaic custom calls: decode {n_decode}, suffix prefill[{w}] {n_suffix} (one per layer)")
    check(n_decode == num_layers, f"decode program holds {n_decode} Mosaic calls, want {num_layers}")
    check(n_suffix == num_layers, f"suffix-prefill program holds {n_suffix} Mosaic calls")
    out.update(mosaic_decode=n_decode, mosaic_suffix_prefill=n_suffix, decode_program=facts)
    # no cache-sized copy in the token loop: the program aliases both
    # cache arrays to its results and holds no cache-sized temporary
    log(f"decode program memory_analysis: arguments {facts['argument_bytes'] / 2**30:.2f} GiB, "
        f"temporaries {facts['temp_bytes'] / 2**30:.3f} GiB, aliased {facts['alias_bytes'] / 2**30:.2f} GiB")
    check(facts["alias_bytes"] >= cache_dev, f"decode aliases {facts['alias_bytes']} B, the cache is {cache_dev} B")
    check(facts["temp_bytes"] < cache_dev // 4,
          f"decode holds {facts['temp_bytes']} B of temporaries beside a {cache_dev} B cache")
    # the block programs the served traffic did not reach: a swap-in
    # write, a host-tier read and the batched wire pair; off the device a
    # block has its logical [L, bs, H, D] shape and comes back bit for bit
    blk = engine.allocator.allocate(2)
    hk, hv = (rs.randn(num_layers, cc.block_size, cfg.num_heads, head_dim).astype(np.float32)
              for _ in range(2))
    engine.import_kv_block(blk[0], hk, hv)
    rk, rv = engine._read_block_jit(engine.cache.k, engine.cache.v, np.int32(blk[0]))
    check(np.array_equal(np.asarray(rk), hk) and np.array_equal(np.asarray(rv), hv),
          "kv_block_write then kv_block_read does not return the block")
    payload = engine.pack_kv_blocks(blk[:1], cc.block_size)
    engine.import_kv_blocks(blk[1:], payload.blocks)
    rk, _ = engine._read_block_jit(engine.cache.k, engine.cache.v, np.int32(blk[1]))
    check(np.array_equal(np.asarray(rk), hk), "kv_blocks_read then kv_blocks_write does not return the block")
    engine.allocator.free(blk)
    mem = jax.devices()[0].memory_stats() or {}
    out["peak_bytes_in_use"] = mem.get("peak_bytes_in_use")
    log(f"device memory: peak {mem.get('peak_bytes_in_use', 0) / 2**30:.2f} GiB "
        f"of {mem.get('bytes_limit', 0) / 2**30:.2f} GiB")
    return out


# ---------------------------------------------------------------- trainer


def train_phase(rs, label: str, config_kwargs: dict, strategy_fn=None,
                num_layers: int = 24):
    """Compile and take five steps on one fixed batch; returns the
    facts and the model (the four-chip legs look at where its arrays
    live)."""
    batch, seq, steps = 8, 512, 5
    import jax
    import jax.numpy as jnp
    import numpy as np

    from flexflow_tpu import AdamOptimizer, DataType, FFConfig, LossType
    from flexflow_tpu.models import TransformerConfig, build_transformer

    cfg = TransformerConfig(
        num_layers=num_layers, hidden_size=1024, num_heads=16, ff_size=4096,
        seq_length=seq, dtype=DataType.BFLOAT16,
    )
    config = FFConfig(batch_size=batch, num_nodes=1, **config_kwargs)
    model = build_transformer(config, cfg)
    t0 = time.monotonic()
    # Adam: its step does not shrink with the mean-squared loss's 1/N
    # gradient, so five steps move the loss well clear of bf16 noise
    model.compile(
        optimizer=AdamOptimizer(alpha=1e-4), loss_type=LossType.MEAN_SQUARED_ERROR,
        strategy=strategy_fn(model.graph) if strategy_fn else None,
    )
    compile_s = time.monotonic() - t0
    ex = model.executor
    mesh = dict(zip(model.mesh.axis_names, model.mesh.devices.shape))
    x = jnp.asarray(rs.randn(batch, seq, cfg.hidden_size), cfg.dtype.jnp)
    y = 0.5 * x
    rng = jax.random.key(SEED)
    n_mosaic = mosaic_calls(
        ex._train_step_fn, ex.params, ex.opt_state, ex.state, (x,), y, rng
    )
    check(n_mosaic >= 3 * num_layers,
          f"{label}: train step holds {n_mosaic} Mosaic calls, want fwd + dq + dkv per layer")
    losses, times = [], []
    for _ in range(steps):
        t0 = time.monotonic()
        losses.append(float(ex.train_batch([x], y, rng)["loss"]))
        times.append(time.monotonic() - t0)
    log(
        f"trainer[{label}]: mesh {mesh}, FFModel.compile {compile_s:.1f}s, first step "
        f"(XLA compile + run) {times[0]:.1f}s, later steps {min(times[1:]):.3f}s, "
        f"{n_mosaic} Mosaic calls, loss {losses[0]:.5f} -> {losses[-1]:.5f}"
    )
    check(all(np.isfinite(l) for l in losses), f"{label}: non-finite loss {losses}")
    check(losses[-1] < losses[0], f"{label}: loss did not fall over {steps} steps: {losses}")
    out = {"mesh": mesh, "losses": losses, "first_step_s": round(times[0], 1),
           "step_s": round(min(times[1:]), 4), "mosaic_calls": n_mosaic}
    if model._search_result is not None:
        out["search_cost_ms"] = model._search_result.best_cost * 1e3
    return out, model


TRAIN_OPS_CELL = "bert-large.mlm-s512"


def train_ops_probe(label: str = "", steps: int = 8, top: int = 40) -> dict:
    """The train step of :data:`TRAIN_OPS_CELL` (the cell's own model,
    batch and optimizer, a fixed random batch) under the profiler for
    ``steps`` steps: ms a step on the device's clock of every
    INSTRUCTION by its own name, joined to the layer it came from (the
    ``op_name`` the compiled HLO carries), beside the families
    ``breakdown.device_ops`` of a cell's traced run sums them into. A
    fusion is named by its LAST op: ``subtract_convert_fusion`` is every
    weight-gradient product with its Adam update behind it, and read as
    "the loss" it cost an issue its premise (PERF.md, PR 49). The
    compiled HLO goes beside the JSON (``label`` in both names)."""
    import gzip
    import re

    import jax
    import numpy as np

    from benchmark import spec, trace_reduce
    from benchmark.drivers import train as driver
    from flexflow_tpu import AdamOptimizer, LossType

    cell = spec.load_cell(TRAIN_OPS_CELL)
    batch, seq = int(cell.workload["deployment"]["per_chip_batch"]) * cell.chips, int(cell.traffic["params"]["seq"])
    model, _ = driver.build_model(cell, SEED, batch, seq)
    model.compile(optimizer=AdamOptimizer(alpha=float(cell.config["optimizer"]["alpha"])),
                  loss_type=LossType.SPARSE_CATEGORICAL_CROSSENTROPY)
    ex = model.executor
    rs = np.random.RandomState(SEED)
    x, y = (rs.randint(0, cell.config["vocab_size"], (batch, seq)).astype(np.int32) for _ in range(2))
    keys = iter(jax.random.split(jax.random.key(SEED), 4 * steps))
    step = lambda: ex.train_batch([x], y, next(keys))["loss"]  # noqa: E731
    losses = [float(step()) for _ in range(steps)]
    check(all(np.isfinite(l) for l in losses) and losses[-1] < losses[0], f"the loss did not fall: {losses}")
    host_ms = _timed_ms(step, (), reps=steps)
    trace = _device_trace(step, steps, "chip_smoke_train_ops")
    hlo = ex._train_step.lower(ex.params, ex.opt_state, ex.state, ex._shard_inputs([x]), y, next(keys)).compile().as_text()
    layer_of = {m.group(1): re.sub(r"^jit\(\w+\)/", "", m.group(2))
                for m in re.finditer(r'^\s+(?:ROOT )?%([\w.\-]+) = .*?op_name="([^"]*)"', hlo, flags=re.M)}
    by_name, by_family = {}, {}
    for dev in trace.devices:
        for (event, _, _), own in zip(dev.ops, trace_reduce.self_times(dev.ops)):
            name = trace_reduce.op_name(event)
            by_name[name] = by_name.get(name, 0.0) + own
            family = trace_reduce.op_family(name)
            by_family[family] = by_family.get(family, 0.0) + own
    ms = lambda ns: round(ns * 1e-6 / steps / len(trace.devices), 4)  # noqa: E731
    ranked = lambda d: sorted(d.items(), key=lambda kv: -kv[1])  # noqa: E731
    out = {
        "cell": TRAIN_OPS_CELL, "loss_form": getattr(ex, "loss_form", None), "losses": losses, "host_step_ms": host_ms,
        "busy_ms_a_step": ms(sum(by_name.values())),
        "families_ms": {k: ms(v) for k, v in ranked(by_family)[:top]},
        "instructions_ms": [[k, ms(v), layer_of.get(k, "")] for k, v in ranked(by_name)[: 4 * top]],
    }
    log(f"train ops of {TRAIN_OPS_CELL} ({out['loss_form']}): host step {host_ms} ms, busy {out['busy_ms_a_step']} ms a step; "
        f"families {dict(list(out['families_ms'].items())[:10])}")
    log(f"largest instructions: {[row for row in out['instructions_ms'] if not row[0].startswith('flash_attention')][:12]}")
    (REPO / "chiprun_out").mkdir(exist_ok=True)
    with gzip.open(REPO / "chiprun_out" / f"chip_smoke_train_ops{label}.hlo.txt.gz", "wt") as f:
        f.write(hlo)
    return out


def calibration_hit(kind: str) -> str:
    """The search's op-cost lookup for this chip must hit a table inside
    the checkout: nothing under $HOME decides a strategy."""
    from flexflow_tpu.search.calibration import load_calibration

    cal = load_calibration(kind)
    check(cal is not None, f"no committed calibration table for device kind {kind!r}")
    inside = pathlib.Path(cal.source).resolve().is_relative_to(REPO)
    check(inside or "FLEXFLOW_TPU_CACHE" in os.environ,
          f"calibration table {cal.source} is outside the checkout")
    return f"{cal.source} ({len(cal.entries)} entries, derates {cal.derates})"


# -------------------------------------------------------------- four chips


def shard_report(arr) -> str:
    shards = arr.addressable_shards
    return (f"{tuple(arr.shape)} as {len(shards)} x {tuple(shards[0].data.shape)} on devices "
            f"{sorted(s.device.id for s in shards)}")


def compiled_flash_call(ex) -> str:
    """The flash forward custom call as the COMPILED (partitioned)
    train step holds it: per-chip batch/heads, or the global tensor
    gathered onto every chip?"""
    import jax
    import jax.numpy as jnp

    x = jnp.zeros((8, 512, 1024), jnp.bfloat16)
    hlo = ex._train_step.lower(
        ex.params, ex.opt_state, ex.state, tuple(ex._shard_inputs([x])), x,
        jax.random.key(SEED),
    ).compile().as_text()
    calls = [l for l in hlo.splitlines() if MOSAIC_CALL in l and "flash_attention_fwd" in l]
    check(bool(calls), "compiled train step holds no flash forward custom call")
    return calls[0].split("custom-call(")[0].strip()[:200]


def four_chip_phase(rs, num_layers: int = 4) -> dict:
    """The sharded legs on one four-chip host, in this one process.
    Widths as in the one-chip run; depth cut to ``num_layers`` so every
    program family compiles inside the chip budget."""
    import jax
    import numpy as np

    from flexflow_tpu.generation import GenerationEngine, init_decoder_params
    from flexflow_tpu.parallel.mesh import serving_mesh
    from flexflow_tpu.parallel.strategy import megatron_strategy

    devs = jax.devices()
    check(len(devs) == 4, f"--four-chips needs 4 devices, have {len(devs)}")
    out: dict = {}

    def used(d):
        return (d.memory_stats() or {}).get("bytes_in_use", 0)

    # ---- trainer: one chip, {data: 4}, and megatron dp=2 x tp=2
    runs = {}
    for label, kwargs, strat in (
        ("1chip", dict(workers_per_node=1, only_data_parallel=True), None),
        ("data4", dict(workers_per_node=4, only_data_parallel=True), None),
        ("dp2xtp2", dict(workers_per_node=4, only_data_parallel=True),
         lambda g: megatron_strategy(g, dp=2, tp=2)),
    ):
        r, model = train_phase(np.random.RandomState(SEED), label, kwargs, strat,
                               num_layers=num_layers)
        leaves = jax.tree.leaves(model.executor.params)
        big = max(leaves, key=lambda a: a.size)
        per_dev = [round(used(d) / 2**20) for d in devs]
        log(f"trainer[{label}]: largest weight {shard_report(big)}; MiB in use per device {per_dev}")
        r.update(largest_weight=shard_report(big), mib_in_use=per_dev)
        if label != "1chip":
            check(int(np.prod(list(r["mesh"].values()))) == 4, f"{label}: mesh {r['mesh']}")
            check(all(m > 0 for m in per_dev), f"{label}: a device holds nothing: {per_dev}")
            r["flash_fwd_hlo"] = compiled_flash_call(model.executor)
            log(f"trainer[{label}]: flash_attention_fwd in the compiled HLO: {r['flash_fwd_hlo']}")
        runs[label] = r
        del model
        gc.collect()
    for label in ("data4", "dp2xtp2"):
        a, b = runs["1chip"]["losses"], runs[label]["losses"]
        rel = max(abs(p - q) / abs(p) for p, q in zip(a, b))
        log(f"trainer[{label}] loss vs one chip: max rel diff {rel:.4f} over {len(a)} steps")
        check(rel <= 0.02, f"{label}: loss departs from the one-chip run: {a} vs {b}")
        runs[label]["loss_rel_diff_vs_1chip"] = rel
    out["trainer"] = runs

    # ---- server: tp_degree=4 against tp=1, same prompts
    cfg = decoder_config(num_layers)
    params = init_decoder_params(jax.random.key(SEED), cfg)
    bodies = make_requests(rs, cfg.vocab_size, (12, 100, 300, 9))
    streams = {}
    for tp in (1, 4):
        engine = GenerationEngine(params, cfg, max_batch_slots=SLOTS, block_size=16, tp_degree=tp)
        jax.block_until_ready(engine.cache.k)
        log(f"server[tp={tp}]: cache {shard_report(engine.cache.k)}; "
            f"wq {shard_report(engine.params['layers'][0]['wq'])}")
        if tp == 4:
            shards = engine.cache.k.addressable_shards
            check(len({s.device.id for s in shards}) == 4, "tp=4 cache is not on four devices")
            check(shards[0].data.shape[3] == engine.cache.k.shape[3] // 4,
                  "tp=4 cache is not sharded on its rows (heads)")
            facts = decode_program_facts(engine)
            n = facts["mosaic_calls"]
            check(n == num_layers, f"tp=4 decode holds {n} Mosaic calls, want {num_layers}")
            check(facts["alias_bytes"] >= cache_device_bytes(engine),
                  f"tp=4 decode does not alias its cache shards: {facts}")
            out["server_tp4_decode_program"] = facts
        streams[tp] = direct_generate(engine, bodies)
        check_streams(bodies, streams[tp], cfg.vocab_size, f"tp={tp}")
        out[f"server_tp{tp}"] = check_teacher_forcing(params, bodies, streams[tp], f"server[tp={tp}]")
        del engine
        gc.collect()
    same = sum(a == b for a, b in zip(streams[1], streams[4]))
    log(f"server: {same}/{len(bodies)} tp=4 streams are token-identical to tp=1 "
        f"(all within the teacher-forcing margin)")
    out["tp4_streams_identical_to_tp1"] = f"{same}/{len(bodies)}"

    # ---- four one-chip engines in one process, one device each
    before = [used(d) for d in devs]
    engines = [
        GenerationEngine(params, cfg, max_batch_slots=SLOTS, block_size=16,
                         mesh=serving_mesh(1, devices=[d]))
        for d in devs
    ]
    placed = []
    for d, e in zip(devs, engines):
        jax.block_until_ready(e.cache.k)
        on = {s.device.id for s in e.cache.k.addressable_shards}
        on |= {s.device.id for leaf in jax.tree.leaves(e.params) for s in leaf.addressable_shards}
        check(on == {d.id}, f"engine asked onto device {d.id} has arrays on {sorted(on)}")
        placed.append(sorted(on))
    grew = [round((used(d) - b) / 2**20) for d, b in zip(devs, before)]
    check(all(g > 0 for g in grew), f"a device did not grow: {grew}")
    short = [{"prompt": bodies[0]["prompt"], "max_new_tokens": 8, "stream": False}]
    outs = [direct_generate(e, short)[0] for e in engines]
    check(all(o == outs[0] for o in outs), f"the four replicas disagree: {outs}")
    log(f"four engines via mesh=: arrays on devices {placed}, MiB grown per device {grew}, "
        f"same 8 tokens from each")
    out["four_engines"] = {"devices": placed, "mib_grown": grew}
    return out


# ------------------------------------------------------------------- main


def main(argv=None) -> int:
    ap = argparse.ArgumentParser(description=__doc__.split("\n\n")[0])
    ap.add_argument("--four-chips", action="store_true",
                    help="run the sharded legs on a four-chip host instead")
    ap.add_argument("--latent-kernel", action="store_true",
                    help="the latent paged kernel alone, at the latent cell's sizes")
    ap.add_argument("--latent", type=int, default=None, metavar="HEADS", choices=sorted(LATENT_CALLS),
                    help="the latent paged kernel alone, at the sizes of the latent cell whose layers have HEADS heads")
    ap.add_argument("--expert-product", nargs="*", default=None, metavar="LAYER", choices=sorted(EXPERT_LAYERS),
                    help="the routed experts' sum alone: dense against grouped, one layer of each expert cell (or of those named)")
    ap.add_argument("--ssm-kernel", action="store_true",
                    help="the state-space decode update alone at the Nemotron cell's sizes: the Pallas call against its XLA "
                         "composition; with --expert-product, both")
    ap.add_argument("--ssm-slots", action="store_true",
                    help="an engine at the Nemotron cell's sizes serving the same prompts at 64 and at 192 slots: the streams and the stored state")
    ap.add_argument("--group16", action="store_true",
                    help="the group-16 paged calls and the streamed prefill kernel alone, at the long-document cell's sizes")
    ap.add_argument("--grouped-kernels", action="store_true",
                    help="every grouped paged call of the cells alone (GROUPED_CALLS and GROUP16_CALLS), at the cells' contexts and at half the table")
    ap.add_argument("--block-kernel", action="store_true",
                    help="the grouped paged call at a block-diffusion cell's rows (W = 4, group 8: 128 query rows a sequence) alone; "
                         "with --expert-product, both")
    ap.add_argument("--walk-sweep", action="store_true",
                    help="the grouped paged calls at 1 to 32 table columns a grid step, ms a call")
    ap.add_argument("--latent-prefill", action="store_true",
                    help="the streamed prefill kernel alone at the 64-head latent cell's two buckets (score width 192, value width 128)")
    ap.add_argument("--flash-kernels", action="store_true",
                    help="the three training flash kernels alone at the bert-large cells' call: error and ms a call")
    ap.add_argument("--flash-sweep", action="store_true",
                    help="the same three kernels at every block pair of {128, 256, 512}: ms a call and compile seconds")
    ap.add_argument("--loss-chain", action="store_true",
                    help="the trainer's softmax + cross-entropy chain alone at the bert-large cells' logits: composed against fused, ms a call and error")
    ap.add_argument("--train-ops", action="store_true",
                    help="the bert-large one-chip cell's train step under the profiler: ms a step of every instruction by name and by the layer it came from")
    ap.add_argument("--release-probe", action="store_true",
                    help="the drop of a finished step's device arrays, timed beside a program in flight and beside woken stream threads")
    ap.add_argument("--dispatch-probe", action="store_true",
                    help="ms a decode dispatch by child span, a steady step beside one after a composition change, at 8 and 64 slots")
    ap.add_argument("--evict-probe", action="store_true",
                    help="ms an eviction and ms a pressure tick of the prefix index at 1,000 / 7,400 / 15,000 entries, beside the plain scan")
    ap.add_argument("--tree", default=None, metavar="DIR",
                    help="import flexflow_tpu from DIR (a parent commit unpacked under the checkout) and not from here")
    args = ap.parse_args(argv)
    if args.tree:
        sys.path.insert(0, str(pathlib.Path(args.tree).resolve()))

    import jax
    import numpy as np

    from flexflow_tpu.device import COMPILE_CACHE_ENV, enable_compile_cache, require_tpu

    dev = require_tpu()
    native = rebuild_native()  # nothing has imported flexflow_tpu._native yet
    device = {"platform": dev.platform, "kind": dev.device_kind, "count": len(jax.devices())}
    cache_dir = enable_compile_cache()
    entries_before = cache_entries(cache_dir)
    log(
        f"device {device}; jax {jax.__version__}, jaxlib "
        f"{importlib.metadata.version('jaxlib')}, libtpu {importlib.metadata.version('libtpu')}; "
        f"native: {native}"
    )
    log(
        f"compile cache: {cache_dir} ({'from ' + COMPILE_CACHE_ENV if os.environ.get(COMPILE_CACHE_ENV) else 'in-checkout default'}), "
        f"{entries_before} entries -> this run is {'WARM' if entries_before else 'COLD'}"
    )
    summary = {"device": device, "jax": jax.__version__, "native": native,
               "compile_cache": {"dir": cache_dir, "entries_before": entries_before}}
    rs = np.random.RandomState(SEED)
    if args.four_chips:
        summary["four_chips"] = four_chip_phase(rs)
    elif args.latent_kernel or args.latent:
        summary["kernels"] = {"latent": latent_kernel_check(LATENT_CALLS[args.latent or 32])}
    elif args.ssm_slots:
        summary["engine"] = {"ssm_slots": ssm_slots_check()}
    elif args.ssm_kernel:
        summary["kernels"] = {"ssm_update": ssm_kernel_check()}
        if args.expert_product is not None:
            summary["experts"] = expert_product_check(args.expert_product)
    elif args.group16:
        summary["kernels"] = {"grouped": grouped_kernels_check(GROUP16_CALLS), "prefill_stream": stream_kernel_check(),
                              "materialised_prefill": materialised_calls_check(), "latent_prefill_stream": latent_stream_check()}
    elif args.grouped_kernels:
        summary["tree"] = args.tree or "."
        summary["kernels"] = {"grouped": grouped_kernels_check({**GROUPED_CALLS, **GROUP16_CALLS})}
    elif args.block_kernel:
        summary["kernels"] = {"block": block_kernel_check()}
        if args.expert_product is not None:
            summary["experts"] = expert_product_check(args.expert_product)
    elif args.walk_sweep:
        summary["walk_sweep"] = walk_sweep()
    elif args.latent_prefill:
        summary["kernels"] = {"latent_prefill_stream": latent_stream_check()}
    elif args.expert_product is not None:
        summary["experts"] = expert_product_check(args.expert_product)
    elif args.flash_kernels or args.flash_sweep:
        summary["tree"] = args.tree or "."
        if args.flash_sweep:
            summary["kernels"] = {"flash": flash_kernels_check([(bq, bk) for bq in (128, 256, 512) for bk in (128, 256, 512)])}
        else:
            summary["kernels"] = {"flash": flash_kernels_check(), "flash_errors": flash_public_check(rs)}
    elif args.loss_chain:
        summary["loss_chain"] = loss_chain_check()
    elif args.train_ops:
        summary["tree"] = args.tree or "."
        summary["train_ops"] = train_ops_probe("_" + pathlib.Path(args.tree).name.strip(".") if args.tree else "")
    elif args.release_probe:
        summary["release_probe"] = release_probe()
        (REPO / "chiprun_out" / "pr38").mkdir(parents=True, exist_ok=True)
        (REPO / "chiprun_out" / "pr38" / "release_probe.json").write_text(json.dumps(summary["release_probe"]) + "\n")
        print(json.dumps(summary["release_probe"]), flush=True)
    elif args.dispatch_probe:
        summary["dispatch_probe"] = {"tree": args.tree or ".", "ms_a_dispatch": dispatch_probe()}
        (REPO / "chiprun_out" / "pr40").mkdir(parents=True, exist_ok=True)
        name = "dispatch_probe_" + (pathlib.Path(args.tree).name.strip(".") if args.tree else "change") + ".json"
        (REPO / "chiprun_out" / "pr40" / name).write_text(json.dumps(summary["dispatch_probe"]) + "\n")
        print(json.dumps(summary["dispatch_probe"]), flush=True)
    elif args.evict_probe:
        summary["evict_probe"] = evict_probe()
    else:
        summary["calibration"] = calibration_hit(dev.device_kind)
        log(f"calibration lookup for {dev.device_kind!r}: {summary['calibration']}")
        summary["kernels"] = kernels_phase(rs)
        summary["server"] = server_phase(rs)
        gc.collect()
        for label, kwargs in (
            ("dp", dict(workers_per_node=1, only_data_parallel=True)),
            ("searched", dict(workers_per_node=1, only_data_parallel=False, search_budget=5)),
        ):
            summary[f"trainer_{label}"], model = train_phase(rs, label, kwargs)
            del model
            gc.collect()
    entries_after = cache_entries(cache_dir)
    summary["compile_cache"]["entries_after"] = entries_after
    summary["wall_s"] = round(time.monotonic() - _T0, 1)
    log(f"compile cache: {entries_before} -> {entries_after} entries; wall {summary['wall_s']}s")
    out_dir = REPO / "chiprun_out"
    out_dir.mkdir(exist_ok=True)
    name = ("chip_smoke_four_chips.json" if args.four_chips else "chip_smoke_latent.json" if args.latent_kernel or args.latent
            else "chip_smoke_ssm_slots.json" if args.ssm_slots else "chip_smoke_ssm.json" if args.ssm_kernel
            else "chip_smoke_group16.json" if args.group16
            else "chip_smoke_grouped" + ("_" + pathlib.Path(args.tree).name.strip(".") if args.tree else "") + ".json" if args.grouped_kernels
            else "chip_smoke_walk_sweep.json" if args.walk_sweep else "chip_smoke_latent_prefill.json" if args.latent_prefill
            else "chip_smoke_flash_sweep.json" if args.flash_sweep
            else "chip_smoke_flash" + ("_" + pathlib.Path(args.tree).name.strip(".") if args.tree else "") + ".json" if args.flash_kernels
            else "chip_smoke_experts.json" if args.expert_product is not None
            else "chip_smoke_loss_chain.json" if args.loss_chain
            else "chip_smoke_train_ops" + ("_" + pathlib.Path(args.tree).name.strip(".") if args.tree else "") + ".json" if args.train_ops
            else "chip_smoke_release.json" if args.release_probe else "chip_smoke_dispatch.json" if args.dispatch_probe
            else "chip_smoke_evict.json" if args.evict_probe
            else "chip_smoke.json")
    (out_dir / name).write_text(json.dumps(summary, indent=1, default=str) + "\n")
    print(json.dumps({"ok": True, "device": device}), flush=True)
    return 0


if __name__ == "__main__":
    sys.exit(main())
