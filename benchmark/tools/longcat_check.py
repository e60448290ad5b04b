#!/usr/bin/env python3
"""LongCat-Flash-Chat's checks beside the benchmark's own runs (as ``command_a_check.py``).

    python benchmark/tools/longcat_check.py compile --slots 16 24 32      (sandbox, no chip)
    chiprun -- python benchmark/tools/longcat_check.py control --seed 1                 (one run of the cell a call)
    chiprun -- python benchmark/tools/longcat_check.py parts                            (device time by sub-layer part)

``compile``: deviceless v5e compiles of the cell's decode program and of
every prefill bucket at the configuration's real widths, as
``compile_check.py`` does for GPT-2 (same rule: a setting fits if every
program leaves 1 GiB of the chip's 15.75 GiB to spare), the weights'
bytes by ``memory_analysis()`` (the decode program's arguments less its
cache), what each prefill's latent attention lowers to (a streamed call
holds no ``[64, S, S]`` temporary, which the temporaries read here show)
and the form the routed branch takes. ``--parts`` writes, per program, the
compiled instructions' names by scope (``chiprun_out/longcat_parts.json``)
for a trace's operations to be grouped by sub-layer part. Nothing runs.

``control``: the readings the cell's limits are set from, taken ON the
timed path: one run of the cell as ``benchmark/run.py`` makes it (its
``main`` and the cell's driver), in which the driver's judged sample
carries, beside the served tokens and the stated arithmetic's choices
after the same prefixes, the choices of the four controls: int8 weights
and bfloat16 running sums (a step coarser, over every judged request); the
identity experts' term dropped and the gates renormalised (this model's
own mechanisms done wrong, over the first ``--mechanism-requests`` judged
requests). Every arm then goes through the driver's own comparison
(``serve_command_a.verdict``), against the limits in the cell's file: the
exit code is 0 only if the served tokens come out correct and every
control does not. The row goes to ``chiprun_out/control/<seed>.json`` and
the judged tokens themselves beside it (``.npz``).
"""
from __future__ import annotations

import argparse
import json
import os
import pathlib
import re
import sys
import time

ROOT = pathlib.Path(__file__).resolve().parents[2]
sys.path.insert(0, str(ROOT))

CELL = "longcat-flash-chat.agent-turns"
PRECISION = ("int8", "bfloat16_sums")  # a step coarser than the configuration states
MECHANISMS = ("no_zero_experts", "renormalised_gates")  # this model's own, done wrong
SCOPES = ("attention.latent.expand", "attention.latent.absorb", "attention.latent", "experts.zero", "experts.shortcut",
          "router", "mlp", "cache_write", "embed", "head")


def parts_of(text: str) -> dict:
    """``{instruction name: scope}`` of a compiled program's text: the
    innermost of :data:`SCOPES` in the instruction's ``op_name``."""
    out = {}
    for name, op_name in re.findall(r"^\s*(?:ROOT )?%?([\w.\-]+) = .*?op_name=\"([^\"]*)\"", text, flags=re.M):
        found = [(op_name.rfind(s), len(s), s) for s in SCOPES if s in op_name]
        layer = re.search(r"layer(\d+)", op_name)
        if found:
            out[name] = max(found)[2] + (f"@{layer.group(1)}" if layer else "")
    return out


def compile_(slots_list, buckets=None, max_seq_len=None, parts=False) -> None:
    os.environ.setdefault("TPU_LOG_DIR", "disabled")
    os.environ.setdefault("JAX_PLATFORMS", "cpu")
    import jax
    import jax.numpy as jnp
    from jax.sharding import SingleDeviceSharding

    from benchmark import spec
    from benchmark.reference import longcat_flash
    from benchmark.tools import compile_check
    from flexflow_tpu.generation import GenerationEngine, init_decoder_params
    from flexflow_tpu.ops import expert_product

    compile_check.take_tpu_paths()
    expert_product.on_tpu = lambda: True  # and the routed sum's form the chip takes
    one = SingleDeviceSharding(compile_check.topology().devices[0])
    cell = spec.load_cell(CELL)
    d = cell.workload["deployment"]
    max_seq_len = int(max_seq_len or d["max_seq_len"])
    buckets = [int(b) for b in (buckets or d["prompt_buckets"])]
    cfg = longcat_flash.engine_config(cell.config, max_seq_len)
    shapes = jax.eval_shape(lambda k: init_decoder_params(k, cfg), jax.random.key(0))
    sds = lambda shape, dtype: jax.ShapeDtypeStruct(shape, dtype, sharding=one)  # noqa: E731
    on_chip = lambda tree: jax.tree.map(lambda a: sds(a.shape, a.dtype), tree)  # noqa: E731
    params = on_chip(shapes)
    weights = sum(a.size * a.dtype.itemsize for a in jax.tree.leaves(shapes))
    key = jax.eval_shape(lambda: jax.random.key(0))
    i32, f32 = jnp.int32, jnp.float32
    found = {}
    for slots in slots_list:
        engine = GenerationEngine(
            shapes, cfg, max_batch_slots=slots, block_size=int(d["block_size"]),
            prompt_buckets=buckets, max_seq_len=max_seq_len,
        )
        engine.backend = "tpu"
        engine.attention_kernels = engine.paged_lowerings()
        print(f"longcat slots={slots}: kernels {engine.kernel_stats()}, prefill {engine.prefill_attention_stats()['programs']}, "
              f"experts {engine.expert_lowerings()}, refused {sorted(engine.unsupported)}", flush=True)
        b, mb, v = slots, engine.max_blocks_per_seq, cfg.vocab_size
        ck, cv = (sds(a.shape, a.dtype) for a in (engine.cache.k, engine.cache.v))
        counts = on_chip(engine.expert_counts)
        held = ck.size * ck.dtype.itemsize
        t0 = time.time()
        dec = jax.jit(engine._decode_impl, donate_argnums=(3, 4, 13)).lower(
            params, sds((b,), i32), sds((b,), i32), ck, cv, sds((b, mb), i32), sds((b,), i32),
            sds((b,), f32), sds((b,), i32), sds((b,), f32), sds((b,), jnp.uint32), sds((b,), i32),
            sds((b, v), f32), {}, counts,
        ).compile()
        text = dec.as_text()
        by_memory = dec.memory_analysis().argument_size_in_bytes - held
        ok = compile_check.report(
            f"longcat slots={slots} decode (latent cache {tuple(ck.shape)} {held / compile_check.GIB:.2f} GiB; weights "
            f"{weights / 1e9:.3f} GB by the shapes, {by_memory / 1e9:.3f} GB by memory_analysis() (arguments less the cache); "
            f"Mosaic calls {text.count('tpu_custom_call')}; {time.time() - t0:.0f}s)", dec)
        found[f"decode[{slots}]"] = parts_of(text)
        for bucket in buckets:
            t0 = time.time()
            pre = jax.jit(engine._prefill_impl).lower(
                params, sds((1, bucket), i32), sds((), i32), ck, cv, sds((mb,), i32), sds((), f32),
                sds((), i32), jax.ShapeDtypeStruct(key.shape, key.dtype, sharding=one), sds((v,), f32),
                {}, None, counts,
            ).compile()
            text = pre.as_text()
            # prefill donates nothing: the old cache lives beside the new
            ok &= compile_check.report(
                f"longcat slots={slots} prefill[{bucket}] ({engine.prefill_lowering(bucket)}; experts {engine.expert_form(bucket)}, "
                f"Mosaic calls {text.count('tpu_custom_call')}; a [heads, S, S] float32 would be "
                f"{4 * cfg.num_heads * bucket * bucket / compile_check.GIB:.1f} GiB; {time.time() - t0:.0f}s)", pre)
            found[f"prefill[{bucket}]"] = parts_of(text)
        print(f"longcat slots={slots}: {'FITS' if ok else 'does not fit'}", flush=True)
        del engine
    if parts:
        out = ROOT / "chiprun_out"
        out.mkdir(exist_ok=True)
        (out / "longcat_parts.json").write_text(json.dumps(found))


def parts(seed: int, prompt_len: int, new_tokens: int) -> int:
    """On the chip: ONE prefill of ``prompt_len`` tokens and ``new_tokens``
    decode steps of a full batch under the profiler, the device's
    operations grouped by the scope their instruction's ``op_name`` holds
    (the compiled text of the very programs that ran: this profiler's
    events carry no scope path): seconds by sub-layer part and program, to
    ``chiprun_out/pr41/parts.json``. Self times: a ``while``'s body is not
    counted twice."""
    import collections
    import glob
    import tempfile

    import jax
    import numpy as np

    from benchmark import spec, trace_reduce
    from benchmark.drivers import serve_longcat
    from flexflow_tpu.device import enable_compile_cache, require_tpu
    from flexflow_tpu.generation.engine import SamplingParams

    require_tpu()
    enable_compile_cache()
    cell = spec.load_cell(CELL)
    _, cfg, engine = serve_longcat.build_engine(cell, seed)
    found = {}
    for attr in ("_prefill_jit", "_decode_jit"):  # the text of what runs, taken at each program's first call
        def wrapped(*args, jit=getattr(engine, attr), attr=attr):
            if attr not in found:
                found[attr] = parts_of(jit.lower(*args).compile().as_text())
            return jit(*args)
        setattr(engine, attr, wrapped)
    rs = np.random.RandomState(seed)
    prompts = [[int(t) for t in rs.randint(0, cfg.vocab_size, size=prompt_len)] for _ in range(engine.max_batch_slots)]
    engine.generate(prompts[:2], SamplingParams(max_new_tokens=4))
    engine.reset()
    out = tempfile.mkdtemp()
    opts = jax.profiler.ProfileOptions()
    opts.python_tracer_level, opts.host_tracer_level = 0, 1
    jax.profiler.start_trace(out, profiler_options=opts)
    engine.generate(prompts, SamplingParams(max_new_tokens=new_tokens))
    jax.profiler.stop_trace()
    dev = trace_reduce.read_xplane(sorted(glob.glob(out + "/plugins/profile/*/*.xplane.pb"))[-1]).devices[0]
    runs = sorted((a, b, "_prefill_jit" if "prefill_impl" in n else "_decode_jit") for n, a, b in dev.modules
                  if "prefill_impl" in n or "decode_impl" in n)
    own = trace_reduce.self_times(dev.ops)
    by_part = {"_prefill_jit": collections.Counter(), "_decode_jit": collections.Counter()}
    by_op = {"_prefill_jit": collections.Counter(), "_decode_jit": collections.Counter()}
    calls = collections.Counter(r[2] for r in runs)
    i = 0
    for (name, a, b), t in sorted(zip(dev.ops, own), key=lambda e: e[0][1]):
        while i < len(runs) and runs[i][1] < a:
            i += 1
        if i == len(runs) or runs[i][0] > a:
            continue
        program, op = runs[i][2], trace_reduce.op_name(name)
        part = found[program].get(op, "(no scope)").split("@")[0]
        by_part[program][part] += t / 1e9
        by_op[program][f"{part}: {trace_reduce.op_family(op)}"] += t / 1e9
    row = {"seed": seed, "slots": engine.max_batch_slots, "prompt_len": prompt_len, "program_runs": dict(calls),
           "program_s": {p: sum((b - a) for a, b, q in runs if q == p) / 1e9 for p in calls},
           "ms_a_run_by_part": {p: {k: round(1e3 * v / calls[p], 3) for k, v in c.most_common()} for p, c in by_part.items()},
           "ms_a_run_by_part_and_op": {p: {k: round(1e3 * v / calls[p], 3) for k, v in c.most_common(24)} for p, c in by_op.items()}}
    (ROOT / "chiprun_out" / "pr41").mkdir(parents=True, exist_ok=True)
    (ROOT / "chiprun_out" / "pr41" / "parts.json").write_text(json.dumps(row, indent=1))
    print("parts row: " + json.dumps(row), flush=True)
    return 0


def control(seed: int, seconds: float, mechanism_requests: int, rehearsal: bool) -> int:
    """One seed's row: a RUN of the cell as ``benchmark/run.py`` makes it
    (the same ``main``, the same driver: weights from the seed, HTTP, the
    closed loop of clients, the timed window), whose judged sample carries
    the controls' choices beside the served tokens; every arm then goes
    through the driver's own ``verdict``. 0 if the served tokens come out
    correct and every control does not."""
    import numpy as np

    from benchmark import run as harness
    from benchmark.drivers import serve_longcat as driver

    driver.CONTROL_ARMS = {c: None if c in PRECISION else mechanism_requests for c in PRECISION + MECHANISMS}
    kept, run = {}, driver.run

    def run_and_keep(cell, rt, peaks):
        kept.update(run(cell, rt, peaks), cell=cell)
        return kept

    driver.run = run_and_keep
    t0 = time.monotonic()
    rc = harness.main(["--workload", CELL, "--seed", str(seed), "--seconds", str(seconds), "--trace", "0"]
                      + (["--rehearse"] if rehearsal else []))
    sample, w = kept["reference"], kept["cell"].workload
    row = {"seed": seed, "seconds": round(time.monotonic() - t0, 1), "run_rc": rc, "run_correct": bool(kept["correct"]),
           "why_incorrect": kept["why_incorrect"],
           "limits": {k: float(w[k]) for k in ("gap_ratio_limit", "request_excess_limit", "capped_gap_ratio_limit")},
           "program": sample["read"], "comes_out_correct": {"program": bool(kept["correct"])}}
    for name, n in driver.CONTROL_ARMS.items():
        row[name], failures = driver.verdict(*driver.arm_of(sample, name, n), w)
        row[name]["fails"] = failures
        row["comes_out_correct"][name] = not failures
    out_dir = ROOT / "chiprun_out" / "control"
    out_dir.mkdir(parents=True, exist_ok=True)
    if not rehearsal:  # the judged tokens themselves, for a reading the row does not hold
        np.savez_compressed(out_dir / f"{seed}.npz", of=sample["of"], valid=sample["valid"],
                            **{f"{arm}.{k}": v for arm, j in sample["judged"].items() for k, v in j.items()})
        (out_dir / f"{seed}.json").write_text(json.dumps(row))
    print("control row: " + json.dumps(row), flush=True)
    for arm in ("program",) + PRECISION + MECHANISMS:
        r = row[arm]
        print(f"{arm:17s} gap_ratio {r['gap_ratio']:.4g}  worst_request_excess {r['worst_request_excess']:.4g}  capped_gap_ratio "
              f"{r['capped_gap_ratio']:.4g}  ({r['tokens']} tokens of {r['requests']} requests)  -> "
              f"{'correct' if row['comes_out_correct'][arm] else 'NOT correct'}", flush=True)
    sound = row["comes_out_correct"].pop("program")
    return 0 if sound and not any(row["comes_out_correct"].values()) else 1


def main() -> int:
    ap = argparse.ArgumentParser(description=__doc__.split("\n\n")[0])
    sub = ap.add_subparsers(dest="what", required=True)
    c = sub.add_parser("compile")
    c.add_argument("--slots", type=int, nargs="+", default=[16, 24, 32])
    c.add_argument("--buckets", type=int, nargs="+", default=None)
    c.add_argument("--max-seq-len", type=int, default=None)
    c.add_argument("--parts", action="store_true", help="write the compiled instructions' scopes to chiprun_out/longcat_parts.json")
    c = sub.add_parser("parts")
    c.add_argument("--seed", type=int, default=4100500003)
    c.add_argument("--prompt-len", type=int, default=4000)
    c.add_argument("--new-tokens", type=int, default=64)
    c = sub.add_parser("control")
    c.add_argument("--seed", type=int, required=True)
    c.add_argument("--seconds", type=float, default=50.0)
    c.add_argument("--mechanism-requests", type=int, default=8,
                   help="the judged requests over which each mechanism control's choices are computed (they fail by tens)")
    c.add_argument("--rehearse", action="store_true", help="sandbox only: tiny widths on the CPU")
    args = ap.parse_args()
    if args.what == "parts":
        return parts(args.seed, args.prompt_len, args.new_tokens)
    if args.what == "compile":
        compile_(args.slots, args.buckets, args.max_seq_len, args.parts)
        return 0
    return control(args.seed, args.seconds, args.mechanism_requests, args.rehearse)


if __name__ == "__main__":
    sys.exit(main())
