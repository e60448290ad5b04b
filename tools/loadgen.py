#!/usr/bin/env python
"""loadgen: stdlib-only open-loop Poisson load generator with a
priority mix and a deadline distribution (ISSUE 14).

Open-loop means arrivals are scheduled by a Poisson process and
submitted at their scheduled time whether or not earlier requests
finished — the load that actually overloads a server, unlike a
closed-loop driver whose offered rate collapses with latency. Each
arrival draws a priority class (interactive / standard / best_effort),
a prompt, and a deadline; the report breaks goodput, shed rate, and
TTFT out per class, which is how the overload-storm smoke proves
"best-effort absorbed the burst, interactive never shed".

Three drive modes:

* **in-process** (default): builds a tiny CPU engine + continuous-
  batching scheduler and drives the schedule deterministically on a
  VIRTUAL clock (seeded arrivals, fixed step dt) — the reproducible
  mode chaoscheck's overload storm reuses via
  :func:`drive_virtual`.
* **--url http://host:port**: real open-loop HTTP load against a
  running server (serving/server.py): one thread per arrival fires a
  ``POST /v2/models/{name}/generate`` at its scheduled wall time;
  503 + Retry-After answers count as sheds, per priority.
* **--disagg-ab** (ISSUE 16): the disaggregated-serving A/B — the SAME
  seeded open-loop schedule of mixed long/short prompts through a
  2-replica unified fleet and a 1 prefill + 1 decode disaggregated
  fleet (equal engine budget), interleaved best-of-N. Per arm: TTFT
  p95 (long prefills queue behind decode iterations on a unified
  replica; a dedicated prefill replica admits back-to-back) and
  decode TPOT p50 (a dedicated decode replica's fixed-shape step loop
  is never interrupted by a prefill). Gates: byte-identical streams
  across arms, zero steady-state retraces on every replica engine
  (ProgramRegistry-backed trace_counts), and both improvement ratios
  over their floors; appends a perfwatch-gated line to
  BENCH_HISTORY.jsonl.

Usage:
  python tools/loadgen.py --rate 50 --duration 2 --mix 0.2,0.2,0.6
  python tools/loadgen.py --url http://127.0.0.1:8000 --model lm ...
  python tools/loadgen.py --disagg-ab --out disagg_bench.json
"""
from __future__ import annotations

import argparse
import dataclasses
import json
import math
import random
import sys
import threading
import time
import urllib.error
import urllib.request
from typing import Callable, Dict, List, Optional, Sequence

sys.path.insert(0, ".")

PRIORITIES = ("interactive", "standard", "best_effort")


@dataclasses.dataclass
class Arrival:
    """One scheduled request."""

    t: float                 # arrival time, seconds from schedule start
    priority: str
    prompt: List[int]
    deadline_s: Optional[float]
    max_new: int


def build_schedule(
    rate_rps: float,
    duration_s: float,
    *,
    mix: Sequence[float] = (0.2, 0.3, 0.5),
    seed: int = 0,
    vocab: int = 40,
    prompt_len_lo: int = 3,
    prompt_len_hi: int = 8,
    deadlines_s: Sequence[Optional[float]] = (None, 5.0, 30.0),
    max_new: int = 8,
) -> List[Arrival]:
    """Seeded Poisson arrival schedule: exponential inter-arrivals at
    ``rate_rps`` over ``duration_s``, priorities drawn from ``mix``
    (interactive, standard, best_effort fractions), deadlines drawn
    uniformly from ``deadlines_s`` (None = no deadline)."""
    if abs(sum(mix) - 1.0) > 1e-6:
        raise ValueError(f"priority mix must sum to 1, got {mix}")
    rng = random.Random(f"loadgen|{seed}")
    out: List[Arrival] = []
    t = 0.0
    while True:
        t += rng.expovariate(rate_rps)
        if t >= duration_s:
            return out
        r = rng.random()
        if r < mix[0]:
            priority = "interactive"
        elif r < mix[0] + mix[1]:
            priority = "standard"
        else:
            priority = "best_effort"
        n = rng.randint(prompt_len_lo, prompt_len_hi)
        prompt = [rng.randrange(1, vocab) for _ in range(n)]
        out.append(Arrival(
            t=t, priority=priority, prompt=prompt,
            deadline_s=rng.choice(list(deadlines_s)), max_new=max_new,
        ))


# ------------------------------------------------------------ schedules
SCHEDULE_SCHEMA = "flexflow-load-schedule-v1"


def save_schedule(schedule: Sequence[Arrival], path: str,
                  *, meta: Optional[Dict] = None) -> None:
    """Serialize the exact arrival schedule (timestamps, prompts,
    priorities, deadlines, max_new) so the identical workload can
    drive live runs, A/B gates, and the sim/ digital twin. ``meta``
    records how it was built (rate, seed, ...) for provenance."""
    doc = {
        "schema": SCHEDULE_SCHEMA,
        "meta": dict(meta or {}),
        "arrivals": [dataclasses.asdict(a) for a in schedule],
    }
    with open(path, "w") as f:
        json.dump(doc, f, indent=1)
        f.write("\n")


def load_schedule(path: str, *, with_meta: bool = False):
    """Replay a recorded schedule deterministically. Returns the
    Arrival list (sorted by arrival time), or (arrivals, meta) with
    ``with_meta=True``."""
    with open(path) as f:
        doc = json.load(f)
    if doc.get("schema") != SCHEDULE_SCHEMA:
        raise ValueError(
            f"{path}: not a load schedule "
            f"(schema={doc.get('schema')!r}, want {SCHEDULE_SCHEMA!r})"
        )
    arrivals = [
        Arrival(
            t=float(d["t"]),
            priority=str(d["priority"]),
            prompt=[int(x) for x in d["prompt"]],
            deadline_s=(
                None if d.get("deadline_s") is None
                else float(d["deadline_s"])
            ),
            max_new=int(d["max_new"]),
        )
        for d in doc["arrivals"]
    ]
    arrivals.sort(key=lambda a: a.t)
    if with_meta:
        return arrivals, dict(doc.get("meta") or {})
    return arrivals


def resolve_schedule(args) -> List[Arrival]:
    """The CLI's schedule source: ``--schedule FILE`` replays a
    recording (and restores its recorded duration for rate math);
    otherwise build from the seeded generator, recording to
    ``--record-schedule FILE`` when asked."""
    if getattr(args, "schedule", ""):
        arrivals, meta = load_schedule(args.schedule, with_meta=True)
        if meta.get("duration_s"):
            args.duration = float(meta["duration_s"])
        elif arrivals:
            args.duration = max(args.duration, arrivals[-1].t)
        return arrivals
    schedule = build_schedule(
        args.rate, args.duration, mix=args.mix_t, seed=args.seed,
        vocab=args.vocab, deadlines_s=args.deadlines_t,
        max_new=args.max_new,
    )
    if getattr(args, "record_schedule", ""):
        save_schedule(schedule, args.record_schedule, meta={
            "rate_rps": args.rate, "duration_s": args.duration,
            "mix": list(args.mix_t), "seed": args.seed,
            "vocab": args.vocab, "max_new": args.max_new,
            "deadlines_s": list(args.deadlines_t),
        })
        print(f"recorded {len(schedule)} arrivals -> "
              f"{args.record_schedule}", file=sys.stderr)
    return schedule


class LoadReport:
    """Per-priority outcome + TTFT accounting; thread-safe for the
    --url mode's per-arrival threads."""

    def __init__(self):
        self._lock = threading.Lock()
        self.per: Dict[str, Dict] = {  # guarded-by: _lock
            p: {
                "submitted": 0, "completed": 0, "shed": 0, "expired": 0,
                "failed": 0, "tokens": 0, "good_tokens": 0, "ttft_s": [],
            }
            for p in PRIORITIES
        }
        self._streams: List = []  # (prompt, tokens) pairs; guarded-by: _lock

    def note_stream(self, prompt: List[int], tokens: List[int]) -> None:
        """Retain one completed stream for byte-exactness checks
        (chaoscheck's overload storm compares against unloaded runs)."""
        with self._lock:
            self._streams.append((list(prompt), list(tokens)))

    def streams(self) -> List:
        with self._lock:
            return list(self._streams)

    def note(self, priority: str, outcome: str, tokens: int = 0,
             good: bool = False, ttft_s: Optional[float] = None) -> None:
        with self._lock:
            d = self.per[priority]
            d["submitted"] += 1
            d[outcome] += 1
            d["tokens"] += tokens
            if good:
                d["good_tokens"] += tokens
            if ttft_s is not None:
                d["ttft_s"].append(ttft_s)

    def render(self, duration_s: float) -> Dict:
        def pct(xs, p):
            if not xs:
                return None
            xs = sorted(xs)
            return xs[min(len(xs) - 1, math.ceil(p * len(xs)) - 1)]

        with self._lock:
            per = {}
            total = {"submitted": 0, "shed": 0, "tokens": 0, "good_tokens": 0}
            for p in PRIORITIES:
                d = self.per[p]
                per[p] = {
                    k: d[k] for k in
                    ("submitted", "completed", "shed", "expired", "failed",
                     "tokens", "good_tokens")
                }
                per[p]["ttft_p50_s"] = pct(d["ttft_s"], 0.50)
                per[p]["ttft_p95_s"] = pct(d["ttft_s"], 0.95)
                for k in total:
                    total[k] += d[k]
        shed_rate = total["shed"] / total["submitted"] if total["submitted"] else 0.0
        return {
            "duration_s": duration_s,
            "submitted": total["submitted"],
            "shed_rate": shed_rate,
            "goodput_tokens_per_s": total["good_tokens"] / max(1e-9, duration_s),
            "tokens_per_s": total["tokens"] / max(1e-9, duration_s),
            "per_priority": per,
        }


# --------------------------------------------------------------- virtual
def drive_virtual(
    scheduler,
    schedule: Sequence[Arrival],
    clock,
    *,
    dt: float = 0.01,
    sampling_cls=None,
    drain_steps: int = 20000,
    on_tick: Optional[Callable[[], None]] = None,
) -> LoadReport:
    """Deterministic open-loop drive on a virtual clock (conftest-style
    ``FakeClock``: callable, with ``.advance(dt)``): each tick submits
    the arrivals now due, steps the scheduler once, and advances the
    clock by ``dt``. Used in-process and by chaoscheck's overload
    storm; returns the filled :class:`LoadReport` (TTFT from request
    traces, so observability must be on)."""
    from flexflow_tpu.generation.engine import SamplingParams
    from flexflow_tpu.serving.resilience import (
        DeadlineExceededError,
        OverloadedError,
    )

    sampling_cls = sampling_cls or SamplingParams
    report = LoadReport()
    live = []  # (arrival, handle)
    i = 0
    t0 = clock()
    steps = 0
    while i < len(schedule) or any(not h.done() for _, h in live):
        now = clock() - t0
        while i < len(schedule) and schedule[i].t <= now:
            a = schedule[i]
            i += 1
            try:
                h = scheduler.submit(
                    a.prompt, sampling_cls(max_new_tokens=a.max_new),
                    deadline_s=a.deadline_s, priority=a.priority,
                )
            except OverloadedError:
                report.note(a.priority, "shed")
                continue
            except DeadlineExceededError:
                report.note(a.priority, "expired")
                continue
            live.append((a, h))
        scheduler.step()
        if on_tick is not None:
            on_tick()
        clock.advance(dt)
        steps += 1
        if steps > drain_steps:
            break
    for a, h in live:
        try:
            tokens = h.result(timeout=0)
        except OverloadedError:
            report.note(a.priority, "shed")
            continue
        except DeadlineExceededError:
            report.note(a.priority, "expired")
            continue
        except Exception:
            report.note(a.priority, "failed")
            continue
        tr = h.trace_dict()
        report.note(
            a.priority, "completed", tokens=len(tokens), good=True,
            ttft_s=tr.get("ttft_s"),
        )
        report.note_stream(a.prompt, tokens)
    return report


def run_inprocess(args) -> Dict:
    """Build a tiny CPU engine + scheduler and drive the schedule on a
    virtual clock (deterministic under --seed)."""
    import os

    os.environ.setdefault("JAX_PLATFORMS", "cpu")
    import jax

    from flexflow_tpu.generation import (
        ContinuousBatchingScheduler,
        GenerationEngine,
        init_decoder_params,
    )
    from flexflow_tpu.models.transformer import TransformerConfig
    from flexflow_tpu.serving.overload import OverloadConfig

    class Clock:
        def __init__(self):
            self.t = 0.0

        def __call__(self):
            return self.t

        def advance(self, dt):
            self.t += dt

    cfg = TransformerConfig(
        num_layers=1, hidden_size=32, num_heads=4, ff_size=64,
        seq_length=64, vocab_size=args.vocab, causal=True,
    )
    params = init_decoder_params(jax.random.key(0), cfg)
    engine = GenerationEngine(
        params, cfg, max_batch_slots=args.slots, block_size=8,
        prompt_buckets=(8, 32, 64),
    )
    clock = Clock()
    sched = ContinuousBatchingScheduler(
        engine, clock=clock, max_queue=args.max_queue,
        overload=OverloadConfig(),
    )
    schedule = resolve_schedule(args)
    report = drive_virtual(sched, schedule, clock, dt=args.dt)
    sched.stop()
    out = report.render(args.duration)
    out["mode"] = "in-process (virtual clock)"
    out["overload"] = sched.overload.activations()
    return out


# ------------------------------------------------------------------ http
def run_http(args) -> Dict:
    """Real open-loop HTTP load: one thread per arrival fires at its
    scheduled wall time. TTFT is approximated by response latency
    (non-streaming generate); sheds are 503 answers."""
    schedule = resolve_schedule(args)
    report = LoadReport()
    base = args.url.rstrip("/")
    url = f"{base}/v2/models/{args.model}/generate"

    def fire(a: Arrival):
        body = {
            "prompt": a.prompt, "max_new_tokens": a.max_new,
            "priority": a.priority,
        }
        if a.deadline_s is not None:
            body["parameters"] = {"timeout_ms": int(a.deadline_s * 1000)}
        req = urllib.request.Request(
            url, data=json.dumps(body).encode(),
            headers={"Content-Type": "application/json"},
        )
        t0 = time.monotonic()
        try:
            with urllib.request.urlopen(req, timeout=300) as r:
                resp = json.loads(r.read())
            report.note(
                a.priority, "completed", tokens=resp.get("num_generated", 0),
                good=True, ttft_s=time.monotonic() - t0,
            )
        except urllib.error.HTTPError as e:
            if e.code == 503:
                report.note(a.priority, "shed")
            elif e.code == 504:
                report.note(a.priority, "expired")
            else:
                report.note(a.priority, "failed")
        except Exception:
            report.note(a.priority, "failed")

    threads = []
    t0 = time.monotonic()
    for a in schedule:
        delay = a.t - (time.monotonic() - t0)
        if delay > 0:
            time.sleep(delay)
        th = threading.Thread(target=fire, args=(a,), daemon=True)
        th.start()
        threads.append(th)
    for th in threads:
        th.join(timeout=300)
    out = report.render(args.duration)
    out["mode"] = f"http ({base})"
    return out


# ------------------------------------------------------------ disagg A/B
def _pct(xs: Sequence[float], p: float) -> Optional[float]:
    if not xs:
        return None
    xs = sorted(xs)
    return xs[min(len(xs) - 1, math.ceil(p * len(xs)) - 1)]


def _git_sha() -> str:
    try:
        import subprocess

        out = subprocess.run(
            ["git", "rev-parse", "--short", "HEAD"],
            capture_output=True, text=True, timeout=10,
        ).stdout.strip()
        return out or "unknown"
    except Exception:
        return "unknown"


def _append_ab_history(path: str, report: Dict) -> None:
    """One perfwatch-schema line (same shape as genbench's
    append_history): timestamped, git-sha-stamped, ok-flagged so a run
    that failed its own gate never enters the rolling baseline."""
    if not path:
        return
    import jax

    entry = {
        "ts": time.strftime("%Y-%m-%dT%H:%M:%S"),
        "git_sha": _git_sha(),
        "backend": jax.default_backend(),
        "mode": "disagg_ab",
        "ok": bool(report.get("ok")),
        "metrics": {
            "disagg_ttft_p95_ratio": report.get("ttft_p95_ratio"),
            "disagg_tpot_p50_ratio": report.get("tpot_p50_ratio"),
            "disagg_ttft_p95_s": (report.get("disagg") or {}).get("ttft_p95_s"),
        },
    }
    try:
        with open(path, "a") as f:
            f.write(json.dumps(entry) + "\n")
    except OSError as e:
        print(f"WARNING: could not append bench history to {path}: {e}",
              file=sys.stderr)


def run_disagg_ab(args) -> Dict:
    """Unified vs disaggregated A/B on live fleets (real clock, real
    threads — the contention being measured IS wall time: prefills
    interleaving into a unified replica's decode loop). Both arms get
    the same engine budget (two engines), the same seeded schedule,
    and fully pre-warmed replicas, so the measured phase is steady
    state and the only difference is pool specialization."""
    import os

    os.environ.setdefault("JAX_PLATFORMS", "cpu")
    import jax

    from flexflow_tpu.generation import (
        GenerationEngine,
        SamplingParams,
        init_decoder_params,
    )
    from flexflow_tpu.models.transformer import TransformerConfig
    from flexflow_tpu.serving.fleet import DisaggregatedFleet, Fleet

    buckets = (8, 128)
    cfg = TransformerConfig(
        num_layers=1, hidden_size=64, num_heads=4, ff_size=128,
        seq_length=160, vocab_size=args.vocab, causal=True,
    )
    params = init_decoder_params(jax.random.key(0), cfg)

    def make_engine():
        # prefix_cache off (genbench's bench idiom): radix reuse would
        # vary prefill suffix shapes and reclaim through the host tier
        # mid-run, which is retrace noise, not the A/B's contention
        return GenerationEngine(
            params, cfg, max_batch_slots=args.slots, block_size=8,
            prompt_buckets=buckets, prefix_cache=False,
        )

    # mixed long/short prompts (3..120 tokens spans both buckets), all
    # standard priority, no deadlines: every arrival must COMPLETE in
    # both arms or the byte-exactness comparison is meaningless
    schedule = build_schedule(
        args.rate, args.duration, mix=(0.0, 1.0, 0.0), seed=args.seed,
        vocab=args.vocab, prompt_len_lo=3, prompt_len_hi=120,
        deadlines_s=(None,), max_new=args.max_new,
    )
    sk = dict(max_queue=max(256, args.max_queue))

    def run_arm(gen):
        """Drive the schedule open-loop; returns (results, retraces)
        with results = [(arrival, tokens|None, ttft_s, total_s)]."""
        reps = list(gen.replicas)
        # steady state: compile every prompt bucket + the decode
        # program on every replica engine BEFORE the measured phase
        for r in reps:
            for b in buckets:
                n = min(b, cfg.seq_length - args.max_new - 2)
                r.engine.generate([[1] * n], SamplingParams(max_new_tokens=2))
        warm = [dict(r.engine.trace_counts) for r in reps]
        gen.start()
        results, lock, threads = [], threading.Lock(), []

        def waiter(a, h, t_sub):
            try:
                tokens = h.result(timeout=120.0)
            except Exception:
                with lock:
                    results.append((a, None, None, None))
                return
            total_s = time.monotonic() - t_sub
            tr = h.trace_dict()
            with lock:
                results.append((a, tokens, tr.get("ttft_s"), total_s))

        t0 = time.monotonic()
        for a in schedule:
            delay = a.t - (time.monotonic() - t0)
            if delay > 0:
                time.sleep(delay)
            t_sub = time.monotonic()
            h = gen.submit(
                a.prompt, SamplingParams(max_new_tokens=a.max_new),
                priority=a.priority,
            )
            th = threading.Thread(target=waiter, args=(a, h, t_sub), daemon=True)
            th.start()
            threads.append(th)
        for th in threads:
            th.join(timeout=120)
        retraces = {}
        for w, r in zip(warm, reps):
            for k, v in r.engine.trace_counts.items():
                d = v - w.get(k, 0)
                if d > 0:
                    retraces[k] = retraces.get(k, 0) + d
        gen.stop()
        return results, retraces

    def metrics(results):
        comp = [x for x in results if x[1] is not None]
        ttfts = [t for (_, _, t, _) in comp if t is not None]
        tpots = [
            (tot - ttft) / max(1, len(toks) - 1)
            for (_, toks, ttft, tot) in comp
            if ttft is not None and len(toks) > 1
        ]
        return {
            "completed": len(comp),
            "ttft_p95_s": _pct(ttfts, 0.95),
            "tpot_p50_s": _pct(tpots, 0.50),
        }

    def build(name):
        # equal engine budget per arm: n prefill + n decode specialized
        # replicas vs 2n unified ones
        if name == "unified":
            return Fleet(
                make_engine, n=2 * args.ab_replicas, name=args.model,
                scheduler_kwargs=sk,
            )
        return DisaggregatedFleet(
            make_engine, n_prefill=args.ab_replicas,
            n_decode=args.ab_replicas, name=args.model,
            scheduler_kwargs=sk,
        )

    per_rep = {"unified": [], "disagg": []}
    streams: Dict[str, List] = {}
    retrace_totals = {"unified": 0, "disagg": 0}
    problems: List[str] = []
    for rep in range(args.ab_repeats):
        for name in ("unified", "disagg"):  # interleaved: shared noise
            results, retraces = run_arm(build(name))
            m = metrics(results)
            per_rep[name].append(m)
            retrace_totals[name] += sum(retraces.values())
            if retraces:
                problems.append(f"{name} rep {rep}: steady-state retraces {retraces}")
            if m["completed"] != len(schedule):
                problems.append(
                    f"{name} rep {rep}: {m['completed']}/{len(schedule)} completed"
                )
            if rep == 0:
                streams[name] = sorted(
                    (tuple(a.prompt), tuple(toks))
                    for (a, toks, _, _) in results if toks is not None
                )

    exact = streams.get("unified") == streams.get("disagg")
    if not exact:
        problems.append("streams diverged between the unified and disagg arms")
    best = {
        name: {
            "ttft_p95_s": min(m["ttft_p95_s"] for m in per_rep[name]),
            "tpot_p50_s": min(m["tpot_p50_s"] for m in per_rep[name]),
            "per_rep": per_rep[name],
        }
        for name in ("unified", "disagg")
    }
    ttft_ratio = best["unified"]["ttft_p95_s"] / max(1e-9, best["disagg"]["ttft_p95_s"])
    tpot_ratio = best["unified"]["tpot_p50_s"] / max(1e-9, best["disagg"]["tpot_p50_s"])
    if ttft_ratio < args.min_ttft_improvement:
        problems.append(
            f"TTFT p95 ratio {ttft_ratio:.3f} below floor {args.min_ttft_improvement}"
        )
    if tpot_ratio < args.min_tpot_improvement:
        problems.append(
            f"decode TPOT ratio {tpot_ratio:.3f} below floor {args.min_tpot_improvement}"
        )
    report = {
        "mode": "disagg_ab",
        "schedule": {
            "arrivals": len(schedule), "rate_rps": args.rate,
            "duration_s": args.duration, "seed": args.seed,
            "max_new": args.max_new,
        },
        "unified": best["unified"],
        "disagg": best["disagg"],
        "ttft_p95_ratio": ttft_ratio,
        "tpot_p50_ratio": tpot_ratio,
        "exact": exact,
        "steady_state_retraces": retrace_totals,
        "problems": problems,
        "ok": not problems,
    }
    _append_ab_history(args.history_out, report)
    return report


def main() -> int:
    ap = argparse.ArgumentParser(
        description=__doc__,
        formatter_class=argparse.RawDescriptionHelpFormatter,
    )
    ap.add_argument("--rate", type=float, default=50.0,
                    help="offered load, requests/s (Poisson)")
    ap.add_argument("--duration", type=float, default=2.0,
                    help="schedule length, seconds")
    ap.add_argument("--mix", default="0.2,0.3,0.5",
                    help="interactive,standard,best_effort fractions")
    ap.add_argument("--deadlines", default="none,5,30",
                    help="deadline choices in seconds ('none' = no deadline)")
    ap.add_argument("--seed", type=int, default=0)
    ap.add_argument("--schedule", default="",
                    help="replay a recorded arrival schedule (JSON) instead "
                    "of building one (in-process and --url modes)")
    ap.add_argument("--record-schedule", default="",
                    help="write the built arrival schedule here (JSON), so "
                    "the identical workload can drive live runs and the "
                    "sim/ digital twin")
    ap.add_argument("--record-only", action="store_true",
                    help="with --record-schedule: write the schedule and "
                    "exit without driving it")
    ap.add_argument("--max-new", type=int, default=None,
                    help="tokens per request (default 8; 32 in --disagg-ab, "
                    "long enough to amortize the handoff over the stream "
                    "and keep the decode batch resident)")
    ap.add_argument("--vocab", type=int, default=40)
    ap.add_argument("--slots", type=int, default=None,
                    help="in-process engine batch slots (default 4; 32 in "
                    "--disagg-ab — the padded decode step IS the unified "
                    "arm's admission interference)")
    ap.add_argument("--max-queue", type=int, default=32,
                    help="in-process scheduler queue bound")
    ap.add_argument("--dt", type=float, default=0.01,
                    help="in-process virtual-clock tick")
    ap.add_argument("--url", default="",
                    help="drive a live server instead of in-process")
    ap.add_argument("--model", default="lm", help="model name (--url mode)")
    ap.add_argument("--out", default="", help="write the JSON report here")
    ap.add_argument("--disagg-ab", action="store_true",
                    help="unified vs disaggregated fleet A/B (ISSUE 16)")
    ap.add_argument("--ab-repeats", type=int, default=3,
                    help="interleaved repeats per arm (best-of)")
    ap.add_argument("--ab-replicas", type=int, default=1,
                    help="disagg-ab pool width: n prefill + n decode vs "
                    "2n unified replicas (keep small on CPU hosts — "
                    "every replica is a thread)")
    ap.add_argument("--min-ttft-improvement", type=float, default=1.0,
                    help="disagg-ab gate: unified/disagg TTFT p95 ratio floor")
    ap.add_argument("--min-tpot-improvement", type=float, default=1.0,
                    help="disagg-ab gate: unified/disagg decode TPOT ratio floor")
    ap.add_argument("--history-out", default="BENCH_HISTORY.jsonl",
                    help="disagg-ab: append a perfwatch line here ('' disables)")
    args = ap.parse_args()

    if args.max_new is None:
        args.max_new = 32 if args.disagg_ab else 8
    if args.slots is None:
        args.slots = 32 if args.disagg_ab else 4
    args.mix_t = tuple(float(x) for x in args.mix.split(","))
    args.deadlines_t = tuple(
        None if x.strip().lower() == "none" else float(x)
        for x in args.deadlines.split(",")
    )
    if args.record_only:
        if not args.record_schedule:
            print("--record-only needs --record-schedule FILE", file=sys.stderr)
            return 2
        resolve_schedule(args)
        return 0
    if not args.url:  # --url drives someone else's server and compiles nothing
        from flexflow_tpu.device import enable_compile_cache

        enable_compile_cache()
    if args.disagg_ab:
        report = run_disagg_ab(args)
    elif args.url:
        report = run_http(args)
    else:
        report = run_inprocess(args)
    text = json.dumps(report, indent=2)
    print(text)
    if args.out:
        with open(args.out, "w") as f:
            f.write(text + "\n")
    if args.disagg_ab and not report["ok"]:
        for p in report["problems"]:
            print(f"FAIL: {p}", file=sys.stderr)
        return 1
    return 0


if __name__ == "__main__":
    sys.exit(main())
