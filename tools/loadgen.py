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

Two drive modes:

* **in-process** (default): builds a tiny CPU engine + continuous-
  batching scheduler and drives the schedule deterministically on a
  VIRTUAL clock (seeded arrivals, fixed step dt) — the reproducible
  mode chaoscheck's overload storm reuses via
  :func:`drive_virtual`.
* **--url http://host:port**: real open-loop HTTP load against a
  running server (serving/server.py): one thread per arrival fires a
  ``POST /v2/models/{name}/generate`` at its scheduled wall time;
  503 + Retry-After answers count as sheds, per priority.

Usage:
  python tools/loadgen.py --rate 50 --duration 2 --mix 0.2,0.2,0.6
  python tools/loadgen.py --url http://127.0.0.1:8000 --model lm ...
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
    ap.add_argument("--max-new", type=int, default=8,
                    help="tokens per request")
    ap.add_argument("--vocab", type=int, default=40)
    ap.add_argument("--slots", type=int, default=4,
                    help="in-process engine batch slots")
    ap.add_argument("--max-queue", type=int, default=32,
                    help="in-process scheduler queue bound")
    ap.add_argument("--dt", type=float, default=0.01,
                    help="in-process virtual-clock tick")
    ap.add_argument("--url", default="",
                    help="drive a live server instead of in-process")
    ap.add_argument("--model", default="lm", help="model name (--url mode)")
    ap.add_argument("--out", default="", help="write the JSON report here")
    args = ap.parse_args()

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
    if args.url:
        report = run_http(args)
    else:
        report = run_inprocess(args)
    text = json.dumps(report, indent=2)
    print(text)
    if args.out:
        with open(args.out, "w") as f:
            f.write(text + "\n")
    return 0


if __name__ == "__main__":
    sys.exit(main())
