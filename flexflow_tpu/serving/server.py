"""HTTP inference server speaking the KServe/Triton v2 protocol subset.

Reference: triton/ (SURVEY §2.9) — the reference serves its Legion op
graph as a Triton backend; its wire protocol is Triton's v2 inference
API. This server implements the same surface directly (stdlib only):

  GET  /v2/health/live                     -> 200 while the process runs
  GET  /v2/health/ready                    -> 200 only when actually able
                                              to serve (not draining, no
                                              model breaker open)
  GET  /v2/stats                           -> per-model serving stats
                                              (queue depth, admission
                                              counters, latency,
                                              generation tokens/s +
                                              cache occupancy, and the
                                              self-healing counters:
                                              recoveries, replayed_tokens,
                                              quarantined, watchdog_trips)
  GET  /metrics                            -> Prometheus text exposition:
                                              every per-model counter,
                                              gauge, latency window and
                                              the TTFT/TPOT/queue-time
                                              histograms (obs/prom.py)
  GET  /v2/debug/traces[?id=N&model=M&n=K] -> recent per-request traces
                                              (queue time, TTFT, TPOT,
                                              event waterfall)
  GET  /v2/debug/timeline[?model=M]        -> engine flight recorder as
                                              chrome://tracing JSON
                                              (+ recent incident dumps)
  GET  /v2/debug/cache[?model=M]           -> KV-cache block telemetry:
                                              per-request residency,
                                              fragmentation, watermarks,
                                              pressure, admission waits
  GET  /v2/debug/programs[?model=M]        -> jit program registry:
                                              traced signatures, compile
                                              times, retrace blame
  GET  /v2/debug/predictions[?model=M]     -> cost-model truth: per-step
                                              (predicted, measured)
                                              pairs, relative-error
                                              distributions, and
                                              calibration-drift alarms
                                              with blame
  GET  /v2/debug/anatomy[?model=M&capture=K] -> step-anatomy profiler:
                                              per-kind phase breakdown,
                                              device-bubble ratio,
                                              host/device-bound
                                              classification, the
                                              overlap-headroom
                                              projection, and (with
                                              capture=K) arming a
                                              K-step two-lane capture
                                              whose chrome://tracing
                                              timeline rides the next
                                              scrape — per replica on
                                              fleets, like the other
                                              debug endpoints
  GET  /v2/slo                             -> per-model SLO objectives
                                              with fast/slow burn rates
  GET  /v2/overload[?model=M]              -> overload control state per
                                              generation unit: adaptive
                                              concurrency limiter,
                                              degrade-ladder level +
                                              history, pressure, and the
                                              per-reason / per-priority
                                              rejection split
  GET  /v2/fleet                           -> fleet serving tier state:
                                              replica lifecycle states,
                                              residency, router score
                                              inputs + decisions, and
                                              recent failover / drain /
                                              replace events
  GET  /v2/fleet/autoscale                 -> want-more / want-fewer
                                              replica signal derived
                                              from sustained limiter
                                              saturation across the
                                              fleet (ROADMAP item 3's
                                              autoscaling remainder)
  GET  /v2/models/{name}                   -> model metadata
  GET  /v2/models/{name}/ready             -> per-model readiness
  POST /v2/models/{name}/infer             -> run inference
  POST /v2/models/{name}/generate          -> autoregressive generation
                                              (GenerationModel); JSON
                                              response, or SSE token
                                              stream with "stream": true

Failed generation requests embed their RequestTrace (and, for
quarantines/restarts, the flight-recorder snapshot riding the error) in
the error response body — the client holds the postmortem without a
second round trip.

Infer request JSON: {"inputs": [{"name", "shape", "datatype", "data"}]},
response mirrors it — the v2 tensor format with row-major flat data. A
per-request deadline may ride along as ``{"parameters": {"timeout_ms":
N}}`` or the ``X-Request-Timeout-Ms`` header; expired requests are
rejected with 504 before they reach the device.

Status mapping for resilience rejections: queue full / circuit open /
draining -> 503, expired deadline -> 504, backend death -> 500.
Overload rejections (serving/overload.py) are 503s that additionally
carry a ``Retry-After`` header and a structured body (``reason`` =
queue_full / limiter / infeasible / degraded, ``priority``,
``retry_after_s``). A request's priority class rides the generate
body's ``"priority"`` field, the infer request's
``{"parameters": {"priority": ...}}``, or the ``X-Request-Priority``
header.
"""
from __future__ import annotations

import json
import threading
from concurrent.futures import TimeoutError as _FuturesTimeout
from http.server import BaseHTTPRequestHandler, ThreadingHTTPServer
from typing import Dict, Optional
from urllib.parse import parse_qs, urlparse

import numpy as np

import math

from ..obs import (
    GLOBAL_LEDGER,
    GLOBAL_PROGRAMS,
    JourneyIndex,
    JourneyRecorder,
    journey_to_chrome_trace,
    journey_to_otlp,
    parse_traceparent,
    render_prometheus,
)
from ..obs.steptrace import phase
from ..runtime import faults
from .batcher import DynamicBatcher, make_batcher
from .model import InferenceModel
from .resilience import ResilienceError, http_status, retry_after_s


def _reject_payload(e: ResilienceError) -> dict:
    """Error body for a typed rejection; OverloadedError additionally
    carries the structured reason / priority / retry_after_s fields."""
    payload = {"error": str(e), "type": type(e).__name__}
    for field in ("reason", "priority", "retry_after_s", "predicted_ttft_s"):
        v = getattr(e, field, None)
        if v is not None:
            payload[field] = v
    return payload


def _reject_headers(e: ResilienceError) -> "dict | None":
    """``Retry-After`` for overload rejections (whole seconds, >= 1,
    per RFC 9110)."""
    ra = retry_after_s(e)
    if ra is None:
        return None
    return {"Retry-After": str(max(1, int(math.ceil(ra))))}

_V2_DTYPES = {
    "FP32": np.float32, "FP64": np.float64, "FP16": np.float16,
    "BF16": np.dtype("bfloat16") if hasattr(np, "bfloat16") else np.float32,
    "INT32": np.int32, "INT64": np.int64, "BOOL": np.bool_,
}
_NP_TO_V2 = {
    "float32": "FP32", "float64": "FP64", "float16": "FP16",
    "bfloat16": "BF16", "int32": "INT32", "int64": "INT64", "bool": "BOOL",
}


class _Listener(ThreadingHTTPServer):
    """The listening socket. ``socketserver``'s backlog of 5 is for a
    desk: a closed loop's clients all connect in the same instant, and
    the accept loop shares the interpreter's lock with handler threads
    that parse prompts of thousands of tokens. Connections past the
    backlog are RESET once they send their request (14 of a run's first
    96 read ``ConnectionResetError`` at the client, about one benchmark
    run in forty: PERF.md §7), so the queue holds a burst of any closed
    loop a cell runs (the kernel caps it at ``net.core.somaxconn``)."""

    request_queue_size = 1024


class InferenceServer:
    """Serves one or more InferenceModels over HTTP with dynamic batching.

    With a ModelRepository attached, the Triton v2 repository lifecycle
    endpoints are live (reference: Triton's model-repository management
    above triton/src/model.cc):

      POST /v2/repository/index                  -> available + loaded state
      POST /v2/repository/models/{name}/load     -> load from disk
      POST /v2/repository/models/{name}/unload   -> stop serving + drop
    """

    def __init__(
        self,
        host: str = "127.0.0.1",
        port: int = 8000,
        max_delay_s: float = 0.005,
        repository=None,
        max_queue: int = 256,
        batcher_kwargs: Optional[dict] = None,
    ):
        self.host = host
        self.port = port
        self.models: Dict[str, InferenceModel] = {}
        self.batchers: Dict[str, DynamicBatcher] = {}
        self.generators: Dict[str, "GenerationModel"] = {}  # noqa: F821
        self.max_delay_s = max_delay_s
        self.repository = repository
        # per-model batcher construction knobs (breaker/retry/clock are
        # injectable here so chaos tests run on virtual time); pass
        # breaker/retry as zero-arg FACTORIES on multi-model servers so
        # each model gets its own instance (see make_batcher)
        self._batcher_kwargs = dict(batcher_kwargs or {})
        self._batcher_kwargs.setdefault("max_delay_s", max_delay_s)
        self._batcher_kwargs.setdefault("max_queue", max_queue)
        self._draining = False
        self._httpd: Optional[ThreadingHTTPServer] = None
        self._thread: Optional[threading.Thread] = None
        # fleet-wide journeys (ISSUE 20): the HTTP ingress span lane.
        # Contexts are minted here (or joined from an inbound W3C
        # traceparent) only for generators whose journeys are on, so a
        # journeys-off deployment stays inert.
        self.journeys = JourneyRecorder(lane="http")

    def register(self, model: InferenceModel):
        self.models[model.name] = model
        b = make_batcher(model, self._batcher_kwargs)
        self.batchers[model.name] = b
        if self._httpd is not None:
            b.start()

    def unregister(self, name: str) -> bool:
        b = self.batchers.pop(name, None)
        if b is not None:
            b.stop()
        return self.models.pop(name, None) is not None

    def register_generation(self, model: "GenerationModel"):  # noqa: F821
        """Serve a GenerationModel (serving/generation.py) next to the
        batched InferenceModels."""
        self.generators[model.name] = model
        if self._httpd is not None:
            model.start()

    def unregister_generation(self, name: str) -> bool:
        g = self.generators.pop(name, None)
        if g is not None:
            g.stop()
        return g is not None

    # ------------------------------------------------------------- health
    def live(self) -> bool:
        return True

    def ready(self) -> bool:
        """Real readiness, not a constant: serving, not draining, and no
        model's circuit breaker holding traffic."""
        if self._httpd is None or self._draining:
            return False
        # snapshot: repository load/unload mutates the dict concurrently
        return all(b.breaker.ready() for b in list(self.batchers.values())) and all(
            g.breaker.ready() for g in list(self.generators.values())
        )

    def model_ready(self, name: str) -> bool:
        g = self.generators.get(name)
        if g is not None:
            return g.ready()
        b = self.batchers.get(name)
        return b is not None and b.ready()

    def readiness(self) -> Dict:
        """Readiness + rationale: per model, the three health inputs —
        circuit breaker state, watchdog/recovery evidence, and SLO burn.
        The boolean keeps the PR 1 semantics (breaker-driven); the
        rationale explains it, and a breaching SLO shows up as degraded
        without flipping readiness."""
        models: Dict[str, Dict] = {}
        for name, b in list(self.batchers.items()):
            models[name] = {"ready": b.ready(), "breaker": b.breaker.state}
        for name, g in list(self.generators.items()):
            models[name] = g.readiness_rationale()
        return {
            "ready": self.ready(),
            "draining": self._draining,
            "models": models,
        }

    def stats(self) -> Dict:
        """Aggregate /v2/stats payload: batcher counters + generation
        engine throughput/occupancy, one entry per model."""
        return {
            "models": {n: b.stats.snapshot() for n, b in list(self.batchers.items())},
            "generation": {
                n: g.stats.snapshot() for n, g in list(self.generators.items())
            },
        }

    # ------------------------------------------------------ observability
    def _all_stats(self) -> Dict:
        """model name -> ServingStats across both serving paths (the
        /metrics scrape set). Snapshots the dicts: repository load/
        unload mutates them concurrently. Fleet generators contribute
        one entry PER REPLICA under a ``(model, replica)`` key, so every
        serving family renders with a ``replica`` label and Prometheus
        aggregates across it."""
        out = {n: b.stats for n, b in list(self.batchers.items())}
        for n, g in list(self.generators.items()):
            reps = getattr(g, "replicas", None)
            if reps is None:
                out[n] = g.stats
            else:
                for r in list(reps):
                    out[(n, r.id)] = r.model.stats
        return out

    def _fleets(self) -> Dict:
        """model name -> fleet lifecycle metrics (Fleet generators
        only): replica states, failover/migration counters, router
        decisions — the ``fleets=`` input to render_prometheus."""
        return {
            n: g.prom_fleet()
            for n, g in list(self.generators.items())
            if hasattr(g, "prom_fleet")
        }

    def _generation_units(self):
        """(label, GenerationModel) pairs across all generators; a
        fleet contributes one unit per replica, labeled
        ``name/replica`` — the shared iteration for the per-engine
        debug endpoints (traces, timeline, cache, programs,
        predictions, slo)."""
        for name, g in sorted(self.generators.items()):
            reps = getattr(g, "replicas", None)
            if reps is None:
                yield name, g
            else:
                for r in list(reps):
                    yield f"{name}/{r.id}", r.model

    @staticmethod
    def _unit_matches(label: str, model: Optional[str]) -> bool:
        """``?model=`` filter: the plain name matches itself, a fleet
        name matches all its replicas, and ``name/rN`` matches one."""
        return (
            model is None
            or label == model
            or label.split("/", 1)[0] == model
        )

    def _all_anatomy(self) -> Dict:
        """model/(model, replica) -> StepAnatomy.prom_snapshot() across
        the generation path — the ``anatomy=`` input to
        render_prometheus, keyed like _all_stats so the
        ``step_phase_seconds`` family carries the same model/replica
        labels as every other serving family."""
        out: Dict = {}
        for n, g in list(self.generators.items()):
            reps = getattr(g, "replicas", None)
            if reps is None:
                an = getattr(g, "anatomy", None)
                if an is not None and an.enabled:
                    out[n] = an.prom_snapshot()
            else:
                for r in list(reps):
                    an = getattr(r.model, "anatomy", None)
                    if an is not None and an.enabled:
                        out[(n, r.id)] = an.prom_snapshot()
        return out

    def metrics_text(self) -> str:
        return render_prometheus(
            self._all_stats(),
            fault_sites=faults.site_counters(),
            ledger=GLOBAL_LEDGER,
            fleets=self._fleets(),
            anatomy=self._all_anatomy(),
        )

    def debug_traces(
        self,
        request_id: Optional[int] = None,
        model: Optional[str] = None,
        n: int = 32,
    ) -> Dict:
        """Recent finished request traces, most recent first, across the
        generation schedulers and the dynamic batchers."""
        rings = []
        for label, unit in self._generation_units():
            if self._unit_matches(label, model):
                rings.append((label, unit.trace_ring))
        for name, b in list(self.batchers.items()):
            if model is None or name == model:
                rings.append((name, b.trace_ring))
        traces = []
        for name, ring in rings:
            if request_id is not None:
                tr = ring.get(request_id)
                if tr is not None:
                    d = tr.to_dict()
                    d["model"] = d["model"] or name
                    traces.append(d)
                continue
            for tr in ring.recent(n):
                d = tr.to_dict()
                d["model"] = d["model"] or name
                traces.append(d)
        traces.sort(key=lambda d: d.get("t_finish") or 0, reverse=True)
        return {"traces": traces[:n]}

    def debug_timeline(self, model: Optional[str] = None) -> Dict:
        """Flight-recorder dump as chrome://tracing JSON (one pid per
        generation model), plus the recent incident snapshots under a
        non-standard ``incidents`` key chrome ignores."""
        events, incidents = [], []
        for pid, (label, unit) in enumerate(self._generation_units(), start=1):
            if not self._unit_matches(label, model):
                continue
            trace = unit.flight.to_chrome_trace(pid=pid, name=label)
            events.extend(trace["traceEvents"])
            incidents.extend(
                {**inc, "model": label}
                for inc in unit.flight.incident_snapshots()
            )
        return {
            "traceEvents": events,
            "displayTimeUnit": "ms",
            "incidents": incidents,
        }

    def debug_cache(self, model: Optional[str] = None) -> Dict:
        """KV-cache block telemetry per generation model: residency
        table, fragmentation, watermarks, pressure, admission waits."""
        return {
            "models": {
                label: unit.cache_report()
                for label, unit in self._generation_units()
                if self._unit_matches(label, model)
            }
        }

    def debug_programs(self, model: Optional[str] = None) -> Dict:
        """Jit program registries: per generation model (prefill
        buckets / decode / verify) plus the process-wide executor
        registry, each with signatures, compile times, and any retrace
        blame."""
        out: Dict = {
            "models": {
                label: {
                    "programs": unit.programs.snapshot(),
                    "retraces": unit.programs.recent_retraces(),
                }
                for label, unit in self._generation_units()
                if self._unit_matches(label, model)
            }
        }
        if model is None:
            out["executor"] = {
                "programs": GLOBAL_PROGRAMS.snapshot(),
                "retraces": GLOBAL_PROGRAMS.recent_retraces(),
            }
        return out

    def debug_predictions(self, model: Optional[str] = None) -> Dict:
        """Cost-model truth: per generation model, the engine ledger's
        (predicted, measured) pairs, relative-error distributions, and
        drift alarms; plus the process-wide ledger (search cost model,
        calibration measurements, executor train programs)."""
        out: Dict = {
            "models": {
                label: unit.ledger.report()
                for label, unit in self._generation_units()
                if self._unit_matches(label, model)
            }
        }
        if model is None:
            out["global"] = GLOBAL_LEDGER.report()
        return out

    def debug_anatomy(
        self, model: Optional[str] = None, capture: Optional[int] = None
    ) -> Dict:
        """Step-anatomy report per generation unit (one entry per fleet
        replica): phase breakdown, device-bubble ratio, classification,
        overlap-headroom projection, capture state, and the two-lane
        chrome://tracing timeline of any captured steps. ``capture=K``
        arms a K-step capture on every matching unit first (the
        timeline fills as the engines step; scrape again to read it)."""
        out: Dict = {"models": {}}
        for label, unit in self._generation_units():
            if not self._unit_matches(label, model):
                continue
            an = unit.anatomy
            armed = an.arm_capture(capture) if capture else None
            payload = {"report": an.report(), "trace": an.to_chrome_trace(name=label)}
            if armed is not None:
                payload["armed"] = armed
            out["models"][label] = payload
        return out

    def slo_report(self) -> Dict:
        """Per-model SLO objectives with multi-window burn rates (one
        entry per fleet replica)."""
        return {
            "models": {
                label: unit.slo.snapshot()
                for label, unit in self._generation_units()
            }
        }

    def overload_report(self, model: Optional[str] = None) -> Dict:
        """GET /v2/overload: per generation unit (one entry per fleet
        replica), the overload controller's state — limiter, ladder
        level + history, pressure, and the per-reason / per-priority
        rejection split."""
        out: Dict = {"models": {}}
        for label, unit in self._generation_units():
            if not self._unit_matches(label, model):
                continue
            try:
                out["models"][label] = unit.overload.report()
            except AttributeError:
                continue  # non-generation unit
        return out

    def fleet_report(self) -> Dict:
        """GET /v2/fleet: per-fleet replica states, residency, router
        score inputs + decisions, and recent lifecycle events."""
        return {
            "models": {
                name: g.report()
                for name, g in sorted(self.generators.items())
                if hasattr(g, "replicas")
            }
        }

    def autoscale_report(self) -> Dict:
        """GET /v2/fleet/autoscale: per-fleet want-more/want-fewer
        replica signal derived from sustained limiter state (the
        ROADMAP item 3 autoscaling remainder)."""
        return {
            "models": {
                name: g.autoscale_report()
                for name, g in sorted(self.generators.items())
                if hasattr(g, "autoscale_report")
            }
        }

    def durable_report(self) -> Dict:
        """GET /v2/durable: per-model WAL/journal/warm-restart state —
        commit watermark, counters, degraded streams, resume-index
        sizes (durable serving, ISSUE 19)."""
        out: Dict = {"models": {}}
        for name, g in sorted(self.generators.items()):
            dur = getattr(g, "durable", None)
            if dur is not None:
                out["models"][name] = dur.report()
            elif hasattr(g, "durable_report"):  # fleet: per-replica view
                rep = g.durable_report()
                if rep is not None:
                    out["models"][name] = rep
        return out

    def durable_lookup(self, durable_id: str):
        """Find the generator + resume state owning a durable stream
        id, across plain models and fleets. Returns ``(model_name,
        ("live", Request) | ("done", dict))`` or None."""
        for name, g in sorted(self.generators.items()):
            dur = getattr(g, "durable", None)
            if dur is not None:
                hit = dur.lookup(durable_id)
                if hit is not None:
                    return name, hit
            elif hasattr(g, "durable_lookup"):
                hit = g.durable_lookup(durable_id)
                if hit is not None:
                    return name, hit
        return None

    # ------------------------------------------------------------ journeys
    def journey_index(self) -> JourneyIndex:
        """A fresh fleet-wide stitcher over the CURRENT topology: the
        HTTP ingress lane, every generator's router + replica lanes
        (retiring replicas included), and every on-disk spool — built
        per query so replica churn can never leave the index stale."""
        idx = JourneyIndex().add(self.journeys)
        for g in list(self.generators.values()):
            recs = getattr(g, "journey_recorders", None)
            if recs is not None:
                for rec in recs():
                    idx.add(rec)
            spools = getattr(g, "journey_spools", None)
            if spools is not None:
                for spool in spools():
                    idx.add_spool(spool)
        return idx

    def debug_journey(self, journey_id: str) -> Optional[Dict]:
        """GET /v2/debug/journey/{id}: the stitched causal timeline —
        spans in parent-chain order with the connectivity verdict, plus
        chrome://tracing (one lane per replica/pool) and OTLP-shaped
        renderings of the same journey."""
        journey = self.journey_index().get(journey_id)
        if journey is None:
            return None
        return {
            "journey": journey,
            "chrome_trace": journey_to_chrome_trace(journey),
            "otlp": journey_to_otlp(journey),
        }

    def debug_journeys(self, slow: Optional[str] = None, n: int = 32) -> Dict:
        """GET /v2/debug/journey[?slow=p99]: known journey ids (newest
        first); with ``slow=``, only the ids the latency windows
        retained as worst-decile exemplars — a bad percentile links
        straight to a stitchable journey."""
        if slow:
            rows = self.debug_slow()
            ids: list = []
            for windows in rows["models"].values():
                for entries in windows.values():
                    for e in entries:
                        if e["journey_id"] not in ids:
                            ids.append(e["journey_id"])
            return {"journeys": ids[:n], "slow": rows["models"]}
        return {"journeys": self.journey_index().journey_ids()[:n]}

    def debug_slow(self, model: Optional[str] = None) -> Dict:
        """GET /v2/debug/slow: per generation unit, each latency
        window's worst-decile samples with their journey ids — the
        tail-latency exemplar table."""
        out: Dict = {"models": {}}
        for label, unit in self._generation_units():
            if not self._unit_matches(label, model):
                continue
            try:
                rows = unit.stats.slow_exemplars()
            except AttributeError:
                continue
            if rows:
                out["models"][label] = rows
        return out

    # ------------------------------------------------------------ control
    def start(self):
        server = self

        class Handler(BaseHTTPRequestHandler):
            def log_message(self, *a):  # quiet
                pass

            def _json(self, code: int, payload: dict, headers=None):
                body = json.dumps(payload).encode()
                self.send_response(code)
                self.send_header("Content-Type", "application/json")
                self.send_header("Content-Length", str(len(body)))
                for k, v in (headers or {}).items():
                    self.send_header(k, v)
                self.end_headers()
                self.wfile.write(body)

            def _text(self, code: int, text: str, content_type: str):
                body = text.encode()
                self.send_response(code)
                self.send_header("Content-Type", content_type)
                self.send_header("Content-Length", str(len(body)))
                self.end_headers()
                self.wfile.write(body)

            def _repository(self, parts):
                repo = server.repository
                if repo is None:
                    return self._json(400, {"error": "no model repository configured"})
                if len(parts) == 4 and parts[3] == "index":
                    return self._json(200, [
                        {
                            "name": n,
                            "state": "READY" if n in server.models else "UNAVAILABLE",
                        }
                        for n in sorted(set(repo.available()) | set(server.models))
                    ])
                if len(parts) == 6 and parts[3] == "models" and parts[5] in ("load", "unload"):
                    name = parts[4]
                    if parts[5] == "load":
                        try:
                            server.register(repo.load(name))
                        except KeyError as e:
                            return self._json(404, {"error": str(e)})
                        except Exception as e:
                            return self._json(500, {"error": str(e)})
                        return self._json(200, {"name": name, "state": "READY"})
                    if not server.unregister(name):
                        return self._json(404, {"error": f"model {name} not loaded"})
                    return self._json(200, {"name": name, "state": "UNAVAILABLE"})
                return self._json(404, {"error": "not found"})

            def do_GET(self):
                url = urlparse(self.path)
                path, query = url.path, parse_qs(url.query)

                def qint(key):
                    try:
                        return int(query[key][0])
                    except (KeyError, IndexError, ValueError):
                        return None

                if path == "/v2/health/live":
                    return self._json(200, {"live": server.live()})
                if path == "/v2/health/ready":
                    payload = server.readiness()
                    return self._json(200 if payload["ready"] else 503, payload)
                if path == "/v2/stats":
                    return self._json(200, server.stats())
                if path == "/metrics":
                    try:
                        text = server.metrics_text()
                    except Exception as e:  # a scrape must fail loudly, not 200-empty
                        return self._json(500, {"error": str(e)})
                    return self._text(200, text, "text/plain; version=0.0.4; charset=utf-8")
                if path == "/v2/debug/traces":
                    return self._json(200, server.debug_traces(
                        request_id=qint("id"),
                        model=(query.get("model") or [None])[0],
                        n=qint("n") or 32,
                    ))
                if path == "/v2/debug/timeline":
                    return self._json(200, server.debug_timeline(
                        model=(query.get("model") or [None])[0]
                    ))
                if path == "/v2/debug/cache":
                    return self._json(200, server.debug_cache(
                        model=(query.get("model") or [None])[0]
                    ))
                if path == "/v2/debug/programs":
                    return self._json(200, server.debug_programs(
                        model=(query.get("model") or [None])[0]
                    ))
                if path == "/v2/debug/predictions":
                    return self._json(200, server.debug_predictions(
                        model=(query.get("model") or [None])[0]
                    ))
                if path == "/v2/debug/anatomy":
                    return self._json(200, server.debug_anatomy(
                        model=(query.get("model") or [None])[0],
                        capture=qint("capture"),
                    ))
                if path.startswith("/v2/debug/journey/"):
                    jid = path[len("/v2/debug/journey/"):]
                    payload = server.debug_journey(jid)
                    if payload is None:
                        return self._json(
                            404, {"error": f"unknown journey {jid}"}
                        )
                    return self._json(200, payload)
                if path == "/v2/debug/journey":
                    return self._json(200, server.debug_journeys(
                        slow=(query.get("slow") or [None])[0],
                        n=qint("n") or 32,
                    ))
                if path == "/v2/debug/slow":
                    return self._json(200, server.debug_slow(
                        model=(query.get("model") or [None])[0]
                    ))
                if path == "/v2/slo":
                    return self._json(200, server.slo_report())
                if path == "/v2/overload":
                    return self._json(200, server.overload_report(
                        model=(query.get("model") or [None])[0]
                    ))
                if path == "/v2/durable":
                    return self._json(200, server.durable_report())
                if path.startswith("/v2/generate/resume/"):
                    return self._resume(
                        path[len("/v2/generate/resume/"):], query
                    )
                if path == "/v2/fleet":
                    return self._json(200, server.fleet_report())
                if path == "/v2/fleet/autoscale":
                    return self._json(200, server.autoscale_report())
                if path == "/v2/models":
                    return self._json(
                        200,
                        {"models": sorted(set(server.models) | set(server.generators))},
                    )
                if path.startswith("/v2/models/"):
                    parts = path.split("/")
                    name = parts[3]
                    m = server.models.get(name) or server.generators.get(name)
                    if m is None:
                        return self._json(404, {"error": f"unknown model {name}"})
                    if len(parts) == 5 and parts[4] == "ready":
                        ok = server.model_ready(name)
                        payload = {"name": name, "ready": ok}
                        g = server.generators.get(name)
                        if g is not None:
                            payload["rationale"] = g.readiness_rationale()
                        return self._json(200 if ok else 503, payload)
                    return self._json(200, m.metadata())
                return self._json(404, {"error": "not found"})

            def _generate(self, name: str):
                """POST /v2/models/{name}/generate — body: {"prompt":
                [ids], "max_new_tokens", "temperature", "top_k",
                "eos_id", "seed", "stream", "parameters": {"timeout_ms",
                "denoising_steps", "remasking", "threshold" (a
                block-diffusion model's rule, this request's)},
                "speculation": {"enabled", "k", "method", "max_ngram",
                "min_ngram", "adaptive"}, "response_format": {"type":
                "json_schema"|"regex", ...}}. The speculation block
                turns on (exact) speculative decoding for this request;
                response_format constrains the stream to a grammar (a
                malformed grammar is THIS request's 400, never the
                batch's).
                Non-streaming: one JSON object. "stream": true: SSE — one
                ``data:`` event per token, then a final done event."""
                gen = server.generators.get(name)
                if gen is None:
                    return self._json(404, {"error": f"unknown generation model {name}"})
                # the front end's two spans, timed from inside: body read
                # -> submit returned, and (streaming) first token taken
                # off the handle -> its SSE event flushed. A fleet's
                # aggregate stats view takes no observations.
                observe = getattr(gen.stats, "observe", None) or (lambda n, s: None)
                try:
                    with phase("http.ingress") as ingress:
                        length = int(self.headers.get("Content-Length", 0))
                        req = json.loads(self.rfile.read(length))
                        prompt = [int(t) for t in req["prompt"]]
                        sampling = gen.sampling_from(req)
                        stream = bool(req.get("stream", False))
                        timeout_ms = (req.get("parameters") or {}).get(
                            "timeout_ms", self.headers.get("X-Request-Timeout-Ms")
                        )
                        deadline_s = None if timeout_ms is None else float(timeout_ms) / 1000.0
                        speculation = gen.speculation_from(req)
                        # priority class: body field first, then the
                        # X-Request-Priority header (absent -> standard)
                        priority = req.get(
                            "priority", self.headers.get("X-Request-Priority")
                        )
                        response_format = gen.response_format_from(req)
                        # journey ingress: mint (or join the client's W3C
                        # traceparent) only when the target unit records
                        # journeys — journeys-off deployments stay inert
                        journey = None
                        if getattr(gen, "journeys", None) is not None:
                            journey = server.journeys.mint(
                                parent=parse_traceparent(
                                    self.headers.get("traceparent")
                                )
                            )
                            journey.hop(
                                "ingress", transport="http", model=name,
                                stream=stream, prompt_len=len(prompt),
                            )
                        handle = gen.submit(
                            prompt, sampling, deadline_s=deadline_s,
                            speculation=speculation, transport="http",
                            priority=priority, response_format=response_format,
                            journey=journey,
                        )
                    observe("http_ingress", ingress.seconds)
                except ResilienceError as e:
                    return self._json(
                        http_status(e), _reject_payload(e),
                        headers=_reject_headers(e),
                    )
                except Exception as e:
                    return self._json(400, {"error": str(e)})

                def error_payload(e):
                    """Failed generations ship their postmortem: the
                    request's trace, and (quarantine/engine-failure) the
                    flight-recorder snapshot riding the exception."""
                    payload = _reject_payload(e)
                    tr = handle.trace_dict()
                    if tr:
                        payload["trace"] = tr
                    flight = getattr(e, "flight_snapshot", None)
                    if flight:
                        payload["flight"] = flight
                    return payload

                wait = deadline_s if deadline_s is not None else 300.0
                if not stream:
                    try:
                        tokens = handle.result(timeout=wait)
                    except ResilienceError as e:
                        return self._json(
                            http_status(e), error_payload(e),
                            headers=_reject_headers(e),
                        )
                    except (TimeoutError, _FuturesTimeout):
                        handle.cancel()
                        return self._json(504, {"error": "generation timed out"})
                    except Exception as e:
                        return self._json(500, error_payload(e))
                    body = {"model_name": name, "tokens": tokens,
                            "num_generated": len(tokens)}
                    if handle._request.fixed_at:  # a block-diffusion model's
                        body["fixed_at"] = list(handle._request.fixed_at[: len(tokens)])
                    if journey is not None:
                        body["journey_id"] = journey.journey_id
                        return self._json(
                            200, body,
                            headers={"traceparent": journey.traceparent()},
                        )
                    return self._json(200, body)
                # SSE stream: status/headers are already committed once the
                # first token flushes, so mid-stream failures surface as an
                # error event, not a status code. With durability
                # attached, X-Durable-Id names the stream for
                # GET /v2/generate/resume/{id}, and each token event
                # carries a monotonic SSE id (= token index) so a
                # reconnecting client's Last-Event-ID pins exactly
                # where replay resumes.
                durable_id = handle._request.durable_id
                self.send_response(200)
                self.send_header("Content-Type", "text/event-stream")
                self.send_header("Cache-Control", "no-cache")
                if durable_id is not None:
                    self.send_header("X-Durable-Id", durable_id)
                if journey is not None:
                    self.send_header("traceparent", journey.traceparent())
                self.end_headers()

                def event(payload: dict, eid=None):
                    if eid is not None:
                        self.wfile.write(f"id: {eid}\n".encode())
                    self.wfile.write(f"data: {json.dumps(payload)}\n\n".encode())
                    self.wfile.flush()

                count = 0
                try:
                    for tok in handle.tokens(timeout=wait):
                        if count == 0:
                            with phase("http.first_write", request=handle._request.id) as first:
                                event({"token": int(tok), "index": 0}, eid=0)
                            observe("http_first_write", first.seconds)
                        else:
                            event({"token": int(tok), "index": count}, eid=count)
                        count += 1
                    done = {"done": True, "tokens": handle.result(timeout=wait)}
                    if durable_id is not None:
                        done["durable_id"] = durable_id
                    if journey is not None:
                        done["journey_id"] = journey.journey_id
                    event(done)
                except Exception as e:
                    handle.cancel()
                    try:
                        event({**error_payload(e), "done": True})
                    except OSError:
                        pass  # client went away mid-stream

            def _resume(self, durable_id: str, query):
                """GET /v2/generate/resume/{durable_id} — SSE replay +
                re-attach (durable serving, ISSUE 19). Journaled tokens
                replay from the resume index (event ids pick up the
                original stream's numbering); if the stream is still
                live the response then follows it to completion
                byte-identically. ``Last-Event-ID`` (header, SSE
                reconnect convention) or ``?last_event_id=`` skips
                events the client already holds."""
                last = self.headers.get("Last-Event-ID")
                if last is None:
                    last = (query.get("last_event_id") or [None])[0]
                try:
                    sent = int(last) + 1 if last is not None else 0
                except ValueError:
                    return self._json(400, {"error": f"bad Last-Event-ID {last!r}"})
                found = server.durable_lookup(durable_id)
                if found is None:
                    return self._json(
                        404, {"error": f"unknown durable stream {durable_id}"}
                    )
                name, (state, obj) = found
                # journey: a resumed live stream keeps its identity — the
                # WAL admission snapshot restored the pre-crash journey id,
                # so the sse_resume hop parent-links into the same trace.
                journey_id = None
                if state == "live" and obj.journey.journey_id is not None:
                    journey_id = obj.journey.journey_id
                    obj.journey.hop(
                        "sse_resume", durable_id=durable_id,
                        last_event_id=last, from_index=sent,
                    )
                self.send_response(200)
                self.send_header("Content-Type", "text/event-stream")
                self.send_header("Cache-Control", "no-cache")
                self.send_header("X-Durable-Id", durable_id)
                if journey_id is not None:
                    self.send_header(
                        "traceparent", obj.journey.traceparent()
                    )
                self.end_headers()

                def event(payload: dict, eid=None):
                    if eid is not None:
                        self.wfile.write(f"id: {eid}\n".encode())
                    self.wfile.write(f"data: {json.dumps(payload)}\n\n".encode())
                    self.wfile.flush()

                def drain(tokens):
                    nonlocal sent
                    while sent < len(tokens):
                        event(
                            {"token": int(tokens[sent]), "index": sent,
                             "model_name": name},
                            eid=sent,
                        )
                        sent += 1

                try:
                    if state == "done":
                        tokens = obj["tokens"]
                        drain(tokens)
                        event({"done": True, "tokens": list(tokens),
                               "outcome": obj["outcome"],
                               "durable_id": durable_id})
                        return
                    # live stream: poll the request's generated list —
                    # the handle's token queue belongs to (and was
                    # consumed by) the original connection. List
                    # appends are atomic under the GIL; we only ever
                    # read a prefix the scheduler already extended.
                    req = obj
                    handle = req.handle
                    # ~300 s ceiling without a wall-clock read: each
                    # poll blocks up to 50 ms on the settle future
                    for _ in range(6000):
                        drain(req.generated)
                        if handle.done():
                            break
                        try:
                            handle.future.exception(timeout=0.05)
                        except _FuturesTimeout:
                            pass
                        except Exception:
                            break  # settled (cancelled counts); drain below
                    drain(req.generated)
                    if not handle.done():
                        event({"done": True, "error": "resume timed out",
                               "durable_id": durable_id})
                        return
                    try:
                        tokens = handle.result(timeout=0)
                        event({"done": True, "tokens": tokens,
                               "outcome": "completed",
                               "durable_id": durable_id})
                    except Exception as e:
                        event({**_reject_payload(e), "done": True,
                               "outcome": type(e).__name__,
                               "durable_id": durable_id})
                except OSError:
                    pass  # client went away mid-replay

            def do_POST(self):
                parts = self.path.split("/")
                if len(parts) >= 3 and parts[1] == "v2" and parts[2] == "repository":
                    return self._repository(parts)
                if len(parts) == 5 and parts[1] == "v2" and parts[2] == "models" and parts[4] == "generate":
                    return self._generate(parts[3])
                if len(parts) < 5 or parts[1] != "v2" or parts[2] != "models" or parts[4] != "infer":
                    return self._json(404, {"error": "not found"})
                name = parts[3]
                batcher = server.batchers.get(name)
                model = server.models.get(name)
                if batcher is None or model is None:
                    return self._json(404, {"error": f"unknown model {name}"})
                # request parsing/validation errors -> 400; backpressure /
                # breaker / drain -> 503; expired deadline -> 504;
                # server-side inference failures -> 500 (round-1 conflated
                # them all into 400)
                try:
                    length = int(self.headers.get("Content-Length", 0))
                    req = json.loads(self.rfile.read(length))
                    timeout_ms = (req.get("parameters") or {}).get(
                        "timeout_ms", self.headers.get("X-Request-Timeout-Ms")
                    )
                    deadline_s = None if timeout_ms is None else float(timeout_ms) / 1000.0
                    by_name = {t["name"]: t for t in req["inputs"]}
                    arrays = []
                    for meta in model.inputs:
                        t = by_name.get(meta.name)
                        if t is None:
                            raise ValueError(f"missing input {meta.name}")
                        dt = _V2_DTYPES.get(t.get("datatype", "FP32"), np.float32)
                        arrays.append(np.asarray(t["data"], dtype=dt).reshape(t["shape"]))
                    priority = (req.get("parameters") or {}).get(
                        "priority", self.headers.get("X-Request-Priority")
                    )
                    fut = batcher.submit(
                        arrays, deadline_s=deadline_s, transport="http",
                        priority=priority,
                    )
                except ResilienceError as e:  # backpressure/deadline/breaker/drain
                    return self._json(
                        http_status(e), _reject_payload(e),
                        headers=_reject_headers(e),
                    )
                except RuntimeError as e:  # batcher stopped: server-side
                    return self._json(500, {"error": str(e)})
                except Exception as e:
                    return self._json(400, {"error": str(e)})
                try:
                    # a request-supplied deadline owns the wait; 60s is
                    # only the default for budget-less requests
                    outs = fut.result(timeout=deadline_s if deadline_s is not None else 60.0)
                except ResilienceError as e:
                    return self._json(
                        http_status(e), _reject_payload(e),
                        headers=_reject_headers(e),
                    )
                except (TimeoutError, _FuturesTimeout):
                    # futures.TimeoutError only aliases the builtin from
                    # 3.11 on; cancel so the abandoned request never
                    # occupies space in a later device batch
                    fut.cancel()
                    return self._json(504, {"error": "inference timed out"})
                except Exception as e:
                    return self._json(500, {"error": str(e)})
                resp = {
                    "model_name": name,
                    "outputs": [
                        {
                            "name": meta.name,
                            "shape": list(o.shape),
                            "datatype": _NP_TO_V2.get(str(o.dtype), "FP32"),
                            "data": np.asarray(o, dtype=np.float64 if o.dtype.kind == "f" else o.dtype).reshape(-1).tolist(),
                        }
                        for meta, o in zip(model.outputs, outs)
                    ],
                }
                return self._json(200, resp)

        self._httpd = _Listener((self.host, self.port), Handler)
        self.port = self._httpd.server_address[1]  # resolve port 0
        for b in self.batchers.values():
            b.start()
        for g in self.generators.values():
            g.start()
        self._thread = threading.Thread(target=self._httpd.serve_forever, daemon=True)
        self._thread.start()

    def stop(self, drain: bool = True):
        """Graceful by default: readiness flips to 503 first (so load
        balancers stop routing here), queued + in-flight requests finish,
        then the listener closes. ``drain=False`` errors queued work."""
        self._draining = True
        try:
            for b in self.batchers.values():
                b.stop(drain=drain)
            for g in self.generators.values():
                g.stop(drain=drain)
            if self._httpd:
                self._httpd.shutdown()
                self._httpd.server_close()
                self._httpd = None
            if self._thread:
                self._thread.join(timeout=5)
                self._thread = None
        finally:
            self._draining = False

    def __enter__(self):
        self.start()
        return self

    def __exit__(self, *exc):
        self.stop()
