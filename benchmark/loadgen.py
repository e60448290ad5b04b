#!/usr/bin/env python3
"""The load generator: a CHILD process that never imports JAX.

Standard library only (HTTP and SSE over loopback), so it shares no
interpreter lock with the server's scheduler thread and never asks for
the chip. It reads one JSON job from stdin, sends every request of the
schedule, stamps every SSE token event on ``time.monotonic()`` (one
clock for every process of a Linux machine, so the parent's window
boundaries mean the same here), and writes one JSON object to stdout.

Job: ``{"url", "model", "mode": "open"|"closed", "t0", "requests":
[{"id", "prompt", "max_new_tokens", "due_s"?}], "clients", "workers",
"stop_s", "drain_s", "timeout_s"}``. Times in the job are seconds after
``t0`` (an absolute monotonic time); times in the records are absolute.

* open: request *i* is due at ``t0 + due_s``; a dispatcher sleeps until
  then and hands it to one of ``workers`` threads. ``sent - due`` is the
  generator's own lateness.
* closed: ``clients`` threads each take the next unsent request when
  their last completes (due = the moment they take it), and take none
  after ``t0 + stop_s``. A client that finds the list empty before then
  ends the run's claim to a closed loop: ``exhausted_after_s`` says how
  many seconds after ``t0`` that was (``None`` if the list lasted).

After the last request is handed out, in-flight requests get
``drain_s`` seconds; one still unfinished then is recorded as failed.
"""
from __future__ import annotations

import http.client
import json
import queue
import sys
import threading
import time
import urllib.parse
from typing import Dict, List, Optional


def send(url: str, model: str, req: Dict, due: float, timeout_s: float) -> Dict:
    """One streamed generation; returns the request's record."""
    rec = {
        "id": req["id"], "due": due, "prompt_len": len(req["prompt"]),
        "max_new_tokens": req["max_new_tokens"], "status": None, "error": None,
        "token_times": [], "tokens": [], "done_time": None,
    }
    body = json.dumps({
        "prompt": req["prompt"], "max_new_tokens": req["max_new_tokens"],
        "stream": True, "temperature": 0.0,
    }).encode()
    u = urllib.parse.urlparse(url)
    conn = http.client.HTTPConnection(u.hostname, u.port, timeout=timeout_s)
    try:
        rec["sent"] = time.monotonic()
        conn.request(
            "POST", f"/v2/models/{model}/generate", body=body,
            headers={"Content-Type": "application/json"},
        )
        resp = conn.getresponse()
        rec["status"] = resp.status
        if resp.status != 200:
            rec["error"] = resp.read(2000).decode(errors="replace")
            return rec
        streamed: List[int] = []
        while True:
            line = resp.readline()
            if not line:
                break
            now = time.monotonic()
            if not line.startswith(b"data: "):
                continue
            event = json.loads(line[len(b"data: "):])
            if event.get("done"):
                rec["done_time"] = now
                if "error" in event:
                    rec["error"] = str(event["error"])[:2000]
                elif event.get("tokens") != streamed:
                    rec["error"] = "token events disagree with the done event"
                break
            streamed.append(int(event["token"]))
            rec["token_times"].append(now)
        rec["tokens"] = streamed
        if rec["done_time"] is None and rec["error"] is None:
            rec["error"] = "stream ended without a done event"
    except (OSError, http.client.HTTPException, ValueError) as e:
        rec["error"] = f"{type(e).__name__}: {e}"
    finally:
        conn.close()
    return rec


def run_open(job: Dict, records: List[Dict], lock: threading.Lock, state: Dict) -> List[threading.Thread]:
    work: "queue.Queue[Optional[Dict]]" = queue.Queue()

    def worker():
        while True:
            req = work.get()
            if req is None:
                return
            rec = send(job["url"], job["model"], req, job["t0"] + req["due_s"], job["timeout_s"])
            with lock:
                records.append(rec)

    threads = [threading.Thread(target=worker, daemon=True) for _ in range(int(job["workers"]))]
    for t in threads:
        t.start()
    for req in sorted(job["requests"], key=lambda r: r["due_s"]):
        wait = job["t0"] + req["due_s"] - time.monotonic()
        if wait > 0:
            time.sleep(wait)
        work.put(req)
    for _ in threads:
        work.put(None)
    return threads


def run_closed(job: Dict, records: List[Dict], lock: threading.Lock, state: Dict) -> List[threading.Thread]:
    pending = iter(job["requests"])
    stop_at = job["t0"] + job["stop_s"]

    def client():
        while True:
            with lock:
                if time.monotonic() >= stop_at:
                    return
                req = next(pending, None)
                if req is None:
                    state.setdefault("exhausted_after_s", time.monotonic() - job["t0"])
                    return
            rec = send(job["url"], job["model"], req, time.monotonic(), job["timeout_s"])
            with lock:
                records.append(rec)

    wait = job["t0"] - time.monotonic()
    if wait > 0:
        time.sleep(wait)
    threads = [threading.Thread(target=client, daemon=True) for _ in range(int(job["clients"]))]
    for t in threads:
        t.start()
    return threads


def main() -> int:
    job = json.load(sys.stdin)
    records: List[Dict] = []
    lock = threading.Lock()
    state: Dict = {}  # "exhausted_after_s": a closed loop that ran out of requests, and when
    threads = (run_open if job["mode"] == "open" else run_closed)(job, records, lock, state)
    # closed clients stop taking requests at stop_s; open workers end
    # when the queue is empty. Either way, in-flight requests now drain.
    t_handed_out = max(time.monotonic(), job["t0"] + job.get("stop_s", 0.0))
    deadline = t_handed_out + job["drain_s"]
    for t in threads:
        t.join(max(0.0, deadline - time.monotonic()))
    undrained = sum(t.is_alive() for t in threads)
    with lock:
        out = list(records)
    json.dump({
        "records": out,
        "undrained": undrained,
        "exhausted": "exhausted_after_s" in state,
        "exhausted_after_s": state.get("exhausted_after_s"),
        "sent": len(out) + undrained,
        "listed": len(job["requests"]),
    }, sys.stdout)
    sys.stdout.flush()
    return 0


if __name__ == "__main__":
    sys.exit(main())
