"""The program's start-up account, for the readers that ``move`` ``setup_s``.

Since its PR 50 the program keeps, once a process, where its seconds go
before the first warm step (``flexflow_tpu/obs/steptrace.py``
``GLOBAL_STARTUP``): every ``ff.startup.*`` span as ``phases``
``{name: {count, total_s, self_s}}``, every jit program's first call as
``programs`` ``{name: {trace_s, lower_s, compile_s, cache_load_s,
cache_hit, run_s, at_s}}`` from JAX's own compile events, what the
persistent cache answered as ``cache`` ``{requests, hits, misses}``, and
``spanned_s``, the union of the top-level spans and the programs'
calls. Offsets count from the process's start, the harness's own zero.

A serving driver snapshots ``/v2/stats`` at the window's opening
(``stats_open``), and the account is its ``startup`` section: all of it
is set-up. The trainer's ``ctx`` carries no stats; the readers run in
the program's process, so there the account is read in place and cut at
``ctx["setup_s"]`` (what ended, or a program that began, before the
window opened: the reference's own compiles come after it). A program
without the account (a commit from before it) gives None, and so does
every reader built on this.
"""
from __future__ import annotations

from typing import Dict, Iterable, Optional


def account(ctx: Dict) -> Optional[Dict]:
    if "stats_open" in ctx:
        return (ctx["stats_open"] or {}).get("startup")
    if "setup_s" not in ctx:
        return None
    try:
        from flexflow_tpu.obs.steptrace import GLOBAL_STARTUP
    except ImportError:
        return None
    return GLOBAL_STARTUP.snapshot(until_s=ctx["setup_s"])


def phase_seconds(ctx: Dict, names: Iterable[str]) -> Optional[float]:
    """Summed ``total_s`` of the spans ``names``; None without the
    account or where it has none of them."""
    acct = account(ctx)
    if acct is None:
        return None
    found = [acct["phases"][n]["total_s"] for n in names if n in acct["phases"]]
    return sum(found) if found else None


def program_seconds(ctx: Dict, parts: Iterable[str]) -> Optional[float]:
    """Summed ``parts`` over every program first called before the
    window opened."""
    acct = account(ctx)
    if acct is None:
        return None
    parts = tuple(parts)
    return sum(p[k] for p in acct["programs"].values() for k in parts)
