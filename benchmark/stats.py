"""The benchmark's arithmetic: percentiles, spreads, open-loop latency
"from due", and the trainer's operations per token.

Pure Python over plain lists, so the load generator's records reduce the
same way everywhere and the tests need no device.
"""
from __future__ import annotations

import json
import math
import pathlib
from typing import Dict, Iterable, List, Optional, Sequence

HERE = pathlib.Path(__file__).resolve().parent


def percentile(values: Sequence[float], p: float) -> float:
    """Nearest-rank percentile, ``p`` in (0, 100]: the smallest sample
    with at least ``p`` % of the samples at or below it. No
    interpolation: a tail is a request someone waited for."""
    if not values:
        raise ValueError("percentile of no samples")
    if not 0.0 < p <= 100.0:
        raise ValueError(f"percentile {p} outside (0, 100]")
    ordered = sorted(values)
    rank = math.ceil(p / 100.0 * len(ordered))
    return ordered[max(0, rank - 1)]


def median(values: Sequence[float]) -> float:
    """The plain median (mean of the middle two for an even count)."""
    if not values:
        raise ValueError("median of no samples")
    ordered = sorted(values)
    n = len(ordered)
    mid = n // 2
    return ordered[mid] if n % 2 else 0.5 * (ordered[mid - 1] + ordered[mid])


def samples_beyond(n: int, p: float) -> int:
    """How many of ``n`` samples lie strictly beyond the ``p``-th
    percentile's rank. A percentile is reported only with ten or more."""
    return n - math.ceil(p / 100.0 * n)


def quartile_spread(values: Sequence[float]) -> float:
    """Distance between the quartiles over the median, as the driver
    measures a set of runs (linear interpolation between ranks)."""
    ordered = sorted(values)

    def q(f: float) -> float:
        pos = f * (len(ordered) - 1)
        lo = math.floor(pos)
        hi = min(lo + 1, len(ordered) - 1)
        return ordered[lo] + (ordered[hi] - ordered[lo]) * (pos - lo)

    return (q(0.75) - q(0.25)) / median(ordered)


# ------------------------------------------------------------ serving


def ttft_from_due_ms(record: Dict) -> Optional[float]:
    """Time to first token as the user of an open loop feels it: from
    when the request was DUE, so the wait a stall imposes on the
    requests behind it counts. None for a request with no token."""
    if not record.get("token_times"):
        return None
    return (record["token_times"][0] - record["due"]) * 1e3


def inter_token_gaps_ms(record: Dict) -> List[float]:
    t = record.get("token_times") or []
    return [(b - a) * 1e3 for a, b in zip(t, t[1:])]


def request_ok(record: Dict) -> bool:
    """A request counts only if it returned exactly the tokens asked."""
    return (
        record.get("status") == 200
        and not record.get("error")
        and len(record.get("tokens") or []) == record["max_new_tokens"]
    )


def due_in_window(records: Iterable[Dict], t_open: float, t_close: float) -> List[Dict]:
    return [r for r in records if t_open <= r["due"] < t_close]


def completed_in_window(records: Iterable[Dict], t_open: float, t_close: float) -> List[Dict]:
    return [
        r for r in records
        if request_ok(r) and r.get("done_time") is not None and t_open <= r["done_time"] < t_close
    ]


def window_ok(ctx: Dict) -> List[Dict]:
    """The requests DUE inside a run's window that returned what was
    asked: the ones its latencies are taken over. ``ctx`` holds the
    client's ``records`` and the ``window``; without them, none."""
    if "records" not in ctx:
        return []
    return [r for r in due_in_window(ctx["records"], *ctx["window"]) if request_ok(r)]


def window_ttft_ms(ctx: Dict) -> List[float]:
    return [ttft_from_due_ms(r) for r in window_ok(ctx)]


def window_gaps_ms(ctx: Dict) -> List[float]:
    return [g for r in window_ok(ctx) for g in inter_token_gaps_ms(r)]


def slo_met(record: Dict, ttft_limit_ms: float, gap_limit_ms: float) -> bool:
    """Inside both limits: first token by ``ttft_limit_ms`` from due and
    a mean inter-token gap within ``gap_limit_ms``. A failed or refused
    request has missed."""
    if not request_ok(record):
        return False
    gaps = inter_token_gaps_ms(record)
    mean_gap = sum(gaps) / len(gaps) if gaps else 0.0
    return ttft_from_due_ms(record) <= ttft_limit_ms and mean_gap <= gap_limit_ms


# ----------------------------------------------------------- training


def train_flops_per_token(n_params: int, num_layers: int, seq_length: int, hidden_size: int) -> float:
    """6N (forward + backward matmul FLOPs per token) + the attention
    score/value matmuls 12 * L * S * H: the PaLM-appendix accounting.
    Recomputation is not counted. Copied from ``bench.py`` (PERF.md §7
    lists the original for deletion)."""
    return 6.0 * n_params + 12.0 * num_layers * seq_length * hidden_size


# -------------------------------------------------------------- peaks


def chip_peaks(device_kind: str) -> Dict:
    """This chip's row of ``peaks.json``. An unknown kind is an error,
    never a default."""
    table = json.loads((HERE / "peaks.json").read_text())
    if device_kind not in table:
        raise KeyError(
            f"no peaks for device kind {device_kind!r} in benchmark/peaks.json "
            f"(has {sorted(table)}): add a row with its source"
        )
    return table[device_kind]


# ------------------------------------------------------------- memory


def memory_peak_bytes(devices) -> int:
    """The peak on the fullest chip: what the allocator had handed out
    at its fullest plus what the runtime had reserved for the loaded
    programs' temporaries (``peak_bytes_reserved``: on this runtime a
    program's scratch is carved out of the chip beside the allocator's
    arrays and is not in ``peak_bytes_in_use``)."""
    peak = 0
    for d in devices:
        m = d.memory_stats() or {}
        peak = max(peak, int(m.get("peak_bytes_in_use", 0)) + int(m.get("peak_bytes_reserved", 0)))
    return peak
