"""Arithmetic from records to what the metric files report. A failed, shed
or truncated request counts in a latency percentile as the drain limit:
worse than any served request, and finite."""

from __future__ import annotations

import math


def percentile(values: list, q: float) -> float | None:
    """Nearest-rank percentile, q in (0, 100]."""
    if not values:
        return None
    s = sorted(values)
    return s[max(0, min(len(s) - 1, math.ceil(q / 100.0 * len(s)) - 1))]


def ttft_ms(records: list, drain_limit_ms: float) -> list:
    """Per request: due to first token; the drain limit where it failed."""
    return [1e3 * (r.first_s - r.due_s) if r.ok and r.first_s is not None
            else drain_limit_ms for r in records]


def tpot_ms(records: list, drain_limit_ms: float) -> list:
    """Per request of two tokens or more: (last − first token) / (tokens − 1)."""
    out = []
    for r in records:
        if not r.ok:
            out.append(drain_limit_ms)
        elif r.tokens > 1:
            out.append(1e3 * (r.last_s - r.first_s) / (r.tokens - 1))
    return out


def tokens_in_window(records: list, seconds: float) -> int:
    """Output tokens that arrived inside the window, whichever request —
    ramp's included — they belong to."""
    return sum(n for r in records for t, n in r.frames if 0.0 <= t < seconds)


def prom_value(text: str, name: str, **labels) -> float | None:
    """The sample of `name` carrying (at least) `labels` in a Prometheus
    exposition; the sum over all that match (e.g. over models)."""
    total, found = 0.0, False
    for line in text.splitlines():
        if not line.startswith(name) or line[len(name)] not in "{ ":
            continue
        head, _, value = line.rpartition(" ")
        if all(f'{k}="{v}"' in head for k, v in labels.items()):
            total, found = total + float(value), True
    return total if found else None


def delta_mean(prom0: str, prom1: str, name: str, **labels) -> float | None:
    """Δ_sum / Δ_count of a histogram between two expositions."""
    s0 = prom_value(prom0, name + "_sum", **labels) or 0.0
    c0 = prom_value(prom0, name + "_count", **labels) or 0.0
    s1 = prom_value(prom1, name + "_sum", **labels)
    c1 = prom_value(prom1, name + "_count", **labels)
    if s1 is None or c1 is None or c1 - c0 <= 0:
        return None
    return (s1 - s0) / (c1 - c0)
