"""Reading the program's step profiler samples (one per dispatch) and its
compile ledger. A dispatch of the fused decode scan holds k_cap forward
passes; every per-step figure here is per forward pass."""

from __future__ import annotations

HOST_PHASES = ("host_prep_ms", "dispatch_ms", "detok_ms")


def passes(sample: dict) -> int:
    return max(1, int(sample.get("k_cap") or 0)) \
        if sample.get("mode") == "decode" else 1


def total_passes(samples: list) -> int:
    return sum(passes(s) for s in samples)


def host_ms(sample: dict) -> float:
    return sum(float(sample.get(p, 0.0)) for p in HOST_PHASES)


def in_window(samples: list, e0: float, e1: float) -> list:
    """Samples that ended inside [e0, e1] (epoch seconds), no compile."""
    return [s for s in samples if e0 <= s["ts"] <= e1]
