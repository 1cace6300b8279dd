"""A series of the server's registry over the window."""

from __future__ import annotations

import math
from typing import Optional


def _family(export: dict, name: str, labels: dict) -> Optional[dict]:
    fam = (export or {}).get(name)
    if not fam:
        return None
    for child in fam["children"]:
        if all(child["labels"].get(k) == v for k, v in labels.items()):
            return child
    return None


def _quantile(buckets_a, buckets_b, q: float) -> Optional[float]:
    """Quantile of what landed between two cumulative bucket lists, by
    linear interpolation inside the bucket (the arithmetic of the
    program's ``obs.histogram.window_quantile``)."""
    deltas, prev = [], 0
    for (le, ca), (_, cb) in zip(buckets_a, buckets_b):
        d = cb - ca
        deltas.append((math.inf if le == "+Inf" else float(le), d - prev))
        prev = d
    n = sum(c for _, c in deltas)
    if n <= 0:
        return None
    target, cum, lo = q * n, 0, 0.0
    for le, c in deltas:
        if c > 0 and cum + c >= target:
            hi = le if math.isfinite(le) else lo
            return lo + (hi - lo) * ((target - cum) / c)
        cum += c
        lo = le if math.isfinite(le) else lo
    return lo


def read(facts, metric: str, stat: str, labels=None,
         scale: float = 1.0, **_):
    """A series of the server's registry over the window: ``mean`` and
    ``p50``/``p95`` of a histogram's observations inside it, or the
    ``value`` of a gauge at its close."""
    before, after = facts.get("registry", (None, None))
    a = _family(before, metric, labels or {})
    b = _family(after, metric, labels or {})
    if b is None:
        return None
    if stat == "value":
        return b["value"] * scale
    zero = {"count": 0, "sum": 0.0,
            "buckets": [[le, 0] for le, _ in b["buckets"]]}
    a = a or zero
    n = b["count"] - a["count"]
    if n <= 0:
        return None
    if stat == "mean":
        return (b["sum"] - a["sum"]) / n * scale
    if stat.startswith("p"):
        v = _quantile(a["buckets"], b["buckets"], float(stat[1:]) / 100.0)
        return None if v is None else v * scale
    raise ValueError(f"unknown registry statistic {stat!r}")
