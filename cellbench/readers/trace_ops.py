"""Device time of operations in the reduced trace."""

from __future__ import annotations

from typing import Optional

from .. import trace


def _unit_count(facts, per: str, program: str) -> Optional[float]:
    tr = facts["trace"]
    if per == "total":
        return 1.0
    if per == "dispatch":
        n, _ = trace.dispatches(tr, program)
        return float(n) or None
    return facts.get("units", {}).get(per)


def read(facts, program: str, op: str = ".", per: str = "total",
         scale: float = 1.0, **_):
    """Device time of the operations matching ``op`` inside the programs
    matching ``program``, per traced ``iteration`` or ``dispatch``."""
    if "trace" not in facts:
        return None
    n = _unit_count(facts, per, program)
    if not n:
        return None
    return trace.op_seconds(facts["trace"], program, op) / n * scale
