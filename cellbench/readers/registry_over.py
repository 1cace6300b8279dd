"""Some children's increase over the window, over the increase of
other children: of the same family, of another one, or of a
histogram's ``sum``."""

from __future__ import annotations

from typing import Optional


def _grew(before: dict, after: dict, metric: str, labels
          ) -> Optional[float]:
    """The summed increase, between the two exports, of the children
    of ``metric`` that carry any of ``labels``' label sets (all of
    them where none are given): a counter's ``value``, a histogram's
    ``sum``. ``None`` where the family, or every such child, is absent
    at the close (a kernel that keeps no run-queue clock)."""
    fam = (after or {}).get(metric)
    if not fam:
        return None

    def number(child: dict) -> float:
        return float(child["value"] if "value" in child else child["sum"])

    def mine(child: dict) -> bool:
        return not labels or any(
            all(child["labels"].get(k) == v for k, v in want.items())
            for want in labels)

    start = {tuple(sorted(c["labels"].items())): number(c)
             for c in ((before or {}).get(metric) or {}).get(
                 "children", ())}
    grew = [number(c) - start.get(tuple(sorted(c["labels"].items())), 0.0)
            for c in fam["children"] if mine(c)]
    return sum(grew) if grew else None


def read(facts, metric: str, over: dict, labels=None, scale: float = 1.0,
         **_):
    """The increase of ``metric``'s children carrying any of ``labels``
    over the increase of ``over["metric"]``'s children carrying any of
    ``over["labels"]``, times ``scale``. Over
    ``pio_pipeline_state_seconds_total`` whole (exclusive states: the
    window's seconds) a family of CPU seconds reads in cores. ``None``
    where either family is absent (a program from before it) or the
    denominator did not move."""
    before, after = facts.get("registry", (None, None))
    top = _grew(before, after, metric, labels)
    bottom = _grew(before, after, over["metric"], over.get("labels"))
    if top is None or bottom is None or bottom <= 0:
        return None
    return top / bottom * scale
