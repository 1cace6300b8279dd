"""One child's share of a counter family's increase over the window."""

from __future__ import annotations


def _values(export: dict, metric: str) -> dict:
    fam = (export or {}).get(metric)
    if not fam:
        return {}
    return {tuple(sorted(c["labels"].items())): float(c["value"])
            for c in fam["children"]}


def read(facts, metric: str, labels: dict, complement: bool = False,
         scale: float = 100.0, **_):
    """The increase of the child carrying ``labels`` between the two
    exports of ``facts['registry']`` over the summed increase of all
    the family's children (``complement``: one less that share), times
    ``scale``. A family whose children accrue one exclusive state each
    (``pio_pipeline_state_seconds_total``) reads as the share of the
    window spent in that state. ``None`` where the family is absent
    (a program from before it) or did not move."""
    before, after = facts.get("registry", (None, None))
    b = _values(after, metric)
    if not b:
        return None
    a = _values(before, metric)
    grew = {k: v - a.get(k, 0.0) for k, v in b.items()}
    total = sum(grew.values())
    if total <= 0:
        return None
    mine = sum(v for k, v in grew.items()
               if all(dict(k).get(lk) == lv for lk, lv in labels.items()))
    share = mine / total
    return (1.0 - share if complement else share) * scale
