"""One child of a counter family over the sum of some of its children,
by their increase over the window."""

from .registry_share import _values


def read(facts, metric: str, labels: dict, over: list, scale: float = 100.0,
         **_):
    """The increase of the child carrying ``labels`` over the summed
    increase of the children carrying any of ``over``'s label sets,
    times ``scale``. ``None`` where the family is absent or the
    denominator did not move."""
    before, after = facts.get("registry", (None, None))
    b = _values(after, metric)
    if not b:
        return None
    a = _values(before, metric)

    def grew(want: dict) -> float:
        return sum(v - a.get(k, 0.0) for k, v in b.items()
                   if all(dict(k).get(lk) == lv
                          for lk, lv in want.items()))

    total = sum(grew(w) for w in over)
    return grew(labels) / total * scale if total > 0 else None
