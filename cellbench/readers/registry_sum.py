"""A gauge family's children summed at the window's close."""

from .registry_share import _values


def read(facts, metric: str, labels=None, scale: float = 1.0, **_):
    """The sum of the values that the children of ``metric`` carrying
    ``labels`` (all of them where none are given) hold in the second
    export of ``facts['registry']``, times ``scale``. ``None`` where
    the family is absent (a program from before it) or has no child."""
    _, after = facts.get("registry", (None, None))
    mine = [v for k, v in _values(after, metric).items()
            if all(dict(k).get(lk) == lv
                   for lk, lv in (labels or {}).items())]
    return sum(mine) * scale if mine else None
