"""One reading less another, each a reader's spec."""

from . import read as read_spec


def read(facts, a: dict, b: dict, **_):
    va, vb = read_spec(facts, a), read_spec(facts, b)
    return None if va is None or vb is None else va - vb
