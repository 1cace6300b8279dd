"""A kernel's share of its roofline."""

from .. import required
from ..peaks import peaks_for
from . import trace_ops


def read(facts, program: str, per: str, need: str, op: str = ".", **_):
    """The least time the chip could take for the work the algorithm
    needs (``required.py``, from ``facts['shapes']``) over the device
    time measured, in percent. ``facts['roofline_bound']`` keeps which
    peak bounds it."""
    seconds = trace_ops.read(facts, program, op, per)
    shapes = facts.get("shapes", {}).get(need)
    if not seconds or shapes is None:
        return None
    least = required.least_seconds(
        getattr(required, need)(**shapes),
        peaks_for(facts["device"]["kind"]))
    facts.setdefault("roofline_bound", {})[need] = least["bound"]
    return 100.0 * least["seconds"] / seconds
