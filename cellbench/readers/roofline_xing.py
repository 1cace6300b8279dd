"""A generative program's share of its roofline for a ``xing4_0``
configuration: ``required_xing.py``'s least time for one dispatch over
the device time one dispatch took."""

from .. import required, required_xing
from ..peaks import peaks_for
from . import trace_ops


def read(facts, program: str, need: str, op: str = ".", **_):
    seconds = trace_ops.read(facts, program, op, per="dispatch")
    shapes = facts.get("shapes", {}).get(need + ".xing")
    if not seconds or shapes is None:
        return None
    least = required.least_seconds(
        getattr(required_xing, need)(**shapes),
        peaks_for(facts["device"]["kind"]))
    facts.setdefault("roofline_bound", {})[need + ".xing"] = least["bound"]
    return 100.0 * least["seconds"] / seconds
