"""A generative program's share of its roofline: ``required_gen.py``'s
least time for one dispatch over the device time one dispatch took."""

from .. import required, required_gen
from ..peaks import peaks_for
from . import trace_ops


def read(facts, program: str, need: str, **_):
    seconds = trace_ops.read(facts, program, per="dispatch")
    shapes = facts.get("shapes", {}).get(need)
    if not seconds or shapes is None:
        return None
    least = required.least_seconds(
        getattr(required_gen, need)(**shapes),
        peaks_for(facts["device"]["kind"]))
    facts.setdefault("roofline_bound", {})[need] = least["bound"]
    return 100.0 * least["seconds"] / seconds
