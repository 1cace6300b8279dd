"""A generative program's, or one of its state-space kernels', share of
its roofline for a ``granitemoehybrid`` configuration:
``required_granite.py``'s least time for one dispatch over the device
time one dispatch took."""

from .. import required, required_granite
from ..peaks import peaks_for
from . import trace_ops


def read(facts, program: str, need: str, op: str = ".", **_):
    seconds = trace_ops.read(facts, program, op, per="dispatch")
    shapes = facts.get("shapes", {}).get(need + ".granite")
    if not seconds or shapes is None:
        return None
    least = required.least_seconds(
        getattr(required_granite, need)(**shapes),
        peaks_for(facts["device"]["kind"]))
    facts.setdefault("roofline_bound", {})[need + ".granite"] = least["bound"]
    return 100.0 * least["seconds"] / seconds
