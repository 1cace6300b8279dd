"""A generative program's, or one of its kernels', share of its roofline
for a ``nemotron_h`` configuration cut to a chip's share:
``required_nemotron.py``'s least time for one dispatch over the device
time one dispatch took."""

from .. import required, required_nemotron
from ..peaks import peaks_for
from . import trace_ops


def read(facts, program: str, need: str, op: str = ".", **_):
    seconds = trace_ops.read(facts, program, op, per="dispatch")
    shapes = facts.get("shapes", {}).get(need + ".nemotron")
    if not seconds or shapes is None:
        return None
    least = required.least_seconds(
        getattr(required_nemotron, need)(**shapes),
        peaks_for(facts["device"]["kind"]))
    facts.setdefault("roofline_bound", {})[need + ".nemotron"] = \
        least["bound"]
    return 100.0 * least["seconds"] / seconds
