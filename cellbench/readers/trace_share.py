"""The share of the device's busy time spent in the operations whose
HLO text matches a pattern."""

from .. import trace


def read(facts, op: str, program: str = ".", **_):
    tr = facts.get("trace")
    if not tr:
        return None
    seconds = trace.op_seconds(tr, program, op)
    return 100.0 * seconds / tr["busy_s"] if seconds else None
