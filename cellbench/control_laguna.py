"""The ``laguna`` cell with its reference computed one step below what
the configuration states: a run that has to come out NOT correct.

    python -m cellbench.control_laguna --control int8_experts \\
        --workload laguna-l5.gen32-hist1k-closed48 --seed <n> --seconds 51 --trace 0

Everything after ``--control`` is ``cellbench.run``'s command line; the
run is the cell's own (same server, same traffic, same limits) but for
``check.control`` in the configuration it is handed: the routed and the
shared experts' weights through an int8 round trip (``int8_experts``:
one precision below the configuration's), or the reference's sliding
layers seeing every earlier key (``no_window``: shows that ``correct``
sees the ring and the mask), on the reference's side of the comparison.
``tests/test_generate_long.py`` drives both at the rehearsal's size;
PERF.md has the readings at the cell's.
"""

from __future__ import annotations

import argparse
import os
import sys

from . import manifest, run

CONTROLS = ("int8_experts", "no_window")
#: a reading, not a verdict: the routed experts alone through the round
#: trip (the shared expert sound). PERF.md finding 32.9 has what it reads
PROBES = ("int8_routed",)


def under(control: str):
    """A ``manifest.read_json`` that hands the ``laguna`` configurations
    out with ``check.control`` set, at the cell's size and at the
    rehearsal's."""
    real = manifest.read_json

    def patched(path):
        cfg = real(path)
        if os.path.basename(path).startswith("laguna-") and "check" in cfg:
            cfg["check"] = {**cfg["check"], "control": control}
            cfg["rehearse"]["check"] = {**cfg["rehearse"]["check"],
                                        "control": control}
        return cfg
    return patched


def main(argv=None) -> int:
    ap = argparse.ArgumentParser(prog="cellbench.control_laguna")
    ap.add_argument("--control", choices=CONTROLS + PROBES, required=True)
    args, rest = ap.parse_known_args(argv)
    manifest.read_json = under(args.control)
    return run.main(rest)


if __name__ == "__main__":
    sys.exit(main())
