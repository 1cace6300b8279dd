"""The ``nemotron_h`` cell with its reference computed one step below
what the configuration states: a run that has to come out NOT correct.

    python -m cellbench.control_nemotron --control state_bf16 \\
        --workload nemotron3-super-l11.gen32-hist192-closed48 --seed <n> --seconds 51 --trace 0

Everything after ``--control`` is ``cellbench.run``'s command line; the
run is the cell's own (same server, same traffic, same limits) but for
``check.control`` in the configuration it is handed: the reference
rounds every ``M`` layer's state ``S`` to bfloat16 after every token
(``state_bf16``: what keeping the recurrent state in the weights' dtype
would do, where the configuration states float32), or puts the mixers'
in- and out-projections, the latent's down- and up-projection, the
shared expert and the routed experts through an int8 round trip
(``int8_weights``: one precision below the configuration's), or the
routed experts' two matrices alone (``int8_routed``: the mechanism the
cell is about, read apart from the rest).
``tests/test_generate_hybrid.py`` drives them at the rehearsal's size;
PERF.md has the readings at the cell's.
"""

from __future__ import annotations

import argparse
import os
import sys

from . import manifest, run

CONTROLS = ("state_bf16", "int8_weights", "int8_routed")


def under(control: str):
    """A ``manifest.read_json`` that hands the ``nemotron`` configurations
    out with ``check.control`` set, at the cell's size and at the
    rehearsal's."""
    real = manifest.read_json

    def patched(path):
        cfg = real(path)
        if os.path.basename(path).startswith("nemotron") and "check" in cfg:
            cfg["check"] = {**cfg["check"], "control": control}
            cfg["rehearse"]["check"] = {**cfg["rehearse"]["check"],
                                        "control": control}
        return cfg
    return patched


def main(argv=None) -> int:
    ap = argparse.ArgumentParser(prog="cellbench.control_nemotron")
    ap.add_argument("--control", choices=CONTROLS, required=True)
    args, rest = ap.parse_known_args(argv)
    manifest.read_json = under(args.control)
    return run.main(rest)


if __name__ == "__main__":
    sys.exit(main())
