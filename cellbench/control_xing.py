"""The ``xing4_0`` cell with its reference computed one step below what
the configuration states: a run that has to come out NOT correct.

    python -m cellbench.control_xing --control int8_weights \\
        --workload xing4-l6.gen32-hist2k-closed12 --seed <n> --seconds 51 --trace 0

Everything after ``--control`` is ``cellbench.run``'s command line; the
run is the cell's own (same server, same traffic, same limits) but for
``check.control`` in the configuration it is handed: the shared
expert's, the dense layers' and the latent up-projections' weights
through an int8 round trip (``int8_weights``: one precision below the
configuration's), or ONE Sinkhorn pass where the configuration states
twenty (``sinkhorn_1``: shows that ``correct`` sees the residual path),
on the reference's side of the comparison.
``tests/test_generate_latent.py`` drives both at the rehearsal's size;
PERF.md has the readings at the cell's.
"""

from __future__ import annotations

import argparse
import os
import sys

from . import manifest, run

CONTROLS = ("int8_weights", "sinkhorn_1")


def under(control: str):
    """A ``manifest.read_json`` that hands the ``xing4`` configurations
    out with ``check.control`` set, at the cell's size and at the
    rehearsal's."""
    real = manifest.read_json

    def patched(path):
        cfg = real(path)
        if os.path.basename(path).startswith("xing4-") and "check" in cfg:
            cfg["check"] = {**cfg["check"], "control": control}
            cfg["rehearse"]["check"] = {**cfg["rehearse"]["check"],
                                        "control": control}
        return cfg
    return patched


def main(argv=None) -> int:
    ap = argparse.ArgumentParser(prog="cellbench.control_xing")
    ap.add_argument("--control", choices=CONTROLS, required=True)
    args, rest = ap.parse_known_args(argv)
    manifest.read_json = under(args.control)
    return run.main(rest)


if __name__ == "__main__":
    sys.exit(main())
