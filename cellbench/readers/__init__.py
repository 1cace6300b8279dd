"""Readers of per-layer metrics. A metric's file under ``metrics/``
names one reader and its arguments; the reader is the module of that
name in this package, so a new kind of reader arrives as a new file.
Its ``read(facts, **arguments)`` takes the run's ``facts`` and returns
a number, or ``None`` where it finds nothing to read (the harness then
leaves the metric out of the line).

``facts`` holds what a run collected, never anything cell-specific:
``stamps`` (stage seconds), ``registry`` (the server registry's export
at the opening and the close of the untraced part of the window),
``loadgen`` (client-side numbers of that same part), ``trace`` (the
reduced device trace), ``shapes`` (sizes from the configuration and
the bound model), ``device`` (kind, memory peak) and ``units`` (how
many iterations or dispatches the traced window held).
"""

from __future__ import annotations

import importlib


def read(facts: dict, spec: dict):
    """Run the reader a metric's file names."""
    args = {k: v for k, v in spec.items() if k != "reader"}
    module = importlib.import_module(f"{__name__}.{spec['reader']}")
    return module.read(facts, **args)
