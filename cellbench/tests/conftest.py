"""``loops/train.py`` has no cell in ``BENCHMARK.json`` (the ML-20M
problem leaves the chip nearly empty and the chip check refuses it), so
the tests add one to the manifest as a later PR would: entries only."""

import json
import os

import pytest

from cellbench import manifest

HERE = os.path.dirname(os.path.abspath(__file__))


@pytest.fixture
def train_cell(monkeypatch):
    """The manifest with ``train_cell.json``'s entries added."""
    with open(os.path.join(HERE, "train_cell.json")) as f:
        extra = json.load(f)
    man = manifest.load()
    for key in ("configs", "workloads", "end_to_end", "per_layer"):
        man[key] = man[key] + extra[key]
    monkeypatch.setattr(manifest, "load", lambda root=manifest.ROOT: man)
    return man
