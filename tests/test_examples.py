"""Every shipped example variant must load through the CLI build path."""

import glob

import pytest

from predictionio_tpu.cli import engine_from_variant, load_variant

VARIANTS = sorted(glob.glob("examples/*/engine.json"))


def test_examples_exist():
    assert len(VARIANTS) == 6


@pytest.mark.parametrize("path", VARIANTS)
def test_variant_loads(path):
    variant = load_variant(path)
    engine, ep = engine_from_variant(variant)
    assert ep.algorithms
    assert engine.make_algorithms(ep)
    assert engine.make_serving(ep) is not None
