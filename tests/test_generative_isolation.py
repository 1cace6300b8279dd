"""The recommendation template's serving process loads nothing of the
generative engine: what ``cellbench/loops/serve.py`` and ``ptpu deploy``
of the recommendation template import (the engine server, the template,
``models/als.py``) leaves ``models/decoder.py``, ``templates/
generative.py`` and the kernels under them out of ``sys.modules``. A
change to those files therefore cannot move an ALS cell's numbers: the
sentence a refusal on such a cell is read against (PR 38 was refused on
``lj-r128.serve-steady``, whose process ran the parent's program byte
for byte)."""

import json
import os
import subprocess
import sys

import pytest

#: what the ALS serving process imports
SERVING = ("predictionio_tpu.server.engineserver",
           "predictionio_tpu.templates.recommendation",
           "predictionio_tpu.models.als")
#: what only the generative engine runs: the decoder, its template and
#: every ``ops`` module under them
GENERATIVE = ("predictionio_tpu.models.decoder",
              "predictionio_tpu.models.decoder_reference",
              "predictionio_tpu.templates.generative",
              "predictionio_tpu.ops.window_attention",
              "predictionio_tpu.ops.head_lanes",
              "predictionio_tpu.ops.hyper_mix",
              "predictionio_tpu.ops.ssm_scan",
              "predictionio_tpu.ops.moe")


@pytest.fixture(scope="module")
def loaded():
    """The package's modules in ``sys.modules`` of a FRESH interpreter
    after the serving imports (this one has imported half the package
    already)."""
    root = os.path.dirname(os.path.dirname(os.path.abspath(__file__)))
    out = subprocess.run(
        [sys.executable, "-c",
         "import importlib, json, sys\n"
         f"for m in {SERVING!r}: importlib.import_module(m)\n"
         "print(json.dumps(sorted(m for m in sys.modules "
         "if m.startswith('predictionio_tpu'))))"],
        cwd=root, env={**os.environ, "JAX_PLATFORMS": "cpu"},
        capture_output=True, text=True, timeout=300)
    assert out.returncode == 0, out.stderr[-2000:]
    return set(json.loads(out.stdout.splitlines()[-1]))


def test_the_serving_imports_are_what_the_process_loads(loaded):
    assert set(SERVING) <= loaded


@pytest.mark.parametrize("module", GENERATIVE)
def test_the_als_serving_process_does_not_load(loaded, module):
    assert module not in loaded
