"""A ``nemotron_h`` model cut to a chip's SHARE on the normal path: the
generative template bound into ``QueryServer`` with
``ServerConfig(batching=True)``, queried over HTTP through
``StagedPipeline``; the expert series count the experts HELD, the
router's assignments are told apart by where the expert lives, and the
residency check counts the share. It imports ``tests/test_decoder.py``'s
fixtures: run it from the repo root."""

from datetime import datetime, timezone

import numpy as np
import pytest

from predictionio_tpu.controller import Context
from predictionio_tpu.controller.params import EngineParams
from predictionio_tpu.data.storage import App, Storage
from predictionio_tpu.data.storage.base import (
    STATUS_COMPLETED,
    EngineInstance,
)
from predictionio_tpu.ops import moe
from predictionio_tpu.server.engineserver import (
    QueryServer,
    ServerConfig,
    StagedPipeline,
    create_engine_server,
)
from predictionio_tpu.templates import generative
from predictionio_tpu.templates.generative import (
    GenerativeModel,
    GenerativeParams,
    Query,
    generative_engine,
)
from tests.test_decoder_nemotron import NEMOTRON
from tests.test_generative_template import _post, _query

#: experts 4-7 of 16 and the first half of the vocabulary: the file's
#: counting key gives the share, ``router_experts`` the router's width
HELD = (4, 5, 6, 7)
SHARE = {**NEMOTRON, "n_routed_experts": 4, "router_experts": 16,
         "experts_held": list(HELD), "vocab_size": 128}
PARAMS = GenerativeParams(model=SHARE, seed=3, max_new=8,
                          row_buckets=(4,), history_buckets=(16, 32))
#: 4 rows x 3 ``M`` layers x (a [16, 128] state + 3 x 192 of window),
#: float32; the three ``E`` layers hold nothing
SSM_BYTES = 4 * 3 * (16 * 128 + 3 * 192) * 4


@pytest.fixture(scope="module")
def served():
    storage = Storage(env={"PIO_STORAGE_SOURCES_MEM_TYPE": "memory"})
    storage.apps().insert(App(0, "gen"))
    now = datetime.now(timezone.utc)
    inst = EngineInstance(
        id="g0", status=STATUS_COMPLETED, start_time=now, end_time=now,
        engine_id="gen", engine_version="1",
        engine_variant="engine.json", engine_factory="synthetic")
    qs = QueryServer(
        Context(app_name="gen", _storage=storage), generative_engine(),
        EngineParams(algorithms=(("decoder", PARAMS),)),
        [GenerativeModel(config=dict(SHARE), seed=PARAMS.seed)], inst,
        ServerConfig(batching=True, max_batch=4, batch_window_ms=20.0))
    assert qs.warm_done.wait(300), qs.warm_error
    srv = create_engine_server(qs, host="127.0.0.1", port=0)
    srv.start_background()
    yield qs, srv
    srv.shutdown()
    qs.close()


def _series(qs, name):
    return {tuple(sorted(c["labels"].items())): c["value"] for c in
            qs.metrics.export()[name]["children"]}


def test_http_answers_items_of_the_slice_and_their_logits(served):
    qs, srv = served
    assert isinstance(qs.batcher, StagedPipeline)
    algo, model = qs.algorithms[0], qs.models[0]
    assert model.weights["head"].shape == (128, 64)
    assert model.weights["layers"][1]["w1"].shape == (4, 32, 48)
    for hist, num in (([5], 8), ([7, 9, 100, 13], 3),
                      (list(range(20, 45)), 5)):
        got = _post(srv.port, _query(hist, num=num))["itemScores"]
        want = algo.batch_predict(
            model, [Query(items=_query(hist)["items"], num=num)]
        )[0].to_json()["itemScores"]
        assert len(got) == num
        assert [s["item"] for s in got] == [s["item"] for s in want]
        assert all(int(s["item"][1:]) < 128 for s in got)
        for g, w in zip(got, want):
            assert g["score"] == pytest.approx(w["score"], abs=1e-6)
    # an id outside the slice held is no item of this model: dropped
    assert algo._history(model, Query(items=("i5", "i200", "i127"))) \
        == [5, 127]


def test_the_expert_series_count_the_experts_held(served):
    """One batch through the engine, its loads in hand: touched and read
    over the 4 held experts (never over the router's 16), the
    assignments split by where the expert lives."""
    qs, _ = served
    algo, model = qs.algorithms[0], qs.models[0]
    name = "pio_moe_assignments_total"
    before = _series(qs, name)
    hists = [[1, 2, 3, 4, 5], [9] * 12, [100, 3], [7]]
    arrays, slots, _ = algo._enqueue(model, hists)
    prefill, decode = (np.asarray(a) for a in arrays[2])
    assert prefill.shape == (3, 16) and decode.shape == (7, 3, 16)
    algo._observe(model.cfg, hists, 4, slots, arrays[2])
    export = qs.metrics.export()
    child, = export["pio_moe_experts_touched"]["children"]
    assert [le for le, _ in child["buckets"]][:4] == [1.0, 2.0, 3.0, 4.0]
    mine = (decode[..., list(HELD)] > 0).sum(axis=-1).mean()
    assert 0 < mine <= 4
    read, = export["pio_moe_experts_read"]["children"]
    assert child["count"] == read["count"] >= 1
    # 4 rows x 4 a token over 16 of which 4 are held: 4 land here, so
    # the step takes the touched experts and reads what it touched
    assert moe.product_form(4, 4, 4, 16) == moe.TOUCHED
    assert read["sum"] == pytest.approx(child["sum"])
    got = _series(qs, name)
    held = got[(("where", "held"),)] \
        - before.get((("where", "held"),), 0.0)
    absent = got[(("where", "absent"),)] \
        - before.get((("where", "absent"),), 0.0)
    here = prefill[:, list(HELD)].sum() + decode[..., list(HELD)].sum()
    assert held == here and held + absent == prefill.sum() + decode.sum()
    # every real token and every decoded row, 4 experts each, 3 layers
    assert held + absent == (20 + 4 * 7) * 4 * 3
    assert 0.1 < held / (held + absent) < 0.45


def test_the_state_and_the_residency_count_what_this_chip_holds(
        served, monkeypatch):
    qs, srv = served
    _post(srv.port, _query([1, 2, 3]))
    kinds = {dict(k)["kind"]: v for k, v in
             _series(qs, "pio_gen_state_bytes").items()}
    # the layers that are a feed-forward alone carry no state, and no
    # kind is invented for them
    assert set(kinds) == {"ssm", "full"}
    assert kinds["ssm"] == SSM_BYTES
    assert kinds["full"] == 4 * 2 * 2 * (32 + 8) * 16 * 4
    import jax

    model = qs.models[0]
    algo = generative.GenerativeAlgorithm(qs.algorithms[0].params)
    algo.batches_in_flight = 2             # as the server's pipeline writes
    leaves = jax.tree_util.tree_leaves(model.weights)
    weights = sum(a.nbytes for a in leaves)
    whole = GenerativeModel(config=dict(NEMOTRON), seed=3).materialise()
    uncut = sum(a.nbytes for a in jax.tree_util.tree_leaves(whole.weights))
    # a quarter of three layers' experts and half the vocabulary less
    assert uncut - weights == 3 * 12 * 2 * 32 * 48 * 4 + 2 * 128 * 64 * 4
    need = weights + 2 * sum(kinds.values())
    device = type(next(iter(leaves[0].devices())))
    monkeypatch.setattr(device, "memory_stats",
                        lambda self: {"bytes_limit": need / 0.79},
                        raising=False)
    algo.warm_serving(model, 4)            # the share fits
    monkeypatch.setattr(device, "memory_stats",
                        lambda self: {"bytes_limit": need / 0.81},
                        raising=False)
    with pytest.raises(RuntimeError, match="pipeline_depth"):
        algo.warm_serving(model, 4)
