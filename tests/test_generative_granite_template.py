"""A ``granitemoehybrid`` model on the normal path: the generative
template bound into ``QueryServer`` with ``ServerConfig(batching=True)``,
queried over HTTP through ``StagedPipeline``; the state-space layers'
state in the engine's accounting. It imports ``tests/test_decoder.py``'s
fixtures: run it from the repo root."""

import threading
from datetime import datetime, timezone

import pytest

from predictionio_tpu.controller import Context
from predictionio_tpu.controller.params import EngineParams
from predictionio_tpu.data.storage import App, Storage
from predictionio_tpu.data.storage.base import (
    STATUS_COMPLETED,
    EngineInstance,
)
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
from tests.test_decoder_granite import GRANITE
from tests.test_generative_template import _post, _query

PARAMS = GenerativeParams(model=GRANITE, seed=3, max_new=8,
                          row_buckets=(4,), history_buckets=(16, 32))
HISTORIES = [[5], [7, 9, 200, 13], list(range(20, 45)),
             list(range(1, 17)), [255] * 40, [3, 1, 4, 1, 5, 9, 2, 6]]
#: 4 rows x 4 state-space layers x (a [16, 128] state + 3 x 160 of
#: window), float32
SSM_BYTES = 4 * 4 * (16 * 128 + 3 * 160) * 4


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
        [GenerativeModel(config=dict(GRANITE), seed=PARAMS.seed)], inst,
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


def test_http_answers_items_and_logits_through_the_pipeline(served):
    qs, srv = served
    assert isinstance(qs.batcher, StagedPipeline)
    got = [None] * len(HISTORIES)

    def fire(i):
        got[i] = _post(srv.port, _query(HISTORIES[i], num=3 + i % 6))

    threads = [threading.Thread(target=fire, args=(i,))
               for i in range(len(HISTORIES))]
    for t in threads:
        t.start()
    for t in threads:
        t.join(120)
        assert not t.is_alive()
    algo, model = qs.algorithms[0], qs.models[0]
    for i, hist in enumerate(HISTORIES):
        want = algo.batch_predict(
            model, [Query(items=_query(hist)["items"], num=3 + i % 6)]
        )[0].to_json()["itemScores"]
        scores = got[i]["itemScores"]
        assert [s["item"] for s in scores] == [s["item"] for s in want]
        for g, w in zip(scores, want):
            assert g["score"] == pytest.approx(w["score"], abs=1e-6)


def test_the_state_is_in_the_engines_accounting(served):
    qs, srv = served
    before = {n: _series(qs, n) for n in (
        "pio_ssm_scan_tokens_total", "pio_ssm_scan_chunks_total")}
    _post(srv.port, _query([1, 2, 3]))
    kinds = {dict(k)["kind"]: v for k, v in
             _series(qs, "pio_gen_state_bytes").items()}
    assert kinds["ssm"] == SSM_BYTES
    assert kinds["full"] == 4 * 2 * 2 * (32 + 8) * 16 * 4  # one layer
    assert set(kinds) == {"ssm", "full"}
    # nothing is in flight once the answer is back: nothing resident
    assert _series(qs, "pio_ssm_state_bytes") == {(): 0.0}
    tokens, chunks = (
        _series(qs, n)[()] - before[n].get((), 0.0) for n in before)
    # 3 real tokens in a stream of 4 x 16 slots: 4 chunks of 16
    assert (tokens, chunks) == (3, 4)


def test_batches_in_flight_hold_their_state_until_their_answer(served):
    qs, _ = served
    algo, model = qs.algorithms[0], qs.models[0]
    a = algo.batch_predict_async(model, [Query(items=("i1", "i2"))])
    b = algo.batch_predict_async(model, [Query(items=("i3",))] * 5)
    # one batch of 4 rows, then two more (5 queries over row bucket 4)
    assert _series(qs, "pio_ssm_state_bytes")[()] == 3 * SSM_BYTES
    a()
    assert _series(qs, "pio_ssm_state_bytes")[()] == 2 * SSM_BYTES
    assert len(b()) == 5
    assert _series(qs, "pio_ssm_state_bytes")[()] == 0


def test_a_ladder_that_does_not_fit_fails_the_warm_up(served, monkeypatch):
    """Weights and the pipeline's depth of batches' state against the
    device's memory, where the backend reports one (the CPU's does not:
    the check is silent there, and speaks once it is told of one). It
    reads no registry: an unregistered algorithm checks the same."""
    qs, _ = served
    model = qs.models[0]
    algo = generative.GenerativeAlgorithm(qs.algorithms[0].params)
    assert qs.algorithms[0].batches_in_flight == qs.batcher.depth == 2
    algo.batches_in_flight = 3            # as a server of depth 3 writes
    algo.warm_serving(model, 4)           # no limit known: nothing to say
    import jax

    leaf = jax.tree_util.tree_leaves(model.weights)[0]
    device = type(next(iter(leaf.devices())))
    weights = sum(a.nbytes for a in jax.tree_util.tree_leaves(model.weights))
    state = sum(v for v in _series(qs, "pio_gen_state_bytes").values())
    assert algo._enqueue(model, [[1]] * 4)[2] == {}  # unasked: uncounted
    assert sum(algo._enqueue(model, [[1]] * 4, sized=True)[2].values()) \
        == state
    need = weights + 3 * state
    monkeypatch.setattr(device, "memory_stats",
                        lambda self: {"bytes_limit": need / 0.79},
                        raising=False)
    algo.warm_serving(model, 4)           # fits under 0.8 of it
    monkeypatch.setattr(device, "memory_stats",
                        lambda self: {"bytes_limit": need / 0.81},
                        raising=False)
    with pytest.raises(RuntimeError, match="pipeline_depth"):
        algo.warm_serving(model, 4)
    algo.batches_in_flight = 2            # a shallower pipeline fits
    algo.warm_serving(model, 4)
