"""The generative template (``templates/generative.py``) on the normal
path: bound into ``QueryServer`` with ``ServerConfig(batching=True)``,
queried over HTTP, batches formed and launched by ``StagedPipeline``."""

import http.client
import json
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
from predictionio_tpu.server.stats import RecompileSentinel
from predictionio_tpu.templates.generative import (
    GenerativeAlgorithm,
    GenerativeModel,
    GenerativeParams,
    Query,
    generative_engine,
)
from test_decoder import SMALL

PARAMS = GenerativeParams(model=SMALL, seed=3, max_new=8,
                          row_buckets=(4, 8), history_buckets=(16, 32))
HISTORIES = [[5], [7, 9, 200, 13], list(range(20, 45)),
             list(range(1, 17)), [255] * 40, [3, 1, 4, 1, 5, 9, 2, 6]]


def _query(hist, num=8):
    return {"items": [f"i{t}" for t in hist], "num": num}


@pytest.fixture(scope="module")
def served():
    storage = Storage(env={"PIO_STORAGE_SOURCES_MEM_TYPE": "memory"})
    storage.apps().insert(App(0, "gen"))
    ctx = Context(app_name="gen", _storage=storage)
    now = datetime.now(timezone.utc)
    inst = EngineInstance(
        id="g0", status=STATUS_COMPLETED, start_time=now, end_time=now,
        engine_id="gen", engine_version="1",
        engine_variant="engine.json", engine_factory="synthetic")
    engine = generative_engine()
    ep = EngineParams(algorithms=(("decoder", PARAMS),))
    model = GenerativeModel(config=dict(SMALL), seed=PARAMS.seed)
    qs = QueryServer(ctx, engine, ep, [model], inst,
                     ServerConfig(batching=True, max_batch=8,
                                  batch_window_ms=20.0))
    assert qs.warm_done.wait(300), qs.warm_error
    srv = create_engine_server(qs, host="127.0.0.1", port=0)
    srv.start_background()
    yield qs, srv
    srv.shutdown()
    qs.close()


def _post(port, body):
    conn = http.client.HTTPConnection("127.0.0.1", port, timeout=120)
    try:
        conn.request("POST", "/queries.json", json.dumps(body),
                     {"Content-Type": "application/json"})
        resp = conn.getresponse()
        payload = resp.read()
        assert resp.status == 200, payload
        return json.loads(payload)
    finally:
        conn.close()


def test_http_answers_what_batch_predict_gives(served):
    """Mixed history lengths (1 .. over the longest bucket) in one
    batch: every caller gets ITS history's continuation, the one
    ``batch_predict`` gives that history alone."""
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
        num = 3 + i % 6
        want = algo.batch_predict(
            model, [Query(items=_query(hist)["items"], num=num)]
        )[0].to_json()["itemScores"]
        scores = got[i]["itemScores"]
        assert len(scores) == num
        assert [s["item"] for s in scores] == [s["item"] for s in want]
        for g, w in zip(scores, want):
            assert g["score"] == pytest.approx(w["score"], abs=2e-4)
    # the batcher coalesced: fewer batches than queries
    occ = qs.metrics.export()["pio_batch_occupancy"]["children"][0]
    assert occ["sum"] / occ["count"] > 1.0


def test_no_compile_after_the_warm_ladder(served):
    qs, srv = served
    for hist in ([9], list(range(30)), list(range(100, 120))):
        _post(srv.port, _query(hist))
    status = _post_get(srv.port, "/status.json")
    assert status["recompile"]["armed"] is True
    assert status["recompile"]["compilesSinceWarm"] == 0


def _post_get(port, path):
    conn = http.client.HTTPConnection("127.0.0.1", port, timeout=60)
    try:
        conn.request("GET", path)
        return json.loads(conn.getresponse().read())
    finally:
        conn.close()


#: history lengths of one batch -> the slots its prefill runs: the row
#: bucket x the history bucket of the batch's MEAN history, every row (a
#: pad row is one token long) taking whole tiles of 8 slots
#: (``decoder.row_ends``), with row buckets (4, 8) and history buckets
#: (16, 32)
MIXES = {
    "one_short_row": ([1], 64),
    "every_row_at_the_top_bucket": ([32, 32, 32, 32], 128),
    "long_rows_beside_short_ones": ([32, 1, 1, 14], 64),
    "a_mean_just_over_a_bucket": ([32, 32, 1], 128),
    "five_rows_in_the_bucket_of_eight": ([16] * 5, 128),
    "eight_rows_at_the_top_bucket": ([32] * 8, 256),
    "seven_ragged_rows": ([32, 31, 2, 9, 17, 1, 25], 256),
    "seven_ragged_rows_on_the_lowest_rung": ([24, 31, 2, 9, 8, 1, 16], 128),
    "eight_rows_on_the_lowest_rung": ([32, 32, 24, 8, 1, 1, 1, 1], 128),
}


def _token_kinds(qs):
    return {c["labels"]["kind"]: c["value"] for c in
            qs.metrics.export()["pio_gen_tokens_total"]["children"]}


def _mix(name):
    lengths, slots = MIXES[name]
    return [[(7 * r + t) % SMALL["vocab_size"] for t in range(n)]
            for r, n in enumerate(lengths)], slots


@pytest.mark.parametrize("name", sorted(MIXES))
def test_ragged_batches_compile_nothing_after_the_uniform_ladder(served,
                                                                 name):
    """The server warmed with UNIFORM histories at every history bucket
    (``warm_serving``); a batch of any mix then finds its programs."""
    qs, _ = served
    hists, slots = _mix(name)
    sentinel = RecompileSentinel()
    sentinel.arm()
    arrays, ran = qs.algorithms[0]._dispatch(qs.models[0], hists)
    arrays[0].block_until_ready()
    assert sentinel.since_armed == 0
    assert ran == slots


@pytest.mark.parametrize("name", sorted(MIXES))
def test_pad_counts_the_slots_run_less_the_real_tokens(served, name):
    qs, _ = served
    hists, slots = _mix(name)
    before = _token_kinds(qs)
    qs.algorithms[0].batch_predict(qs.models[0], [
        Query(items=_query(h)["items"]) for h in hists])
    after = _token_kinds(qs)
    prompt = sum(len(h) for h in hists)
    assert after["prompt"] - before["prompt"] == prompt
    assert after["pad"] - before["pad"] == slots - prompt
    assert after["generated"] - before["generated"] == 8 * len(hists)


def test_per_batch_series_are_on_the_servers_registry(served):
    qs, srv = served
    _post(srv.port, _query([1, 2, 3]))
    export = qs.metrics.export()
    kinds = _token_kinds(qs)
    assert kinds["prompt"] > 0 and kinds["generated"] > 0
    # one query in a stream of 4 x 16 slots: 3 prompt tokens, the rest
    # padding
    assert kinds["pad"] >= 4 * 16 - 3
    touched = export["pio_moe_experts_touched"]["children"][0]
    assert touched["count"] >= 1
    assert 1 <= touched["sum"] / touched["count"] <= SMALL["num_experts"]
    # beside it, once a batch too: what a step fetched is what it
    # touched (batches of 4 rows: 8 assignments over 8 experts) or all 8
    read = export["pio_moe_experts_read"]["children"][0]
    assert read["count"] == touched["count"]
    assert touched["sum"] <= read["sum"] <= 8 * read["count"]
    assert export["pio_moe_load_imbalance"]["children"][0]["count"] >= 4


@pytest.mark.parametrize("rows,held,want", [
    (4, None, 2.5),         # 8 assignments over 8 experts: the touched
    (8, None, 8.0),         # 16 over 8: every held expert
    (2, (0, 1, 2, 5), 1.5),  # 4 over 8, half of them held: 2 land on the
    (4, (0, 1, 2, 5), 1.5),  # 4 held here, then 4: those touched HERE
    (5, (0, 1, 2, 5), 4.0),  # 10 over 8, 5 here over 4 held: every one
])
def test_experts_read_follows_the_form_the_step_took(rows, held, want):
    """``pio_moe_experts_read`` off the loads the decode returns and the
    form ``ops/moe.py`` takes for that many rows: the touched count
    (among the experts held) or all that are held; under a share both
    series count the experts HELD, and the form reckons with the share
    of the assignments that can land here."""
    import dataclasses

    import numpy as np

    from predictionio_tpu.models.decoder import DecoderConfig
    from predictionio_tpu.obs.registry import MetricsRegistry

    algo = GenerativeAlgorithm(PARAMS)
    registry = MetricsRegistry()
    algo.register_metrics(registry)
    cfg = dataclasses.replace(DecoderConfig.from_dict(SMALL),
                              experts_held=held)
    # two steps of one expert layer: experts 0, 3, 5 then 5, 6
    decode = np.zeros((2, 1, 8), np.int32)
    decode[0, 0, [0, 3, 5]] = 2
    decode[1, 0, [5, 6]] = 3
    algo._observe(cfg, [[1, 2]] * rows, rows, 64,
                  (np.zeros((1, 8)), decode))
    export = registry.export()
    read = export["pio_moe_experts_read"]["children"][0]
    assert (read["count"], read["sum"]) == (1, want)
    touched = export["pio_moe_experts_touched"]["children"][0]
    assert (touched["count"], touched["sum"]) == (
        1, 2.5 if held is None else 1.5)


def test_unknown_items_and_empty_histories():
    algo = GenerativeAlgorithm(PARAMS)
    model = GenerativeModel(config=dict(SMALL), seed=1).materialise()
    out = algo.batch_predict(model, [
        Query(items=("i999", "nope"), num=4), Query(items=("i5",), num=2)])
    assert out[0].item_scores == ()
    assert len(out[1].item_scores) == 2
    stored = algo.make_persistent_model(model, "x", 0)
    assert stored.weights is None
    again = algo.prepare_serving_model(stored, 8)
    assert algo.batch_predict(again, [Query(items=("i5",), num=2)]
                              )[0] == out[1]


# -- the ``laguna`` family through the same engine --------------------------

def _laguna_toy():
    """An untied head, window rings, per-kind heads and a 4096 bucket at
    toy widths: one layer of each kind, window 128."""
    from test_decoder import LAGUNA

    return {**LAGUNA, "hidden_size": 32, "intermediate_size": 64,
            "moe_intermediate_size": 16,
            "shared_expert_intermediate_size": 16, "head_dim": 8,
            "num_hidden_layers": 2, "sliding_window": 128,
            "layer_types": ["full_attention", "sliding_attention"],
            "mlp_layer_types": ["dense", "sparse"],
            "num_attention_heads_per_layer": [4, 6]}


LAGUNA_PARAMS = GenerativeParams(
    model=_laguna_toy(), seed=5, max_new=4, row_buckets=(2,),
    history_buckets=(1024, 2048, 4096))


@pytest.mark.parametrize("lengths,slots", [
    ([300, 1500], 2 * 1024), ([4096, 700], 2 * 4096),
    ([5000, 2], 2 * 4096)])
def test_long_histories_through_an_untied_head(lengths, slots):
    """Histories to the 4096 bucket (a longer one keeps its last 4096)
    in streams sized by the batch's mean; the answer's first item is
    the reference's greedy choice through the head's OWN matrix, and the
    state's bytes by kind are on the registry."""
    import numpy as np

    from predictionio_tpu.models import decoder_reference as ref
    from predictionio_tpu.obs.registry import MetricsRegistry

    algo = GenerativeAlgorithm(LAGUNA_PARAMS)
    registry = MetricsRegistry()
    algo.register_metrics(registry)
    model = GenerativeModel(config=_laguna_toy(), seed=5).materialise()
    assert "head" in model.weights
    assert not np.array_equal(np.asarray(model.weights["head"]),
                              np.asarray(model.weights["embed"]))
    hists = [[(11 * r + 3 * t) % 256 for t in range(n)]
             for r, n in enumerate(lengths)]
    arrays, ran = algo._dispatch(model, [h[-4096:] for h in hists])
    assert ran == slots
    out = algo.batch_predict(model, [Query(items=_query(h)["items"], num=4)
                                     for h in hists])
    kept = hists[1][-4096:]
    logits = np.asarray(ref.forward(model.weights, kept,
                                    _laguna_toy()))[-1]
    first = out[1].item_scores[0]
    assert first.item == f"i{int(logits.argmax())}"
    assert first.score == pytest.approx(float(logits.max()), abs=2e-4)
    kinds = {c["labels"]["kind"]: c["value"] for c in
             registry.export()["pio_gen_state_bytes"]["children"]}
    # float32 here: 2 rows x 2 key-value heads x 8 x 4 bytes x (keys and
    # values) x slots
    assert kinds == {"full": 2 * 2 * 8 * 4 * 2 * (4096 + 4),
                     "window": 2 * 2 * 8 * 4 * 2 * 128}
    touched = registry.export()["pio_moe_experts_touched"]["children"][0]
    assert [b[0] for b in touched["buckets"]] == [
        1.0, 2.0, 3.0, 4.0, 5.0, 6.0, 7.0, 8.0, "+Inf"]
    # 2 rows x 2 over 8 experts: the decode went through the touched
    # experts' kernel, and read what it touched
    read = registry.export()["pio_moe_experts_read"]["children"][0]
    assert read["buckets"] == touched["buckets"]
    assert (read["count"], read["sum"]) == (touched["count"],
                                            pytest.approx(touched["sum"]))


# -- the ``xing4_0`` family through the same engine -------------------------

def test_four_streams_and_a_latent_cache_through_the_same_engine():
    """Latent attention inside four residual streams, by the family's
    published keys: the answer's first item is the reference's greedy
    choice, the state's bytes read the latents, the Sinkhorn passes'
    gap is observed once a batch and rides BEHIND the three arrays of
    an answer (a one-stream model's dispatch returns three, as ever)."""
    import numpy as np

    from predictionio_tpu.models import decoder_reference as ref
    from predictionio_tpu.obs.registry import MetricsRegistry
    from test_decoder import XING

    toy = {**XING, "num_hidden_layers": 2, "first_k_dense_replace": 1}
    params = GenerativeParams(model=toy, seed=5, max_new=4,
                              row_buckets=(2,), history_buckets=(16, 32))
    algo = GenerativeAlgorithm(params)
    registry = MetricsRegistry()
    algo.register_metrics(registry)
    model = GenerativeModel(config=toy, seed=5).materialise()
    hists = [[(11 * r + 3 * t) % 256 for t in range(n)]
             for r, n in enumerate([9, 30])]
    arrays, ran = algo._dispatch(model, hists)
    assert ran == 2 * 32 and len(arrays) == 4 and arrays[3].shape == ()
    out = algo.batch_predict(model, [Query(items=_query(h)["items"], num=4)
                                     for h in hists])
    logits = np.asarray(ref.forward(model.weights, hists[1], toy))[-1]
    first = out[1].item_scores[0]
    assert first.item == f"i{int(logits.argmax())}"
    assert first.score == pytest.approx(float(logits.max()), abs=2e-4)
    kinds = {c["labels"]["kind"]: c["value"] for c in
             registry.export()["pio_gen_state_bytes"]["children"]}
    # float32 here: 2 layers x 2 rows x (32 + 4) slots x (16 + 8) x 4 bytes
    assert kinds == {"latent": 2 * 2 * (32 + 4) * (16 + 8) * 4}
    gap = registry.export()["pio_mhc_sinkhorn_gap"]["children"][0]
    assert gap["count"] == 1 and 0 < gap["sum"] < 5e-3
    touched = registry.export()["pio_moe_experts_touched"]["children"][0]
    assert [b[0] for b in touched["buckets"]][-2:] == [8.0, "+Inf"]


def test_a_history_bucket_that_is_not_whole_tiles_is_refused():
    """Rows end on a tile's edge (``decoder.row_ends``), so the ladder's
    uniform rows at a bucket of 12 would be sized as rows of 16 and the
    stream a ragged batch can give there would never be warmed: the
    engine says so at the first dispatch, the warm-up's."""
    params = GenerativeParams(model=SMALL, seed=3, max_new=4,
                              row_buckets=(2,), history_buckets=(12, 32))
    model = GenerativeModel(config=dict(SMALL), seed=3)
    with pytest.raises(ValueError, match="whole tiles of 8"):
        GenerativeAlgorithm(params)._dispatch(model, [[1, 2, 3]])


def test_experts_touched_bounds_follow_the_model():
    from predictionio_tpu.templates.generative import (
        experts_touched_bounds)

    assert experts_touched_bounds(32) == (1, 2, 4, 8, 12, 16, 20, 24, 28,
                                          30, 31, 32)
    big = experts_touched_bounds(256)
    assert big[-3:] == (240, 255, 256) and big[:4] == (1, 2, 4, 8)
    assert all(a < b for a, b in zip(big, big[1:]))
    assert experts_touched_bounds(8) == (1, 2, 3, 4, 5, 6, 7, 8)
