"""The one batched top-k program (``models/als.py::_serve_topk``) held
against an independent float32 numpy ranking: ragged shapes, the pad
mask and the int8/bf16 tables; its host mirror ``_host_topk``; the
serving entries that launch it (``recommend_batch`` / ``_products`` /
``_pinned``), the sharded ranker and a ``StagedPipeline`` answer; the
``_compiled_k`` clamp; and the removed selectors staying removed."""

from datetime import datetime, timezone

import numpy as np
import pytest

import jax

from predictionio_tpu.data.bimap import BiMap
from predictionio_tpu.models import als
from predictionio_tpu.models.als import (
    ALSModel,
    ALSParams,
    QuantizedFactors,
    quantize_serving_model,
    recommend_batch,
    recommend_pinned,
    recommend_products,
    table_host_f32,
)

#: (B, padded catalog rows, n_items, k): B off the pow2 ladder,
#: catalogs off any tile multiple, k of 1, and a padded catalog
SHAPES = [(1, 33, 33, 8), (13, 97, 97, 10), (7, 512, 512, 16),
          (19, 130, 130, 1), (8, 140, 100, 12)]
QUANTS = ["off", "int8", "bf16"]


def make_tables(m=120, I=200, r=16, seed=0):
    rng = np.random.default_rng(seed)
    U = rng.normal(size=(m, r)).astype(np.float32)
    V = rng.normal(size=(I, r)).astype(np.float32)
    return U, V


def as_quant(arr, quant):
    if quant == "off":
        return arr
    return QuantizedFactors(*als._quantize_rows(arr, quant), quant=quant)


def numpy_topk(vecs, V, k, n_items):
    """Float32 scores of the real rows, descending, ties to the lowest
    id — written against the semantics, not the program."""
    scores = vecs.astype(np.float32) @ V[:n_items].astype(np.float32).T
    order = np.argsort(-scores, axis=1, kind="stable")[:, :k]
    return order, np.take_along_axis(scores, order, axis=1)


def case(B, n_pad):
    U, V = make_tables(I=n_pad, seed=B * 31 + n_pad)
    idx = np.random.default_rng(B).integers(0, U.shape[0], B)
    return U, V, idx


@pytest.mark.parametrize("quant", QUANTS)
@pytest.mark.parametrize("B,n_pad,n_items,k", SHAPES)
def test_serve_topk_matches_numpy(B, n_pad, n_items, k, quant):
    U, V, idx = case(B, n_pad)
    qU, qV = as_quant(U, quant), as_quant(V, quant)
    s, i = als._serve_topk(qU, qV, idx, k=k, n_items=n_items)
    s, i = np.asarray(s), np.asarray(i)
    assert s.shape == (B, k) and i.shape == (B, k)
    assert i.max() < n_items  # a pad row is never answered
    # the reference ranks the tables the program was GIVEN (the wire's
    # rounding is the quantizer's business, tests/test_serving_quant.py)
    ids, scores = numpy_topk(table_host_f32(qU)[idx], table_host_f32(qV),
                             k, n_items)
    assert np.array_equal(i, ids)
    tol = 1e-6 if quant == "off" else 1e-5
    np.testing.assert_allclose(s, scores, rtol=tol, atol=tol)
    if quant == "int8" and k >= 8:
        # and against the f32 truth only quantization error
        truth, _ = numpy_topk(U[idx], V, k, n_items)
        overlap = np.mean([len(set(a) & set(b)) / k
                           for a, b in zip(i.tolist(), truth.tolist())])
        assert overlap >= 0.8


@pytest.mark.parametrize("B,n_pad,n_items,k", SHAPES)
def test_host_topk_mirrors_serve_topk(B, n_pad, n_items, k):
    """Whichever side of ``HOST_SERVE_WORK`` serves a model, it answers
    identically."""
    U, V, idx = case(B, n_pad)
    s_dev, i_dev = als._serve_topk(U, V, idx, k=k, n_items=n_items)
    i_host, s_host = als._host_topk(U[idx], V, k, n_items)
    assert np.array_equal(i_host, np.asarray(i_dev))
    np.testing.assert_allclose(s_host, np.asarray(s_dev),
                               rtol=1e-6, atol=1e-6)


def make_model(quant="off", r=16, nu=150, ni=180, seed=0, device=True):
    U, V = make_tables(m=nu, I=ni, r=r, seed=seed)
    if device:  # device-resident tables: the device path serves
        U, V = jax.device_put(U), jax.device_put(V)
    m = ALSModel(
        user_factors=U, item_factors=V, n_users=nu, n_items=ni,
        user_ids=BiMap({f"u{i}": i for i in range(nu)}),
        item_ids=BiMap({f"i{i}": i for i in range(ni)}),
        params=ALSParams(rank=r))
    return quantize_serving_model(m, quant)


@pytest.mark.parametrize("quant", QUANTS)
def test_serving_entries_agree(quant):
    m = make_model(quant)
    ids_b, s_b = recommend_batch(m, np.arange(20), 10)
    ids_1, s_1 = recommend_products(m, 7, 10)
    pinned, nbytes = als.pin_user_rows(m, [7], 1)
    assert nbytes > 0
    # the hot tier stays quantized when the tables are
    assert isinstance(pinned, QuantizedFactors) == (quant != "off")
    ids_p, s_p = recommend_pinned(m, pinned, 0, 10)
    assert np.array_equal(ids_b[7], ids_1)
    assert np.array_equal(ids_1, ids_p)
    np.testing.assert_allclose(s_b[7], s_1, rtol=1e-6)
    np.testing.assert_allclose(s_1, s_p, rtol=1e-6)


@pytest.mark.parametrize("k,k_dev", [(500, 180), (10, 16)])
def test_compiled_k_clamps_and_the_answer_is_sliced(k, k_dev):
    """k past the catalog is the whole catalog ranked; a k off the
    pow2 ladder runs the next rung and returns its first k."""
    m = make_model()
    assert als._compiled_k(k, m.n_items) == k_dev
    ids, scores = recommend_batch(m, np.arange(4), k)
    want, want_s = numpy_topk(table_host_f32(m.user_factors)[:4],
                              table_host_f32(m.item_factors), k, m.n_items)
    assert ids.shape == (4, min(k, m.n_items))
    assert np.array_equal(ids, want)
    np.testing.assert_allclose(scores, want_s, rtol=1e-6, atol=1e-6)


@pytest.mark.skipif(len(jax.devices()) < 8,
                    reason="needs the forced-8-device CPU mesh")
class TestShardedRank:
    """The mesh program (per-shard top-k, candidate all-gather, merge)
    answers as the single-device program does."""

    def _pair(self, quant):
        from predictionio_tpu.models.als import shard_model
        from predictionio_tpu.parallel.mesh import make_serving_mesh

        host = make_model(quant, r=8, nu=64, ni=56, seed=7, device=False)
        single = make_model(quant, r=8, nu=64, ni=56, seed=7)
        return single, shard_model(host, make_serving_mesh())

    @pytest.mark.parametrize("quant", ["off", "int8"])
    def test_sharded_matches_single(self, quant):
        single, sharded = self._pair(quant)
        ids_1, s_1 = recommend_batch(single, np.arange(12), 10)
        ids_n, s_n = recommend_batch(sharded, np.arange(12), 10)
        assert np.array_equal(ids_1, ids_n)
        np.testing.assert_allclose(s_1, s_n, rtol=1e-5, atol=1e-5)

    def test_pinned_sharded_matches_single(self):
        single, sharded = self._pair("int8")
        pinned, _ = als.pin_user_rows(sharded, [3, 5], 2)
        ids_p, _ = recommend_pinned(sharded, pinned, 1, 10)
        ids_1, _ = recommend_products(single, 5, 10)
        assert np.array_equal(ids_p, ids_1)


def test_staged_pipeline_answers_as_recommend_batch():
    """An int8 binding served through the real ``StagedPipeline``
    (coalesced batches of whatever size the burst forms) answers each
    user exactly as ``recommend_batch`` on the bound tables."""
    import concurrent.futures as cf

    from predictionio_tpu.controller import Context
    from predictionio_tpu.data.storage import App, Storage
    from predictionio_tpu.data.storage.base import (
        STATUS_COMPLETED,
        EngineInstance,
    )
    from predictionio_tpu.server.engineserver import (
        QueryServer,
        ServerConfig,
    )
    from predictionio_tpu.templates.recommendation import (
        default_engine_params,
        recommendation_engine,
    )

    model = make_model(nu=200, ni=160, seed=11, device=False)
    storage = Storage(env={"PIO_STORAGE_SOURCES_MEM_TYPE": "memory"})
    storage.apps().insert(App(0, "st"))
    now = datetime.now(timezone.utc)
    inst = EngineInstance(
        id="st", status=STATUS_COMPLETED, start_time=now, end_time=now,
        engine_id="st", engine_version="1", engine_variant="e.json",
        engine_factory="s")
    qs = QueryServer(
        Context(app_name="st", _storage=storage), recommendation_engine(),
        default_engine_params("st", rank=16), [model], inst,
        ServerConfig(batching=True, warm_start=False,
                     serving_quant="int8"))
    try:
        bound = qs.models[0]
        assert isinstance(bound.item_factors, QuantizedFactors)
        with cf.ThreadPoolExecutor(8) as pool:
            futs = {u: pool.submit(qs.serve, {"user": f"u{u}", "num": 10})
                    for u in range(24)}
            answers = {u: f.result(timeout=120) for u, f in futs.items()}
        want, _ = recommend_batch(bound, np.arange(24), 10)
        for u in range(24):
            assert [s["item"] for s in answers[u]["itemScores"]] \
                == [f"i{i}" for i in want[u]]
        assert qs.pipeline_status()["mode"] == "staged"
    finally:
        qs.close()


@pytest.mark.parametrize("field", ["serving_topk", "serving_pipeline"])
def test_removed_config_fields_are_rejected(field):
    from predictionio_tpu.server.engineserver import ServerConfig

    with pytest.raises(TypeError, match=field):
        ServerConfig(**{field: "auto"})


@pytest.mark.parametrize("flag,value", [("--pipeline", "serial"),
                                        ("--serving-topk", "fused")])
def test_removed_deploy_flags_are_rejected(flag, value, capsys):
    from predictionio_tpu.cli import build_parser

    with pytest.raises(SystemExit) as exit_:
        build_parser().parse_args(
            ["deploy", "--engine-json", "engine.json", flag, value])
    assert exit_.value.code == 2
    assert "error:" in capsys.readouterr().err
