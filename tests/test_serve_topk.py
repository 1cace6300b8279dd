"""The one batched top-k program (``models/als.py::_serve_topk``) held
against an independent float32 numpy ranking: ragged shapes, the pad
mask and the int8/bf16 tables; its host mirror ``_host_topk``; the
serving entries that launch it (``recommend_batch`` / ``_products`` /
``_pinned``), the sharded ranker and a ``StagedPipeline`` answer; the
``_compiled_k`` clamp; the removed selectors staying removed; and the
two-stage selection (``_select_topk``: chunk maxima, ``k`` chunks, a
top-k of those) held to ``lax.top_k`` on the same scores, id for id and
score for score, at chunks small enough for arrays of a few hundred
items."""

from datetime import datetime, timezone

import numpy as np
import pytest

import jax
import jax.numpy as jnp

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


# -- the two-stage selection ------------------------------------------------

def _ints(B, n, seed, lo=-3, hi=4):
    """Integer scores, so that ties abound."""
    return np.random.default_rng(seed).integers(
        lo, hi, size=(B, n)).astype(np.float32)


def _ties_across_a_border():
    s = np.zeros((3, 40), np.float32)
    s[:, [7, 8, 15, 16, 23, 24]] = 5.0   # L = 8: either side of a border
    return s


def _all_in_one_chunk():
    s = _ints(4, 96, 1, lo=-9, hi=0)
    s[:, 16:24] = np.arange(8, 0, -1)    # the whole answer in chunk 2
    return s


def _several_winners_a_chunk():
    s = _ints(5, 96, 2, lo=-9, hi=0)
    s[:, [3, 5, 6]] = [4.0, 4.0, 9.0]    # three in chunk 0
    s[:, [90, 95]] = [9.0, 4.0]          # two in the last
    return s


def _pads(n, n_items, seed):
    s = _ints(6, n, seed)
    s[:, n_items:] = -np.inf
    return s


#: name -> (scores, k, L)
SELECT_CASES = {
    "ties-across-a-border": (_ties_across_a_border(), 4, 8),
    "ties-everywhere": (np.ones((2, 50), np.float32), 5, 4),
    "all-k-in-one-chunk": (_all_in_one_chunk(), 8, 8),
    "several-winners-a-chunk": (_several_winners_a_chunk(), 4, 8),
    "ragged-tail": (_ints(7, 203, 3), 8, 16),
    "ragged-tail-of-one": (_ints(3, 65, 4), 4, 16),
    "tail-holds-the-answer": (
        np.concatenate([_ints(3, 64, 5, -9, 0), _ints(3, 5, 6, 1, 9)], 1),
        4, 16),
    "pads-inside-the-last-real-chunk": (_pads(120, 83, 7), 8, 8),
    "whole-chunks-of-pads": (_pads(128, 41, 8), 4, 8),
    "pads-and-a-ragged-tail": (_pads(131, 70, 9), 8, 16),
    "fewer-real-items-than-k": (_pads(90, 5, 10), 8, 8),
    "k-1": (_ints(9, 77, 11), 1, 8),
    "k-16": (_ints(5, 300, 12, -50, 50), 16, 16),
    "one-row": (_ints(1, 100, 13), 4, 8),
    "rows-off-the-ladder": (_ints(13, 150, 14), 8, 16),
    "one-chunk-more-than-k": (_ints(4, 72, 15), 8, 8),
    "normal-scores": (np.random.default_rng(16).normal(
        size=(11, 260)).astype(np.float32), 16, 16),
}


@pytest.mark.parametrize("name", list(SELECT_CASES))
def test_select_topk_is_top_k(name):
    scores, k, L = SELECT_CASES[name]
    assert -(-scores.shape[1] // L) > k  # the chunked path
    want_s, want_i = jax.lax.top_k(jnp.asarray(scores), k)
    got_s, got_i = als._select_topk(jnp.asarray(scores), k, L=L)
    assert np.array_equal(np.asarray(got_i), np.asarray(want_i))
    assert np.array_equal(np.asarray(got_s), np.asarray(want_s))
    # and the semantics, not only the primitive: descending, ties to
    # the lowest index
    order = np.argsort(-scores, axis=1, kind="stable")[:, :k]
    assert np.array_equal(np.asarray(got_i), order)


def test_a_nan_score_is_named_as_top_k_names_it():
    """A NaN in the table is a fault the answer must show, not hide
    (``obs/numerics`` probes the served scores): its chunk's maximum is
    NaN, the chunk is picked, and the NaN ranks where ``top_k`` ranks
    it."""
    scores = _ints(3, 100, 17).astype(np.float32)
    scores[0, 37] = scores[1, 99] = np.nan
    want_s, want_i = jax.lax.top_k(jnp.asarray(scores), 4)
    got_s, got_i = als._select_topk(jnp.asarray(scores), 4, L=8)
    assert np.isnan(np.asarray(got_s)[:2, 0]).all()
    assert np.array_equal(np.asarray(got_i), np.asarray(want_i))
    assert np.array_equal(np.asarray(got_s), np.asarray(want_s),
                          equal_nan=True)


@pytest.mark.parametrize("seed", range(6))
def test_select_topk_on_random_shapes(seed):
    """The issue's scratch check, kept: integer scores, pads at -inf,
    chunks of 4, 8 and 16, every shape past the rule."""
    rng = np.random.default_rng(44 + seed)
    for _ in range(5):
        L, k = int(rng.choice([4, 8, 16])), int(rng.choice([1, 2, 5, 8]))
        B, n = int(rng.integers(1, 6)), int(rng.integers(k * L + 1, 200))
        s = _ints(B, n, int(rng.integers(1 << 30)))
        s[:, int(rng.integers(1, n + 1)):] = -np.inf
        want = jax.lax.top_k(jnp.asarray(s), k)
        got = als._select_topk(jnp.asarray(s), k, L=L)
        for g, w in zip(got, want):
            assert np.array_equal(np.asarray(g), np.asarray(w)), (L, k, B, n)


def _serve_with_chunks(monkeypatch, L):
    """``_serve_topk``'s own lines (untraced, so that the patched chunk
    is read) selecting in chunks of ``L``."""
    chunked = als._select_topk
    monkeypatch.setattr(
        als, "_select_topk", lambda scores, k: chunked(scores, k, L=L))
    return als._serve_topk.__wrapped__


@pytest.mark.parametrize("quant", QUANTS)
@pytest.mark.parametrize("B,n_pad,n_items,k,L", [
    (1, 200, 200, 8, 8),       # the exact float32 multiply-reduce
    (13, 203, 203, 16, 8),     # off the pow2 ladder, a ragged tail
    (8, 140, 100, 12, 8),      # pads inside a chunk, then chunks of pads
    (5, 97, 97, 1, 16),
], ids=["one-row", "ragged", "padded", "k-1"])
def test_serve_topk_in_chunks_answers_as_whole(monkeypatch, B, n_pad,
                                               n_items, k, L, quant):
    """The program with its selection in small chunks against the same
    program with ONE ``top_k`` (the parent's last line) and against the
    numpy ranking, for all three table kinds."""
    U, V, idx = case(B, n_pad)
    qU, qV = as_quant(U, quant), as_quant(V, quant)
    whole_s, whole_i = als._serve_topk(qU, qV, idx, k=k, n_items=n_items)
    assert -(-n_pad // L) > k >= -(-n_pad // als.SELECT_CHUNK)
    s, i = _serve_with_chunks(monkeypatch, L)(
        qU, qV, jnp.asarray(idx), k=k, n_items=n_items)
    assert np.array_equal(np.asarray(i), np.asarray(whole_i))
    np.testing.assert_allclose(np.asarray(s), np.asarray(whole_s),
                               rtol=1e-6, atol=1e-6)
    ids, _ = numpy_topk(table_host_f32(qU)[idx], table_host_f32(qV),
                        k, n_items)
    assert np.array_equal(np.asarray(i), ids)


def test_duplicated_item_rows_in_different_chunks_tie_to_the_lowest_id(
        monkeypatch):
    """The same factor row at ids in different chunks scores the same
    to the bit: the answer names the lowest ids first."""
    U, V, idx = case(6, 120)
    V[[5, 37, 38, 77, 119]] = 4.0 * U[idx[0]]  # row 0's five best, tied
    s, i = _serve_with_chunks(monkeypatch, 8)(
        U, V, jnp.asarray(idx), k=8, n_items=120)
    assert np.asarray(i)[0, :5].tolist() == [5, 37, 38, 77, 119]
    want_s, want_i = als._serve_topk(U, V, idx, k=8, n_items=120)
    assert np.array_equal(np.asarray(i), np.asarray(want_i))


@pytest.mark.parametrize("B,n,L", [(1, 100, 8), (4, 300, 128), (8, 1000, 8),
                                   (13, 77, 4), (19, 2051, 16)])
def test_chunk_maxima_kernel_and_twin_agree(B, n, L):
    """The Pallas pass (in its interpreter here) against the plain
    reduction the CPU takes and against numpy: rows short of a sublane
    tile, a row block past the batch, a ragged last chunk."""
    scores = np.random.default_rng(n).normal(size=(B, n)).astype(np.float32)
    padded = np.full((B, -(-n // L) * L), -np.inf, np.float32)
    padded[:, :n] = scores
    want = padded.reshape(B, -1, L).max(-1)
    twin = als._chunk_maxima(jnp.asarray(scores), L)
    kernel = als._chunk_maxima_kernel(jnp.asarray(scores), L,
                                      interpret=True)
    assert np.array_equal(np.asarray(twin), want)
    assert np.array_equal(np.asarray(kernel), want)


def _widest_selection(jaxpr):
    """Widths of the operands of every ``top_k`` and ``sort`` of a
    jaxpr, nested jaxprs included."""
    widths = []
    for eqn in jaxpr.eqns:
        if eqn.primitive.name in ("top_k", "sort"):
            widths.append(max(v.aval.shape[-1] for v in eqn.invars))
        for sub in jax.core.jaxprs_in_params(eqn.params):
            widths.extend(_widest_selection(sub))
    return widths


def test_no_selection_is_as_wide_as_the_catalog():
    """Past the rule (more chunks than ``k``) no ``top_k`` or ``sort``
    of the program takes an operand ``n_pad`` wide; under it exactly
    one does. The full-width selection cannot come back unnoticed."""
    k, r = 16, 8
    past = (k + 1) * als.SELECT_CHUNK + 3
    under = k * als.SELECT_CHUNK

    def widths(n_pad):
        tables = [jax.ShapeDtypeStruct((n_pad, r), jnp.float32)] * 2
        idx = jax.ShapeDtypeStruct((4,), jnp.int32)
        closed = jax.make_jaxpr(
            lambda u, v, i: als._serve_topk(u, v, i, k=k, n_items=n_pad)
        )(*tables, idx)
        return _widest_selection(closed.jaxpr)

    assert widths(past) and max(widths(past)) == k * als.SELECT_CHUNK
    assert [w for w in widths(under) if w >= under] == [under]


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
