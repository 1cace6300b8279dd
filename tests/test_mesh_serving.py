"""Mesh-wide serving & training (ISSUE 6) on the 8-device virtual CPU
mesh: replicated fan-out (per-device lanes through the StagedPipeline)
must answer identically on every lane, row-sharded factor tables
(``shard_model`` over the ``(batch, model)`` serving mesh) must answer
identically to the single-device baseline, and ALS must train to the
same factors over the serving mesh as meshless. ``tests/conftest.py``
forces ``XLA_FLAGS=--xla_force_host_platform_device_count=8``; CI also
runs this module as its own forced-8-device step.
"""

import json
from datetime import datetime, timezone

import numpy as np
import pytest
from conftest import serve_staged_batch

import jax
import jax.numpy as jnp
from jax.sharding import PartitionSpec as P

from predictionio_tpu.controller import Context
from predictionio_tpu.data.bimap import BiMap
from predictionio_tpu.data.storage import App, Storage
from predictionio_tpu.data.storage.base import (
    STATUS_COMPLETED,
    EngineInstance,
)
from predictionio_tpu.models.als import (
    ALSModel,
    ALSParams,
    RatingsCOO,
    _serve_topk,
    pin_user_rows,
    pin_user_rows_lanes,
    recommend_batch,
    recommend_pinned,
    recommend_products,
    replicate_model,
    shard_model,
    train_als,
)
from predictionio_tpu.parallel import (
    BATCH_AXIS,
    MODEL_AXIS,
    make_serving_mesh,
    resolve_serving_mode,
    rows_spec,
)
from predictionio_tpu.server.engineserver import QueryServer, ServerConfig
from predictionio_tpu.templates.recommendation import (
    default_engine_params,
    recommendation_engine,
)

GiB = 1 << 30


class TestMeshPlumbing:
    def test_serving_mesh_axes_and_shape(self):
        mesh = make_serving_mesh()
        assert mesh.axis_names == (BATCH_AXIS, MODEL_AXIS)
        assert mesh.devices.size == len(jax.devices())
        mesh2 = make_serving_mesh(batch=4, model=2)
        assert dict(zip(mesh2.axis_names, mesh2.devices.shape)) == {
            "batch": 4, "model": 2}

    def test_rows_spec_covers_every_axis(self):
        mesh = make_serving_mesh(batch=4, model=2)
        assert rows_spec(mesh) == P(("batch", "model"))
        from predictionio_tpu.parallel import make_mesh

        assert rows_spec(make_mesh(data=2, model=4)) \
            == P(("data", "model"))
        assert rows_spec(None) == P()

    def test_resolve_serving_mode(self):
        # explicit modes pass through; auto sizes against one HBM
        assert resolve_serving_mode("replicated", None, 8) == "replicated"
        assert resolve_serving_mode("sharded", None, 8) == "sharded"
        assert resolve_serving_mode("auto", None, 1) == "single"
        # model fits comfortably → a full copy per device
        assert resolve_serving_mode(
            "auto", 1 * GiB, 8, hbm_limit=16 * GiB) == "replicated"
        # 10M users × rank 256 × f32 ≈ 10.2 GB > 0.6 × 16 GiB → sharded
        big = (10_000_000 + 100_000) * 256 * 4
        assert resolve_serving_mode(
            "auto", big, 8, hbm_limit=16 * GiB) == "sharded"
        with pytest.raises(ValueError):
            resolve_serving_mode("bogus", None, 8)


def _ratings(nu=96, ni=40, nnz=2000, seed=0):
    rng = np.random.default_rng(seed)
    return RatingsCOO(rng.integers(0, nu, nnz).astype(np.int32),
                      rng.integers(0, ni, nnz).astype(np.int32),
                      (rng.random(nnz) * 4 + 1).astype(np.float32),
                      nu, ni)


class TestTrainOverServingMesh:
    """The SAME training code runs over the ``(batch, model)`` serving
    mesh: rows_spec derives the row sharding from the mesh's own axis
    names, and the Gramian all-reduce rides the same mesh."""

    def test_explicit_matches_meshless(self):
        r = _ratings()
        p = ALSParams(rank=8, num_iterations=3, seed=3)
        U0, V0 = train_als(r, p)
        mesh = make_serving_mesh(batch=4, model=2)
        U1, V1 = train_als(r, p, mesh=mesh)
        np.testing.assert_allclose(np.asarray(U0)[:r.n_users],
                                   np.asarray(U1)[:r.n_users],
                                   atol=5e-4)
        np.testing.assert_allclose(np.asarray(V0)[:r.n_items],
                                   np.asarray(V1)[:r.n_items],
                                   atol=5e-4)

    def test_implicit_matches_meshless(self):
        r = _ratings(seed=1)
        p = ALSParams(rank=8, num_iterations=2, implicit_prefs=True,
                      alpha=4.0, seed=3)
        U0, V0 = train_als(r, p)
        U1, V1 = train_als(r, p, mesh=make_serving_mesh())
        np.testing.assert_allclose(np.asarray(U0)[:r.n_users],
                                   np.asarray(U1)[:r.n_users],
                                   atol=5e-4)


def _model(nu=200, ni=101, rank=16, seed=0):
    rng = np.random.default_rng(seed)
    return ALSModel(
        user_factors=rng.standard_normal((nu, rank)).astype(np.float32),
        item_factors=rng.standard_normal((ni, rank)).astype(np.float32),
        n_users=nu, n_items=ni,
        user_ids=BiMap({f"u{i}": i for i in range(nu)}),
        item_ids=BiMap({f"i{i}": i for i in range(ni)}),
        params=ALSParams(rank=rank))


class TestShardedServing:
    def test_shard_model_places_rows_on_every_device(self):
        mesh = make_serving_mesh()
        ms = shard_model(_model(), mesh)
        assert ms.mesh is mesh
        assert len(ms.user_factors.sharding.device_set) == 8
        # rows padded to a device multiple, real counts preserved
        assert ms.item_factors.shape[0] % 8 == 0
        assert ms.n_items == 101

    def test_sharded_predictions_match_single_device(self):
        m = _model()
        mesh = make_serving_mesh(batch=4, model=2)
        ms = shard_model(m, mesh)
        rng = np.random.default_rng(2)
        idx = rng.integers(0, m.n_users, 7)
        want_s, want_i = _serve_topk(
            jnp.asarray(m.user_factors), jnp.asarray(m.item_factors),
            idx, k=10, n_items=m.n_items)
        ids, scores = recommend_batch(ms, idx, 10)
        np.testing.assert_array_equal(ids, np.asarray(want_i))
        np.testing.assert_allclose(scores, np.asarray(want_s),
                                   rtol=1e-5)
        i1, s1 = recommend_products(ms, int(idx[0]), 10)
        np.testing.assert_array_equal(i1, ids[0])

    def test_sharded_k_exceeding_local_shard(self):
        # 104 padded items over 8 devices = 13 per shard; ask for 20
        m = _model(ni=101)
        ms = shard_model(m, make_serving_mesh())
        want_s, want_i = _serve_topk(
            jnp.asarray(m.user_factors), jnp.asarray(m.item_factors),
            np.asarray([3]), k=20, n_items=m.n_items)
        ids, scores = recommend_batch(ms, np.asarray([3]), 20)
        np.testing.assert_array_equal(ids[0], np.asarray(want_i)[0][:20])

    def test_sharded_concurrent_dispatch_is_safe(self):
        # the mesh program's candidate all-gather deadlocks if two host
        # threads interleave their per-device launches — the dispatch
        # lock serializes them; this must finish, and identically
        import threading

        m = _model()
        ms = shard_model(m, make_serving_mesh())
        want, _ = recommend_batch(ms, np.asarray([1, 2, 3]), 5)
        results = [None] * 8
        def fire(i):
            results[i] = recommend_batch(ms, np.asarray([1, 2, 3]), 5)[0]
        threads = [threading.Thread(target=fire, args=(i,))
                   for i in range(8)]
        for t in threads:
            t.start()
        for t in threads:
            t.join()
        for got in results:
            np.testing.assert_array_equal(got, want)

    def test_sharded_pinned_hot_rows(self):
        m = _model()
        ms = shard_model(m, make_serving_mesh())
        pinned, nbytes = pin_user_rows(ms, [5, 9], 4)
        assert pinned is not None and nbytes > 0
        want_i, _ = recommend_products(ms, 9, 10)
        ids, _ = recommend_pinned(ms, pinned, 1, 10)
        np.testing.assert_array_equal(ids, want_i)


class TestReplicatedLanes:
    def test_replicate_model_commits_to_device(self):
        m = _model()
        dev = jax.devices()[3]
        mr = replicate_model(m, dev)
        assert list(mr.user_factors.devices()) == [dev]
        assert mr.mesh is None

    def test_lane_pinned_tables_follow_lane_model_device(self):
        # per-device pinned shards: whichever lane's model serves the
        # hot query, the pinned copy on ITS device is used — fully
        # lane-local, and identical answers on every lane
        m0 = _model()
        devs = jax.devices()[:4]
        lane_models = [replicate_model(m0, d) for d in devs]
        tables, nbytes = pin_user_rows_lanes(lane_models[0], [5, 9], 4,
                                             devs)
        assert tables is not None and len(tables) == 4
        assert nbytes > 0
        want_i, _ = recommend_products(lane_models[0], 5, 10)
        for lm, dev, table in zip(lane_models, devs, tables):
            ids, _ = recommend_pinned(lm, tables, 0, 10)
            np.testing.assert_array_equal(ids, want_i)
            assert list(table.devices()) == [dev]


def _mk_server(cfg: ServerConfig, model: ALSModel) -> QueryServer:
    storage = Storage(env={"PIO_STORAGE_SOURCES_MEM_TYPE": "memory"})
    storage.apps().insert(App(0, "meshtest"))
    ctx = Context(app_name="meshtest", _storage=storage)
    engine = recommendation_engine()
    ep = default_engine_params("meshtest", rank=model.params.rank)
    now = datetime.now(timezone.utc)
    inst = EngineInstance(
        id="mesh-inst", status=STATUS_COMPLETED, start_time=now,
        end_time=now, engine_id="meshtest", engine_version="1",
        engine_variant="engine.json", engine_factory="synthetic")
    return QueryServer(ctx, engine, ep, [model], inst, cfg)


class TestQueryServerMeshModes:
    """The engine-server integration: mode resolution at bind,
    per-device lane fan-out through the StagedPipeline, the sharded
    binding serving /queries.json-shaped queries, and the status
    surfaces."""

    def test_replicated_lanes_answer_identically(self):
        model = _model(nu=300, ni=150)
        want = _mk_server(ServerConfig(warm_start=False),
                          model).query({"user": "u7", "num": 5})
        qs = _mk_server(
            ServerConfig(warm_start=False, serving_mode="replicated",
                         batching=True, max_batch=8), model)
        assert qs.serving_mode_resolved == "replicated"
        assert len(qs.lane_models) == 8
        assert qs.batcher is not None and qs.batcher.lanes == 8
        # batch after batch until every lane's dispatcher has had one
        by_lane = {}
        for _ in range(64):
            out, lane = serve_staged_batch(qs, [{"user": "u7", "num": 5}])
            by_lane.setdefault(lane, out[0])
            if len(by_lane) == 8:
                break
        assert sorted(by_lane) == list(range(8))
        outs = list(by_lane.values())
        assert all(o == outs[0] for o in outs)
        assert [s["item"] for s in outs[0]["itemScores"]] \
            == [s["item"] for s in want["itemScores"]]
        # the serve() entry (what /queries.json calls) rides the lanes
        r = qs.serve({"user": "u7", "num": 5})
        assert [s["item"] for s in r["itemScores"]] \
            == [s["item"] for s in want["itemScores"]]

    def test_replicated_mesh_status_and_metrics(self):
        qs = _mk_server(
            ServerConfig(warm_start=False, serving_mode="replicated",
                         batching=True, max_batch=8),
            _model(nu=300, ni=150))
        for _ in range(64):  # until lane 0's dispatcher has had a batch
            if serve_staged_batch(qs, [{"user": "u1", "num": 3}])[1] == 0:
                break
        mesh = qs.mesh_status()
        assert mesh["mode"] == "replicated"
        assert mesh["devices"] == 8
        assert len(mesh["lanes"]) == 8
        assert mesh["lanes"][0]["dispatches"] >= 1
        assert {lane["deviceId"] for lane in mesh["lanes"]} \
            == {d.id for d in jax.devices()}
        # the per-lane series land in the exposition
        text = qs.metrics.render()
        assert "pio_lane_dispatches_total" in text
        assert "pio_serving_lanes" in text

    def test_sharded_server_matches_single(self):
        model = _model(nu=300, ni=150)
        want = _mk_server(ServerConfig(warm_start=False),
                          model).query({"user": "u7", "num": 5})
        qs = _mk_server(
            ServerConfig(warm_start=False, serving_mode="sharded"),
            model)
        assert qs.serving_mode_resolved == "sharded"
        assert qs.serving_mesh is not None
        got = qs.query({"user": "u7", "num": 5})
        assert [s["item"] for s in got["itemScores"]] \
            == [s["item"] for s in want["itemScores"]]
        mesh = qs.mesh_status()
        assert mesh["mode"] == "sharded"
        assert mesh["meshShape"] == {"batch": 8, "model": 1}

    def test_auto_resolves_replicated_on_unsized_backend(self):
        # CPU reports no HBM limit: auto must stay conservative —
        # fan-out, never auto-shard on unknown sizing
        qs = _mk_server(
            ServerConfig(warm_start=False, serving_mode="auto"),
            _model())
        assert qs.serving_mode_resolved == "replicated"

    def test_single_mode_is_unchanged(self):
        qs = _mk_server(ServerConfig(warm_start=False), _model())
        assert qs.serving_mode_resolved == "single"
        assert qs.lane_models == [] and qs.batcher is None
        assert qs.mesh_status() == {"mode": "single"}

    def test_sharded_end_to_end_train_deploy_query(self):
        """The acceptance path at test scale: ALS trains row-sharded
        over the serving mesh, the model deploys sharded, and
        /queries.json-shaped queries answer identically to a
        single-device deployment of the same factors."""
        r = _ratings(nu=120, ni=60, nnz=3000, seed=5)
        p = ALSParams(rank=8, num_iterations=2, seed=3)
        mesh = make_serving_mesh()
        U, V = train_als(r, p, mesh=mesh)
        model = ALSModel(
            user_factors=np.asarray(U)[:r.n_users],
            item_factors=np.asarray(V)[:r.n_items],
            n_users=r.n_users, n_items=r.n_items,
            user_ids=BiMap({f"u{i}": i for i in range(r.n_users)}),
            item_ids=BiMap({f"i{i}": i for i in range(r.n_items)}),
            params=p)
        want = _mk_server(ServerConfig(warm_start=False),
                          model).query({"user": "u11", "num": 4})
        qs = _mk_server(
            ServerConfig(warm_start=False, serving_mode="sharded"),
            model)
        got = qs.query({"user": "u11", "num": 4})
        assert [s["item"] for s in got["itemScores"]] \
            == [s["item"] for s in want["itemScores"]]
        status_mesh = json.loads(json.dumps(qs.mesh_status()))
        assert status_mesh["mode"] == "sharded"
