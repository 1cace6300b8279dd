"""Row-quantized serving factor tables (ISSUE 13): quantize/dequant
accuracy, the ≥~4x users-per-HBM sizing claim, the NDCG@10 parity gate
(the tier-1 half of the CI quality gate — a trained fixture model must
rank within tolerance of f32 under int8/bf16, and a pathological model
must trip the auto-off fallback), streaming hot-swap re-quantization,
the hot tier's quantized pinned table, server-side bind wiring +
``pio_serving_kernel`` gauge, and the conditional hot-tier refresh
fix."""

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
    RatingsCOO,
    SERVING_QUANT_NDCG_FLOOR,
    apply_row_updates,
    extend_factor_rows,
    quantize_serving_model,
    recommend_batch,
    serving_quant_ndcg,
    serving_quant_of,
    table_host_f32,
    train_als,
)


def synth_model(nu=200, ni=160, r=16, seed=0, device=False):
    rng = np.random.default_rng(seed)
    U = rng.standard_normal((nu, r)).astype(np.float32)
    V = rng.standard_normal((ni, r)).astype(np.float32)
    if device:
        U, V = jax.device_put(U), jax.device_put(V)
    return ALSModel(
        user_factors=U, item_factors=V, n_users=nu, n_items=ni,
        user_ids=BiMap({f"u{i}": i for i in range(nu)}),
        item_ids=BiMap({f"i{i}": i for i in range(ni)}),
        params=ALSParams(rank=r))


def trained_fixture(rank=8, seed=3):
    """A small TRAINED model (structured factors, not noise) — the
    fixture the NDCG parity gate runs on."""
    rng = np.random.default_rng(seed)
    nu, ni, nnz = 80, 60, 1200
    coo = RatingsCOO(rng.integers(0, nu, nnz).astype(np.int32),
                     rng.integers(0, ni, nnz).astype(np.int32),
                     (rng.random(nnz).astype(np.float32) * 4 + 1),
                     nu, ni)
    U, V = train_als(coo, ALSParams(rank=rank, num_iterations=4,
                                    seed=seed))
    return ALSModel(
        user_factors=np.asarray(U), item_factors=np.asarray(V),
        n_users=nu, n_items=ni,
        user_ids=BiMap({f"u{i}": i for i in range(nu)}),
        item_ids=BiMap({f"i{i}": i for i in range(ni)}),
        params=ALSParams(rank=rank))


class TestQuantizeRoundtrip:
    def test_int8_per_row_error_bound(self):
        rng = np.random.default_rng(0)
        # rows at wildly different magnitudes: per-ROW scales must
        # hold relative error on every row, which one global scale
        # cannot
        rows = rng.standard_normal((32, 24)).astype(np.float32)
        rows *= (10.0 ** rng.integers(-3, 3, (32, 1)))
        data, scale = als._quantize_rows(rows, "int8")
        back = data.astype(np.float32) * scale
        rel = np.abs(back - rows).max(axis=1) \
            / np.abs(rows).max(axis=1)
        assert rel.max() < 1 / 127 + 1e-6

    def test_bf16_has_no_scale(self):
        rows = np.random.default_rng(1).standard_normal(
            (8, 16)).astype(np.float32)
        data, scale = als._quantize_rows(rows, "bf16")
        assert scale is None
        np.testing.assert_allclose(
            np.asarray(data, dtype=np.float32), rows, rtol=1e-2)

    def test_capacity_claim(self):
        """The HBM sizing math (docs/sharded-serving.md): int8 shrinks
        the factor bytes 4x; with the per-row f32 scale the per-user
        bytes are r+4 vs 4r — ≥3.7x more users per HBM at rank 64 and
        asymptotically 4x."""
        m = synth_model(nu=1000, ni=100, r=64)
        q = quantize_serving_model(m, "int8", parity_sample=0)
        f32_user_bytes = m.user_factors.nbytes
        q_user_bytes = q.user_factors.nbytes
        ratio = f32_user_bytes / q_user_bytes
        assert ratio == pytest.approx(4 * 64 / (64 + 4), rel=1e-6)
        assert ratio > 3.7
        b = quantize_serving_model(m, "bf16", parity_sample=0)
        assert m.user_factors.nbytes / b.user_factors.nbytes == 2.0

    def test_off_and_idempotent(self):
        m = synth_model()
        assert quantize_serving_model(m, "off") is m
        q = quantize_serving_model(m, "int8")
        assert quantize_serving_model(q, "int8") is q
        with pytest.raises(ValueError, match="quant"):
            quantize_serving_model(m, "fp4")


class TestNDCGParityGate:
    """The CI quality gate: quantized ranking vs f32 ranking on a
    TRAINED fixture must clear the same floor the deploy-time auto-off
    probe enforces — `--serving-quant` can never silently degrade
    ranking past it."""

    @pytest.mark.parametrize("quant,floor", [("int8", 0.97),
                                             ("bf16", 0.99)])
    def test_trained_fixture_parity(self, quant, floor):
        m = trained_fixture()
        q = quantize_serving_model(m, quant, parity_sample=0)
        ndcg = serving_quant_ndcg(
            table_host_f32(m.user_factors),
            table_host_f32(m.item_factors),
            q.user_factors, q.item_factors, m.n_items, k=10,
            sample=64)
        assert ndcg >= floor, \
            f"{quant} NDCG@10 {ndcg:.4f} below the {floor} gate"

    def test_auto_off_on_pathological_model(self):
        """Items nearly identical within int8 resolution: quantization
        destroys the ranking, the probe must refuse and keep f32."""
        rng = np.random.default_rng(5)
        nu, ni, r = 60, 50, 8
        U = rng.standard_normal((nu, r)).astype(np.float32)
        v0 = rng.standard_normal(r).astype(np.float32)
        V = (v0[None, :]
             + 1e-5 * rng.standard_normal((ni, r))).astype(np.float32)
        m = ALSModel(
            user_factors=U, item_factors=V, n_users=nu, n_items=ni,
            user_ids=BiMap({f"u{i}": i for i in range(nu)}),
            item_ids=BiMap({f"i{i}": i for i in range(ni)}),
            params=ALSParams(rank=r))
        q = quantize_serving_model(m, "int8")
        assert not isinstance(q.user_factors, QuantizedFactors)
        assert serving_quant_of(q) == "off"
        # the healthy fixture passes the same probe
        ok = quantize_serving_model(trained_fixture(), "int8")
        assert serving_quant_of(ok) == "int8"

    def test_floor_constant_sane(self):
        assert 0.9 <= SERVING_QUANT_NDCG_FLOOR < 1.0


class TestServingParity:
    def test_int8_ranking_close_to_f32(self):
        m = trained_fixture()
        ids_f, _ = recommend_batch(
            als.ensure_device_resident(m), np.arange(30), 10)
        q = als.ensure_device_resident(
            quantize_serving_model(m, "int8", parity_sample=0))
        ids_q, _ = recommend_batch(q, np.arange(30), 10)
        overlap = np.mean([len(set(a) & set(b)) / 10
                           for a, b in zip(ids_f, ids_q)])
        assert overlap >= 0.9

    def test_host_fast_path_untouched(self):
        """A small f32 model keeps the host numpy fast path; the quant
        knob moves serving to the device only when asked."""
        m = synth_model()
        assert als._serve_on_host(m, 1)
        q = quantize_serving_model(m, "int8", parity_sample=0)
        assert not als._serve_on_host(q, 1)


class TestStreamingHotSwap:
    def test_apply_row_updates_requantizes(self):
        m = quantize_serving_model(synth_model(device=True), "int8",
                                   parity_sample=0)
        rng = np.random.default_rng(2)
        rows = rng.standard_normal((4, 16)).astype(np.float32)
        idx = np.array([0, 3, 9, 11])
        m2 = apply_row_updates(m, "user", idx, rows)
        assert isinstance(m2.user_factors, QuantizedFactors)
        got = table_host_f32(m2.user_factors)[idx]
        rel = np.abs(got - rows).max() / np.abs(rows).max()
        assert rel < 0.02  # int8 quantization error, nothing more
        # untouched rows bit-identical (functional update)
        before = table_host_f32(m.user_factors)
        after = table_host_f32(m2.user_factors)
        keep = np.setdiff1d(np.arange(m.n_users), idx)
        np.testing.assert_array_equal(after[keep], before[keep])

    def test_extend_factor_rows_quantized(self):
        m = quantize_serving_model(synth_model(device=True), "int8",
                                   parity_sample=0)
        rows = np.random.default_rng(3).standard_normal(
            (2, 16)).astype(np.float32)
        m2 = extend_factor_rows(m, "user", ["new-a", "new-b"], rows)
        assert m2.n_users == m.n_users + 2
        assert isinstance(m2.user_factors, QuantizedFactors)
        got = table_host_f32(m2.user_factors)[m.n_users:m.n_users + 2]
        assert np.abs(got - rows).max() / np.abs(rows).max() < 0.02

    def test_fold_in_rows_against_quant_table(self):
        """fold_in_rows dequantizes the fixed side: solving against a
        quantized serving table lands near the f32 solve."""
        m = trained_fixture()
        q = quantize_serving_model(m, "int8", parity_sample=0)
        idx = np.array([[1, 2, 3, 0]], dtype=np.int32)
        val = np.array([[4.0, 3.0, 5.0, 0.0]], dtype=np.float32)
        cnt = np.array([3], dtype=np.int32)
        r_f = als.fold_in_rows(m.item_factors, idx, val, cnt, m.params)
        r_q = als.fold_in_rows(q.item_factors, idx, val, cnt, m.params)
        np.testing.assert_allclose(r_q, r_f, rtol=0.1, atol=0.05)


def _boot_server(cfg, model=None, rank=16):
    from predictionio_tpu.controller import Context
    from predictionio_tpu.data.storage import App, Storage
    from predictionio_tpu.data.storage.base import (
        STATUS_COMPLETED,
        EngineInstance,
    )
    from predictionio_tpu.server.engineserver import QueryServer
    from predictionio_tpu.templates.recommendation import (
        default_engine_params,
        recommendation_engine,
    )

    model = model or synth_model(r=rank)
    storage = Storage(env={"PIO_STORAGE_SOURCES_MEM_TYPE": "memory"})
    storage.apps().insert(App(0, "sq"))
    ctx = Context(app_name="sq", _storage=storage)
    now = datetime.now(timezone.utc)
    inst = EngineInstance(
        id="sq", status=STATUS_COMPLETED, start_time=now, end_time=now,
        engine_id="sq", engine_version="1", engine_variant="e.json",
        engine_factory="s")
    return QueryServer(ctx, recommendation_engine(),
                       default_engine_params("sq", rank=rank),
                       [model], inst, cfg)


class TestServerWiring:
    def test_bind_quantizes_and_records_gauge(self):
        from predictionio_tpu.server.engineserver import ServerConfig

        qs = _boot_server(ServerConfig(warm_start=False,
                                       serving_quant="int8"))
        assert isinstance(qs.models[0].user_factors, QuantizedFactors)
        st = qs.serving_kernel_status()
        assert st == {"quant": "int8", "configuredQuant": "int8"}
        fam = qs.metrics.gauge("pio_serving_kernel")
        active = {tuple(sorted(dict(items).items())): c.value
                  for items, c in fam.children()}
        assert active == {(("quant", "int8"),): 1.0}
        # queries still answer on the quantized binding
        out = qs.query({"user": "u3", "num": 5})
        assert len(out["itemScores"]) == 5

    def test_bad_config_fails_deploy(self):
        from predictionio_tpu.server.engineserver import ServerConfig

        with pytest.raises(ValueError, match="serving_quant"):
            _boot_server(ServerConfig(warm_start=False,
                                      serving_quant="fp8"))

    def test_off_default_serves_f32(self):
        from predictionio_tpu.server.engineserver import ServerConfig

        qs = _boot_server(ServerConfig(warm_start=False))
        assert not isinstance(qs.models[0].user_factors,
                              QuantizedFactors)
        assert qs.serving_kernel_status()["quant"] == "off"


class TestConditionalHotRefresh:
    """Satellite fix: a stream hot-swap that touches NO pinned entity
    must not re-warm the pinned table (the unconditional refresh paid
    a full re-pin + k-ladder warm per fold-in)."""

    def _server_with_hot(self):
        from predictionio_tpu.server.engineserver import ServerConfig

        model = synth_model(nu=2000, ni=2000, r=32, device=True)
        qs = _boot_server(
            ServerConfig(warm_start=False, serving_cache=True,
                         hot_entities=8, hot_refresh_every=4),
            model=model, rank=32)
        return qs

    def test_untouched_swap_skips_refresh(self):
        qs = self._server_with_hot()
        hot = qs.cache.hot
        # pin u1 by hand (deterministic, no background thread timing)
        for _ in range(3):
            hot.record("u1")
        hot.refresh(wait=True)
        assert hot.lookup("u1") is not None
        refreshes_before = hot.stats()["refreshes"]
        with qs._lock:
            base_id = qs.instance.id
        m2 = apply_row_updates(
            qs.models[0], "user", np.array([500]),
            np.random.default_rng(0).standard_normal(
                (1, 32)).astype(np.float32))
        assert qs.apply_stream_delta(0, m2, ["u500"], base_id,
                                     rows_updated=1)
        # u500 was never pinned: no refresh scheduled
        assert hot.stats()["refreshes"] == refreshes_before
        assert hot.lookup("u1") is not None  # pin survives

    def test_touched_swap_refreshes(self):
        import time

        qs = self._server_with_hot()
        hot = qs.cache.hot
        for _ in range(3):
            hot.record("u1")
        hot.refresh(wait=True)
        refreshes_before = hot.stats()["refreshes"]
        with qs._lock:
            base_id = qs.instance.id
        m2 = apply_row_updates(
            qs.models[0], "user", np.array([1]),
            np.random.default_rng(1).standard_normal(
                (1, 32)).astype(np.float32))
        assert qs.apply_stream_delta(0, m2, ["u1"], base_id,
                                     rows_updated=1)
        deadline = time.monotonic() + 30
        while time.monotonic() < deadline:
            if hot.stats()["refreshes"] > refreshes_before:
                break
            time.sleep(0.05)
        assert hot.stats()["refreshes"] > refreshes_before
