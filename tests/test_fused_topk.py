"""Fused gather→score→top-k Pallas serving kernel (ISSUE 13), run in
interpret mode on CPU so tier-1 covers it without a TPU: exactness
against the ``_serve_topk`` einsum reference on f32 and tolerance on
the bf16/int8 quantized wires, ragged B tails and non-chunk-multiple
catalogs, the global-id ``base`` contract the sharded ranker relies
on, routing parity through every serving mode (single / replicated
lanes / sharded on the 8-device CPU mesh), the staged pipeline end to
end, and the autotune table's support-gated einsum fallback."""

import json

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
    resolved_topk_mode,
    set_serving_topk_mode,
)
from predictionio_tpu.ops._probe import NO_TPU
from predictionio_tpu.ops.fused_topk import (
    TOPK_MAX_K,
    fused_topk,
    fused_topk_dispatch,
    fused_topk_reference,
    fused_topk_refusal,
    fused_topk_vmem_bytes,
)


@pytest.fixture(autouse=True)
def _reset_topk_mode():
    yield
    set_serving_topk_mode(None)


def make_tables(m=120, I=200, r=16, seed=0):
    rng = np.random.default_rng(seed)
    U = rng.normal(size=(m, r)).astype(np.float32)
    V = rng.normal(size=(I, r)).astype(np.float32)
    return U, V


def quantize(arr):
    amax = np.abs(arr).max(axis=1, keepdims=True)
    scale = np.maximum(amax, 1e-12).astype(np.float32) / 127.0
    data = np.clip(np.rint(arr / scale), -127, 127).astype(np.int8)
    return data, scale


class TestKernelInterpret:
    def test_f32_exact_vs_serve_topk(self):
        """f32 kernel vs the einsum serving program — ids EXACT, same
        tie semantics (descending score, lowest id first)."""
        U, V = make_tables()
        idx = np.random.default_rng(1).integers(0, U.shape[0], 24)
        s, i = fused_topk(jnp.asarray(U), jnp.asarray(idx.astype(np.int32)),
                          jnp.asarray(V), k=10, n_items=V.shape[0],
                          chunk=64, interpret=True)
        s_ref, i_ref = als._serve_topk(jnp.asarray(U), jnp.asarray(V),
                                       idx, k=10, n_items=V.shape[0])
        assert np.array_equal(np.asarray(i), np.asarray(i_ref))
        np.testing.assert_allclose(np.asarray(s), np.asarray(s_ref),
                                   rtol=1e-6, atol=1e-6)

    @pytest.mark.parametrize("B,I,k", [(1, 33, 8), (13, 97, 10),
                                       (7, 512, 16), (19, 130, 1)])
    def test_ragged_tails(self, B, I, k):
        """B not a block multiple, catalog not a chunk multiple: the
        internal pad-and-slice must be invisible."""
        U, V = make_tables(I=I, seed=B * 31 + I)
        idx = np.random.default_rng(B).integers(
            0, U.shape[0], B).astype(np.int32)
        s, i = fused_topk(jnp.asarray(U), jnp.asarray(idx),
                          jnp.asarray(V), k=k, n_items=I, chunk=32,
                          interpret=True)
        s_ref, i_ref = fused_topk_reference(
            jnp.asarray(U), jnp.asarray(idx), jnp.asarray(V),
            k=k, n_items=I)
        assert s.shape == (B, k) and i.shape == (B, k)
        assert np.array_equal(np.asarray(i), np.asarray(i_ref))
        np.testing.assert_allclose(np.asarray(s), np.asarray(s_ref),
                                   rtol=1e-6, atol=1e-6)

    def test_padded_items_masked(self):
        """n_items below the padded catalog: padding rows never appear
        in the result (the -inf mask parity with _serve_topk)."""
        U, V = make_tables(I=140)
        idx = np.arange(8, dtype=np.int32)
        s, i = fused_topk(jnp.asarray(U), jnp.asarray(idx),
                          jnp.asarray(V), k=12, n_items=100, chunk=64,
                          interpret=True)
        assert np.asarray(i).max() < 100

    def test_int8_wire_matches_dequant_reference(self):
        """int8 rows + per-row scales on the wire: must match the
        dequantized reference tightly — the f32-accumulation
        contract, not a new quality budget."""
        U, V = make_tables(seed=3)
        Uq, us = quantize(U)
        Vq, vs = quantize(V)
        idx = np.random.default_rng(3).integers(
            0, U.shape[0], 15).astype(np.int32)
        s, i = fused_topk(jnp.asarray(Uq), jnp.asarray(idx),
                          jnp.asarray(Vq), jnp.asarray(us),
                          jnp.asarray(vs), k=10, n_items=V.shape[0],
                          chunk=64, interpret=True)
        s_ref, i_ref = fused_topk_reference(
            jnp.asarray(Uq), jnp.asarray(idx), jnp.asarray(Vq),
            jnp.asarray(us), jnp.asarray(vs), k=10,
            n_items=V.shape[0])
        assert np.array_equal(np.asarray(i), np.asarray(i_ref))
        np.testing.assert_allclose(np.asarray(s), np.asarray(s_ref),
                                   rtol=1e-5, atol=1e-5)
        # and against the f32 truth only quantization error
        _, i_f32 = fused_topk_reference(
            jnp.asarray(U), jnp.asarray(idx), jnp.asarray(V),
            k=10, n_items=V.shape[0])
        overlap = np.mean([len(set(a.tolist()) & set(b.tolist())) / 10
                           for a, b in zip(np.asarray(i),
                                           np.asarray(i_f32))])
        assert overlap >= 0.8

    def test_bf16_wire(self):
        U, V = make_tables(seed=4)
        idx = np.arange(9, dtype=np.int32)
        U16 = jnp.asarray(U).astype(jnp.bfloat16)
        V16 = jnp.asarray(V).astype(jnp.bfloat16)
        s, i = fused_topk(U16, jnp.asarray(idx), V16, k=10,
                          n_items=V.shape[0], chunk=64, interpret=True)
        s_ref, i_ref = fused_topk_reference(U16, jnp.asarray(idx), V16,
                                            k=10, n_items=V.shape[0])
        assert np.array_equal(np.asarray(i), np.asarray(i_ref))

    def test_base_offsets_global_ids(self):
        """The sharded ranker's contract: ids come back offset by
        ``base`` and the n_items mask applies to GLOBAL ids."""
        U, V = make_tables(I=64)
        idx = np.arange(4, dtype=np.int32)
        s, i = fused_topk(jnp.asarray(U), jnp.asarray(idx),
                          jnp.asarray(V), base=jnp.asarray(1000),
                          k=8, n_items=1060, chunk=32, interpret=True)
        arr = np.asarray(i)
        assert arr.min() >= 1000
        assert arr.max() < 1060  # global ids 1060..1063 are masked

    def test_dispatch_runs_kernel_on_cpu(self):
        """No TPU attached → dispatch runs the interpret-mode kernel
        (the debugging contract), not the reference fallback."""
        assert fused_topk_refusal(5, 16, 64, 8) \
            == NO_TPU  # CPU host
        U, V = make_tables()
        idx = np.arange(5, dtype=np.int32)
        s, i = fused_topk_dispatch(jnp.asarray(U), jnp.asarray(idx),
                                   jnp.asarray(V), k=8,
                                   n_items=V.shape[0])
        _, i_ref = fused_topk_reference(jnp.asarray(U),
                                        jnp.asarray(idx),
                                        jnp.asarray(V), k=8,
                                        n_items=V.shape[0])
        assert np.array_equal(np.asarray(i), np.asarray(i_ref))

    def test_vmem_budget_math(self):
        # the chunked sweep caps the working set however large the
        # catalog grows; quantized wires shrink the dominant term
        assert fused_topk_vmem_bytes(128, 128, wire_bytes=1) \
            < fused_topk_vmem_bytes(128, 128, wire_bytes=4)
        # r=128 f32 double-buffered item tile alone is 512 KiB
        assert fused_topk_vmem_bytes(128, 16) > 2 * 512 * 128 * 4
        # the trace-time assert mirrors this bound
        assert fused_topk_vmem_bytes(128, TOPK_MAX_K) \
            < 16 * 1024 * 1024

    def test_k_over_budget_rejected(self):
        U, V = make_tables()
        with pytest.raises(AssertionError, match="fused_topk"):
            fused_topk(jnp.asarray(U),
                       jnp.asarray(np.arange(4, dtype=np.int32)),
                       jnp.asarray(V), k=TOPK_MAX_K * 2,
                       n_items=V.shape[0], interpret=True)


class TestServingRoutes:
    """`_device_topk` routing: with the process override pinned to
    "fused", every serving entry answers identically to the einsum
    lane — the switch must be invisible."""

    def _model(self, quant=None, r=16, nu=150, ni=180, seed=0):
        U, V = make_tables(m=nu, I=ni, r=r, seed=seed)
        m = ALSModel(
            user_factors=jax.device_put(U),
            item_factors=jax.device_put(V), n_users=nu, n_items=ni,
            user_ids=BiMap({f"u{i}": i for i in range(nu)}),
            item_ids=BiMap({f"i{i}": i for i in range(ni)}),
            params=ALSParams(rank=r))
        if quant:
            m = quantize_serving_model(m, quant)
        return m

    @pytest.mark.parametrize("quant", [None, "int8", "bf16"])
    def test_recommend_batch_parity(self, quant):
        m = self._model(quant)
        set_serving_topk_mode("einsum")
        ids_e, s_e = recommend_batch(m, np.arange(20), 10)
        set_serving_topk_mode("fused")
        ids_f, s_f = recommend_batch(m, np.arange(20), 10)
        assert np.array_equal(ids_e, ids_f)
        np.testing.assert_allclose(s_e, s_f, rtol=1e-5, atol=1e-5)

    def test_recommend_products_and_pinned_parity(self):
        m = self._model("int8")
        set_serving_topk_mode("fused")
        ids_1, _ = recommend_products(m, 7, 10)
        pinned, nbytes = als.pin_user_rows(m, [7], 1)
        assert isinstance(pinned, QuantizedFactors)  # hot tier stays
        assert nbytes > 0                            # quantized
        ids_2, _ = recommend_pinned(m, pinned, 0, 10)
        set_serving_topk_mode("einsum")
        ids_3, _ = recommend_products(m, 7, 10)
        assert np.array_equal(ids_1, ids_2)
        assert np.array_equal(ids_1, ids_3)

    def test_large_k_falls_back_to_einsum(self):
        """k past the on-chip merge budget must route to einsum, not
        assert inside the kernel."""
        m = self._model(ni=600)
        set_serving_topk_mode("fused")
        ids, scores = recommend_batch(m, np.arange(4), 400)
        set_serving_topk_mode("einsum")
        ids_e, _ = recommend_batch(m, np.arange(4), 400)
        assert np.array_equal(ids, ids_e)


@pytest.mark.skipif(len(jax.devices()) < 8,
                    reason="needs the forced-8-device CPU mesh")
class TestShardedParity:
    """The sharded collective ranking picks up the kernel per shard
    (base-offset local top-k) and answers identically."""

    def _model(self, quant=None):
        rng = np.random.default_rng(7)
        nu, ni, r = 64, 56, 8
        U = rng.normal(size=(nu, r)).astype(np.float32)
        V = rng.normal(size=(ni, r)).astype(np.float32)
        m = ALSModel(
            user_factors=U, item_factors=V, n_users=nu, n_items=ni,
            user_ids=BiMap({f"u{i}": i for i in range(nu)}),
            item_ids=BiMap({f"i{i}": i for i in range(ni)}),
            params=ALSParams(rank=r))
        if quant:
            m = quantize_serving_model(m, quant)
        return m

    @pytest.mark.parametrize("quant", [None, "int8"])
    def test_sharded_fused_matches_single_einsum(self, quant):
        from predictionio_tpu.models.als import shard_model
        from predictionio_tpu.parallel.mesh import make_serving_mesh

        m = self._model(quant)
        set_serving_topk_mode("einsum")
        ids_e, _ = recommend_batch(m, np.arange(12), 10)
        sm = shard_model(m, make_serving_mesh())
        set_serving_topk_mode("fused")
        ids_f, _ = recommend_batch(sm, np.arange(12), 10)
        assert np.array_equal(ids_e, ids_f)

    def test_pinned_sharded_fused(self):
        from predictionio_tpu.models.als import (
            pin_user_rows,
            shard_model,
        )
        from predictionio_tpu.parallel.mesh import make_serving_mesh

        m = self._model("int8")
        sm = shard_model(m, make_serving_mesh())
        set_serving_topk_mode("fused")
        pinned, _ = pin_user_rows(sm, [3, 5], 2)
        ids_p, _ = recommend_pinned(sm, pinned, 1, 10)
        set_serving_topk_mode("einsum")
        ids_e, _ = recommend_products(sm, 5, 10)
        assert np.array_equal(ids_p, ids_e)


class TestStagedPipelineEndToEnd:
    """serving_quant=int8 + serving_topk=fused through the REAL staged
    pipeline (QueryServer + batcher) answers exactly like the einsum
    lane on the same quantized tables — acceptance criterion."""

    def _boot(self, topk):
        from datetime import datetime, timezone

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

        rng = np.random.default_rng(11)
        nu, ni, r = 200, 160, 16
        model = ALSModel(
            user_factors=rng.standard_normal((nu, r)).astype(np.float32),
            item_factors=rng.standard_normal((ni, r)).astype(np.float32),
            n_users=nu, n_items=ni,
            user_ids=BiMap({f"u{i}": i for i in range(nu)}),
            item_ids=BiMap({f"i{i}": i for i in range(ni)}),
            params=ALSParams(rank=r))
        storage = Storage(env={"PIO_STORAGE_SOURCES_MEM_TYPE": "memory"})
        storage.apps().insert(App(0, "ft"))
        ctx = Context(app_name="ft", _storage=storage)
        now = datetime.now(timezone.utc)
        inst = EngineInstance(
            id="ft", status=STATUS_COMPLETED, start_time=now,
            end_time=now, engine_id="ft", engine_version="1",
            engine_variant="e.json", engine_factory="s")
        cfg = ServerConfig(batching=True, serving_pipeline="staged",
                           warm_start=False, serving_quant="int8",
                           serving_topk=topk)
        return QueryServer(ctx, recommendation_engine(),
                           default_engine_params("ft", rank=r),
                           [model], inst, cfg)

    def test_fused_pipeline_matches_einsum(self):
        import concurrent.futures as cf

        try:
            qs_f = self._boot("fused")
            answers_f = {}
            with cf.ThreadPoolExecutor(8) as pool:
                futs = {u: pool.submit(qs_f.serve,
                                       {"user": f"u{u}", "num": 10})
                        for u in range(24)}
                for u, f in futs.items():
                    answers_f[u] = f.result(timeout=120)
            qs_e = self._boot("einsum")
            for u in range(24):
                expect = qs_e.serve({"user": f"u{u}", "num": 10})
                got = answers_f[u]
                assert [s["item"] for s in got["itemScores"]] \
                    == [s["item"] for s in expect["itemScores"]]
        finally:
            set_serving_topk_mode(None)


class TestTopkAutotune:
    """Satellite: the gram_autotune-style serving top-k mode table — a
    pure lookup like best_mode; ``auto`` skips a named kernel that
    cannot run at the dispatch shapes."""

    def test_fused_entry_falls_back_on_cpu(self, tmp_path, monkeypatch):
        from predictionio_tpu.ops import gram_autotune as ga

        cache = tmp_path / "gram_autotune.json"
        cache.write_text(json.dumps(
            {"cpu|topk|r64|f32": {"mode": "fused", "source": "test"}}))
        monkeypatch.setenv("PIO_GRAM_AUTOTUNE_CACHE", str(cache))
        ga.reset_for_tests()
        try:
            assert ga.best_topk_mode(64, device_kind="cpu") == "fused"
            assert resolved_topk_mode(64, "off", batch=8, n_rows=100,
                                      k=8) == "einsum"  # no TPU here
        finally:
            ga.reset_for_tests()

    def test_einsum_entry_honored(self, tmp_path, monkeypatch):
        from predictionio_tpu.ops import gram_autotune as ga

        cache = tmp_path / "gram_autotune.json"
        cache.write_text(json.dumps(
            {"TPU v5 lite|topk|r64|int8": {"mode": "einsum",
                                           "source": "test"}}))
        monkeypatch.setenv("PIO_GRAM_AUTOTUNE_CACHE", str(cache))
        ga.reset_for_tests()
        try:
            assert ga.best_topk_mode(
                64, "int8", device_kind="TPU v5 lite0") == "einsum"
        finally:
            ga.reset_for_tests()

    def test_recordable(self, tmp_path, monkeypatch):
        from predictionio_tpu.ops import gram_autotune as ga

        cache = tmp_path / "gram_autotune.json"
        monkeypatch.setenv("PIO_GRAM_AUTOTUNE_CACHE", str(cache))
        ga.reset_for_tests()
        try:
            assert ga.record_topk(64, "fused", "int8",
                                  device_kind="TPU v5 lite0",
                                  measured={"source": "serving_bench"})
            saved = json.loads(cache.read_text())
            assert saved["TPU v5 lite|topk|r64|int8"]["mode"] == "fused"
            assert not ga.record_topk(64, "bogus", "int8",
                                      device_kind="TPU v5 lite0")
        finally:
            ga.reset_for_tests()

    def test_defaults_name_no_topk_kernel(self):
        """fused_topk does not lower on the installed JAX at any shape
        (``lax.top_k`` has no Pallas TPU lowering), so the committed
        table carries no top-k entry and ``auto`` serves einsum."""
        from predictionio_tpu.ops import gram_autotune as ga

        table = json.loads(open(ga._DEFAULTS_PATH).read())
        assert not [k for k in table if "|topk|" in k]
        for q in ("f32", "bf16", "int8"):
            assert ga.best_topk_mode(
                64, q, device_kind="TPU v5 lite0") == "einsum"

    def test_resolved_topk_mode_override_and_validation(self):
        shapes = dict(batch=8, n_rows=100, k=8)
        set_serving_topk_mode("fused")
        assert resolved_topk_mode(64, "int8", **shapes) == "fused"
        set_serving_topk_mode("auto")
        assert resolved_topk_mode(64, "off", **shapes) == "einsum"
        with pytest.raises(ValueError, match="serving topk"):
            set_serving_topk_mode("fusion")
