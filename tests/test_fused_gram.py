"""Fused gather+Gramian Pallas kernel (ISSUE 7), run in interpret mode
on CPU so tier-1 covers the kernel without a TPU: accuracy against the
materialized-gather oracle in f32, tolerance against the bf16-shadow
wire, ragged/odd tail blocks, the full training paths (explicit and
implicit, pad and bucket layouts), and mesh-sharded parity against
meshless factors on the forced-8-device CPU mesh. Plus the satellite
contracts: centralized odd-B handling in ``gram_dispatch`` and the
autotune table's graceful einsum fallback where the kernel can't lower.
"""

import json
import os

import numpy as np
import pytest

import jax
import jax.numpy as jnp

from predictionio_tpu.models.als import (
    ALSParams,
    RatingsCOO,
    _lhs_fn,
    _shadow_lhs_fn,
    resolved_gram_mode,
    train_als,
)
from predictionio_tpu.ops._probe import NO_TPU
from predictionio_tpu.ops.fused_gram import (
    fused_gram,
    fused_gram_dispatch,
    fused_gram_reference,
    fused_gram_refusal,
    fused_vmem_bytes,
)
from predictionio_tpu.ops.gram import gram_dispatch, gram_weighted


def make_problem(m=100, r=24, B=40, L=33, seed=0):
    rng = np.random.default_rng(seed)
    tab = rng.normal(size=(m, r)).astype(np.float32)
    idx = rng.integers(0, m, (B, L)).astype(np.int32)
    wa = rng.random((B, L)).astype(np.float32)
    wb = rng.random((B, L)).astype(np.float32)
    return tab, idx, wa, wb


def oracle(tab, idx, wa, wb):
    F = np.asarray(tab, dtype=np.float32)[idx]
    return (np.einsum("blr,bls,bl->brs", F, F, wa),
            np.einsum("blr,bl->br", F, wb))


class TestKernelInterpret:
    def test_f32_matches_gram_weighted(self):
        """Kernel output vs the einsum path's gram_weighted on the SAME
        pre-gathered rows — the equivalence `gram_mode="fused"` claims.
        f32 end to end: only summation-order noise is allowed."""
        tab, idx, wa, wb = make_problem()
        A, b = fused_gram(jnp.asarray(tab), jnp.asarray(idx),
                          jnp.asarray(wa), jnp.asarray(wb),
                          interpret=True)
        F = jnp.asarray(tab)[jnp.asarray(idx)]
        A_ein = np.asarray(gram_weighted(F, jnp.asarray(wa)))
        np.testing.assert_allclose(np.asarray(A), A_ein,
                                   rtol=1e-5, atol=1e-5)
        _, b_ref = oracle(tab, idx, wa, wb)
        np.testing.assert_allclose(np.asarray(b), b_ref,
                                   rtol=1e-5, atol=1e-5)

    def test_matches_reference_exactly_shaped(self):
        tab, idx, wa, wb = make_problem(seed=3)
        A, b = fused_gram(jnp.asarray(tab), jnp.asarray(idx),
                          jnp.asarray(wa), jnp.asarray(wb),
                          interpret=True)
        A_ref, b_ref = fused_gram_reference(
            jnp.asarray(tab), jnp.asarray(idx), jnp.asarray(wa),
            jnp.asarray(wb))
        assert A.shape == A_ref.shape and b.shape == b_ref.shape
        np.testing.assert_allclose(np.asarray(A), np.asarray(A_ref),
                                   rtol=1e-5, atol=1e-5)

    def test_bf16_wire_within_shadow_tolerance(self):
        """bf16 table on the wire: must match the bf16-SHADOW oracle
        (gather bf16, contract f32) tightly — the shadow path's
        existing quality budget, not a new one."""
        tab, idx, wa, wb = make_problem(seed=1)
        tab16 = jnp.asarray(tab).astype(jnp.bfloat16)
        A, b = fused_gram(tab16, jnp.asarray(idx), jnp.asarray(wa),
                          jnp.asarray(wb), interpret=True)
        F16 = np.asarray(tab16.astype(jnp.float32))[idx]
        A_sh = np.einsum("blr,bls,bl->brs", F16, F16, wa)
        np.testing.assert_allclose(np.asarray(A), A_sh,
                                   rtol=1e-4, atol=1e-4)
        # and against the f32 truth only bf16-quantization error
        A_f32, _ = oracle(tab, idx, wa, wb)
        np.testing.assert_allclose(np.asarray(A), A_f32,
                                   rtol=0.1, atol=0.05)

    @pytest.mark.parametrize("B,L", [(1, 5), (13, 33), (7, 1),
                                     (19, 70)])
    def test_ragged_tails(self, B, L):
        """B not a block multiple, L not a chunk multiple: pad-and-
        slice must be invisible (pad slots carry w=0)."""
        tab, idx, wa, wb = make_problem(B=B, L=L, seed=B * 31 + L)
        A, b = fused_gram(jnp.asarray(tab), jnp.asarray(idx),
                          jnp.asarray(wa), jnp.asarray(wb),
                          chunk=16, interpret=True)
        A_ref, b_ref = oracle(tab, idx, wa, wb)
        assert A.shape == (B,) + A_ref.shape[1:]
        np.testing.assert_allclose(np.asarray(A), A_ref,
                                   rtol=1e-5, atol=1e-5)
        np.testing.assert_allclose(np.asarray(b), b_ref,
                                   rtol=1e-5, atol=1e-5)

    def test_zero_weight_rows_are_exactly_zero(self):
        tab, idx, wa, wb = make_problem(B=9, L=12)
        wa[3:] = 0.0
        wb[3:] = 0.0
        A, b = fused_gram(jnp.asarray(tab), jnp.asarray(idx),
                          jnp.asarray(wa), jnp.asarray(wb),
                          interpret=True)
        assert np.all(np.asarray(A)[3:] == 0.0)
        assert np.all(np.asarray(b)[3:] == 0.0)

    def test_dispatch_runs_kernel_on_cpu(self):
        """No TPU attached → dispatch runs the interpret-mode kernel
        (the debugging contract), not the reference fallback."""
        tab, idx, wa, wb = make_problem(B=6, L=9)
        A, b = fused_gram_dispatch(jnp.asarray(tab), jnp.asarray(idx),
                                   jnp.asarray(wa), jnp.asarray(wb))
        A_ref, b_ref = oracle(tab, idx, wa, wb)
        np.testing.assert_allclose(np.asarray(A), A_ref,
                                   rtol=1e-5, atol=1e-5)

    def test_vmem_budget_math(self):
        # chunking caps the working set however long L grows
        assert fused_vmem_bytes(8192, 128) == fused_vmem_bytes(
            8192, 128, chunk=512)
        assert fused_vmem_bytes(512, 128, wire_bytes=2) \
            < fused_vmem_bytes(512, 128, wire_bytes=4)
        # r=128 f32 double buffer alone is 512 KiB
        assert fused_vmem_bytes(512, 128) > 2 * 512 * 128 * 4


class TestLhsFn:
    """models/als.py::_lhs_fn — the one place the gather exists."""

    def test_fused_equals_einsum_path(self):
        tab, idx, wa, wb = make_problem(B=16, L=20)
        idx3, wa3, wb3 = (x.reshape(1, *x.shape) for x in (idx, wa, wb))
        A_e, b_e = _lhs_fn(jnp.asarray(tab), jnp.asarray(idx3),
                           jnp.asarray(wa3), jnp.asarray(wb3),
                           gram="einsum", bf16=False)
        A_f, b_f = _lhs_fn(jnp.asarray(tab), jnp.asarray(idx3),
                           jnp.asarray(wa3), jnp.asarray(wb3),
                           gram="fused", bf16=False)
        np.testing.assert_allclose(np.asarray(A_f), np.asarray(A_e),
                                   rtol=1e-5, atol=1e-5)
        np.testing.assert_allclose(np.asarray(b_f), np.asarray(b_e),
                                   rtol=1e-5, atol=1e-5)

    def test_shadow_lhs_fn_casts_to_wire(self):
        tab, idx, wa, wb = make_problem(B=8, L=10)
        idx3, wa3, wb3 = (x.reshape(1, *x.shape) for x in (idx, wa, wb))
        A_s, _ = _shadow_lhs_fn(jnp.asarray(tab), jnp.asarray(idx3),
                                jnp.asarray(wa3), jnp.asarray(wb3),
                                gram="fused", bf16=False)
        tab16 = jnp.asarray(tab).astype(jnp.bfloat16)
        A_w, _ = _lhs_fn(tab16, jnp.asarray(idx3), jnp.asarray(wa3),
                         jnp.asarray(wb3), gram="fused", bf16=False)
        np.testing.assert_allclose(np.asarray(A_s), np.asarray(A_w),
                                   rtol=1e-6, atol=1e-6)


class TestTrainingParity:
    """gram_mode="fused" must train to the einsum path's factors —
    f32 exact within solver tolerance (acceptance criterion)."""

    def _coo(self, nu=60, ni=40, nnz=900, seed=0):
        rng = np.random.default_rng(seed)
        return RatingsCOO(
            rng.integers(0, nu, nnz).astype(np.int32),
            rng.integers(0, ni, nnz).astype(np.int32),
            (rng.random(nnz).astype(np.float32) * 4 + 1),
            nu, ni)

    @pytest.mark.parametrize("implicit", [False, True])
    @pytest.mark.parametrize("layout", ["pad", "bucket"])
    def test_fused_vs_einsum_factors(self, implicit, layout):
        coo = self._coo()
        kw = dict(rank=6, num_iterations=2, seed=3, history_mode=layout,
                  implicit_prefs=implicit, alpha=8.0)
        U1, V1 = train_als(coo, ALSParams(**kw, gram_mode="einsum"))
        U2, V2 = train_als(coo, ALSParams(**kw, gram_mode="fused"))
        np.testing.assert_allclose(np.asarray(U2), np.asarray(U1),
                                   rtol=2e-4, atol=2e-5)
        np.testing.assert_allclose(np.asarray(V2), np.asarray(V1),
                                   rtol=2e-4, atol=2e-5)

    def test_fused_bf16_shadow_within_existing_tolerance(self):
        """bf16-gather + fused kernel stays inside the SAME budget the
        shadow path's einsum run is held to (TestGatherDtype)."""
        coo = self._coo(seed=4)
        kw = dict(rank=6, num_iterations=3, seed=4, history_mode="pad",
                  implicit_prefs=True, alpha=8.0)
        U1, V1 = train_als(coo, ALSParams(**kw, gram_mode="einsum"))
        U2, V2 = train_als(coo, ALSParams(**kw, gram_mode="fused",
                                          gather_dtype="bfloat16"))
        np.testing.assert_allclose(np.asarray(U2), np.asarray(U1),
                                   rtol=0.1, atol=0.02)

    def test_split_layout_routes_through_fused(self):
        coo = self._coo(seed=5)
        kw = dict(rank=5, num_iterations=2, seed=5, max_history=8,
                  history_mode="split", implicit_prefs=False)
        with pytest.warns(UserWarning):
            U1, V1 = train_als(coo, ALSParams(**kw, gram_mode="einsum"))
        with pytest.warns(UserWarning):
            U2, V2 = train_als(coo, ALSParams(**kw, gram_mode="fused"))
        np.testing.assert_allclose(np.asarray(U2), np.asarray(U1),
                                   rtol=2e-4, atol=2e-5)


@pytest.mark.skipif(len(jax.devices()) < 8,
                    reason="needs the forced-8-device CPU mesh")
class TestMeshParity:
    """Mesh-sharded fused training (kernel per device on local rows via
    shard_map, Gramian all-reduce overlapped) vs meshless factors."""

    def test_sharded_fused_matches_meshless(self):
        from predictionio_tpu.parallel.mesh import make_mesh

        rng = np.random.default_rng(7)
        nu, ni, nnz = 64, 48, 800
        coo = RatingsCOO(rng.integers(0, nu, nnz).astype(np.int32),
                         rng.integers(0, ni, nnz).astype(np.int32),
                         np.ones(nnz, np.float32), nu, ni)
        mesh = make_mesh(data=4, model=2)
        kw = dict(rank=6, num_iterations=2, seed=3, history_mode="pad",
                  implicit_prefs=True, alpha=8.0, gram_mode="fused")
        U0, V0 = train_als(coo, ALSParams(**kw))
        Um, Vm = train_als(coo, ALSParams(**kw), mesh=mesh)
        np.testing.assert_allclose(np.asarray(Um)[:nu],
                                   np.asarray(U0)[:nu],
                                   rtol=2e-4, atol=2e-5)
        np.testing.assert_allclose(np.asarray(Vm)[:ni],
                                   np.asarray(V0)[:ni],
                                   rtol=2e-4, atol=2e-5)

    def test_sharded_fused_matches_sharded_einsum(self):
        from predictionio_tpu.parallel.mesh import make_serving_mesh

        rng = np.random.default_rng(9)
        nu, ni, nnz = 56, 40, 700
        coo = RatingsCOO(rng.integers(0, nu, nnz).astype(np.int32),
                         rng.integers(0, ni, nnz).astype(np.int32),
                         np.ones(nnz, np.float32), nu, ni)
        # the (batch, model) SERVING mesh: rows_spec is axis-name
        # agnostic, so the fused shard_map must be too
        mesh = make_serving_mesh()
        kw = dict(rank=4, num_iterations=2, seed=2,
                  history_mode="bucket", implicit_prefs=True, alpha=4.0)
        U1, V1 = train_als(coo, ALSParams(**kw, gram_mode="einsum"),
                           mesh=mesh)
        U2, V2 = train_als(coo, ALSParams(**kw, gram_mode="fused"),
                           mesh=mesh)
        np.testing.assert_allclose(np.asarray(U2), np.asarray(U1),
                                   rtol=2e-4, atol=2e-5)

    def test_gramian_allreduce_matches_einsum(self):
        from predictionio_tpu.parallel.collectives import (
            gramian_allreduce,
        )
        from predictionio_tpu.parallel.mesh import make_mesh, rows_spec
        from jax.sharding import NamedSharding

        mesh = make_mesh(data=4, model=2)
        x = np.random.default_rng(0).normal(
            size=(64, 8)).astype(np.float32)
        xs = jax.device_put(x, NamedSharding(mesh, rows_spec(mesh)))
        G = gramian_allreduce(xs, mesh)
        np.testing.assert_allclose(np.asarray(G), x.T @ x,
                                   rtol=1e-5, atol=1e-4)


class TestGramDispatchOddRows:
    """Satellite: odd-B handling is centralized in gram_dispatch —
    pad-and-slice, never a silent einsum fallback, never an assert."""

    @pytest.mark.parametrize("n", [1, 3, 7])
    def test_pair_odd_rows_pad_and_slice(self, n):
        rng = np.random.default_rng(n)
        F = jnp.asarray(rng.normal(size=(n, 12, 8)).astype(np.float32))
        w = jnp.asarray(rng.random((n, 12)).astype(np.float32))
        out = gram_dispatch(F, w, mode="pair")
        ref = gram_weighted(F, w)
        assert out.shape == ref.shape
        np.testing.assert_allclose(np.asarray(out), np.asarray(ref),
                                   rtol=1e-4, atol=1e-5)

    def test_pair_odd_rows_with_lead_axis(self):
        rng = np.random.default_rng(5)
        F = jnp.asarray(rng.normal(size=(2, 5, 9, 6)).astype(np.float32))
        w = jnp.asarray(rng.random((2, 5, 9)).astype(np.float32))
        out = gram_dispatch(F, w, mode="pair")
        np.testing.assert_allclose(np.asarray(out),
                                   np.asarray(gram_weighted(F, w)),
                                   rtol=1e-4, atol=1e-5)

    def test_fused_mode_on_materialized_gather_degrades(self):
        # F already exists → nothing to fuse → baseline einsum result
        rng = np.random.default_rng(2)
        F = jnp.asarray(rng.normal(size=(4, 6, 5)).astype(np.float32))
        w = jnp.asarray(rng.random((4, 6)).astype(np.float32))
        np.testing.assert_allclose(
            np.asarray(gram_dispatch(F, w, mode="fused")),
            np.asarray(gram_weighted(F, w)), rtol=1e-6)


class TestAutotuneFusedFallback:
    """Satellite: the table only NAMES a mode; ``auto`` skips a named
    kernel that cannot run at the shapes about to run (here: no TPU)
    and trains on einsum, it never raises."""

    def test_fused_entry_falls_back_on_cpu(self, tmp_path, monkeypatch):
        from predictionio_tpu.ops import gram_autotune as ga

        cache = tmp_path / "gram_autotune.json"
        cache.write_text(json.dumps(
            {"cpu|r64|f32": {"mode": "fused", "source": "test"}}))
        monkeypatch.setenv("PIO_GRAM_AUTOTUNE_CACHE", str(cache))
        ga.reset_for_tests()
        try:
            assert fused_gram_refusal(64) == NO_TPU
            assert ga.best_mode(64, device_kind="cpu") == "fused"
            assert resolved_gram_mode(ALSParams(rank=64)) == "einsum"
        finally:
            ga.reset_for_tests()

    def test_fused_recordable(self, tmp_path, monkeypatch):
        from predictionio_tpu.ops import gram_autotune as ga

        cache = tmp_path / "gram_autotune.json"
        monkeypatch.setenv("PIO_GRAM_AUTOTUNE_CACHE", str(cache))
        ga.reset_for_tests()
        try:
            assert ga.record(64, "fused", device_kind="TPU v5 lite0",
                             measured={"source": "bench_race"})
            saved = json.loads(cache.read_text())
            assert saved["TPU v5 lite|r64|f32"]["mode"] == "fused"
        finally:
            ga.reset_for_tests()

    def test_no_default_names_a_kernel_the_chip_refused(self):
        """The v5e compiler refuses both Pallas kernels at the shapes
        training and serving run (CHANGES.md PR 21), so no committed
        entry may name them; what stays carries its own measurement."""
        from predictionio_tpu.ops.gram_autotune import _DEFAULTS_PATH

        table = json.loads(open(_DEFAULTS_PATH).read())
        assert all(ent["mode"] != "fused" for ent in table.values())
        ent = table["TPU v5 lite|r64|f32"]
        assert ent["mode"] == "einsum"
        assert ent["einsum_ms"] < ent["pair_ms"]

    def test_resolved_gram_mode_helper(self):
        assert resolved_gram_mode(
            ALSParams(gram_mode="fused")) == "fused"
        # auto on CPU: heuristic einsum (no fused without lowering)
        assert resolved_gram_mode(
            ALSParams(rank=64, gram_mode="auto")) in ("einsum", "pair")


class TestParamsValidation:
    def test_fused_accepted(self):
        assert ALSParams(gram_mode="fused").gram_mode == "fused"

    def test_bogus_rejected(self):
        with pytest.raises(ValueError, match="gram_mode"):
            ALSParams(gram_mode="fusion")
