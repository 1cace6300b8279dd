"""The ALS normal-equation build — ``models/als.py::_lhs_fn`` over
``ops/gram.py::gram_weighted``, the path every ``ptpu train``, fold-in
and streaming update takes — held to a float64 numpy reference written
here: ``A[b] = Σ_l wa[b,l]·f fᵀ`` and ``b[b] = Σ_l wb[b,l]·f`` over
``f = table[indices[b,l]]``. Ragged and odd shapes, the bfloat16 wire
and the bfloat16 compute mode, padding slots, row- and L-sharded blocks
on the forced 8-device CPU mesh, the three history layouts of
``train_als`` against a float64 solve of the same normal equations, and
what reaches ``ALSParams`` from outside the program."""

import base64
import dataclasses

import numpy as np
import pytest

import jax
import jax.numpy as jnp
from jax.sharding import NamedSharding, PartitionSpec as P

from predictionio_tpu.models.als import (
    ALSParams,
    RatingsCOO,
    _init_factors,
    _lhs_fn,
    _shadow_lhs_fn,
    fold_in_rows,
    pack_ratings,
    recommend_products,
    train_als,
    training_report,
)
from predictionio_tpu.ops.gram import gram_weighted
from predictionio_tpu.parallel.mesh import rows_spec

#: ragged (rows, history) shapes, then odd row counts at one history
SHAPES = [(1, 5), (13, 33), (7, 1), (19, 70), (1, 12), (3, 12), (7, 12)]
RANKS = [8, 64]


def problem(B, L, r, m=100, lead=(), seed=None):
    rng = np.random.default_rng(B * 31 + L + r if seed is None else seed)
    tab = rng.normal(size=(m, r)).astype(np.float32)
    idx = rng.integers(0, m, lead + (B, L)).astype(np.int32)
    wa = rng.random(lead + (B, L)).astype(np.float32)
    wb = rng.random(lead + (B, L)).astype(np.float32)
    return tab, idx, wa, wb


def reference(tab, idx, wa, wb):
    """The float64 normal-equation build over whatever rows ``tab``
    holds (pass the bfloat16-rounded table for the wire's reference)."""
    F = np.asarray(tab, dtype=np.float64)[idx]
    wa = np.asarray(wa, dtype=np.float64)
    wb = np.asarray(wb, dtype=np.float64)
    return (np.einsum("...lr,...ls,...l->...rs", F, F, wa),
            np.einsum("...lr,...l->...r", F, wb))


def bf16_rounded(x):
    return np.asarray(jnp.asarray(x).astype(jnp.bfloat16)
                      .astype(jnp.float32))


def lhs(tab, idx, wa, wb, fn=_lhs_fn, bf16=False):
    A, b = fn(jnp.asarray(tab), jnp.asarray(idx), jnp.asarray(wa),
              jnp.asarray(wb), bf16=bf16)
    assert A.dtype == jnp.float32 and b.dtype == jnp.float32
    return np.asarray(A), np.asarray(b)


class TestLhsFloat32:
    @pytest.mark.parametrize("lead", [(), (2,)], ids=["flat", "devices"])
    @pytest.mark.parametrize("r", RANKS)
    @pytest.mark.parametrize("B,L", SHAPES)
    def test_matches_float64(self, B, L, r, lead):
        tab, idx, wa, wb = problem(B, L, r, lead=lead)
        A, b = lhs(tab, idx, wa, wb)
        A_ref, b_ref = reference(tab, idx, wa, wb)
        assert A.shape == lead + (B, r, r) and b.shape == lead + (B, r)
        np.testing.assert_allclose(A, A_ref, rtol=1e-5, atol=1e-5)
        np.testing.assert_allclose(b, b_ref, rtol=1e-5, atol=1e-5)


class TestLhsBfloat16:
    @pytest.mark.parametrize("r", RANKS)
    @pytest.mark.parametrize("B,L", SHAPES)
    def test_shadow_wire_accumulates_in_float32(self, B, L, r):
        """``gather_dtype="bfloat16"``: rows are rounded ONCE on the
        wire; against the float64 build over those rounded rows only
        float32 summation noise is left."""
        tab, idx, wa, wb = problem(B, L, r, lead=(1,))
        A, b = lhs(tab, idx, wa, wb, fn=_shadow_lhs_fn)
        A_sh, b_sh = reference(bf16_rounded(tab), idx, wa, wb)
        np.testing.assert_allclose(A, A_sh, rtol=1e-4, atol=1e-4)
        np.testing.assert_allclose(b, b_sh, rtol=1e-4, atol=1e-4)
        # and against the float32 rows only the rounding of the wire
        A_ref, _ = reference(tab, idx, wa, wb)
        np.testing.assert_allclose(A, A_ref, rtol=0.1,
                                   atol=0.05 * max(1.0, L / 33))

    @pytest.mark.parametrize("B,L", SHAPES)
    def test_bf16_compute_mode_close(self, B, L):
        """``matmul_dtype="bfloat16"``: both operands of the product
        are rounded, the sum stays float32."""
        tab, idx, wa, wb = problem(B, L, 8, lead=(1,))
        A, b = lhs(tab, idx, wa, wb, bf16=True)
        A_ref, b_ref = reference(tab, idx, wa, wb)
        np.testing.assert_allclose(A, A_ref, rtol=3e-2,
                                   atol=3e-2 * max(1.0, L / 9))
        # the right-hand side is not part of the compute mode
        np.testing.assert_allclose(b, b_ref, rtol=1e-5, atol=1e-5)

    def test_rhs_of_a_shadow_table_does_not_drift(self):
        """4,096 slots of one row, every term 1 + 2⁻⁷ (exact in
        bfloat16): a sum carried in bfloat16 stalls at 256 (a step of
        1 is below its spacing there), the float32 one is exact."""
        L, r = 4096, 8
        tab = np.full((4, r), 1.0 + 2.0 ** -7, np.float32)
        idx = np.zeros((1, 1, L), np.int32)
        w = np.ones((1, 1, L), np.float32)
        A, b = lhs(tab, idx, w, w, fn=_shadow_lhs_fn)
        assert bf16_rounded(tab)[0, 0] == tab[0, 0]
        np.testing.assert_allclose(b, L * (1.0 + 2.0 ** -7), rtol=1e-6)
        np.testing.assert_allclose(A, L * (1.0 + 2.0 ** -7) ** 2,
                                   rtol=1e-6)


class TestPaddingSlots:
    @pytest.mark.parametrize("fn, bf16", [(_lhs_fn, False),
                                          (_shadow_lhs_fn, False),
                                          (_lhs_fn, True)],
                             ids=["float32", "shadow", "bf16-compute"])
    def test_zero_weight_rows_are_exactly_zero(self, fn, bf16):
        tab, idx, wa, wb = problem(9, 12, 8, lead=(1,))
        wa[:, 3:] = 0.0
        wb[:, 3:] = 0.0
        A, b = lhs(tab, idx, wa, wb, fn=fn, bf16=bf16)
        assert np.all(A[:, 3:] == 0.0) and np.all(b[:, 3:] == 0.0)
        assert np.any(A[:, :3] != 0.0)

    def test_masked_slots_may_point_anywhere(self):
        """A padding slot carries index 0 and weight 0: what the table
        holds at row 0 must not reach the sums."""
        tab, idx, wa, wb = problem(5, 9, 8, lead=(1,))
        idx[..., 6:] = 0
        wa[..., 6:] = 0.0
        wb[..., 6:] = 0.0
        A, b = lhs(tab, idx, wa, wb)
        tab2 = tab.copy()
        tab2[0] = 1e6
        keep = ~(idx[..., :6] == 0).any(axis=-1)[0]
        A2, b2 = lhs(tab2, idx, wa, wb)
        assert keep.any()
        np.testing.assert_array_equal(A[0, keep], A2[0, keep])
        np.testing.assert_array_equal(b[0, keep], b2[0, keep])


@pytest.mark.skipif(len(jax.devices()) < 8,
                    reason="needs the forced-8-device CPU mesh")
class TestMesh:
    """GSPMD places both einsums: what it derives equals the meshless
    result."""

    @pytest.mark.parametrize("fn", [_lhs_fn, _shadow_lhs_fn],
                             ids=["float32", "shadow"])
    def test_row_sharded_blocks(self, mesh8, fn):
        n_dev = mesh8.devices.size
        tab, idx, wa, wb = problem(6, 20, 8, m=8 * n_dev, lead=(n_dev,))
        A0, b0 = lhs(tab, idx, wa, wb, fn=fn)
        rows = NamedSharding(mesh8, rows_spec(mesh8))
        args = [jax.device_put(x, rows) for x in (tab, idx, wa, wb)]
        A, b = jax.jit(lambda *a: fn(*a, bf16=False))(*args)
        np.testing.assert_allclose(np.asarray(A), A0, rtol=1e-5,
                                   atol=1e-5)
        np.testing.assert_allclose(np.asarray(b), b0, rtol=1e-5,
                                   atol=1e-5)
        A_ref, _ = reference(bf16_rounded(tab) if fn is _shadow_lhs_fn
                             else tab, idx, wa, wb)
        np.testing.assert_allclose(np.asarray(A), A_ref, rtol=1e-4,
                                   atol=1e-4)

    def test_history_sharded_skinny_bucket(self, mesh8):
        """One mega row whose HISTORY axis is spread over the mesh
        (``_blocked_bucket``'s skinny layout): per-device partial
        Gramians and an all-reduce."""
        n_dev = mesh8.devices.size
        tab, idx, wa, wb = problem(2, 64 * n_dev, 8, m=8 * n_dev,
                                   lead=(1,))
        hist = NamedSharding(mesh8, P(None, None,
                                      tuple(mesh8.axis_names)))
        args = [jax.device_put(tab, NamedSharding(mesh8,
                                                  rows_spec(mesh8)))]
        args += [jax.device_put(x, hist) for x in (idx, wa, wb)]
        fn = jax.jit(lambda *a: _lhs_fn(*a, bf16=False))
        assert "all-reduce" in fn.lower(*args).compile().as_text()
        A, b = fn(*args)
        A_ref, b_ref = reference(tab, idx, wa, wb)
        np.testing.assert_allclose(np.asarray(A), A_ref, rtol=1e-5,
                                   atol=1e-4)
        np.testing.assert_allclose(np.asarray(b), b_ref, rtol=1e-5,
                                   atol=1e-4)

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


# -- train_als against a float64 solve of the same normal equations ---------

def unique_coo(nu=40, ni=30, nnz=500, seed=0):
    rng = np.random.default_rng(seed)
    flat = rng.choice(nu * ni, size=nnz, replace=False)
    return RatingsCOO((flat // ni).astype(np.int32),
                      (flat % ni).astype(np.int32),
                      (rng.random(nnz) * 4 + 1).astype(np.float32),
                      nu, ni)


def solve_side(fixed, rows, cols, vals, n_rows, p: ALSParams):
    """One half-iteration in float64: every row's normal equations
    against ``fixed``, as ``_update_block`` states them (ALS-WR
    regularization by the row's count, Hu-Koren-Volinsky confidence,
    ``solve_spd_batch``'s jitter)."""
    fixed = np.asarray(fixed, dtype=np.float64)
    r = fixed.shape[1]
    G = fixed.T @ fixed
    out = np.zeros((n_rows, r))
    for u in range(n_rows):
        sel = rows == u
        F, v = fixed[cols[sel]], vals[sel].astype(np.float64)
        if p.implicit_prefs:
            A = G + (F * (p.alpha * v)[:, None]).T @ F
            b = F.T @ (1.0 + p.alpha * v)
        else:
            A = F.T @ F
            b = F.T @ v
        A = A + (p.reg * max(int(sel.sum()), 1) + 1e-6) * np.eye(r)
        out[u] = np.linalg.solve(A, b)
    return out


def one_iteration_float64(coo: RatingsCOO, p: ALSParams):
    _, ki = jax.random.split(jax.random.key(p.seed))
    V0 = np.asarray(_init_factors(ki, n=coo.n_items, n_padded=coo.n_items,
                                  rank=p.rank))
    U = solve_side(V0, coo.users, coo.items, coo.ratings, coo.n_users, p)
    V = solve_side(U, coo.items, coo.users, coo.ratings, coo.n_items, p)
    return U, V


class TestTrainAgainstFloat64:
    @pytest.mark.parametrize("implicit", [False, True],
                             ids=["explicit", "implicit"])
    @pytest.mark.parametrize("layout", ["pad", "bucket", "split"])
    def test_one_iteration(self, layout, implicit):
        coo = unique_coo(seed=3)
        p = ALSParams(rank=6, num_iterations=1, reg=0.1, seed=5,
                      implicit_prefs=implicit, alpha=2.0,
                      history_mode=layout,
                      max_history=8 if layout == "split" else None)
        if layout == "split":
            with pytest.warns(UserWarning):
                U, V = train_als(coo, p)
        else:
            U, V = train_als(coo, p)
        U_ref, V_ref = one_iteration_float64(coo, p)
        np.testing.assert_allclose(np.asarray(U)[:coo.n_users], U_ref,
                                   rtol=2e-4, atol=2e-5)
        np.testing.assert_allclose(np.asarray(V)[:coo.n_items], V_ref,
                                   rtol=2e-4, atol=2e-5)
        assert np.all(np.asarray(U)[coo.n_users:] == 0.0)

    @pytest.mark.skipif(len(jax.devices()) < 8,
                        reason="needs the forced-8-device CPU mesh")
    @pytest.mark.parametrize("layout", ["pad", "bucket"])
    def test_one_iteration_on_the_mesh(self, mesh8, layout):
        coo = unique_coo(nu=64, ni=48, nnz=800, seed=7)
        p = ALSParams(rank=6, num_iterations=1, reg=0.1, seed=3,
                      implicit_prefs=True, alpha=2.0, history_mode=layout)
        U, V = train_als(coo, p, mesh=mesh8)
        U_ref, V_ref = one_iteration_float64(coo, p)
        np.testing.assert_allclose(np.asarray(U)[:coo.n_users], U_ref,
                                   rtol=2e-4, atol=2e-5)
        np.testing.assert_allclose(np.asarray(V)[:coo.n_items], V_ref,
                                   rtol=2e-4, atol=2e-5)

    @pytest.mark.parametrize("implicit", [False, True],
                             ids=["explicit", "implicit"])
    def test_fold_in_solves_the_same_equations(self, implicit):
        """``fold_in_rows`` (the streaming update) is ``_update_block``
        over one padded block: the same float64 solve."""
        coo = unique_coo(nu=9, ni=30, nnz=120, seed=11)
        p = ALSParams(rank=6, reg=0.1, implicit_prefs=implicit, alpha=2.0)
        V = np.random.default_rng(2).normal(
            size=(coo.n_items, p.rank)).astype(np.float32)
        counts = np.bincount(coo.users, minlength=coo.n_users)
        L = int(counts.max())
        idx = np.zeros((coo.n_users, L), np.int32)
        val = np.zeros((coo.n_users, L), np.float32)
        for u in range(coo.n_users):
            sel = coo.users == u
            idx[u, :counts[u]] = coo.items[sel]
            val[u, :counts[u]] = coo.ratings[sel]
        rows = fold_in_rows(V, idx, val, counts, p)
        ref = solve_side(V, coo.users, coo.items, coo.ratings,
                         coo.n_users, p)
        np.testing.assert_allclose(rows, ref, rtol=2e-4, atol=2e-5)


# -- what reaches ALSParams from outside the program ------------------------

class TestTheOptionIsGone:
    def test_gram_mode_is_not_a_parameter(self):
        with pytest.raises(TypeError, match="gram_mode"):
            ALSParams(gram_mode="einsum")
        assert len(dataclasses.fields(ALSParams)) == 12

    def test_training_report_keys(self):
        coo = unique_coo()
        p = ALSParams(rank=4)
        report = training_report(p, pack_ratings(coo, p))
        assert set(report) == {"rank", "solver", "gatherDtype", "layout",
                               "historyLens"}

    def test_gram_weighted_is_the_one_realization(self):
        import predictionio_tpu.ops.gram as gram

        public = {n for n in vars(gram) if not n.startswith("_")}
        assert public == {"annotations", "jax", "jnp", "gram_weighted"}
        F = jnp.asarray(np.arange(24, dtype=np.float32).reshape(2, 3, 4))
        w = jnp.asarray(np.ones((2, 3), np.float32))
        np.testing.assert_allclose(
            np.asarray(gram_weighted(F, w)),
            np.einsum("blr,bls->brs", np.asarray(F), np.asarray(F)))


#: ``workflow.persistence.dumps_models([ALSModel(...)])`` as the parent
#: commit (PR 46) wrote it: 3 users, 5 items, rank 4, its ``ALSParams``
#: instance still carrying ``gram_mode="auto"``
BLOB_WRITTEN_BY_PR_46 = base64.b64decode(
    "gASVYgMAAAAAAABdlIwbcHJlZGljdGlvbmlvX3RwdS5tb2RlbHMuYWxzlIwIQUxTTW9k"
    "ZWyUk5QpgZR9lCiMDHVzZXJfZmFjdG9yc5SMFm51bXB5Ll9jb3JlLm11bHRpYXJyYXmU"
    "jAxfcmVjb25zdHJ1Y3SUk5SMBW51bXB5lIwHbmRhcnJheZSTlEsAhZRDAWKUh5RSlChL"
    "AUsDSwSGlGgKjAVkdHlwZZSTlIwCZjSUiYiHlFKUKEsDjAE8lE5OTkr/////Sv////9L"
    "AHSUYolDMJybKr9flMc9oSfzvzVU+L/ki7S/YvNMvybuO7925Om+MG5Dvif+X78HCpY7"
    "O8kXP5R0lGKMDGl0ZW1fZmFjdG9yc5RoCWgMSwCFlGgOh5RSlChLAUsFSwSGlGgWiUNQ"
    "f1yLPoySYT+FTOo/Uikhv/cdzT/dp4e/t9N+vcAogT9zyK4/tHgwPw7lNT8zyKk/4FvF"
    "Pb3pMT/u6jC/coHGv0JlCcDQRsI/CSApP/wZCL+UdJRijAduX3VzZXJzlEsDjAduX2l0"
    "ZW1zlEsFjAh1c2VyX2lkc5SMG3ByZWRpY3Rpb25pb190cHUuZGF0YS5iaW1hcJSMBUJp"
    "TWFwlJOUKYGUfZQojARfZndklH2UKIwCdTCUSwCMAnUxlEsBjAJ1MpRLAnWMBF9yZXaU"
    "fZQoSwBoLEsBaC1LAmgudXVijAhpdGVtX2lkc5RoJymBlH2UKGgqfZQojAJpMJRLAIwC"
    "aTGUSwGMAmkylEsCjAJpM5RLA4wCaTSUSwR1aC99lChLAGg1SwFoNksCaDdLA2g4SwRo"
    "OXV1YowGcGFyYW1zlGgBjAlBTFNQYXJhbXOUk5QpgZR9lCiMBHJhbmuUSwSMDm51bV9p"
    "dGVyYXRpb25zlEsKjANyZWeURz+EeuFHrhR7jAVhbHBoYZRHQAAAAAAAAACMDmltcGxp"
    "Y2l0X3ByZWZzlIiMBHNlZWSUSwOMC21heF9oaXN0b3J5lE6MEnNjYWxlX3JlZ19ieV9j"
    "b3VudJSIjApibG9ja19yb3dzlE6MDG1hdG11bF9kdHlwZZSMB2Zsb2F0MzKUjAxnYXRo"
    "ZXJfZHR5cGWUaEqMCWdyYW1fbW9kZZSMBGF1dG+UjAxoaXN0b3J5X21vZGWUaE11YowE"
    "bWVzaJROdWJhLg==")


class TestAModelStoredByTheParent:
    def test_loads_serves_and_folds_in(self):
        from predictionio_tpu.workflow import persistence

        assert b"gram_mode" in BLOB_WRITTEN_BY_PR_46
        (model,) = persistence.loads_models(BLOB_WRITTEN_BY_PR_46)
        p = model.params
        assert (p.rank, p.implicit_prefs, p.alpha) == (4, True, 2.0)
        assert p == ALSParams(rank=4, implicit_prefs=True, alpha=2.0)
        assert hash(p) == hash(ALSParams(rank=4, implicit_prefs=True,
                                         alpha=2.0))
        ids, scores = recommend_products(model, 1, 3)
        want = np.asarray(model.user_factors)[1] \
            @ np.asarray(model.item_factors).T
        assert list(np.asarray(ids)) == list(np.argsort(-want)[:3])
        np.testing.assert_allclose(np.asarray(scores),
                                   np.sort(want)[::-1][:3], rtol=1e-5)
        # the fold-in reads the stored instance's parameters
        rows = fold_in_rows(np.asarray(model.item_factors),
                            np.array([[0, 2, 4]], np.int32),
                            np.array([[1.0, 3.0, 2.0]], np.float32),
                            np.array([3], np.int32), p)
        ref = solve_side(np.asarray(model.item_factors),
                         np.zeros(3, np.int64), np.array([0, 2, 4]),
                         np.array([1.0, 3.0, 2.0], np.float32), 1, p)
        np.testing.assert_allclose(rows, ref, rtol=2e-4, atol=2e-5)
