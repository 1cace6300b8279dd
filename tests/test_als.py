"""ALS correctness tests: packing, normal-equation exactness vs a dense
numpy reference, convergence on synthetic low-rank data, implicit mode,
and sharded-vs-single-device equivalence on the 8-device CPU mesh."""

import numpy as np
import pytest

from predictionio_tpu.models.als import (
    ALSModel,
    ALSParams,
    RatingsCOO,
    recommend_batch,
    recommend_products,
    train_als,
)
from predictionio_tpu.ops.ragged import pack_histories


def make_synthetic(n_users=60, n_items=40, rank=4, density=0.4, seed=0,
                   noise=0.01):
    rng = np.random.default_rng(seed)
    U = rng.normal(size=(n_users, rank)) / np.sqrt(rank)
    V = rng.normal(size=(n_items, rank)) / np.sqrt(rank)
    full = U @ V.T
    mask = rng.random((n_users, n_items)) < density
    users, items = np.nonzero(mask)
    vals = full[users, items] + noise * rng.normal(size=users.shape)
    return RatingsCOO(users.astype(np.int32), items.astype(np.int32),
                      vals.astype(np.float32), n_users, n_items), full, mask


class TestPackHistories:
    def test_basic(self):
        rows = np.array([0, 2, 0, 2, 2])
        cols = np.array([5, 6, 7, 8, 9])
        vals = np.array([1., 2., 3., 4., 5.])
        h = pack_histories(rows, cols, vals, n_rows=3)
        assert h.indices.shape == (3, 3)
        assert h.counts.tolist() == [2, 0, 3]
        assert sorted(h.indices[0, :2].tolist()) == [5, 7]
        assert h.indices[1].tolist() == [0, 0, 0]
        assert sorted(h.indices[2].tolist()) == [6, 8, 9]

    def test_max_len_cap(self):
        rows = np.array([0, 0, 0, 0])
        cols = np.array([1, 2, 3, 4])
        vals = np.ones(4)
        h = pack_histories(rows, cols, vals, n_rows=1, max_len=2)
        assert h.max_len == 2
        assert h.counts.tolist() == [2]

    def test_pad_rows_to(self):
        rows = np.array([0, 1, 2])
        h = pack_histories(rows, rows, np.ones(3), n_rows=3, pad_rows_to=8)
        assert h.n_rows == 8
        assert h.counts[3:].tolist() == [0] * 5


def explicit_als_reference(ratings, rank, iters, reg, seed,
                           scale_reg=True):
    """Dense numpy ALS-WR — the oracle the TPU path must match."""
    import jax
    ku, ki = jax.random.split(jax.random.key(seed))
    U = np.asarray(jax.random.normal(ku, (ratings.n_users, rank))) / np.sqrt(rank)
    V = np.asarray(jax.random.normal(ki, (ratings.n_items, rank))) / np.sqrt(rank)
    R = np.zeros((ratings.n_users, ratings.n_items), dtype=np.float64)
    M = np.zeros_like(R)
    R[ratings.users, ratings.items] = ratings.ratings
    M[ratings.users, ratings.items] = 1.0
    for _ in range(iters):
        for u in range(ratings.n_users):
            m = M[u] > 0
            n_u = max(m.sum(), 1)
            Vm = V[m]
            A = Vm.T @ Vm + (reg * n_u if scale_reg else reg) * np.eye(rank) \
                + 1e-6 * np.eye(rank)
            U[u] = np.linalg.solve(A, Vm.T @ R[u, m]) if m.any() else \
                np.linalg.solve(A, np.zeros(rank))
        for i in range(ratings.n_items):
            m = M[:, i] > 0
            n_i = max(m.sum(), 1)
            Um = U[m]
            A = Um.T @ Um + (reg * n_i if scale_reg else reg) * np.eye(rank) \
                + 1e-6 * np.eye(rank)
            V[i] = np.linalg.solve(A, Um.T @ R[m, i]) if m.any() else \
                np.linalg.solve(A, np.zeros(rank))
    return U, V


class TestExplicitALS:
    def test_matches_dense_reference(self):
        ratings, _, _ = make_synthetic(n_users=20, n_items=15, rank=3)
        params = ALSParams(rank=3, num_iterations=3, reg=0.1, seed=7)
        U, V = train_als(ratings, params)
        U_ref, V_ref = explicit_als_reference(ratings, 3, 3, 0.1, seed=7)
        np.testing.assert_allclose(np.asarray(U)[:20], U_ref, rtol=2e-3,
                                   atol=2e-4)
        np.testing.assert_allclose(np.asarray(V)[:15], V_ref, rtol=2e-3,
                                   atol=2e-4)

    def test_convergence_on_low_rank(self):
        ratings, full, mask = make_synthetic(seed=1)
        params = ALSParams(rank=4, num_iterations=10, reg=0.01, seed=3)
        U, V = train_als(ratings, params)
        pred = np.asarray(U)[:ratings.n_users] @ np.asarray(V)[:ratings.n_items].T
        rmse = np.sqrt(((pred - full)[mask] ** 2).mean())
        assert rmse < 0.08, f"train RMSE too high: {rmse}"

    def test_blocked_updates_match_single_block(self):
        ratings, _, _ = make_synthetic(n_users=40, n_items=30, rank=3, seed=6)
        p1 = ALSParams(rank=3, num_iterations=3, reg=0.05, seed=5)
        p2 = ALSParams(rank=3, num_iterations=3, reg=0.05, seed=5,
                       block_rows=7)  # forces multi-block path
        U1, V1 = train_als(ratings, p1)
        U2, V2 = train_als(ratings, p2)
        np.testing.assert_allclose(np.asarray(U2), np.asarray(U1),
                                   rtol=1e-4, atol=1e-6)
        np.testing.assert_allclose(np.asarray(V2), np.asarray(V1),
                                   rtol=1e-4, atol=1e-6)

    def test_blocked_sharded_matches(self, mesh8):
        ratings, _, _ = make_synthetic(n_users=48, n_items=32, rank=3, seed=7)
        p = ALSParams(rank=3, num_iterations=2, reg=0.05, seed=5,
                      block_rows=2)
        U1, V1 = train_als(ratings, ALSParams(rank=3, num_iterations=2,
                                              reg=0.05, seed=5))
        U8, V8 = train_als(ratings, p, mesh=mesh8)
        np.testing.assert_allclose(np.asarray(U8)[:48], np.asarray(U1)[:48],
                                   rtol=1e-3, atol=1e-5)

    def test_sharded_matches_single_device(self, mesh8):
        ratings, _, _ = make_synthetic(n_users=32, n_items=24, rank=3, seed=2)
        params = ALSParams(rank=3, num_iterations=3, reg=0.05, seed=5)
        U1, V1 = train_als(ratings, params)
        U8, V8 = train_als(ratings, params, mesh=mesh8)
        np.testing.assert_allclose(np.asarray(U8)[:32], np.asarray(U1)[:32],
                                   rtol=1e-3, atol=1e-5)
        np.testing.assert_allclose(np.asarray(V8)[:24], np.asarray(V1)[:24],
                                   rtol=1e-3, atol=1e-5)


class TestImplicitALS:
    def test_ranks_observed_above_unobserved(self):
        # user 0 interacts with items 0..4 heavily; never with 15..19
        users, items, vals = [], [], []
        rng = np.random.default_rng(0)
        for u in range(30):
            liked = rng.choice(10, size=5, replace=False) if u % 2 == 0 \
                else rng.choice(np.arange(10, 20), size=5, replace=False)
            for i in liked:
                users.append(u)
                items.append(i)
                vals.append(1.0)
        ratings = RatingsCOO(np.array(users, np.int32),
                             np.array(items, np.int32),
                             np.array(vals, np.float32), 30, 20)
        params = ALSParams(rank=8, num_iterations=10, reg=0.01, alpha=40.0,
                           implicit_prefs=True, seed=1)
        U, V = train_als(ratings, params)
        pred = np.asarray(U)[:30] @ np.asarray(V)[:20].T
        # even-indexed users prefer items 0-9 on average
        even_pref = pred[0::2, :10].mean() - pred[0::2, 10:].mean()
        odd_pref = pred[1::2, 10:].mean() - pred[1::2, :10].mean()
        assert even_pref > 0.3
        assert odd_pref > 0.3

    def test_implicit_sharded_matches(self, mesh8):
        rng = np.random.default_rng(3)
        nnz = 200
        ratings = RatingsCOO(
            rng.integers(0, 25, nnz).astype(np.int32),
            rng.integers(0, 18, nnz).astype(np.int32),
            np.ones(nnz, np.float32), 25, 18)
        params = ALSParams(rank=4, num_iterations=2, reg=0.1, alpha=10.0,
                           implicit_prefs=True, seed=2)
        U1, V1 = train_als(ratings, params)
        U8, V8 = train_als(ratings, params, mesh=mesh8)
        np.testing.assert_allclose(np.asarray(U8)[:25], np.asarray(U1)[:25],
                                   rtol=1e-3, atol=1e-5)


class TestRecommend:
    def _model(self):
        ratings, _, _ = make_synthetic(seed=4)
        params = ALSParams(rank=4, num_iterations=5, reg=0.01, seed=0)
        U, V = train_als(ratings, params)
        return ALSModel(user_factors=U, item_factors=V,
                        n_users=ratings.n_users, n_items=ratings.n_items,
                        params=params), ratings

    def test_topk_shapes_and_order(self):
        model, ratings = self._model()
        ids, scores = recommend_products(model, 0, 10)
        assert ids.shape == (10,)
        assert all(scores[i] >= scores[i + 1] for i in range(9))
        assert all(0 <= i < ratings.n_items for i in ids)

    def test_topk_matches_numpy(self):
        model, ratings = self._model()
        ids, scores = recommend_products(model, 3, 5)
        full = np.asarray(model.user_factors)[3] @ \
            np.asarray(model.item_factors)[:ratings.n_items].T
        np_top = np.argsort(-full)[:5]
        np.testing.assert_array_equal(ids, np_top)

    def test_batch_matches_single(self):
        model, _ = self._model()
        ids_b, scores_b = recommend_batch(model, np.array([0, 3, 7]), 4)
        for row, u in enumerate([0, 3, 7]):
            ids_s, scores_s = recommend_products(model, u, 4)
            np.testing.assert_array_equal(ids_b[row], ids_s)
            np.testing.assert_allclose(scores_b[row], scores_s, rtol=1e-6)

    def test_batch_axis_padded_to_pow2_shapes(self):
        """Serving-path jit-cache bound: arbitrary micro-batch sizes
        must collapse onto power-of-two compiled shapes (each novel
        [B, r] shape is a fresh XLA compile, which under traffic lands
        in the micro-batch p90)."""
        import jax.numpy as jnp

        from predictionio_tpu.models.als import _serve_topk

        # device-resident tables (so the device path serves) of a shape
        # no other test compiles: every program counted here is new
        rng = np.random.default_rng(11)
        model = ALSModel(
            user_factors=jnp.asarray(rng.normal(size=(37, 5)), jnp.float32),
            item_factors=jnp.asarray(rng.normal(size=(53, 5)), jnp.float32),
            n_users=37, n_items=53, params=ALSParams(rank=5))
        before = _serve_topk._cache_size()
        for batch in ([0], [0, 1], [0, 1, 2], [0, 1, 2, 3],
                      [0] * 5, [0] * 7):
            ids, _ = recommend_batch(model, np.array(batch), 3)
            assert ids.shape == (len(batch), 3)
        added = _serve_topk._cache_size() - before
        # sizes {1,2,3,4,5,7} collapse to padded {1,2,4,8}
        assert added == 4, f"cache grew by {added}, not 4 shapes"

    def test_padded_items_never_recommended(self, mesh8):
        ratings, _, _ = make_synthetic(n_users=16, n_items=10, seed=5)
        params = ALSParams(rank=3, num_iterations=2, seed=0)
        U, V = train_als(ratings, params, mesh=mesh8)
        model = ALSModel(user_factors=np.asarray(U), item_factors=np.asarray(V),
                         n_users=16, n_items=10, params=params)
        assert np.asarray(V).shape[0] >= 16  # actually padded
        ids, _ = recommend_products(model, 0, 10)
        assert ids.max() < 10


class TestBF16MatmulPath:
    def test_bf16_preserves_preference_structure(self):
        """bfloat16 MXU einsums (f32 accumulation) must not degrade the
        learned preference structure."""
        users, items, vals = [], [], []
        rng = np.random.default_rng(0)
        for u in range(30):
            liked = rng.choice(10, size=5, replace=False) if u % 2 == 0 \
                else rng.choice(np.arange(10, 20), size=5, replace=False)
            for i in liked:
                users.append(u)
                items.append(i)
                vals.append(1.0)
        ratings = RatingsCOO(np.array(users, np.int32),
                             np.array(items, np.int32),
                             np.array(vals, np.float32), 30, 20)
        params = ALSParams(rank=8, num_iterations=10, reg=0.01, alpha=40.0,
                           implicit_prefs=True, seed=1,
                           matmul_dtype="bfloat16")
        U, V = train_als(ratings, params)
        pred = np.asarray(U)[:30] @ np.asarray(V)[:20].T
        even_pref = pred[0::2, :10].mean() - pred[0::2, 10:].mean()
        odd_pref = pred[1::2, 10:].mean() - pred[1::2, :10].mean()
        assert even_pref > 0.3
        assert odd_pref > 0.3

    def test_bf16_close_to_f32_explicit(self):
        ratings, _, _ = make_synthetic(seed=3)
        f32 = ALSParams(rank=4, num_iterations=6, reg=0.05, seed=2)
        b16 = ALSParams(rank=4, num_iterations=6, reg=0.05, seed=2,
                        matmul_dtype="bfloat16")
        U1, V1 = train_als(ratings, f32)
        U2, V2 = train_als(ratings, b16)
        p1 = np.asarray(U1) @ np.asarray(V1).T
        p2 = np.asarray(U2) @ np.asarray(V2).T
        # predictions agree to bf16-level tolerance
        assert np.abs(p1 - p2).mean() < 0.05 * max(np.abs(p1).mean(), 1.0)


class TestHostServeParity:
    def _model(self, n_items=40):
        ratings, _, _ = make_synthetic(n_items=n_items, seed=5)
        params = ALSParams(rank=4, num_iterations=5, reg=0.05, seed=2)
        U, V = train_als(ratings, params)
        from predictionio_tpu.models.als import ALSModel
        return (ALSModel(user_factors=np.asarray(U),
                         item_factors=np.asarray(V), n_users=60,
                         n_items=n_items, user_ids=None, item_ids=None,
                         params=params),
                ALSModel(user_factors=U, item_factors=V, n_users=60,
                         n_items=n_items, user_ids=None, item_ids=None,
                         params=params))

    def test_host_matches_device(self):
        from predictionio_tpu.models.als import (
            recommend_batch,
            recommend_products,
        )

        host, dev = self._model()
        for u in (0, 13, 42):
            ih, sh = recommend_products(host, u, 7)
            idv, sv = recommend_products(dev, u, 7)
            assert list(np.asarray(ih)) == list(np.asarray(idv))
            np.testing.assert_allclose(np.asarray(sh), np.asarray(sv),
                                       rtol=1e-5)
        bh = recommend_batch(host, np.array([0, 13]), 5)
        bd = recommend_batch(dev, np.array([0, 13]), 5)
        np.testing.assert_array_equal(np.asarray(bh[0]),
                                      np.asarray(bd[0]))

    def test_tie_break_lowest_index(self):
        """Duplicate factor rows: host path must prefer the lowest item
        index, like lax.top_k."""
        from predictionio_tpu.models.als import _host_topk

        V = np.ones((6, 4), dtype=np.float32)  # all items tie
        u = np.ones((1, 4), dtype=np.float32)
        ids, scores = _host_topk(u, V, k=3, n_items=6)
        assert ids[0].tolist() == [0, 1, 2]

    def test_work_gate_scales_with_batch(self):
        from predictionio_tpu.models.als import (
            HOST_SERVE_WORK,
            _serve_on_host,
        )

        host, _ = self._model()
        size = host.item_factors.size
        assert _serve_on_host(host, batch=1)
        assert not _serve_on_host(host, batch=HOST_SERVE_WORK // size + 1)


class TestSplitHistories:
    """Split (drop-free) history mode — VERDICT r1 task 3."""

    def test_pack_split_covers_every_entry(self):
        from predictionio_tpu.ops.ragged import pack_histories_split

        rng = np.random.default_rng(0)
        rows = rng.integers(0, 10, 500).astype(np.int32)
        cols = rng.integers(0, 50, 500).astype(np.int32)
        vals = rng.random(500).astype(np.float32)
        h = pack_histories_split(rows, cols, vals, n_rows=10, max_len=8)
        # every entry present exactly once, attributed to the right row
        got = []
        for v in range(h.n_virtual):
            r = int(h.row_ids[v])
            if r >= 10:
                assert h.counts[v] == 0
                continue
            for k in range(int(h.counts[v])):
                got.append((r, int(h.indices[v, k]),
                            float(np.float32(h.values[v, k]))))
        want = sorted(zip(rows.tolist(), cols.tolist(),
                          [float(np.float32(v)) for v in vals]))
        assert sorted(got) == want
        assert h.real_counts[:10].tolist() == \
            np.bincount(rows, minlength=10).tolist()

    def test_device_pack_matches_host(self):
        from predictionio_tpu.ops.ragged import (
            pack_histories_split,
            pack_histories_split_device,
        )

        rng = np.random.default_rng(1)
        rows = rng.integers(0, 7, 200).astype(np.int32)
        cols = rng.integers(0, 20, 200).astype(np.int32)
        vals = rng.random(200).astype(np.float32)
        hh = pack_histories_split(rows, cols, vals, 7, 16, pad_rows_to=4)
        hd = pack_histories_split_device(rows, cols, vals, 7, 16,
                                         pad_rows_to=4)
        np.testing.assert_array_equal(hh.indices, np.asarray(hd.indices))
        np.testing.assert_array_equal(hh.values, np.asarray(hd.values))
        np.testing.assert_array_equal(hh.counts, np.asarray(hd.counts))
        np.testing.assert_array_equal(hh.row_ids, np.asarray(hd.row_ids))
        np.testing.assert_array_equal(hh.real_counts,
                                      np.asarray(hd.real_counts))

    def test_split_matches_pad_explicit(self):
        ratings, _, _ = make_synthetic(n_users=25, n_items=18, rank=3,
                                       seed=11)
        base = dict(rank=3, num_iterations=4, reg=0.05, seed=5)
        U_p, V_p = train_als(ratings, ALSParams(**base,
                                                history_mode="pad"))
        # max_history=4 in split mode splits rows, drops nothing
        U_s, V_s = train_als(ratings, ALSParams(**base, max_history=4,
                                                history_mode="split"))
        np.testing.assert_allclose(np.asarray(U_s)[:25],
                                   np.asarray(U_p)[:25], rtol=2e-3,
                                   atol=2e-4)
        np.testing.assert_allclose(np.asarray(V_s)[:18],
                                   np.asarray(V_p)[:18], rtol=2e-3,
                                   atol=2e-4)

    def test_split_matches_pad_implicit(self):
        ratings, _, _ = make_synthetic(n_users=22, n_items=16, rank=3,
                                       seed=12)
        ratings = RatingsCOO(ratings.users, ratings.items,
                             np.abs(ratings.ratings) + 0.1,
                             ratings.n_users, ratings.n_items)
        base = dict(rank=3, num_iterations=4, reg=0.05, seed=5,
                    implicit_prefs=True, alpha=2.0)
        U_p, V_p = train_als(ratings, ALSParams(**base,
                                                history_mode="pad"))
        U_s, V_s = train_als(ratings, ALSParams(**base, max_history=4,
                                                history_mode="split"))
        np.testing.assert_allclose(np.asarray(U_s)[:22],
                                   np.asarray(U_p)[:22], rtol=2e-3,
                                   atol=2e-4)

    def test_split_sharded_matches_single_device(self, mesh8):
        ratings, _, _ = make_synthetic(n_users=32, n_items=24, rank=3,
                                       seed=13)
        params = ALSParams(rank=3, num_iterations=3, reg=0.05, seed=5,
                           max_history=4, history_mode="split")
        U_1, V_1 = train_als(ratings, params)
        U_8, V_8 = train_als(ratings, params, mesh=mesh8)
        np.testing.assert_allclose(np.asarray(U_8)[:32],
                                   np.asarray(U_1)[:32], rtol=2e-3,
                                   atol=2e-4)
        np.testing.assert_allclose(np.asarray(V_8)[:24],
                                   np.asarray(V_1)[:24], rtol=2e-3,
                                   atol=2e-4)

    def test_auto_mode_drops_nothing_under_skew(self, monkeypatch):
        import predictionio_tpu.ops.ragged as ragged
        from predictionio_tpu.models.als import _pack

        # shrink the auto-cap so the skewed side can't use a flat pad
        monkeypatch.setattr(ragged, "AUTO_CAP_ENTRIES", 2000)
        rng = np.random.default_rng(3)
        rows = np.concatenate([np.zeros(900, np.int32),
                               rng.integers(1, 100, 300).astype(np.int32)])
        cols = rng.integers(0, 50, 1200).astype(np.int32)
        vals = rng.random(1200).astype(np.float32)
        from predictionio_tpu.ops.ragged import BucketedHistories

        h = _pack(rows, cols, vals, 100, ALSParams(history_mode="auto"), 1)
        assert isinstance(h, BucketedHistories)
        # nothing dropped: bucket counts sum to nnz
        total = sum(int(np.asarray(b.counts).sum()) for b in h.buckets)
        assert total == 1200
        # pow2 padding bound: at most 2x + the min-length floor
        assert h.padded_entries <= 2 * 1200 + 8 * 100

    def test_auto_split_len_minimizes_padding(self):
        from predictionio_tpu.models.als import auto_split_len

        counts = np.array([1000000, 3, 3, 3])
        L = auto_split_len(counts)
        padded = (-(-counts // L) * L).sum()
        for cand in (32, 64, 128, 4096, 8192):
            assert padded <= (-(-counts // cand) * cand).sum()


class TestBucketedHistories:
    """Bucket mode: drop-free pow2 length buckets (the TPU-fast drop-free
    layout — unique-index scatters only, MXU-deep contractions)."""

    def test_pack_covers_every_entry_once(self):
        from predictionio_tpu.ops.ragged import (
            BucketedHistories,
            pack_histories_bucketed_device,
        )

        rng = np.random.default_rng(5)
        rows = np.concatenate([np.zeros(500, np.int32),
                               rng.integers(1, 40, 700).astype(np.int32)])
        cols = rng.integers(0, 64, 1200).astype(np.int32)
        vals = rng.random(1200).astype(np.float32)
        h = pack_histories_bucketed_device(rows, cols, vals, 40,
                                           pad_rows_to=4)
        assert isinstance(h, BucketedHistories)
        # every (row, col, val) triple appears exactly once across buckets
        seen = []
        for b in h.buckets:
            idx = np.asarray(b.indices)
            val = np.asarray(b.values)
            for j in range(idx.shape[0]):
                rid = int(b.row_ids[j])
                c = int(b.counts[j])
                if rid >= h.n_rows_padded or c == 0:
                    continue
                for k in range(c):
                    seen.append((rid, int(idx[j, k]), float(val[j, k])))
        assert len(seen) == 1200
        expect = sorted(zip(rows.tolist(), cols.tolist(),
                            [float(v) for v in vals]))
        assert sorted(seen) == expect
        # each real row appears in at most one bucket
        owners = [int(r) for b in h.buckets for r in b.row_ids
                  if int(r) < h.n_rows_padded]
        assert len(owners) == len(set(owners))

    def test_bucket_matches_pad_explicit(self):
        ratings, _, _ = make_synthetic(n_users=25, n_items=18, rank=3,
                                       seed=11)
        base = dict(rank=3, num_iterations=4, reg=0.05, seed=5)
        U_p, V_p = train_als(ratings, ALSParams(**base,
                                                history_mode="pad"))
        U_b, V_b = train_als(ratings, ALSParams(**base,
                                                history_mode="bucket"))
        np.testing.assert_allclose(np.asarray(U_b)[:25],
                                   np.asarray(U_p)[:25], rtol=2e-3,
                                   atol=2e-4)
        np.testing.assert_allclose(np.asarray(V_b)[:18],
                                   np.asarray(V_p)[:18], rtol=2e-3,
                                   atol=2e-4)

    def test_bucket_matches_pad_implicit(self):
        ratings, _, _ = make_synthetic(n_users=22, n_items=16, rank=3,
                                       seed=12)
        ratings = RatingsCOO(ratings.users, ratings.items,
                             np.abs(ratings.ratings) + 0.1,
                             ratings.n_users, ratings.n_items)
        base = dict(rank=3, num_iterations=4, reg=0.05, seed=5,
                    implicit_prefs=True, alpha=2.0)
        U_p, V_p = train_als(ratings, ALSParams(**base,
                                                history_mode="pad"))
        U_b, V_b = train_als(ratings, ALSParams(**base,
                                                history_mode="bucket"))
        np.testing.assert_allclose(np.asarray(U_b)[:22],
                                   np.asarray(U_p)[:22], rtol=2e-3,
                                   atol=2e-4)

    def test_bucket_matches_split_on_skew(self):
        # zipf-ish skew: one mega row + many small rows
        rng = np.random.default_rng(9)
        rows = np.concatenate([np.zeros(600, np.int32),
                               rng.integers(1, 60, 400).astype(np.int32)])
        cols = rng.integers(0, 40, 1000).astype(np.int32)
        vals = np.ones(1000, np.float32)
        ratings = RatingsCOO(rows, cols, vals, 60, 40)
        base = dict(rank=3, num_iterations=3, reg=0.05, seed=5,
                    implicit_prefs=True, alpha=5.0)
        U_s, V_s = train_als(ratings, ALSParams(**base, max_history=8,
                                                history_mode="split"))
        U_b, V_b = train_als(ratings, ALSParams(**base,
                                                history_mode="bucket"))
        np.testing.assert_allclose(np.asarray(U_b)[:60],
                                   np.asarray(U_s)[:60], rtol=2e-3,
                                   atol=2e-4)
        np.testing.assert_allclose(np.asarray(V_b)[:40],
                                   np.asarray(V_s)[:40], rtol=2e-3,
                                   atol=2e-4)

    def test_bucket_sharded_matches_single_device(self, mesh8):
        # includes a mega row (thinner than the mesh -> L-axis sharding)
        rng = np.random.default_rng(13)
        rows = np.concatenate([np.zeros(500, np.int32),
                               rng.integers(1, 32, 300).astype(np.int32)])
        cols = rng.integers(0, 24, 800).astype(np.int32)
        vals = np.ones(800, np.float32)
        ratings = RatingsCOO(rows, cols, vals, 32, 24)
        params = ALSParams(rank=3, num_iterations=3, reg=0.05, seed=5,
                           implicit_prefs=True, alpha=3.0,
                           history_mode="bucket")
        U_1, V_1 = train_als(ratings, params)
        U_8, V_8 = train_als(ratings, params, mesh=mesh8)
        np.testing.assert_allclose(np.asarray(U_8)[:32],
                                   np.asarray(U_1)[:32], rtol=2e-3,
                                   atol=2e-4)
        np.testing.assert_allclose(np.asarray(V_8)[:24],
                                   np.asarray(V_1)[:24], rtol=2e-3,
                                   atol=2e-4)

    def test_flops_model_counts_buckets(self):
        from predictionio_tpu.models.als import als_flops_per_iter
        from predictionio_tpu.models.als import pack_ratings

        ratings, _, _ = make_synthetic(n_users=16, n_items=12, rank=3,
                                       seed=2)
        p = ALSParams(rank=4, history_mode="bucket")
        packed = pack_ratings(ratings, p)
        f = als_flops_per_iter(packed.user_h, packed.item_h, p)
        # lower bound: both sides' A-outer products over real entries
        nnz = len(ratings.users)
        assert f >= 2 * (2 * nnz * 16)

    def test_bucket_honors_max_history(self):
        # bucket + max_history truncates like pad (same factors)
        ratings, _, _ = make_synthetic(n_users=20, n_items=14, rank=3,
                                       seed=21)
        base = dict(rank=3, num_iterations=3, reg=0.05, seed=5)
        U_p, V_p = train_als(ratings, ALSParams(**base, max_history=4,
                                                history_mode="pad"))
        U_b, V_b = train_als(ratings, ALSParams(**base, max_history=4,
                                                history_mode="bucket"))
        np.testing.assert_allclose(np.asarray(U_b)[:20],
                                   np.asarray(U_p)[:20], rtol=2e-3,
                                   atol=2e-4)
        # and the packing itself kept no more than max_history per row
        from predictionio_tpu.ops.ragged import (
            pack_histories_bucketed_device,
        )

        h = pack_histories_bucketed_device(
            ratings.users, ratings.items, ratings.ratings,
            ratings.n_users, max_len=4)
        assert all(int(np.asarray(b.counts).max(initial=0)) <= 4
                   for b in h.buckets)

    def test_mega_row_bucket_shards_history_axis(self, mesh8):
        # a 1-real-row bucket on an 8-device mesh must shard L, not rows
        from predictionio_tpu.models.als import _blocked_bucket
        from predictionio_tpu.ops.ragged import (
            pack_histories_bucketed_device,
        )

        rows = np.zeros(512, np.int32)  # one mega row, L=512
        cols = np.arange(512, dtype=np.int32) % 40
        vals = np.ones(512, np.float32)
        h = pack_histories_bucketed_device(rows, cols, vals, 1,
                                           pad_rows_to=8)
        bk = _blocked_bucket(h, 8, mesh8)
        mega = [b for b in bk["buckets"] if b["idx"].shape[-1] >= 512]
        assert mega, [b["idx"].shape for b in bk["buckets"]]
        # L-sharded layout keeps the row axes unsharded: [1, n_bk, L]
        assert mega[0]["idx"].shape[0] == 1


class TestSplitModeWarning:
    """Round-3 (VERDICT r2 weak #8): opting into split mode warns about
    the measured TPU scatter-serialization hazard."""

    def test_split_mode_warns(self):
        import warnings

        from predictionio_tpu.models.als import (
            ALSParams, RatingsCOO, pack_ratings)

        rng = np.random.default_rng(0)
        coo = RatingsCOO(rng.integers(0, 20, 200).astype(np.int32),
                         rng.integers(0, 30, 200).astype(np.int32),
                         np.ones(200, np.float32), 20, 30)
        with warnings.catch_warnings(record=True) as w:
            warnings.simplefilter("always")
            pack_ratings(coo, ALSParams(history_mode="split"))
        assert any("bucket" in str(x.message) for x in w)

    def test_bucket_mode_does_not_warn(self):
        import warnings

        from predictionio_tpu.models.als import (
            ALSParams, RatingsCOO, pack_ratings)

        rng = np.random.default_rng(0)
        coo = RatingsCOO(rng.integers(0, 20, 200).astype(np.int32),
                         rng.integers(0, 30, 200).astype(np.int32),
                         np.ones(200, np.float32), 20, 30)
        with warnings.catch_warnings(record=True) as w:
            warnings.simplefilter("always")
            pack_ratings(coo, ALSParams(history_mode="bucket"))
        assert not [x for x in w if "serialize" in str(x.message)]


class TestColumnarRatingsSource:
    """Sharded partial reads off a ColumnarBatch (VERDICT r2 task 5)."""

    def _batch(self, nnz=700, n_users=40, n_items=25, seed=2):
        from predictionio_tpu.data.columnar import (
            ColumnarDicts,
            columnar_from_columns,
        )
        rng = np.random.default_rng(seed)
        u = rng.integers(0, n_users, nnz)
        i = rng.integers(0, n_items, nnz)
        r = rng.integers(1, 6, nnz).astype(np.float64)
        batch = columnar_from_columns(
            ColumnarDicts(), ["rate"] * nnz, ["user"] * nnz,
            [f"u{x}" for x in u], ["item"] * nnz,
            [f"i{x}" for x in i], np.arange(nnz, dtype=np.int64),
            [None] * nnz, float_props=())
        batch.float_props["rating"] = r
        return batch

    def test_shard_reads_cover_exactly_the_log(self):
        from predictionio_tpu.models.data import (
            ColumnarRatingsSource,
            ratings_from_columnar,
        )
        batch = self._batch()
        src = ColumnarRatingsSource(batch, chunk=64)
        ref, uids, iids = ratings_from_columnar(batch)
        assert src.n_users == ref.n_users
        assert src.n_items == ref.n_items
        # union of disjoint shards == the full log, no dup/loss
        got = []
        bounds = np.linspace(0, src.n_users, 4).astype(int)
        for a, b in zip(bounds[:-1], bounds[1:]):
            rows, cols, vals = src.read_rows("user", a, b)
            assert ((rows >= a) & (rows < b)).all()
            got.append((rows, cols, vals))
        rows = np.concatenate([g[0] for g in got])
        cols = np.concatenate([g[1] for g in got])
        vals = np.concatenate([g[2] for g in got])
        assert sorted(zip(rows, cols, vals)) == \
            sorted(zip(ref.users, ref.items, ref.ratings))
        # item side mirrors
        r2, c2, v2 = src.read_rows("item", 0, src.n_items)
        assert sorted(zip(r2, c2, v2)) == \
            sorted(zip(ref.items, ref.users, ref.ratings))
        # row_counts agree with a bincount of the reference COO
        np.testing.assert_array_equal(
            src.row_counts("user"),
            np.bincount(ref.users, minlength=ref.n_users))

    def test_sharded_source_single_process_identity(self):
        """ShardedColumnarRatingsSource (v3: storage shard + collective
        shuffle) under ONE process: shard (0, 1) is the whole log, the
        exchange is the identity, and every read must match the plain
        source — including global-storage-order restoration (order
        affects max_history truncation)."""
        from predictionio_tpu.models.data import (
            ColumnarRatingsSource,
            ShardedColumnarRatingsSource,
        )
        batch = self._batch()
        batch.shard_offset = 0
        plain = ColumnarRatingsSource(batch, chunk=64)
        sharded = ShardedColumnarRatingsSource(batch, chunk=64,
                                               exchange_chunk=97)
        assert sharded.n_users == plain.n_users
        assert sharded.n_items == plain.n_items
        np.testing.assert_array_equal(sharded.row_counts("user"),
                                      plain.row_counts("user"))
        for side, a, b in (("user", 7, 23), ("item", 0, plain.n_items)):
            r1, c1, v1 = plain.read_rows(side, a, b)
            r2, c2, v2 = sharded.read_rows(side, a, b)
            np.testing.assert_array_equal(r1, r2)  # exact order match
            np.testing.assert_array_equal(c1, c2)
            np.testing.assert_array_equal(v1, v2)
        mask = np.zeros(plain.n_users, dtype=bool)
        mask[::3] = True
        r1, c1, v1 = plain.read_row_mask("user", mask)
        r2, c2, v2 = sharded.read_row_mask("user", mask)
        np.testing.assert_array_equal(r1, r2)
        np.testing.assert_array_equal(c1, c2)
        np.testing.assert_array_equal(v1, v2)

    def test_buy_weight_and_nan_rating_semantics(self):
        from predictionio_tpu.data.columnar import (
            ColumnarDicts,
            columnar_from_columns,
        )
        from predictionio_tpu.models.data import (
            ColumnarRatingsSource,
            ratings_from_columnar,
        )
        n = 6
        batch = columnar_from_columns(
            ColumnarDicts(),
            ["rate", "buy", "rate", "view", "buy", "rate"],
            ["user"] * n, [f"u{k}" for k in range(n)],
            ["item"] * n, [f"i{k % 2}" for k in range(n)],
            np.arange(n, dtype=np.int64), [None] * n, float_props=())
        batch.float_props["rating"] = np.array(
            [4.0, np.nan, np.nan, 2.0, np.nan, 1.0])
        src = ColumnarRatingsSource(batch)
        ref, _, _ = ratings_from_columnar(batch)
        coo = src.to_coo()
        assert sorted(zip(coo.users, coo.items, coo.ratings)) == \
            sorted(zip(ref.users, ref.items, ref.ratings))
        assert len(coo.users) == 4  # 2 rate + 2 buy; view + NaN-rate drop


class TestPadFusedTrainer:
    """The fused whole-run pad program must match the per-step path."""

    def _coo(self):
        rng = np.random.default_rng(6)
        return RatingsCOO(rng.integers(0, 40, 800).astype(np.int32),
                          rng.integers(0, 25, 800).astype(np.int32),
                          (rng.random(800) * 4 + 1).astype(np.float32),
                          40, 25)

    @pytest.mark.parametrize("implicit", [False, True])
    def test_fused_matches_stepwise(self, tmp_path, implicit):
        coo = self._coo()
        params = ALSParams(rank=6, num_iterations=3, seed=4,
                           history_mode="pad",
                           implicit_prefs=implicit, alpha=8.0)
        U1, V1 = train_als(coo, params)  # fused (no checkpointing)
        # checkpoint_dir forces the per-step path. Same math and order,
        # but the fused program inlines the Gramian into one XLA
        # computation whose fusion reassociates f32 reductions — a few
        # 1e-4-rel ulps of drift per iteration is expected, bitwise
        # equality is not.
        U2, V2 = train_als(coo, params,
                           checkpoint_dir=str(tmp_path / "ck"),
                           checkpoint_every=100)
        np.testing.assert_allclose(np.asarray(U1), np.asarray(U2),
                                   rtol=2e-3, atol=1e-5)
        np.testing.assert_allclose(np.asarray(V1), np.asarray(V2),
                                   rtol=2e-3, atol=1e-5)

    def test_fused_on_mesh(self, mesh8):
        coo = self._coo()
        params = ALSParams(rank=6, num_iterations=3, seed=4,
                           history_mode="pad")
        U1, V1 = train_als(coo, params)
        U8, V8 = train_als(coo, params, mesh=mesh8)
        np.testing.assert_allclose(np.asarray(U8), np.asarray(U1),
                                   rtol=2e-3, atol=2e-4)

    def test_mixed_pad_bucket_fused(self):
        """history_mode='auto' can resolve pad on one side and bucket on
        the other (per-side skew); the unified fused trainer must handle
        the mix and agree with the uniform layouts."""
        from predictionio_tpu.models.als import PackedRatings, pack_ratings
        from predictionio_tpu.ops.ragged import (
            pack_histories_bucketed_device,
            pack_histories_device,
        )

        coo = self._coo()
        params = ALSParams(rank=6, num_iterations=3, seed=4,
                           implicit_prefs=True, alpha=8.0)
        counts_u = np.bincount(coo.users, minlength=coo.n_users)
        user_h = pack_histories_device(
            coo.users, coo.items, coo.ratings, coo.n_users,
            max_len=int(counts_u.max()), pad_rows_to=1)
        item_h = pack_histories_bucketed_device(
            coo.items, coo.users, coo.ratings, coo.n_items,
            pad_rows_to=1)
        mixed = PackedRatings(user_h=user_h, item_h=item_h, mesh=None,
                              n_users=coo.n_users, n_items=coo.n_items)
        Um, Vm = train_als(coo, params, packed=mixed)
        import dataclasses
        Ub, Vb = train_als(coo, dataclasses.replace(
            params, history_mode="bucket"))
        np.testing.assert_allclose(np.asarray(Um)[:coo.n_users],
                                   np.asarray(Ub)[:coo.n_users],
                                   rtol=2e-3, atol=2e-4)
        np.testing.assert_allclose(np.asarray(Vm)[:coo.n_items],
                                   np.asarray(Vb)[:coo.n_items],
                                   rtol=2e-3, atol=2e-4)


class TestAutoLayoutWasteBound:
    def test_skewed_but_under_cap_picks_bucket(self):
        """auto layout must bound padding WASTE, not just absolute
        size: a 5%-sample eval fold padded 0.5M entries into 33M slots
        per side (30x waste) and exhausted device memory (round 4).
        Skewed counts under the absolute cap now go bucketed."""
        from predictionio_tpu.models.als import _pack
        from predictionio_tpu.ops.ragged import (
            BucketedHistories,
            PaddedHistories,
        )

        rng = np.random.default_rng(0)
        n_rows, nnz = 30_000, 400_000
        # one mega-row (L_full ~ 4k) over a light tail: slots ~ 123M
        # (< 200M cap) but waste ~ 300x
        rows = rng.integers(0, n_rows, nnz).astype(np.int32)
        rows[:4_000] = 7
        cols = rng.integers(0, 1000, nnz).astype(np.int32)
        vals = np.ones(nnz, np.float32)
        params = ALSParams(rank=4, history_mode="auto")
        h = _pack(rows, cols, vals, n_rows, params, n_dev=1)
        assert isinstance(h, BucketedHistories)

        # dense counts (waste <= 4x) still take the simpler pad path
        rows_d = np.repeat(np.arange(2000, dtype=np.int32), 50)
        cols_d = rng.integers(0, 100, len(rows_d)).astype(np.int32)
        h2 = _pack(rows_d, cols_d, np.ones(len(rows_d), np.float32),
                   2000, params, n_dev=1)
        assert isinstance(h2, PaddedHistories)

    def test_packs_are_host_resident(self):
        """Packed layouts live on HOST; only PackedRatings.blocked()
        ships mesh-shaped copies to the device (keeping both doubled
        HBM per pack — the round-4 eval OOM)."""
        from predictionio_tpu.models.als import _pack
        rng = np.random.default_rng(1)
        rows = rng.integers(0, 500, 20_000).astype(np.int32)
        cols = rng.integers(0, 300, 20_000).astype(np.int32)
        vals = np.ones(20_000, np.float32)
        for mode in ("pad", "bucket", "split"):
            import warnings

            with warnings.catch_warnings():
                warnings.simplefilter("ignore")
                h = _pack(rows, cols, vals, 500,
                          ALSParams(rank=4, history_mode=mode), n_dev=1)
            arrs = []
            if hasattr(h, "buckets"):
                for b in h.buckets:
                    arrs += [b.indices, b.values]
            else:
                arrs += [h.indices, h.values]
            for a in arrs:
                assert isinstance(a, np.ndarray), (mode, type(a))


class TestGatherDtype:
    """gather_dtype='bfloat16' (round 4): factor rows are gathered from
    a bf16 shadow of the f32 table — master weights, gram accumulation
    and solves stay f32. Must stay CLOSE to the f32 run on every
    layout, and must not damage ranking quality."""

    def _coo(self, seed=0):
        coo, _, _ = make_synthetic(n_users=120, n_items=80, rank=4,
                                   density=0.3, seed=seed)
        return coo

    @pytest.mark.parametrize("mode", ["pad", "bucket", "split"])
    def test_close_to_f32_per_layout(self, mode):
        coo = self._coo()
        kw = dict(rank=6, num_iterations=3, seed=4, history_mode=mode,
                  implicit_prefs=True, alpha=8.0)
        U1, V1 = train_als(coo, ALSParams(**kw))
        U2, V2 = train_als(coo, ALSParams(**kw,
                                          gather_dtype="bfloat16"))
        # bf16 mantissa is 8 bits: inputs perturbed ~4e-3 relative;
        # after 3 alternating solves the factors drift accordingly
        np.testing.assert_allclose(np.asarray(U2), np.asarray(U1),
                                   rtol=0.1, atol=0.02)
        np.testing.assert_allclose(np.asarray(V2), np.asarray(V1),
                                   rtol=0.1, atol=0.02)

    def test_ranking_quality_preserved(self):
        # reconstruction quality of the completed matrix must match the
        # f32 run to noise level: rank the held-out positives
        coo, full, mask = make_synthetic(n_users=120, n_items=80,
                                         rank=4, density=0.3, seed=1)
        kw = dict(rank=4, num_iterations=8, seed=3, reg=0.05)

        def rmse(gd):
            U, V = train_als(coo, ALSParams(**kw, gather_dtype=gd))
            rec = np.asarray(U)[:coo.n_users] @ np.asarray(V)[:coo.n_items].T
            return float(np.sqrt(np.mean((rec[mask] - full[mask]) ** 2)))

        r32 = rmse("float32")
        r16 = rmse("bfloat16")
        assert r16 < r32 * 1.05 + 1e-3, (r32, r16)

    def test_checkpoint_fingerprint_distinct(self, tmp_path):
        coo = self._coo()
        kw = dict(rank=4, num_iterations=2, seed=3)
        d = str(tmp_path / "ck")
        train_als(coo, ALSParams(**kw), checkpoint_dir=d,
                  checkpoint_every=1)
        with pytest.raises(ValueError, match="different"):
            train_als(coo, ALSParams(**kw, gather_dtype="bfloat16"),
                      checkpoint_dir=d, checkpoint_every=1)
