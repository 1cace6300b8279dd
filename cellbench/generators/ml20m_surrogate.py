"""The ML-20M surrogate ratings: a copy of
``benchmarks/ml20m_surrogate.py::generate`` (GroupLens ml-20m's
published marginals) without the timestamps, which ALS never reads and
which are drawn last, so users, items and stars are the arrays that
file gives for the same ``data_seed``."""

from __future__ import annotations

import numpy as np

RATING_HISTOGRAM = {
    0.5: 239_125, 1.0: 680_732, 1.5: 279_252, 2.0: 1_430_997,
    2.5: 883_398, 3.0: 4_291_193, 3.5: 2_200_156, 4.0: 5_561_926,
    4.5: 1_534_824, 5.0: 2_898_660,
}
ML20M = {"n_ratings": 20_000_263, "n_users": 138_493, "n_movies": 26_744,
         "top_movie_count": 67_310, "top_user_count": 9_254}


def _sizes_with_exact_total(raw, total, lo, hi, rng):
    sizes = np.clip(np.round(raw).astype(np.int64), lo, hi)
    diff = int(total - sizes.sum())
    step = 1 if diff > 0 else -1
    while diff != 0:
        k = min(abs(diff), len(sizes))
        idx = rng.choice(len(sizes), size=k, replace=False)
        room = (sizes[idx] < hi) if step > 0 else (sizes[idx] > lo)
        sizes[idx[room]] += step
        diff = int(total - sizes.sum())
    return sizes


def _item_popularity(n_movies, total, top, rng, sizes):
    w = np.sort(rng.lognormal(mean=0.0, sigma=2.6, size=n_movies))[::-1]
    p0 = min(top / total, 0.5)
    if top < 0.98 * len(sizes):
        n_u = sizes.astype(np.float64)
        lo, hi = p0, min(64.0 * p0, 0.5)
        for _ in range(60):
            mid = 0.5 * (lo + hi)
            if float(np.sum(1.0 - np.power(1.0 - mid, n_u))) < top:
                lo = mid
            else:
                hi = mid
        p0 = 0.5 * (lo + hi)
    tail = w[1:]
    for _ in range(16):
        p_tail = tail / tail.sum() * (1.0 - p0)
        if p_tail.max() <= p0 * (1.0 + 1e-9):
            break
        np.minimum(tail, tail.max() * 0.7, out=tail)
    p = np.concatenate([[p0], p_tail])
    return p / p.sum()


def _surrogate(scale: float, seed: int):
    """``(users, items, stars, n_users, n_movies)`` as the repo's
    surrogate gives them; ``scale`` shrinks every marginal alike."""
    rng = np.random.default_rng(seed)
    exact = abs(scale - 1.0) < 1e-9
    n_ratings = int(round(ML20M["n_ratings"] * scale))
    n_users = max(int(round(ML20M["n_users"] * scale)), 8)
    n_movies = max(int(round(ML20M["n_movies"] * scale)), 8)
    top_m = max(int(round(ML20M["top_movie_count"] * scale)), 4)
    top_u = max(int(round(ML20M["top_user_count"] * scale)), 4)
    min_per_user = 20 if exact else max(
        int(round(20 * min(1.0, n_ratings / (n_users * 20 * 2)))), 1)

    mean_excess = n_ratings / n_users - min_per_user
    sig_u = 1.5
    mu_u = np.log(max(mean_excess, 1.0)) - sig_u * sig_u / 2.0
    raw = min_per_user + rng.lognormal(mu_u, sig_u, size=n_users)
    hi = min(max(top_u, int(np.ceil(n_ratings / n_users)) + 2), n_movies)
    if n_ratings > n_users * n_movies:
        raise ValueError("more ratings than (user, item) pairs")
    sizes = _sizes_with_exact_total(raw, n_ratings, min_per_user, hi, rng)
    p = _item_popularity(n_movies, n_ratings, top_m, rng, sizes)

    users = np.repeat(np.arange(n_users, dtype=np.int32), sizes)
    items = np.empty(n_ratings, dtype=np.int32)
    offs = np.zeros(n_users + 1, dtype=np.int64)
    np.cumsum(sizes, out=offs[1:])
    heavy = np.flatnonzero(sizes > 500)
    light = np.flatnonzero(sizes <= 500)
    logp = np.log(p + 1e-300)
    for u in heavy:
        n = int(sizes[u])
        g = logp + rng.gumbel(size=n_movies)
        items[offs[u]:offs[u + 1]] = np.argpartition(g, -n)[-n:]
    if len(light):
        idx = (np.flatnonzero(np.isin(users, light))
               if len(light) == n_users else np.concatenate(
                   [np.arange(offs[u], offs[u + 1]) for u in light]))
        need = check = idx
        for _round in range(30):
            items[need] = rng.choice(n_movies, size=len(need), p=p)
            key = users[check].astype(np.int64) * n_movies + items[check]
            order = np.argsort(key, kind="stable")
            dup = np.zeros(len(check), dtype=bool)
            dup[order[1:]] = key[order[1:]] == key[order[:-1]]
            need = check[dup]
            if len(need) == 0:
                break
            check = idx[np.isin(users[idx], np.unique(users[need]))]
        for j in need:  # final repair: uniform over the user's unseen
            u = users[j]
            have = set(items[offs[u]:offs[u + 1]].tolist())
            for cand in rng.permutation(n_movies):
                if int(cand) not in have:
                    items[j] = cand
                    break

    hist = sorted(RATING_HISTOGRAM.items())
    vals = np.concatenate([
        np.full(c if exact else int(round(c * scale)), v, dtype=np.float32)
        for v, c in hist])
    if len(vals) > n_ratings:
        vals = np.sort(vals[rng.choice(len(vals), n_ratings,
                                       replace=False)])
    elif len(vals) < n_ratings:
        extra = rng.choice(
            np.array([v for v, _ in hist], dtype=np.float32),
            n_ratings - len(vals),
            p=np.array([c for _, c in hist], dtype=np.float64)
            / ML20M["n_ratings"])
        vals = np.sort(np.concatenate([vals, extra]))
    pop_rank = p[items] + rng.normal(scale=p.mean() * 8.0, size=n_ratings)
    stars = np.empty(n_ratings, dtype=np.float32)
    stars[np.argsort(pop_rank, kind="stable")] = vals
    return users, items, stars, n_users, n_movies


def cache_name(dataset: dict) -> str:
    return (f"ml20m_s{float(dataset['scale']):g}"
            f"_d{int(dataset['data_seed'])}")


def generate(dataset: dict):
    return _surrogate(float(dataset["scale"]), int(dataset["data_seed"]))
