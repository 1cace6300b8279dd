"""Plain references. Nothing here imports the program.

Training: implicit ALS (Hu, Koren, Volinsky 2008) with ALS-WR's
count-scaled regulariser, one row at a time in float64 numpy: the copy
of ``bench.py::cpu_als_baseline``'s mathematics. A trained table is held
to the equation its last half-iteration solves: row ``j`` of the side
solved last equals ``(G + sum_l alpha r_l f_l f_l^T + reg n I)^-1
sum_l (1 + alpha r_l) f_l`` over the rows ``f`` of the other side's
final table that ``j``'s ratings name.

Serving: the scores of one user against every item, in float32 at
``highest`` precision on the device in blocks, or float64 on the host.
"""

from __future__ import annotations

import numpy as np


def histories(rows: np.ndarray, cols: np.ndarray, vals: np.ndarray,
              sample: np.ndarray):
    """``{row: (cols, vals)}`` for the sampled rows of a COO."""
    sel = np.flatnonzero(np.isin(rows, sample))
    r, c, v = rows[sel], cols[sel], vals[sel]
    order = np.argsort(r, kind="stable")
    r, c, v = r[order], c[order], v[order]
    starts = np.searchsorted(r, sample, side="left")
    ends = np.searchsorted(r, sample, side="right")
    return {int(j): (c[s:e], v[s:e])
            for j, s, e in zip(sample, starts, ends)}


def _normal_equations(fixed: np.ndarray, hist: dict, reg: float,
                      alpha: float, scale_reg: bool):
    """``(row, A, b)`` of each sampled row's normal equations, float64."""
    fx = np.asarray(fixed, dtype=np.float64)
    G = fx.T @ fx
    eye = np.eye(fx.shape[1])
    for j, (cols, vals) in hist.items():
        F = fx[cols]
        c1 = alpha * vals.astype(np.float64)
        A = G + (F * c1[:, None]).T @ F \
            + (reg * max(len(cols), 1) if scale_reg else reg) * eye
        yield j, A, (c1 + 1.0) @ F


def als_rows(fixed: np.ndarray, hist: dict, *, reg: float, alpha: float,
             scale_reg: bool = True) -> dict:
    """Implicit-ALS rows for the sampled histories, holding ``fixed``."""
    return {j: np.linalg.solve(A, b) for j, A, b in
            _normal_equations(fixed, hist, reg, alpha, scale_reg)}


def row_gaps(got: np.ndarray, want: dict) -> np.ndarray:
    """Per sampled row, ``|got - want|_2`` over the larger of the
    reference row's norm and the median reference row's norm (some rows
    are all but zero)."""
    ids = np.fromiter(want.keys(), dtype=np.int64)
    ref = np.stack([want[int(j)] for j in ids])
    norms = np.linalg.norm(ref, axis=1)
    denom = np.maximum(norms, np.median(norms))
    return np.linalg.norm(np.asarray(got, np.float64)[ids] - ref,
                          axis=1) / denom


def topk_scores_fn():
    """The jitted reference for a block of served answers: float32 at
    ``highest`` precision over the whole item table, reduced on the
    device to what the comparison reads. Takes the tables as arguments
    (a closure would bake 5 GB of constants into the program)."""
    import jax
    import jax.numpy as jnp

    @jax.jit
    def scores(U, V, users, served):
        u = U[users]
        ref = jnp.einsum("br,nr->bn", u, V, precision="highest")
        mag = jnp.einsum("br,nr->bn", jnp.abs(u), jnp.abs(V),
                         precision="highest")
        best = jax.lax.top_k(ref, served.shape[1])[0]
        return (jnp.take_along_axis(ref, served, axis=1),
                jnp.take_along_axis(mag, served, axis=1), best)

    return scores


def topk_gaps(served_scores: np.ndarray, ref_at_served: np.ndarray,
              mag_at_served: np.ndarray, ref_best: np.ndarray):
    """One user's answer against the reference.

    Returns ``(score_gap, rank_gap)`` in units of ``2^-7 * sum_i
    |u_i v_ji|`` (PR 21's per-score bound): the widest distance of a
    served score from the reference's score of that item, and the
    widest distance by which the item served at place ``p`` lies under
    the reference's ``p``-th best score."""
    unit = mag_at_served * 2.0 ** -7
    score_gap = np.max(np.abs(served_scores - ref_at_served) / unit)
    rank_gap = np.max((ref_best - ref_at_served) / unit)
    return float(score_gap), float(max(rank_gap, 0.0))


def residuals(got: np.ndarray, fixed: np.ndarray, hist: dict, *,
              reg: float, alpha: float, scale_reg: bool = True) -> np.ndarray:
    """Per sampled row, ``|A v - b|_2 / |b|_2`` in float64 with the
    program's row ``v`` in that row's exact normal equations: what a
    solve is held to, and unlike the distance to the exact row it does
    not grow with the system's condition number."""
    return np.array([
        np.linalg.norm(A @ np.asarray(got[j], dtype=np.float64) - b)
        / np.linalg.norm(b)
        for j, A, b in _normal_equations(fixed, hist, reg, alpha,
                                         scale_reg)])
