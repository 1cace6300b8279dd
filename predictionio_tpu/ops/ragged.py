"""Ragged→dense packing for TPU-friendly layouts.

Event logs are ragged and string-keyed (SURVEY §7 hard part 2): each user
has a variable-length rating history. XLA wants static shapes, so the host
packs COO ratings into padded per-row histories once, before the training
loop — ``[n_rows, max_len]`` index + weight matrices where padding carries
weight 0 and a sentinel index that still gathers safely. The device never
sees ragged data; the train loop is pure static-shape array code.
"""

from __future__ import annotations

import logging
from dataclasses import dataclass
from typing import Optional, Tuple

import numpy as np

log = logging.getLogger(__name__)

#: With no explicit cap, the dense [n_rows, max_len] matrices are bounded
#: to this many entries; beyond it the longest histories are truncated to
#: the smallest length covering 99.9% of rows (skew guard: one heavy item
#: must not inflate every row — MovieLens-20M's top item has ~100k raters).
AUTO_CAP_ENTRIES = 200_000_000


def _host(*arrays) -> tuple:
    """Explicit host landing for pack results.

    The only device-resident form of a pack should be the BLOCKED
    (mesh-shaped) copies training actually reads
    (``PackedRatings.blocked``); keeping the raw pack on device too made
    every pack live twice in HBM — measured as the eval sweep's
    RESOURCE_EXHAUSTED with fold packs held by the fast-eval cache. All
    intentional D2H transfers of this module funnel through here, so
    the hot-path lint has exactly one blessed sync site.
    """
    # ptpu: allow[host-sync-in-hot-path] — the pack's one intended D2H
    return tuple(np.asarray(a) for a in arrays)


def _c_contig(arr: np.ndarray, dtype) -> np.ndarray:
    """Contiguous host buffer for the native codec (host→host: inputs
    are already numpy when the native lane is reachable)."""
    # ptpu: allow[host-sync-in-hot-path] — C++ codec needs C buffers
    return np.ascontiguousarray(arr, dtype=dtype)


@dataclass(frozen=True)
class PaddedHistories:
    """Per-row padded histories: ``indices[i, k]`` is the k-th counterpart
    id for row i (0-padded), ``values[i, k]`` its rating (0-padded), and
    ``counts[i]`` the true history length."""

    indices: np.ndarray  # [n_rows, max_len] int32
    values: np.ndarray   # [n_rows, max_len] float32
    counts: np.ndarray   # [n_rows] int32

    @property
    def n_rows(self) -> int:
        return self.indices.shape[0]

    @property
    def max_len(self) -> int:
        return self.indices.shape[1]


def pack_histories(rows: np.ndarray, cols: np.ndarray, vals: np.ndarray,
                   n_rows: int, max_len: Optional[int] = None,
                   pad_rows_to: int = 1) -> PaddedHistories:
    """Pack COO triples into row-major padded histories.

    ``max_len`` caps history length (longest-kept-first is NOT applied;
    entries beyond the cap are dropped in input order — callers wanting
    recency should pre-sort). ``pad_rows_to`` rounds the row count up so
    the leading axis divides evenly across mesh shards.
    """
    order = np.argsort(rows, kind="stable")
    rows_s, cols_s, vals_s = rows[order], cols[order], vals[order]
    counts = np.bincount(rows_s, minlength=n_rows).astype(np.int32)
    L = resolve_max_len(counts, n_rows, max_len)

    n_pad = ((n_rows + pad_rows_to - 1) // pad_rows_to) * pad_rows_to
    indices = np.zeros((n_pad, L), dtype=np.int32)
    values = np.zeros((n_pad, L), dtype=np.float32)

    # position of each entry within its row
    starts = np.zeros(n_rows + 1, dtype=np.int64)
    np.cumsum(counts, out=starts[1:])
    pos_in_row = np.arange(len(rows_s)) - starts[rows_s]
    keep = pos_in_row < L
    indices[rows_s[keep], pos_in_row[keep]] = cols_s[keep]
    values[rows_s[keep], pos_in_row[keep]] = vals_s[keep]
    kept_counts = np.minimum(counts, L)
    out_counts = np.zeros(n_pad, dtype=np.int32)
    out_counts[:n_rows] = kept_counts
    return PaddedHistories(indices=indices, values=values, counts=out_counts)


def transpose_coo(rows: np.ndarray, cols: np.ndarray, vals: np.ndarray
                  ) -> Tuple[np.ndarray, np.ndarray, np.ndarray]:
    """Swap the roles of rows and cols (users↔items)."""
    return cols, rows, vals


@dataclass(frozen=True)
class SplitHistories:
    """Row-split packing: every real row longer than ``max_len`` becomes
    ⌈count/L⌉ *virtual rows* of up to L entries each, so **no entry is
    ever dropped** regardless of skew (MLlib uses every rating —
    ``ALSAlgorithm.scala:75-85``; a zipf item catalog must too). The ALS
    update computes per-virtual-row normal-equation partials and
    scatter-adds them onto the owning real row before solving.

    ``indices/values`` are ``[n_virtual_pad, L]`` like
    :class:`PaddedHistories`; ``counts`` holds per-*virtual*-row entry
    counts; ``row_ids[v]`` is the real row owning virtual row v
    (``n_rows`` sentinel on padding rows — scatter mode="drop" territory);
    ``real_counts`` are true per-real-row totals (regularization scaling).
    """

    indices: np.ndarray      # [n_virtual_pad, L] int32
    values: np.ndarray       # [n_virtual_pad, L] float32
    counts: np.ndarray       # [n_virtual_pad] int32 (per virtual row)
    row_ids: np.ndarray      # [n_virtual_pad] int32 → real row (or n_rows)
    real_counts: np.ndarray  # [n_rows_pad] int32
    n_rows: int              # real rows (unpadded)

    @property
    def n_virtual(self) -> int:
        return self.indices.shape[0]

    @property
    def n_rows_padded(self) -> int:
        return self.real_counts.shape[0]

    @property
    def max_len(self) -> int:
        return self.indices.shape[1]


def split_layout(counts: np.ndarray, max_len: int,
                 pad_rows_to: int = 1) -> Tuple[np.ndarray, int, int]:
    """Host-side split bookkeeping: per-real-row virtual-row counts, the
    total virtual rows, and the padded virtual row count. Split shapes are
    data-dependent, so this must run on the host before the static-shape
    device pack."""
    groups = -(-counts // max_len)  # ceil; 0-count rows get 0 virtual rows
    n_virtual = int(groups.sum())
    n_vpad = max(((n_virtual + pad_rows_to - 1) // pad_rows_to)
                 * pad_rows_to, pad_rows_to)
    return groups.astype(np.int64), n_virtual, n_vpad


def pack_histories_split(rows: np.ndarray, cols: np.ndarray,
                         vals: np.ndarray, n_rows: int, max_len: int,
                         pad_rows_to: int = 1) -> SplitHistories:
    """Host-numpy split packing (see :class:`SplitHistories`)."""
    L = max(int(max_len), 1)
    order = np.argsort(rows, kind="stable")
    rs, cs, vs = rows[order], cols[order], vals[order]
    counts = np.bincount(rs, minlength=n_rows).astype(np.int64)
    groups, n_virtual, n_vpad = split_layout(counts, L, pad_rows_to)
    gstarts = np.zeros(n_rows + 1, dtype=np.int64)
    np.cumsum(groups, out=gstarts[1:])

    starts = np.zeros(n_rows + 1, dtype=np.int64)
    np.cumsum(counts, out=starts[1:])
    pos = np.arange(len(rs)) - starts[rs]
    vrow = gstarts[rs] + pos // L
    vpos = pos % L

    indices = np.zeros((n_vpad, L), dtype=np.int32)
    values = np.zeros((n_vpad, L), dtype=np.float32)
    indices[vrow, vpos] = cs
    values[vrow, vpos] = vs

    row_ids = np.full(n_vpad, n_rows, dtype=np.int32)
    row_ids[:n_virtual] = np.repeat(
        np.arange(n_rows, dtype=np.int32), groups)
    vcounts = np.zeros(n_vpad, dtype=np.int32)
    # entries in virtual row v of row r: min(L, count_r - k·L)
    k_within = np.arange(n_virtual) - gstarts[row_ids[:n_virtual]]
    vcounts[:n_virtual] = np.minimum(
        counts[row_ids[:n_virtual]] - k_within * L, L).astype(np.int32)

    n_rows_pad = max(((n_rows + pad_rows_to - 1) // pad_rows_to)
                     * pad_rows_to, pad_rows_to)
    real_counts = np.zeros(n_rows_pad, dtype=np.int32)
    real_counts[:n_rows] = counts
    return SplitHistories(indices=indices, values=values, counts=vcounts,
                          row_ids=row_ids, real_counts=real_counts,
                          n_rows=n_rows)


def pack_histories_split_device(rows: np.ndarray, cols: np.ndarray,
                                vals: np.ndarray, n_rows: int,
                                max_len: int,
                                pad_rows_to: int = 1) -> SplitHistories:
    """Device-side split packing: the host computes only the cheap
    bincount-derived layout (shapes must be static); the heavy sort +
    scatters run as one jitted XLA program, mirroring
    :func:`pack_histories_device`."""
    import jax.numpy as jnp

    L = max(int(max_len), 1)
    # COO triples may arrive as device arrays: land rows ONCE here for
    # the host-side layout math (shapes must be static), instead of a
    # fresh implicit transfer per use. ptpu: allow[host-sync-in-hot-path]
    rows = np.asarray(rows)
    counts_h = np.bincount(rows, minlength=n_rows)
    groups, n_virtual, n_vpad = split_layout(counts_h, L, pad_rows_to)
    n_rows_pad = max(((n_rows + pad_rows_to - 1) // pad_rows_to)
                     * pad_rows_to, pad_rows_to)
    idx, val, vcnt, row_ids, real_counts = _pack_split_on_device(
        jnp.asarray(rows, dtype=jnp.int32),
        jnp.asarray(cols, dtype=jnp.int32),
        jnp.asarray(vals, dtype=jnp.float32),
        jnp.asarray(groups, dtype=jnp.int32),
        n_rows=n_rows, L=L, n_vpad=n_vpad, n_virtual=n_virtual,
        n_rows_pad=n_rows_pad)
    # host-land for the same reason as the bucketed pack: only the
    # blocked copies belong in HBM
    idx, val, vcnt, row_ids, real_counts = _host(
        idx, val, vcnt, row_ids, real_counts)
    return SplitHistories(indices=idx, values=val, counts=vcnt,
                          row_ids=row_ids, real_counts=real_counts,
                          n_rows=n_rows)


def _pack_split_on_device(r, c, v, groups, *, n_rows: int, L: int,
                          n_vpad: int, n_virtual: int, n_rows_pad: int):
    import jax

    global _pack_split_jit
    if _pack_split_jit is None:
        import jax.numpy as jnp

        def pack(r, c, v, groups, n_rows, L, n_vpad, n_virtual,
                 n_rows_pad):
            nnz = r.shape[0]
            order = jnp.argsort(r, stable=True)
            rs, cs, vs = r[order], c[order], v[order]
            counts = jnp.bincount(rs, length=n_rows).astype(jnp.int32)
            starts = jnp.concatenate(
                [jnp.zeros(1, jnp.int32),
                 jnp.cumsum(counts, dtype=jnp.int32)])
            gstarts = jnp.concatenate(
                [jnp.zeros(1, jnp.int32),
                 jnp.cumsum(groups, dtype=jnp.int32)])
            pos = jnp.arange(nnz, dtype=jnp.int32) - starts[rs]
            vrow = gstarts[rs] + pos // L
            vpos = pos % L
            flat = vrow * jnp.int32(L) + vpos
            idx = jnp.zeros(n_vpad * L, jnp.int32).at[flat].set(
                cs, mode="drop")
            val = jnp.zeros(n_vpad * L, jnp.float32).at[flat].set(
                vs, mode="drop")
            owners = jnp.repeat(jnp.arange(n_rows, dtype=jnp.int32),
                                groups, total_repeat_length=n_virtual)
            row_ids = jnp.full(n_vpad, n_rows, jnp.int32) \
                .at[jnp.arange(n_virtual)].set(owners)
            k_within = jnp.arange(n_vpad, dtype=jnp.int32) \
                - gstarts[jnp.minimum(row_ids, n_rows - 1)]
            vcnt = jnp.where(
                row_ids < n_rows,
                jnp.minimum(counts[jnp.minimum(row_ids, n_rows - 1)]
                            - k_within * L, L), 0).astype(jnp.int32)
            real_counts = jnp.zeros(n_rows_pad, jnp.int32).at[:n_rows].set(
                counts)
            return (idx.reshape(n_vpad, L), val.reshape(n_vpad, L), vcnt,
                    row_ids, real_counts)

        _pack_split_jit = jax.jit(
            pack, static_argnames=("n_rows", "L", "n_vpad", "n_virtual",
                                   "n_rows_pad"))
    return _pack_split_jit(r, c, v, groups, n_rows=n_rows, L=L,
                           n_vpad=n_vpad, n_virtual=n_virtual,
                           n_rows_pad=n_rows_pad)


_pack_split_jit = None


@dataclass(frozen=True)
class HistoryBucket:
    """One length class of a :class:`BucketedHistories` layout: all rows
    whose history fits L (and not L/2). ``row_ids[j]`` is the real row
    that bucket-row j belongs to (``n_rows_padded`` sentinel on padding
    rows); each real row appears in AT MOST ONE bucket, so writing the
    per-bucket solve results back is a unique-index scatter — no
    duplicate-index scatter-add anywhere (TPU serializes those)."""

    length: int
    indices: np.ndarray   # [n_bk_pad, L] int32
    values: np.ndarray    # [n_bk_pad, L] float32
    counts: np.ndarray    # [n_bk_pad] int32 (true history length)
    row_ids: np.ndarray   # [n_bk_pad] int32

    @property
    def n_rows(self) -> int:
        return self.indices.shape[0]


@dataclass(frozen=True)
class BucketedHistories:
    """Drop-free dense layout for skewed histories: each row is padded to
    the next power of two of its own length (≤2× padding waste) instead
    of a single global ``max_len``. Besides never dropping entries (MLlib
    parity — ``ALSAlgorithm.scala:75-85``), per-bucket updates give every
    normal-equation einsum a contraction depth K = L_bucket, where the
    single-L split layout forced the small L that minimizes padding —
    and tiny K starves the MXU."""

    buckets: tuple          # of HistoryBucket, ascending length
    n_rows: int
    n_rows_padded: int

    @property
    def padded_entries(self) -> int:
        return sum(b.n_rows * b.length for b in self.buckets)

    @property
    def max_len(self) -> int:
        return max((b.length for b in self.buckets), default=1)


def bucket_layout(counts: np.ndarray, min_len: int = 8,
                  pad_rows_to: int = 1, max_len: Optional[int] = None):
    """Host-side bucket planning: per-row bucket length (next pow2 of the
    row's count, floored at ``min_len``, optionally capped at
    ``max_len`` — capped rows TRUNCATE like the pad layout), member rows
    per bucket, and the flat destination offset of every row's first
    slot."""
    n_rows = len(counts)
    if max_len is not None:
        counts = np.minimum(counts, max_len)
    lengths = np.maximum(min_len, 1 << np.int64(
        np.ceil(np.log2(np.maximum(counts, 1)))))
    lengths[counts == 0] = 0  # empty rows join no bucket
    plan = []
    row_base = np.zeros(n_rows, dtype=np.int64)
    off = 0
    for L in np.unique(lengths):
        if L == 0:
            continue
        rows_k = np.flatnonzero(lengths == L)
        n_bk = len(rows_k)
        n_bk_pad = max(-(-n_bk // pad_rows_to) * pad_rows_to, pad_rows_to)
        row_base[rows_k] = off + np.arange(n_bk, dtype=np.int64) * int(L)
        plan.append((int(L), rows_k, n_bk_pad, off))
        off += n_bk_pad * int(L)
    return plan, row_base, off  # off == total flat slots S


def pack_histories_bucketed_device(rows: np.ndarray, cols: np.ndarray,
                                   vals: np.ndarray, n_rows: int,
                                   pad_rows_to: int = 1,
                                   min_len: int = 8,
                                   max_len: Optional[int] = None,
                                   counts: Optional[np.ndarray] = None
                                   ) -> BucketedHistories:
    """Pack COO triples into the bucketed layout with ONE compiled
    scatter (host work is bincount + per-row offset arithmetic): sort by
    row on device, scatter each entry to ``row_base[row] + pos_in_row``
    in a flat buffer, then carve per-bucket views. ``max_len`` caps each
    row's history (truncating in input order, pad-layout semantics);
    without it the layout is drop-free."""
    import jax.numpy as jnp

    # single host landing for the layout math (see the split pack)
    rows = np.asarray(rows)  # ptpu: allow[host-sync-in-hot-path]
    if counts is None:  # callers that already histogrammed pass it in
        counts = np.bincount(rows, minlength=n_rows)
    if max_len is not None:
        counts = np.minimum(counts, int(max_len))
    plan, row_base, S = bucket_layout(counts, min_len, pad_rows_to)
    n_rows_pad = max(-(-n_rows // pad_rows_to) * pad_rows_to, pad_rows_to)
    if S == 0:
        return BucketedHistories(buckets=(), n_rows=n_rows,
                                 n_rows_padded=n_rows_pad)
    if S >= 2 ** 31:  # pragma: no cover — would need >1B ratings
        raise ValueError(f"bucketed layout needs {S} slots (> int32); "
                         "shard the dataset across hosts first")
    flat = _pack_flat_native(rows, cols, vals, row_base, counts,
                             n_rows, S)
    if flat is None:
        flat = _pack_flat_on_device(
            jnp.asarray(rows, dtype=jnp.int32),
            jnp.asarray(cols, dtype=jnp.int32),
            jnp.asarray(vals, dtype=jnp.float32),
            jnp.asarray(row_base, dtype=jnp.int32),
            jnp.asarray(counts, dtype=jnp.int32),  # post-cap budget
            n_rows=n_rows, S=S)
    # land the packed layout on HOST (see _host for why)
    flat_idx, flat_val = _host(flat[0], flat[1])
    buckets = []
    for L, rows_k, n_bk_pad, off in plan:
        n_bk = len(rows_k)
        # each padding row gets a DISTINCT out-of-range sentinel: the
        # result-writeback scatter promises unique_indices=True, and a
        # shared sentinel would make that promise false (UB per the JAX
        # scatter contract) even though the rows drop
        row_ids = (n_rows_pad
                   + np.arange(n_bk_pad, dtype=np.int64) - n_bk
                   ).astype(np.int32)
        row_ids[:n_bk] = rows_k
        cnt = np.zeros(n_bk_pad, dtype=np.int32)
        cnt[:n_bk] = counts[rows_k]
        buckets.append(HistoryBucket(
            length=L,
            indices=flat_idx[off:off + n_bk_pad * L].reshape(n_bk_pad, L),
            values=flat_val[off:off + n_bk_pad * L].reshape(n_bk_pad, L),
            counts=cnt, row_ids=row_ids))
    return BucketedHistories(buckets=tuple(buckets), n_rows=n_rows,
                             n_rows_padded=n_rows_pad)


def _pack_flat_native(rows, cols, vals, row_base, row_cap, n_rows: int,
                      S: int):
    """Host C++ counting-sort pack (``native/_codec.cpp pack_flat``), or
    None when the extension is unavailable. Same contract as
    :func:`_pack_flat_on_device` but the flat buffers are born on the
    host — which is where the bucket carving wants them anyway, so the
    device round-trip (~240MB H2D + ~320MB D2H at ML-20M scale, plus
    two program compiles) disappears."""
    from ..native import codec

    mod = codec()
    if mod is None or not hasattr(mod, "pack_flat"):
        return None
    r32 = _c_contig(rows, np.int32)
    c32 = _c_contig(cols, np.int32)
    v32 = _c_contig(vals, np.float32)
    b32 = _c_contig(row_base, np.int32)
    k32 = _c_contig(row_cap, np.int32)
    ib, vb = mod.pack_flat(r32, c32, v32, b32, k32, int(n_rows), int(S))
    return (np.frombuffer(ib, dtype=np.int32),
            np.frombuffer(vb, dtype=np.float32))


def _pack_flat_on_device(r, c, v, row_base, row_cap, *, n_rows: int,
                         S: int):
    import jax

    global _pack_flat_jit
    if _pack_flat_jit is None:
        import jax.numpy as jnp

        def pack(r, c, v, row_base, row_cap, n_rows, S):
            # int32 throughout: S and nnz stay < 2^31 (S ≤ ~2·nnz by the
            # ≤2× pow2-padding bound; the flat buffer is range-checked on
            # the host before this program is built)
            nnz = r.shape[0]
            order = jnp.argsort(r, stable=True)
            rs, cs, vs = r[order], c[order], v[order]
            counts = jnp.bincount(rs, length=n_rows).astype(jnp.int32)
            starts = jnp.concatenate(
                [jnp.zeros(1, jnp.int32),
                 jnp.cumsum(counts, dtype=jnp.int32)])
            pos = jnp.arange(nnz, dtype=jnp.int32) - starts[rs]
            # entries past a row's (possibly max_len-capped) budget drop;
            # without a cap pos < row_cap always holds
            dest = jnp.where(pos < row_cap[rs], row_base[rs] + pos,
                             jnp.int32(S))
            # no unique_indices promise: capped entries all alias the
            # OOB sentinel S (they drop, but the promise would be a lie)
            idx = jnp.zeros(S, jnp.int32).at[dest].set(cs, mode="drop")
            val = jnp.zeros(S, jnp.float32).at[dest].set(vs, mode="drop")
            return idx, val

        _pack_flat_jit = jax.jit(pack, static_argnames=("n_rows", "S"))
    return _pack_flat_jit(r, c, v, row_base, row_cap, n_rows=n_rows, S=S)


_pack_flat_jit = None


def resolve_max_len(counts: np.ndarray, n_rows: int,
                    max_len: Optional[int]) -> int:
    """Padded history length: the explicit cap, or the longest row with
    the 99.9th-percentile auto-cap (warning when entries get dropped)."""
    if max_len is not None:
        return max(int(max_len), 1)
    L = int(counts.max(initial=1))
    if n_rows * L > AUTO_CAP_ENTRIES:
        capped = int(np.quantile(counts, 0.999)) or 1
        capped = max(capped, AUTO_CAP_ENTRIES // max(n_rows, 1))
        if capped < L:
            dropped = int(np.maximum(counts - capped, 0).sum())
            log.warning(
                "pack_histories: capping history length %d → %d "
                "(99.9th pct; dense layout would be %d×%d); dropping "
                "%d/%d entries from the heaviest rows. Set max_len to "
                "override.", L, capped, n_rows, L, dropped,
                int(counts.sum()))
            L = capped
    return max(L, 1)


def pack_histories_device(rows: np.ndarray, cols: np.ndarray,
                          vals: np.ndarray, n_rows: int, max_len: int,
                          pad_rows_to: int = 1) -> PaddedHistories:
    """Device-side :func:`pack_histories`: one jitted sort + scatter.

    Packing 20M MovieLens-shaped entries takes ~10s of host numpy
    (argsort + fancy-index scatters) but milliseconds as a compiled XLA
    program, so the COO triples ship to the device raw and the padded
    layout is built there. Semantics match the host packer: stable
    within-row input order, entries beyond ``max_len`` dropped, rows
    padded to a ``pad_rows_to`` multiple.

    Returns the padded arrays as ``jax.Array``s still resident on device
    (duck-typed into ``PaddedHistories``) so the training loop can shard
    them without a host round-trip.
    """
    import jax.numpy as jnp

    L = max(int(max_len), 1)
    n_pad = ((n_rows + pad_rows_to - 1) // pad_rows_to) * pad_rows_to
    # single host landing for the layout math (see the split pack)
    rows = np.asarray(rows)  # ptpu: allow[host-sync-in-hot-path]
    # native host pack first (no device round-trip, no pack compile)
    base = np.arange(n_rows, dtype=np.int64) * L
    if n_pad * L < 2 ** 31:
        flat = _pack_flat_native(
            rows, cols, vals, base,
            np.full(n_rows, L, dtype=np.int32), n_rows, n_pad * L)
    else:  # pragma: no cover — >2^31 slots needs the device path
        flat = None
    if flat is not None:
        counts = np.bincount(rows, minlength=n_rows)
        cnt = np.zeros(n_pad, np.int32)
        cnt[:n_rows] = np.minimum(counts, L)
        return PaddedHistories(indices=flat[0].reshape(n_pad, L),
                               values=flat[1].reshape(n_pad, L),
                               counts=cnt)
    idx, val, cnt = _pack_on_device(
        jnp.asarray(rows, dtype=jnp.int32),
        jnp.asarray(cols, dtype=jnp.int32),
        jnp.asarray(vals, dtype=jnp.float32),
        n_rows=n_rows, L=L, n_pad=n_pad)
    # host-land (same reason as the bucketed/split packs; see _host)
    idx, val, cnt = _host(idx, val, cnt)
    return PaddedHistories(indices=idx, values=val, counts=cnt)


def _pack_on_device(r, c, v, *, n_rows: int, L: int, n_pad: int):
    import jax

    global _pack_jit
    if _pack_jit is None:
        import jax.numpy as jnp

        def pack(r, c, v, n_rows, L, n_pad):
            nnz = r.shape[0]
            order = jnp.argsort(r, stable=True)
            rs, cs, vs = r[order], c[order], v[order]
            counts = jnp.bincount(rs, length=n_rows).astype(jnp.int32)
            starts = jnp.concatenate(
                [jnp.zeros(1, jnp.int32),
                 jnp.cumsum(counts, dtype=jnp.int32)])
            pos = jnp.arange(nnz, dtype=jnp.int32) - starts[rs]
            flat = rs * jnp.int32(L) + pos
            oob = jnp.int32(n_pad * L)  # mode="drop" sentinel for pos >= L
            flat = jnp.where(pos < L, flat, oob)
            idx = jnp.zeros(n_pad * L, jnp.int32).at[flat].set(
                cs, mode="drop")
            val = jnp.zeros(n_pad * L, jnp.float32).at[flat].set(
                vs, mode="drop")
            cnt = jnp.zeros(n_pad, jnp.int32).at[:n_rows].set(
                jnp.minimum(counts, L))
            return idx.reshape(n_pad, L), val.reshape(n_pad, L), cnt

        _pack_jit = jax.jit(pack,
                            static_argnames=("n_rows", "L", "n_pad"))
    return _pack_jit(r, c, v, n_rows=n_rows, L=L, n_pad=n_pad)


_pack_jit = None
