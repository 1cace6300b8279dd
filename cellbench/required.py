"""Bytes and operations the ALGORITHM needs, from shapes alone.

Not what the device executes (padding, re-reads, recomputation): a
roofline share says how close a kernel's time is to the least the chip
could take for the work that has to be done.
"""


def als_iteration(n_users: int, n_items: int, n_ratings: int, rank: int,
                  factor_bytes: int = 4) -> dict:
    """One implicit-ALS iteration (both half-steps).

    Bytes: each rating names one row of the other side's table in each
    half-step (``2 * nnz * rank`` factor elements read), each rating's
    index and value are read once per half-step (4 + 4 bytes), each
    table is read once for its Gramian and written once.
    Operations: per rating and half-step the symmetric rank-1 update of
    the normal matrix (``rank * (rank + 1)`` flops) and the right-hand
    side (``2 * rank``); per row a Cholesky factorisation
    (``rank^3 / 3``) and two triangular solves (``2 * rank^2``); per
    table row the symmetric Gramian (``rank * (rank + 1)``)."""
    rows = n_users + n_items
    bytes_ = (2 * n_ratings * rank * factor_bytes
              + 2 * n_ratings * 8
              + 2 * rows * rank * factor_bytes)
    ops = (2 * n_ratings * (rank * (rank + 1) + 2 * rank)
           + rows * (rank ** 3 / 3.0 + 2 * rank ** 2)
           + rows * rank * (rank + 1))
    return {"bytes": float(bytes_), "ops": float(ops)}


def topk_dispatch(item_table_bytes: int, n_items: int, rank: int,
                  batch: float, k: int) -> dict:
    """One batched top-k dispatch: the item table as bound on the device
    is read once (a quantised table counts its own bytes), ``batch``
    user rows are read and ``batch * k`` (id, score) pairs written;
    ``2 * batch * n_items * rank`` flops of scores."""
    bytes_ = item_table_bytes + batch * rank * 4 + batch * k * 8
    return {"bytes": float(bytes_),
            "ops": float(2.0 * batch * n_items * rank)}


def least_seconds(need: dict, peaks: dict) -> dict:
    """The least time the chip could take, and which peak bounds it."""
    t_ops = need["ops"] / peaks["flops"]
    t_bytes = need["bytes"] / peaks["bytes_per_s"]
    return {"seconds": max(t_ops, t_bytes),
            "bound": "bandwidth" if t_bytes >= t_ops else "compute"}
