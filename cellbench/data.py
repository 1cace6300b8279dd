"""Seeded inputs: the configuration's ratings under the seed's
labelling, and entity draws.

Every ``--seed`` sees the SAME bipartite structure under another
labelling: the structure comes from the configuration's ``data_seed``
and the run's seed permutes user ids, item ids and the order of the
ratings. The multiset of history lengths, and with it every shape the
trainer compiles and every byte it moves, is then the same for every
seed, which is what keeps a cell's runs comparable and its programs in
the compile cache.
"""

from __future__ import annotations

import os
from typing import Optional

import numpy as np

from . import generators


def base_ratings(dataset: dict, cache_dir: Optional[str]):
    """The configuration's fixed structure, items compacted to those a
    rating touches (a factor table has a row per entity seen). Cached
    in ``cache_dir`` as ``sizes`` per user, ``items`` and half-star
    codes: it is a function of the configuration alone."""
    gen = generators.find(dataset["generator"])
    path = None
    if cache_dir:
        path = os.path.join(cache_dir, gen.cache_name(dataset) + ".npz")
        if os.path.exists(path):
            z = np.load(path)
            sizes = z["sizes"]
            users = np.repeat(np.arange(len(sizes), dtype=np.int32), sizes)
            return (users, z["items"].astype(np.int32),
                    z["stars2"].astype(np.float32) / 2.0,
                    len(sizes), int(z["n_items"]))
    users, items, stars, n_users, n_movies = gen.generate(dataset)
    seen = np.flatnonzero(np.bincount(items, minlength=n_movies))
    remap = np.full(n_movies, -1, dtype=np.int32)
    remap[seen] = np.arange(len(seen), dtype=np.int32)
    items = remap[items]
    if path:
        os.makedirs(cache_dir, exist_ok=True)
        tmp = path + ".tmp.npz"
        np.savez(tmp, sizes=np.bincount(users, minlength=n_users),
                 items=items.astype(np.uint16 if len(seen) < 65536
                                    else np.int32),
                 stars2=np.round(stars * 2).astype(np.uint8),
                 n_items=np.int64(len(seen)))
        os.replace(tmp, path)
    return users, items, stars, n_users, len(seen)


def relabel(users, items, stars, n_users, n_items, seed: int):
    """The same ratings under the seed's labelling and order."""
    rng = np.random.default_rng([int(seed), 0x5eed])
    pu = rng.permutation(n_users).astype(np.int32)
    pi = rng.permutation(n_items).astype(np.int32)
    order = rng.permutation(len(users))
    return pu[users][order], pi[items][order], stars[order]


def sample_entities(rng, n_entities: int, size: int,
                    zipf: Optional[float] = None) -> np.ndarray:
    """Uniform entity draw, or Zipf(alpha) with rank 1 the hottest
    (copy of ``benchmarks/_loadgen.py::sample_entities``)."""
    if zipf is None:
        return rng.integers(0, n_entities, size)
    return (rng.zipf(float(zipf), size=size) - 1) % n_entities
