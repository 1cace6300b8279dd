"""Shape-keyed gram-mode selection for ``"auto"``.

``ALSParams(gram_mode="auto")`` needs a concrete realization (baseline
einsum, the pair-packed MXU tiling of ``ops/gram.py``, or the fused
Pallas kernel of ``ops/fused_gram.py``) at trace time. This module is
the TABLE half of that choice: it reads what was measured and says
which realization the table names. Whether a named kernel compiles at
the shapes about to run is decided where those shapes are known
(``models/als.py``), never here.

resolution order for ``best_mode(rank, bf16)``:

1. the file ``PIO_GRAM_AUTOTUNE_CACHE`` names, when that variable is
   set — written by ``record()`` whenever a measured race runs
   (bench.py's gram race, ``benchmarks/gram_profile.py --record``).
   With the variable unset nothing outside the checkout is read and
   ``record()`` writes nothing;
2. the packaged defaults (``gram_autotune_defaults.json`` next to this
   file) — the committed table;
3. a hardware heuristic: on TPU, "pair" below rank 128 (two rank<128
   systems share one 128-wide MXU tile; a full-rank system doesn't),
   "einsum" otherwise and on every non-TPU backend.

Keys are ``<device family>|r<rank bucket>|<f32|bf16>`` — the L/B batch
axes move the absolute time but not the winner (measured: the winner is
set by how full the MXU tile is, i.e. by rank and dtype), so they are
deliberately not in the key.
"""

from __future__ import annotations

import json
import os
import re
import threading

_LOCK = threading.Lock()
_DEFAULTS_PATH = os.path.join(os.path.dirname(os.path.abspath(__file__)),
                              "gram_autotune_defaults.json")
_cache_mem: dict | None = None


def _cache_path() -> str | None:
    """The measured-race overlay file, or None: only an explicit
    ``PIO_GRAM_AUTOTUNE_CACHE`` names one (state outside the checkout
    would let one run change what another compiles)."""
    return os.environ.get("PIO_GRAM_AUTOTUNE_CACHE") or None


def device_family(kind: str | None = None) -> str:
    """Coarse device family ("TPU v5 lite", "TPU v4", "cpu", ...) — fine
    enough to key tuning, coarse enough to survive kind-string noise."""
    if kind is None:
        try:
            import jax

            kind = jax.devices()[0].device_kind
        except Exception:  # noqa: BLE001 — no backend: untuned
            return "unknown"
    kind = str(kind)
    # "TPU v5 lite0" -> "TPU v5 lite"; "TPU v4" -> "TPU v4" (the version
    # digit is part of the family; only a trailing chip INDEX is noise)
    m = re.match(r"^(TPU v\d+[a-z]*(?: lite)?)", kind)
    if m:
        return m.group(1)
    if kind.lower().startswith("tpu"):
        return kind
    return kind.split(" ")[0].lower() or "unknown"


def _rank_bucket(rank: int) -> int:
    for b in (32, 64, 128):
        if rank <= b:
            return b
    return 256


def _key(family: str, rank: int, bf16: bool) -> str:
    return f"{family}|r{_rank_bucket(rank)}|{'bf16' if bf16 else 'f32'}"


def _load(path: str) -> dict:
    try:
        with open(path) as f:
            d = json.load(f)
        return d if isinstance(d, dict) else {}
    except (OSError, json.JSONDecodeError):
        return {}


def _table() -> dict:
    """The committed defaults, overlaid by the measured-race file when
    ``PIO_GRAM_AUTOTUNE_CACHE`` names one (it wins: it was measured on
    THIS machine)."""
    global _cache_mem
    with _LOCK:
        if _cache_mem is None:
            t = _load(_DEFAULTS_PATH)
            path = _cache_path()
            if path:
                t.update(_load(path))
            _cache_mem = t
        return dict(_cache_mem)


#: the gram realizations an autotune entry may name (``ops/gram.py``
#: einsum/pair on a materialized gather; ``ops/fused_gram.py`` for the
#: gather-fusing Pallas kernel)
MODES = ("einsum", "pair", "fused")


def best_mode(rank: int, bf16: bool = False,
              device_kind: str | None = None) -> str:
    """The gram mode ("einsum" | "pair" | "fused") the table names for
    ``gram_mode="auto"``. A pure lookup: an entry naming "fused" is
    returned as such, and the caller that knows the shapes about to
    run (``models/als.py::_resolve_gram``) compiles the kernel there
    and reports the compiler's message if it refuses."""
    fam = device_family(device_kind)
    ent = _table().get(_key(fam, rank, bf16))
    if isinstance(ent, dict) and ent.get("mode") in MODES:
        return ent["mode"]
    # heuristic: pair-packing helps exactly when two systems fit one
    # 128-wide MXU tile; CPUs/GPUs gain nothing from the extra flops
    if fam.startswith("TPU") and _rank_bucket(rank) < 128:
        return "pair"
    return "einsum"


def record(rank: int, mode: str, bf16: bool = False,
           device_kind: str | None = None,
           measured: dict | None = None) -> bool:
    """Persist a measured winner (atomic write; merge-on-write so
    concurrent processes tuning different shapes don't clobber).
    Returns whether anything was persisted — callers reporting
    "recorded" must not claim success for a refused write."""
    if mode not in MODES:
        return False
    fam = device_family(device_kind)
    if fam in ("unknown", "cpu"):
        return False  # only persist real-accelerator measurements
    path = _cache_path()
    if not path:
        return False  # nothing outside the checkout is written
    ent = {"mode": mode}
    if measured:
        ent.update(measured)
    key = _key(fam, rank, bf16)
    global _cache_mem
    # whole-training measurements (bench_race) beat single-op profile
    # measurements for the same key: the end-to-end number includes the
    # fusion context the op actually runs in
    prio = {"bench_race": 2, "gram_profile": 1}
    with _LOCK:
        try:
            os.makedirs(os.path.dirname(os.path.abspath(path)),
                        exist_ok=True)
            cur = _load(path)
            old = cur.get(key)
            if (isinstance(old, dict)
                    and prio.get(old.get("source"), 0)
                    > prio.get(ent.get("source"), 0)):
                return False
            cur[key] = ent
            tmp = f"{path}.tmp.{os.getpid()}"
            with open(tmp, "w") as f:
                json.dump(cur, f, indent=1, sort_keys=True)
            os.replace(tmp, path)
        except OSError:
            return False  # cache is advisory; never fail the caller
        _cache_mem = None  # re-overlay on next lookup
        return True


def reset_for_tests() -> None:
    global _cache_mem
    with _LOCK:
        _cache_mem = None
