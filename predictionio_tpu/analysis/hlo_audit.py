"""``ptpu audit-hlo`` — the compiled-HLO sharding audit.

The static sharding-flow rules (:mod:`.sharding`) catch spec
disagreements the AST can see; this module catches the ones only XLA
sees. It compiles the framework's registered SPMD entry points on a
forced 8-device CPU mesh (``.lower().compile()`` — no TPU needed, the
GSPMD partitioner runs identically), parses the optimized HLO for
collective ops (all-gather / all-reduce / reduce-scatter / all-to-all /
collective-permute) and the executable's temp-buffer allocation, and
diffs the result against a committed golden manifest
(``analysis/hlo_baseline.json``) with the same ratchet semantics as
the ``ptpu check`` baseline:

- a collective op a baseline entry does not record — or a count above
  the recorded one — FAILS, with the op name, its result shape, and
  the entry point named: an accidental reshard introduced three
  helpers away is caught in CI before it eats ICI bandwidth on a real
  mesh;
- temp bytes above ``TEMP_GROWTH_RATIO`` × recorded (plus a fixed
  slack) fail the same way — a spec change that materializes a
  gathered table shows up here even when the collective count is
  unchanged;
- counts/temps BELOW the record print as shrinkable, and
  ``--write-baseline`` only ever ratchets the file down; recording new
  collectives (a deliberately added entry point or schedule change)
  takes the explicit ``--baseline-grow``.

Everything jax-flavored imports lazily: ``ptpu check`` must stay
importable on a storage-only host, and the CLI sets
``JAX_PLATFORMS=cpu`` + the forced-device-count flag *before* the
first jax import (:func:`ensure_cpu_devices`).

Entry points audited (small shapes — the *collective structure* is
shape-independent, which is exactly why a golden manifest works):

- ``gramian_allreduce`` — the explicit per-shard partial + ICI psum
  (``parallel/collectives.py``); the overlapped-all-reduce contract.
- ``gather_rows`` — ``models/als.py::_gather_rows_fn``: the GSPMD
  collective resolving a cross-shard user-row fetch.
- ``sharded_rank`` — ``_sharded_rank_fn``: per-shard top-k + the
  O(k·n_dev) candidate all-gather (einsum realization).
- ``lhs_einsum`` — ``_lhs_fn`` under GSPMD with row-sharded
  table/indices: the half-step's derived gather collective.
- ``train_update_block`` — ``_update_block``: one whole training
  block (gather + Gramian + solve) under GSPMD.
- ``seqrec_train_step`` — ``models/seqrec.py::_train_step`` with
  replicated weights and a row-sharded batch: the gradient
  all-reduces XLA derives for data parallelism.
- ``sharded_topk`` — ``parallel/collectives.py::sharded_top_k`` over
  a ``(data=2, model=4)`` mesh's model axis.

See docs/parallelism.md ("How to read an audit-hlo diff") and
docs/static-analysis.md.
"""

from __future__ import annotations

import json
import os
import re
from typing import Callable, Dict, List, Optional, Sequence, Tuple

MANIFEST_VERSION = 1
AUDIT_DEVICE_COUNT = 8

#: temp allocation may grow this factor (plus slack) over the recorded
#: baseline before the gate fails — fusion-order jitter across XLA
#: builds moves temps a little; a materialized gathered table moves
#: them a lot
TEMP_GROWTH_RATIO = 1.5
TEMP_SLACK_BYTES = 64 * 1024

DEFAULT_BASELINE = os.path.join(
    os.path.dirname(os.path.abspath(__file__)), "hlo_baseline.json")

#: `= <shape> <op>(`-form HLO instruction heads; `-start` variants
#: count (async launch), `-done` halves do not (they would double
#: count the same collective)
_COLLECTIVE_RE = re.compile(
    r"=\s*(\([^)]*\)|\S+)\s+"
    r"(all-gather|all-reduce|reduce-scatter|all-to-all|"
    r"collective-permute|collective-broadcast)(-start)?\(")


class AuditError(RuntimeError):
    """Environment/usage errors (wrong device count, unknown entry)."""


def ensure_cpu_devices(n: int = AUDIT_DEVICE_COUNT) -> None:
    """Arrange for ``n`` forced CPU devices — MUST run before the
    first jax import (the flags are read at backend init). A process
    that already imported jax with a different topology cannot be
    fixed up; :func:`run_audit` verifies the live device count."""
    os.environ.setdefault("JAX_PLATFORMS", "cpu")
    flags = os.environ.get("XLA_FLAGS", "")
    if "xla_force_host_platform_device_count" not in flags:
        os.environ["XLA_FLAGS"] = (
            f"{flags} --xla_force_host_platform_device_count={n}"
        ).strip()


def parse_collectives(hlo: str) -> Tuple[Dict[str, int],
                                         Dict[str, List[str]]]:
    """(op → count, op → result shapes) over one compiled module's
    HLO text."""
    counts: Dict[str, int] = {}
    shapes: Dict[str, List[str]] = {}
    for m in _COLLECTIVE_RE.finditer(hlo):
        op = m.group(2)
        counts[op] = counts.get(op, 0) + 1
        shapes.setdefault(op, []).append(m.group(1))
    return counts, shapes


def audit_compiled(compiled) -> dict:
    """One entry-point record: collectives (count + shapes) and the
    executable's temp allocation."""
    counts, shapes = parse_collectives(compiled.as_text())
    temp = 0
    try:
        ma = compiled.memory_analysis()
        if ma is not None:
            temp = int(getattr(ma, "temp_size_in_bytes", 0) or 0)
    except Exception:  # noqa: BLE001 — backend-optional API
        temp = 0
    return {"collectives": counts,
            "collective_shapes": shapes,
            "temp_bytes": temp}


# ---------------------------------------------------------------------------
# entry-point builders (each returns a jax.stages.Compiled)
# ---------------------------------------------------------------------------

def _serving_mesh():
    from ..parallel.mesh import make_serving_mesh

    return make_serving_mesh()


def _training_mesh():
    from ..parallel.mesh import make_mesh

    return make_mesh()


def _rows(mesh, arr):
    import jax
    from jax.sharding import NamedSharding

    from ..parallel.mesh import rows_spec

    return jax.device_put(arr, NamedSharding(mesh, rows_spec(mesh)))


def _entry_gramian_allreduce():
    import jax
    import numpy as np

    from ..parallel.collectives import gramian_allreduce

    mesh = _training_mesh()
    x = _rows(mesh, np.ones((8 * mesh.devices.size, 16), np.float32))
    return jax.jit(lambda t: gramian_allreduce(t, mesh)).lower(x).compile()


def _entry_gather_rows():
    import numpy as np

    from ..models.als import _gather_rows_fn

    mesh = _serving_mesh()
    table = _rows(mesh, np.ones((8 * mesh.devices.size, 16), np.float32))
    idx = np.zeros((4,), np.int64)
    return _gather_rows_fn(mesh).lower(table, idx).compile()


def _entry_sharded_rank():
    import numpy as np

    from ..models.als import _sharded_rank_fn

    mesh = _serving_mesh()
    n = 8 * mesh.devices.size
    table = _rows(mesh, np.ones((n, 16), np.float32))
    vecs = np.ones((4, 16), np.float32)
    fn = _sharded_rank_fn(mesh, 8, 8, n)
    return fn.lower(vecs, table).compile()


def _lhs_inputs(mesh):
    import numpy as np

    n_dev = mesh.devices.size
    table = _rows(mesh, np.ones((8 * n_dev, 16), np.float32))
    idx = _rows(mesh, np.zeros((n_dev, 4, 8), np.int32))
    w = _rows(mesh, np.ones((n_dev, 4, 8), np.float32))
    return table, idx, w


def _entry_lhs_einsum():
    import functools

    import jax

    from ..models.als import _lhs_fn

    mesh = _training_mesh()
    table, idx, w = _lhs_inputs(mesh)
    fn = jax.jit(functools.partial(_lhs_fn, bf16=False))
    return fn.lower(table, idx, w, w).compile()


def _entry_train_update_block():
    import functools

    import jax
    import numpy as np

    from ..models.als import _update_block

    mesh = _training_mesh()
    table, idx, w = _lhs_inputs(mesh)
    counts = _rows(mesh, np.ones((mesh.devices.size, 4), np.float32))
    G = np.zeros((16, 16), np.float32)
    fn = jax.jit(functools.partial(
        _update_block.__wrapped__, implicit=True, scale_reg=True,
        bf16=False, mesh=None))
    return fn.lower(table, G, idx, w, counts, 0.1, 40.0).compile()


def _entry_seqrec_train_step():
    import jax
    import jax.numpy as jnp
    import numpy as np
    from jax.sharding import NamedSharding, PartitionSpec as P

    from ..models.seqrec import SeqRecParams, _init_weights, _train_step

    mesh = _training_mesh()
    p = SeqRecParams(dim=16, heads=2, max_len=8, n_negatives=4,
                     batch_size=8)
    w = _init_weights(jax.random.key(0), 32, p)
    rep = NamedSharding(mesh, P())
    w = jax.device_put(w, rep)
    m = jax.device_put({k: jnp.zeros_like(v) for k, v in w.items()}, rep)
    v = jax.device_put({k: jnp.zeros_like(v) for k, v in w.items()}, rep)
    seq = _rows(mesh, np.zeros((mesh.devices.size, 8), np.int32))
    return _train_step.lower(w, m, v, jnp.zeros((), jnp.int32), seq,
                             jax.random.key(1), p, 32).compile()


def _entry_sharded_topk():
    import jax
    import numpy as np
    from jax.sharding import NamedSharding, PartitionSpec as P

    from ..parallel.collectives import sharded_top_k
    from ..parallel.mesh import make_mesh

    mesh = make_mesh(data=2, model=4)
    scores = jax.device_put(
        np.ones((4, 64), np.float32),
        NamedSharding(mesh, P(None, "model")))
    fn = jax.jit(lambda s: sharded_top_k(s, 8, mesh, axis="model"))
    return fn.lower(scores).compile()


#: name → (builder, one-line description); ordered — the manifest and
#: the CI artifact list entries in this order
ENTRY_POINTS: Dict[str, Tuple[Callable[[], object], str]] = {
    "gramian_allreduce": (
        _entry_gramian_allreduce,
        "explicit per-shard Gramian partial + ICI psum"),
    "gather_rows": (
        _entry_gather_rows,
        "cross-shard user-row fetch (GSPMD-derived collective)"),
    "sharded_rank": (
        _entry_sharded_rank,
        "per-shard top-k + candidate all-gather (einsum ranker)"),
    "lhs_einsum": (
        _entry_lhs_einsum,
        "_lhs_fn normal-equation build under GSPMD row sharding"),
    "train_update_block": (
        _entry_train_update_block,
        "one ALS training block (gather+Gramian+solve) under GSPMD"),
    "seqrec_train_step": (
        _entry_seqrec_train_step,
        "sequential-model Adam step: data-parallel gradient "
        "all-reduces"),
    "sharded_topk": (
        _entry_sharded_topk,
        "two-phase global top-k over the (data=2, model=4) mesh"),
}


def run_audit(names: Optional[Sequence[str]] = None) -> dict:
    """Compile + parse every (selected) entry point; returns the
    manifest dict. Raises :class:`AuditError` when the process does
    not see the forced device count (the collective structure depends
    on it — a 1-device audit would record an empty manifest)."""
    import jax

    n_dev = len(jax.devices())
    if n_dev < AUDIT_DEVICE_COUNT:
        raise AuditError(
            f"audit-hlo needs {AUDIT_DEVICE_COUNT} devices, found "
            f"{n_dev}; run in a fresh process (the CLI forces "
            f"XLA_FLAGS=--xla_force_host_platform_device_count="
            f"{AUDIT_DEVICE_COUNT} before importing jax)")
    unknown = set(names or ()) - set(ENTRY_POINTS)
    if unknown:
        raise AuditError(f"unknown entry point(s): {sorted(unknown)} "
                         f"(have: {sorted(ENTRY_POINTS)})")
    entries: Dict[str, dict] = {}
    for name, (builder, _desc) in ENTRY_POINTS.items():
        if names and name not in names:
            continue
        entries[name] = audit_compiled(builder())
    return {"version": MANIFEST_VERSION,
            "devices": AUDIT_DEVICE_COUNT,
            "entries": entries}


# ---------------------------------------------------------------------------
# manifest I/O + ratchet diff
# ---------------------------------------------------------------------------

def load_manifest(path: str) -> dict:
    with open(path, "r", encoding="utf-8") as fh:
        doc = json.load(fh)
    if not isinstance(doc, dict) \
            or doc.get("version") != MANIFEST_VERSION:
        raise ValueError(f"{path}: not an audit-hlo manifest "
                         f"(expected version {MANIFEST_VERSION})")
    return doc


def write_manifest(path: str, manifest: dict,
                   cap: Optional[dict] = None) -> None:
    """Persist the manifest. With ``cap`` (the previously committed
    baseline) the write RATCHETS: entries/ops the old baseline never
    held are dropped, counts and temp bytes clamp to the recorded
    values — the file only shrinks (use :func:`diff_manifests` first
    to fail on unabsorbed growth; ``--baseline-grow`` writes as-is)."""
    doc = manifest
    if cap is not None:
        old = cap.get("entries", {})
        entries = {}
        for name, rec in manifest.get("entries", {}).items():
            if name not in old:
                continue
            orec = old[name]
            colls = {op: min(c, orec.get("collectives", {})[op])
                     for op, c in rec.get("collectives", {}).items()
                     if op in orec.get("collectives", {})}
            entries[name] = {
                "collectives": colls,
                "collective_shapes": {
                    op: rec.get("collective_shapes", {}).get(op, [])
                    for op in colls},
                "temp_bytes": min(rec.get("temp_bytes", 0),
                                  orec.get("temp_bytes", 0)),
            }
        doc = {"version": MANIFEST_VERSION,
               "devices": manifest.get("devices", AUDIT_DEVICE_COUNT),
               "entries": entries}
    from .baseline import atomic_write_text

    atomic_write_text(
        path, json.dumps(doc, indent=2, sort_keys=True) + "\n")


def diff_manifests(current: dict, baseline: dict
                   ) -> Tuple[List[str], List[str]]:
    """(violations, shrinkable) between a fresh audit and the golden
    baseline. Violations name the entry point, the op, and its result
    shape — the line an operator greps for."""
    violations: List[str] = []
    shrinkable: List[str] = []
    if current.get("devices") != baseline.get("devices"):
        violations.append(
            f"device count {current.get('devices')} != baseline "
            f"{baseline.get('devices')} (the collective structure is "
            f"topology-dependent; audit on the forced mesh)")
    cur = current.get("entries", {})
    base = baseline.get("entries", {})
    for name, rec in cur.items():
        brec = base.get(name)
        if brec is None:
            violations.append(
                f"{name}: entry point not in the baseline — record it "
                f"deliberately with --write-baseline --baseline-grow")
            continue
        bcolls = brec.get("collectives", {})
        for op, count in sorted(rec.get("collectives", {}).items()):
            b = bcolls.get(op, 0)
            shapes = rec.get("collective_shapes", {}).get(op, [])
            if count > b:
                violations.append(
                    f"{name}: {op} x{count} (baseline {b}) — new "
                    f"collective in the compiled program"
                    + (f"; shapes {shapes}" if shapes else "")
                    + ". A spec change made XLA insert a reshard: "
                    f"diff the specs feeding this entry point, or "
                    f"record deliberately with --baseline-grow")
            elif count < b:
                shrinkable.append(f"{name}: {op} recorded {b}, "
                                  f"found {count}")
        for op, b in sorted(bcolls.items()):
            if op not in rec.get("collectives", {}):
                shrinkable.append(f"{name}: {op} recorded {b}, "
                                  f"found 0")
        btemp = brec.get("temp_bytes", 0)
        temp = rec.get("temp_bytes", 0)
        if temp > btemp * TEMP_GROWTH_RATIO + TEMP_SLACK_BYTES:
            violations.append(
                f"{name}: temp allocation {temp}B vs baseline "
                f"{btemp}B (> x{TEMP_GROWTH_RATIO} + "
                f"{TEMP_SLACK_BYTES}B slack) — a spec change is "
                f"materializing a gathered buffer; check for an "
                f"implicit reshard, or --baseline-grow")
        elif temp < btemp / TEMP_GROWTH_RATIO - TEMP_SLACK_BYTES:
            shrinkable.append(f"{name}: temp_bytes recorded {btemp}, "
                              f"found {temp}")
    for name in base:
        if name not in cur:
            shrinkable.append(f"{name}: entry point no longer audited")
    return violations, shrinkable


def format_text(manifest: dict) -> str:
    lines: List[str] = []
    for name, rec in manifest.get("entries", {}).items():
        colls = rec.get("collectives", {})
        summary = ", ".join(f"{op} x{c}"
                            for op, c in sorted(colls.items())) \
            or "no collectives"
        lines.append(f"{name}: {summary}; "
                     f"temp {rec.get('temp_bytes', 0)}B")
        for op, shapes in sorted(
                rec.get("collective_shapes", {}).items()):
            lines.append(f"  {op}: {' '.join(shapes)}")
    return "\n".join(lines)


__all__ = (
    "AUDIT_DEVICE_COUNT",
    "AuditError",
    "DEFAULT_BASELINE",
    "ENTRY_POINTS",
    "audit_compiled",
    "diff_manifests",
    "ensure_cpu_devices",
    "format_text",
    "load_manifest",
    "parse_collectives",
    "run_audit",
    "write_manifest",
)
