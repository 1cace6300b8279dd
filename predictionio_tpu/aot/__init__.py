"""AOT compile artifacts — capture at build time, load at deploy time.

The cold-start gap is almost entirely XLA compilation of the serving
entry points (``first_setup_s`` against ``setup_s`` in
PERF_LEDGER.jsonl: the ALS cells' set-up is 9.2-9.5 s on the v5e once
the compile cache holds their programs; ledger, PR 28).  JAX's AOT API makes those
executables portable: ``fn.lower(...).compile()`` yields a loaded
executable whose bytes ``jax.experimental.serialize_executable``
round-trips, and the deserialized executable is called with the dynamic
arguments only (statics are baked in) and answers bitwise-identically.

This module is the seam between the jit serving paths and that artifact
mechanism.  Serving entry points route their launches through
:func:`dispatch`, which has three behaviours selected by process-global
state:

* **normal** (neither store active): call the jit function unchanged —
  zero overhead beyond one global read.
* **capture** (``capture_into`` — during ``ptpu build``): lower+compile
  the entry, serialize it into the capture store keyed by the entry
  signature, and answer from the freshly compiled executable.  The
  build-time warm ladder (``warm_serving``) drives exactly the shapes
  deploy will see, so the artifact dir covers the serving envelope.
* **serve** (``activate`` — during ``QueryServer._warm_serving``): look
  the signature up in the store; a hit answers from the deserialized
  executable (milliseconds), a miss falls through to the jit path and
  compiles — the stale-key / corrupt-artifact fallback.  Every failure
  mode degrades to "compile like before", never to an error.

Artifact stores are versioned directories::

    <root>/<key-digest>/manifest.json     # store key + entry table
    <root>/<key-digest>/<entry-key>.exec  # pickled {blob, in_tree, out_tree}

The store key (jax version, backend, device count, mesh shape, rank,
quant mode, top-k mode, max batch — see :func:`store_key`) must match
EXACTLY between build and deploy; any drift resolves the digest to a
different directory and deploy falls back to compiling (counted in
``stats()["stale"]``).  Entry files carry a sha256 in the manifest and a
corrupt or truncated file is skipped, never trusted.

Artifacts embed pickled PyTreeDefs: treat an artifact dir with the same
trust as the model store it sits beside (docs/cold-start.md).
"""

from __future__ import annotations

import hashlib
import json
import os
import pickle
import threading
import time
from contextlib import contextmanager
from typing import Any, Dict, Iterable, Optional, Sequence, Tuple

__all__ = [
    "ArtifactStore", "activate", "capture_into", "deactivate",
    "dispatch", "entry_key", "key_digest", "reset_stats", "stats",
    "store_key",
]

_FORMAT = 1
_MANIFEST = "manifest.json"
_EXT = ".exec"

_lock = threading.Lock()
_capture_store: Optional["ArtifactStore"] = None
_serve_store: Optional["ArtifactStore"] = None


def _zero_stats() -> Dict[str, Any]:
    return {
        "loaded_entries": 0,    # artifact files deserialized
        "loaded_calls": 0,      # dispatches answered from an artifact
        "compiled_calls": 0,    # dispatches that fell through while serving
        "captured_entries": 0,  # entries written by capture
        "capture_errors": 0,    # entries that would not serialize
        "corrupt_entries": 0,   # sha/unpickle failures (skipped)
        "stale": 0,             # store-open key mismatches
        "load_seconds": 0.0,    # cumulative deserialize time
    }


_stats = _zero_stats()


def stats() -> Dict[str, Any]:
    """Snapshot of the process-wide AOT counters (see `_zero_stats`)."""
    with _lock:
        return dict(_stats)


def reset_stats() -> None:
    with _lock:
        _stats.update(_zero_stats())


def _bump(name: str, by: float = 1) -> None:
    with _lock:
        _stats[name] += by


# ---------------------------------------------------------------------------
# keys

def store_key(**fields: Any) -> Dict[str, Any]:
    """The store-level cache key: artifact format + toolchain identity +
    caller-supplied serving-shape fields (mesh shape, rank, quant mode,
    top-k mode, max batch...).  Build and deploy MUST derive the key from
    the same inputs — :func:`key_digest` of the key names the artifact
    subdirectory, so any mismatch is an automatic fallback-to-compile."""
    import jax

    key: Dict[str, Any] = {
        "format": _FORMAT,
        "jax": jax.__version__,
        "backend": jax.default_backend(),
        "device_count": jax.device_count(),
    }
    for name, value in fields.items():
        key[name] = list(value) if isinstance(value, tuple) else value
    return key


def key_digest(key: Dict[str, Any]) -> str:
    blob = json.dumps(key, sort_keys=True, default=str)
    return hashlib.sha256(blob.encode()).hexdigest()[:16]


def _leaf_sig(leaf: Any) -> Tuple:
    """Identity of one dynamic argument leaf: dtype + shape + placement.
    Placement matters — a serialized executable records its device
    assignment, so per-device replicated-lane entries must not collide."""
    if leaf is None:
        return ("none",)
    dtype = str(getattr(leaf, "dtype", type(leaf).__name__))
    shape = tuple(getattr(leaf, "shape", ()))
    sharding = getattr(leaf, "sharding", None)
    if sharding is None:
        placement: Any = "host"
    else:
        try:
            placement = tuple(sorted(d.id for d in sharding.device_set))
        except Exception:  # noqa: BLE001 — exotic shardings: opaque repr
            placement = repr(sharding)
    return (dtype, shape, placement)


def entry_key(name: str, dyn_args: Sequence[Any],
              statics: Optional[Dict[str, Any]] = None,
              key_extra: Iterable[Any] = ()) -> str:
    """Per-entry key: entry name + dynamic-arg signature (treedef, and
    per-leaf dtype/shape/placement) + static kwargs + caller extras
    (e.g. the sharded ranker's mesh/k/quant cache key)."""
    import jax

    leaves, treedef = jax.tree_util.tree_flatten(tuple(dyn_args))
    sig = (name, str(treedef), tuple(_leaf_sig(l) for l in leaves),
           tuple(sorted((statics or {}).items())), tuple(key_extra))
    digest = hashlib.sha256(repr(sig).encode()).hexdigest()[:20]
    return f"{name}-{digest}"


# ---------------------------------------------------------------------------
# store

class ArtifactStore:
    """One versioned artifact directory (``<root>/<key-digest>``) holding
    serialized serving executables, plus the in-memory cache of loaded /
    freshly captured ones.  Thread-safe; all IO failures are contained
    (a bad entry is skipped and the caller compiles)."""

    def __init__(self, root: str, key: Dict[str, Any]):
        self.root = os.path.abspath(root)
        self.key = dict(key)
        self.path = os.path.join(self.root, key_digest(self.key))
        self.entries: Dict[str, Dict[str, Any]] = {}
        self._loaded: Dict[str, Any] = {}
        self._failed: set[str] = set()
        self._lock = threading.Lock()

    # -- build side ---------------------------------------------------

    def capture(self, ekey: str, fn: Any, dyn_args: Sequence[Any],
                statics: Optional[Dict[str, Any]] = None) -> Any:
        """Lower+compile ``fn`` for this signature, persist the
        serialized executable, and return the compiled (loaded)
        executable so the build-time warm ladder still executes it."""
        from jax.experimental import serialize_executable as se

        with self._lock:
            cached = self._loaded.get(ekey)
        if cached is not None:
            return cached
        compiled = fn.lower(*dyn_args, **(statics or {})).compile()
        blob, in_tree, out_tree = se.serialize(compiled)
        # the executable runs on exactly the devices it was compiled
        # for: record them so load() binds it back to those, not to
        # every device of the backend (the deserializer's default)
        devices = [d.id for d in
                   compiled.runtime_executable().local_devices()]
        payload = pickle.dumps(
            {"blob": blob, "in_tree": in_tree, "out_tree": out_tree,
             "devices": devices},
            protocol=pickle.HIGHEST_PROTOCOL)
        os.makedirs(self.path, exist_ok=True)
        fname = ekey + _EXT
        fpath = os.path.join(self.path, fname)
        tmp = fpath + ".tmp"
        with open(tmp, "wb") as f:
            f.write(payload)
        os.replace(tmp, fpath)
        with self._lock:
            self.entries[ekey] = {
                "file": fname,
                "sha256": hashlib.sha256(payload).hexdigest(),
                "bytes": len(payload),
            }
            self._loaded[ekey] = compiled
        _bump("captured_entries")
        return compiled

    def flush(self) -> str:
        """Atomically (re)write the manifest; returns the store path."""
        os.makedirs(self.path, exist_ok=True)
        with self._lock:
            doc = {"key": self.key, "entries": dict(self.entries)}
        tmp = os.path.join(self.path, _MANIFEST + ".tmp")
        with open(tmp, "w") as f:
            json.dump(doc, f, indent=1, sort_keys=True)
        os.replace(tmp, os.path.join(self.path, _MANIFEST))
        return self.path

    # -- deploy side --------------------------------------------------

    @classmethod
    def open(cls, root: str, key: Dict[str, Any]
             ) -> Optional["ArtifactStore"]:
        """Open the store for ``key`` under ``root``.  Returns ``None``
        (and counts ``stale``) when the directory or manifest is missing
        or the manifest's key disagrees — the caller compiles."""
        store = cls(root, key)
        manifest = os.path.join(store.path, _MANIFEST)
        try:
            with open(manifest) as f:
                doc = json.load(f)
        except (OSError, ValueError):
            _bump("stale")
            return None
        if doc.get("key") != store.key:
            _bump("stale")
            return None
        entries = doc.get("entries")
        if not isinstance(entries, dict):
            _bump("stale")
            return None
        store.entries = entries
        return store

    def load(self, ekey: str) -> Optional[Any]:
        """Deserialize (once) and return the executable for ``ekey``, or
        ``None`` on miss / checksum mismatch / unpickle failure."""
        from jax.experimental import serialize_executable as se

        with self._lock:
            if ekey in self._loaded:
                return self._loaded[ekey]
            if ekey in self._failed:
                return None
            meta = self.entries.get(ekey)
        if meta is None:
            return None
        t0 = time.perf_counter()
        try:
            with open(os.path.join(self.path, meta["file"]), "rb") as f:
                payload = f.read()
            if hashlib.sha256(payload).hexdigest() != meta.get("sha256"):
                raise ValueError("artifact checksum mismatch")
            doc = pickle.loads(payload)
            import jax

            by_id = {d.id: d for d in jax.devices()}
            executable = se.deserialize_and_load(
                doc["blob"], doc["in_tree"], doc["out_tree"],
                execution_devices=[by_id[i] for i in doc["devices"]])
        except Exception:  # noqa: BLE001 — any bad artifact ⇒ compile
            _bump("corrupt_entries")
            with self._lock:
                self._failed.add(ekey)
            return None
        with self._lock:
            self._loaded[ekey] = executable
        _bump("loaded_entries")
        _bump("load_seconds", time.perf_counter() - t0)
        return executable

    def __len__(self) -> int:
        return len(self.entries)


# ---------------------------------------------------------------------------
# process-global modes

@contextmanager
def capture_into(store: ArtifactStore):
    """Route every :func:`dispatch` in this process through AOT capture
    into ``store`` for the duration (the ``ptpu build`` driver)."""
    global _capture_store
    with _lock:
        prev, _capture_store = _capture_store, store
    try:
        yield store
    finally:
        with _lock:
            _capture_store = prev
        store.flush()


def activate(store: Optional[ArtifactStore]) -> None:
    """Serve dispatches from ``store`` (misses compile as before).
    Stays active for the server's lifetime so post-warm shape misses
    still probe the artifact table first."""
    global _serve_store
    with _lock:
        _serve_store = store


def deactivate() -> None:
    activate(None)


def serving_store() -> Optional[ArtifactStore]:
    return _serve_store


def dispatch(name: str, fn: Any, dyn_args: Sequence[Any],
             statics: Optional[Dict[str, Any]] = None,
             key_extra: Iterable[Any] = ()) -> Any:
    """Launch a serving entry point through the AOT seam.

    ``fn`` is the jit-wrapped callable; ``dyn_args`` its dynamic
    arguments (passed positionally), ``statics`` its static kwargs, and
    ``key_extra`` any additional identity the signature cannot see
    (e.g. the mesh/k tuple keying a compile-once product function).
    Normal mode is a tail call into ``fn`` — the seam costs one global
    read on the hot path."""
    serve = _serve_store
    capture = _capture_store
    if serve is None and capture is None:
        return fn(*dyn_args, **(statics or {}))
    ekey = entry_key(name, dyn_args, statics, key_extra)
    if serve is not None:
        executable = serve.load(ekey)
        if executable is not None:
            _bump("loaded_calls")
            return executable(*dyn_args)
        _bump("compiled_calls")
    if capture is not None:
        try:
            compiled = capture.capture(ekey, fn, dyn_args, statics)
        except Exception:  # noqa: BLE001 — unserializable ⇒ jit as usual
            _bump("capture_errors")
        else:
            return compiled(*dyn_args)
    return fn(*dyn_args, **(statics or {}))
