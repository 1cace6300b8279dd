"""Test configuration: force an 8-device virtual CPU mesh.

The reference's unit tests run Spark with a `local` master
(`core/src/test/.../workflow/BaseTest.scala`); the TPU build does better —
multi-device semantics are exercised on every test run via XLA's virtual
host devices, so `shard_map`/`pjit` sharding is covered without TPU hardware.
Must run before jax initializes its backends, hence os.environ at import.
"""

import os

# Force CPU regardless of ambient JAX_PLATFORMS (the session may point at a
# real TPU; unit tests must be deterministic f32 on the virtual mesh). The
# env var alone is not enough when a TPU PJRT plugin is installed — the
# config update below is authoritative.
os.environ["JAX_PLATFORMS"] = "cpu"
flags = os.environ.get("XLA_FLAGS", "")
if "xla_force_host_platform_device_count" not in flags:
    os.environ["XLA_FLAGS"] = (
        flags + " --xla_force_host_platform_device_count=8").strip()
os.environ.setdefault("JAX_ENABLE_X64", "0")

import jax  # noqa: E402

jax.config.update("jax_platforms", "cpu")

import pytest  # noqa: E402


@pytest.fixture(scope="session")
def mesh8():
    import jax
    from predictionio_tpu.parallel.mesh import make_mesh

    assert len(jax.devices()) >= 8, "expected 8 virtual CPU devices"
    return make_mesh(data=4, model=2)


def start_sqlite_backed_storage_server(tmp_path, secret=None):
    """Shared bootstrap for remote-backend tests: a sqlite-backed
    Storage served by a real storage server on a loopback port.
    Returns (server, backing_storage); caller shuts the server down."""
    from predictionio_tpu.data.storage import Storage
    from predictionio_tpu.server.storageserver import (
        create_storage_server,
    )

    backing = Storage(env={
        "PIO_STORAGE_SOURCES_SQ_TYPE": "sqlite",
        "PIO_STORAGE_SOURCES_SQ_PATH": str(tmp_path / "backing.db"),
        "PIO_STORAGE_REPOSITORIES_METADATA_SOURCE": "SQ",
        "PIO_STORAGE_REPOSITORIES_EVENTDATA_SOURCE": "SQ",
        "PIO_STORAGE_REPOSITORIES_MODELDATA_SOURCE": "SQ",
    })
    srv = create_storage_server(backing, host="127.0.0.1", port=0,
                                secret=secret)
    srv.start_background()
    return srv, backing


def serve_staged_batch(qs, query_jsons, obs_list=None, timeout=60.0):
    """Serve ``query_jsons`` as ONE batch of ``qs``'s StagedPipeline:
    assembled here (``_assemble``, on the calling thread), then handed
    to the pipeline's own dispatch and readback threads, as the
    assemble loop hands its batches over. Returns ``(slots, lane)``:
    the result slots in query order and the lane that dispatched the
    batch (None on a single binding). For tests that must name a
    batch's members; the rest ``submit``."""
    from predictionio_tpu.server.engineserver import _Submit

    pipe = qs.batcher
    entries = [_Submit(q, obs_list[i] if obs_list else None, 0.0)
               for i, q in enumerate(query_jsons)]
    # the in-flight slot the assemble loop takes before a pickup; the
    # readback stage frees it, as it does for the loop's batches
    pipe._inflight.acquire()
    ab = pipe._assemble(entries)
    if not ab.entries:  # every query answered at parse
        pipe._inflight.release()
    else:
        pipe._dispatch_q.put(ab)
    for e in entries:
        assert e.done.wait(timeout), "the pipeline never answered"
    return [e.slot[0] for e in entries], ab.lane


@pytest.fixture(autouse=True)
def _fail_on_lock_inversions():
    """Instrumented-lock CI mode: when the suite runs with
    PTPU_DEBUG_LOCKS=1 (the separate workflow step that re-runs the
    cache/rollout stress tests), any lock-order inversion or
    non-reentrant re-entry the DebugLock registry records during a test
    fails THAT test — an ordering regression dies in CI, not in
    production. A no-op (plain locks, no registry reads) otherwise."""
    from predictionio_tpu.concurrency import (
        lock_registry,
        locks_instrumented,
    )

    if not locks_instrumented():
        yield
        return
    reg = lock_registry()
    before_inv = len(reg.inversions)
    before_re = len(reg.reentries)
    yield
    inversions = reg.inversions[before_inv:]
    reentries = reg.reentries[before_re:]
    problems = [f"lock-order inversion: acquiring {i['acquiring']!r} "
                f"while holding {i['held']!r} at {i['site']} "
                f"(prior order established at {i['prior_site']})"
                for i in inversions]
    problems += [f"same-thread re-entry on {r['lock']!r} at {r['site']}"
                 for r in reentries]
    assert not problems, "\n".join(problems)
