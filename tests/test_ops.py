"""Unit tests for the hot-op kernels in ``predictionio_tpu.ops``.

The Pallas SPD solver is validated in interpreter mode on CPU against
the XLA Cholesky path and a float64 numpy reference — the same kernel
runs compiled on TPU (dispatch in ``solve_spd_batch``).
"""

import jax
import numpy as np
import jax.numpy as jnp
import pytest

from predictionio_tpu.ops.solve import (
    _solve_spd_pallas,
    gramian,
    solve_spd_batch,
)


def _spd_batch(n, r, seed=0, reg=0.1):
    rng = np.random.default_rng(seed)
    W = rng.standard_normal((n, max(r // 4, 2), r)).astype(np.float32)
    A = np.einsum("nkr,nks->nrs", W, W).astype(np.float32)
    A += reg * np.eye(r, dtype=np.float32)
    b = rng.standard_normal((n, r)).astype(np.float32)
    return A, b


@pytest.mark.parametrize("n,r", [(4, 8), (130, 64), (256, 10), (1, 16),
                                 (70, 128)])
def test_pallas_solver_matches_float64(n, r):
    """Lane-batched Cholesky kernel (interpret mode) vs float64 numpy,
    covering batch sizes off the 128-lane multiple and ranks off the
    8-sublane multiple (both hit the padding paths)."""
    A, b = _spd_batch(n, r)
    ref = np.linalg.solve(A.astype(np.float64),
                          b.astype(np.float64)[..., None])[..., 0]
    out = np.asarray(_solve_spd_pallas(jnp.asarray(A), jnp.asarray(b),
                                       interpret=True))
    assert out.shape == (n, r)
    # r=128 systems are worse-conditioned; a couple of elements land
    # just past 1e-3 absolute in f32 — still parity with the XLA path
    np.testing.assert_allclose(out, ref, rtol=2e-3,
                               atol=(3e-3 if r >= 128 else 1e-3))


def test_rank_routing_vmem_budget():
    """VMEM budget routing: scratch variant to rp=88, aliased in-place
    variant to rp=128 (the measured chip OOM boundary), XLA beyond."""
    from predictionio_tpu.ops.solve import _RP_ALIAS, _RP_SCRATCH

    assert _RP_SCRATCH == 88 and _RP_ALIAS == 128
    # scratch variant footprint: block + scratch
    assert 2 * _RP_SCRATCH**2 * 128 * 4 <= 12 * 2**20
    # aliased variant footprint: one block only
    assert _RP_ALIAS**2 * 128 * 4 <= 12 * 2**20
    # rank 192 must not assert inside the pallas path: the public entry
    # routes it to XLA
    A, b = _spd_batch(9, 192)
    out = np.asarray(solve_spd_batch(jnp.asarray(A), jnp.asarray(b)))
    ref = np.linalg.solve(A.astype(np.float64),
                          b.astype(np.float64)[..., None])[..., 0]
    np.testing.assert_allclose(out, ref, rtol=2e-3, atol=2e-3)


def test_pallas_solver_matches_xla_path():
    """The two dispatch targets of solve_spd_batch agree (same jitter)."""
    A, b = _spd_batch(37, 24, seed=3)
    xla = np.asarray(solve_spd_batch(jnp.asarray(A), jnp.asarray(b)))
    r = A.shape[-1]
    pal = np.asarray(_solve_spd_pallas(
        jnp.asarray(A) + 1e-6 * jnp.eye(r), jnp.asarray(b),
        interpret=True))
    np.testing.assert_allclose(pal, xla, rtol=2e-3, atol=2e-4)


@pytest.mark.parametrize("lead", [(8, 6), (1, 5)])
def test_pallas_solver_runs_per_device_under_a_mesh(lead):
    """GSPMD cannot partition a Mosaic kernel (sharded training failed
    on four real chips with exactly that message), so under a mesh the
    kernel runs per device in shard_map: row blocks whose leading axis
    the devices divide are solved sharded, anything else replicated."""
    from jax.sharding import NamedSharding, PartitionSpec as P

    from predictionio_tpu.ops.solve import _solve_spd_pallas_nd
    from predictionio_tpu.parallel.mesh import make_mesh, rows_spec

    mesh = make_mesh(data=4, model=2)
    r = 16
    A, b = _spd_batch(lead[0] * lead[1], r, seed=5)
    A, b = A.reshape(*lead, r, r), b.reshape(*lead, r)
    spec = rows_spec(mesh) if lead[0] == 8 else P()
    Ad = jax.device_put(A, NamedSharding(mesh, spec))
    bd = jax.device_put(b, NamedSharding(mesh, spec))
    out = np.asarray(jax.jit(
        lambda A, b: _solve_spd_pallas_nd(A, b, mesh, interpret=True)
    )(Ad, bd))
    ref = np.linalg.solve(A.astype(np.float64),
                          b.astype(np.float64)[..., None])[..., 0]
    np.testing.assert_allclose(out, ref, rtol=2e-3, atol=2e-3)


def test_pallas_solver_empty_history_rows():
    """Rows whose normal matrix is just λI (empty histories) solve to
    b/λ without NaNs — the padding-lane regime inside the kernel."""
    r = 16
    lam = 0.5
    A = np.broadcast_to(lam * np.eye(r, dtype=np.float32),
                        (5, r, r)).copy()
    b = np.ones((5, r), dtype=np.float32)
    out = np.asarray(_solve_spd_pallas(jnp.asarray(A), jnp.asarray(b),
                                       interpret=True))
    np.testing.assert_allclose(out, b / lam, rtol=1e-5)
    assert np.isfinite(out).all()


def test_gramian():
    F = np.arange(12, dtype=np.float32).reshape(4, 3)
    np.testing.assert_allclose(np.asarray(gramian(jnp.asarray(F))),
                               F.T @ F, rtol=1e-6)
