"""Ring attention: sequence-parallel attention over the device mesh.

Long-context support as core infrastructure (the distributed design the
framework is built around, SURVEY §2.3 — the reference has no sequence
models at all, so this is new capability, not a port): queries, keys and
values are sharded along the SEQUENCE axis across the mesh; each device
computes blockwise attention against its resident KV block while the KV
blocks rotate around the ring via ``ppermute`` over ICI — full attention
over a sequence P× longer than one device could hold, with no all-gather
of the sequence anywhere.

Numerics: the classic streaming-softmax accumulation (running max ``m``,
normalizer ``l``, weighted accumulator) — each incoming KV block updates
the triple exactly, so the result equals dense softmax attention to
float rounding, block order notwithstanding. The (m, l, acc) triple is
f32 regardless of the q/k/v wire dtype, with
``preferred_element_type=f32`` on every contraction — the same
accumulate-in-f32 contract ``ptpu check`` enforces on Pallas scratch
(``low-precision-accumulator``, docs/static-analysis.md): bf16 belongs
on the wire, never in the running sum (a bf16 ``l`` visibly skews long
-sequence attention weights).

The op is jit/shard_map-first: no data-dependent Python control flow,
static shapes, a ``lax.fori_loop`` of P ring steps.
"""

from __future__ import annotations

import functools
from typing import Optional

import jax
import jax.numpy as jnp
from jax.sharding import Mesh, NamedSharding, PartitionSpec as P


def _ring_attention_local(q, k, v, kmask, *, axis_name: str,
                          causal: bool, scale: float):
    """Per-device body under shard_map. q/k/v: [B, S_loc, H, D] (this
    device's sequence chunk); kmask: [B, S_loc] bool key-validity (all
    True when no padding) — it rotates around the ring WITH its k/v
    block. Returns the local output chunk."""
    n_dev = jax.lax.psum(1, axis_name)
    idx = jax.lax.axis_index(axis_name)
    B, S_loc, H, D = q.shape

    q_pos = idx * S_loc + jnp.arange(S_loc)  # global query positions

    # the accumulators join a carry with device-varying k/v —
    # shard_map's varying-axis typing requires the whole carry to agree
    def _vary(x):
        return jax.lax.pcast(x, (axis_name,), to="varying")

    m0 = _vary(jnp.full((B, H, S_loc), -jnp.inf, jnp.float32))
    l0 = _vary(jnp.zeros((B, H, S_loc), jnp.float32))
    acc0 = _vary(jnp.zeros((B, S_loc, H, D), jnp.float32))

    def step(j, carry):
        k_blk, v_blk, km_blk, m, l, acc = carry
        # rotate at the START for steps > 0: n_dev blocks need only
        # n_dev-1 rotations, and a trailing rotation would pay one
        # discarded ICI hop per block per call. The predicate is the
        # loop counter — identical on every device, so the collective
        # stays globally consistent inside lax.cond.
        perm = [(i, (i + 1) % n_dev) for i in range(n_dev)]

        def rotate(kv):
            return tuple(jax.lax.ppermute(x, axis_name, perm)
                         for x in kv)

        k_blk, v_blk, km_blk = jax.lax.cond(
            j > 0, rotate, lambda kv: kv, (k_blk, v_blk, km_blk))
        # after j rotations this device holds the KV block originally
        # owned by device (idx - j) mod n_dev
        kv_owner = (idx - j) % n_dev
        kv_pos = kv_owner * S_loc + jnp.arange(S_loc)

        # [B, H, Sq, Sk] block scores in f32 (inputs may be bf16)
        s = jnp.einsum("bqhd,bkhd->bhqk", q, k_blk,
                       preferred_element_type=jnp.float32) * scale
        if causal:
            mask = q_pos[:, None] >= kv_pos[None, :]
            s = jnp.where(mask[None, None], s, -jnp.inf)
        s = jnp.where(km_blk[:, None, None, :], s, -jnp.inf)

        # streaming softmax: fold this block into (m, l, acc)
        blk_max = jnp.max(s, axis=-1)
        m_new = jnp.maximum(m, blk_max)
        # rows with nothing attendable yet keep m=-inf; exp(-inf - -inf)
        # would be NaN — substitute 0 for the shift in that case
        shift = jnp.where(jnp.isinf(m_new), 0.0, m_new)
        p = jnp.exp(s - shift[..., None])  # masked slots: exp(-inf)=0
        corr = jnp.where(jnp.isinf(m), 0.0, jnp.exp(m - shift))
        l_new = l * corr + jnp.sum(p, axis=-1)
        pv = jnp.einsum("bhqk,bkhd->bqhd", p,
                        v_blk.astype(jnp.float32),
                        preferred_element_type=jnp.float32)
        acc_new = acc * corr.transpose(0, 2, 1)[..., None] + pv
        return k_blk, v_blk, km_blk, m_new, l_new, acc_new

    _, _, _, m, l, acc = jax.lax.fori_loop(0, n_dev, step,
                                           (k, v, kmask, m0, l0, acc0))
    # fully-masked rows (can't happen for causal self-attention, where
    # position t always sees itself) would have l=0; keep them 0, not NaN
    denom = jnp.maximum(l, 1e-30).transpose(0, 2, 1)[..., None]
    return (acc / denom).astype(q.dtype)


def ring_attention(q: jax.Array, k: jax.Array, v: jax.Array,
                   mesh: Optional[Mesh] = None, axis: str = "data",
                   causal: bool = False,
                   scale: Optional[float] = None,
                   key_valid: Optional[jax.Array] = None) -> jax.Array:
    """Sequence-parallel multi-head attention.

    q/k/v: ``[batch, seq, heads, head_dim]`` with the sequence axis
    sharded over ``mesh`` axis ``axis`` (``seq`` must divide evenly by
    that axis size). ``key_valid`` ([batch, seq] bool) masks key
    positions — padding slots in right-aligned sequence-model windows —
    on BOTH paths (the mask rotates around the ring with its KV block).
    Returns attention output with the same sharding. With ``mesh=None``
    this is plain single-device DENSE softmax attention (the whole
    ``[batch, heads, seq, seq]`` float32 score matrix; the same
    contract, what the sequence model runs at its window of a few
    hundred). The blockwise single-device kernel, with a causal and a
    window mask, is ``ops/window_attention.py`` (the generative
    prefill's).
    """
    if scale is None:
        scale = float(q.shape[-1]) ** -0.5
    fn = _compiled(mesh, axis, causal, scale)
    if key_valid is None:
        key_valid = jnp.ones(q.shape[:2], bool)
    if mesh is None:
        return fn(q, k, v, key_valid)
    sharding = NamedSharding(mesh, P(None, axis, None, None))
    km_sharding = NamedSharding(mesh, P(None, axis))
    return fn(jax.device_put(q, sharding), jax.device_put(k, sharding),
              jax.device_put(v, sharding),
              jax.device_put(key_valid, km_sharding))


_fn_cache: dict = {}


def _compiled(mesh, axis: str, causal: bool, scale: float):
    """Cached jitted entry per (mesh, axis, causal, scale) — a fresh
    jax.jit per call would re-trace every invocation (~200x the cost of
    the cached dispatch; same convention as models/als.py). The Mesh
    itself keys the cache (hashable, value-compared over devices AND
    axis layout)."""
    key = (mesh, axis, causal, scale)
    fn = _fn_cache.get(key)
    if fn is None:
        if mesh is None:
            def nodist(q, k, v, key_valid):
                return _ring_attention_local_nodist(
                    q, k, v, causal=causal, scale=scale,
                    key_valid=key_valid)
            fn = jax.jit(nodist)
        else:
            spec = P(None, axis, None, None)
            km_spec = P(None, axis)
            fn = jax.jit(jax.shard_map(
                functools.partial(_ring_attention_local, axis_name=axis,
                                  causal=causal, scale=scale),
                mesh=mesh, in_specs=(spec, spec, spec, km_spec),
                out_specs=spec, check_vma=False))
        _fn_cache[key] = fn
    return fn


def _ring_attention_local_nodist(q, k, v, *, causal: bool, scale: float,
                                 key_valid=None):
    """Single-device reference/fallback: dense softmax attention with
    the same masking and dtype conventions. ``key_valid`` ([B, Sk]
    bool) additionally masks key positions (padding slots in
    right-aligned sequence-model windows); fully-masked query rows
    return 0, never NaN."""
    s = jnp.einsum("bqhd,bkhd->bhqk", q, k,
                   preferred_element_type=jnp.float32) * scale
    if causal:
        S = q.shape[1]
        mask = jnp.arange(S)[:, None] >= jnp.arange(S)[None, :]
        s = jnp.where(mask[None, None], s, -jnp.inf)
    if key_valid is not None:
        s = jnp.where(key_valid[:, None, None, :], s, -jnp.inf)
    m = jnp.max(s, axis=-1, keepdims=True)
    m = jnp.where(jnp.isinf(m), 0.0, m)  # all-masked rows
    p = jnp.exp(s - m)
    denom = p.sum(axis=-1, keepdims=True)
    p = jnp.where(denom > 0, p / jnp.maximum(denom, 1e-30), 0.0)
    return jnp.einsum("bhqk,bkhd->bqhd", p, v.astype(jnp.float32),
                      preferred_element_type=jnp.float32
                      ).astype(q.dtype)


def sequence_shard(x: jax.Array, mesh: Mesh, axis: str = "data"
                   ) -> jax.Array:
    """Shard ``[batch, seq, ...]`` along the sequence dimension over a
    mesh axis (the layout :func:`ring_attention` consumes)."""
    spec = P(*([None, axis] + [None] * (x.ndim - 2)))
    return jax.device_put(x, NamedSharding(mesh, spec))
