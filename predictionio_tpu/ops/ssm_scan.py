"""The selective state-space recurrence of a Mamba-2 layer
(``models/decoder.py``'s ``mamba`` kind) in the two forms serving needs:
the prefill's CHUNKED scan over a packed stream, and the decode's
one-token update.

The recurrence, a head ``h`` with ``head_dim`` channels ``x_t``, a
scalar step ``dt_t > 0`` and decay rate ``A < 0``, and ``B_t``, ``C_t
[N]`` shared by the heads of ``h``'s GROUP (``groups`` equal runs of
consecutive heads; one group: by all): ``S_t = exp(dt_t A) S_{t-1} +
dt_t x_t B_t^T`` (``S`` zero before a row's first token), ``y_t = S_t
C_t``. The skip ``D x_t`` is the caller's. ``B`` and ``C`` come as the
in-projection lays them, ``[T, groups x N]``, a group's ``N`` behind
the last one's.

:func:`ssm_scan` takes the prefill's stream as ``models/decoder.py``
packs it: the rows' tokens one row behind the other, ``row [T]`` saying
whose a slot is, and ``x``, ``B``, ``C`` and ``dt`` ZERO in the spare
slots. In chunks of ``chunk`` tokens (``mamba_chunk_size``), with ``cum_t
= sum_{r <= t, r in t's chunk} dt_r A``:

- inside a chunk, ``y_t += sum_{s <= t} (C_t . B_s) exp(cum_t - cum_s)
  dt_s x_s`` over the ``s`` of ``t``'s OWN row: ``C B^T`` once a chunk
  and group for all its heads, masked to same-row causal pairs, then a
  head's decay and one product with its channels;
- between chunks, ``y_t += exp(cum_t) C_t S_in`` where ``t``'s row is
  the row of the last slot before the chunk (else nothing: the state is
  another row's), and ``S_out = exp(cum_end) S_in [same row] + sum_s
  exp(cum_end - cum_s) dt_s x_s B_s^T`` over the ``s`` of the END
  slot's row.

So a row restarts the recurrence wherever in a chunk it begins, by the
mask alone: rows are never padded to chunks. The state a row leaves for
the decode is the same expression taken at the row's LAST slot in place
of the chunk's; a chunk holds the ends of none, one or several rows.

The state is laid out ``[N, heads x head_dim]``: a head's channels in
the LANES beside the next head's (two heads of 64 a lane tile), ``N`` on
the sublanes, so that every product writes whole lane tiles and the
decode's update is elementwise over full vectors. float32 throughout:
the state, ``dt``, ``cum`` and the decays; the products take ``x``'s
dtype (bfloat16 as served) and accumulate in float32.

``ssm_scan`` and ``ssm_step`` are ``custom-call`` operations of those
names in a device trace. Off the TPU both take their plain
``jax.numpy`` twins (:func:`scan_chunked`, :func:`step_plain`: the same
algebra as batched einsums; the tests hold the kernels, in Pallas'
interpreter, and the twins to the token-by-token recurrence).
"""

from __future__ import annotations

import functools
from typing import Tuple

import jax
import jax.numpy as jnp
from jax.experimental import pallas as pl
from jax.experimental.pallas import tpu as pltpu

LANES = 128
#: heads a grid step of the scan takes (their channels side by side: 512
#: lanes of 64-wide heads). The rows' final states of one group stay in
#: VMEM over the chunks: 16 rows x 128 x 512 float32 are 4 MiB
HEAD_GROUP = 8
#: lanes of a row's state a grid step of the decode's update takes:
#: ``[128, 1024]`` float32 coming and going, 512 KiB each
STEP_LANES = 1024
VMEM_LIMIT = 40 << 20


def _on_chip() -> bool:
    return jax.default_backend() == "tpu"


def kernel_takes(heads: int, head_dim: int, chunk: int,
                 groups: int = 1) -> bool:
    """Whether the Pallas scan is written for these sizes: heads that
    tile the lanes (``head_dim`` a divisor of 128), grid steps of whole
    lane tiles whose heads lie inside ONE of the ``groups`` that share a
    ``B`` and a ``C``, chunks of whole sublane tiles. Anything else
    takes the twin."""
    group = min(HEAD_GROUP, heads)
    return LANES % head_dim == 0 and heads % group == 0 \
        and (group * head_dim) % LANES == 0 and chunk % 16 == 0 \
        and heads % groups == 0 and heads // groups % group == 0


# -- what both forms of the scan share -----------------------------------------

def _pad(a, T: int, value=0):
    return a if a.shape[0] == T else jnp.pad(
        a, ((0, T - a.shape[0]),) + ((0, 0),) * (a.ndim - 1),
        constant_values=value)


def _plan(x, b, c, dt, a, row, last, chunk: int):
    """The stream padded to whole chunks (zeros; a row id no row has)
    and the small per-token arrays of its scan: ``cum [T, heads]`` (the
    running sum of ``dt A`` inside a chunk), ``seg [T]`` (``row`` as
    float32), and per chunk ``prev`` (the row of the slot before it; -1
    before the first) and the rows that END in it, ``lo <= r < hi``,
    with ``end [B]`` each row's last slot inside its chunk and ``of
    [B]`` that chunk."""
    T = -(-dt.shape[0] // chunk) * chunk
    x, b, c, dt = (_pad(v, T) for v in (x, b, c, dt))
    row = _pad(row, T, value=last.shape[0])
    heads = dt.shape[1]
    n = T // chunk
    cum = jnp.cumsum((dt * a).reshape(n, chunk, heads), axis=1)
    seg = row.astype(jnp.float32)
    prev = jnp.concatenate([jnp.full((1,), -1, jnp.int32),
                            row[chunk - 1:-1:chunk].astype(jnp.int32)])
    of = (last // chunk).astype(jnp.int32)
    chunks = jnp.arange(n, dtype=jnp.int32)
    lo = jnp.sum(of[None, :] < chunks[:, None], axis=1, dtype=jnp.int32)
    hi = jnp.sum(of[None, :] <= chunks[:, None], axis=1, dtype=jnp.int32)
    return (x, b, c, dt), (cum.reshape(T, heads), seg, prev, lo, hi,
                           (last % chunk).astype(jnp.int32), of)


# -- the twin ------------------------------------------------------------------

def scan_chunked(x, b, c, dt, a, row, last, *, chunk: int, groups: int = 1):
    """:func:`ssm_scan` as batched einsums over the chunks (and a
    ``lax.scan`` over them for the carried state): the same algebra and
    the same roundings as the kernel, for the CPU (whose batched
    products take no bfloat16: an operand is rounded to ``x``'s dtype
    and widened again). A group at a time where ``B`` and ``C`` differ
    by group: one group is the lines it always was."""
    f32, dtp = jnp.float32, x.dtype

    def rounded(v):
        return v.astype(dtp).astype(f32)

    T0, heads = dt.shape
    N, D = b.shape[1] // groups, x.shape[1] // heads
    per = heads // groups  # heads that share a B and a C
    (x, b, c, dt), (cum, seg, prev, _, _, end, of) = _plan(
        x, b, c, dt, a, row, last, chunk)
    T = dt.shape[0]
    n, Q = T // chunk, chunk
    xs = rounded(x).reshape(n, Q, heads, D)
    bs = rounded(b).reshape(n, Q, groups, N)
    cs = rounded(c).reshape(n, Q, groups, N)

    def by_group(fn):
        """``fn(group, its heads)`` of every group, side by side along
        the heads' axis (the third of what ``fn`` returns)."""
        parts = [fn(k, slice(k * per, (k + 1) * per)) for k in range(groups)]
        return parts[0] if groups == 1 else jnp.concatenate(parts, axis=2)

    cum, dts, seg = (v.reshape((n, Q) + v.shape[1:])
                     for v in (cum, dt, seg))
    prev = prev.astype(f32)

    pairs = (seg[:, :, None] == seg[:, None, :]) \
        & (jnp.arange(Q)[None, :] <= jnp.arange(Q)[:, None])
    decay = jnp.exp(jnp.minimum(cum[:, :, None] - cum[:, None, :], 0.0))

    def inside(k, mine):  # [n, q, heads of the group, D]
        g = jnp.where(pairs, jnp.einsum("cqn,csn->cqs", cs[:, :, k],
                                        bs[:, :, k]), 0.0)
        m = g[..., None] * decay[..., mine] * dts[:, None, :, mine]
        return jnp.einsum("cqsh,cshd->cqhd", rounded(m), xs[:, :, mine])

    y = by_group(inside)

    def state_at(cum_e, seg_e, chunks, s_in):
        """The state after the slot whose running sum is ``cum_e [k,
        heads]`` and row ``seg_e [k]``, in chunk ``chunks [k]`` entered
        with ``s_in [k, N, heads, D]``."""
        w = jnp.exp(jnp.minimum(cum_e[:, None] - cum[chunks], 0.0)) \
            * dts[chunks] * (seg[chunks] == seg_e[:, None])[..., None]
        keep = jnp.where((seg_e == prev[chunks])[:, None], jnp.exp(cum_e),
                         0.0)
        xw, bk = rounded(xs[chunks] * w[..., None]), bs[chunks]
        return keep[:, None, :, None] * s_in + by_group(
            lambda k, mine: jnp.einsum("ksn,kshd->knhd", bk[:, :, k],
                                       xw[:, :, mine]))

    every = jnp.arange(n)

    def carry(s, i):
        return state_at(cum[i, -1][None], seg[i, -1][None], i[None],
                        s[None])[0], s

    _, s_in = jax.lax.scan(carry, jnp.zeros((N, heads, D), f32), every)
    seen = jnp.where((seg == prev[:, None])[..., None], jnp.exp(cum), 0.0)
    s_r = rounded(s_in)
    y = y + seen[..., None] * by_group(
        lambda k, mine: jnp.einsum("cqn,cnhd->cqhd", cs[:, :, k],
                                   s_r[:, :, mine]))
    rows = jnp.arange(last.shape[0])
    final = state_at(cum[of, end], rows.astype(f32), of, s_in[of])
    return (y.reshape(T, heads * D)[:T0],
            final.reshape(last.shape[0], N, heads * D))


def step_plain(state, x, b, c, decay, dt):
    """:func:`ssm_step` as plain lines."""
    rows, N, W = state.shape
    D = W // dt.shape[-1]

    def lanes(v):  # [B, groups x N] -> [B, N, lanes]: a group's lanes its own
        v = v.reshape(rows, -1, N).swapaxes(1, 2)
        return jnp.repeat(v, W // v.shape[-1], axis=-1)

    new = jnp.repeat(decay, D, axis=-1)[:, None, :] * state \
        + lanes(b) * (jnp.repeat(dt, D, axis=-1) * x)[:, None, :]
    return new, jnp.sum(new * lanes(c), axis=1)


# -- the kernels ---------------------------------------------------------------

def _scan_kernel(lo_ref, hi_ref, end_ref, prev_ref, x_ref, bt_ref, c_ref,
                 col_ref, row_ref, y_ref, fin_ref, s_ref, *, group: int,
                 head_dim: int):
    """One chunk of one group of heads. ``col_ref [Q, 2 group + 1]``: the
    heads' ``cum``, their ``dt`` and the slot's row, a token a row (what
    is broadcast along the lanes); ``row_ref [.., Q]`` the same a token
    a lane (what is broadcast along the sublanes)."""
    f32, dtp = jnp.float32, x_ref.dtype
    ci = pl.program_id(1)
    Q, W = x_ref.shape
    per, seg_at = LANES // head_dim, 2 * group

    @pl.when(ci == 0)
    def _():
        s_ref[...] = jnp.zeros(s_ref.shape, f32)

    bt, cm = bt_ref[...], c_ref[...]
    seg_c = col_ref[:, seg_at:seg_at + 1]
    ti = jax.lax.broadcasted_iota(jnp.int32, (Q, Q), 0)
    si = jax.lax.broadcasted_iota(jnp.int32, (Q, Q), 1)
    pairs = (seg_c == row_ref[seg_at:seg_at + 1, :]) & (si <= ti)
    g = jnp.where(pairs, jnp.dot(cm, bt, preferred_element_type=f32), 0.0)
    prev = prev_ref[ci].astype(f32)
    seen = seg_c == prev
    lane = jax.lax.broadcasted_iota(jnp.int32, (1, LANES), 1)

    def by_head(vals):
        """A lane tile's heads' scalars ``[.., 1]`` along its lanes."""
        out = vals[0]
        for k in range(1, per):
            out = jnp.where(lane >= k * head_dim, vals[k], out)
        return out

    def state_at(e_row, r, t):
        """Lane tile ``t`` of the state after the slot whose side row is
        ``e_row [1, ..]`` and whose row is ``r``."""
        heads = range(t * per, (t + 1) * per)
        w = by_head([jnp.exp(jnp.minimum(
            e_row[:, j:j + 1] - col_ref[:, j:j + 1], 0.0))
            * col_ref[:, group + j:group + j + 1] for j in heads])
        keep = by_head([jnp.where(r == prev, jnp.exp(e_row[:, j:j + 1]), 0.0)
                        for j in heads])
        lanes = slice(t * LANES, (t + 1) * LANES)
        xw = (x_ref[:, lanes].astype(f32)
              * jnp.where(seg_c == r, w, 0.0)).astype(dtp)
        return keep * s_ref[:, lanes] \
            + jnp.dot(bt, xw, preferred_element_type=f32)

    for t in range(W // LANES):
        lanes = slice(t * LANES, (t + 1) * LANES)
        x2 = x_ref[:, lanes]
        heads = range(t * per, (t + 1) * per)
        y2 = jnp.dot(cm, s_ref[:, lanes].astype(dtp),
                     preferred_element_type=f32) * by_head(
            [jnp.where(seen, jnp.exp(col_ref[:, j:j + 1]), 0.0)
             for j in heads])
        for k, j in enumerate(heads):
            m = g * jnp.exp(jnp.minimum(
                col_ref[:, j:j + 1] - row_ref[j:j + 1, :], 0.0)) \
                * row_ref[group + j:group + j + 1, :]
            mine = (lane >= k * head_dim) & (lane < (k + 1) * head_dim)
            y2 = y2 + jnp.dot(m.astype(dtp), jnp.where(mine, x2, 0),
                              preferred_element_type=f32)
        y_ref[:, lanes] = y2

    def leave(r, _):  # a row that ends here leaves its state
        e_row = col_ref[pl.ds(end_ref[r], 1), :]
        for t in range(W // LANES):
            fin_ref[r, :, t * LANES:(t + 1) * LANES] = state_at(
                e_row, r.astype(f32), t)
        return 0

    jax.lax.fori_loop(lo_ref[ci], hi_ref[ci], leave, 0)
    e_row = col_ref[Q - 1:Q, :]
    new = [state_at(e_row, e_row[:, seg_at:seg_at + 1], t)
           for t in range(W // LANES)]
    for t, s in enumerate(new):
        s_ref[:, t * LANES:(t + 1) * LANES] = s


def scan_kernel(x, b, c, dt, a, row, last, *, chunk: int, groups: int = 1,
                interpret: bool = False):
    """:func:`ssm_scan` as the Pallas kernel (``interpret``: in Pallas'
    interpreter, for the tests). A grid step's heads read the ``N``
    rows of ``B^T`` and columns of ``C`` that are their group's."""
    f32 = jnp.float32
    T0, heads = dt.shape
    N, D, rows = b.shape[1] // groups, x.shape[1] // heads, last.shape[0]
    group = min(HEAD_GROUP, heads)
    if not kernel_takes(heads, D, chunk, groups):
        raise ValueError(f"{heads} heads of {D} in {groups} group(s), "
                         f"chunks of {chunk}: not a shape the scan kernel "
                         f"is written for")
    steps = heads // groups // group  # grid steps a group of B and C

    def of(g):  # the B-and-C group of grid step g's heads
        return 0 if groups == 1 else g // steps

    (x, b, c, dt), (cum, seg, prev, lo, hi, end, _) = _plan(
        x, b, c, dt, a, row, last, chunk)
    T = dt.shape[0]
    ng, W = heads // group, group * D
    side = jnp.concatenate([
        cum.reshape(T, ng, group), dt.reshape(T, ng, group),
        jnp.broadcast_to(seg[:, None, None], (T, ng, 1))], axis=-1)
    col = side.transpose(1, 0, 2)                        # [ng, T, 2 g + 1]
    wide = -(-col.shape[-1] // 8) * 8
    by_lane = jnp.pad(side.transpose(1, 2, 0),
                      ((0, 0), (0, wide - col.shape[-1]), (0, 0)))
    y, final = pl.pallas_call(
        functools.partial(_scan_kernel, group=group, head_dim=D),
        grid_spec=pltpu.PrefetchScalarGridSpec(
            num_scalar_prefetch=4, grid=(ng, T // chunk),
            in_specs=[
                pl.BlockSpec((chunk, W), lambda g, i, *_: (i, g)),
                pl.BlockSpec((N, chunk), lambda g, i, *_: (of(g), i)),
                pl.BlockSpec((chunk, N), lambda g, i, *_: (i, of(g))),
                pl.BlockSpec((None, chunk, col.shape[-1]),
                             lambda g, i, *_: (g, i, 0)),
                pl.BlockSpec((None, wide, chunk),
                             lambda g, i, *_: (g, 0, i))],
            out_specs=[
                pl.BlockSpec((chunk, W), lambda g, i, *_: (i, g)),
                pl.BlockSpec((rows, N, W), lambda g, i, *_: (0, 0, g))],
            scratch_shapes=[pltpu.VMEM((N, W), f32)]),
        out_shape=[jax.ShapeDtypeStruct((T, heads * D), f32),
                   jax.ShapeDtypeStruct((rows, N, heads * D), f32)],
        compiler_params=pltpu.CompilerParams(
            dimension_semantics=("parallel", "arbitrary"),
            vmem_limit_bytes=VMEM_LIMIT),
        interpret=interpret, name="ssm_scan",
    )(lo, hi, end, prev, x, b.T, c, col, by_lane)
    return y[:T0], final


def _step_kernel(s_ref, decay_ref, dtx_ref, b_ref, c_ref, new_ref, y_ref):
    new = decay_ref[0] * s_ref[0] + b_ref[0] * dtx_ref[0]
    new_ref[0] = new
    y_ref[0] = jnp.sum(new * c_ref[0], axis=0, keepdims=True)


def step_kernel(state, x, b, c, decay, dt, *, interpret: bool = False):
    """:func:`ssm_step` as the Pallas kernel: a row's state goes through
    VMEM once, ``STEP_LANES`` lanes at a time, and comes back in its own
    buffer."""
    f32 = jnp.float32
    rows, N, W = state.shape
    D = W // dt.shape[-1]
    groups = b.shape[-1] // N
    # a grid step's lanes lie inside one group's
    lanes = next(n for n in (STEP_LANES, 512, 256, LANES, W)
                 if W // groups % n == 0)
    steps = W // groups // lanes  # grid steps a group of B and C
    wide = lambda v: jnp.repeat(v, D, axis=-1)  # noqa: E731
    row = pl.BlockSpec((1, 1, lanes), lambda r, i: (r, 0, i))
    col = pl.BlockSpec((1, N, 1), lambda r, i: (
        r, 0 if groups == 1 else i // steps, 0))
    block = pl.BlockSpec((1, N, lanes), lambda r, i: (r, 0, i))
    new, y = pl.pallas_call(
        _step_kernel, grid=(rows, W // lanes),
        in_specs=[block, row, row, col, col], out_specs=[block, row],
        out_shape=[jax.ShapeDtypeStruct(state.shape, f32),
                   jax.ShapeDtypeStruct((rows, 1, W), f32)],
        input_output_aliases={0: 0},
        compiler_params=pltpu.CompilerParams(
            dimension_semantics=("parallel", "parallel")),
        interpret=interpret, name="ssm_step",
    )(state, wide(decay)[:, None], (wide(dt) * x)[:, None],
      b.astype(f32)[..., None], c.astype(f32)[..., None])
    return new, y[:, 0]


# -- what the decoder calls ----------------------------------------------------

def ssm_scan(x: jax.Array, b: jax.Array, c: jax.Array, dt: jax.Array,
             a: jax.Array, row: jax.Array, last: jax.Array, *,
             chunk: int, groups: int = 1) -> Tuple[jax.Array, jax.Array]:
    """The recurrence over a packed stream. ``x [T, heads x D]``, ``b``,
    ``c [T, groups x N]`` in the products' dtype and ``dt [T, heads]``
    float32, all ZERO in the spare slots; ``a [heads]`` float32,
    negative; ``row [T]`` the row each slot belongs to (a spare slot: a
    row beside it); ``last [B]`` each row's last slot -> ``(y [T, heads
    x D] float32, final [B, N, heads x D] float32)``: ``y_t = S_t C_t``
    and each row's state after its last token."""
    heads = dt.shape[1]
    if _on_chip() and kernel_takes(heads, x.shape[1] // heads, chunk,
                                   groups):
        return scan_kernel(x, b, c, dt, a, row, last, chunk=chunk,
                           groups=groups)
    return scan_chunked(x, b, c, dt, a, row, last, chunk=chunk,
                        groups=groups)


def ssm_step(state: jax.Array, x: jax.Array, b: jax.Array, c: jax.Array,
             decay: jax.Array, dt: jax.Array) -> Tuple[jax.Array, jax.Array]:
    """One token a row: ``state [B, N, heads x D]`` float32, ``x [B,
    heads x D]``, ``b``, ``c [B, groups x N]``, ``decay = exp(dt A)``
    and ``dt [B, heads]``, all float32 -> ``(state', y [B, heads x D])``
    with ``state' = decay state + b (dt x)^T`` and ``y = state' c``, a
    head against its group's ``b`` and ``c``."""
    groups = b.shape[-1] // state.shape[1]
    if _on_chip() and state.shape[-1] // groups % LANES == 0 \
            and state.shape[1] % 8 == 0:
        return step_kernel(state, x, b, c, decay, dt)
    return step_plain(state, x, b, c, decay, dt)
