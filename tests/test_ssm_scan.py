"""``ops/ssm_scan.py`` against the recurrence it is algebra on, token by
token, on packed streams: rows that start mid-chunk, span several
chunks or are shorter than one, spare slots between and behind them.
The kernels run in Pallas' interpreter here; float32 operands, so the
forms differ by the order of their sums only."""

import jax
import jax.numpy as jnp
import numpy as np
import pytest

from predictionio_tpu.ops import ssm_scan

HEADS, D, N = 4, 64, 16
TOL = 2e-5


def _recurrence(x, b, c, dt, a, lengths, ends):
    """``S_t = exp(dt_t A) S_{t-1} + dt_t x_t B_t^T`` from zero at each
    row's first token, ``y_t = S_t C_t``: plain numpy, float64."""
    T = x.shape[0]
    y = np.zeros((T, HEADS, D))
    finals = []
    for n, end in zip(lengths, ends):
        S = np.zeros((HEADS, D, N))
        for t in range(end - n, end):
            S = np.exp(dt[t] * a)[:, None, None] * S \
                + dt[t][:, None, None] * x[t].reshape(HEADS, D)[:, :, None] \
                * b[t][None, None, :]
            y[t] = S @ c[t]
        finals.append(S)
    return y.reshape(T, -1), np.stack(finals)


def _stream(lengths, slots, align, seed):
    """A packed stream as the decoder makes it: rows ending on tiles of
    ``align`` slots, every slot carrying a row id, and the inputs ZERO in
    the spare slots (before a row's first token, behind the last row)."""
    rng = np.random.default_rng(seed)
    lengths = np.asarray(lengths)
    ends = np.cumsum(-(-lengths // align) * align)
    assert ends[-1] <= slots
    slot = np.arange(slots)
    row = np.minimum(np.searchsorted(ends, slot, side="right"),
                     len(lengths) - 1)
    valid = (slot >= (ends - lengths)[row]) & (slot < ends[-1])
    x = rng.normal(size=(slots, HEADS * D))
    b, c = rng.normal(size=(2, slots, N))
    dt = np.exp(rng.uniform(np.log(1e-3), np.log(0.5), (slots, HEADS)))
    a = -np.exp(rng.uniform(0, np.log(16), HEADS))
    x, b, c, dt = (np.where(valid[:, None], v, 0.0) for v in (x, b, c, dt))
    return (x, b, c, dt, a, row, ends - 1), lengths, ends, valid


CASES = {
    # chunk 32: a row inside one chunk, one that starts mid-chunk and
    # spans three, rows shorter than a tile, one ending on a chunk's edge
    "ragged": ([20, 70, 3, 1, 40, 26], 192, 8, 32),
    "one_long_row": ([150], 160, 8, 32),
    "many_short": ([5, 1, 1, 9, 2, 7, 1, 3], 96, 8, 32),
    "ends_on_chunks": ([32, 64, 32], 128, 16, 32),
    "stream_not_whole_chunks": ([30, 50], 104, 8, 32),
}


@pytest.mark.parametrize("form", ["twin", "kernel"])
@pytest.mark.parametrize("case", sorted(CASES))
def test_the_chunked_scan_is_the_recurrence(case, form):
    lengths, slots, align, chunk = CASES[case]
    args, lengths, ends, valid = _stream(lengths, slots, align, seed=3)
    want_y, want_s = _recurrence(*args[:5], lengths, ends)
    dev = [jnp.asarray(v, jnp.float32) for v in args[:5]] \
        + [jnp.asarray(v, jnp.int32) for v in args[5:]]
    fn = ssm_scan.scan_chunked if form == "twin" else jax.jit(
        lambda *a: ssm_scan.scan_kernel(*a, chunk=chunk, interpret=True))
    y, final = fn(*dev, chunk=chunk) if form == "twin" else fn(*dev)
    scale = np.abs(want_y[valid]).max()
    assert np.abs(np.asarray(y)[valid] - want_y[valid]).max() < TOL * scale
    # the state as the decode reads it: [N, heads x D]
    got = np.asarray(final).reshape(len(lengths), N, HEADS, D)
    want = want_s.transpose(0, 3, 1, 2)
    assert np.abs(got - want).max() < TOL * np.abs(want).max()


@pytest.mark.parametrize("form", ["twin", "kernel"])
def test_a_rows_outputs_do_not_depend_on_its_neighbours(form):
    """The same row behind another neighbour, at another place in its
    chunk: bit for bit where it lies on the same slots of a chunk, to
    rounding where it does not."""
    chunk = 32
    outs = []
    for before in ([24], [40, 8]):
        (x, b, c, dt, a, row, last), lengths, ends, _ = _stream(
            before + [45], 128, 8, seed=1)
        # the last row's own inputs, the same in both streams
        rng = np.random.default_rng(9)
        mine = slice(ends[-1] - 45, ends[-1])
        x[mine] = rng.normal(size=(45, HEADS * D))
        b[mine], c[mine] = rng.normal(size=(2, 45, N))
        dt[mine] = 0.05
        dev = [jnp.asarray(v, jnp.float32) for v in (x, b, c, dt, a)] \
            + [jnp.asarray(v, jnp.int32) for v in (row, last)]
        fn = ssm_scan.scan_chunked if form == "twin" else (
            lambda *a, chunk: ssm_scan.scan_kernel(*a, chunk=chunk,
                                                   interpret=True))
        y, final = fn(*dev, chunk=chunk)
        outs.append((np.asarray(y)[mine], np.asarray(final)[-1]))
    (y0, s0), (y1, s1) = outs
    assert np.abs(y0 - y1).max() < TOL * np.abs(y0).max()
    assert np.abs(s0 - s1).max() < TOL * np.abs(s0).max()


@pytest.mark.parametrize("form", ["plain", "kernel"])
def test_a_step_is_one_token_of_the_recurrence(form):
    rng = np.random.default_rng(5)
    rows = 3
    state = rng.normal(size=(rows, N, HEADS * D)).astype(np.float32)
    x = rng.normal(size=(rows, HEADS * D)).astype(np.float32)
    b, c = rng.normal(size=(2, rows, N)).astype(np.float32)
    dt = rng.uniform(1e-3, 0.3, (rows, HEADS)).astype(np.float32)
    a = -rng.uniform(1, 16, HEADS).astype(np.float32)
    decay = np.exp(dt * a)
    fn = ssm_scan.step_plain if form == "plain" else (
        lambda *v: ssm_scan.step_kernel(*v, interpret=True))
    new, y = fn(*(jnp.asarray(v) for v in (state, x, b, c, decay, dt)))
    S = state.reshape(rows, N, HEADS, D).astype(np.float64)
    want = decay[:, None, :, None] * S + b[:, :, None, None] \
        * (dt[:, :, None] * x.reshape(rows, HEADS, D))[:, None]
    want_y = np.einsum("rnhd,rn->rhd", want, c).reshape(rows, -1)
    assert np.abs(np.asarray(new).reshape(want.shape) - want).max() < 1e-5
    assert np.abs(np.asarray(y) - want_y).max() < 1e-4


def test_scan_then_steps_is_the_scan_of_the_longer_row():
    """A row's final state IS what the decode goes on from: scanning 40
    tokens, then 5 steps, gives the scan of 45."""
    (x, b, c, dt, a, row, last), lengths, ends, _ = _stream(
        [45], 48, 8, seed=2)
    first = ends[0] - 45
    dev = lambda v, kind=jnp.float32: jnp.asarray(v, kind)  # noqa: E731
    y_all, s_all = ssm_scan.scan_chunked(
        dev(x), dev(b), dev(c), dev(dt), dev(a), dev(row, jnp.int32),
        dev(last, jnp.int32), chunk=16)
    cut = first + 40
    head = np.arange(48) < cut
    y, s = ssm_scan.scan_chunked(
        *(dev(np.where(head[:, None], v, 0.0)) for v in (x, b, c, dt)),
        dev(a), dev(row, jnp.int32), jnp.asarray([cut - 1], jnp.int32),
        chunk=16)
    for t in range(cut, cut + 5):
        s, yt = ssm_scan.step_plain(
            s, dev(x[t:t + 1]), dev(b[t:t + 1]), dev(c[t:t + 1]),
            dev(np.exp(dt[t:t + 1] * a)), dev(dt[t:t + 1]))
        assert np.abs(np.asarray(yt)[0] - np.asarray(y_all)[t]).max() < 1e-4
    assert np.abs(np.asarray(s) - np.asarray(s_all)).max() < 1e-4


def test_the_kernel_says_which_shapes_it_takes():
    assert ssm_scan.kernel_takes(64, 64, 256)
    assert ssm_scan.kernel_takes(4, 32, 16)
    assert not ssm_scan.kernel_takes(4, 48, 16)   # heads do not tile lanes
    assert not ssm_scan.kernel_takes(2, 32, 16)   # half a lane tile a group
    with pytest.raises(ValueError, match="not a shape"):
        ssm_scan.scan_kernel(
            jnp.zeros((16, 96)), jnp.zeros((16, 8)), jnp.zeros((16, 8)),
            jnp.zeros((16, 2)), jnp.zeros((2,)), jnp.zeros((16,), jnp.int32),
            jnp.asarray([15], jnp.int32), chunk=16)


# -- several groups: a B and a C for each run of consecutive heads ---------

G_D = 16  # heads of 16: eight of them a lane tile, one grid step's


def _grouped(groups, lengths, slots, align, seed):
    """A packed stream of ``8 x groups`` heads of 16 in ``groups``
    groups (a grid step's 8 heads are exactly one group's: the fewest
    the kernel takes), ``B`` and ``C`` ``[T, groups x N]`` as the
    in-projection lays them, and the token-by-token recurrence's ``y``
    and final states, float64."""
    heads = 8 * groups
    rng = np.random.default_rng(seed)
    lengths = np.asarray(lengths)
    ends = np.cumsum(-(-lengths // align) * align)
    slot = np.arange(slots)
    row = np.minimum(np.searchsorted(ends, slot, side="right"),
                     len(lengths) - 1)
    valid = (slot >= (ends - lengths)[row]) & (slot < ends[-1])
    x = rng.normal(size=(slots, heads * G_D))
    b, c = rng.normal(size=(2, slots, groups * N))
    dt = np.exp(rng.uniform(np.log(1e-3), np.log(0.5), (slots, heads)))
    a = -np.exp(rng.uniform(0, np.log(16), heads))
    x, b, c, dt = (np.where(valid[:, None], v, 0.0) for v in (x, b, c, dt))
    of = np.arange(heads) // 8  # a head's group
    y = np.zeros((slots, heads, G_D))
    finals = []
    for n, end in zip(lengths, ends):
        S = np.zeros((heads, G_D, N))
        for t in range(end - n, end):
            bt, ct = (v[t].reshape(groups, N)[of] for v in (b, c))
            S = np.exp(dt[t] * a)[:, None, None] * S \
                + dt[t][:, None, None] * x[t].reshape(heads, G_D)[:, :, None] \
                * bt[:, None, :]
            y[t] = np.einsum("hdn,hn->hd", S, ct)
        finals.append(S)
    return (x, b, c, dt, a, row, ends - 1), valid, \
        y.reshape(slots, -1), np.stack(finals)


@pytest.mark.parametrize("form", ["twin", "kernel"])
@pytest.mark.parametrize("groups", [2, 8])
def test_the_grouped_scan_is_the_recurrence(groups, form):
    """Rows that start mid-chunk, span three chunks or are shorter than
    a tile, every head against ITS group's ``B`` and ``C``."""
    lengths, slots, align, chunk = CASES["ragged"]
    args, valid, want_y, want_s = _grouped(groups, lengths, slots, align, 7)
    dev = [jnp.asarray(v, jnp.float32) for v in args[:5]] \
        + [jnp.asarray(v, jnp.int32) for v in args[5:]]
    assert ssm_scan.kernel_takes(8 * groups, G_D, chunk, groups)
    if form == "twin":
        y, final = ssm_scan.scan_chunked(*dev, chunk=chunk, groups=groups)
    else:
        y, final = jax.jit(lambda *a: ssm_scan.scan_kernel(
            *a, chunk=chunk, groups=groups, interpret=True))(*dev)
    scale = np.abs(want_y[valid]).max()
    assert np.abs(np.asarray(y)[valid] - want_y[valid]).max() < TOL * scale
    got = np.asarray(final).reshape(len(lengths), N, 8 * groups, G_D)
    want = want_s.transpose(0, 3, 1, 2)
    assert np.abs(got - want).max() < TOL * np.abs(want).max()
    # one B and one C for all the heads is another answer
    y1, _ = ssm_scan.scan_chunked(dev[0], dev[1][:, :N], dev[2][:, :N],
                                  *dev[3:], chunk=chunk)
    assert np.abs(np.asarray(y1)[valid] - want_y[valid]).max() > 0.1 * scale


@pytest.mark.parametrize("form", ["plain", "kernel"])
@pytest.mark.parametrize("groups", [2, 8])
def test_a_grouped_step_is_one_token_of_the_recurrence(groups, form):
    rng = np.random.default_rng(5)
    rows, heads = 3, 8 * groups
    state = rng.normal(size=(rows, N, heads * G_D)).astype(np.float32)
    x = rng.normal(size=(rows, heads * G_D)).astype(np.float32)
    b, c = rng.normal(size=(2, rows, groups * N)).astype(np.float32)
    dt = rng.uniform(1e-3, 0.3, (rows, heads)).astype(np.float32)
    a = -rng.uniform(1, 16, heads).astype(np.float32)
    decay = np.exp(dt * a)
    fn = ssm_scan.step_plain if form == "plain" else (
        lambda *v: ssm_scan.step_kernel(*v, interpret=True))
    new, y = fn(*(jnp.asarray(v) for v in (state, x, b, c, decay, dt)))
    of = np.arange(heads) // 8
    bh, ch = (v.reshape(rows, groups, N)[:, of] for v in (b, c))  # [r, h, N]
    S = state.reshape(rows, N, heads, G_D).astype(np.float64)
    want = decay[:, None, :, None] * S + bh.transpose(0, 2, 1)[..., None] \
        * (dt[:, :, None] * x.reshape(rows, heads, G_D))[:, None]
    want_y = np.einsum("rnhd,rhn->rhd", want, ch).reshape(rows, -1)
    assert np.abs(np.asarray(new).reshape(want.shape) - want).max() < 1e-5
    assert np.abs(np.asarray(y) - want_y).max() < 1e-4


def test_the_kernel_takes_groups_of_whole_grid_steps():
    assert ssm_scan.kernel_takes(128, 64, 128, 8)    # 16 heads a group
    assert ssm_scan.kernel_takes(16, 16, 32, 2)
    assert not ssm_scan.kernel_takes(16, 16, 32, 4)  # 4 heads a group
    assert not ssm_scan.kernel_takes(64, 64, 256, 3)
    with pytest.raises(ValueError, match="4 group"):
        ssm_scan.scan_kernel(
            jnp.zeros((32, 256)), jnp.zeros((32, 4 * N)),
            jnp.zeros((32, 4 * N)), jnp.zeros((32, 16)), jnp.zeros((16,)),
            jnp.zeros((32,), jnp.int32), jnp.asarray([31], jnp.int32),
            chunk=32, groups=4)
