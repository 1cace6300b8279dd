"""``ops/hyper_mix.py`` against the plain lines of ``models/decoder.py``
(``_hc_coefficients`` and the branch of ``_sub_block`` that a stream
under a tile's tokens takes), on the CPU through Pallas' interpreter.

Tolerances. Float32 on both sides; the two forms differ by the order of
their sums and by how the projection's float32 is put together (three
bfloat16 parts a side in the kernel, whatever ``highest`` does outside
it): ``RTOL`` of the largest value compared holds every comparison
(readings 1e-7 .. 4e-7 here). A projection at ONE bfloat16 pass reads
1e-3 (the last test but one), a thousand times the tolerance.
"""

import functools
import json
import os

import jax
import jax.numpy as jnp
import numpy as np
import pytest

from predictionio_tpu.models import decoder
from predictionio_tpu.ops import hyper_mix
from test_decoder import LAGUNA, SMALL, XING, _all_eqns, _setup

ROOT = os.path.dirname(os.path.dirname(os.path.abspath(__file__)))
RTOL = 2e-6
TILE = hyper_mix.TILE
QUANTITIES = ("pre", "post", "res", "z", "x_new", "gap")


def _config(width=None, **over):
    """The benchmark's configuration (``width`` None: the published
    3584) or the tests' small one at ``width``."""
    if width is None:
        with open(os.path.join(ROOT, "cellbench", "configs",
                               "xing4-29b-a4b-l6.json")) as f:
            d = json.load(f)
    else:
        d = {**XING, "hidden_size": width}
    return decoder.DecoderConfig.from_dict({**d, **over})


def _weights(cfg, seed=0, **init):
    """One sub-block's hyper-connection weights and its norm's gain,
    drawn by ``INIT`` as ``init_weights`` draws a layer's."""
    shapes = tuple((k, v) for k, v in sorted(
        decoder._layer_shapes(cfg, 0).items())
        if k.startswith("hc_op_") or k == "op_norm")
    lw = decoder._draw(jax.random.key(seed), {**decoder.INIT, **init},
                       shapes=shapes, dtype="float32")
    gain = 1.0 + 0.1 * jax.random.normal(jax.random.key(seed + 1),
                                         lw["op_norm"].shape)
    return {**lw, "op_norm": gain}


def _streams(cfg, tokens, seed=0):
    """Streams that differ from each other, about unit RMS."""
    scale = jnp.array([1.0, 2.0, 0.5, 1.5][:cfg.hc_mult])
    return jax.random.normal(
        jax.random.key(100 + seed),
        (cfg.hc_mult, tokens, cfg.hidden_size)) * scale[:, None, None]


def _plain(lw, x, out_of, cfg, valid=None):
    """Today's lines, whatever the stream's length."""
    n = cfg.hc_mult
    pre, post, res = decoder._hc_coefficients(lw, "op", x, cfg)
    u = sum(pre[j][:, None] * x[j] for j in range(n))
    z = decoder._rms(u, lw["op_norm"], cfg.norm_eps)
    out = out_of(z)
    new = jnp.stack([sum(res[i, j][:, None] * x[j] for j in range(n))
                     + post[i][:, None] * out for i in range(n)])
    off = jnp.abs(jnp.sum(res, axis=1) - 1.0)
    if valid is not None:
        off = jnp.where(valid[None, :], off, 0.0)
    return {"pre": pre, "post": post, "res": res, "z": z, "x_new": new,
            "gap": jnp.max(off)}


def _kernels(lw, x, out_of, cfg, valid=None):
    n = cfg.hc_mult
    seen = {}

    def fn(z):
        seen["z"] = z
        return out_of(z), None

    new, _, gap = decoder._sub_block(lw, "op", x, fn, cfg, valid)
    _, coef = hyper_mix.hyper_mix_read(
        x, *decoder._hc_columns(lw, "op", n), lw["op_norm"],
        eps=cfg.hc_eps, norm_eps=cfg.norm_eps, iters=cfg.hc_sinkhorn_iters,
        clamp=(cfg.mhc_h_res_clamp_min, cfg.mhc_h_res_clamp_max))
    pre, post, res = hyper_mix.coefficients(coef, n)
    return {"pre": pre, "post": post, "res": res, "z": seen["z"],
            "x_new": new, "gap": gap}


def _operator(z):
    """Something a token's own for ``F``: a sub-block's write must add
    it at ``post``, not the read back."""
    return jnp.tanh(z) * 0.5 + 0.1


def _close(got, want, rtol=RTOL):
    got, want = np.asarray(got), np.asarray(want)
    assert got.shape == want.shape
    np.testing.assert_allclose(got, want, rtol=0,
                               atol=rtol * max(float(np.abs(want).max()), 1))


@functools.lru_cache(maxsize=None)
def _both(size):
    """Both forms at one size: ``toy`` is width 64 over a tile and a
    half (the last tile is Pallas' to pad), ``published`` one tile of
    the benchmark's ``[4, t, 3584]``."""
    cfg = _config(64) if size == "toy" else _config()
    tokens = TILE + TILE // 2 if size == "toy" else TILE
    lw, x = _weights(cfg), _streams(cfg, tokens)
    assert x.shape[1] >= TILE  # the kernels' side of the rule
    return _plain(lw, x, _operator, cfg), _kernels(lw, x, _operator, cfg)


@pytest.mark.parametrize("what", QUANTITIES)
@pytest.mark.parametrize("size", ["toy", "published"])
def test_the_kernels_are_the_plain_lines(size, what):
    want, got = _both(size)
    if what == "gap":  # of a sum that is 1: float32 rounding of it
        assert abs(float(got[what]) - float(want[what])) <= RTOL
    else:
        _close(got[what], want[what])


def test_the_published_weights_mix_neither_evenly_nor_not_at_all():
    """What the comparison above is worth: ``res`` off its diagonal and
    ``pre`` move from token to token at the benchmark's ``init``."""
    res = np.asarray(_both("published")[1]["res"])
    off = 1.0 - np.einsum("iit->it", res)
    assert 0.05 < off.min() and off.max() < 0.95 and off.std() > 0.02


@pytest.mark.parametrize("clamp", [(-0.5, 0.5), (0.25, 30.0)])
def test_the_clamp_engages_where_the_plain_lines_engage_it(clamp):
    """``a_res`` large enough that most of ``a x~ phi + b`` lies
    outside the clamp: ``exp`` of the bound, not of the value."""
    cfg = _config(64, mhc_h_res_clamp_min=clamp[0],
                  mhc_h_res_clamp_max=clamp[1])
    lw = _weights(cfg)
    lw = {**lw, "hc_op_a": jnp.array([1.0, 1.0, 6.0])}
    x = _streams(cfg, TILE)
    want, got = _plain(lw, x, _operator, cfg), _kernels(lw, x, _operator,
                                                        cfg)
    free = decoder._hc_coefficients(
        lw, "op", x, _config(64))[2]  # the family's own -30 .. 30
    assert float(jnp.max(jnp.abs(free - want["res"]))) > 1e-2
    for what in ("res", "x_new"):
        _close(got[what], want[what])


def test_the_gap_is_taken_over_the_valid_tokens_only():
    """A pad slot may hold anything finite. Here: the signs of one of
    ``phi``'s columns, so that one entry of ``H_res`` dwarfs the rest
    and ONE pass leaves its rows far from 1; the gap does not see it."""
    cfg = _config(64, hc_sinkhorn_iters=1)
    lw, x = _weights(cfg), _streams(cfg, TILE)
    valid = jnp.arange(TILE) < TILE - 40
    x = x.at[:, TILE - 40:].set(jnp.sign(
        lw["hc_op_phi_res"][:, 1]).reshape(cfg.hc_mult, 1, -1))
    want = _plain(lw, x, _operator, cfg, valid)
    got = _kernels(lw, x, _operator, cfg, valid)
    everywhere = _kernels(lw, x, _operator, cfg)
    assert abs(float(got["gap"]) - float(want["gap"])) <= RTOL
    assert float(everywhere["gap"]) > 1.5 * float(got["gap"])
    _close(got["x_new"][:, :TILE - 40], want["x_new"][:, :TILE - 40])


def test_a_token_is_its_own_row_whatever_tile_it_lies_in():
    """Two tiles: the second tile's tokens alone, as the first tile of a
    shorter stream, come out the same to the last bit."""
    cfg = _config(64)
    lw, x = _weights(cfg), _streams(cfg, 2 * TILE)
    whole = _kernels(lw, x, _operator, cfg)
    half = _kernels(lw, x[:, TILE:], _operator, cfg)
    for what in ("pre", "res", "z", "x_new"):
        np.testing.assert_array_equal(
            np.asarray(whole[what])[..., TILE:, :] if what in ("z", "x_new")
            else np.asarray(whole[what])[..., TILE:], np.asarray(half[what]))


@pytest.mark.parametrize("slots", [8192, 12288])
def test_the_cells_rungs_at_a_small_width(slots):
    """64 and 96 tiles of the cell's streams, 128 wide: every tile is
    written, none twice."""
    cfg = _config(128)
    lw, x = _weights(cfg), _streams(cfg, slots)
    want = jax.jit(lambda x: _plain(lw, x, _operator, cfg))(x)
    got = _kernels(lw, x, _operator, cfg)
    for what in ("res", "z", "x_new"):
        _close(got[what], want[what])


def _calls(fn, *args):
    """The names of the Pallas kernels a function holds, sorted."""
    return sorted(e.params["name"] for e in _all_eqns(
        jax.make_jaxpr(fn)(*args).jaxpr) if e.primitive.name == "pallas_call")


BOTH = ["hyper_mix_read", "hyper_mix_write"]


def test_fewer_tokens_than_a_tile_keep_the_plain_lines():
    """The rule is the stream's length against ``TILE``, seen when the
    program is traced: a decode step's 4 tokens and a stream one short
    of a tile hold no kernel, a tile's tokens hold the two."""
    cfg = _config(64)
    lw = _weights(cfg)

    def block(x):
        return decoder._sub_block(lw, "op", x,
                                  lambda z: (_operator(z), None), cfg)[0]

    assert _calls(block, _streams(cfg, 4)) == []
    assert _calls(block, _streams(cfg, TILE - 1)) == []
    assert _calls(block, _streams(cfg, TILE)) == BOTH
    assert _calls(block, _streams(cfg, 3 * TILE + 8)) == BOTH


@pytest.mark.parametrize("short", [4, TILE - 8])
def test_the_same_answer_either_side_of_the_rule(short):
    """The first tokens of a tile through the kernels against the same
    tokens as a stream of their own through the plain lines."""
    cfg = _config(64)
    lw, x = _weights(cfg), _streams(cfg, TILE)

    def block(x):
        return decoder._sub_block(lw, "op", x,
                                  lambda z: (_operator(z), None), cfg)

    (new, _, _), (few, _, gap) = block(x), block(x[:, :short])
    _close(new[:, :short], few)
    assert 0.0 <= float(gap) < 1e-2


@pytest.mark.parametrize("base", [SMALL, LAGUNA], ids=["lfm2_moe", "laguna"])
def test_a_one_stream_program_holds_no_hyper_mix_call(base):
    """``hc_mult`` 1 takes the first branch of ``_sub_block``: nothing
    of this module is in either program, at a stream of many tiles."""
    _, cfg, w = _setup(base=base)
    slots, rows = 4 * TILE, 4
    tokens = jnp.zeros((slots,), jnp.int32)
    lengths = jnp.full((rows,), 8, jnp.int32)
    how = dict(cfg=cfg, history=40, room=4)
    text = decoder._gen_prefill.lower(w, tokens, lengths, **how).as_text()
    first, state = jax.eval_shape(
        lambda w, t, n: decoder._gen_prefill(w, t, n, **how),
        w, tokens, lengths)
    text += decoder._gen_decode.lower(w, state, first, cfg=cfg,
                                      steps=4).as_text()
    assert cfg.hc_mult == 1 and "hyper_mix" not in text


def test_the_four_stream_prefill_holds_them_and_its_decode_does_not():
    _, cfg, w = _setup(base=XING)
    tokens = jnp.zeros((TILE,), jnp.int32)
    lengths = jnp.full((4,), 8, jnp.int32)
    how = dict(cfg=cfg, history=32, room=4)
    mine = [k for k in _calls(
        lambda w, t, n: decoder._gen_prefill(w, t, n, **how),
        w, tokens, lengths) if k.startswith("hyper_mix")]
    assert mine == sorted(BOTH * 2 * cfg.num_hidden_layers)
    first, state = jax.eval_shape(
        lambda w, t, n: decoder._gen_prefill(w, t, n, **how),
        w, tokens, lengths)
    assert not any(k.startswith("hyper_mix") for k in _calls(
        lambda w, s, f: decoder._gen_decode(w, s, f, cfg=cfg, steps=4),
        w, state, first))


def test_one_bfloat16_pass_would_not_pass():
    """What the tolerance is worth, and what ``phi``'s three parts are
    for: with ``phi`` rounded to its first part alone the coefficients
    miss by a thousand tolerances."""
    cfg = _config(64)
    lw, x = _weights(cfg), _streams(cfg, TILE)
    want = _plain(lw, x, _operator, cfg)
    rough = {k: v.astype(jnp.bfloat16).astype(jnp.float32)
             if "_phi_" in k else v for k, v in lw.items()}
    got = _kernels(rough, x, _operator, cfg)
    assert float(jnp.max(jnp.abs(got["res"] - want["res"]))) > 100 * RTOL


def test_the_three_parts_of_phi_add_up_to_it():
    """``_packed``: ``[n, H, 128]`` bfloat16 whose three groups of ``c``
    columns add up to ``phi`` to float32's last bit, the second not
    empty (a cast there and back that a compiler drops leaves it so)."""
    cfg = _config(64)
    phi = decoder._hc_columns(_weights(cfg), "op", cfg.hc_mult)[0]
    c = phi.shape[1]
    w = jax.jit(lambda p: hyper_mix._packed(p, cfg.hc_mult, 64))(phi)
    assert w.shape == (cfg.hc_mult, 64, 128) and w.dtype == jnp.bfloat16
    parts = np.asarray(w.astype(jnp.float32)).reshape(-1, 128)
    np.testing.assert_allclose(
        parts[:, :c] + parts[:, c:2 * c] + parts[:, 2 * c:3 * c],
        np.asarray(phi), rtol=2 ** -22, atol=0)
    assert np.abs(parts[:, c:2 * c]).max() > 0
    assert not parts[:, 3 * c:].any()
