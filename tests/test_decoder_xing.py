"""The ``xing4_0`` family of ``models/decoder.py`` (latent attention,
four residual streams under Sinkhorn-normalised hyper-connections, 8
sigmoid-routed experts and a shared one at this size) against the plain
reference at a small size on the CPU, logit by logit.

Tolerances. Float32 on both sides, so the program and the reference
differ by the order of their sums: ``TOL32`` (2e-4 of a logit whose
spread is ~0.16; readings under 1e-6 here) holds every comparison, as
in ``test_decoder.py``; a path that is stated float32 and computed in
bfloat16 reads 1e-3 or more (the last test), fifty times the tolerance.
"""

import functools
import json
import os

import jax
import jax.numpy as jnp
import numpy as np
import pytest

from predictionio_tpu.models import decoder, decoder_reference as ref
from test_decoder import (  # noqa: F401 — ``xing`` is a fixture
    HISTORY, LAGUNA_INIT, TOL32, XING, _benchmarks_copy, _generate, _hists,
    _pack, _prefill, _setup, xing)

ROOT = os.path.dirname(os.path.dirname(os.path.abspath(__file__)))
#: the shapes nearly every test here runs at, so that the programs (and
#: the reference, jitted over sequences padded behind their end: the
#: model is causal) compile once: 4 rows in a stream of 128 slots and 4
#: tokens a row, or the served fixture's 6 rows, 192 slots and 9 tokens
ROWS, SLOTS, FEW = 4, 128, 4
PADDED = (8, 48)


@functools.lru_cache(maxsize=None)
def _forward(frozen, **how):
    d = json.loads(frozen)

    def one(w, seq):
        x = ref.streams_in(ref.embed(w, seq), d)
        for l, lw in enumerate(w["layers"]):
            x = ref.feed_forward(lw, l, ref.operator(lw, l, x, d, **how),
                                 d, **how)
        return ref.head(w, ref.streams_out(x, d), d)

    return jax.jit(lambda w, seqs: jax.vmap(lambda s: one(w, s))(seqs))


def _reference(d, w, seqs, **how):
    """The reference's logits ``[len, V]`` of each sequence, from ONE
    jitted call over the sequences right-padded to ``PADDED``."""
    tokens = np.zeros(PADDED, np.int32)
    for i, seq in enumerate(seqs):
        tokens[i, :len(seq)] = seq
    logits = np.asarray(_forward(json.dumps(d, sort_keys=True), **how)(
        w, jnp.asarray(tokens)))
    return [logits[i, :len(seq)] for i, seq in enumerate(seqs)]


def _check(d, w, hists, first, toks, scores):
    """``test_decoder._check_against_reference`` with the reference
    jitted: every served score is the reference's logit of the served
    token at that position, from ONE uncached forward over history +
    served tokens; and greedy took the reference's best."""
    seqs = [h + toks[r, :-1].tolist() for r, h in enumerate(hists)]
    for r, (h, logits) in enumerate(zip(hists, _reference(d, w, seqs))):
        logits = logits[len(h) - 1:]
        assert logits.shape[0] == toks.shape[1]
        np.testing.assert_allclose(first[r], logits[0], atol=TOL32)
        at = logits[np.arange(len(logits)), toks[r]]
        np.testing.assert_allclose(scores[r], at, atol=TOL32)
        assert np.all(logits.max(axis=1) - at <= TOL32)


def test_config_reads_the_published_keys_and_refuses_the_unwritten():
    """The family's own key names; the catalog row's 40 layers as they
    stand; the benchmark's cut; a key that switches on mathematics
    nobody has written raises."""
    _, cfg, _ = _setup(base=XING)
    assert cfg.layer_types == (decoder.LATENT,) * 4
    assert cfg.mlp_layer_types == ("dense", "dense", "sparse", "sparse")
    assert (cfg.num_experts, cfg.shared_expert_intermediate_size,
            cfg.head_dim, cfg.norm_eps, cfg.routed_scaling_factor) == (
        8, 32, 24, 1e-6, 2)
    assert cfg.use_expert_bias and not cfg.tie_word_embeddings
    assert hash(cfg) == hash(decoder.DecoderConfig.from_dict(XING))
    with open(os.path.join(ROOT, "cellbench", "configs",
                           "xing4-29b-a4b-l6.json")) as f:
        cut = json.load(f)
    served = decoder.DecoderConfig.from_dict(cut)
    assert served.num_hidden_layers == 6
    assert served.mlp_layer_types == ("dense",) * 2 + ("sparse",) * 4
    whole = decoder.DecoderConfig.from_dict({**cut, "num_hidden_layers": 40})
    assert whole.mlp_layer_types.count("sparse") == 38
    assert (whole.hidden_size, whole.q_lora_rank, whole.kv_lora_rank,
            whole.head_dim, whole.v_head_dim, whole.num_experts,
            whole.num_experts_per_tok, whole.hc_mult, whole.vocab_size) == (
        3584, 768, 512, 192, 128, 64, 4, 4, 131072)
    # 0.1 ln(64) + 1 squared over sqrt(192); cos and sin take 1
    assert whole.latent_scale == pytest.approx(0.14468, rel=1e-4)
    inv, on_cos = whole.rope(decoder.LATENT)
    assert len(inv) == 32 and on_cos == 1.0
    for bad in ({"n_group": 8}, {"topk_group": 4}, {"ep_size": 8},
                {"scoring_func": "softmax"}, {"topk_method": "greedy"},
                {"attention_bias": True}, {"moe_layer_freq": 2},
                {"kv_lora_rank": None}, {"v_head_dim": None},
                {"hc_mult": 0}, {"hc_sinkhorn_iters": 0},
                {"rope_scaling": {**XING["rope_scaling"],
                                  "type": "llama3"}}):
        with pytest.raises(ValueError):
            decoder.DecoderConfig.from_dict({**XING, **bad})


def test_yarn_is_on_the_softmax_and_not_on_cos_and_sin():
    """The rotated dimensions' frequencies are yarn's blend (the formula
    in numpy), the factor on cos and sin is ``yarn(mscale) /
    yarn(mscale_all_dim)`` = 1, and ``m^2`` scales the softmax: a
    reference without it differs by far more than the tolerance."""
    from test_decoder_laguna import _yarn_numpy

    d, cfg, w = _setup(base=XING, init=LAGUNA_INIT)
    inv, on_cos = cfg.rope(decoder.LATENT)
    np.testing.assert_allclose(inv, _yarn_numpy(8, 1e4, 64, 16, 4, 1),
                               rtol=1e-12)
    assert on_cos == 1.0
    m = 0.1 * np.log(64) + 1
    assert cfg.latent_scale == pytest.approx(24 ** -0.5 * m * m)
    uneven = decoder.DecoderConfig.from_dict({**XING, "rope_scaling": {
        **XING["rope_scaling"], "mscale": 0.5}})
    assert uneven.rope(decoder.LATENT)[1] == pytest.approx(
        (0.05 * np.log(64) + 1) / m)
    x = ref.embed(w, _hists(np.random.default_rng(2), [19])[0])
    z = ref.rms(x, w["layers"][0]["op_norm"], 1e-6)
    with jax.default_matmul_precision("highest"):
        a = np.asarray(ref.latent_attention_op(w["layers"][0], z, d))
        b = np.asarray(ref.latent_attention_op(w["layers"][0], z, d,
                                               yarn_scale=False))
        c = np.asarray(ref.latent_attention_op(w["layers"][0], z, d,
                                               query_block=8))
    assert np.abs(a - b).max() > 100 * TOL32
    np.testing.assert_allclose(c, a, atol=1e-6)


@pytest.fixture(scope="module")
def xing_served(xing):
    d, cfg, w = xing
    hists = _hists(np.random.default_rng(1), [5, 32, 23, 1, 17, 30])
    return hists, _generate(w, cfg, hists, 192)


def test_prefill_and_decode_through_the_latents_match_the_full_forward(
        xing, xing_served):
    """Six rows of 1 to 32 tokens in one packed stream, then 8 steps
    through the latent cache with the up-projections absorbed, against
    ONE uncached, expanded forward of the reference over history +
    served tokens, logit by logit."""
    d, cfg, w = xing
    hists, (first, toks, scores, _) = xing_served
    _check(d, w, hists, first, toks, scores)


RAGGED = {
    "rows_of_one_token": ([1, 1, 1, 1], 4, 32),
    "a_row_at_the_top_bucket": ([32, 3, 9, 20], 4, 128),
    "every_row_at_the_top_bucket": ([32, 32, 32, 32], 4, 128),
    "a_batch_under_its_row_bucket": ([7, 12], 4, 64),
}


@pytest.mark.parametrize("case", sorted(RAGGED))
def test_a_packed_ragged_batch_matches_the_reference(xing, case):
    d, cfg, w = xing
    lengths, rows, slots = RAGGED[case]
    hists = _hists(np.random.default_rng(len(case)), lengths)
    first, toks, scores, (pre, _) = _generate(w, cfg, hists, slots, rows,
                                              steps=FEW)
    _check(d, w, hists, first[:len(hists)],
                             toks[:len(hists)], scores[:len(hists)])
    assert (np.asarray(pre).sum(axis=1) == cfg.num_experts_per_tok
            * (sum(lengths) + rows - len(lengths))).all()


@pytest.mark.parametrize("slots,beside", [(192, []), (192, [30, 32]),
                                          (128, [3, 32, 20])])
def test_the_stream_and_neighbours_do_not_move_a_row(xing, xing_served,
                                                     slots, beside):
    """The same history alone and behind other rows in larger streams
    gives the same logits and tokens: the streams' norm, read, write and
    mix are a token's own, and the attention stays inside a row."""
    d, cfg, w = xing
    hists, (first, toks, scores, _) = xing_served
    mix = _hists(np.random.default_rng(2), beside) + [hists[1]]
    f2, t2, s2, _ = _generate(w, cfg, mix, slots, rows=len(hists),
                              steps=toks.shape[1])
    at = len(mix) - 1
    np.testing.assert_allclose(f2[at], first[1], atol=TOL32)
    np.testing.assert_array_equal(t2[at], toks[1])
    np.testing.assert_allclose(s2[at], scores[1], atol=TOL32)


def test_the_state_is_the_latents_right_aligned(xing):
    """A token leaves its normalised latent and its rotated shared key,
    ``kv_lora_rank + rope`` wide, whatever the heads; a row's lie in the
    last slots before ``history``, zeros before them, room behind."""
    d, cfg, w = xing
    hists = _hists(np.random.default_rng(4), [3, 32, 13])
    _, st = _prefill(w, cfg, hists, SLOTS, ROWS, FEW)
    assert [a["kv"].shape for a in st["layers"]] == [
        (ROWS, HISTORY + FEW, 24)] * 4
    lw = w["layers"][0]
    for r, h in enumerate(hists):
        got = np.asarray(st["layers"][0]["kv"][r])
        assert not got[:HISTORY - len(h)].any() and not got[HISTORY:].any()
        if r != 1:  # the reference written out for one row: eager ops
            continue
        x = ref.streams_in(ref.embed(w, h), d)
        pre, _, _ = ref.hyper_coefficients(lw, "op", x, d)
        z = ref.rms(jnp.einsum("tn,tnh->th", pre, x), lw["op_norm"], 1e-6)
        kv = z @ lw["w_kva"]
        rope = {**XING["rope_scaling"], "rope_theta": 1e4,
                "rope_type": "yarn", "attention_factor": 1.0}
        want = jnp.concatenate([
            ref.rms(kv[:, :16], lw["kv_a_norm"], 1e-6),
            ref.rotary(kv[:, None, 16:], rope)[:, 0]], axis=-1)
        np.testing.assert_allclose(got[HISTORY - len(h):HISTORY], want,
                                   atol=1e-5)


def test_the_absorbed_step_is_the_expanded_one_on_the_same_state(xing):
    """One decode step's attention over a prefilled cache, with ``W_uk``
    absorbed into the query and ``W_uv`` applied after the weighted sum
    of latents, against keys and values of every head laid out from
    that same cache (numpy): two formulations of one product, so they
    differ by the order of float32 sums (1e-5 of outputs near 1)."""
    d, cfg, w = xing
    hists = _hists(np.random.default_rng(6), [9, 32, 20])
    _, st = _prefill(w, cfg, hists, SLOTS, ROWS, FEW)
    lw, B = w["layers"][1], ROWS
    z = jax.random.normal(jax.random.key(3), (B, cfg.hidden_size))
    at, pos = st["filled"], st["pos"]
    valid = st["valid"].at[:, at].set(True)
    got, new = decoder._latent_step(lw, z, st["layers"][1], valid, pos, at,
                                    cfg)
    cq, kv = decoder._latent_project(lw, z, pos, cfg)
    q = decoder._dot(cq, lw["w_qb"]).reshape(B, 4, 24)
    q = jnp.concatenate([q[..., :16], decoder._rotary(
        q[..., 16:], pos, cfg.rope(decoder.LATENT))], axis=-1)
    cache = np.asarray(new["kv"], np.float64)
    np.testing.assert_array_equal(np.asarray(new["kv"][:, at]), kv)
    up = np.asarray(lw["w_kvb"], np.float64).reshape(16, 4, 32)
    k = np.concatenate([np.einsum("bsc,cnd->bnsd", cache[..., :16],
                                  up[..., :16]),
                        np.repeat(cache[:, None, :, 16:], 4, axis=1)], -1)
    v = np.einsum("bsc,cnd->bnsd", cache[..., :16], up[..., 16:])
    s = np.einsum("bnd,bnsd->bns", np.asarray(q, np.float64), k) \
        * cfg.latent_scale
    s = np.where(np.asarray(valid)[:, None, :], s, -np.inf)
    p = np.exp(s - s.max(-1, keepdims=True))
    p /= p.sum(-1, keepdims=True)
    o = np.einsum("bns,bnsd->bnd", p, v).reshape(B, -1)
    np.testing.assert_allclose(got, o @ np.asarray(lw["wo"], np.float64),
                               atol=1e-5)


def test_h_res_is_doubly_stochastic_within_the_gap_the_prefill_returns(
        xing):
    """Program and reference give the same coefficients; ``H_res``'s
    columns sum to 1 (the last step divided them), its rows to 1 within
    the gap the prefill reports, which one Sinkhorn pass misses by two
    orders; the seeded ``H_res`` is neither uniform nor the identity and
    differs token to token."""
    d, cfg, w = xing
    hists = _hists(np.random.default_rng(8), [32, 32, 32, 32])
    tokens, lengths = _pack(hists, ROWS, SLOTS)
    _, st = _prefill(w, cfg, hists, SLOTS, ROWS, FEW)
    gap = float(st["sinkhorn_gap"])
    x = ref.streams_in(ref.embed(w, np.asarray(tokens)), d)
    lw = w["layers"][0]
    pre, post, res = ref.hyper_coefficients(lw, "op", x, d)
    got = decoder._hc_coefficients(lw, "op", jnp.moveaxis(x, 1, 0), cfg)
    np.testing.assert_allclose(got[0].T, pre, atol=1e-6)
    np.testing.assert_allclose(got[1].T, post, atol=1e-6)
    np.testing.assert_allclose(jnp.moveaxis(got[2], 2, 0), res, atol=1e-6)
    res = np.asarray(res)
    np.testing.assert_allclose(res.sum(axis=1), 1.0, atol=3e-6)
    rows_off = np.abs(res.sum(axis=2) - 1.0).max()
    assert rows_off <= gap <= 1e-3          # readings 1e-6 .. 2e-4
    _, _, once = ref.hyper_coefficients(lw, "op", x, d, iters=1)
    assert np.abs(np.asarray(once).sum(axis=2) - 1.0).max() > 100 * gap
    off = 1.0 - np.trace(res, axis1=1, axis2=2) / 4
    assert 0.2 < off.mean() < 0.7 and off.std() > 0.02
    assert (0 < np.asarray(pre)).all() and (np.asarray(pre) < 1).all()
    assert (np.asarray(post) < 2).all()
    # fewer passes are another model: the benchmark's ``sinkhorn_1``
    full, = _reference(d, w, hists[:1])
    fewer, = _reference(d, w, hists[:1], sinkhorn_iters=1)
    assert np.abs(fewer - full).max() > 100 * TOL32


def test_one_stream_is_the_plain_residual_exactly():
    """``hc_mult`` 1 (or absent) takes ``x + F(n(x))``, bit for bit what
    the sum written out gives, and holds no hyper-connection weights;
    the family then still matches the reference."""
    d, cfg, w = _setup(base=XING, init=LAGUNA_INIT, hc_mult=1)
    assert not [k for k in w["layers"][0] if k.startswith("hc_")]
    lw = w["layers"][0]
    x = jax.random.normal(jax.random.key(1), (12, cfg.hidden_size))

    def fn(z):
        return decoder._swiglu(z, lw["w1"], lw["w3"], lw["w2"]), None

    got, _, gap = decoder._sub_block(lw, "ff", x, fn, cfg)
    want = x + fn(decoder._rms(x, lw["ff_norm"], cfg.norm_eps))[0]
    np.testing.assert_array_equal(got, want)
    assert gap is None
    hists = _hists(np.random.default_rng(5), [7, 32, 18])
    first, state = _prefill(w, cfg, hists, SLOTS, ROWS, FEW)
    assert "sinkhorn_gap" not in state
    first, toks, scores, _ = _generate(w, cfg, hists, SLOTS, ROWS, FEW)
    _check(d, w, hists, first, toks, scores)
    absent = {k: v for k, v in XING.items() if not k.startswith(("hc_",
                                                                 "mhc_"))}
    assert decoder.DecoderConfig.from_dict(absent).hc_mult == 1


def test_four_copies_through_an_identity_mix_are_the_plain_residual(xing):
    """The hyper-connection with ``H_pre`` = (1, 0, 0, 0), ``H_post`` =
    (1, 1, 1, 1) and ``H_res`` = I carries the plain residual in stream
    0: the read, the write and the mix are what the equations say."""
    d, cfg, w = xing
    lw = dict(w["layers"][0])
    big = 40.0  # sigmoid(40) is 1 in float32; exp(-30) over exp(30) is 0
    for k in ("phi_pre", "phi_post", "phi_res"):
        lw[f"hc_ff_{k}"] = jnp.zeros_like(lw[f"hc_ff_{k}"])
    lw["hc_ff_b_pre"] = jnp.asarray([big, -big, -big, -big])
    lw["hc_ff_b_post"] = jnp.zeros((4,))   # 2 sigmoid(0) = 1
    lw["hc_ff_b_res"] = 2 * big * jnp.eye(4) - big
    x = jax.random.normal(jax.random.key(2), (4, 10, cfg.hidden_size))

    def fn(z):
        return decoder._swiglu(z, lw["w1"], lw["w3"], lw["w2"]), None

    got, _, gap = decoder._sub_block(lw, "ff", x, fn, cfg)
    y = fn(decoder._rms(x[0], lw["ff_norm"], cfg.norm_eps))[0]
    np.testing.assert_allclose(got, x + y[None], atol=1e-5)
    assert float(gap) < 1e-5


@pytest.mark.parametrize("seed", [5, 13])
def test_bfloat16_is_inside_its_tolerance_and_outside_float32s(seed):
    """The served precision (bfloat16 weights and operands, float32
    streams, norms, Sinkhorn and softmax) against the float32 reference
    ON THOSE WEIGHTS: the median position under 0.05 of a position's
    spread of reference logits (readings 0.004 .. 0.012: the rounding of
    the operands of some thirty products in a row), and over the float32
    tolerance by far, so that a path stated float32 and computed in
    bfloat16 fails the float32 tests above. The streams' coefficients
    from bfloat16 streams miss the float32 ones by 1e-3 or more."""
    with pytest.MonkeyPatch.context() as patch:
        patch.setattr(decoder, "ATTENTION_BLOCK", 8)
        d, cfg, w = _setup("bfloat16", seed=seed, base=XING,
                           init=LAGUNA_INIT)
        hists = _hists(np.random.default_rng(seed + 1), [30, 32, 7, 19])
        _, toks, scores, _ = _generate(w, cfg, hists, SLOTS, ROWS, FEW)
    gaps, plain = [], []
    seqs = [h + toks[r, :-1].tolist() for r, h in enumerate(hists)]
    for r, (h, logits) in enumerate(zip(hists, _reference(d, w, seqs))):
        logits = logits[len(h) - 1:]
        at = logits[np.arange(len(logits)), toks[r]]
        gaps.append(np.abs(scores[r] - at) / logits.std(axis=1))
        plain.append(np.abs(scores[r] - at))
    assert np.median(np.concatenate(gaps)) <= 0.05
    assert np.median(np.concatenate(plain)) > TOL32
    x = ref.streams_in(ref.embed(w, hists[0]), d) \
        + jax.random.normal(jax.random.key(seed), (30, 4, 64))
    lw = w["layers"][0]
    exact = ref.hyper_coefficients(lw, "op", x, d)
    rounded = ref.hyper_coefficients(
        lw, "op", x.astype(jnp.bfloat16).astype(jnp.float32), d)
    assert max(float(jnp.abs(a - b).max())
               for a, b in zip(exact, rounded)) > 1e-3


def test_the_benchmarks_copy_of_the_reference_is_the_same(xing):
    """``cellbench/reference_xing.py`` imports nothing of the program;
    it is held to this package's reference output for output, whole,
    and with the queries in blocks under the ``sinkhorn_1`` control."""
    copy = _benchmarks_copy("reference_xing")
    d, cfg, w = xing
    seq = jnp.asarray(_hists(np.random.default_rng(9), [29])[0])
    np.testing.assert_array_equal(
        np.asarray(jax.jit(lambda w: copy.forward(w, seq, d))(w)),
        np.asarray(jax.jit(lambda w: ref.forward(w, seq, d))(w)))
    x = ref.streams_in(ref.embed(w, seq), d)
    np.testing.assert_array_equal(
        np.asarray(copy.streams_in(copy.embed(w, seq), d)), np.asarray(x))
    lw, how = w["layers"][2], {"query_block": 8, "sinkhorn_iters": 1}
    np.testing.assert_array_equal(
        np.asarray(jax.jit(functools.partial(
            copy.operator, l=2, cfg=d, **how))(lw, x=x)),
        np.asarray(jax.jit(functools.partial(
            ref.operator, l=2, cfg=d, **how))(lw, x=x)))
    np.testing.assert_array_equal(
        np.asarray(jax.jit(functools.partial(
            copy.feed_forward, l=2, cfg=d, sinkhorn_iters=1))(lw, h=x)),
        np.asarray(jax.jit(functools.partial(
            ref.feed_forward, l=2, cfg=d, sinkhorn_iters=1))(lw, h=x)))
