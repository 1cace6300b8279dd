"""The ``laguna`` family of ``models/decoder.py`` against the plain
reference at a small size on the CPU: what has no case in
``test_decoder.py``'s parametrised tests (those hold the packed ragged
batches, the neighbours, the shares and the ring wrapped twice): its
published keys and the ones nobody has written, the rotary by kind
against the formula in numpy, the rings' layout, the stages taken in
parts, bfloat16 against int8, the benchmark's copy of the reference,
and a decode whose steps take the touched-experts kernel. Tolerances as
``test_decoder.py`` states them."""

import jax
import jax.numpy as jnp
import numpy as np
import pytest

from predictionio_tpu.models import decoder, decoder_reference as ref
from predictionio_tpu.ops import moe
from test_decoder import (  # noqa: F401 — ``lag`` is a fixture
    LAGUNA, LAGUNA_HISTORY, LAGUNA_INIT, LONG, _all_eqns, _benchmarks_copy,
    _check_against_reference, _generate, _hists, _int8_round_trip, _pack,
    _prefill, _rel, _setup, lag)


def test_config_reads_the_laguna_keys():
    """The published keys by their published names; the catalog row's
    40 layers as they stand; a key nobody has written raises."""
    _, cfg, _ = _setup(base=LAGUNA)
    assert cfg.norm_eps == 1e-6 and cfg.routed_scaling_factor == 2.5
    assert cfg.num_attention_heads_per_layer == (4, 6, 6, 6, 4)
    assert cfg.mlp_layer_types[0] == "dense" and not cfg.tie_word_embeddings
    assert hash(cfg) == hash(decoder.DecoderConfig.from_dict(LAGUNA))
    inv, factor = cfg.rope(decoder.ATTENTION)
    assert len(inv) == 4 and factor == pytest.approx(1.41589, rel=1e-5)
    inv, factor = cfg.rope(decoder.SLIDING)
    assert len(inv) == 8 and factor == 1.0
    import json
    import os

    path = os.path.join(os.path.dirname(os.path.dirname(
        os.path.abspath(__file__))), "cellbench", "configs",
        "laguna-xs2-l5.json")
    with open(path) as f:
        cut = json.load(f)
    assert decoder.DecoderConfig.from_dict(cut).num_hidden_layers == 5
    row = dict(cut, num_hidden_layers=40,
               layer_types=cut["layer_types"][:4] * 10,
               mlp_layer_types=["dense"] + ["sparse"] * 39,
               num_attention_heads_per_layer=[48, 64, 64, 64] * 10)
    whole = decoder.DecoderConfig.from_dict(row)
    assert whole.layer_types.count(decoder.SLIDING) == 30
    assert (whole.hidden_size, whole.head_dim, whole.num_experts,
            whole.num_experts_per_tok, whole.sliding_window,
            whole.vocab_size) == (2048, 128, 256, 8, 512, 100352)
    for bad in ({"attention_bias": True},
                {"moe_apply_router_weight_on_input": True},
                {"moe_router_logit_softcapping": 30.0},
                {"sliding_window": None},
                {"layer_types": ["linear_attention"] * 5},
                {"mlp_layer_types": ["dense"] * 4},
                {"num_attention_heads_per_layer": [4, 5, 6, 6, 4]},
                {"norm_eps": 1e-5},  # beside rms_norm_eps 1e-6: which?
                {"rope_parameters": {**LAGUNA["rope_parameters"],
                                     "sliding_attention": {
                                         "rope_type": "llama3",
                                         "rope_theta": 1e4}}}):
        with pytest.raises(ValueError):
            decoder.DecoderConfig.from_dict({**LAGUNA, **bad})


def _yarn_numpy(rotated, theta, factor, ctx, fast, slow):
    """The blend written out: the HF rope utilities' arithmetic."""
    def turns(n):
        return rotated * np.log(ctx / (n * 2 * np.pi)) / (2 * np.log(theta))

    low, high = max(np.floor(turns(fast)), 0), \
        min(np.ceil(turns(slow)), rotated - 1)
    high = high + 0.001 if low == high else high
    pos = theta ** (np.arange(0, rotated, 2) / rotated)
    keep = 1 - np.clip((np.arange(rotated // 2) - low) / (high - low), 0, 1)
    return (1 / (factor * pos)) * (1 - keep) + (1 / pos) * keep


@pytest.mark.parametrize("kind", [decoder.ATTENTION, decoder.SLIDING])
def test_rotary_by_kind_is_the_formula_written_out(kind):
    """The yarn blend, the partial rotation and the factor on cos and
    sin against numpy, in the program and in the reference; at the
    published parameters the blend's range lies inside the 32 rotated
    frequencies (5 .. 16), so all three regimes are there."""
    _, cfg, _ = _setup(base=LAGUNA)
    p = LAGUNA["rope_parameters"][kind]
    D, R = 16, int(16 * p["partial_rotary_factor"])
    if kind == decoder.ATTENTION:
        inv = _yarn_numpy(R, 5e5, 64, 16, 4, 1)
        big = _yarn_numpy(64, 5e5, 64, 4096, 64, 1)
        plain = 1 / 5e5 ** (np.arange(0, 64, 2) / 64)
        ratio = big / plain
        assert np.allclose(ratio[:6], 1) and np.allclose(ratio[16:], 1 / 64)
        assert (np.diff(ratio[5:17]) < 0).all()
        published = decoder._inverse_frequencies(
            64, 5e5, "yarn", tuple(sorted({
                "factor": 64, "original_max_position_embeddings": 4096,
                "beta_slow": 1, "beta_fast": 64}.items())))
        np.testing.assert_allclose(published[0], big, rtol=1e-12)
        assert published[1] == pytest.approx(0.1 * np.log(64) + 1)
    else:
        inv = 1 / 1e4 ** (np.arange(0, R, 2) / R)
    factor = p.get("attention_factor", 1.0)
    np.testing.assert_allclose(cfg.rope(kind)[0], inv, rtol=1e-12)
    x = np.random.default_rng(0).normal(size=(12, 3, D)).astype(np.float32)
    ang = np.arange(12)[:, None] * inv[None, :]
    cos, sin = np.cos(ang)[:, None] * factor, np.sin(ang)[:, None] * factor
    x1, x2 = x[..., :R // 2], x[..., R // 2:R]
    want = np.concatenate([x1 * cos - x2 * sin, x2 * cos + x1 * sin,
                           x[..., R:]], axis=-1)
    got = decoder._rotary(jnp.asarray(x), jnp.arange(12), cfg.rope(kind))
    np.testing.assert_allclose(got, want, atol=2e-6)
    np.testing.assert_allclose(ref.rotary(jnp.asarray(x), p), want,
                               atol=2e-6)


def test_the_state_is_rings_beside_caches(lag):
    """A sliding layer carries ``sliding_window`` slots whatever the
    history, slot ``p mod window`` holding position ``p``; a full layer
    its right-aligned ``history + room``."""
    d, cfg, w = lag
    hists = _hists(np.random.default_rng(4), [3, 40, 13])
    _, st = _prefill(w, cfg, hists, 120)
    kinds = [a["k"].shape for a in st["layers"]]
    assert kinds == [(3, 2, 49, 16)] + [(3, 2, 8, 16)] * 3 + [(3, 2, 49, 16)]
    lw = w["layers"][1]
    for r, h in enumerate(hists):
        x = ref.embed(w, h)
        x = ref.layer(w["layers"][0], 0, x, d)
        z = ref.rms(x, lw["op_norm"], 1e-6)
        k = ref.rotary(ref.rms((z @ lw["wk"]).reshape(len(h), 2, 16),
                               lw["k_norm"], 1e-6),
                       LAGUNA["rope_parameters"]["sliding_attention"])
        ring = np.asarray(st["layers"][1]["k"][r])  # [kv, 8, D]
        for s in range(8):
            held = [p for p in range(len(h)) if p % 8 == s]
            want = np.asarray(k[held[-1]]) if held else np.zeros((2, 16))
            np.testing.assert_allclose(ring[:, s], want, atol=1e-5)


def test_the_stream_in_blocks_is_the_stream_whole(lag, monkeypatch):
    """``ops/moe.py`` takes a stream of more than ``BLOCK_ASSIGNMENTS``
    in equal blocks of tokens, and the prefill's wide stages go by
    blocks of tokens and groups of heads: the same numbers (a token's
    experts are all in its block; a head is in one group)."""
    d, cfg, w = lag
    lw = w["layers"][2]
    z = jax.random.normal(jax.random.key(8), (384, cfg.hidden_size))
    valid = jnp.arange(384) < 301
    sel, wts = moe.route(z, lw["gate"], lw["gate_bias"], top_k=2,
                         scale=2.5)
    local = jnp.where(valid[:, None], sel, 8)
    whole = moe._sorted_groups(z, local, wts, lw["w1"], lw["w3"], lw["w2"])
    monkeypatch.setattr(moe, "BLOCK_ASSIGNMENTS", 200)  # 384 x 2 in 4
    jaxpr = jax.make_jaxpr(moe._sorted_groups)(
        z, local, wts, lw["w1"], lw["w3"], lw["w2"])
    assert any(e.primitive.name == "scan" for e in jaxpr.jaxpr.eqns)
    blocks = moe._sorted_groups(z, local, wts, lw["w1"], lw["w3"], lw["w2"])
    np.testing.assert_allclose(blocks, whole, atol=1e-6)
    assert not np.asarray(blocks)[301:].any()
    # the stack: dense feed-forward by token blocks, queries by head
    # groups
    hists = _hists(np.random.default_rng(12), [40, 9, 25, 16])
    tokens, lengths = _pack(hists, 4, 160)
    plain = decoder._gen_prefill.__wrapped__(
        w, tokens, lengths, cfg=cfg, history=LAGUNA_HISTORY, room=4)
    monkeypatch.setattr(decoder, "BLOCK_ELEMENTS", 160 * 128 // 4)
    monkeypatch.setattr(decoder, "HEAD_GROUP_ELEMENTS", 160 * 16 * 2)
    assert moe.equal_parts(160, 128, decoder.BLOCK_ELEMENTS) == 4
    assert moe.equal_parts(6, 160 * 16, decoder.HEAD_GROUP_ELEMENTS) == 3
    parts = decoder._gen_prefill.__wrapped__(
        w, tokens, lengths, cfg=cfg, history=LAGUNA_HISTORY, room=4)
    for a, b in zip(jax.tree_util.tree_leaves(plain),
                    jax.tree_util.tree_leaves(parts)):
        np.testing.assert_allclose(a, b, atol=1e-5)  # values to 4


@pytest.mark.parametrize("seed", [5, 13])
def test_laguna_in_bfloat16_is_inside_the_tolerance_and_int8_is_not(seed):
    """As the test below, for the ``laguna`` block (routed experts and
    the shared one through int8): the expert block 4e-3 of its output's
    norm in bfloat16 (readings 1.6e-3 .. 2.0e-3), the reference on
    int8 experts outside it (readings 8e-3 .. 1.1e-2); the whole stack
    with the ring wrapped twice at the median position under 0.05 of a
    position's spread of reference logits (readings 0.006 .. 0.02)."""
    with pytest.MonkeyPatch.context() as patch:
        patch.setattr(decoder, "ATTENTION_BLOCK", 8)
        d, cfg, w = _setup("bfloat16", seed=seed, base=LAGUNA,
                           init=LAGUNA_INIT)
        lw = w["layers"][3]
        z = jax.random.normal(jax.random.key(seed), (64, cfg.hidden_size)
                              ).astype(jnp.bfloat16).astype(jnp.float32)
        want = ref.expert_ff(lw, z, d) + ref.dense_ff(lw, z,
                                                      ("s1", "s3", "s2"))
        got, _ = decoder._feed_forward(lw, z, None, cfg)
        assert _rel(got, want) <= 4e-3
        lossy = {**lw, **{n: _int8_round_trip(lw[n])
                          for n in ("w1", "w3", "w2", "s1", "s3", "s2")}}
        assert _rel(ref.expert_ff(lossy, z, d)
                    + ref.dense_ff(lossy, z, ("s1", "s3", "s2")),
                    want) > 4e-3
        hists = _hists(np.random.default_rng(seed + 1), [30, 40, 7, 19])
        _, toks, scores, _ = _generate(w, cfg, hists, 160, steps=LONG)
    gaps = []
    for r, h in enumerate(hists):
        seq = h + toks[r, :-1].tolist()
        logits = np.asarray(ref.forward(w, seq, d))[len(h) - 1:]
        at = logits[np.arange(len(logits)), toks[r]]
        gaps.append(np.abs(scores[r] - at) / logits.std(axis=1))
    assert np.median(np.concatenate(gaps)) <= 0.05


def test_the_benchmarks_laguna_copy_of_the_reference_is_the_same(lag):
    """``cellbench/reference_laguna.py`` imports nothing of the program;
    it is held to this package's reference output for output, in the
    plain form, with the queries in blocks and under the ``no_window``
    control; and each assumed convention is ONE argument that moves the
    result."""
    copy = _benchmarks_copy("reference_laguna")
    d, cfg, w = lag
    seq = _hists(np.random.default_rng(9), [29])[0]
    np.testing.assert_array_equal(np.asarray(copy.forward(w, seq, d)),
                                  np.asarray(ref.forward(w, seq, d)))
    x = ref.embed(w, seq)
    lw = w["layers"][1]
    plain = np.asarray(ref.operator(lw, 1, x, d))
    for how in ({"query_block": 8}, {"window": None}, {"qk_norm": False}):
        a = np.asarray(copy.operator(lw, 1, x, d, **how))
        np.testing.assert_array_equal(
            a, np.asarray(ref.operator(lw, 1, x, d, **how)))
        if "query_block" in how:
            np.testing.assert_allclose(a, plain, atol=1e-5)
        else:
            assert np.abs(a - plain).max() > 1e-2
    wide = {**lw, "wg": jnp.concatenate([lw["wg"]] * 16, axis=1)}
    assert np.abs(np.asarray(ref.operator(wide, 1, x, d, head_gate="wide"))
                  - plain).max() > 1e-2
    z = jax.random.normal(jax.random.key(10), (5, cfg.hidden_size))
    assert np.abs(np.asarray(ref.expert_ff(lw, z, d, scores="softmax"))
                  - np.asarray(ref.expert_ff(lw, z, d))).max() > 1e-2
    np.testing.assert_array_equal(
        np.asarray(copy.feed_forward(lw, 1, z, d)),
        np.asarray(ref.feed_forward(lw, 1, z, d)))


def test_a_decode_step_through_the_touched_form_matches_the_forward():
    """``_gen_decode`` at a toy ``laguna`` shape whose steps take the
    touched-experts form (16 experts, 2 a token, 4 rows: 8 assignments)
    while its prefill takes the sorted groups: every served score is the
    float32 reference forward's logit of the served token."""
    with pytest.MonkeyPatch.context() as patch:
        patch.setattr(decoder, "ATTENTION_BLOCK", 8)
        d, cfg, w = _setup(base=LAGUNA, init=LAGUNA_INIT, num_experts=16)
        hists = _hists(np.random.default_rng(3), [40, 33, 40, 33])
        assert moe.product_form(len(hists), cfg.num_experts_per_tok,
                                cfg.num_experts) == moe.TOUCHED
        assert moe.product_form(160, cfg.num_experts_per_tok,
                                cfg.num_experts) == moe.SORTED
        first, state = _prefill(w, cfg, hists, 160, steps=5)
        kernels = [e.params["name"] for e in _all_eqns(jax.make_jaxpr(
            lambda w, state, first: decoder._gen_decode(
                w, state, first, cfg=cfg, steps=5))(
                    w, state, first).jaxpr)
                   if e.primitive.name == "pallas_call"]
        assert kernels == ["touched_experts"] * d["mlp_layer_types"].count(
            "sparse")
        toks, scores, (_, dec), _ = decoder._gen_decode(
            w, state, first, cfg=cfg, steps=5)
        _check_against_reference(d, w, hists, np.asarray(first),
                                 np.asarray(toks), np.asarray(scores))
        assert (np.asarray(dec).sum(axis=2)
                == cfg.num_experts_per_tok * len(hists)).all()
