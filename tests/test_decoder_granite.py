"""The ``granitemoehybrid`` family through ``models/decoder.py`` against
the plain reference (``models/decoder_reference.py``) at a small size on
the CPU: state-space (``mamba``) layers beside NoPE grouped-query ones,
the four multipliers, prefill and decoding through the recurrent state,
logit by logit. float32 weights, so the program (a chunked scan, then
one-token updates) and the reference (the recurrence token by token)
differ by the order of their sums only: the tolerance is 1e-4 of a
position's SPREAD of logits (readings 5e-6; the tied head over an
embedding of 0.004 and ``logits_scaling`` 8 leave logits of 0.004, so
``test_decoder.py``'s absolute 2e-4 would see nothing here).
It imports ``tests/test_decoder.py``'s fixtures: run it from the repo
root."""

import jax
import jax.numpy as jnp
import numpy as np
import pytest

from predictionio_tpu.models import decoder, decoder_reference as ref
from tests.test_decoder import (
    HISTORY, LAGUNA, SMALL, STEPS, XING, _benchmarks_copy, _generate, _hists,
    _pack, _prefill, _setup)

TOL = 1e-4  # of a position's spread of logits

#: the family at a small size, by its published key names: one period's
#: kinds in another ratio (3 state-space layers an attention layer), 4
#: heads of 32 over a state of 16 in chunks of 16, attention 4 heads over
#: 2 of 16
GRANITE = {
    "model_type": "granitemoehybrid", "vocab_size": 256, "hidden_size": 64,
    "intermediate_size": 128, "shared_intermediate_size": 128,
    "num_hidden_layers": 5,
    "layer_types": ["mamba", "mamba", "attention", "mamba", "mamba"],
    "num_attention_heads": 4, "num_key_value_heads": 2,
    "attention_bias": False, "attention_multiplier": 0.0625,
    "embedding_multiplier": 12, "residual_multiplier": 0.22,
    "logits_scaling": 8, "mamba_chunk_size": 16, "mamba_conv_bias": True,
    "mamba_d_conv": 4, "mamba_d_head": 32, "mamba_d_state": 16,
    "mamba_expand": 2, "mamba_n_groups": 1, "mamba_n_heads": 4,
    "mamba_proj_bias": False, "num_experts_per_tok": 0,
    "num_local_experts": 0, "position_embedding_type": "nope",
    "rms_norm_eps": 1e-5, "rope_scaling": None, "rope_theta": 10000,
    "tie_word_embeddings": True, "normalization_function": "rmsnorm",
    "hidden_act": "silu", "max_position_embeddings": 131072,
    "dtype": "float32",
}
#: the benchmark configuration's factors (its file has the reasons)
INIT = {"embed": 0.004, "op_out": 1.0, "dense_out": 1.0}
RAGGED = {
    # rows that start mid-chunk, span three chunks, are shorter than one
    "mixed": ([20, 5, 32, 1, 17, 9], 128),
    "one_row_a_chunk": ([16, 16, 16, 16], 64),
    "short_rows": ([3, 1, 2, 1, 7, 1, 1, 4], 64),
}


@pytest.fixture(scope="module")
def granite():
    return _setup(base=GRANITE, init=INIT)


def _close(got, want):
    """Logits ``[.., V]`` within ``TOL`` of their position's spread."""
    got, want = np.asarray(got), np.asarray(want)
    gap = np.abs(got - want).max(axis=-1) / want.std(axis=-1)
    assert gap.max() <= TOL, gap.max()


def test_config_reads_the_published_keys():
    cfg = decoder.DecoderConfig.from_dict(GRANITE)
    assert cfg.layer_types == ("mamba", "mamba", "full_attention", "mamba",
                               "mamba")
    assert cfg.mlp_layer_types == ("dense",) * 5 and cfg.num_experts == 0
    assert (cfg.conv_L_cache, cfg.conv_bias) == (4, True)
    assert (cfg.head_dim, cfg.dense_width, cfg.nope) == (16, 128, True)
    assert cfg.attention_scale == 0.0625 and cfg.norm_eps == 1e-5
    assert (cfg.embedding_multiplier, cfg.residual_multiplier,
            cfg.logits_scaling) == (12, 0.22, 8)
    w = decoder.init_weights(jax.random.key(0), cfg)
    assert set(w["layers"][0]) == {
        "op_norm", "ff_norm", "w_in", "conv_w", "conv_b", "A_log",
        "dt_bias", "D", "ssm_norm", "w_out", "w1", "w3", "w2"}
    assert set(w["layers"][2]) == {"op_norm", "ff_norm", "wq", "wk", "wv",
                                   "wo", "w1", "w3", "w2"}
    assert w["layers"][0]["w_in"].shape == (64, 2 * 128 + 2 * 16 + 4)
    assert w["layers"][0]["conv_w"].shape == (128 + 32, 4)
    # the Mamba-2 conventions: a step of 0.001..0.1, a rate of 1..16
    step = np.asarray(jax.nn.softplus(w["layers"][0]["dt_bias"]))
    rate = np.exp(np.asarray(w["layers"][0]["A_log"]))
    assert (1e-3 * 0.999 <= step).all() and (step <= 0.1 * 1.001).all()
    assert (1 <= rate).all() and (rate <= 16).all()
    assert np.array_equal(np.asarray(w["layers"][0]["D"]), np.ones(4))


@pytest.mark.parametrize("key,value", [
    ("num_local_experts", 8), ("mamba_proj_bias", True),
    ("attention_bias", True), ("mamba_n_groups", 3),
    ("position_embedding_type", "alibi"), ("mamba_d_head", 16),
])
def test_an_unwritten_key_of_the_family_raises(key, value):
    with pytest.raises(ValueError, match=key.split("_")[0]):
        decoder.DecoderConfig.from_dict({**GRANITE, key: value})


def test_a_conv_bias_beside_a_conv_layer_and_nope_beside_a_window_raise():
    with pytest.raises(ValueError, match="conv_bias"):
        decoder.DecoderConfig.from_dict({**SMALL, "conv_bias": True})
    with pytest.raises(ValueError, match="nope"):
        decoder.DecoderConfig.from_dict(
            {**LAGUNA, "position_embedding_type": "nope"})
    with pytest.raises(ValueError, match="nope"):
        decoder.DecoderConfig.from_dict(
            {**XING, "position_embedding_type": "nope"})
    # the key alone says it: no family's name is looked up
    named = {k: v for k, v in GRANITE.items() if k != "model_type"}
    assert decoder.DecoderConfig.from_dict(named) \
        == decoder.DecoderConfig.from_dict(GRANITE)
    with pytest.raises(ValueError, match="residual_multiplier"):
        decoder.DecoderConfig.from_dict({**XING, "residual_multiplier": 0.5})


def test_prefill_and_decode_through_the_state_match_the_full_forward(
        granite):
    d, cfg, w = granite
    hists = _hists(np.random.default_rng(0), [20, 5, 32, 1, 17, 9])
    first, toks, scores, _ = _generate(w, cfg, hists, 128)
    assert toks.shape == (6, STEPS)
    for r, h in enumerate(hists):
        # ONE uncached forward over the history and the served tokens
        logits = np.asarray(ref.forward(w, h + toks[r, :-1].tolist(), d)
                            )[len(h) - 1:]
        _close(first[r], logits[0])
        at = logits[np.arange(STEPS), toks[r]]
        spread = logits.std(axis=1)
        assert (np.abs(scores[r] - at) <= TOL * spread).all()
        assert (logits.max(axis=1) - at <= TOL * spread).all()  # greedy


@pytest.mark.parametrize("case", sorted(RAGGED))
def test_a_packed_ragged_batch_matches_the_reference(granite, case):
    d, cfg, w = granite
    lengths, slots = RAGGED[case]
    hists = _hists(np.random.default_rng(4), lengths)
    first, _ = _prefill(w, cfg, hists, slots)
    for r, h in enumerate(hists):
        _close(np.asarray(first)[r], np.asarray(ref.forward(w, h, d))[-1])


@pytest.mark.parametrize("slots,beside", [(64, []), (128, [30, 32]),
                                          (128, [7, 1, 9])])
def test_the_stream_and_neighbours_do_not_move_a_row(granite, slots,
                                                     beside):
    """A row's logits and the state it leaves, behind other rows and at
    another place in its chunk: the recurrence restarts at its first
    token, whatever lies before it."""
    d, cfg, w = granite
    rng = np.random.default_rng(6)
    mine = _hists(rng, [21])[0]
    alone, state = _prefill(w, cfg, [mine], 64, rows=1)
    hists = _hists(rng, beside) + [mine]
    got, other = _prefill(w, cfg, hists, slots)
    _close(np.asarray(got)[-1], np.asarray(alone)[0])
    for st, so in zip(state["layers"], other["layers"]):
        for k in st:
            a, b = np.asarray(so[k])[-1], np.asarray(st[k])[0]
            assert np.abs(a - b).max() <= 1e-5 * max(np.abs(b).max(), 1e-9)


def test_the_state_follows_the_rows_not_the_histories(granite):
    _, cfg, w = granite
    _, state = _prefill(w, cfg, _hists(np.random.default_rng(1), [9, 30]),
                        64)
    ssm = state["layers"][0]
    assert ssm["ssm"].shape == (2, 16, 4 * 32) \
        and ssm["ssm"].dtype == jnp.float32
    assert ssm["win"].shape == (2, 3, 128 + 32)
    assert state["layers"][2]["k"].shape == (2, 2, HISTORY + STEPS, 16)
    assert state["load"].shape == (0, 0)


def test_a_step_equals_the_prefill_of_one_more_token(granite):
    """``_mamba_step`` on the state a prefill of ``n`` tokens left is the
    prefill of ``n + 1``: output and state."""
    _, cfg, w = granite
    lw = w["layers"][0]
    rng = np.random.default_rng(2)
    z = jnp.asarray(rng.normal(size=(24, cfg.hidden_size)), jnp.float32)
    lengths = jnp.asarray([23], jnp.int32)
    # a row ENDS on a tile's edge: 23 tokens lie in slots 1..23, behind
    # a spare slot that holds anything
    shorter = jnp.concatenate([jnp.full((1, cfg.hidden_size), 5.0), z[:23]])
    valid, pos, rows = decoder._row_maps(lengths, 24, HISTORY, cfg.dtype)
    _, st = decoder._mamba_prefill(lw, shorter, valid, pos, rows, cfg)
    out, new = decoder._mamba_step(lw, z[-1:], st, cfg)
    valid, pos, rows = decoder._row_maps(lengths + 1, 24, HISTORY, cfg.dtype)
    want, want_st = decoder._mamba_prefill(lw, z, valid, pos, rows, cfg)
    np.testing.assert_allclose(np.asarray(out)[0], np.asarray(want)[-1],
                               atol=1e-5)
    for k in new:
        np.testing.assert_allclose(np.asarray(new[k]),
                                   np.asarray(want_st[k]), atol=1e-5)


def test_the_recurrence_carries_history_the_logits_see(granite):
    """Another first token, 30 tokens back, moves the last logits by far
    more than rounding: the state is not a window."""
    d, cfg, w = granite
    h = _hists(np.random.default_rng(8), [31])[0]
    other = [(h[0] + 1) % 256] + h[1:]
    a, _ = _prefill(w, cfg, [h], 64, rows=1)
    b, _ = _prefill(w, cfg, [other], 64, rows=1)
    a, b = np.asarray(a), np.asarray(b)
    assert np.abs(a - b).max() > 0.2 * a.std()  # 2,000 tolerances


DEFAULTS = {"embedding_multiplier": 1.0, "residual_multiplier": 1.0,
            "logits_scaling": 1.0, "position_embedding_type": "rope"}


@pytest.mark.parametrize("family,init", [
    (SMALL, None), (LAGUNA, None), (XING, None)],
    ids=["lfm2_moe", "laguna", "xing4_0"])
def test_the_multipliers_at_their_defaults_change_no_program(family, init):
    """The three families that give no multiplier: stating every one at
    its default (and the softmax scale as ``attention_multiplier``)
    traces the SAME program, equation for equation, and the same
    logits bit for bit."""
    d, cfg, w = _setup(base=family, init=init)
    stated = {**DEFAULTS}
    if "kv_lora_rank" not in family:
        stated["attention_multiplier"] = cfg.head_dim ** -0.5
    cfg2 = decoder.DecoderConfig.from_dict({**d, **stated})
    history = 40 if cfg.sliding_window else HISTORY
    hists = _hists(np.random.default_rng(3), [9, 17])
    tokens, lengths = _pack(hists, 2, 64,
                            align=decoder.row_align(history, cfg.dtype))

    def program(c):
        return jax.make_jaxpr(lambda w, t, n: decoder._gen_prefill(
            w, t, n, cfg=c, history=history, room=4))(w, tokens, lengths)

    assert str(program(cfg)) == str(program(cfg2))
    a, _ = decoder._gen_prefill(w, tokens, lengths, cfg=cfg,
                                history=history, room=4)
    b, _ = decoder._gen_prefill(w, tokens, lengths, cfg=cfg2,
                                history=history, room=4)
    np.testing.assert_array_equal(np.asarray(a), np.asarray(b))


def test_the_benchmarks_copy_of_the_reference_is_the_same(granite):
    """``cellbench/reference_granite.py`` imports nothing of the program;
    it is held to this package's reference output for output, and its
    ``state_bf16`` control to this package's ``round_state``."""
    copy = _benchmarks_copy("reference_granite")
    d, cfg, w = granite
    seq = _hists(np.random.default_rng(9), [29])[0]
    np.testing.assert_array_equal(np.asarray(copy.forward(w, seq, d)),
                                  np.asarray(ref.forward(w, seq, d)))
    z = jax.random.normal(jax.random.key(10), (29, cfg.hidden_size))
    lw = w["layers"][0]
    with jax.default_matmul_precision("highest"):
        sound = np.asarray(copy.mamba_op(lw, z, d))
        lossy = np.asarray(copy.mamba_op(lw, z, d, copy.round_bf16))
        np.testing.assert_array_equal(lossy, np.asarray(
            ref.mamba_op(lw, z, d, round_state=copy.round_bf16)))
    assert 1e-4 < np.abs(lossy - sound).max() / np.abs(sound).max() < 0.1


def _rel(got, want):
    return float(np.abs(got - want).max() / np.abs(want).max())


@pytest.mark.parametrize("seed", [7, 11])
def test_bfloat16_stays_inside_its_tolerance_and_a_bfloat16_state_does_not(
        seed):
    """What the served dtype costs at this size: bfloat16 weights and
    operands with a float32 state stay within 5 % of a logit's largest
    (readings 1.3-2 %); the check's ``state_bf16`` control moves the
    float32 reference itself by more than rounding."""
    d, cfg, w = _setup("bfloat16", seed=seed, base=GRANITE, init=INIT)
    hists = _hists(np.random.default_rng(seed), [25, 12])
    first, _ = _prefill(w, cfg, hists, 64)
    for r, h in enumerate(hists):
        want = np.asarray(ref.forward(w, h, d))[-1]
        assert _rel(np.asarray(first)[r], want) < 0.05
