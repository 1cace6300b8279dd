"""``models/decoder.py`` and ``ops/moe.py`` against the plain reference
(``models/decoder_reference.py``) at a small size on the CPU: prefill
and cached decoding, logit by logit.

Tolerances. In float32 the program and the reference differ by the
order of their sums only: 2e-4 of a logit whose spread is ~0.16
(readings under 2e-5). The bfloat16 tolerances are written at their
test.
"""

import dataclasses

import jax
import jax.numpy as jnp
import numpy as np
import pytest

from predictionio_tpu.models import decoder, decoder_reference as ref
from predictionio_tpu.ops import moe

SMALL = {
    "hidden_size": 64, "intermediate_size": 128,
    "moe_intermediate_size": 32, "num_hidden_layers": 6,
    "layer_types": ["conv", "conv", "full_attention", "conv", "conv",
                    "conv"],
    "num_dense_layers": 2, "num_attention_heads": 4,
    "num_key_value_heads": 2, "num_experts": 8,
    "num_experts_per_tok": 2, "vocab_size": 256, "conv_L_cache": 3,
    "conv_bias": False, "norm_eps": 1e-5, "norm_topk_prob": True,
    "use_expert_bias": True, "routed_scaling_factor": 1.0,
    "rope_theta": 1000000, "head_dim": 16, "dtype": "float32",
    "model_type": "lfm2_moe", "max_position_embeddings": 128000,
}
#: the ``laguna`` family at a small size: window 8, two query-head
#: counts over 2 key-value heads, 8 experts of which 2 a token and a
#: shared one, per-kind rotary (yarn over half a head in the full
#: layers, with a correction range that falls inside the 4 rotated
#: frequencies), the head gate, an untied head
LAGUNA = {
    "model_type": "laguna", "vocab_size": 256, "hidden_size": 64,
    "intermediate_size": 128, "num_hidden_layers": 5,
    "num_attention_heads": 4, "num_key_value_heads": 2, "head_dim": 16,
    "max_position_embeddings": 4096, "attention_bias": False,
    "rms_norm_eps": 1e-6, "num_experts": 8, "num_experts_per_tok": 2,
    "moe_intermediate_size": 32, "shared_expert_intermediate_size": 32,
    "tie_word_embeddings": False, "gating": True, "sliding_window": 8,
    "rope_parameters": {
        "full_attention": {
            "rope_theta": 500000, "rope_type": "yarn", "factor": 64,
            "original_max_position_embeddings": 16, "beta_slow": 1,
            "beta_fast": 4, "attention_factor": 1.4158883083359672,
            "partial_rotary_factor": 0.5},
        "sliding_attention": {"rope_type": "default", "rope_theta": 10000,
                              "partial_rotary_factor": 1},
        "original_max_position_embeddings": 16},
    "layer_types": ["full_attention", "sliding_attention",
                    "sliding_attention", "sliding_attention",
                    "full_attention"],
    "moe_apply_router_weight_on_input": False,
    "partial_rotary_factor": 0.5,
    "mlp_layer_types": ["dense", "sparse", "sparse", "sparse", "sparse"],
    "moe_routed_scaling_factor": 2.5,
    "num_attention_heads_per_layer": [4, 6, 6, 6, 4],
    "norm_topk_prob": True, "use_expert_bias": True, "dtype": "float32",
}
#: the ``xing4_0`` family at a small size, by its published key names:
#: latent attention (ranks 24 / 16, a head 16 + 8 wide against values
#: 16 wide, yarn whose correction range falls inside the 4 rotated
#: frequencies), four residual streams under hyper-connections, two
#: dense layers and two of 8 experts (2 a token) with a shared one
XING = {
    "model_type": "xing4_0", "vocab_size": 256, "hidden_size": 64,
    "intermediate_size": 128, "moe_intermediate_size": 32,
    "num_hidden_layers": 4, "num_attention_heads": 4,
    "num_key_value_heads": 4, "q_lora_rank": 24, "kv_lora_rank": 16,
    "qk_nope_head_dim": 16, "qk_rope_head_dim": 8, "v_head_dim": 16,
    "first_k_dense_replace": 2, "moe_layer_freq": 1,
    "n_routed_experts": 8, "num_experts_per_tok": 2,
    "n_shared_experts": 1, "scoring_func": "sigmoid",
    "topk_method": "noaux_tc", "n_group": 1, "topk_group": 1,
    "norm_topk_prob": True, "routed_scaling_factor": 2,
    "rms_norm_eps": 1e-6, "rope_theta": 10000,
    "rope_scaling": {"type": "yarn", "factor": 64,
                     "original_max_position_embeddings": 16,
                     "beta_fast": 4, "beta_slow": 1, "mscale": 1,
                     "mscale_all_dim": 1},
    "hc_mult": 4, "hc_sinkhorn_iters": 20, "hc_eps": 1e-6,
    "mhc_h_res_clamp_min": -30, "mhc_h_res_clamp_max": 30,
    "tie_word_embeddings": False, "attention_bias": False, "ep_size": 1,
    "hidden_act": "silu", "num_nextn_predict_layers": 1,
    "max_position_embeddings": 262144, "dtype": "float32",
}
#: attention outputs and expert blocks both a visible share of the
#: stream, as the benchmark's configuration sets them
LAGUNA_INIT = {"op_out": 4.0, "expert_out": 1.0}
LAGUNA_HISTORY = 40  # five tiles of 8: see the ``lag`` fixture
TOL32 = 2e-4
STEPS = 9  # the prefill's token and 8 decode steps


def _setup(dtype="float32", seed=0, base=SMALL, init=None, **over):
    d = {**base, "dtype": dtype, **over}
    cfg = decoder.DecoderConfig.from_dict(d)
    w = decoder.init_weights(jax.random.key(seed), cfg, init)
    return d, cfg, w


def _ref_weights(w, cfg):
    """The reference reads the program's tree as it is; a copy, so that
    a test can put a layer's weights through a round trip."""
    return {**w, "layers": [dict(lw) for lw in w["layers"]]}


HISTORY = 32  # the slots the state is laid out at in these tests


def _pack(hists, rows, slots, seed=0, align=8):
    """The engine's layout: the rows' tokens one row behind the other,
    each row ENDING on a tile of ``align`` slots (``decoder.row_ends``;
    8: what ``row_align`` gives these tests' float32 weights and
    histories of 32 and 40), pad rows of one token behind them,
    ARBITRARY ids in the spare slots."""
    lengths = np.ones((rows,), np.int32)
    lengths[:len(hists)] = [len(h) for h in hists]
    ends = decoder.row_ends(lengths, align)
    assert ends[-1] <= slots, "the stream does not hold its rows"
    tokens = np.random.default_rng(seed).integers(
        0, SMALL["vocab_size"], slots).astype(np.int32)
    for n, end, h in zip(lengths, ends, list(hists) + [[0]] * rows):
        tokens[end - n:end] = h
    return jnp.asarray(tokens), jnp.asarray(lengths)


def _history(cfg):
    return LAGUNA_HISTORY if cfg.sliding_window else HISTORY


def _prefill(w, cfg, hists, slots, rows=None, steps=STEPS):
    tokens, lengths = _pack(
        hists, rows or len(hists), slots,
        align=decoder.row_align(_history(cfg), cfg.dtype))
    return decoder._gen_prefill(w, tokens, lengths, cfg=cfg,
                                history=_history(cfg), room=steps)


def _generate(w, cfg, hists, slots, rows=None, steps=STEPS):
    first, state = _prefill(w, cfg, hists, slots, rows, steps)
    first = np.asarray(first)
    toks, scores, load, _ = decoder._gen_decode(
        w, state, jnp.asarray(first), cfg=cfg, steps=steps)
    return first, np.asarray(toks), np.asarray(scores), load


def _check_against_reference(d, w, hists, first, toks, scores):
    """Every served score is the reference's logit of the served token
    at that position, from ONE uncached forward over history + served
    tokens; and greedy took the reference's best."""
    rw = _ref_weights(w, None)
    for r, h in enumerate(hists):
        seq = h + toks[r, :-1].tolist()
        logits = np.asarray(ref.forward(rw, seq, d))[len(h) - 1:]
        assert logits.shape[0] == toks.shape[1]
        np.testing.assert_allclose(first[r], logits[0], atol=TOL32)
        at = logits[np.arange(len(logits)), toks[r]]
        np.testing.assert_allclose(scores[r], at, atol=TOL32)
        assert np.all(logits.max(axis=1) - at <= TOL32)


def _hists(rng, lengths):
    return [rng.integers(0, SMALL["vocab_size"], n).tolist()
            for n in lengths]


@pytest.fixture(scope="module")
def small():
    return _setup()


@pytest.fixture(scope="module")
def lag():
    """The ``laguna`` family at a small size, its prefill's attention
    in tiles of 8 (the window): histories to 40 cross five of them."""
    with pytest.MonkeyPatch.context() as patch:
        patch.setattr(decoder, "ATTENTION_BLOCK", 8)
        yield _setup(base=LAGUNA, init=LAGUNA_INIT)


@pytest.fixture(scope="module")
def xing():
    """The ``xing4_0`` family at a small size, its prefill's attention
    in tiles of 8: histories to 32 cross four of them."""
    with pytest.MonkeyPatch.context() as patch:
        patch.setattr(decoder, "ATTENTION_BLOCK", 8)
        yield _setup(base=XING, init=LAGUNA_INIT)


LONG = 21  # the prefill's token and 20 steps: a ring of 8 wraps twice


@pytest.fixture(scope="module")
def lag_served(lag):
    d, cfg, w = lag
    hists = _hists(np.random.default_rng(1), [5, 40, 23, 1, 17, 33])
    return hists, _generate(w, cfg, hists, 240, steps=LONG)


@pytest.fixture(scope="module")
def served(small):
    d, cfg, w = small
    # 192 slots: the prefill's experts go through the sorted groups,
    # the decode's 6 rows through the every-expert product
    hists = _hists(np.random.default_rng(1), [5, 16, 11, 1, 30, 32])
    return hists, _generate(w, cfg, hists, 192)


def test_config_reads_the_published_keys():
    _, cfg, _ = _setup()
    assert cfg.head_dim == 16 and cfg.n_held == 8
    lfm = dict(SMALL, num_hidden_layers=14, head_dim=None,
               layer_types=["conv", "conv"] + ["full_attention", "conv",
                                               "conv", "conv"] * 3)
    big = decoder.DecoderConfig.from_dict(lfm)
    assert big.head_dim == 16 and len(big.layer_types) == 14
    with pytest.raises(ValueError):
        decoder.DecoderConfig.from_dict(dict(SMALL, conv_bias=True))
    with pytest.raises(ValueError):
        decoder.DecoderConfig.from_dict(dict(SMALL, num_hidden_layers=5))


def test_prefill_and_cached_decode_match_the_full_forward(small, served):
    d, cfg, w = small
    hists, (first, toks, scores, _) = served
    _check_against_reference(d, w, hists, first, toks, scores)


def test_the_ring_wraps_twice_and_still_matches_the_full_forward(
        lag, lag_served):
    """Histories to five windows, then 20 cached steps (a ring of 8
    overwritten two and a half times) against ONE uncached forward of
    the reference over history + served tokens, logit by logit."""
    d, cfg, w = lag
    hists, (first, toks, scores, _) = lag_served
    assert toks.shape[1] == LONG
    _check_against_reference(d, w, hists, first, toks, scores)


#: (history lengths, rows, slots): the stream's sizes are the engine's
#: ladder for 4 rows over history buckets (8, 16, 32), every row (a pad
#: row of one token too) taking whole tiles of 8 slots
RAGGED = {
    "rows_of_one_token": ([1, 1, 1, 1], 4, 32),
    "rows_shorter_than_the_conv_window": ([2, 1, 2, 1], 4, 32),
    "a_row_at_the_top_bucket": ([32, 3, 9, 20], 4, 128),
    "every_row_at_the_top_bucket": ([32, 32, 32, 32], 4, 128),
    "a_batch_under_its_row_bucket": ([7, 12], 4, 64),
    "one_row_in_a_row_bucket": ([32], 4, 64),
    "a_sum_on_the_lowest_rung": ([8, 8, 8, 8], 4, 32),
    "a_sum_just_over_the_lowest_rung": ([9, 8, 8, 8], 4, 64),
    "a_sum_on_the_middle_rung": ([16, 6, 2, 16], 4, 64),
    "a_sum_on_the_top_rung": ([32, 31, 30, 29], 4, 128),
    "the_sorted_product_with_a_spare_tail": ([32, 1, 2, 32, 17, 5, 3, 9],
                                             8, 256),
    # the ``laguna`` family (history 40, window 8, tiles of 8)
    "laguna_rows_inside_one_window": ([3, 8, 1, 7], 4, 32),
    "laguna_rows_of_several_windows": ([40, 9, 25, 16], 4, 160),
    "laguna_a_batch_under_its_row_bucket": ([33, 12], 4, 160),
    "laguna_every_row_at_the_top_bucket": ([40, 40, 40, 40], 4, 160),
}


@pytest.mark.parametrize("case", sorted(RAGGED))
def test_a_packed_ragged_batch_matches_the_reference(small, lag, case):
    """Prefill's last-token logits and the decode that follows, row by
    row, whatever the rows' lengths, the spare slots' ids and the
    stream's size."""
    d, cfg, w = lag if case.startswith("laguna") else small
    lengths, rows, slots = RAGGED[case]
    hists = _hists(np.random.default_rng(len(case)), lengths)
    first, toks, scores, (pre, _) = _generate(w, cfg, hists, slots, rows,
                                              steps=4)
    _check_against_reference(d, w, hists, first[:len(hists)],
                             toks[:len(hists)], scores[:len(hists)])
    # pad rows are one token long; spare slots count for no expert
    assert (np.asarray(pre).sum(axis=1) == cfg.num_experts_per_tok
            * (sum(lengths) + rows - len(lengths))).all()


@pytest.mark.parametrize("slots,beside,family", [
    (32, [], "lfm2"), (96, [30, 32], "lfm2"), (128, [32, 20, 3], "lfm2"),
    (256, [3, 32], "lfm2"), (40, [], "laguna"), (160, [40, 9], "laguna")])
def test_the_stream_and_neighbours_do_not_move_a_row(
        small, served, lag, lag_served, slots, beside, family):
    """The same history alone, and behind other rows in streams of 2x,
    4x and 8x the slots, gives the same logits and the same tokens."""
    d, cfg, w = lag if family == "laguna" else small
    hists, (first, toks, scores, _) = \
        lag_served if family == "laguna" else served
    mix = _hists(np.random.default_rng(2), beside) + [hists[1]]
    f2, t2, s2, _ = _generate(w, cfg, mix, slots, steps=toks.shape[1])
    np.testing.assert_allclose(f2[-1], first[1], atol=TOL32)
    np.testing.assert_array_equal(t2[-1], toks[1])
    np.testing.assert_allclose(s2[-1], scores[1], atol=TOL32)


@pytest.mark.parametrize("family,lanes,head_parts", [
    ("lfm2", 16, 1), ("laguna", 16, 1), ("laguna", 16, 3), ("laguna", 8, 2),
    ("xing4", 16, 1), ("xing4", 8, 1)])
def test_the_heads_layout_moves_nothing(small, lag, xing, monkeypatch, family,
                                        lanes, head_parts):
    """A head that fills whole lane tiles stays in its projection's
    lanes (``[G, T, heads / G x D]``, what ``window_attention`` reads and
    writes), a narrower one gets an axis of its own: logits, state and a
    decode step are the same numbers either way. These families' heads
    are 16 wide: heads first at 128 lanes, in the lanes at 16 or 8 (the
    latent family's 8 rotated dimensions are then padded to 16, or not
    at all), alone or with the queries in ``head_parts`` groups."""
    d, cfg, w = {"lfm2": small, "laguna": lag, "xing4": xing}[family]
    hists = _hists(np.random.default_rng(14), [29, 7, 18, 1])
    tokens, lengths = _pack(hists, 4, 96)

    def run():
        first, st = decoder._gen_prefill.__wrapped__(
            w, tokens, lengths, cfg=cfg, history=_history(cfg), room=2)
        tok = jnp.argmax(first, axis=-1).astype(jnp.int32)
        return first, st, decoder._decode_step(w, st, tok, cfg)

    assert decoder._groups(4, 96, cfg.head_dim) == 4  # heads first as it is
    want = run()
    monkeypatch.setattr(decoder, "LANES", lanes)
    monkeypatch.setattr(decoder, "STREAM_TOKENS", 32)  # 96 slots are one
    monkeypatch.setattr(decoder, "HEAD_GROUP_ELEMENTS",
                        96 * 16 * 6 // head_parts)
    assert decoder._groups(4, 96, 16) == 1 and decoder._groups(4, 4, 16) == 4
    for a, b in zip(jax.tree_util.tree_leaves(want),
                    jax.tree_util.tree_leaves(run())):
        np.testing.assert_allclose(a, b, atol=2e-5)


def _row_state(state, r):
    """Row ``r``'s logits-independent state, layer by layer."""
    return [np.asarray(a[r]) for st in state["layers"]
            for _, a in sorted(st.items())] + [
        np.asarray(state[k][r]) for k in ("pos", "valid")]


@pytest.mark.parametrize("order", [(5, 4, 3, 2, 1, 0), (2, 0, 5, 1, 4, 3),
                                   (1, 2, 3, 4, 5, 0)])
def test_permuting_the_rows_permutes_logits_and_state(small, served, order):
    d, cfg, w = small
    hists, _ = served
    f0, st0 = _prefill(w, cfg, hists, 192)
    f1, st1 = _prefill(w, cfg, [hists[i] for i in order], 192)
    for new, old in enumerate(order):
        np.testing.assert_allclose(f1[new], f0[old], atol=TOL32)
        for a, b in zip(_row_state(st1, new), _row_state(st0, old)):
            np.testing.assert_allclose(a, b, atol=1e-5)
    np.testing.assert_array_equal(np.asarray(st1["load"]),
                                  np.asarray(st0["load"]))


@pytest.mark.parametrize("changed,family", [
    (0, "lfm2"), (3, "lfm2"), (5, "lfm2"), (1, "laguna")])
def test_a_neighbours_tokens_move_no_other_row(small, served, lag,
                                               lag_served, changed, family):
    d, cfg, w = lag if family == "laguna" else small
    hists, _ = lag_served if family == "laguna" else served
    f0, st0 = _prefill(w, cfg, hists, 192)
    other = [list(h) for h in hists]
    other[changed] = [(t + 1) % SMALL["vocab_size"]
                      for t in other[changed]]
    f1, st1 = _prefill(w, cfg, other, 192)
    assert sum(len(h) for h in hists) <= 192
    for r in range(len(hists)):
        if r == changed:
            assert np.abs(np.asarray(f1[r]) - np.asarray(f0[r])).max() \
                > 10 * TOL32
            continue
        np.testing.assert_allclose(f1[r], f0[r], atol=TOL32)
        for a, b in zip(_row_state(st1, r), _row_state(st0, r)):
            np.testing.assert_allclose(a, b, atol=1e-5)


def test_the_state_does_not_depend_on_the_batch(small):
    """Keys, values and ``valid`` are laid out at ``history`` slots
    whatever the rows' lengths and the stream's size: the decode's
    program depends on the rows alone."""
    d, cfg, w = small
    shapes = []
    for lengths, slots in (([1, 2], 16), ([32, 32], 64), ([9, 30], 64)):
        _, st = _prefill(w, cfg, _hists(np.random.default_rng(3), lengths),
                         slots)
        shapes.append(jax.tree_util.tree_map(lambda a: a.shape, st))
        assert int(st["filled"]) == HISTORY
        assert st["valid"].shape == (2, HISTORY + STEPS)
        np.testing.assert_array_equal(
            np.asarray(st["valid"]).sum(axis=1), lengths)
    assert shapes[0] == shapes[1] == shapes[2]


def test_conv_step_equals_its_prefill(small):
    """Two rows of 7 in a stream of 16: a row's window after 6 tokens
    and one step is its window after 7, and a tap never reaches into
    the row before."""
    d, cfg, w = small
    lw = w["layers"][0]
    z = jax.random.normal(jax.random.key(3), (16, cfg.hidden_size))
    slot = jnp.arange(16)

    def run(stream, n):
        first = jnp.asarray([0, n])
        return decoder._conv_prefill(
            lw, stream, slot < 2 * n, slot - first[jnp.minimum(slot // n, 1)],
            first + n - 1, cfg)

    full, st = run(z, 7)
    # the same rows less their last token, one behind the other
    _, st6 = run(jnp.concatenate([z[:6], z[7:13], z[:4]]), 6)
    step, st7 = decoder._conv_step(lw, jnp.stack([z[6], z[13]]), st6, cfg)
    np.testing.assert_allclose(step, jnp.stack([full[6], full[13]]),
                               atol=1e-5)
    np.testing.assert_allclose(st7["win"], st["win"], atol=1e-6)
    for r in (0, 1):
        np.testing.assert_allclose(
            full[7 * r:7 * r + 7], ref.conv_op(lw, z[7 * r:7 * r + 7], d),
            atol=1e-5)


def test_expert_load_counts_real_tokens_only(small, served):
    d, cfg, w = small
    hists, (_, _, _, (pre, dec)) = served
    pre, dec = np.asarray(pre), np.asarray(dec)
    k = cfg.num_experts_per_tok
    n_expert = cfg.num_hidden_layers - cfg.num_dense_layers
    assert pre.shape == (n_expert, cfg.num_experts)
    assert dec.shape == (STEPS - 1, n_expert, cfg.num_experts)
    assert (pre.sum(axis=1) == k * sum(len(h) for h in hists)).all()
    assert (dec.sum(axis=2) == k * len(hists)).all()


def test_router_bias_moves_the_selection_not_the_weights():
    z = jax.random.normal(jax.random.key(4), (32, 64))
    gate = jax.random.normal(jax.random.key(5), (64, 8)) / 8.0
    sel0, w0 = moe.route(z, gate, None, top_k=2)
    bias = jnp.zeros(8).at[3].set(10.0)
    sel1, w1 = moe.route(z, gate, bias, top_k=2)
    assert (np.asarray(sel1) == 3).any(axis=1).all()
    assert not (np.asarray(sel0) == 3).any(axis=1).all()
    s = np.asarray(jax.nn.sigmoid(jnp.dot(z, gate, precision="highest")))
    picked = np.take_along_axis(s, np.asarray(sel1), axis=1)
    # the weights come from s WITHOUT the bias, and sum to
    # 1 / (1 + 1e-6 / sum)
    np.testing.assert_allclose(
        w1, picked / (picked.sum(1, keepdims=True) + 1e-6), rtol=1e-5)
    np.testing.assert_allclose(
        np.asarray(w1).sum(1), 1 / (1 + 1e-6 / picked.sum(1)), rtol=1e-5)
    _, raw = moe.route(z, gate, bias, top_k=2, norm_topk=False, scale=2.0)
    np.testing.assert_allclose(raw, 2.0 * picked, rtol=1e-5)


HELD7 = (0, 1, 2, 4, 5, 6, 7)


@pytest.mark.parametrize("tokens,top_k,real,held", [
    (24, 2, None, HELD7), (200, 2, None, HELD7),
    (24, 4, None, HELD7), (200, 4, None, HELD7),
    (512, 4, 300, None), (512, 4, 300, HELD7), (512, 4, 300, (3, 6)),
    (192, 4, 192, None), (130, 2, 1, (5,)), (640, 4, 0, None),
    # tokens x top_k at the held experts or under: the touched form
    (4, 2, None, None), (2, 2, 2, None), (2, 4, 1, None), (2, 2, 0, None),
    (3, 2, 3, HELD7), (1, 2, 1, (3, 6)),
], ids=lambda v: "all" if v is None else str(v).replace(" ", ""))
def test_both_forms_of_the_product_give_the_reference(small, tokens, top_k,
                                                      real, held):
    """Few tokens (every expert for every token, or the touched experts
    alone) and many (sorted groups) against each other at ANY size, and
    what ``expert_product`` picks against the reference. ``tokens`` slots
    of which the first ``real`` are a packed stream's tokens (``None``:
    every fifth slot is padding), routed ``top_k`` ways over 8 experts of
    which ``held`` are here (``None``: all)."""
    d, cfg, w = small
    lw = w["layers"][4]
    z = jax.random.normal(jax.random.key(tokens + top_k),
                          (tokens, cfg.hidden_size))
    valid = jnp.arange(tokens) % 5 != 0 if real is None \
        else jnp.arange(tokens) < real
    sel, wts = moe.route(z, lw["gate"], lw["gate_bias"], top_k=top_k)
    idx = jnp.arange(8) if held is None else jnp.asarray(held)
    ws = (lw["w1"][idx], lw["w3"][idx], lw["w2"][idx])
    got = moe.expert_product(z, sel, wts, *ws, n_experts=8, held=held,
                             valid=valid)
    assert got.dtype == jnp.float32 and got.shape == z.shape
    local, n_held = moe.local_index(sel, 8, held)
    local = jnp.where(valid[:, None], local, n_held)
    few = moe._every_expert(z, local, wts, *ws)
    many = moe._sorted_groups(z, local, wts, *ws)
    touched = moe._touched_experts(z, local, wts, *ws)
    np.testing.assert_allclose(few, many, atol=1e-5)
    np.testing.assert_allclose(touched, few, atol=1e-5)
    form = moe.product_form(tokens, top_k, n_held)
    assert form == (moe.SORTED if tokens > moe.DENSE_MAX_ROWS
                    else moe.TOUCHED if tokens * top_k <= n_held
                    else moe.EVERY)
    np.testing.assert_array_equal(
        got, {moe.SORTED: many, moe.TOUCHED: touched, moe.EVERY: few}[form])
    share = {**lw, "w1": ws[0], "w3": ws[1], "w2": ws[2]}
    cfg_ref = {**d, "num_experts_per_tok": top_k}
    if held is not None:
        cfg_ref["experts_held"] = held
    want = np.where(np.asarray(valid)[:, None],
                    np.asarray(ref.expert_ff(share, z, cfg_ref)), 0.0)
    np.testing.assert_allclose(got, want, atol=1e-5)
    np.testing.assert_allclose(many, want, atol=1e-5)
    # a pad slot's output is exactly 0, not nearly
    assert not np.asarray(many)[~np.asarray(valid)].any()
    assert not np.asarray(touched)[~np.asarray(valid)].any()


@pytest.mark.parametrize("tokens,top_k,n_held,form", [
    (16, 8, 256, moe.TOUCHED),    # the laguna cell's step: 128 over 256
    (16, 8, 128, moe.TOUCHED),    # T k == E_held
    (16, 8, 127, moe.EVERY),      # T k == E_held + 1
    (32, 8, 256, moe.TOUCHED), (32, 8, 255, moe.EVERY),
    (64, 4, 32, moe.EVERY),       # the lfm2_moe cell's step: 256 over 32
    (4, 2, 16, moe.TOUCHED), (4, 2, 8, moe.TOUCHED), (5, 2, 9, moe.EVERY),
    (1, 1, 1, moe.TOUCHED), (1, 2, 1, moe.EVERY),
    (128, 1, 128, moe.TOUCHED), (128, 1, 127, moe.EVERY),
    (129, 1, 100000, moe.SORTED), (32768, 8, 256, moe.SORTED),
])
def test_the_form_follows_from_the_shapes_alone(tokens, top_k, n_held,
                                                form):
    """Over ``DENSE_MAX_ROWS`` tokens the sorted groups; else the touched
    experts where the step's assignments do not outnumber the held
    experts, else every expert: at the boundary and one assignment past
    it."""
    assert moe.TOUCHED_REACH == 1
    assert moe.product_form(tokens, top_k, n_held) == form


def _touched_case(name):
    """``(sel [T, k], valid [T] or None, n_experts, held, dtype,
    distinct held experts selected)`` at shapes the rule sends to the
    touched form (``T k <= E_held``)."""
    rng = np.random.default_rng(len(name))
    T, k, E = 4, 2, 16
    spread = rng.permutation(E)[:T * k].reshape(T, k)
    if name == "same_k":        # n = k: every later step is skipped
        return np.tile([[11, 2]], (T, 1)), None, E, None, "float32", k
    if name == "all_distinct":  # n = G: nothing is skipped
        return spread, None, E, None, "float32", T * k
    if name == "pads_among_real":
        valid = np.array([True, False, True, False])
        return spread, valid, E, None, "float32", 2 * k
    if name == "every_row_a_pad":  # n = 0
        return spread, np.zeros(T, bool), E, None, "float32", 0
    if name == "bfloat16":
        return spread, None, E, None, "bfloat16", T * k
    # shares of 32 experts, 16 held: none, some, all of the selected
    held = tuple(range(0, 32, 2))
    sel = {"held_none": 2 * spread + 1,
           "held_some": np.where(np.arange(k) == 0, 2 * spread,
                                 2 * spread + 1),
           "held_all": 2 * spread}[name]
    return (sel, None, 32, held, "float32",
            len(set(sel.reshape(-1).tolist()) & set(held)))


@pytest.mark.parametrize("name", [
    "same_k", "all_distinct", "pads_among_real", "every_row_a_pad",
    "held_none", "held_some", "held_all", "bfloat16"])
def test_the_touched_form_is_the_every_expert_product(name):
    """The kernel (through Pallas' interpreter here) against
    ``_every_expert`` at shapes ``expert_product`` sends to it: only the
    float32 order of the sum over experts may differ. The list it walks
    holds the distinct held experts in ascending order, padded with its
    last entry; pad rows, and a step that selected nothing held here,
    come out as exact zeros."""
    sel, valid, n_experts, held, dtype, distinct = _touched_case(name)
    T, k = sel.shape
    H, F = 64, 32
    n_held = n_experts if held is None else len(held)
    assert moe.product_form(T, k, n_held) == moe.TOUCHED
    keys = jax.random.split(jax.random.key(T + n_experts), 5)
    x = jax.random.normal(keys[0], (T, H)).astype(dtype)
    w1, w3 = (jax.random.normal(key, (n_held, H, F)).astype(dtype) / 8
              for key in keys[1:3])
    w2 = (jax.random.normal(keys[3], (n_held, F, H)) / 6).astype(dtype)
    wts = jax.random.uniform(keys[4], (T, k), minval=0.2)
    sel = jnp.asarray(sel, jnp.int32)
    valid = None if valid is None else jnp.asarray(valid)
    got = moe.expert_product(x, sel, wts, w1, w3, w2, n_experts=n_experts,
                             held=held, valid=valid)
    local, _ = moe.local_index(sel, n_experts, held)
    if valid is not None:
        local = jnp.where(valid[:, None], local, n_held)
    ids, n = moe.touched_list(local, n_held, min(n_held, T * k))
    want_ids = sorted(set(np.asarray(local).reshape(-1).tolist())
                      - {n_held})
    assert int(n) == distinct == len(want_ids)
    assert np.asarray(ids).tolist() == (
        want_ids + want_ids[-1:] * (len(ids) - distinct) if want_ids
        else [0] * len(ids))
    want = moe._every_expert(x, local, wts, w1, w3, w2)
    assert got.dtype == jnp.float32 and got.shape == (T, H)
    np.testing.assert_allclose(got, want, atol=1e-5)
    if distinct:
        assert np.abs(np.asarray(want)).max() > 0.1
    else:
        assert not np.asarray(got).any()
    if valid is not None:
        assert not np.asarray(got)[~np.asarray(valid)].any()


def test_a_wide_expert_goes_through_the_kernel_in_tiles(monkeypatch):
    """Where an expert's three weights are over ``EXPERT_BLOCK_BYTES``
    the grid takes ``F`` in equal tiles of whole lanes; the sum over the
    tiles is the expert's output."""
    assert moe._f_tiles(2048, 512, 2) == 1      # the laguna cell: whole
    assert moe._f_tiles(2048, 1792, 2) == 7     # lfm2_moe's: 256 a tile
    assert moe._f_tiles(64, 96, 4) == 1         # no tile of whole lanes
    T, k, E, H, F = 2, 2, 8, 16, 256
    monkeypatch.setattr(moe, "EXPERT_BLOCK_BYTES", 3 * H * 128 * 4)
    assert moe._f_tiles(H, F, 4) == 2
    keys = jax.random.split(jax.random.key(2), 5)
    x = jax.random.normal(keys[0], (T, H))
    w1, w3 = (jax.random.normal(key, (E, H, F)) / 4 for key in keys[1:3])
    w2 = jax.random.normal(keys[3], (E, F, H)) / 16
    wts = jax.random.uniform(keys[4], (T, k))
    local = jnp.asarray([[6, 1], [1, 3]], jnp.int32)
    np.testing.assert_allclose(
        moe._touched_experts(x, local, wts, w1, w3, w2),
        moe._every_expert(x, local, wts, w1, w3, w2), atol=1e-5)


def _all_eqns(jaxpr):
    """Every equation of a jaxpr and of the jaxprs inside it."""
    for eqn in jaxpr.eqns:
        yield eqn
        for sub in jax.core.jaxprs_in_params(eqn.params):
            yield from _all_eqns(sub)


def test_the_many_token_form_moves_each_assignment_row_once():
    """Structure of the many-token form at ``T`` 512, ``k`` 4: every
    gather promises its indices in bounds (a fill-mode gather pays a
    select over its whole output), the only ``[T*k, H]`` arrays are the
    gathered tokens and the third product's output, and none of them is
    reshaped (``[T*k, H] -> [T, k, H]`` moves ``k`` into the tiled
    dimension on the chip)."""
    T, k, E, H, F = 512, 4, 8, 64, 32
    f32 = jnp.float32
    shapes = (jax.ShapeDtypeStruct((T, H), f32),
              jax.ShapeDtypeStruct((T, k), jnp.int32),
              jax.ShapeDtypeStruct((T, k), f32),
              jax.ShapeDtypeStruct((E, H, F), f32),
              jax.ShapeDtypeStruct((E, H, F), f32),
              jax.ShapeDtypeStruct((E, F, H), f32))
    eqns = list(_all_eqns(
        jax.make_jaxpr(moe._sorted_groups)(*shapes).jaxpr))
    gathers = [e for e in eqns if e.primitive.name == "gather"]
    assert len(gathers) >= 1 + k
    for e in gathers:
        assert e.params["mode"] in (
            jax.lax.GatherScatterMode.PROMISE_IN_BOUNDS,
            jax.lax.GatherScatterMode.CLIP), e
    wide = [e.primitive.name for e in eqns for v in e.outvars
            if getattr(v.aval, "shape", None) == (T * k, H)]
    assert wide == ["gather", "ragged_dot_general"], wide
    for e in eqns:
        if e.primitive.name == "reshape":
            assert e.invars[0].aval.shape != (T * k, H), e
    assert sum(e.primitive.name == "ragged_dot_general" for e in eqns) == 3


@pytest.mark.parametrize("garbage", [np.nan, np.inf, -np.inf])
def test_rows_behind_the_last_group_cannot_leak(garbage):
    """``ragged_dot`` never writes the rows behind the last group: the
    combine must not let them through, whatever they hold (0 x NaN is
    NaN). Token 0 is a pad slot, token 1 routed to absent experts only,
    token 2 to one held and one absent expert."""
    T, k, H, E = 6, 2, 8, 3
    local = np.array([[E, E], [E, E], [1, E], [0, 2], [2, 1], [0, 1]],
                     np.int32)
    wts = np.full((T, k), 0.5, np.float32)
    flat = local.reshape(-1)
    order = np.argsort(flat, kind="stable")
    n_live = int((flat < E).sum())
    y = np.arange(T * k * H, dtype=np.float32).reshape(T * k, H) + 1.0
    y[n_live:] = garbage
    back = np.empty_like(order)
    back[order] = np.arange(T * k)
    back = back.reshape(T, k)
    got = np.asarray(moe._combine(jnp.asarray(y), jnp.asarray(back),
                                  jnp.asarray(local < E), jnp.asarray(wts)))
    assert np.isfinite(got).all()
    assert not got[:2].any()
    want = np.where((local < E)[..., None], wts[..., None] * y[back],
                    0.0).sum(axis=1)
    np.testing.assert_allclose(got, want, rtol=1e-6)
    assert (got[2] == 0.5 * y[back[2, 0]]).all()


@pytest.mark.parametrize("tokens,family", [
    (24, "lfm2"), (384, "lfm2"), (24, "laguna"), (384, "laguna"),
    (24, "xing"), (384, "xing")])
def test_four_shares_add_up_to_the_uncut_layer(small, lag, xing, tokens,
                                               family):
    """The guide's share test: four chips of 2 experts each route over
    all 8 and compute their own experts' part; the parts add up to the
    reference's whole layer (program AND reference given the shares),
    in the few-token form and in the many-token one. What every chip
    computes alike, the ``laguna`` family's shared expert, is counted
    ONCE: each share's output holds it whole. The ``xing4_0`` family
    likewise (its 8 experts by their published key ``n_routed_experts``,
    scaled by 2, and its shared expert)."""
    d, cfg, w = {"laguna": lag, "xing": xing}.get(family, small)
    lw = w["layers"][3]
    z = jax.random.normal(jax.random.key(6), (tokens, cfg.hidden_size))
    whole = np.asarray(ref.expert_ff(lw, z, d))
    alike = np.asarray(ref.dense_ff(lw, z, ("s1", "s3", "s2"))) \
        if family != "lfm2" else 0.0
    if family != "lfm2":
        assert np.abs(alike).mean() > 0.1 * np.abs(whole).mean()
    full, _ = decoder._feed_forward(lw, z, None, cfg)
    # float32 both sides, outputs up to 7: the largest difference read is
    # 1.9e-6 (lfm2) and 2.9e-6 (laguna: routed scale 2.5, shared expert
    # added), at 384 tokens; the same 1e-5 holds both families
    np.testing.assert_allclose(full, whole + alike, atol=1e-5)
    # the reference's layer, residual and norm left out: z IS n_ff(h)
    got = np.zeros_like(whole)
    want = np.zeros_like(whole)
    for held in ((0, 1), (2, 3), (4, 5), (6, 7)):
        idx = jnp.asarray(held)
        share = {**lw, "w1": lw["w1"][idx], "w3": lw["w3"][idx],
                 "w2": lw["w2"][idx]}
        part, load = decoder._feed_forward(
            share, z, None, dataclasses.replace(cfg, experts_held=held))
        assert int(load.sum()) == tokens * cfg.num_experts_per_tok
        got += np.asarray(part) - alike
        want += np.asarray(ref.expert_ff(share, z,
                                         {**d, "experts_held": held}))
    np.testing.assert_allclose(got + alike, whole + alike, atol=1e-5)
    np.testing.assert_allclose(want, whole, atol=1e-5)


def _int8_round_trip(a):
    """Symmetric int8 with one scale per output column."""
    a = np.asarray(a, np.float32)
    scale = np.abs(a).max(axis=-2, keepdims=True) / 127.0
    return jnp.asarray(np.round(a / scale) * scale)


def _rel(got, want):
    got, want = np.asarray(got, np.float64), np.asarray(want, np.float64)
    return float(np.linalg.norm(got - want) / np.linalg.norm(want))


@pytest.mark.parametrize("seed", [7, 11])
def test_bfloat16_stays_inside_the_tolerance_and_int8_does_not(seed):
    """The expert block with bfloat16 weights, against the reference in
    float32 on those same weights: 4e-3 of the output's norm (readings
    1.5e-3 .. 1.8e-3: the rounding of two operands to bfloat16), which
    the reference does not meet once the expert weights have been
    through int8 (readings 9.8e-3 .. 1.04e-2). The whole stack is then
    held at the median position, in units of the position's spread of
    reference logits (limit 0.05; readings 0.008 .. 0.015): there the
    rounding of every layer's operands is as large as what int8 adds
    in the experts, so that limit does not tell the two apart and the
    layer's does."""
    d, cfg, w = _setup("bfloat16", seed=seed)
    lw = w["layers"][3]
    z = jax.random.normal(jax.random.key(seed), (64, cfg.hidden_size)
                          ).astype(jnp.bfloat16).astype(jnp.float32)
    want = ref.expert_ff(lw, z, d)
    got, _ = decoder._feed_forward(lw, z, None, cfg)
    assert _rel(got, want) <= 4e-3
    lossy = {**lw, **{n: _int8_round_trip(lw[n])
                      for n in ("w1", "w3", "w2")}}
    assert _rel(ref.expert_ff(lossy, z, d), want) > 4e-3

    hists = _hists(np.random.default_rng(seed + 1), [12, 16, 7, 16])
    _, toks, scores, _ = _generate(w, cfg, hists, 64)
    gaps = []
    for r, h in enumerate(hists):
        seq = h + toks[r, :-1].tolist()
        logits = np.asarray(ref.forward(w, seq, d))[len(h) - 1:]
        at = logits[np.arange(len(logits)), toks[r]]
        gaps.append(np.abs(scores[r] - at) / logits.std(axis=1))
    assert np.median(np.concatenate(gaps)) <= 0.05


def _benchmarks_copy(name):
    import importlib.util
    import os

    path = os.path.join(os.path.dirname(os.path.dirname(
        os.path.abspath(__file__))), "cellbench", name + ".py")
    spec = importlib.util.spec_from_file_location(name, path)
    copy = importlib.util.module_from_spec(spec)
    spec.loader.exec_module(copy)
    return copy


def test_the_benchmarks_copy_of_the_reference_is_the_same(small):
    """``cellbench/reference_lfm2.py`` imports nothing of the program;
    it is held to this package's reference output for output."""
    copy = _benchmarks_copy("reference_lfm2")
    d, cfg, w = small
    seq = _hists(np.random.default_rng(9), [13])[0]
    np.testing.assert_array_equal(np.asarray(copy.forward(w, seq, d)),
                                  np.asarray(ref.forward(w, seq, d)))
    share = {**d, "experts_held": (2, 5)}
    lw = w["layers"][4]
    lw = {**lw, **{n: lw[n][jnp.asarray((2, 5))]
                   for n in ("w1", "w3", "w2")}}
    z = jax.random.normal(jax.random.key(10), (5, cfg.hidden_size))
    np.testing.assert_array_equal(np.asarray(copy.expert_ff(lw, z, share)),
                                  np.asarray(ref.expert_ff(lw, z, share)))
