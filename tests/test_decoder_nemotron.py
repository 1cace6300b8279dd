"""The ``nemotron_h`` family through ``models/decoder.py`` against the
plain reference (``models/decoder_reference.py``) at a small size on the
CPU: layers of ONE sub-block each by ``hybrid_override_pattern`` (a
Mamba-2 mixer over several groups, NoPE grouped-query attention, or
relu² experts in a latent beside a shared expert), prefill and decoding
through the state, logit by logit; the chip's share of the experts tied
to the uncut layer; and the four older families' logits held, bit for
bit, to what the commit before this family served. float32 weights, so
the program and the reference differ by the order of their sums only:
the tolerance is 1e-4 of a position's SPREAD of logits.
It imports ``tests/test_decoder.py``'s fixtures: run it from the repo
root."""

import os
import re

import jax
import jax.numpy as jnp
import numpy as np
import pytest

from predictionio_tpu.models import decoder, decoder_reference as ref
from predictionio_tpu.ops import moe
from tests.golden import make_decoder_logits as golden
from tests.test_decoder import (
    HISTORY, STEPS, _benchmarks_copy, _generate, _hists, _prefill, _setup)

TOL = 1e-4  # of a position's spread of logits

#: the family at a small size, by its published key names: all three
#: letters, 8 Mamba heads of 16 in 2 groups over a state of 16 in chunks
#: of 16, attention 4 heads over 2 of 16, 16 experts (4 a token) 48 wide
#: in a latent of 32 beside a shared expert 96 wide
NEMOTRON = {
    "model_type": "nemotron_h", "vocab_size": 256, "hidden_size": 64,
    "hybrid_override_pattern": "MEM*EME", "num_hidden_layers": 7,
    "num_attention_heads": 4, "num_key_value_heads": 2, "head_dim": 16,
    "attention_bias": False, "chunk_size": 16, "conv_kernel": 4,
    "expand": 2, "intermediate_size": 48, "layer_norm_epsilon": 1e-5,
    "mamba_head_dim": 16, "mamba_hidden_act": "silu", "mamba_num_heads": 8,
    "mamba_proj_bias": False, "mlp_bias": False, "mlp_hidden_act": "relu2",
    "moe_intermediate_size": 48, "moe_latent_size": 32,
    "moe_shared_expert_intermediate_size": 96, "n_group": 1, "n_groups": 2,
    "n_routed_experts": 16, "n_shared_experts": 1, "norm_eps": 1e-5,
    "norm_topk_prob": True, "num_experts_per_tok": 4,
    "partial_rotary_factor": 1, "routed_scaling_factor": 5,
    "rope_theta": 10000, "ssm_state_size": 16, "tie_word_embeddings": False,
    "topk_group": 1, "use_bias": False, "use_conv_bias": True,
    "num_nextn_predict_layers": 1, "mtp_hybrid_override_pattern": "*E",
    "max_position_embeddings": 262144, "dtype": "float32",
}
#: every sub-block a visible share of the stream
INIT = {"op_out": 1.0, "expert_out": 1.0}
SHARES = [tuple(range(s, s + 4)) for s in (0, 4, 8, 12)]


def _share(held):
    """The configuration as a benchmark file cuts it: the counting key
    gives the share, ``router_experts`` the router's width."""
    return {**NEMOTRON, "n_routed_experts": len(held),
            "router_experts": 16, "experts_held": list(held)}


@pytest.fixture(scope="module")
def nemotron():
    return _setup(base=NEMOTRON, init=INIT)


def _close(got, want):
    got, want = np.asarray(got), np.asarray(want)
    gap = np.abs(got - want).max(axis=-1) / want.std(axis=-1)
    assert gap.max() <= TOL, gap.max()


def test_config_reads_the_published_keys(nemotron):
    _, cfg, w = nemotron
    assert cfg.layer_types == (
        "mamba", "none", "mamba", "full_attention", "none", "mamba", "none")
    assert cfg.mlp_layer_types == (
        "none", "sparse", "none", "none", "sparse", "none", "sparse")
    assert cfg.nope and not cfg.tie_word_embeddings
    assert (cfg.mamba_n_heads, cfg.mamba_d_head, cfg.mamba_d_state,
            cfg.mamba_n_groups, cfg.conv_L_cache, cfg.conv_bias,
            cfg.mamba_chunk_size) == (8, 16, 16, 2, 4, True, 16)
    assert (cfg.num_experts, cfg.n_held, cfg.moe_latent_size,
            cfg.shared_expert_intermediate_size, cfg.mlp_hidden_act,
            cfg.norm_eps) == (16, 16, 32, 96, "relu2", 1e-5)
    # ONE norm and one sub-block's weights a layer: no stand-in
    mixer, experts, attention = (w["layers"][l] for l in (0, 1, 3))
    assert sorted(mixer) == ["A_log", "D", "conv_b", "conv_w", "dt_bias",
                             "op_norm", "ssm_norm", "w_in", "w_out"]
    assert sorted(attention) == ["op_norm", "wk", "wo", "wq", "wv"]
    assert sorted(experts) == ["ff_norm", "gate", "gate_bias", "s1", "s2",
                               "w1", "w2", "w_down", "w_up"]
    assert mixer["w_in"].shape == (64, 2 * 128 + 2 * 2 * 16 + 8)
    assert mixer["conv_w"].shape == (128 + 2 * 2 * 16, 4)
    assert experts["w1"].shape == (16, 32, 48) \
        and experts["w2"].shape == (16, 48, 32)
    assert experts["w_down"].shape == (64, 32) \
        and experts["w_up"].shape == (32, 64)
    assert experts["s1"].shape == (64, 96) and experts["gate"].shape == (64, 16)
    assert "head" in w


def test_a_share_is_said_by_the_counting_key_and_the_routers_width():
    cfg = decoder.DecoderConfig.from_dict(_share(SHARES[1]))
    assert (cfg.num_experts, cfg.n_held, cfg.experts_held) == (
        16, 4, (4, 5, 6, 7))
    shapes = decoder._layer_shapes(cfg, 1)
    assert shapes["gate"][0] == (64, 16) and shapes["w1"][0] == (4, 32, 48)
    with pytest.raises(ValueError, match="router_experts"):
        decoder.DecoderConfig.from_dict(
            {**_share(SHARES[1]), "n_routed_experts": 16})
    with pytest.raises(ValueError, match="router_experts"):
        decoder.DecoderConfig.from_dict({**NEMOTRON, "router_experts": 64})


@pytest.mark.parametrize("key,value,named", [
    ("attention_bias", True, "attention_bias"),
    ("mamba_proj_bias", True, "mamba_proj_bias"),
    ("mlp_bias", True, "mlp_bias"), ("use_bias", True, "use_bias"),
    ("n_group", 2, "n_group"), ("topk_group", 2, "topk_group"),
    ("mamba_hidden_act", "relu", "mamba_hidden_act"),
    ("mlp_hidden_act", "gelu", "mlp_hidden_act"),
    ("n_groups", 3, "mamba_n_groups"), ("norm_eps", 1e-6, "norm_eps"),
    ("hybrid_override_pattern", "MEM-EME", "letter '-'"),
    ("hybrid_override_pattern", "MEM*EM", "num_hidden_layers"),
    ("layer_types", ["mamba"] * 7, "twice"),
])
def test_an_unwritten_key_of_the_family_raises_by_name(key, value, named):
    """Each key that switches on mathematics nobody has written, a ``-``
    in the pattern (the family's dense feed-forward layer), a pattern of
    another length, the layers given twice and ``norm_eps`` beside
    another ``layer_norm_epsilon``: all say what they are."""
    with pytest.raises(ValueError, match=named):
        decoder.DecoderConfig.from_dict({**NEMOTRON, key: value})


def test_experts_on_the_stream_itself_take_no_projection():
    """``moe_latent_size`` 0: the same two-matrix experts, their rows
    the stream's; gated experts in a latent are not written."""
    cfg = decoder.DecoderConfig.from_dict({**NEMOTRON, "moe_latent_size": 0})
    shapes = decoder._layer_shapes(cfg, 1)
    assert "w_down" not in shapes and "w3" not in shapes
    assert shapes["w1"][0] == (16, 64, 48)
    with pytest.raises(ValueError, match="moe_latent_size"):
        decoder.DecoderConfig.from_dict({**NEMOTRON,
                                         "mlp_hidden_act": "silu"})


def test_prefill_and_decode_through_the_state_match_the_full_forward(
        nemotron):
    d, cfg, w = nemotron
    hists = _hists(np.random.default_rng(0), [20, 5, 32, 1, 17, 9])
    first, toks, scores, load = _generate(w, cfg, hists, 128)
    assert toks.shape == (6, STEPS)
    assert load[0].shape == (3, 16) and load[1].shape == (STEPS - 1, 3, 16)
    for r, h in enumerate(hists):
        logits = np.asarray(ref.forward(w, h + toks[r, :-1].tolist(), d)
                            )[len(h) - 1:]
        _close(first[r], logits[0])
        at = logits[np.arange(STEPS), toks[r]]
        spread = logits.std(axis=1)
        assert (np.abs(scores[r] - at) <= TOL * spread).all()
        assert (logits.max(axis=1) - at <= TOL * spread).all()  # greedy


@pytest.mark.parametrize("lengths,slots", [
    ([20, 5, 32, 1, 17, 9], 128), ([16, 16, 16, 16], 64),
    ([3, 1, 2, 1, 7, 1, 1, 4], 64), ([30, 31, 32, 29, 28, 32, 27, 32], 256),
], ids=["mixed", "a_row_a_chunk", "short_rows", "sorted_groups"])
def test_a_packed_ragged_batch_matches_the_reference(nemotron, lengths,
                                                     slots):
    """Rows that start mid-chunk, span chunks or are shorter than one;
    256 slots put the experts through the sorted grouped product, the
    smaller streams through the every-expert one."""
    d, cfg, w = nemotron
    hists = _hists(np.random.default_rng(4), lengths)
    first, _ = _prefill(w, cfg, hists, slots)
    for r, h in enumerate(hists):
        _close(np.asarray(first)[r], np.asarray(ref.forward(w, h, d))[-1])


@pytest.mark.parametrize("slots,beside", [(64, []), (128, [30, 32]),
                                          (128, [7, 1, 9])])
def test_the_stream_and_neighbours_do_not_move_a_row(nemotron, slots,
                                                     beside):
    d, cfg, w = nemotron
    rng = np.random.default_rng(6)
    mine = _hists(rng, [21])[0]
    alone, state = _prefill(w, cfg, [mine], 64, rows=1)
    hists = _hists(rng, beside) + [mine]
    got, other = _prefill(w, cfg, hists, slots)
    _close(np.asarray(got)[-1], np.asarray(alone)[0])
    for st, so in zip(state["layers"], other["layers"]):
        assert set(st) == set(so)
        for k in st:
            a, b = np.asarray(so[k])[-1], np.asarray(st[k])[0]
            assert np.abs(a - b).max() <= 1e-5 * max(np.abs(b).max(), 1e-9)


def test_a_layer_without_a_mixer_carries_no_state(nemotron):
    _, cfg, w = nemotron
    _, state = _prefill(w, cfg, _hists(np.random.default_rng(1), [9, 30]),
                        64)
    kinds = [sorted(st) for st in state["layers"]]
    assert kinds == [["ssm", "win"], [], ["ssm", "win"], ["k", "v"], [],
                     ["ssm", "win"], []]
    ssm = state["layers"][0]
    assert ssm["ssm"].shape == (2, 16, 8 * 16) \
        and ssm["ssm"].dtype == jnp.float32
    assert ssm["win"].shape == (2, 3, 128 + 2 * 2 * 16)
    assert state["layers"][3]["k"].shape == (2, 2, HISTORY + STEPS, 16)
    assert state["load"].shape == (3, 16)


def test_the_grouped_norm_is_each_groups_own(nemotron):
    """Scaling ONE group's channels of the scan's output leaves the
    other group's normalised channels where they were (a norm over all
    the inner channels would move them)."""
    _, cfg, w = nemotron
    lw = {**w["layers"][0], "w_out": jnp.eye(128, dtype=jnp.float32)}
    rng = np.random.default_rng(2)
    y, x, gate = (jnp.asarray(rng.normal(size=(5, 128)), jnp.float32)
                  for _ in range(3))
    a = np.asarray(decoder._mamba_out(lw, y, x, gate, cfg))
    scaled = gate.at[:, :64].multiply(3.0)
    b = np.asarray(decoder._mamba_out(lw, y, x, scaled, cfg))
    np.testing.assert_allclose(a[:, 64:], b[:, 64:], rtol=1e-6)
    assert np.abs(a[:, :64] - b[:, :64]).max() > 1e-3
    g = np.asarray((y + x) * jax.nn.silu(gate))  # D is 1
    want = g.reshape(5, 2, 64)
    want = want / np.sqrt((want ** 2).mean(-1, keepdims=True) + 1e-5)
    np.testing.assert_allclose(a, want.reshape(5, 128), rtol=2e-5,
                               atol=1e-6)


def test_four_shares_and_one_shared_expert_are_the_whole_layer(nemotron):
    """The share tied to the model: the latent sums of the four shares
    through ``W_up``, the shared expert counted once, add up to what the
    UNCUT reference gives for the whole ``E`` layer; and one share's
    layer through the program is that share's through the reference."""
    d, cfg, w = nemotron
    lw = w["layers"][1]
    z = jax.random.normal(jax.random.key(3), (40, cfg.hidden_size))
    valid = jnp.arange(40) % 7 != 0
    with jax.default_matmul_precision("highest"):
        whole = np.asarray(ref.expert_ff(lw, z, d)
                           + ref.relu2_ff(lw["s1"], lw["s2"], z))
        shared = np.asarray(ref.relu2_ff(lw["s1"], lw["s2"], z))
    total = np.zeros_like(whole)
    for held in SHARES:
        mine = {**lw, "w1": lw["w1"][jnp.asarray(held)],
                "w2": lw["w2"][jnp.asarray(held)]}
        cfg_s = decoder.DecoderConfig.from_dict(_share(held))
        out, load = decoder._feed_forward(mine, z, valid, cfg_s)
        assert load.shape == (16,)  # the router's, over all its experts
        with jax.default_matmul_precision("highest"):
            want = np.asarray(ref.expert_ff(mine, z, _share(held))) + shared
        np.testing.assert_allclose(
            np.asarray(out), np.where(np.asarray(valid)[:, None], want,
                                      shared), atol=2e-4)
        total += np.asarray(out) - shared  # r W_up alone
    live = np.asarray(valid)
    np.testing.assert_allclose((total + shared)[live], whole[live],
                               atol=4e-4)
    assert np.abs(whole - shared).max() > 0.1  # the experts add something


@pytest.mark.parametrize("tokens,top_k,held", [
    (3, 2, None), (6, 4, None), (24, 4, None), (200, 4, None),
    (6, 4, (1, 2, 5, 6, 9, 12, 13)), (40, 4, (0, 3, 8, 15)),
    (200, 4, (2, 3, 5, 7, 11, 13)), (2, 4, (6,)),
], ids=lambda v: "all" if v is None else str(v).replace(" ", ""))
def test_the_plain_expert_in_each_form_is_the_dense_sum(nemotron, tokens,
                                                        top_k, held):
    """``relu(u W1)^2 W2`` an expert, rows of the latent width, in the
    sorted grouped product, the every-expert product and the
    touched-experts kernel against each other and against the dense sum
    over the selected experts held, with assignments to absent experts
    and pad slots present."""
    _, cfg, w = nemotron
    lw = w["layers"][4]
    z = jax.random.normal(jax.random.key(tokens + top_k),
                          (tokens, cfg.hidden_size))
    u = jnp.dot(z, lw["w_down"], precision="highest")
    valid = jnp.arange(tokens) % 5 != 0
    sel, wts = moe.route(z, lw["gate"], lw["gate_bias"], top_k=top_k)
    idx = jnp.arange(16) if held is None else jnp.asarray(held)
    w1, w2 = lw["w1"][idx], lw["w2"][idx]
    local, n_held = moe.local_index(sel, 16, held)
    local = jnp.where(valid[:, None], local, n_held)
    few = moe._every_expert(u, local, wts, w1, None, w2)
    many = moe._sorted_groups(u, local, wts, w1, None, w2)
    touched = moe._touched_experts(u, local, wts, w1, None, w2)
    dense = np.zeros((tokens, 32))
    un, w1n, w2n = (np.asarray(a, np.float64) for a in (u, w1, w2))
    for t in range(tokens):
        for j in range(top_k):
            e = int(local[t, j])
            if e < n_held:
                dense[t] += float(wts[t, j]) * (
                    np.maximum(un[t] @ w1n[e], 0.0) ** 2 @ w2n[e])
    for got in (few, many, touched):
        assert got.shape == (tokens, 32) and got.dtype == jnp.float32
        np.testing.assert_allclose(np.asarray(got), dense, atol=2e-5)
        assert not np.asarray(got)[~np.asarray(valid)].any()
    got = moe.expert_product(u, sel, wts, w1, None, w2, n_experts=16,
                             held=held, valid=valid)
    form = moe.product_form(tokens, top_k, n_held, 16)
    np.testing.assert_array_equal(
        got, {moe.SORTED: many, moe.TOUCHED: touched, moe.EVERY: few}[form])


@pytest.mark.parametrize("tokens,top_k,n_held,n_experts,form", [
    (16, 22, 128, 512, moe.TOUCHED),   # the cell's step: 88 land here
    (16, 22, 128, None, moe.EVERY),    # every assignment here: 352 > 128
    (23, 22, 128, 512, moe.TOUCHED),   # 506 <= 512
    (24, 22, 128, 512, moe.EVERY),     # 528 > 512
    (32, 22, 128, 512, moe.EVERY), (16, 22, 512, 512, moe.TOUCHED),
    (16, 8, 256, 256, moe.TOUCHED), (64, 4, 8, 32, moe.EVERY),
    (64, 4, 8, 256, moe.TOUCHED), (129, 22, 128, 512, moe.SORTED),
])
def test_the_form_reckons_with_the_share(tokens, top_k, n_held, n_experts,
                                         form):
    """``T x k x E_held / E`` assignments can land on the held experts:
    that many, and not ``T x k``, against ``E_held``."""
    assert moe.product_form(tokens, top_k, n_held, n_experts) == form
    if n_experts in (None, n_held):  # no share: the rule it always was
        assert moe.product_form(tokens, top_k, n_held) == form


def test_the_kernels_tiles_count_the_matrices_an_expert_has():
    assert moe._f_tiles(1024, 2688, 2, 2) == 3   # 896 lanes a tile
    assert moe._f_tiles(1024, 2688, 2) == 3
    assert moe._f_tiles(2048, 512, 2, 2) == 1
    assert moe._f_tiles(2048, 1792, 2, 2) == 2 \
        and moe._f_tiles(2048, 1792, 2, 3) == 7


def test_the_benchmarks_copy_of_the_reference_is_the_same(nemotron):
    """``cellbench/reference_nemotron.py`` imports nothing of the
    program; it is held to this package's reference output for output,
    whole and under a share, and its ``state_bf16`` control to this
    package's ``round_state``."""
    copy = _benchmarks_copy("reference_nemotron")
    d, cfg, w = nemotron
    seq = _hists(np.random.default_rng(9), [29])[0]
    np.testing.assert_allclose(np.asarray(copy.forward(w, seq, d)),
                               np.asarray(ref.forward(w, seq, d)),
                               rtol=1e-5, atol=1e-6)
    held = SHARES[2]
    cut = {**w, "layers": [
        {**lw, "w1": lw["w1"][jnp.asarray(held)],
         "w2": lw["w2"][jnp.asarray(held)]} if "gate" in lw else lw
        for lw in w["layers"]]}
    a = np.asarray(copy.forward(cut, seq, _share(held)))
    np.testing.assert_allclose(
        a, np.asarray(ref.forward(cut, seq, _share(held))), rtol=1e-5,
        atol=1e-6)
    assert np.abs(a - np.asarray(copy.forward(w, seq, d))).max() > 1e-2
    z = jax.random.normal(jax.random.key(10), (29, cfg.hidden_size))
    lw = w["layers"][0]
    with jax.default_matmul_precision("highest"):
        sound = np.asarray(copy.mamba_op(lw, z, d))
        lossy = np.asarray(copy.mamba_op(lw, z, d, copy.round_bf16))
        np.testing.assert_allclose(lossy, np.asarray(
            ref.mamba_op(lw, z, d, round_state=copy.round_bf16)),
            rtol=1e-5, atol=1e-6)
    assert 1e-4 < np.abs(lossy - sound).max() / np.abs(sound).max() < 0.1
    with open(copy.__file__) as f:
        source = f.read()
    assert not re.search(r"^\s*(from|import) .*(predictionio_tpu|\.\.)",
                         source, re.M)


GOLDEN = os.path.join(os.path.dirname(os.path.abspath(golden.__file__)),
                      "decoder_logits.npz")


@pytest.fixture(scope="module")
def before():
    return dict(np.load(GOLDEN))


@pytest.fixture(scope="module")
def now():
    return golden.served()


@pytest.mark.parametrize("family", ["lfm2_moe", "laguna", "xing4_0",
                                    "granitemoehybrid"])
def test_the_four_older_families_serve_the_logits_they_served(
        family, before, now):
    """The programs of the four families stay the ones they were: their
    tiny presets' logits (a prefill, then 8 decode steps) are, bit for
    bit, what the commit before ``nemotron_h`` served
    (``tests/golden/decoder_logits.npz``, made there by
    ``make_decoder_logits.py``). Bit for bit on a machine whose CPU and
    XLA round the file's canary as the machine that made it did;
    elsewhere to 1e-6 of a logit's spread, which is all another
    rounding leaves to compare."""
    first, toks, scores = now[family]
    same = np.array_equal(golden.canary(), before["canary"])
    if same:
        np.testing.assert_array_equal(first, before[f"{family}.first"])
        np.testing.assert_array_equal(toks, before[f"{family}.tokens"])
        np.testing.assert_array_equal(scores, before[f"{family}.scores"])
        return
    want = before[f"{family}.first"]
    assert np.abs(first - want).max() <= 1e-6 * want.std() + 1e-7
