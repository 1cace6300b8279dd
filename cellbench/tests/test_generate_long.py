"""The ``laguna`` cell's own pieces: what its programs need
(``required_laguna.py``), the configuration against its source's widths,
its reference run in blocks against the plain forward pass, and a
rehearsal of the loop with ``correct`` shown to hold and shown to come
out false under each control (CPU, the rehearsal's size)."""

import json

import numpy as np
import pytest

from cellbench import control_laguna, manifest, required_laguna, run
from cellbench.loops import generate, generate_long

CELL = "laguna-l5.gen32-hist1k-closed48"


@pytest.fixture(scope="module")
def laguna():
    man = manifest.load()
    return manifest.read_json(
        f"{manifest.ROOT}/{manifest.config_of(man, manifest.cell(man, CELL))['file']}")


def test_the_configuration_is_the_published_one_cut_in_depth(laguna):
    man = manifest.load()
    entry = manifest.config_of(man, manifest.cell(man, CELL))
    assert entry["reduced"] == ["num_hidden_layers", "layer_types",
                                "mlp_layer_types",
                                "num_attention_heads_per_layer"]
    assert (laguna["hidden_size"], laguna["intermediate_size"],
            laguna["moe_intermediate_size"],
            laguna["shared_expert_intermediate_size"]) == (2048, 8192, 512,
                                                           512)
    assert (laguna["num_experts"], laguna["num_experts_per_tok"],
            laguna["vocab_size"], laguna["sliding_window"]) == (
        256, 8, 100352, 512)
    assert (laguna["num_key_value_heads"], laguna["head_dim"]) == (8, 128)
    assert laguna["num_hidden_layers"] == 5
    assert laguna["layer_types"] == ["full_attention"] \
        + ["sliding_attention"] * 3 + ["full_attention"]
    assert laguna["mlp_layer_types"] == ["dense"] + ["sparse"] * 4
    assert laguna["num_attention_heads_per_layer"] == [48, 64, 64, 64, 48]
    assert laguna["moe_routed_scaling_factor"] == 2.5
    assert laguna["tie_word_embeddings"] is False and laguna["gating"]
    full = laguna["rope_parameters"]["full_attention"]
    assert (full["rope_type"], full["factor"], full["beta_fast"],
            full["partial_rotary_factor"]) == ("yarn", 64, 64, 0.5)
    for key in ("published", "deployment", "assumed", "precision"):
        assert laguna[key]
    for key in ("head_gate", "qk_norm", "routing"):
        assert laguna["assumed"][key]


def test_required_work_is_the_issues_arithmetic(laguna):
    model = generate.model_keys(laguna)
    s = required_laguna._sizes(model)
    assert s["expert"] == 3 * 2048 * 512            # 3.15 M
    assert s["n_expert_layers"] == 4
    assert s["full_heads"] == [48, 48] and s["window_heads"] == [64] * 3
    # a sliding layer outside its routed experts: 41.6 M
    sliding = 2 * 2048 * 64 * 128 + 2 * 2048 * 8 * 128 + 2048 * 64 \
        + 3 * 2048 * 512 + 2048 * 256
    assert sliding == pytest.approx(41.6e6, rel=0.005)
    params = s["outside"] + 4 * 256 * s["expert"] + 2 * s["head"]
    assert params == pytest.approx(3869.8e6, rel=0.001)   # 7.74 GB
    # matrices a token: 677 MFLOP
    active = s["outside"] + 4 * 8 * s["expert"]
    assert 2 * active == pytest.approx(677e6, rel=0.01)
    assert required_laguna.window_pairs(100, 512) == 100 * 101 / 2
    assert required_laguna.window_pairs(4096, 512) == \
        512 * 513 / 2 + (4096 - 512) * 512
    attn = required_laguna.attn_prefill(model, pairs_full=1e6,
                                        pairs_window=1e5, tokens=1000)
    assert attn["ops"] == 4 * 128 * (96 * 1e6 + 192 * 1e5)
    step = required_laguna.gen_decode(
        model, rows=16, steps=1, experts_touched=256, cache_full=1349,
        cache_window=512)
    # every weight but the embedding once, and 16 rows' state
    state = 16 * (2 * 1349 + 3 * 512) * 2 * 8 * 128 * 2
    assert step["bytes"] == pytest.approx(7.74e9 - 0.411e9 + state,
                                          rel=0.002)
    fewer = required_laguna.gen_decode(
        model, rows=16, steps=1, experts_touched=100, cache_full=1349,
        cache_window=512)
    assert step["bytes"] - fewer["bytes"] == 4 * 156 * s["expert"] * 2
    pre = required_laguna.gen_prefill(model, rows=16, tokens=21300,
                                      pairs_full=0, pairs_window=0)
    assert pre["ops"] == pytest.approx(21300 * 677e6, rel=0.01)
    assert pre["bytes"] == pytest.approx(7.74e9 - 0.411e9
                                         + 5 * 21300 * 4096, rel=0.002)


def test_history_lengths_are_the_issues_distribution():
    tr = manifest.read_json(manifest.traffic_path("gen32-hist1k-closed48"))
    a = generate.history_lengths(tr, 2552)
    assert a.min() >= 64 and a.max() <= 4096
    assert 960 <= np.median(a) <= 1090 and 1250 <= a.mean() <= 1420
    assert 0.76 <= (a > 512).mean() <= 0.86
    np.testing.assert_array_equal(a, generate.history_lengths(tr, 2552))
    assert (tr["generators"] * tr["connections"], tr["num"]) == (48, 32)
    assert tr["warm_shapes"] == [[16, 1024], [16, 2048], [16, 4096]]


def test_the_checks_reference_is_the_plain_forward_pass(laguna,
                                                        monkeypatch):
    """``_reference_gaps`` takes operators a padded sequence at a time
    with the queries in blocks and feed-forwards by blocks of real
    tokens; what it returns is the one-sequence forward pass's, sequence
    by sequence; under ``no_window`` it is another model's."""
    import jax
    import jax.numpy as jnp

    from cellbench import reference_laguna as ref
    from predictionio_tpu.models import decoder

    tr = manifest.read_json(manifest.traffic_path("gen32-hist1k-closed48"))
    cell = generate_long.Cell.__new__(generate_long.Cell)
    cell.config = {**laguna, **laguna["rehearse"]}
    cell.traffic = {**tr, **tr["rehearse"]}
    cell.model = generate.model_keys(cell.config)
    cfg = decoder.DecoderConfig.from_dict(cell.model)
    cell.weights = decoder.init_weights(jax.random.key(5), cfg,
                                        cell.config["init"])
    monkeypatch.setattr(generate_long, "BLOCK", 32)   # several blocks
    rng = np.random.default_rng(3)
    n = int(cell.traffic["num"])
    lengths = [9, 64, 17, 33, 12, 71, 26]  # padded to 24, 40, 72
    seqs = [rng.integers(0, cfg.vocab_size, k).tolist() for k in lengths]
    firsts = [k - n for k in lengths]
    served = [(rng.integers(0, cfg.vocab_size, n),
               rng.normal(size=n).astype(np.float32)) for _ in seqs]
    got = cell._reference_gaps(*cell._reference_under(None), seqs, firsts,
                               served)
    other = cell._reference_gaps(*cell._reference_under("no_window"), seqs,
                                 firsts, served)
    w32 = jax.tree_util.tree_map(lambda a: a.astype(jnp.float32),
                                 cell.weights)
    for k, seq, first, (toks, scores), (s, r), (s2, _) in zip(
            lengths, seqs, firsts, served, got, other):
        logits = ref.forward(w32, jnp.asarray(seq), cell.model)
        want_s, want_r = ref.served_gaps(logits[first:first + n], toks,
                                         scores)
        np.testing.assert_allclose(s, want_s, rtol=1e-4, atol=1e-5)
        np.testing.assert_allclose(r, want_r, rtol=1e-4, atol=1e-5)
        # a sequence inside one window sees every key either way
        assert (np.abs(s2 - s).max() > 1e-3) == (k > 8)


@pytest.mark.parametrize("control,lossy", [
    ("int8_experts", {"w1", "w3", "w2", "s1", "s3", "s2"}),
    ("int8_routed", {"w1", "w3", "w2"})])
def test_an_int8_control_rounds_the_experts_it_names(laguna, control, lossy):
    """Expert layers only, and every other leaf widened untouched."""
    import jax
    import jax.numpy as jnp

    from predictionio_tpu.models import decoder

    cell = generate_long.Cell.__new__(generate_long.Cell)
    cell.model = generate.model_keys({**laguna, **laguna["rehearse"]})
    cfg = decoder.DecoderConfig.from_dict(cell.model)
    layers = decoder.init_weights(jax.random.key(5), cfg,
                                  laguna["init"])["layers"]
    _, (widen, attention) = cell._reference_under(control)
    assert attention == {} and control in (control_laguna.CONTROLS
                                           + control_laguna.PROBES)
    for lw in layers:
        changed = {k for k, v in widen(lw).items()
                   if not np.array_equal(v, lw[k].astype(jnp.float32))}
        assert changed == (lossy if "gate" in lw else set())


def _run(capsys, *extra, main=run.main):
    rc = main(["--workload", CELL, "--seed", "2147483659",
               "--seconds", "3", "--rehearse", *extra])
    assert rc == 0
    lines = [ln for ln in capsys.readouterr().out.splitlines() if ln]
    tagged = {}
    for ln in lines[:-1]:
        tag, _, rest = ln.partition(" ")
        tagged.setdefault(tag, []).append(json.loads(rest))
    checks = {c["name"]: c for c in tagged.get("check", ())}
    return json.loads(lines[-1]), tagged, checks


def test_rehearsal_is_correct(capsys):
    result, tagged, checks = _run(capsys, "--trace", "0")
    assert result["correct"] is True and result["rehearsal"] is True
    assert result["failed"] == 0 and result["attempted"] > 0
    assert set(checks) == {"score_gap_max", "rank_gap_max",
                           "score_gap_p50", "answers_not_compared",
                           "failed_requests", "compiles_in_window"}
    for name in ("answers_not_compared", "failed_requests",
                 "compiles_in_window"):
        assert checks[name]["value"] == 0
    assert tagged["check_detail"][0]["control"] is None


@pytest.mark.parametrize("control", control_laguna.CONTROLS)
def test_a_control_comes_out_not_correct(capsys, monkeypatch, control):
    """The whole run under ``control_laguna``: the same server, traffic
    and limits, the reference one step below the configuration
    (``int8_experts``) or without the window (``no_window``)."""
    monkeypatch.setattr(manifest, "read_json", manifest.read_json)
    result, tagged, checks = _run(
        capsys, "--trace", "0",
        main=lambda argv: control_laguna.main(["--control", control,
                                               *argv]))
    assert tagged["check_detail"][0]["control"] == control
    assert result["correct"] is False and result["failed"] == 0
    assert not checks["score_gap_p50"]["ok"]
    for name in ("answers_not_compared", "failed_requests",
                 "compiles_in_window"):
        assert checks[name]["ok"]


def test_traced_rehearsal_reads_the_counters_and_the_sizes(capsys):
    result, tagged, _ = _run(capsys, "--trace", "1")
    read = tagged["rehearsal_values_not_device_metrics"][0]
    for name in ("moe_experts_touched", "moe_load_imbalance",
                 "gen_pad_pct", "gen_state_gb", "batch_occupancy.sat",
                 "server_latency_mean_ms.gen", "queue_wait_mean_ms.gen",
                 "device_wait_mean_ms.gen", "client_overhead_mean_ms.gen",
                 "host_starved_pct.sat", "http_overhead_ms.sat"):
        assert name in read, name
    assert 1 <= read["moe_experts_touched"]["value"] <= 8
    # bfloat16 at the rehearsal's size: 8 rows, 2 key-value heads of 16,
    # keys and values: two full layers at 64 + 8 slots, three rings of 8
    assert read["gen_state_gb"]["value"] == pytest.approx(
        8 * 2 * 16 * 2 * 2 * (2 * 72 + 3 * 8) * 1e-9)
    assert not any(name.startswith("topk_") for name in read)
    shapes = tagged["shapes"][0]
    assert shapes["gen_decode.laguna"]["steps"] == 7
    assert shapes["gen_decode.laguna"]["cache_window"] <= 8
    assert shapes["attn_prefill.laguna"]["pairs_window"] \
        < shapes["attn_prefill.laguna"]["pairs_full"]
