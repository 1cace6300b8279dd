"""The ``xing4_0`` cell's own pieces: what its programs need
(``required_xing.py``), the configuration against its source's widths,
its reference run in chunks and blocks against the plain forward pass,
and a rehearsal of the loop with ``correct`` shown to hold and shown to
come out false under each control (CPU, the rehearsal's size)."""

import json

import numpy as np
import pytest

from cellbench import control_xing, manifest, required_xing, run
from cellbench.loops import generate, generate_latent

CELL = "xing4-l6.gen32-hist2k-closed12"
CATALOG = "/opt/skills/guides/model-configs/architectures.jsonl"


@pytest.fixture(scope="module")
def xing():
    man = manifest.load()
    return manifest.read_json(
        f"{manifest.ROOT}/{manifest.config_of(man, manifest.cell(man, CELL))['file']}")


def test_the_configuration_is_the_published_one_cut_in_depth(xing):
    man = manifest.load()
    entry = manifest.config_of(man, manifest.cell(man, CELL))
    assert entry["reduced"] == xing["reduced"] == ["num_hidden_layers"]
    assert manifest.cell(man, CELL)["chips"] == 1
    assert (xing["hidden_size"], xing["intermediate_size"],
            xing["moe_intermediate_size"], xing["vocab_size"]) == (
        3584, 9216, 1024, 131072)
    assert (xing["q_lora_rank"], xing["kv_lora_rank"],
            xing["qk_nope_head_dim"], xing["qk_rope_head_dim"],
            xing["v_head_dim"], xing["num_attention_heads"]) == (
        768, 512, 128, 64, 128, 32)
    assert (xing["n_routed_experts"], xing["num_experts_per_tok"],
            xing["n_shared_experts"], xing["routed_scaling_factor"],
            xing["first_k_dense_replace"]) == (64, 4, 1, 2, 2)
    assert (xing["hc_mult"], xing["hc_sinkhorn_iters"], xing["hc_eps"],
            xing["mhc_h_res_clamp_min"], xing["mhc_h_res_clamp_max"]) == (
        4, 20, 1e-6, -30, 30)
    assert xing["num_hidden_layers"] == 6
    assert xing["published"]["num_hidden_layers"] == 40
    assert xing["tie_word_embeddings"] is False
    assert xing["rope_scaling"] == {
        "beta_fast": 32, "beta_slow": 1, "factor": 64, "mscale": 1,
        "mscale_all_dim": 1, "original_max_position_embeddings": 4096,
        "type": "yarn"}
    for key in ("source", "published", "deployment", "assumed",
                "not_served", "precision", "init"):
        assert xing[key]
    assert "num_nextn_predict_layers" in xing["not_served"]
    for key in ("residual_entry_and_exit", "stream_norm", "sinkhorn",
                "coefficients", "hyper_connection_weights", "rope",
                "routing", "weights"):
        assert xing["assumed"][key]
    assert xing["server"] == {"batching": True, "max_batch": 4}
    assert xing["engine"] == {"row_buckets": [4], "history_buckets": [
        1024, 2048, 3072, 4096]}


def test_every_number_of_the_catalog_row_is_in_the_file(xing):
    try:
        with open(CATALOG) as f:
            rows = [json.loads(line) for line in f]
    except OSError:
        pytest.skip("no catalog beside the model-configs guide here")
    row, = [r for r in rows if r["name"] == "Xing4.0-29B-A4B"]
    assert row["source_url"] in xing["source"] or \
        row["source_url"].split("://")[1] in xing["source"]
    differs = {k for k, v in row["config"].items() if xing.get(k) != v}
    assert differs == {"num_hidden_layers"}


def test_required_work_is_the_issues_arithmetic(xing):
    model = generate.model_keys(xing)
    s = required_xing._sizes(model)
    attn = 3584 * 768 + 768 * 6144 + 3584 * 576 + 512 * 8192 + 4096 * 3584
    assert attn == pytest.approx(28.41e6, rel=0.001)       # without norms
    assert s["expert"] == 3 * 3584 * 1024                  # 11.01 M
    assert (s["n_expert_layers"], s["n_experts"], s["k"]) == (4, 64, 4)
    # the coefficients' projections: 2 sub-blocks x 4 H x 24, float32
    assert s["hc"] == 6 * 2 * 4 * 3584 * 24
    assert s["hc"] / 6 == pytest.approx(0.69e6, rel=0.01)
    assert (s["pair_ops"], s["cached_ops"], s["latent"]) == (
        2 * 192 + 2 * 128, 2 * 576 + 2 * 512, 576)
    # the whole cut: 4.176 B parameters, 8.35 GB in bfloat16
    params = s["outside"] + s["hc"] + 4 * 64 * s["expert"] + 2 * s["head"]
    assert params == pytest.approx(4.176e9, rel=0.001)
    # one token's matrices: 2 dense layers, 4 x (4 + 1) experts, attention
    active = s["outside"] + 4 * 4 * s["expert"]
    assert active == 6 * attn + 2 * 3 * 3584 * 9216 \
        + 4 * (3584 * 64 + s["expert"]) + 16 * s["expert"]
    pairs = required_xing.attn_prefill(model, pairs=1e6, tokens=1000)
    assert pairs["ops"] == 6 * 32 * 640 * 1e6
    assert pairs["bytes"] == 1000 * 6 * 32 * 2 * 320 * 2
    step = required_xing.gen_decode(model, rows=4, steps=1,
                                    experts_touched=64, cache=2000)
    # every weight but the embedding once (the coefficients in float32),
    # and 4 rows' latents: 1,152 bytes a token a layer
    state = 4 * 6 * 2000 * 1152
    assert step["bytes"] == pytest.approx(
        8.35e9 - 0.94e9 + s["hc"] * 2 + state, rel=0.002)
    fewer = required_xing.gen_decode(model, rows=4, steps=1,
                                     experts_touched=14, cache=2000)
    assert step["bytes"] - fewer["bytes"] == 4 * 50 * s["expert"] * 2
    pre = required_xing.gen_prefill(model, rows=4, tokens=9400, pairs=0)
    assert pre["ops"] == pytest.approx(
        9400 * (2 * (active + s["hc"]) + s["mix_ops"]) + 8 * s["head"],
        rel=1e-9)
    # the four float32 streams once in and once out of 12 sub-blocks
    streams = 12 * 2 * 9400 * 4 * 3584 * 4
    assert pre["bytes"] == pytest.approx(
        8.35e9 - 0.94e9 + s["hc"] * 2 + 6 * 9400 * 1152 + streams,
        rel=0.002)


def test_history_lengths_are_the_issues_distribution():
    tr = manifest.read_json(manifest.traffic_path("gen32-hist2k-closed12"))
    a = generate.history_lengths(tr, 920)
    assert a.min() >= 256 and a.max() <= 4096
    assert 1900 <= np.median(a) <= 2200 and 2100 <= a.mean() <= 2500
    assert 0.08 <= (a == 4096).mean() <= 0.17     # about an eighth
    np.testing.assert_array_equal(a, generate.history_lengths(tr, 920))
    assert (tr["generators"] * tr["connections"], tr["num"],
            tr["check_sample"]) == (12, 32, 12)
    assert tr["warm_shapes"] == [[4, 1024], [4, 2048], [4, 3072], [4, 4096]]
    assert tr["loop"] == "generate_latent" and tr["zipf"] == 1.1


def _cell(xing):
    import jax

    from predictionio_tpu.models import decoder

    tr = manifest.read_json(manifest.traffic_path("gen32-hist2k-closed12"))
    cell = generate_latent.Cell.__new__(generate_latent.Cell)
    cell.config = {**xing, **xing["rehearse"]}
    cell.traffic = {**tr, **tr["rehearse"]}
    cell.model = generate.model_keys(cell.config)
    cfg = decoder.DecoderConfig.from_dict(cell.model)
    cell.weights = decoder.init_weights(jax.random.key(5), cfg,
                                        cell.config["init"])
    return cell, cfg


def test_the_checks_reference_is_the_plain_forward_pass(xing, monkeypatch):
    """``_reference_gaps`` takes ``chunk`` sequences' streams at a time,
    attention a padded sequence at a time with the queries in blocks and
    feed-forwards by blocks of real tokens; what it returns is the
    one-sequence forward pass's, sequence by sequence; under
    ``sinkhorn_1`` it is another model's."""
    import jax
    import jax.numpy as jnp

    from cellbench import reference_xing as ref

    cell, cfg = _cell(xing)
    monkeypatch.setattr(generate_latent, "BLOCK", 32)   # several blocks
    rng = np.random.default_rng(3)
    n = int(cell.traffic["num"])
    lengths = [9, 64, 17, 33, 12, 71, 26]   # a chunk of 5, then one of 2
    seqs = [rng.integers(0, cfg.vocab_size, k).tolist() for k in lengths]
    firsts = [k - n for k in lengths]
    served = [(rng.integers(0, cfg.vocab_size, n),
               rng.normal(size=n).astype(np.float32)) for _ in seqs]
    got = cell._reference_gaps(*cell._reference_under(None), seqs, firsts,
                               served)
    other = cell._reference_gaps(*cell._reference_under("sinkhorn_1"),
                                 seqs, firsts, served)
    assert len(got) == len(other) == len(seqs)
    w32 = jax.tree_util.tree_map(lambda a: a.astype(jnp.float32),
                                 cell.weights)
    for seq, first, (toks, scores), (s, r), (s2, _) in zip(
            seqs, firsts, served, got, other):
        logits = ref.forward(w32, jnp.asarray(seq), cell.model)
        want_s, want_r = ref.served_gaps(logits[first:first + n], toks,
                                         scores)
        np.testing.assert_allclose(s, want_s, rtol=1e-4, atol=1e-5)
        np.testing.assert_allclose(r, want_r, rtol=1e-4, atol=1e-5)
        assert np.abs(s2 - s).max() > 1e-3


def test_the_int8_control_rounds_the_leaves_it_names(xing):
    """The latent up-projections everywhere, a dense layer's
    feed-forward, an expert layer's shared expert (its routed experts
    left sound); every other leaf widened untouched."""
    import jax.numpy as jnp

    cell, _ = _cell(xing)
    _, (widen, sub) = cell._reference_under("int8_weights")
    assert sub == {}
    assert cell._reference_under("sinkhorn_1")[1][1] == {
        "sinkhorn_iters": 1}
    with pytest.raises(ValueError):
        cell._reference_under("no_such_control")
    for lw in cell.weights["layers"]:
        changed = {k for k, v in widen(lw).items()
                   if not np.array_equal(v, lw[k].astype(jnp.float32))}
        assert changed == {"w_qb", "w_kvb"} | (
            {"s1", "s3", "s2"} if "gate" in lw else {"w1", "w3", "w2"})


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


@pytest.mark.parametrize("control", control_xing.CONTROLS)
def test_a_control_comes_out_not_correct(capsys, monkeypatch, control):
    """The whole run under ``control_xing``: the same server, traffic
    and limits, the reference one precision below the configuration
    (``int8_weights``) or with one Sinkhorn pass (``sinkhorn_1``)."""
    monkeypatch.setattr(manifest, "read_json", manifest.read_json)
    result, tagged, checks = _run(
        capsys, "--trace", "0",
        main=lambda argv: control_xing.main(["--control", control, *argv]))
    assert tagged["check_detail"][0]["control"] == control
    assert result["correct"] is False and result["failed"] == 0
    assert not checks["score_gap_p50"]["ok"]
    for name in ("answers_not_compared", "failed_requests",
                 "compiles_in_window"):
        assert checks[name]["ok"]


def test_traced_rehearsal_reads_the_counters_and_the_sizes(capsys):
    result, tagged, _ = _run(capsys, "--trace", "1")
    read = tagged["rehearsal_values_not_device_metrics"][0]
    for name in ("moe_experts_touched", "moe_experts_read",
                 "moe_load_imbalance", "gen_pad_pct", "gen_state_gb",
                 "mhc_sinkhorn_gap", "batch_occupancy.sat",
                 "server_latency_mean_ms.gen", "queue_wait_mean_ms.gen",
                 "device_wait_mean_ms.gen", "client_overhead_mean_ms.gen",
                 "host_starved_pct.sat", "http_overhead_ms.sat"):
        assert name in read, name
    assert 1 <= read["moe_experts_touched"]["value"] <= 8
    # twenty passes leave float32's rounding to a few 1e-4; one leaves
    # a hundred times that (tests/test_decoder_xing.py)
    assert 0 < read["mhc_sinkhorn_gap"]["value"] < 5e-3
    # bfloat16 at the rehearsal's size: 4 rows, 6 layers, 64 + 8 slots,
    # a latent of 16 beside a rotated key of 8
    assert read["gen_state_gb"]["value"] == pytest.approx(
        4 * 6 * 72 * 24 * 2 * 1e-9)
    assert not any(name.startswith("topk_") for name in read)
    shapes = tagged["shapes"][0]
    assert shapes["gen_decode.xing"]["steps"] == 7
    assert shapes["attn_prefill.xing"]["pairs"] \
        > shapes["attn_prefill.xing"]["tokens"]
    assert shapes["gen_prefill.xing"] == {
        "rows": shapes["gen_decode.xing"]["rows"],
        **shapes["attn_prefill.xing"]}
