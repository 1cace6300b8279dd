"""The ``granitemoehybrid`` cell's own pieces: what its programs need
(``required_granite.py``, hand-counted), the configuration against the
catalog's row, its reference run in chunks and blocks against the plain
forward pass, and a rehearsal of the loop with ``correct`` shown to hold
and shown to come out false under each control (CPU, the rehearsal's
size)."""

import json

import numpy as np
import pytest

from cellbench import control_granite, manifest, required_granite, run
from cellbench.loops import generate, generate_ssm

CELL = "granite-h-micro.gen32-hist192-closed48"
CATALOG = "/opt/skills/guides/model-configs/architectures.jsonl"


@pytest.fixture(scope="module")
def granite():
    man = manifest.load()
    return manifest.read_json(
        f"{manifest.ROOT}/{manifest.config_of(man, manifest.cell(man, CELL))['file']}")


def test_the_configuration_is_the_published_one_whole(granite):
    man = manifest.load()
    entry = manifest.config_of(man, manifest.cell(man, CELL))
    assert entry["reduced"] == granite["reduced"] == []
    assert manifest.cell(man, CELL)["chips"] == 1
    assert all(w["chips"] == 1 for w in man["workloads"])
    assert len(man["workloads"]) == 6
    assert granite["num_hidden_layers"] == 40 \
        == granite["published"]["num_hidden_layers"]
    kinds = granite["layer_types"]
    assert [l for l, k in enumerate(kinds) if k == "attention"] \
        == [5, 15, 25, 35] and kinds.count("mamba") == 36
    assert (granite["hidden_size"], granite["shared_intermediate_size"],
            granite["vocab_size"], granite["num_local_experts"]) == (
        2048, 8192, 100352, 0)
    assert (granite["mamba_n_heads"], granite["mamba_d_head"],
            granite["mamba_d_state"], granite["mamba_d_conv"],
            granite["mamba_chunk_size"], granite["mamba_n_groups"]) == (
        64, 64, 128, 4, 256, 1)
    assert (granite["embedding_multiplier"], granite["residual_multiplier"],
            granite["attention_multiplier"], granite["logits_scaling"],
            granite["position_embedding_type"]) == (
        12, 0.22, 0.015625, 8, "nope")
    for key in ("source", "published", "deployment", "assumed",
                "precision", "init", "check"):
        assert granite[key]
    for key in ("head_dim", "dt_limits", "gated_norm",
                "state_space_precision", "weights"):
        assert granite["assumed"][key]
    assert granite["server"] == {"batching": True, "max_batch": 16}
    # the issue's four rungs and three between 256 and 512, where the
    # batches' mean histories fall (PERF.md finding 43.3)
    assert granite["engine"] == {"row_buckets": [16], "history_buckets": [
        128, 256, 320, 384, 448, 512, 1024]}


def test_every_number_of_the_catalog_row_is_in_the_file(granite):
    try:
        with open(CATALOG) as f:
            rows = [json.loads(line) for line in f]
    except OSError:
        pytest.skip("no catalog beside the model-configs guide here")
    row, = [r for r in rows if r["name"] == "granite-4.0-h-micro"]
    assert row["source_url"] in granite["source"]
    assert {k for k, v in row["config"].items() if granite.get(k) != v} \
        == set()


def test_required_work_is_the_issues_arithmetic(granite):
    model = generate.model_keys(granite)
    s = required_granite._sizes(model)
    assert (s["n_mamba"], s["n_attn"], s["I"], s["N"]) == (36, 4, 4096, 128)
    # a mixer 25.8 M with its vectors, an attention mixer 10.5 M, a
    # feed-forward 50.3 M, the tied embedding 205.5 M: 3.19 B, 6.38 GB
    assert s["mamba"] == 2048 * 8512 + 4096 * 2048
    assert s["mamba"] + (4352 * 5 + 3 * 64 + 4096) \
        == pytest.approx(25.8e6, rel=0.002)
    assert s["attn"] == pytest.approx(10.5e6, rel=0.002)
    assert s["ff"] == 3 * 2048 * 8192
    params = s["matrices"] + s["vectors"] + s["head"]
    assert params == pytest.approx(3.19e9, rel=0.002)
    # a decode step of 16 rows: every weight once, 2 x 75.5 MB a row of
    # state (36 layers x 2 MiB, read and written)
    state = 36 * 128 * 4096 * 4
    assert state == pytest.approx(75.5e6, rel=0.001)
    step = required_granite.ssm_step(model, rows=16, steps=1)
    assert step["bytes"] == 16 * 2 * state
    assert step["bytes"] == pytest.approx(2.4e9, rel=0.01)
    assert step["ops"] == 16 * 36 * 5 * 128 * 4096
    one = required_granite.gen_decode(model, rows=16, steps=1, cache=300)
    windows = 16 * 36 * 2 * 3 * 4352 * 4
    caches = 16 * 4 * 300 * 2 * 8 * 64 * 2
    assert one["bytes"] == pytest.approx(
        2 * (s["matrices"] + s["head"]) + 4 * s["vectors"] + step["bytes"]
        + windows + caches, rel=1e-12)
    assert one["bytes"] == pytest.approx(8.9e9, rel=0.01)  # the issue's 8.9 GB
    assert required_granite.gen_decode(model, 16, 31, 300)["bytes"] \
        == pytest.approx(31 * one["bytes"])


def test_the_scans_work_is_hand_counted_at_a_tiny_size():
    """Two heads of 4 over a state of 3, chunks of 4, one state-space
    layer: a row of 6 tokens (chunks of 4 and 2: 10 + 3 same-row causal
    pairs) and a row of 3 (6 pairs)."""
    tiny = {"hidden_size": 8, "num_attention_heads": 2,
            "num_key_value_heads": 1, "mamba_n_heads": 2, "mamba_d_head": 4,
            "mamba_d_state": 3, "mamba_d_conv": 4, "mamba_chunk_size": 4,
            "layer_types": ["mamba", "attention"], "num_hidden_layers": 2,
            "shared_intermediate_size": 16, "vocab_size": 32}
    assert required_granite.chunk_pairs(6, 4) == 10 + 3
    assert required_granite.chunk_pairs(3, 4) == 6
    assert required_granite.chunk_pairs(256, 256) == 256 * 257 // 2
    assert required_granite.chunk_pairs(600, 256) \
        == 2 * (256 * 257 // 2) + 88 * 89 // 2
    need = required_granite.ssm_scan(tiny, rows=2, tokens=9, scan_pairs=19)
    # a pair: C . B once (2 x 3) and 2 x 4 a head of 2; a token: C S and
    # the state's update, 2 x 3 x 8 each
    assert need["ops"] == 19 * (6 + 16) + 9 * (48 + 48)
    # x and y 8 wide, B and C 3 wide at 2 bytes, dt 2 heads at 4; a
    # row's state 3 x 8 float32
    assert need["bytes"] == 9 * ((16 + 6) * 2 + 8) + 2 * 24 * 4
    step = required_granite.ssm_step(tiny, rows=2, steps=3)
    assert (step["bytes"], step["ops"]) == (3 * 2 * 2 * 24 * 4,
                                            3 * 2 * 5 * 24)
    s = required_granite._sizes(tiny)
    assert s["mamba"] == 8 * (16 + 6 + 2) + 8 * 8
    assert s["attn"] == 8 * 8 + 2 * 8 * 4 + 8 * 8 and s["ff"] == 3 * 8 * 16
    pre = required_granite.gen_prefill(tiny, rows=2, tokens=9, pairs=27,
                                       scan_pairs=19)
    assert pre["ops"] == 2 * 9 * s["matrices"] + 2 * 2 * 32 * 8 \
        + 2 * 4 * 4 * 27 + need["ops"]
    with pytest.raises(ValueError):
        required_granite._sizes({**tiny, "layer_types": ["conv", "mamba"]})


def test_history_lengths_are_the_issues_distribution():
    tr = manifest.read_json(manifest.traffic_path("gen32-hist192-closed48"))
    a = generate.history_lengths(tr, 3000)
    assert a.min() >= 32 and a.max() <= 1024
    assert 175 <= np.median(a) <= 210 and 230 <= a.mean() <= 290
    np.testing.assert_array_equal(a, generate.history_lengths(tr, 3000))
    assert (tr["generators"] * tr["connections"], tr["num"],
            tr["check_sample"]) == (48, 32, 16)
    assert tr["warm_shapes"] == [[16, b] for b in (
        128, 256, 320, 384, 448, 512, 1024)]
    assert tr["loop"] == "generate_ssm" and tr["zipf"] == 1.1
    # about a third of the rows cross a chunk's edge wherever they start
    assert 0.25 <= (a > 256).mean() <= 0.45


def _cell(granite):
    import jax

    from predictionio_tpu.models import decoder

    tr = manifest.read_json(manifest.traffic_path("gen32-hist192-closed48"))
    cell = generate_ssm.Cell.__new__(generate_ssm.Cell)
    cell.config = {**granite, **granite["rehearse"]}
    cell.traffic = {**tr, **tr["rehearse"]}
    cell.model = generate.model_keys(cell.config)
    cfg = decoder.DecoderConfig.from_dict(cell.model)
    cell.weights = decoder.init_weights(jax.random.key(5), cfg,
                                        cell.config["init"])
    return cell, cfg


def test_the_checks_reference_is_the_plain_forward_pass(granite,
                                                        monkeypatch):
    """``_reference_gaps`` takes ``chunk`` padded sequences an operator
    call and the feed-forwards by blocks of real tokens; what it returns
    is the one-sequence forward pass's, sequence by sequence; under
    ``state_bf16`` it is another computation's."""
    import jax
    import jax.numpy as jnp

    from cellbench import reference_granite as ref

    cell, cfg = _cell(granite)
    monkeypatch.setattr(generate_ssm, "BLOCK", 32)   # several blocks
    rng = np.random.default_rng(3)
    n = int(cell.traffic["num"])
    lengths = [9, 64, 17, 33, 12, 71, 26]   # a chunk of 5, then one of 2
    seqs = [rng.integers(0, cfg.vocab_size, k).tolist() for k in lengths]
    firsts = [k - n for k in lengths]
    served = [(rng.integers(0, cfg.vocab_size, n),
               rng.normal(size=n).astype(np.float32) * 1e-3) for _ in seqs]
    got = cell._reference_gaps(*cell._reference_under(None), seqs, firsts,
                               served)
    other = cell._reference_gaps(*cell._reference_under("state_bf16"),
                                 seqs, firsts, served)
    assert len(got) == len(other) == len(seqs)
    w32 = jax.tree_util.tree_map(lambda a: a.astype(jnp.float32),
                                 cell.weights)
    for seq, first, (toks, scores), (s, r), (s2, _) in zip(
            seqs, firsts, served, got, other):
        logits = ref.forward(w32, jnp.asarray(seq), cell.model)
        want_s, want_r = ref.served_gaps(logits[first:first + n], toks,
                                         scores)
        np.testing.assert_allclose(s, want_s, rtol=1e-3, atol=1e-4)
        np.testing.assert_allclose(r, want_r, rtol=1e-3, atol=1e-4)
        assert np.abs(s2 - s).max() > 1e-3


def test_the_int8_control_rounds_the_leaves_it_names(granite):
    import jax.numpy as jnp

    cell, _ = _cell(granite)
    _, (widen, round_state) = cell._reference_under("int8_weights")
    assert round_state is None
    assert cell._reference_under("state_bf16")[1][1] is not None
    with pytest.raises(ValueError):
        cell._reference_under("no_such_control")
    for kind, lw in zip(cell.model["layer_types"], cell.weights["layers"]):
        changed = {k for k, v in widen(lw).items()
                   if not np.array_equal(v, lw[k].astype(jnp.float32))}
        assert changed == {"w1", "w3", "w2"} | (
            {"w_in", "w_out"} if kind == "mamba" else set())


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


def test_the_int8_control_comes_out_not_correct(capsys, monkeypatch):
    """The whole run under ``control_granite``: the same server, traffic
    and limits, the reference's projections through int8."""
    monkeypatch.setattr(manifest, "read_json", manifest.read_json)
    result, tagged, checks = _run(
        capsys, "--trace", "0",
        main=lambda argv: control_granite.main(
            ["--control", "int8_weights", *argv]))
    assert tagged["check_detail"][0]["control"] == "int8_weights"
    assert result["correct"] is False and result["failed"] == 0
    assert not checks["score_gap_p50"]["ok"]
    for name in ("answers_not_compared", "failed_requests",
                 "compiles_in_window"):
        assert checks[name]["ok"]


def test_the_state_control_comes_out_not_correct(capsys, monkeypatch):
    """``state_bf16`` at the rehearsal's size: sixteen heads (some of
    them remember hundreds of tokens) over histories of median 320 let a
    state rounded to bfloat16 every token add up, as at the cell's
    sizes: the worst answer reads several times the limit, the median
    over it too (``rehearse.check.readings``)."""
    monkeypatch.setattr(manifest, "read_json", manifest.read_json)
    result, tagged, checks = _run(
        capsys, "--trace", "0",
        main=lambda argv: control_granite.main(
            ["--control", "state_bf16", *argv]))
    assert tagged["check_detail"][0]["control"] == "state_bf16"
    assert result["correct"] is False and result["failed"] == 0
    assert not checks["score_gap_max"]["ok"]
    assert not checks["score_gap_p50"]["ok"]
    for name in ("rank_gap_max", "answers_not_compared", "failed_requests",
                 "compiles_in_window"):
        assert checks[name]["ok"]
    assert set(control_granite.CONTROLS) == {"state_bf16", "int8_weights"}


def test_traced_rehearsal_reads_the_counters_and_the_sizes(capsys):
    result, tagged, _ = _run(capsys, "--trace", "1")
    read = tagged["rehearsal_values_not_device_metrics"][0]
    for name in ("gen_pad_pct", "gen_state_gb", "ssm_state_gb",
                 "ssm_chunk_fill_pct", "batch_occupancy.sat",
                 "server_latency_mean_ms.gen", "queue_wait_mean_ms.gen",
                 "device_wait_mean_ms.gen", "client_overhead_mean_ms.gen",
                 "host_starved_pct.sat", "http_overhead_ms.sat"):
        assert name in read, name
    assert not any(name.startswith(("moe_", "mhc_", "topk_"))
                   for name in read)
    # float32 at the rehearsal's size, 4 rows: 9 state-space layers of
    # [128, 64] states and 3-wide windows of 256, one attention layer's
    # bfloat16 keys and values over 1,024 + 8 slots
    ssm = 4 * 9 * (128 * 64 + 3 * 256) * 4
    assert read["gen_state_gb"]["value"] == pytest.approx(
        (ssm + 4 * 2 * 2 * 1032 * 16 * 2) * 1e-9)
    # what is RESIDENT: a whole number of batches' states
    assert read["ssm_state_gb"]["value"] * 1e9 % ssm == pytest.approx(0)
    assert 0 < read["ssm_chunk_fill_pct"]["value"] <= 100
    shapes = tagged["shapes"][0]
    assert shapes["ssm_step.granite"]["steps"] == 7
    assert shapes["gen_prefill.granite"]["pairs"] \
        >= shapes["gen_prefill.granite"]["scan_pairs"] \
        >= shapes["gen_prefill.granite"]["tokens"]
    assert shapes["ssm_scan.granite"]["tokens"] \
        == shapes["gen_prefill.granite"]["tokens"]
