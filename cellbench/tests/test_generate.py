"""The generative cell's own pieces: what its programs need
(``required_gen.py``), the cut of a capture's edges, and a rehearsal of
the loop with ``correct`` shown to hold, and shown to come out false
under each control and for one wrong token an answer (CPU, the
rehearsal's size)."""

import json

import numpy as np
import pytest

from cellbench import control_gen, manifest, required_gen, run
from cellbench.loops import generate

CELL = "lfm2-l14.gen32-closed192"


@pytest.fixture(scope="module")
def lfm2():
    man = manifest.load()
    return manifest.read_json(
        f"{manifest.ROOT}/{manifest.config_of(man, manifest.cell(man, CELL))['file']}")


def test_the_configuration_is_the_published_one_cut_in_depth(lfm2):
    man = manifest.load()
    entry = manifest.config_of(man, manifest.cell(man, CELL))
    assert entry["reduced"] == ["num_hidden_layers", "layer_types"]
    assert (lfm2["hidden_size"], lfm2["intermediate_size"],
            lfm2["moe_intermediate_size"]) == (2048, 7168, 1792)
    assert (lfm2["num_experts"], lfm2["num_experts_per_tok"],
            lfm2["vocab_size"]) == (32, 4, 65536)
    assert (lfm2["num_attention_heads"], lfm2["num_key_value_heads"],
            lfm2["conv_L_cache"]) == (32, 8, 3)
    kinds = lfm2["layer_types"]
    assert len(kinds) == lfm2["num_hidden_layers"] == 14
    assert kinds[:2] == ["conv", "conv"]
    assert kinds[2:] == ["full_attention", "conv", "conv", "conv"] * 3
    for key in ("published", "deployment", "assumed", "precision"):
        assert lfm2[key]


def test_required_work_is_the_issues_arithmetic(lfm2):
    model = generate.model_keys(lfm2)
    s = required_gen._sizes(model)
    assert s["expert"] == 3 * 2048 * 1792          # 11.01 M
    assert s["n_expert_layers"] == 12 and s["n_attn"] == 3
    active = s["outside"] + 12 * 4 * s["expert"]
    assert 2 * active == pytest.approx(1.66e9, rel=0.01)   # ops a token
    step = required_gen.gen_decode(model, rows=64, steps=1,
                                   experts_touched=32, history_mean=165)
    # every weight once: 9.33 GB, and 64 rows' keys and values
    assert step["bytes"] == pytest.approx(9.33e9 + 0.21e9 * 181 / 544,
                                          rel=0.01)
    one = required_gen.gen_decode(model, rows=64, steps=1,
                                  experts_touched=1, history_mean=165)
    assert step["bytes"] - one["bytes"] == 12 * 31 * s["expert"] * 2
    many = required_gen.gen_decode(model, rows=64, steps=31,
                                   experts_touched=32, history_mean=165)
    assert many["ops"] == pytest.approx(
        31 * 64 * (2 * (active + s["head"])), rel=0.02)
    pre = required_gen.gen_prefill(model, rows=64, tokens=64 * 165,
                                   tokens_squared=64 * 165 ** 2 * 1.5)
    assert pre["ops"] == pytest.approx(64 * 165 * 1.66e9, rel=0.05)
    assert pre["bytes"] == pytest.approx(9.33e9, rel=0.01)
    twice = required_gen.gen_prefill(model, rows=64, tokens=2 * 64 * 165,
                                     tokens_squared=64 * 165 ** 2 * 1.5)
    assert twice["ops"] > 1.9 * pre["ops"]


def test_history_lengths_are_one_multiset_for_every_seed():
    tr = manifest.read_json(manifest.traffic_path("gen32-closed192"))
    a = generate.history_lengths(tr, 4000)
    assert a.min() >= 16 and a.max() <= 512
    assert 118 <= np.median(a) <= 138 and 150 <= a.mean() <= 180
    np.testing.assert_array_equal(a, generate.history_lengths(tr, 4000))


def test_whole_dispatches_cuts_the_edges_of_a_capture():
    dev, ms = "/device:TPU:0", 1_000_000
    events = [("/host:CPU", "python", "pio:dispatch", 0, 5 * ms)]
    # an operation of a dispatch that began before the capture, three
    # whole dispatches, one cut short by the capture's end
    events.append((dev, "XLA Ops", "%orphan = f32[] add()", 0, 2 * ms))
    for i, start in enumerate((3, 14, 25, 36, 47)):
        events.append((dev, "XLA Modules", f"jit__gen_decode({i})",
                       start * ms, 10 * ms))
        events.append((dev, "XLA Ops", "%fusion = f32[8]{0} fusion()",
                       start * ms, 4 * ms))
        events.append((dev, "XLA Ops", "%ragged-dot-none = f32[8]",
                       (start + 5) * ms, 5 * ms))
    kept, whole_s, cut_s = generate.whole_dispatches(events, 0.060)
    mods = [e for e in kept if e[1] == "XLA Modules"]
    assert [m[3] for m in mods] == [14 * ms, 25 * ms, 36 * ms]
    assert whole_s == pytest.approx(0.032) and cut_s == pytest.approx(0.028)
    assert len([e for e in kept if e[1] == "XLA Ops"]) == 6
    assert any(e[0] == "/host:CPU" for e in kept)
    from cellbench import trace
    reduced = trace.reduce_device(kept, whole_s)
    assert reduced["busy_s"] == pytest.approx(0.027)
    assert trace.dispatches(reduced, "^jit__gen_decode$")[0] == 3
    # too few modules to cut: the capture as it is
    assert generate.whole_dispatches(events[:4], 0.06)[0] == events[:4]


def test_the_checks_reference_is_the_plain_forward_pass(lfm2, monkeypatch):
    """``_reference_gaps`` takes operators by chunks of padded sequences
    and feed-forwards by blocks of real tokens; what it returns is the
    one-sequence forward pass's, sequence by sequence."""
    import jax
    import jax.numpy as jnp

    from cellbench import reference_lfm2 as ref
    from predictionio_tpu.models import decoder

    tr = manifest.read_json(manifest.traffic_path("gen32-closed192"))
    cell = generate.Cell.__new__(generate.Cell)
    cell.config = {**lfm2, **lfm2["rehearse"]}
    cell.traffic = {**tr, **tr["rehearse"]}
    cell.model = generate.model_keys(cell.config)
    cfg = decoder.DecoderConfig.from_dict(cell.model)
    cell.weights = decoder.init_weights(jax.random.key(5), cfg,
                                        cell.config["init"])
    monkeypatch.setattr(generate, "BLOCK", 32)   # several blocks
    rng = np.random.default_rng(3)
    n = int(cell.traffic["num"])
    lengths = [9, 40, 17, 33, 12, 8, 26, 40, 21]  # two chunks, one short
    seqs = [rng.integers(0, cfg.vocab_size, k).tolist() for k in lengths]
    firsts = [k - n for k in lengths]
    served = [(rng.integers(0, cfg.vocab_size, n),
               rng.normal(size=n).astype(np.float32)) for _ in seqs]
    got = cell._reference_gaps(*cell._reference_under(None), seqs, firsts,
                               served)
    w32 = jax.tree_util.tree_map(lambda a: a.astype(jnp.float32),
                                 cell.weights)
    for seq, first, (toks, scores), (s, r) in zip(seqs, firsts, served,
                                                  got):
        logits = ref.forward(w32, jnp.asarray(seq), cell.model)
        want_s, want_r = ref.served_gaps(logits[first:first + n], toks,
                                         scores)
        np.testing.assert_allclose(s, want_s, rtol=1e-4, atol=1e-5)
        np.testing.assert_allclose(r, want_r, rtol=1e-4, atol=1e-5)


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


@pytest.mark.parametrize("control", control_gen.CONTROLS)
def test_a_control_comes_out_not_correct(capsys, monkeypatch, control):
    """The whole run under ``control_gen``: the same server, traffic and
    limits, the reference one step below the configuration."""
    monkeypatch.setattr(manifest, "read_json", manifest.read_json)
    result, tagged, checks = _run(
        capsys, "--trace", "0",
        main=lambda argv: control_gen.main(["--control", control, *argv]))
    assert tagged["check_detail"][0]["control"] == control
    assert result["correct"] is False and result["failed"] == 0
    assert not checks["score_gap_p50"]["ok"]
    for name in ("answers_not_compared", "failed_requests",
                 "compiles_in_window"):
        assert checks[name]["ok"]


def test_answers_altered_where_they_are_produced_fail(capsys, monkeypatch):
    """Every item of every answer is the next id, with the score the
    program gave the one it chose."""
    from predictionio_tpu.templates import generative

    real = generative.GenerativeAlgorithm._dispatch

    def altered(self, model, hists):
        (toks, scores, load), slots = real(self, model, hists)
        vocab = int(model.config["vocab_size"])
        return ((toks + 1) % vocab, scores, load), slots

    monkeypatch.setattr(generative.GenerativeAlgorithm, "_dispatch",
                        altered)
    result, _, checks = _run(capsys, "--trace", "0")
    assert result["correct"] is False and result["failed"] == 0
    assert not checks["rank_gap_max"]["ok"]
    assert not checks["score_gap_max"]["ok"]
    assert not checks["score_gap_p50"]["ok"]


def test_traced_rehearsal_reads_the_counters_the_generic_metrics_too(
        capsys):
    result, tagged, _ = _run(capsys, "--trace", "1")
    read = tagged["rehearsal_values_not_device_metrics"][0]
    for name in ("moe_experts_touched", "moe_load_imbalance",
                 "gen_pad_pct", "batch_occupancy.sat",
                 "server_latency_mean_ms.gen", "queue_wait_mean_ms.gen",
                 "device_wait_mean_ms.gen", "client_overhead_mean_ms.gen",
                 "host_starved_pct.sat", "http_overhead_ms.sat"):
        assert name in read, name
    assert 1 <= read["moe_experts_touched"]["value"] <= 8
    assert 0 < read["gen_pad_pct"]["value"] < 100
    assert not any(name.startswith("topk_") for name in read)
    shapes = tagged["shapes"][0]
    assert shapes["gen_decode"]["steps"] == 7
