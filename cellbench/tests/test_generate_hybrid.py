"""The ``nemotron_h`` cell's own pieces: what its programs need
(``required_nemotron.py``, hand-counted), the configuration against the
catalog's row, its reference run in chunks and blocks against the plain
forward pass, and a rehearsal of the loop with ``correct`` shown to hold
and shown to come out false under each control (CPU, the rehearsal's
size). No count of cells or of metrics is pinned here."""

import json

import numpy as np
import pytest

from cellbench import control_nemotron, manifest, required_nemotron, run
from cellbench.loops import generate, generate_hybrid

CELL = "nemotron3-super-l11.gen32-hist192-closed48"
CATALOG = "/opt/skills/guides/model-configs/architectures.jsonl"
REDUCED = ["num_hidden_layers", "hybrid_override_pattern",
           "n_routed_experts", "vocab_size"]


@pytest.fixture(scope="module")
def nemotron():
    man = manifest.load()
    return manifest.read_json(
        f"{manifest.ROOT}/{manifest.config_of(man, manifest.cell(man, CELL))['file']}")


def test_the_configuration_is_the_stated_cut(nemotron):
    man = manifest.load()
    entry = manifest.config_of(man, manifest.cell(man, CELL))
    assert entry["reduced"] == nemotron["reduced"] == REDUCED
    assert manifest.cell(man, CELL)["chips"] == 1
    assert all(w["chips"] == 1 for w in man["workloads"])
    assert nemotron["hybrid_override_pattern"] == "MEMEMEM*EME" \
        == nemotron["published"]["hybrid_override_pattern"][:11]
    assert (nemotron["num_hidden_layers"], nemotron["n_routed_experts"],
            nemotron["router_experts"], nemotron["vocab_size"]) == (
        11, 128, 512, 32768)
    assert nemotron["experts_held"] == list(range(128))
    assert (nemotron["published"]["num_hidden_layers"],
            nemotron["published"]["n_routed_experts"],
            nemotron["published"]["vocab_size"]) == (88, 512, 131072)
    # no width is cut
    assert (nemotron["hidden_size"], nemotron["num_attention_heads"],
            nemotron["num_key_value_heads"], nemotron["head_dim"]) == (
        4096, 32, 2, 128)
    assert (nemotron["mamba_num_heads"], nemotron["mamba_head_dim"],
            nemotron["n_groups"], nemotron["ssm_state_size"],
            nemotron["chunk_size"], nemotron["conv_kernel"]) == (
        128, 64, 8, 128, 128, 4)
    assert (nemotron["moe_latent_size"], nemotron["moe_intermediate_size"],
            nemotron["moe_shared_expert_intermediate_size"],
            nemotron["num_experts_per_tok"],
            nemotron["routed_scaling_factor"],
            nemotron["mlp_hidden_act"]) == (1024, 2688, 5376, 22, 5, "relu2")
    for key in ("source", "published", "deployment", "assumed",
                "not_served", "precision", "init", "check"):
        assert nemotron[key]
    for key in ("router_experts", "attention", "topk_norm", "dt_limits",
                "gated_norm", "projections", "precision", "weights"):
        assert nemotron["assumed"][key]
    assert "num_nextn_predict_layers" in nemotron["not_served"]
    assert nemotron["server"] == {"batching": True, "max_batch": 16}
    # the weights' key is the configuration's, not the run's
    assert isinstance(nemotron["weights_seed"], int)
    assert "weights_seed" in nemotron["assumed"]["weights"]
    assert nemotron["engine"]["row_buckets"] == [16]
    assert nemotron["engine"]["history_buckets"][-1] == 1024
    for text in (entry["source"], entry["why"],
                 manifest.cell(man, CELL)["why"]):
        assert 1 <= len(text) <= 200 and "\n" not in text


def test_every_number_of_the_catalog_row_is_in_the_file(nemotron):
    try:
        with open(CATALOG) as f:
            rows = [json.loads(line) for line in f]
    except OSError:
        pytest.skip("no catalog beside the model-configs guide here")
    row, = [r for r in rows
            if r["name"] == "NVIDIA-Nemotron-3-Super-120B-A12B-BF16"]
    assert row["source_url"] in nemotron["source"]
    assert {k for k, v in row["config"].items() if nemotron.get(k) != v} \
        == set(REDUCED)
    for key in REDUCED:
        assert nemotron["published"][key] == row["config"][key]


def test_the_program_reads_the_file_as_the_share_it_states(nemotron):
    from predictionio_tpu.models import decoder

    cfg = decoder.DecoderConfig.from_dict(generate.model_keys(nemotron))
    assert cfg.layer_types.count("mamba") == 5 \
        and cfg.mlp_layer_types.count("sparse") == 5 \
        and cfg.layer_types.count("full_attention") == 1
    assert (cfg.num_experts, cfg.n_held, cfg.num_experts_per_tok,
            cfg.vocab_size, cfg.mamba_n_groups, cfg.mamba_chunk_size,
            cfg.norm_eps, cfg.nope) == (512, 128, 22, 32768, 8, 128, 1e-5,
                                        True)
    params = sum(int(np.prod(shape)) for l in range(11)
                 for shape, _, _ in decoder._layer_shapes(cfg, l).values())
    # the issue's 9.30 GB: 5 x 1.52 + 5 x 0.219 + 0.071 + 0.54
    assert 2 * (params + 2 * 32768 * 4096) == pytest.approx(9.30e9, rel=2e-3)


def test_required_work_is_the_issues_arithmetic(nemotron):
    model = generate.model_keys(nemotron)
    s = required_nemotron._sizes(model)
    assert (s["n_m"], s["n_e"], s["n_a"], s["I"], s["N"], s["G"]) == (
        5, 5, 1, 8192, 128, 8)
    # the issue's table: M 109.6 M, * 35.7 M, E outside its experts
    # 54.5 M, a routed expert 5.505 M, E with 128 held 759 M
    assert s["mamba"] == 4096 * 18560 + 8192 * 4096
    assert s["mamba"] + 10240 * 5 + 3 * 128 + 8192 \
        == pytest.approx(109.6e6, rel=1e-3)
    assert s["attn"] == pytest.approx(35.7e6, rel=2e-3)
    assert s["router"] + s["latent"] + s["shared"] + 512 \
        == pytest.approx(54.5e6, rel=2e-3)
    assert s["expert"] == 2 * 1024 * 2688 == pytest.approx(5.505e6, rel=1e-3)
    assert s["router"] + s["latent"] + s["shared"] + 128 * s["expert"] \
        == pytest.approx(759e6, rel=2e-3)
    # a decode step of 16 rows, by the issue's count: experts 3.6 GB at
    # 65 touched a layer, Mamba weights 1.10, state 0.67, the E layers'
    # other weights 0.55, the head 0.27, attention 0.07: 6.2 GB
    experts = required_nemotron.moe_step(model, 16, 1, 65.0, 0.25)
    assert experts["bytes"] == 5 * 65 * 11010048 \
        == pytest.approx(3.6e9, rel=0.01)
    assert experts["ops"] == 5 * 16 * 22 * 0.25 * 4 * 1024 * 2688
    state = required_nemotron.ssm_step(model, 16, 1)
    assert state["bytes"] == 16 * 5 * 2 * 128 * 8192 * 4 \
        == pytest.approx(0.67e9, rel=0.01)
    one = required_nemotron.gen_decode(model, rows=16, steps=1, cache=300,
                                       experts_touched=65.0,
                                       held_share=0.25)
    assert one["bytes"] == pytest.approx(6.2e9, rel=0.02)
    assert one["bytes"] == pytest.approx(
        2 * (required_nemotron._fixed(s) + s["head"]) + 4 * s["vectors"]
        + experts["bytes"] + state["bytes"]
        + 16 * 5 * 2 * 3 * 10240 * 4 + 16 * 300 * 512 * 2, rel=1e-12)
    assert required_nemotron.gen_decode(
        model, 16, 31, 300, 65.0, 0.25)["bytes"] \
        == pytest.approx(31 * one["bytes"])
    # a prefill's token: about 2.07 GFLOP (Mamba 1.15, the E layers 0.85
    # with their held experts' 5.5 assignments, attention 0.07)
    pre = required_nemotron.gen_prefill(model, rows=16, tokens=4000,
                                        pairs=0, scan_pairs=0,
                                        held_share=0.25)
    per_token = (pre["ops"] - 2 * 16 * s["head"]) / 4000
    assert per_token == pytest.approx(2.07e9, rel=0.06)
    held = required_nemotron.moe_prefill(model, 4000, 0.25)
    assert held["bytes"] == 5 * 128 * 11010048       # 7.0 GB a prefill
    assert held["ops"] == 5 * 4000 * 5.5 * 4 * 1024 * 2688


def test_the_work_is_hand_counted_at_a_tiny_size():
    """Four heads of 4 in 2 groups over a state of 3, chunks of 4; one
    layer of each letter; 4 experts held of 8, 2 a token, 5 wide in a
    latent of 6; a shared expert 7 wide."""
    tiny = {"hidden_size": 8, "num_attention_heads": 2,
            "num_key_value_heads": 1, "head_dim": 4, "mamba_num_heads": 4,
            "mamba_head_dim": 4, "ssm_state_size": 3, "n_groups": 2,
            "conv_kernel": 4, "chunk_size": 4,
            "hybrid_override_pattern": "ME*", "num_hidden_layers": 3,
            "moe_latent_size": 6, "moe_intermediate_size": 5,
            "moe_shared_expert_intermediate_size": 7,
            "n_routed_experts": 4, "router_experts": 8,
            "num_experts_per_tok": 2, "vocab_size": 32}
    assert required_nemotron.chunk_pairs(6, 4) == 10 + 3
    assert required_nemotron.chunk_pairs(300, 128) \
        == 2 * (128 * 129 // 2) + 44 * 45 // 2
    need = required_nemotron.ssm_scan(tiny, rows=2, tokens=9, scan_pairs=19)
    # a pair: C . B once a group (2 x 3 x 2) and 2 x 4 a head of 4; a
    # token: C S and the state's update, 2 x 3 x 16 each
    assert need["ops"] == 19 * (12 + 32) + 9 * (96 + 96)
    # x and y 16 wide, a B and a C a group 2 x 3 wide at 2 bytes, dt 4
    # heads at 4; a row's state 3 x 16 float32
    assert need["bytes"] == 9 * ((32 + 12) * 2 + 16) + 2 * 48 * 4
    step = required_nemotron.ssm_step(tiny, rows=2, steps=3)
    assert (step["bytes"], step["ops"]) == (3 * 2 * 2 * 48 * 4,
                                            3 * 2 * 5 * 48)
    s = required_nemotron._sizes(tiny)
    assert s["mamba"] == 8 * (16 + 16 + 12 + 4) + 16 * 8
    assert s["attn"] == 8 * 8 + 2 * 8 * 4 + 8 * 8
    assert (s["router"], s["latent"], s["shared"], s["expert"]) == (
        64, 96, 112, 60)
    # a step that touched 3 of the 4 held experts: each read once
    moe = required_nemotron.moe_step(tiny, rows=2, steps=3,
                                     experts_touched=3.0, held_share=0.5)
    assert moe["bytes"] == 3 * 3.0 * 60 * 2
    assert moe["ops"] == 3 * 2 * 2 * 0.5 * 2 * 60
    pre = required_nemotron.moe_prefill(tiny, tokens=9, held_share=0.5)
    assert (pre["bytes"], pre["ops"]) == (4 * 60 * 2, 9 * 2 * 0.5 * 120)
    whole = required_nemotron.gen_prefill(tiny, rows=2, tokens=9, pairs=27,
                                          scan_pairs=19, held_share=0.5)
    fixed = s["mamba"] + s["attn"] + 64 + 96 + 112
    assert required_nemotron._fixed(s) == fixed
    assert whole["ops"] == 2 * 9 * fixed + 2 * 2 * 32 * 8 \
        + 2 * 4 * 4 * 27 + need["ops"] + pre["ops"]
    with pytest.raises(ValueError):
        required_nemotron._sizes({**tiny, "hybrid_override_pattern": "M-*"})


def test_the_traffic_is_the_issues(nemotron):
    tr = manifest.read_json(
        manifest.traffic_path("gen32-hist192-hybrid-closed48"))
    other = manifest.read_json(
        manifest.traffic_path("gen32-hist192-closed48"))
    a = generate.history_lengths(tr, 3000)
    # granite-h-micro's multiset: the two state-space cells differ by
    # the model alone
    np.testing.assert_array_equal(a, generate.history_lengths(other, 3000))
    assert a.min() >= 32 and a.max() <= 1024 and 175 <= np.median(a) <= 210
    assert (tr["generators"] * tr["connections"], tr["num"],
            tr["check_sample"], tr["zipf"], tr["loop"]) == (
        48, 32, 16, 1.1, "generate_hybrid")
    assert tr["warm_shapes"] == [
        [16, b] for b in nemotron["engine"]["history_buckets"]]


def _cell(nemotron):
    from predictionio_tpu.models import decoder

    tr = manifest.read_json(
        manifest.traffic_path("gen32-hist192-hybrid-closed48"))
    cell = generate_hybrid.Cell.__new__(generate_hybrid.Cell)
    cell.config = {**nemotron, **nemotron["rehearse"]}
    cell.traffic = {**tr, **tr["rehearse"]}
    cell.model = generate.model_keys(cell.config)
    cfg = decoder.DecoderConfig.from_dict(cell.model)
    cell.weights = generate_hybrid.weights_of(cell.config, cfg)
    return cell, cfg


def test_the_weights_are_the_configurations_whatever_the_seed(
        nemotron, monkeypatch):
    """``inputs`` ends with the weights of the configuration's
    ``weights_seed``: two runs' seeds order the traffic and draw the
    same model; another ``weights_seed`` is another model."""
    import jax

    cell, cfg = _cell(nemotron)

    def runs_own(self):
        self.cfg, self.weights = cfg, "drawn from the run's seed"
    monkeypatch.setattr(generate.Cell, "inputs", runs_own)
    got = []
    for seed in (11, 2147483659):
        cell.seed = seed
        cell.inputs()
        got.append(jax.tree_util.tree_map(np.asarray, cell.weights))
    same = jax.tree_util.tree_map(np.array_equal, *got)
    assert all(jax.tree_util.tree_leaves(same))
    other = generate_hybrid.weights_of(
        {**cell.config, "weights_seed": cell.config["weights_seed"] + 1}, cfg)
    assert not np.array_equal(np.asarray(other["layers"][1]["gate"]),
                              got[0]["layers"][1]["gate"])


def test_the_checks_reference_is_the_plain_forward_pass(nemotron,
                                                        monkeypatch):
    """``_reference_gaps`` takes ``chunk`` padded sequences a mixer's
    call and the expert layers by blocks of real tokens; what it returns
    is the one-sequence forward pass's, sequence by sequence, GIVEN THE
    SAME SHARE; under ``state_bf16`` it is another computation's."""
    import jax
    import jax.numpy as jnp

    from cellbench import reference_nemotron as ref

    cell, cfg = _cell(nemotron)
    assert cfg.n_held == 8 and cfg.num_experts == 32
    monkeypatch.setattr(generate_hybrid, "BLOCK", 32)   # several blocks
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


def test_the_int8_control_rounds_the_leaves_it_names(nemotron):
    import jax.numpy as jnp

    cell, _ = _cell(nemotron)
    _, (widen, round_state) = cell._reference_under("int8_weights")
    assert round_state is None
    assert cell._reference_under("state_bf16")[1][1] is not None
    with pytest.raises(ValueError):
        cell._reference_under("no_such_control")
    _, (routed, _) = cell._reference_under("int8_routed")
    lossy = {"M": {"w_in", "w_out"}, "*": set(),
             "E": {"w_down", "w_up", "s1", "s2", "w1", "w2"}}
    for letter, lw in zip(cell.model["hybrid_override_pattern"],
                          cell.weights["layers"]):
        def changed(through):
            return {k for k, v in through(lw).items()
                    if not np.array_equal(v, lw[k].astype(jnp.float32))}
        assert changed(widen) == lossy[letter]
        assert changed(routed) == lossy[letter] & {"w1", "w2"}


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


@pytest.mark.parametrize("control", control_nemotron.CONTROLS)
def test_a_control_comes_out_not_correct(capsys, monkeypatch, control):
    """The whole run under ``control_nemotron``: the same server, traffic
    and limits, the reference one step below the configuration.
    ``int8_routed`` is told apart at the CELL's size (the file's
    ``check.readings``: 4-5 x a sound run's ``score_gap_p50``), not at
    the rehearsal's, where 6 of 32 experts of width 48 a token leave the
    routed experts' rounding at 1.2-1.6 x a sound run's reading: here it
    only has to run as the control it names."""
    monkeypatch.setattr(manifest, "read_json", manifest.read_json)
    result, tagged, checks = _run(
        capsys, "--trace", "0",
        main=lambda argv: control_nemotron.main(
            ["--control", control, *argv]))
    assert tagged["check_detail"][0]["control"] == control
    assert result["failed"] == 0
    if control != "int8_routed":
        assert result["correct"] is False
        assert not checks["score_gap_p50"]["ok"]
    for name in ("answers_not_compared", "failed_requests",
                 "compiles_in_window"):
        assert checks[name]["ok"]


def test_traced_rehearsal_reads_the_counters_and_the_sizes(capsys):
    result, tagged, _ = _run(capsys, "--trace", "1")
    read = tagged["rehearsal_values_not_device_metrics"][0]
    for name in ("gen_pad_pct", "gen_state_gb", "ssm_state_gb",
                 "ssm_chunk_fill_pct.nemotron", "moe_held_assignment_pct",
                 "moe_experts_touched", "moe_experts_read",
                 "moe_load_imbalance", "batch_occupancy.sat",
                 "server_latency_mean_ms.gen", "queue_wait_mean_ms.gen",
                 "device_wait_mean_ms.gen", "client_overhead_mean_ms.gen",
                 "host_starved_pct.sat", "http_overhead_ms.sat"):
        assert name in read, name
    assert not any(name.startswith(("mhc_", "topk_"))
                   or name.endswith(".granite") for name in read)
    # 8 of 32 experts held: about a quarter of the assignments land here,
    # and a step's rows touch some of the 8, never more
    assert 15 < read["moe_held_assignment_pct"]["value"] < 40
    assert 0 < read["moe_experts_touched"]["value"] <= 8
    assert read["moe_experts_read"]["value"] <= 8
    # float32 at the rehearsal's size, 4 rows: 5 state-space layers of
    # [64, 128] states and 3-wide windows of 128 + 2 x 2 x 64, one
    # attention layer's keys and values over 1,024 + 8 slots, and
    # NOTHING for the five expert layers
    ssm = 4 * 5 * (64 * 128 + 3 * 384) * 4
    assert read["gen_state_gb"]["value"] == pytest.approx(
        (ssm + 4 * 2 * 2 * 1032 * 16 * 2) * 1e-9)
    assert read["ssm_state_gb"]["value"] * 1e9 % ssm == pytest.approx(0)
    shapes = tagged["shapes"][0]
    assert shapes["decode"]["steps"] == 7
    assert shapes["prefill"]["pairs"] >= shapes["prefill"]["scan_pairs"] \
        >= shapes["prefill"]["tokens"]
    assert 0.15 < shapes["prefill"]["held_share"] < 0.4
    assert shapes["decode"]["experts_touched"] \
        == read["moe_experts_touched"]["value"]
