"""The yardstick's own tests: the trace reduction on a small recorded
trace, the required-work functions on hand-worked shapes, the manifest
lint. Run by hand: ``python -m pytest cellbench/tests -q`` (tier-1's
``tests/`` does not collect them)."""

import copy
import json
import os

import pytest

from cellbench import manifest, readers, required, trace

HERE = os.path.dirname(os.path.abspath(__file__))


@pytest.fixture(scope="module")
def recorded():
    with open(os.path.join(HERE, "recorded_trace.json")) as f:
        return [tuple(e) for e in json.load(f)["events"]]


def naive_union_ns(intervals):
    covered = set()
    for s, e in intervals:  # microsecond grid is fine for a cross-check
        covered.update(range(int(s) // 1000, int(e) // 1000))
    return len(covered) * 1000


def test_recorded_trace_reduces(recorded):
    window_s = 0.024470354  # first dispatch's start to the fourth's end
    r = trace.reduce_device(recorded, window_s)
    # four dispatches of one program, counted from the XLA Modules line
    assert r["programs"] == {"jit__serve_topk": {
        "dispatches": 4, "busy_s": pytest.approx(0.015723443, abs=1e-9)}}
    assert r["busy_s"] == pytest.approx(0.015723443, abs=1e-9)
    ops = [(e[3], e[3] + e[4]) for e in recorded if e[1] == "XLA Ops"]
    assert r["busy_s"] * 1e9 == pytest.approx(naive_union_ns(ops), rel=0.01)
    # dispatches x mean device time per dispatch is the busy time
    n, busy = trace.dispatches(r, "^jit__serve_topk$")
    assert n * (busy / n) == pytest.approx(r["busy_s"])
    # self times add up to the busy time: nothing is counted twice
    assert sum(sec for _, _, sec in r["ops"]) == pytest.approx(
        r["busy_s"], rel=1e-6)
    # per-name time: three batch-1 scans and one batch-2 matmul
    scan = trace.op_seconds(r, "serve_topk", r"^%multiply_reduce_fusion")
    assert scan == pytest.approx(0.009843856, abs=1e-9)
    assert trace.top_device_ops(r)[0][0] == \
        "jit__serve_topk/multiply_reduce_fusion"
    idle = readers.read({"trace": r}, {"reader": "device_idle"})
    assert 0.0 < idle < 100.0
    assert idle == pytest.approx(100 * (1 - 0.015723443 / window_s))


def test_reduction_refuses_more_than_the_window(recorded):
    with pytest.raises(AssertionError, match="exceeds the traced window"):
        trace.reduce_device(recorded, 0.010)


def test_reduction_refuses_busy_time_outside_dispatches(recorded):
    stray = ("/device:TPU:0", "XLA Ops", "%stray = f32[1] add()",
             40_000_000, 5_000_000)
    with pytest.raises(AssertionError, match="dispatches account for"):
        trace.reduce_device(list(recorded) + [stray], 0.1)


def test_self_times_of_nested_operations():
    ops = [("%while", 0, 100), ("%a", 10, 30), ("%b", 50, 40),
           ("%b.inner", 60, 10), ("%after", 120, 5)]
    got = {n: s for n, _, s in trace.self_times(ops)}
    assert got == {"%while": 30, "%a": 30, "%b": 30, "%b.inner": 10,
                   "%after": 5}
    assert trace.union_seconds([(0, 100), (10, 40), (120, 125)]) \
        == pytest.approx(105e-9)


def test_op_label():
    assert trace.op_label(
        "%fusion.740 = f32[4194304,64]{1,0:T(8,128)} fusion(f32[138493,64]"
    ) == "fusion:f32[4194304,64]"
    assert trace.op_label(
        "%_solve_spd_pallas.241 = f32[45056,64]{1,0} custom-call(") \
        == "_solve_spd_pallas"
    assert trace.program_label("jit__serve_topk(1211164447228806255)") \
        == "jit__serve_topk"


def test_required_work_of_one_als_iteration():
    # 3 users, 2 items, 5 ratings, rank 4, float32
    need = required.als_iteration(3, 2, 5, 4)
    # rows gathered 2*5*4*4 = 160; index+value 2*5*8 = 80;
    # tables read and written 2*(3+2)*4*4 = 160
    assert need["bytes"] == 400
    # ratings 2*5*(4*5 + 2*4) = 280; solves 5*(64/3 + 32) = 266.67;
    # Gramians 5*4*5 = 100
    assert need["ops"] == pytest.approx(280 + 5 * (64 / 3 + 32) + 100)
    # the flagship: 20,000,263 ratings at rank 64 need 10.24 GB of rows
    big = required.als_iteration(138_493, 25_279, 20_000_263, 64)
    assert 2 * 20_000_263 * 64 * 4 == pytest.approx(10.24e9, rel=1e-3)
    assert big["bytes"] == pytest.approx(10.64e9, rel=1e-2)


def test_required_work_of_one_topk_dispatch():
    need = required.topk_dispatch(item_table_bytes=1000, n_items=10,
                                  rank=4, batch=2, k=3)
    assert need["bytes"] == 1000 + 2 * 4 * 4 + 2 * 3 * 8
    assert need["ops"] == 2 * 2 * 10 * 4
    peaks = {"flops": 100.0, "bytes_per_s": 10.0}
    least = required.least_seconds(need, peaks)
    assert least == {"seconds": 108.0, "bound": "bandwidth"}
    assert required.least_seconds({"bytes": 1.0, "ops": 1000.0}, peaks) \
        == {"seconds": 10.0, "bound": "compute"}


def test_unknown_device_kind_is_an_error():
    from cellbench.peaks import peaks_for

    assert peaks_for("TPU v5 lite")["bytes_per_s"] == 819e9
    with pytest.raises(KeyError, match="no peaks for device kind"):
        peaks_for("TPU v9 imaginary")


def test_registry_window_statistics():
    def fam(count, total, cum):
        return {"m": {"children": [{
            "labels": {"phase": "q"}, "count": count, "sum": total,
            "buckets": [[0.001, cum[0]], [0.002, cum[1]],
                        ["+Inf", cum[2]]]}]}}

    facts = {"registry": (fam(10, 0.01, [10, 10, 10]),
                          fam(30, 0.05, [10, 30, 30]))}
    # the 20 observations of the window all lie in (1 ms, 2 ms]
    kw = dict(reader="registry", metric="m", labels={"phase": "q"})
    assert readers.read(facts, dict(kw, stat="mean")) \
        == pytest.approx(0.002)
    assert readers.read(facts, dict(kw, stat="p50", scale=1000.0)) \
        == pytest.approx(1.5)
    assert readers.read(facts, dict(kw, stat="p50", metric="absent")) \
        is None


def test_manifest_lint(train_cell):
    committed = manifest.read_json(
        os.path.join(manifest.ROOT, "BENCHMARK.json"))
    assert manifest.lint(committed) == []
    man = train_cell  # and with a train cell's entries added
    assert manifest.lint(man) == []

    def faults(edit):
        bad = copy.deepcopy(man)
        edit(bad)
        return " | ".join(manifest.lint(bad))

    assert "name 'bad name'" in faults(
        lambda m: m["workloads"][0].update(name="bad name"))
    assert "unit 'tokens per second'" in faults(
        lambda m: m["end_to_end"][0].update(unit="tokens per second"))
    assert "traffic file" in faults(
        lambda m: m["workloads"][0].update(traffic="no-such-mix"))
    assert "config nope unknown" in faults(
        lambda m: m["workloads"][0].update(config="nope"))
    # a per-layer metric whose cells do not all report what it moves
    assert "does not report query_p50_ms" in faults(
        lambda m: m["per_layer"][0].update(
            moves="query_p50_ms", workloads=["ml20m-r64.train"]))
    assert "reader file" in faults(
        lambda m: m["per_layer"][0].update(name="no_such_metric"))


def test_code_is_found_by_the_name_a_data_file_gives():
    """A loop, a reader and a generator are modules of their packages:
    the lint sees a name that no module carries."""
    assert manifest.has_module("loops", "closed")
    assert manifest.has_module("readers", "roofline")
    assert manifest.has_module("generators", "poisson")
    assert not manifest.has_module("readers", "no_such_reader")
    assert not manifest.has_module("loops", "../run")
    for name in os.listdir(os.path.join(HERE, "..", "metrics")):
        spec = manifest.read_json(os.path.join(HERE, "..", "metrics", name))
        assert manifest.has_module("readers", spec["reader"]), name


def test_cells_find_their_metrics(train_cell):
    man = train_cell
    train = {m["name"] for m in manifest.per_layer_of(man, "ml20m-r64.train")}
    assert {"pack_s", "gram_device_ms", "train_roofline_pct",
            "hbm_peak_gb"} <= train
    assert not any(n.endswith((".sat", ".steady")) for n in train)
    sat = {m["name"] for m in
           manifest.per_layer_of(man, "lj-r128.serve-closed64")}
    assert "topk_roofline_pct.sat" in sat and "pack_s" not in sat


def test_every_seed_gets_the_same_work_in_another_order():
    import numpy as np

    from cellbench import data, generators

    mix = {"arrivals": "poisson", "rate": 100.0, "traffic_seed": 23}
    poisson = generators.find(mix["arrivals"])
    a = poisson.arrivals(mix, 5.0, seed=1)
    b = poisson.arrivals(mix, 5.0, seed=3_000_000_001)
    assert len(a) == len(b) == 500 and a.max() < 5.0 and (a != b).any()
    gaps = lambda t: np.sort(np.diff(np.concatenate([[0.0], t])))
    assert np.allclose(gaps(a), gaps(b))

    base = data.base_ratings(
        {"generator": "ml20m_surrogate", "scale": 0.01, "data_seed": 20},
        None)
    users, items, stars, n_users, n_items = base
    assert len(np.unique(items)) == n_items  # compacted to those touched
    u1, i1, s1 = data.relabel(*base, seed=5)
    u2, i2, s2 = data.relabel(*base, seed=3_000_000_005)
    for x, y in ((u1, u2), (i1, i2)):
        assert (x != y).any()
        assert (np.sort(np.bincount(x)) == np.sort(np.bincount(y))).all()
    assert np.sort(s1).tolist() == np.sort(s2).tolist() \
        == np.sort(stars).tolist()
