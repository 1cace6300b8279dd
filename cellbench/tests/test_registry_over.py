"""``readers/registry_over.py`` on two hand-written exports, and the
per-layer metrics of ISSUE 37 (the host's seconds by thread role): eight
in the manifest, and the two ``host_runqueue_pct`` files, which no cell
lists because the chip machine's kernel keeps no run-queue clock (as
``train_cell.json``'s metric files, they wait for a cell that can read
them)."""

import pytest

from cellbench import manifest, readers

PYTHON = ("handler", "acceptor", "assemble", "dispatch", "readback",
          "supplement", "other")
SAT = ["lj-r128.serve-closed64", "lfm2-l14.gen32-closed192",
       "laguna-l5.gen32-hist1k-closed48", "xing4-l6.gen32-hist2k-closed12"]
NEW = {
    "host_python_cores.sat": SAT,
    "host_python_cores.steady": ["lj-r128.serve-steady"],
    "host_native_cores.sat": SAT,
    "host_native_cores.steady": ["lj-r128.serve-steady"],
    "handler_cpu_pct.sat": ["lj-r128.serve-closed64"],
    "handler_cpu_pct.steady": ["lj-r128.serve-steady"],
    "dispatch_cpu_ratio.sat": ["lj-r128.serve-closed64"],
    "host_cpus": SAT,
}


def export(cpu, runqueue, states, dispatch_sum, cpus=13):
    """An export as ``MetricsRegistry.export()`` writes one: the thread
    seconds by (role, state), the starvation clock's five states, the
    dispatch stage's histogram and the core count."""
    threads = [{"labels": {"role": r, "state": "cpu"}, "value": v}
               for r, v in cpu.items()]
    threads += [{"labels": {"role": r, "state": "runqueue"}, "value": v}
                for r, v in runqueue.items()]
    return {
        "pio_thread_seconds_total": {"kind": "counter",
                                     "children": threads},
        "pio_pipeline_state_seconds_total": {"kind": "counter", "children": [
            {"labels": {"state": s}, "value": v}
            for s, v in zip(("enqueued", "launching", "staged",
                             "assembling", "empty"), states)]},
        "pio_pipeline_stage_seconds": {"kind": "histogram", "children": [
            {"labels": {"stage": "assemble"}, "buckets": [["+Inf", 9]],
             "count": 9, "sum": 123.0},
            {"labels": {"stage": "dispatch"}, "buckets": [["+Inf", 9]],
             "count": 9, "sum": dispatch_sum}]},
        "pio_host_cpus": {"kind": "gauge", "children": [
            {"labels": {}, "value": cpus}]},
    }


def role_seconds(handler, dispatch, rest, native, exited=None):
    out = {r: rest for r in PYTHON}
    out.update(handler=handler, dispatch=dispatch, native=native)
    if exited is not None:
        out["exited"] = exited
    return out


# a window of 40 s (30 + 4 + 2 + 1 + 3 of the exclusive states). In it
# the handlers burned 20 s, the dispatch threads 6 s, each of the five
# other Python roles 2 s: 36 s of Python, 0.9 of one interpreter; the
# runtime's threads 10 s, and 5 s went to threads that had exited.
# Ready to run the Python roles waited 3 + 1 + 5 x 0 = 4 s, the native
# ones 50 s (not the interpreter's business). The dispatch stage's wall
# time grew by 24 s. What came before the window does not count.
BEFORE = export(role_seconds(100.0, 10.0, 1.0, 7.0, exited=2.0),
                role_seconds(9.0, 1.0, 0.5, 20.0),
                [1.0, 0.5, 0.5, 1.0, 97.0], 3.0, cpus=8)
AFTER = export(role_seconds(120.0, 16.0, 3.0, 17.0, exited=7.0),
               role_seconds(12.0, 2.0, 0.5, 70.0),
               [31.0, 4.5, 2.5, 2.0, 100.0], 27.0)
UNLISTED = ["host_runqueue_pct.sat", "host_runqueue_pct.steady"]
HAND = {
    "host_python_cores": 36.0 / 40.0,
    "host_native_cores": 10.0 / 40.0,
    "handler_cpu_pct": 100.0 * 20.0 / 36.0,
    "host_runqueue_pct": 100.0 * 4.0 / (36.0 + 4.0),
    "dispatch_cpu_ratio": 6.0 / 24.0,
    "host_cpus": 13.0,
}


@pytest.mark.parametrize("name", sorted(NEW) + UNLISTED)
def test_each_metric_reads_the_hand_computed_number(name):
    spec = manifest.read_json(manifest.metric_path(name))
    stem = name.rsplit(".", 1)[0] if name != "host_cpus" else name
    assert readers.read({"registry": (BEFORE, AFTER)}, spec) \
        == pytest.approx(HAND[stem])


@pytest.mark.parametrize("name", sorted(NEW) + UNLISTED)
@pytest.mark.parametrize("registry", [
    ({}, {}),                 # a program from before the families
    (None, None),
    ({k: v for k, v in BEFORE.items() if "thread" not in k
      and "cpus" not in k},
     {k: v for k, v in AFTER.items() if "thread" not in k
      and "cpus" not in k}),  # the parent: a pipeline, no thread clocks
], ids=["absent", "no-export", "parent"])
def test_nothing_to_read_is_none(name, registry):
    spec = manifest.read_json(manifest.metric_path(name))
    assert readers.read({"registry": registry}, spec) is None
    assert readers.read({}, spec) is None


def test_a_kernel_without_a_run_queue_clock_reads_nothing():
    # the chip machine's: the family is there, its runqueue children
    # are not. That is "not measured", never 0 %.
    def cpu_only(export_):
        fam = export_["pio_thread_seconds_total"]
        return {**export_, "pio_thread_seconds_total": {**fam, "children": [
            c for c in fam["children"] if c["labels"]["state"] == "cpu"]}}

    facts = {"registry": (cpu_only(BEFORE), cpu_only(AFTER))}
    for name in UNLISTED:
        spec = manifest.read_json(manifest.metric_path(name))
        assert readers.read(facts, spec) is None
    spec = manifest.read_json(manifest.metric_path("host_python_cores.sat"))
    assert readers.read(facts, spec) == pytest.approx(0.9)


def test_registry_over_by_hand():
    kw = dict(reader="registry_over", metric="pio_thread_seconds_total")
    facts = {"registry": (BEFORE, AFTER)}
    window = {"metric": "pio_pipeline_state_seconds_total"}
    # every child of the family, the exited one among them: the
    # process's CPU over the window, in cores
    assert readers.read(facts, dict(
        kw, labels=[{"state": "cpu"}], over=window)) \
        == pytest.approx((36.0 + 10.0 + 5.0) / 40.0)
    # a family first seen at the window's close counts from zero
    assert readers.read({"registry": ({}, AFTER)}, dict(
        kw, labels=[{"role": "native", "state": "cpu"}], over=window,
        scale=2.0)) == pytest.approx(2.0 * 17.0 / 140.0)
    # a denominator that did not move has no ratio
    assert readers.read({"registry": (AFTER, AFTER)}, dict(
        kw, labels=[{"role": "native"}], over=window)) is None
    # a histogram is read by its sum, one child of it by its labels
    assert readers.read(facts, dict(
        kw, labels=[{"role": "dispatch", "state": "cpu"}],
        over={"metric": "pio_pipeline_stage_seconds"})) \
        == pytest.approx(6.0 / 24.0)  # assemble's sum stood still


def test_the_new_metrics_are_in_the_manifest():
    man = manifest.load()
    assert manifest.lint(man) == []
    listed = [m["name"] for m in man["per_layer"]]
    # appended, in the issue's order, after everything that was there
    assert listed[-len(NEW):] == list(NEW)
    for name, cells in NEW.items():
        entry = next(m for m in man["per_layer"] if m["name"] == name)
        spec = manifest.read_json(manifest.metric_path(name))
        assert entry["source"] == "program_counter"
        assert entry["workloads"] == cells
        assert (entry["layer"], entry["unit"], entry["moves"]) == (
            spec["layer"], spec["unit"], spec["moves"])
        assert entry["moves"] == ("query_p50_ms"
                                  if name.endswith(".steady")
                                  else "served_qps")
        for cell in cells:
            assert name in {m["name"] for m in
                            manifest.per_layer_of(man, cell)}
    assert not {m["name"] for m in man["per_layer"]} & set(UNLISTED)
    # one reader file came with them
    assert {manifest.read_json(manifest.metric_path(n))["reader"]
            for n in list(NEW) + UNLISTED} == {
                "registry_over", "registry_ratio", "registry"}
