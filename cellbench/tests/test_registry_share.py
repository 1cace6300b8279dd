"""``readers/registry_share.py`` on two hand-written exports, and the
eight per-layer metrics of ISSUE 24 in the manifest."""

import pytest

from cellbench import manifest, readers

STATES = ("enqueued", "launching", "staged", "assembling", "empty")
NEW = [f"{stem}{suffix}" for stem in (
    "device_wait_ms", "http_overhead_ms", "host_starved_pct",
    "starved_empty_pct") for suffix in (".steady", ".sat")]


def export(seconds):
    return {"pio_pipeline_state_seconds_total": {
        "kind": "counter", "children": [
            {"labels": {"state": s}, "value": v}
            for s, v in zip(STATES, seconds)]}}


def test_share_of_the_window_between_two_exports():
    # before the window 100 s had passed, nearly all of them empty;
    # inside it 40 s: 30 enqueued, 2 launching, 1 staged, 3 assembling,
    # 4 empty. What came before the window does not count.
    facts = {"registry": (export([1.0, 0.5, 0.5, 1.0, 97.0]),
                          export([31.0, 2.5, 1.5, 4.0, 101.0]))}
    kw = dict(reader="registry_share",
              metric="pio_pipeline_state_seconds_total")
    starved = readers.read(facts, dict(
        kw, labels={"state": "enqueued"}, complement=True, scale=100.0))
    empty = readers.read(facts, dict(
        kw, labels={"state": "empty"}, scale=100.0))
    assert starved == pytest.approx(25.0)
    assert empty == pytest.approx(10.0)
    # the host's part is what remains
    assert starved - empty == pytest.approx(100.0 * (2 + 1 + 3) / 40)
    # a family first seen at the window's close counts from zero
    facts = {"registry": ({}, export([3.0, 0.0, 0.0, 0.0, 1.0]))}
    assert readers.read(facts, dict(
        kw, labels={"state": "empty"})) == pytest.approx(25.0)


@pytest.mark.parametrize("registry", [
    ({}, {}),                                   # a program without it
    (None, None),
    (export([1, 1, 1, 1, 1]), export([1, 1, 1, 1, 1])),  # did not move
], ids=["absent", "no-export", "did-not-move"])
def test_nothing_to_read_is_none(registry):
    spec = manifest.read_json(manifest.metric_path("host_starved_pct.sat"))
    assert readers.read({"registry": registry}, spec) is None
    assert readers.read({}, spec) is None


def test_the_new_metrics_are_in_the_manifest():
    man = manifest.load()
    assert manifest.lint(man) == []
    steady = {m["name"] for m in
              manifest.per_layer_of(man, "lj-r128.serve-steady")}
    sat = {m["name"] for m in
           manifest.per_layer_of(man, "lj-r128.serve-closed64")}
    assert {n for n in NEW if n.endswith(".steady")} <= steady
    assert {n for n in NEW if n.endswith(".sat")} <= sat
    # appended, in the issue's order, after everything that was there
    assert [m["name"] for m in man["per_layer"]][-8:] == NEW
    for name in NEW:
        entry = next(m for m in man["per_layer"] if m["name"] == name)
        spec = manifest.read_json(manifest.metric_path(name))
        assert entry["source"] == "program_counter"
        assert (entry["layer"], entry["unit"], entry["moves"]) == (
            spec["layer"], spec["unit"], spec["moves"])
        # on a program from before this PR each reads nothing, and
        # says so without raising
        assert readers.read({"registry": ({}, {})}, spec) is None
