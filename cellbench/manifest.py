"""``BENCHMARK.json`` and the files it names. Everything that belongs to
one configuration, one traffic mix or one per-layer metric is a file of
its own, found by the name in the manifest:

- ``configs[].file``                       the configuration as run
- ``cellbench/traffic/<traffic>.json``     the mix's parameters
- ``cellbench/metrics/<metric>.json``      the per-layer metric's reader

and the code a file names is a module found by that name:
``loops/<loop>.py``, ``readers/<reader>.py``, ``generators/<name>.py``.

``python -m cellbench.manifest`` lints the manifest and exits non-zero
on a fault.
"""

from __future__ import annotations

import json
import os
import re
import sys

ROOT = os.path.dirname(os.path.dirname(os.path.abspath(__file__)))
HERE = os.path.dirname(os.path.abspath(__file__))
NAME_RE = re.compile(r"^[A-Za-z0-9_][A-Za-z0-9_.\-]{0,63}$")
UNIT_RE = re.compile(r"^[A-Za-z0-9_/%.\-]{1,16}$")
SOURCES = ("device_trace", "program_span", "program_counter",
           "host_clock")


def load(root: str = ROOT) -> dict:
    with open(os.path.join(root, "BENCHMARK.json")) as f:
        return json.load(f)


def read_json(path: str) -> dict:
    with open(path) as f:
        return json.load(f)


def traffic_path(name: str) -> str:
    return os.path.join(HERE, "traffic", name + ".json")


def metric_path(name: str) -> str:
    return os.path.join(HERE, "metrics", name + ".json")


def has_module(package: str, name) -> bool:
    return isinstance(name, str) and bool(NAME_RE.match(name)) \
        and os.path.isfile(os.path.join(HERE, package, name + ".py"))


def cell(man: dict, name: str) -> dict:
    for w in man["workloads"]:
        if w["name"] == name:
            return w
    raise KeyError(f"no workload {name!r} in BENCHMARK.json")


def config_of(man: dict, cell_: dict) -> dict:
    for c in man["configs"]:
        if c["name"] == cell_["config"]:
            return c
    raise KeyError(f"no config {cell_['config']!r} in BENCHMARK.json")


def end_to_end_of(man: dict, cell_name: str) -> list:
    """The end-to-end metrics a cell reports."""
    return [m for m in man["end_to_end"]
            if "workloads" not in m or cell_name in m["workloads"]]


def per_layer_of(man: dict, cell_name: str) -> list:
    """The per-layer metrics due in a cell: those that list it, and
    those without a list whose ``moves`` target the cell reports."""
    e2e = {m["name"] for m in end_to_end_of(man, cell_name)}
    return [m for m in man["per_layer"]
            if (cell_name in m["workloads"] if "workloads" in m
                else m["moves"] in e2e)]


def lint(man: dict, root: str = ROOT) -> list:
    """Faults of the manifest, as strings; empty when it is sound."""
    bad = []
    names = set()

    def name_ok(kind, value):
        if not isinstance(value, str) or not NAME_RE.match(value):
            bad.append(f"{kind} name {value!r} is outside "
                       f"[A-Za-z0-9_.-]{{1,64}}")

    for c in man["configs"]:
        name_ok("config", c["name"])
        if not os.path.isfile(os.path.join(root, c["file"])):
            bad.append(f"config {c['name']}: file {c['file']} missing")
        for key in c["reduced"]:
            name_ok("reduced key", key)
    config_names = {c["name"] for c in man["configs"]}
    e2e_names = {m["name"] for m in man["end_to_end"]}
    cell_names = {w["name"] for w in man["workloads"]}
    for w in man["workloads"]:
        name_ok("workload", w["name"])
        name_ok("traffic", w["traffic"])
        if w["config"] not in config_names:
            bad.append(f"cell {w['name']}: config {w['config']} unknown")
        if not os.path.isfile(traffic_path(w["traffic"])):
            bad.append(f"cell {w['name']}: traffic file "
                       f"{traffic_path(w['traffic'])} missing")
        elif not has_module("loops", read_json(
                traffic_path(w["traffic"])).get("loop")):
            bad.append(f"cell {w['name']}: traffic {w['traffic']} names "
                       f"no loop under loops/")
        if w["chips"] not in (1, 4):
            bad.append(f"cell {w['name']}: chips {w['chips']!r}")
        if not 1 <= len(w["why"]) <= 200 or "\n" in w["why"]:
            bad.append(f"cell {w['name']}: why is not one line of "
                       f"1 to 200 characters")
        mine = end_to_end_of(man, w["name"])
        if not any(m["name"] == "setup_s" for m in mine) or len(mine) < 2:
            bad.append(f"cell {w['name']}: needs setup_s and one other "
                       f"end-to-end metric")
        if not per_layer_of(man, w["name"]):
            bad.append(f"cell {w['name']}: no per-layer metric")
    for m in man["end_to_end"] + man["per_layer"]:
        name_ok("metric", m["name"])
        if m["name"] in names:
            bad.append(f"metric {m['name']}: name used twice")
        names.add(m["name"])
        if not UNIT_RE.match(str(m.get("unit", ""))):
            bad.append(f"metric {m['name']}: unit {m.get('unit')!r} is "
                       f"outside [A-Za-z0-9_/%.-]{{1,16}}")
        if m.get("better") not in ("lower", "higher"):
            bad.append(f"metric {m['name']}: better {m.get('better')!r}")
        if m.get("source") not in SOURCES:
            bad.append(f"metric {m['name']}: source {m.get('source')!r}")
        for c in m.get("workloads", ()):
            if c not in cell_names:
                bad.append(f"metric {m['name']}: cell {c} unknown")
    for m in man["end_to_end"]:
        if not 0 < m.get("bound", 0) <= 0.1:
            bad.append(f"metric {m['name']}: bound {m.get('bound')!r}")
        if m["source"] not in ("host_clock", "device_trace"):
            bad.append(f"metric {m['name']}: an end-to-end metric is "
                       f"taken by host_clock or device_trace")
    for m in man["per_layer"]:
        if m["moves"] not in e2e_names:
            bad.append(f"metric {m['name']}: moves {m['moves']!r} is "
                       f"no end-to-end metric")
            continue
        if not os.path.isfile(metric_path(m["name"])):
            bad.append(f"metric {m['name']}: reader file "
                       f"{metric_path(m['name'])} missing")
        elif not has_module("readers", read_json(
                metric_path(m["name"])).get("reader")):
            bad.append(f"metric {m['name']}: its file names no reader "
                       f"under readers/")
        for c in m.get("workloads", cell_names):
            reported = {e["name"] for e in end_to_end_of(man, c)}
            if "workloads" in m and m["moves"] not in reported:
                bad.append(f"metric {m['name']}: cell {c} does not "
                           f"report {m['moves']}")
    return bad


if __name__ == "__main__":
    faults = lint(load())
    for line in faults:
        print("manifest:", line, file=sys.stderr)
    print(f"manifest: {len(faults)} fault(s)")
    sys.exit(1 if faults else 0)
