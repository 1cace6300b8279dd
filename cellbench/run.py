"""Run one cell once.

    python -m cellbench.run --workload <name> --seed <n> --seconds <s> --trace <0|1>

Stages, each stamped with ``time.perf_counter`` and printed as
``stage_seconds``: ``attach`` (the chip answers), ``inputs`` (what the
benchmark manufactures from the seed), ``setup`` (everything the SYSTEM
does before the window: the end-to-end metric ``setup_s``), the timed
window, and the output check outside every clock. The last line of
standard output is the result; everything else is on earlier lines.

The kinds of loop (``loops/<loop>.py``), the readers
(``readers/<reader>.py``) and the generators (``generators/<name>.py``)
are the only code, each found by the name a data file gives: a
configuration, a traffic mix and a per-layer metric are files that
``BENCHMARK.json`` names.
"""

from __future__ import annotations

import argparse
import importlib
import json
import os
import sys
import time

from . import manifest, readers

def say(tag: str, obj) -> None:
    print(tag, json.dumps(obj, default=float), flush=True)


class Stamps(dict):
    """Stage seconds; ``with stamps.stage("pack"):`` adds to a stage."""

    def stage(self, name: str):
        stamps = self

        class _Stage:
            def __enter__(self):
                self.t = time.perf_counter()

            def __exit__(self, *exc):
                stamps[name] = stamps.get(name, 0.0) \
                    + time.perf_counter() - self.t
        return _Stage()


def attach(cell: dict, rehearse: bool, stamps: Stamps):
    """``import jax``, list the devices, fetch one trivial dispatch.
    Nothing of the program is imported before this returns."""
    root = manifest.ROOT
    if rehearse:
        os.environ["JAX_PLATFORMS"] = "cpu"
    if not os.environ.get("JAX_COMPILATION_CACHE_DIR"):
        # a fixed path inside the checkout: the path is part of the
        # cache's key. The program takes the variable where it is set.
        os.environ["JAX_COMPILATION_CACHE_DIR"] = os.path.join(
            root, ".jax_cache")
    with stamps.stage("attach"):
        import jax
        import jax.numpy as jnp

        devs = jax.devices()
        if devs[0].platform != "cpu":
            jax.config.update("jax_persistent_cache_min_entry_size_bytes", -1)
            jax.config.update(
                "jax_persistent_cache_min_compile_time_secs", 0.0)
        float(jax.jit(lambda a: a + 1)(jnp.zeros((8, 128)))[0, 0])
    if not rehearse and devs[0].platform != "tpu":
        sys.exit(f"cellbench: no TPU (JAX found {devs[0].platform}); "
                 f"there is no CPU fall-back, see --rehearse")
    if len(devs) < cell["chips"]:
        sys.exit(f"cellbench: cell {cell['name']} needs {cell['chips']} "
                 f"chip(s), JAX found {len(devs)}")
    return devs[:cell["chips"]]


def device_block(devs, memory_peak=None, traced=None) -> dict:
    out = {"platform": devs[0].platform, "kind": devs[0].device_kind,
           "count": len(devs), "memory_peak_bytes": memory_peak}
    if traced:
        out["busy_s"] = traced["busy_s"]
        out["window_s"] = traced["window_s"]
    return out


def memory_peak(devs):
    peaks = [(d.memory_stats() or {}).get("peak_bytes_in_use") for d in devs]
    peaks = [p for p in peaks if p is not None]
    return max(peaks) if peaks else None


def main(argv=None) -> int:
    ap = argparse.ArgumentParser(prog="cellbench.run")
    ap.add_argument("--workload", required=True)
    ap.add_argument("--seed", type=int, required=True)
    ap.add_argument("--seconds", type=float, required=True)
    ap.add_argument("--trace", type=int, choices=(0, 1), default=0)
    ap.add_argument("--rehearse", action="store_true",
                    help="tiny sizes on the CPU; prints no device metric")
    args = ap.parse_args(argv)

    man = manifest.load()
    cell = manifest.cell(man, args.workload)
    if not os.path.isdir(os.path.join(manifest.ROOT, "predictionio_tpu")):
        sys.exit("cellbench: the system under test (predictionio_tpu/) "
                 "is not in this directory")
    config = manifest.read_json(os.path.join(
        manifest.ROOT, manifest.config_of(man, cell)["file"]))
    traffic = manifest.read_json(manifest.traffic_path(cell["traffic"]))
    if args.rehearse:
        config = {**config, **config.get("rehearse", {})}
        traffic = {**traffic, **traffic.get("rehearse", {})}
    stamps = Stamps()
    devs = attach(cell, args.rehearse, stamps)
    loop = importlib.import_module(
        f"{__package__}.loops.{traffic['loop']}")
    run = loop.Cell(config=config, traffic=traffic, seed=args.seed,
                    seconds=args.seconds, traced=bool(args.trace),
                    stamps=stamps, devs=devs, say=say,
                    cache_dir=os.path.join(manifest.ROOT,
                                           ".cellbench_cache"))
    try:
        with stamps.stage("inputs"):
            run.inputs()
        with stamps.stage("setup"):
            run.setup()
        say("stage_seconds", {**stamps, "wall_before_window":
                              stamps["attach"] + stamps["inputs"]
                              + stamps["setup"]})
        run.window()
        peak = memory_peak(devs)
        checks = run.check()
    finally:
        run.close()

    correct = True
    for c in checks:
        ok = bool(c["value"] <= c["limit"])
        correct &= ok
        say("check", {**c, "ok": ok})
    facts = run.facts
    facts["stamps"] = dict(stamps)
    facts["device"] = {"kind": devs[0].device_kind,
                       "memory_peak_bytes": peak}
    measured = {**run.end_to_end(), "setup_s": stamps["setup"]}
    units = {m["name"]: m["unit"]
             for m in man["end_to_end"] + man["per_layer"]}
    metrics = {}
    if not args.trace:
        for m in manifest.end_to_end_of(man, cell["name"]):
            if m["name"] not in measured:
                sys.exit(f"cellbench: loop {traffic['loop']!r} does not "
                         f"measure {m['name']}")
            metrics[m["name"]] = measured[m["name"]]
    else:
        say("traced_run_rate", {"end_to_end_of_this_run": measured,
                                **run.traced_rates()})
        for m in manifest.per_layer_of(man, cell["name"]):
            spec = manifest.read_json(manifest.metric_path(m["name"]))
            value = readers.read(facts, spec)
            if value is not None:
                if m["unit"] == "%" and not -1e-6 <= value <= 100.0001:
                    sys.exit(f"cellbench: {m['name']} = {value} % is "
                             f"outside 0..100: the work or the time is "
                             f"counted wrong")
                metrics[m["name"]] = value
        if facts.get("roofline_bound"):
            say("roofline_bound", facts["roofline_bound"])
    result = {
        "correct": bool(correct),
        "attempted": int(run.attempted), "failed": int(run.failed),
        "metrics": {k: {"value": float(v), "unit": units[k]}
                    for k, v in metrics.items()},
        "device": device_block(devs, peak, facts.get("trace")
                               if args.trace else None),
    }
    if args.rehearse:
        # a CPU run yields no time, rate or share of the device
        say("rehearsal_values_not_device_metrics", result["metrics"])
        result["metrics"] = {}
        result["rehearsal"] = True
    if args.trace and "breakdown" in facts:
        result["breakdown"] = facts["breakdown"]
    print(json.dumps(result), flush=True)
    return 0


if __name__ == "__main__":
    sys.exit(main())
