#!/usr/bin/env python3
"""chip_smoke.py — the quickest proof that the system still starts on the chip.

Drives the main path once, through the entry points a user calls:

    ptpu app new → ptpu import → ptpu train → ptpu deploy --batching
        → POST /queries.json → POST /stop

on the flagship model at full WIDTH: the recommendation template,
implicit ALS, rank 64, every entity of the ML-20M surrogate
(``benchmarks/ml20m_surrogate.py``: 138,493 users and the 25,279 of its
26,744 titles that its 20,000,263 ratings touch — a factor table has a
row per entity seen in the events, so these are the tables a full-scale
``ptpu train`` builds: [138493, 64] and [25279, 64]), default
``ALSParams`` otherwise (``history_mode`` and the solver both
``auto``).

What is CUT is depth only, to fit the 1200 s limit with compilation:

- ratings: 5,000,000 of the surrogate's 20,000,263, sampled uniformly
  after one rating per user and one per item were kept (at most 163,772
  forced picks), so that no entity — and therefore no table row — is
  lost to the sampling;
- iterations: 2 of 10.

The weights are whatever two iterations give from the seeded init; the
data is generated from ``SEED``. The resulting table shapes are printed.

One process owns the chip at a time. This parent never initialises a
JAX backend: every stage is its own ``python -m predictionio_tpu.cli``
child, one after another (``train`` has exited before ``deploy``
starts); ``deploy --batching`` is a child the parent talks to over HTTP
and stops with ``POST /stop``. Reading the persisted factors back runs
in a child pinned to the CPU.

It exits non-zero — and prints no result line — when: JAX finds no TPU
(it has no CPU mode); a stage exits non-zero; the train or deploy child
reports a backend other than ``tpu``; HBM in use after bind is below
the size of the two tables; the warm-up logged a failure; a compile
happened after warm-up; a returned top-10 disagrees with plain numpy
``U[u] @ V.T`` (descending, ties to the lowest id) over the factors
``ptpu train`` persisted; or any factor is non-finite.

Tolerance of the comparison. f32 matmuls on the chip do not run at f32
precision by default (inputs are rounded to bf16, products accumulate
in f32), and the program's precision is not raised to tighten this. A
score ``s_j = Σ_i u_i·v_ji`` computed that way is off by at most
``2^-8·Σ_i |u_i·v_ji|`` (two roundings of relative error 2^-9 each); the
check allows ``2^-7·Σ_i |u_i·v_ji|`` per score. Ids are compared as
sets: a returned id that is not in the reference top-10 must be within
that bound of the reference's 10th score (a tie the rounding may order
either way). The largest deviation seen is printed, absolute and as a
share of the bound.

With more than one device reported by the deploy child, deploy and
queries are repeated with ``--serving-mode replicated`` and every lane
must have served.

Last two lines of stdout on success: the run's JSON summary (shapes,
what ``auto`` resolved to, stage seconds, ..., ending ``"claim": null``),
then the result line, one JSON object with exactly these keys:
``{"ok": true, "device": {"platform": ..., "kind": ..., "count": ...}}``,
the device as JAX reports it.
"""

import http.client
import json
import os
import re
import shutil
import socket
import subprocess
import sys
import threading
import time
from pathlib import Path

REPO = Path(__file__).resolve().parent
WORK = REPO / "build" / "chip_smoke"   # git-ignored scratch, removed at exit

SEED = 20
#: entities the surrogate's ratings touch (of 138,493 x 26,744 nominal)
N_USERS, N_ITEMS, RANK = 138_493, 25_279, 64
N_RATINGS = 5_000_000
ITERATIONS = 2
TOP_N = 10
SEQUENTIAL_QUERIES = 24
BURST_QUERIES = 96           # sent at one instant, so batches above 8 form
BURST_ROUNDS = 6             # at most; stops at the first batch above 8
ENGINE_ID = "chip_smoke"
#: allowed |chip − numpy| per score, as a multiple of Σ|u_i·v_ji|
SCORE_TOL = 2.0 ** -7


class SmokeFailure(Exception):
    """A phase failed; the message says which and why."""


def say(msg: str) -> None:
    print(f"[chip_smoke] {msg}", flush=True)


def require(cond: bool, msg: str) -> None:
    if not cond:
        raise SmokeFailure(msg)


# -- children ----------------------------------------------------------------

def child_env(**extra: str) -> dict:
    env = dict(os.environ)
    pp = env.get("PYTHONPATH", "")
    env.update({
        "PIO_HOME": str(WORK / "pio_home"),
        "PYTHONPATH": f"{REPO}:{pp}" if pp else str(REPO),
        # segmentfs event data (native bulk import), sqlite metadata
        "PIO_STORAGE_SOURCES_SEG_TYPE": "segmentfs",
        "PIO_STORAGE_SOURCES_SEG_PATH": str(WORK / "segmentfs"),
        "PIO_STORAGE_REPOSITORIES_EVENTDATA_SOURCE": "SEG",
    })
    env.update(extra)
    return env


def run_child(label: str, argv: list, env: dict, timeout: float) -> str:
    """Run one child to its end; a non-zero exit fails the smoke."""
    t0 = time.monotonic()
    proc = subprocess.run(argv, env=env, cwd=str(REPO),
                          capture_output=True, text=True, timeout=timeout)
    dt = time.monotonic() - t0
    if proc.returncode != 0:
        raise SmokeFailure(
            f"{label} exited {proc.returncode} after {dt:.1f}s\n"
            f"--- stdout ---\n{proc.stdout[-3000:]}\n"
            f"--- stderr ---\n{proc.stderr[-6000:]}")
    STAGES[label] = round(dt, 1)
    say(f"{label}: {dt:.1f}s")
    return proc.stdout


def ptpu(label: str, *args: str, timeout: float = 900.0) -> str:
    return run_child(
        label, [sys.executable, "-m", "predictionio_tpu.cli", *args],
        child_env(), timeout)


STAGES: dict = {}


# -- phases ------------------------------------------------------------------

def find_tpu() -> dict:
    """Fail at once where there is no TPU: first on the environment,
    then by asking JAX in a child that exits before any stage runs."""
    plats = os.environ.get("JAX_PLATFORMS", "").strip().lower()
    require(not plats or "tpu" in plats.split(","),
            f"no TPU: JAX_PLATFORMS={plats!r} holds JAX off the chip, and "
            f"this script has no CPU mode")
    out = run_child(
        "device probe",
        [sys.executable, "-c",
         "import json, jax; d = jax.devices(); print(json.dumps("
         "{'platform': d[0].platform, 'kind': d[0].device_kind, "
         "'count': len(d)}))"],
        dict(os.environ), timeout=300)
    device = json.loads(out.strip().splitlines()[-1])
    require(device["platform"] == "tpu",
            f"no TPU: JAX found {device}, and this script has no CPU mode")
    say(f"device: {device['kind']} x{device['count']} "
        f"(platform {device['platform']})")
    return device


def make_events(path: Path) -> None:
    """The surrogate at full scale from SEED, cut to N_RATINGS by
    uniform sampling after a cover of every user and item, written as
    import-ready JSONL in bulk."""
    import numpy as np

    from benchmarks.ml20m_surrogate import generate

    t0 = time.monotonic()
    users, items, stars, ts, n_users, n_items = generate(1.0, seed=SEED)
    require((len(np.unique(users)), len(np.unique(items)))
            == (N_USERS, N_ITEMS),
            f"surrogate entity counts changed: {n_users} x {n_items} "
            f"nominal, {len(np.unique(users))} x {len(np.unique(items))} "
            f"rated")
    rng = np.random.default_rng(SEED)
    order = rng.permutation(len(users))
    # first rating (in a random order) of each user and of each item
    _, first_u = np.unique(users[order], return_index=True)
    _, first_i = np.unique(items[order], return_index=True)
    keep = np.zeros(len(users), dtype=bool)
    keep[order[first_u]] = True
    keep[order[first_i]] = True
    forced = int(keep.sum())
    rest = order[~keep[order]]
    keep[rest[:N_RATINGS - forced]] = True
    sel = np.flatnonzero(keep)
    users, items, stars, ts = users[sel], items[sel], stars[sel], ts[sel]
    require(len(users) == N_RATINGS, f"sampled {len(users)} ratings")
    require(len(np.unique(users)) == N_USERS
            and len(np.unique(items)) == N_ITEMS,
            "the sample lost an entity")
    when = np.datetime_as_string(ts.astype("datetime64[s]"), unit="ms")
    with open(path, "w") as f:
        chunk = 500_000
        for s in range(0, len(users), chunk):
            e = min(s + chunk, len(users))
            f.write("".join(
                '{"event":"rate","entityType":"user","entityId":"%d",'
                '"targetEntityType":"item","targetEntityId":"%d",'
                '"properties":{"rating":%.1f},"eventTime":"%sZ"}\n'
                % row for row in zip(users[s:e].tolist(),
                                     items[s:e].tolist(),
                                     stars[s:e].tolist(),
                                     when[s:e].tolist())))
    STAGES["generate"] = round(time.monotonic() - t0, 1)
    say(f"generate: {STAGES['generate']}s — {N_RATINGS:,} of "
        f"{len(order):,} ratings ({forced:,} forced to cover every "
        f"entity), {N_USERS:,} users x {N_ITEMS:,} items")


def train(engine_json: Path) -> dict:
    out = ptpu("train", "train", "--engine-json", str(engine_json),
               timeout=1000)
    found = {}
    for line in out.splitlines():
        for label, key in (("Train stages: ", "stages"),
                           ("Train build info: ", "build"),
                           ("Train kernels: ", "kernels")):
            if line.startswith(label):
                found[key] = json.loads(line[len(label):])
    require({"stages", "build", "kernels"} <= set(found),
            f"train printed no stages/build info/kernels:\n{out[-2000:]}")
    require(found["build"].get("backend") == "tpu",
            f"train ran on {found['build']}, not on a TPU")
    k = found["kernels"]
    say(f"train backend: {found['build']}")
    say(f"train resolved: solver {k['solver']}, layout {k['layout']}")
    say(f"train stages: {found['stages']}")
    return found


def read_factors(engine_json: Path) -> dict:
    """The factors ``ptpu train`` persisted, read back by a child
    pinned to the CPU (it must not touch the chip)."""
    import numpy as np

    out_npz = WORK / "factors.npz"
    run_child(
        "read factors",
        [sys.executable, "-c", f"""
import numpy as np
from predictionio_tpu.data.storage.registry import get_storage
from predictionio_tpu.workflow import persistence
st = get_storage()
inst = st.engine_instances().get_latest_completed(
    {ENGINE_ID!r}, "1", {str(engine_json)!r})
m = persistence.loads_models(st.models().get(inst.id).models)[0]
uinv, iinv = m.user_ids.inverse, m.item_ids.inverse
np.savez({str(out_npz)!r},
         U=np.asarray(m.user_factors), V=np.asarray(m.item_factors),
         n_users=m.n_users, n_items=m.n_items,
         users=np.array([uinv[i] for i in range(m.n_users)]),
         items=np.array([iinv[i] for i in range(m.n_items)]))
"""],
        child_env(JAX_PLATFORMS="cpu"), timeout=300)
    d = np.load(out_npz)
    U, V = d["U"][:int(d["n_users"])], d["V"][:int(d["n_items"])]
    require(U.shape == (N_USERS, RANK) and V.shape == (N_ITEMS, RANK),
            f"table shapes {U.shape} / {V.shape}, expected "
            f"({N_USERS}, {RANK}) / ({N_ITEMS}, {RANK})")
    require(U.dtype == np.float32 and V.dtype == np.float32,
            f"factor dtypes {U.dtype} / {V.dtype}")
    require(bool(np.isfinite(U).all() and np.isfinite(V).all()),
            "non-finite factors")
    require(float(np.abs(U).max()) > 0 and float(np.abs(V).max()) > 0,
            "all-zero factors")
    say(f"factor tables: U {list(U.shape)} V {list(V.shape)} float32, "
        f"finite, |U|max {np.abs(U).max():.3f} |V|max "
        f"{np.abs(V).max():.3f}")
    return {"U": U, "V": V,
            "user_index": {u: i for i, u in enumerate(d["users"].tolist())},
            "item_index": {v: i for i, v in enumerate(d["items"].tolist())}}


def http_json(port: int, method: str, path: str, body=None,
              timeout: float = 60.0, before_send=None):
    """One request to the deploy child; ``before_send`` runs between
    connecting and sending (the burst's starting gate)."""
    conn = http.client.HTTPConnection("127.0.0.1", port, timeout=timeout)
    try:
        conn.connect()
        if before_send is not None:
            before_send()
        conn.request(method, path,
                     body=None if body is None else json.dumps(body),
                     headers={"Content-Type": "application/json"})
        resp = conn.getresponse()
        raw = resp.read()
        require(resp.status == 200,
                f"{method} {path} -> {resp.status}: {raw[:300]!r}")
        return json.loads(raw) if raw.startswith(b"{") else raw.decode()
    finally:
        conn.close()


def check_answer(f: dict, user: str, answer: dict, worst: dict) -> None:
    """One returned top-10 against plain numpy over the persisted
    factors (module docstring: tolerance, ids as sets)."""
    import numpy as np

    got = answer.get("itemScores")
    require(isinstance(got, list) and len(got) == TOP_N,
            f"user {user}: bad answer {str(answer)[:200]}")
    u = f["U"][f["user_index"][user]].astype(np.float64)
    V = f["V"].astype(np.float64)
    scores = V @ u
    bound = SCORE_TOL * (np.abs(V) @ np.abs(u))
    # descending score, ties to the lowest id
    ref = np.lexsort((np.arange(len(scores)), -scores))[:TOP_N]
    ids = [f["item_index"][s["item"]] for s in got]
    require(len(set(ids)) == TOP_N, f"user {user}: repeated ids {ids}")
    for j, s in zip(ids, got):
        dev = abs(float(s["score"]) - scores[j])
        require(dev <= bound[j],
                f"user {user} item {j}: score {s['score']} vs numpy "
                f"{scores[j]:.6f}, off by {dev:.2e} > bound {bound[j]:.2e}")
        if dev > worst["abs"]:
            worst["abs"] = dev
        worst["share"] = max(worst["share"], dev / bound[j])
    cut = scores[ref[-1]]
    for j in set(ids) - set(ref.tolist()):
        require(cut - scores[j] <= bound[j] + bound[ref[-1]],
                f"user {user}: returned item {j} scores {scores[j]:.6f}, "
                f"below the reference's 10th ({cut:.6f}) by more than "
                f"the rounding bound")
    worst["swapped"] += len(set(ids) - set(ref.tolist()))


def burst(port: int, users: list, label: str) -> dict:
    """All of ``users`` asked at the same instant: every thread opens
    its connection first and they send together, so the batcher has a
    crowd to coalesce."""
    answers, errors = {}, []
    gate = threading.Barrier(len(users))

    def ask(user: str) -> None:
        try:
            answers[user] = http_json(
                port, "POST", "/queries.json",
                {"user": user, "num": TOP_N}, timeout=120,
                before_send=lambda: gate.wait(timeout=60))
        except Exception as e:  # noqa: BLE001 — re-raised by the caller
            errors.append(f"{user}: {type(e).__name__}: {e}")

    threads = [threading.Thread(target=ask, args=(u,)) for u in users]
    for t in threads:
        t.start()
    for t in threads:
        t.join(timeout=180)
    require(not errors and not any(t.is_alive() for t in threads),
            f"{label}: burst queries failed: {errors[:3]}")
    return answers


def metric_values(metrics: str, name: str) -> dict:
    """``{label-string: value}`` of one family in a /metrics page."""
    out = {}
    for m in re.finditer(rf"^{name}(\{{[^}}]*\}})? ([0-9.eE+-]+)$",
                         metrics, re.M):
        out[m.group(1) or ""] = float(m.group(2))
    return out


def deploy_and_query(engine_json: Path, f: dict, label: str,
                     extra_args: tuple = ()) -> dict:
    """One ``ptpu deploy --batching`` child: wait for a clean warm-up,
    check it serves from HBM on a TPU, query it, check the answers and
    that nothing compiled under traffic, stop it."""
    import numpy as np

    with socket.socket() as probe:
        probe.bind(("127.0.0.1", 0))
        port = probe.getsockname()[1]
    log_path = WORK / f"{label.replace(' ', '_')}.log"
    t0 = time.monotonic()
    with open(log_path, "w") as log:
        child = subprocess.Popen(
            [sys.executable, "-m", "predictionio_tpu.cli", "deploy",
             "--engine-json", str(engine_json), "--ip", "127.0.0.1",
             "--port", str(port), "--batching", *extra_args],
            env=child_env(), cwd=str(REPO), stdout=log,
            stderr=subprocess.STDOUT)
    try:
        status = None
        while time.monotonic() - t0 < 900:
            require(child.poll() is None,
                    f"{label} exited {child.returncode} before it was "
                    f"warm:\n{log_path.read_text()[-6000:]}")
            try:
                status = http_json(port, "GET", "/status.json", timeout=5)
            except (OSError, SmokeFailure):
                status = None  # still binding
            if status and (status.get("servingWarm")
                           or status.get("warmReport", {}).get("error")):
                break
            time.sleep(1.0)
        require(bool(status) and status.get("servingWarm"),
                f"{label} did not warm up: "
                f"{(status or {}).get('warmReport')}\n"
                f"{log_path.read_text()[-4000:]}")
        STAGES[f"{label} warm"] = round(time.monotonic() - t0, 1)
        say(f"{label} warm: {STAGES[f'{label} warm']}s, "
            f"warmReport {status['warmReport'].get('seconds')}")
        require("warmup failed" not in log_path.read_text(),
                f"{label}: servingWarm came up after a logged warm-up "
                f"failure:\n{log_path.read_text()[-4000:]}")

        # a TPU process, serving from HBM
        metrics = http_json(port, "GET", "/metrics")
        build = next(iter(metric_values(metrics, "pio_build_info")), "")
        require('backend="tpu"' in build,
                f"{label} reports pio_build_info{build}, not a TPU")
        hbm = status["hbm"]
        require(bool(hbm) and all(str(h["kind"]).startswith("TPU")
                                  for h in hbm),
                f"{label} hbm block names no TPU: {hbm}")
        table_bytes = (N_USERS + N_ITEMS) * RANK * 4
        # replicated lanes each hold a full copy; otherwise device 0 does
        holders = hbm if status["mesh"].get("mode") == "replicated" \
            else hbm[:1]
        in_use = min(h["bytesInUse"] for h in holders)
        require(in_use >= table_bytes,
                f"{label}: {in_use} B of HBM in use after bind, below "
                f"the two tables' {table_bytes} B — not serving from HBM "
                f"({[h['bytesInUse'] for h in hbm]})")
        kern = status["servingKernel"]
        say(f"{label}: {len(hbm)} device(s) {hbm[0]['kind']}, HBM in use "
            f"{in_use / 2**20:.1f} MiB (tables {table_bytes / 2**20:.1f}"
            f" MiB); quant {kern['configuredQuant']} -> {kern['quant']}; "
            f"mesh {status['mesh'].get('mode')}")

        # queries: one at a time, then concurrent bursts until the
        # batcher has formed a batch above 8 (a burst usually does at
        # once; how requests coalesce is up to thread scheduling)
        rng = np.random.default_rng(SEED + 1)
        worst = {"abs": 0.0, "share": 0.0, "swapped": 0}
        asked = 0
        t1 = time.monotonic()
        for user in rng.integers(0, N_USERS, SEQUENTIAL_QUERIES):
            check_answer(f, str(int(user)), http_json(
                port, "POST", "/queries.json",
                {"user": str(int(user)), "num": TOP_N}), worst)
            asked += 1
        for _ in range(BURST_ROUNDS):
            users = sorted({str(int(u)) for u in
                            rng.integers(0, N_USERS, BURST_QUERIES)})
            for user, answer in burst(port, users, label).items():
                check_answer(f, user, answer, worst)
            asked += len(users)
            metrics = http_json(port, "GET", "/metrics")
            occ = metric_values(metrics, "pio_batch_occupancy_bucket")
            upto8 = next((v for k, v in occ.items() if 'le="8"' in k),
                         None)
            batches = sum(metric_values(
                metrics, "pio_batch_occupancy_count").values())
            if upto8 is not None and batches - upto8 > 0:
                break
        STAGES[f"{label} queries"] = round(time.monotonic() - t1, 1)
        require(upto8 is not None and batches - upto8 > 0,
                f"{label}: no batch above 8 formed in {BURST_ROUNDS} "
                f"bursts ({batches} batches, {upto8} of them <= 8)")

        after = http_json(port, "GET", "/status.json")
        recompiles = after["recompile"]["compilesSinceWarm"]
        require(after["recompile"]["armed"] and recompiles == 0,
                f"{label}: {recompiles} compile(s) after warm-up "
                f"({after['recompile']})")
        say(f"{label}: {asked} answers match numpy "
            f"(largest deviation {worst['abs']:.2e} = "
            f"{100 * worst['share']:.0f}% of the bound; "
            f"{worst['swapped']} id(s) swapped inside it), "
            f"{int(batches)} batches of which {int(batches - upto8)} "
            f"above 8, compilesSinceWarm {recompiles}")
        lanes = metric_values(metrics, "pio_lane_dispatches_total")
        report = {"devices": len(hbm), "kind": hbm[0]["kind"],
                  "quant": kern["quant"], "lanes": lanes,
                  "worst_abs": worst["abs"], "worst_share": worst["share"]}
        http_json(port, "POST", "/stop")
        require(child.wait(timeout=60) == 0,
                f"{label} exited {child.returncode} after /stop")
        return report
    finally:
        if child.poll() is None:
            child.kill()
            child.wait(timeout=30)


def cache_entries() -> tuple:
    from predictionio_tpu.utils.platform import COMPILE_CACHE_DIR

    d = os.environ.get("JAX_COMPILATION_CACHE_DIR") or COMPILE_CACHE_DIR
    n = sum(1 for p in Path(d).iterdir() if p.name.endswith("-cache")) \
        if os.path.isdir(d) else 0
    return d, n


def result_line(device: dict) -> str:
    """The last line of stdout on success: these keys and no others."""
    return json.dumps({"ok": True, "device": {
        "platform": str(device["platform"]), "kind": str(device["kind"]),
        "count": int(device["count"])}})


def main() -> int:
    require((REPO / "predictionio_tpu").is_dir()
            and (REPO / "benchmarks" / "ml20m_surrogate.py").is_file(),
            f"{REPO} is not a checkout of the repository")
    sys.path.insert(0, str(REPO))
    device = find_tpu()
    cache_dir, cache_before = cache_entries()

    shutil.rmtree(WORK, ignore_errors=True)
    WORK.mkdir(parents=True)
    try:
        return run(device, cache_dir, cache_before)
    finally:
        shutil.rmtree(WORK, ignore_errors=True)  # ~1 GB of events


def run(device: dict, cache_dir: str, cache_before: int) -> int:
    events = WORK / "events.jsonl"
    make_events(events)
    engine_json = WORK / "engine.json"
    engine_json.write_text(json.dumps({
        "id": ENGINE_ID, "version": "1",
        "engineFactory": "predictionio_tpu.templates.recommendation:"
                         "recommendation_engine",
        "datasource": {"params": {"app_name": ENGINE_ID}},
        "algorithms": [{"name": "als", "params": {
            "rank": RANK, "num_iterations": ITERATIONS, "reg": 0.01,
            "seed": 3, "implicit_prefs": True, "alpha": 40.0}}],
    }))

    ptpu("app new", "app", "new", ENGINE_ID)
    ptpu("import", "import", "--app", ENGINE_ID, "--input", str(events))
    trained = train(engine_json)
    factors = read_factors(engine_json)
    served = deploy_and_query(engine_json, factors, "deploy")
    require(served["devices"] == device["count"],
            f"deploy saw {served['devices']} device(s), JAX {device}")
    replicated = None
    if served["devices"] > 1:
        replicated = deploy_and_query(
            engine_json, factors, "deploy replicated",
            ("--serving-mode", "replicated"))
        lanes = replicated["lanes"]
        require(len(lanes) == served["devices"]
                and all(v > 0 for v in lanes.values()),
                f"replicated: not every lane served: {lanes}")
        say(f"replicated: every lane served: {lanes}")

    _, cache_after = cache_entries()
    say(f"compile cache {cache_dir}: {cache_before} entries before, "
        f"{cache_after} after ({cache_after - cache_before} added)")
    say(f"stage seconds: {STAGES}")
    print("[chip_smoke] summary: " + json.dumps({
        "device": device,
        "shapes": {"user_factors": [N_USERS, RANK],
                   "item_factors": [N_ITEMS, RANK],
                   "ratings": N_RATINGS, "iterations": ITERATIONS},
        "resolved": {"solver": trained["kernels"]["solver"],
                     "serving_quant": served["quant"]},
        "replicated_lanes": None if replicated is None
        else len(replicated["lanes"]),
        "largest_score_deviation": served["worst_abs"],
        "stage_seconds": STAGES,
        "compile_cache_entries_added": cache_after - cache_before,
        "claim": None,
    }))
    print(result_line(device), flush=True)
    return 0


if __name__ == "__main__":
    try:
        sys.exit(main())
    except SmokeFailure as e:
        print(f"[chip_smoke] FAILED: {e}", file=sys.stderr, flush=True)
        sys.exit(1)
