"""North-star run: MovieLens-20M (documented surrogate) through the REAL
CLI — app new → import → train → eval (VERDICT r3 task 6).

The reference's end-to-end is ``pio build && pio train && pio eval`` on
the scala-parallel-recommendation template over ml-20m
(``Evaluation.scala:32-89`` metric grid).
This script drives the same flow through ``predictionio_tpu.cli``
subprocesses: the surrogate events land in a segmentfs store via
``ptpu import``, ``ptpu train`` runs the recommendation engine at the
requested scale on the attached device, and ``ptpu eval`` runs the
shipped Precision@K grid + NDCG@10 over k folds.

Every stage is wall-clocked; the result is ONE JSON document for
BASELINE.md's real-data-vs-synthetic table.

Usage:
  python benchmarks/northstar_ml20m.py --scale 1.0 \
      [--npz /tmp/ml20m_full.npz] [--rank 64] [--eval-scale 0.1]

``--eval-scale`` bounds the k-fold grid's cost: the eval app holds a
seeded subsample of the ratings (1.0 = the full set). The train stage
always runs at --scale.
"""

import argparse
import json
import os
import subprocess
import sys
import tempfile
import time
from pathlib import Path

import numpy as np

REPO = Path(__file__).resolve().parent.parent
sys.path.insert(0, str(REPO))


def cli_env(home: Path, events_dir: Path, platform: str) -> dict:
    env = dict(os.environ)
    # APPEND to PYTHONPATH, never replace: whatever the ambient
    # PYTHONPATH carries must reach every CLI subprocess too
    pp = env.get("PYTHONPATH", "")
    env.update({
        "PIO_HOME": str(home),
        "PYTHONPATH": f"{REPO}:{pp}" if pp else str(REPO),
        # segmentfs event data (the TPU-pod backend, native codec);
        # sqlite metadata rides the default under PIO_HOME
        "PIO_STORAGE_SOURCES_SEG_TYPE": "segmentfs",
        "PIO_STORAGE_SOURCES_SEG_PATH": str(events_dir),
        "PIO_STORAGE_REPOSITORIES_EVENTDATA_SOURCE": "SEG",
    })
    if platform:
        env["JAX_PLATFORMS"] = platform
    return env


def run_cli(env: dict, *args, timeout=7200, tolerate_failure=False):
    t0 = time.monotonic()
    proc = subprocess.run(
        [sys.executable, "-m", "predictionio_tpu.cli", *args],
        env=env, capture_output=True, text=True, timeout=timeout,
        cwd=str(REPO))
    dt = time.monotonic() - t0
    if proc.returncode != 0 and not tolerate_failure:
        sys.stderr.write(f"FAILED {args}: rc={proc.returncode}\n"
                         f"{proc.stdout[-2000:]}\n{proc.stderr[-2000:]}\n")
        raise SystemExit(1)
    return proc, dt


def main():
    ap = argparse.ArgumentParser()
    ap.add_argument("--scale", type=float, default=1.0)
    ap.add_argument("--npz", default="")
    ap.add_argument("--rank", type=int, default=64)
    ap.add_argument("--iters", type=int, default=10)
    ap.add_argument("--eval-scale", type=float, default=0.1,
                    help="fraction of ratings in the eval app's store")
    ap.add_argument("--eval-k", type=int, default=2)
    ap.add_argument("--platform", default="",
                    help="JAX_PLATFORMS override ('' = leave as-is)")
    ap.add_argument("--workdir", default="")
    args = ap.parse_args()

    from benchmarks.ml20m_surrogate import (
        generate,
        verify_marginals,
        write_events_jsonl,
    )

    result: dict = {"metric": "northstar_ml20m",
                    "scale": args.scale, "rank": args.rank}

    # --- dataset ---
    t0 = time.monotonic()
    if args.npz and os.path.exists(args.npz):
        d = np.load(args.npz)
        users, items, stars, ts = (d["users"], d["items"], d["stars"],
                                   d["ts"])
        n_users, n_movies = int(d["n_users"]), int(d["n_movies"])
    else:
        users, items, stars, ts, n_users, n_movies = generate(args.scale)
    result["marginals"] = verify_marginals(users, items, stars, ts,
                                           n_users, n_movies, args.scale)
    result["gen_s"] = round(time.monotonic() - t0, 1)

    workdir = Path(args.workdir) if args.workdir else \
        Path(tempfile.mkdtemp(prefix="northstar_"))
    workdir.mkdir(parents=True, exist_ok=True)
    partial_path = workdir / "result_partial.json"
    if partial_path.exists():
        try:
            prev = json.loads(partial_path.read_text())
            # completed stage numbers survive a late-stage crash+retry
            for k2, v2 in prev.items():
                result.setdefault(k2, v2)
        except (OSError, json.JSONDecodeError):
            pass

    def checkpoint_result():
        partial_path.write_text(json.dumps(result))
    home = workdir / "pio_home"
    home.mkdir(exist_ok=True)
    events_dir = workdir / "segmentfs"
    env = cli_env(home, events_dir, args.platform)

    # --- JSONL + import through the real CLI (resumable: a completed
    # import leaves a marker so a retried run — e.g. after a failure
    # in a later stage — skips the slow stages) ---
    marker = workdir / ".import_done"
    if marker.exists():
        # keep the measured value restored from result_partial.json if
        # the import ran in an earlier attempt of this workdir
        result.setdefault("import_s", "skipped (marker present)")
    else:
        t0 = time.monotonic()
        jsonl = workdir / "events.jsonl"
        if not jsonl.exists():
            write_events_jsonl(jsonl, users, items, stars, ts)
            result["jsonl_write_s"] = round(time.monotonic() - t0, 1)

        # resume-after-mid-import-crash: the app may exist with a
        # partial chunk prefix committed — recreate it empty rather
        # than dying on "already exists" or double-importing
        run_cli(env, "app", "new", "ml20m", tolerate_failure=True)
        run_cli(env, "app", "data-delete", "ml20m", "-f",
                tolerate_failure=True)
        proc, dt = run_cli(env, "import", "--app", "ml20m",
                           "--input", str(jsonl))
        result["import_s"] = round(dt, 1)
        # `ptpu import` now also builds the columnar sidecar (the
        # one-time encode the first train used to pay); report the
        # split so the ingest rate stays comparable across rounds
        warm_s = 0.0
        for line in proc.stdout.splitlines():
            if line.startswith("Columnar sidecar ready ("):
                warm_s = float(line.split("(")[1].split("s")[0])
        result["import_columnar_warm_s"] = round(warm_s, 1)
        result["import_ev_per_s"] = round(
            len(users) / max(dt - warm_s, 1e-9), 1)
        marker.write_text("ok")
        checkpoint_result()

    # --- train via ptpu train (the full-data flagship run) ---
    variant = {
        "id": "northstar", "version": "1",
        "engineFactory":
            "predictionio_tpu.templates.recommendation:"
            "recommendation_engine",
        "datasource": {"params": {"app_name": "ml20m"}},
        "algorithms": [{
            "name": "als",
            "params": {"rank": args.rank, "num_iterations": args.iters,
                       "reg": 0.01, "seed": 3, "implicit_prefs": True,
                       "alpha": 40.0}}],
    }
    ej = workdir / "engine.json"
    ej.write_text(json.dumps(variant))
    def parse_stages(stdout: str):
        for line in stdout.splitlines():
            if line.startswith("Train stages: "):
                try:
                    return json.loads(line[len("Train stages: "):])
                except json.JSONDecodeError:
                    return None
        return None

    def needs_third(res):
        t1, t2 = res.get("train_s"), res.get("train2_s")
        return (t1 is not None and t2 is not None
                and "train3_s" not in res
                and abs(t1 - t2) / max(min(t1, t2), 1e-9) > 0.2)

    if ("train_s" in result and "train2_s" in result
            and os.environ.get("NORTHSTAR_RETRAIN") != "1"):
        # both completed train runs survive the retry — but the
        # third-sample-on-wide-spread guarantee still applies to a
        # resumed artifact
        if needs_third(result):
            proc, dt = run_cli(env, "train", "--engine-json", str(ej))
            result["train3_s"] = round(dt, 1)
            result["train3_stages"] = parse_stages(proc.stdout)
    else:
        # a forced retrain replaces ALL samples: a stale third sample
        # from a previous attempt must not suppress (or pollute) the
        # fresh spread check
        for stale in ("train3_s", "train3_stages"):
            result.pop(stale, None)
        # TWO consecutive trains: the flagship number plus its
        # run-to-run stability (VERDICT r4 weak #1: 2x variance with
        # no evidence of where the host seconds went — the per-stage
        # breakdown the CLI now prints lands in this artifact)
        proc, dt = run_cli(env, "train", "--engine-json", str(ej))
        result["train_s"] = round(dt, 1)
        result["train_stages"] = parse_stages(proc.stdout)
        result["train_ratings_per_s_per_iter"] = round(
            len(users) * args.iters / dt, 1)
        checkpoint_result()
        proc, dt = run_cli(env, "train", "--engine-json", str(ej))
        result["train2_s"] = round(dt, 1)
        result["train2_stages"] = parse_stages(proc.stdout)
        # a >20% spread gets a third sample so the artifact shows the
        # distribution, not two draws
        if needs_third(result):
            proc, dt = run_cli(env, "train", "--engine-json", str(ej))
            result["train3_s"] = round(dt, 1)
            result["train3_stages"] = parse_stages(proc.stdout)
    checkpoint_result()

    # --- deploy + query: the serving moment through the real CLI
    # (CreateServer.scala:484-633 role) — load the trained model from
    # the blob store, bind (device placement happens here), serve real
    # HTTP queries with the micro-batcher on ---
    if os.environ.get("NORTHSTAR_DEPLOY", "1") == "1" \
            and "deploy_query_p50_ms" not in result:
        import http.client
        import socket
        import urllib.request

        # a resumed run must not carry a stale failure next to fresh
        # numbers (same rule as the train3 purge above)
        result.pop("deploy_query_error", None)
        with socket.socket() as probe:  # a free port, not a guess
            probe.bind(("127.0.0.1", 0))
            port = probe.getsockname()[1]
        dp = subprocess.Popen(
            [sys.executable, "-m", "predictionio_tpu.cli", "deploy",
             "--engine-json", str(ej), "--ip", "127.0.0.1",
             "--port", str(port), "--batching"],
            env=env, cwd=str(REPO), stdout=subprocess.DEVNULL,
            stderr=subprocess.PIPE, text=True)
        # drain stderr continuously (an unread PIPE blocks the server
        # once the buffer fills) but keep the tail for diagnostics
        import threading

        err_tail: list = [""]

        def _drain():
            for line in dp.stderr:
                err_tail[0] = (err_tail[0] + line)[-300:]

        threading.Thread(target=_drain, daemon=True).start()
        try:
            t0 = time.monotonic()
            warm = False
            while time.monotonic() - t0 < 600:
                if dp.poll() is not None:  # died at startup: fail fast
                    result["deploy_query_error"] = \
                        f"deploy exited rc={dp.returncode}: " \
                        f"{err_tail[0]}"
                    break
                try:
                    st = json.loads(urllib.request.urlopen(
                        f"http://127.0.0.1:{port}/status.json",
                        timeout=5).read())
                    if st.get("servingWarm"):
                        warm = True
                        break
                except Exception:  # noqa: BLE001 — still starting
                    pass
                time.sleep(1.0)
            result["deploy_warm_s"] = round(time.monotonic() - t0, 1)
            if warm:
                lats = []
                bad = None
                try:
                    conn = http.client.HTTPConnection(
                        "127.0.0.1", port, timeout=60)
                    rng_q = np.random.default_rng(3)
                    for q in rng_q.integers(1, n_users, 60):
                        body = json.dumps({"user": str(int(q)),
                                           "num": 10}).encode()
                        t1 = time.monotonic()
                        conn.request("POST", "/queries.json",
                                     body=body,
                                     headers={"Content-Type":
                                              "application/json"})
                        out = json.loads(conn.getresponse().read())
                        if "itemScores" not in out:
                            bad = f"bad response: {str(out)[:200]}"
                            break
                        lats.append(time.monotonic() - t1)
                    conn.close()
                except Exception as qe:  # noqa: BLE001 — the deploy
                    # probe must not abort the remaining stages (eval
                    # still has to run; every other stage tolerates
                    # failure)
                    bad = f"{type(qe).__name__}: {str(qe)[:200]}"
                if bad is not None:
                    result["deploy_query_error"] = bad
                elif lats:
                    arr = np.asarray(lats[10:] or lats) * 1e3
                    result["deploy_query_p50_ms"] = round(
                        float(np.percentile(arr, 50)), 2)
                    result["deploy_query_p99_ms"] = round(
                        float(np.percentile(arr, 99)), 2)
            elif "deploy_query_error" not in result:
                result["deploy_query_error"] = "warmup timeout"
        finally:
            try:
                urllib.request.urlopen(urllib.request.Request(
                    f"http://127.0.0.1:{port}/stop", method="POST"),
                    timeout=10).read()
            except Exception:  # noqa: BLE001 — kill below regardless
                pass
            try:
                dp.wait(timeout=15)
            except subprocess.TimeoutExpired:
                dp.kill()
        checkpoint_result()

    # --- eval: shipped Precision@K grid + NDCG@10, k-fold, through
    # ptpu eval on a seeded subsample app (documented --eval-scale) ---
    if args.eval_scale > 0:
        rng = np.random.default_rng(17)
        if args.eval_scale < 1.0:
            sel = rng.random(len(users)) < args.eval_scale
        else:
            sel = np.ones(len(users), bool)
        # tolerate "already exists" on a resumed run; marker prevents
        # duplicate event import (and a pointless JSONL rewrite) on
        # retry
        run_cli(env, "app", "new", "ml20m_eval", tolerate_failure=True)
        emarker = workdir / ".eval_import_done"
        if not emarker.exists():
            ejsonl = workdir / "events_eval.jsonl"
            write_events_jsonl(ejsonl, users[sel], items[sel],
                               stars[sel], ts[sel])
            run_cli(env, "app", "data-delete", "ml20m_eval", "-f",
                    tolerate_failure=True)
            run_cli(env, "import", "--app", "ml20m_eval",
                    "--input", str(ejsonl))
            emarker.write_text("ok")
        evmod = workdir / "northstar_eval.py"
        evmod.write_text(f"""
from predictionio_tpu.controller import Evaluation
from predictionio_tpu.controller.evaluation import EngineParamsGenerator
from predictionio_tpu.controller.params import EngineParams
from predictionio_tpu.models.als import ALSParams
from predictionio_tpu.templates.recommendation import (
    DataSourceParams, NDCGAtK, PrecisionAtK, recommendation_engine)

APP = "ml20m_eval"
evaluation = Evaluation(
    engine=recommendation_engine(),
    metric=NDCGAtK(k=10, rating_threshold=2.0),
    other_metrics=[PrecisionAtK(k=1, rating_threshold=4.0),
                   PrecisionAtK(k=3, rating_threshold=4.0),
                   PrecisionAtK(k=10, rating_threshold=4.0)],
)


class _Gen(EngineParamsGenerator):
    engine_params_list = [
        EngineParams(
            datasource=("", DataSourceParams(app_name=APP,
                                             eval_k={args.eval_k})),
            algorithms=[("als", ALSParams(
                rank={args.rank}, num_iterations={args.iters}, reg=reg,
                seed=3, implicit_prefs=True, alpha=40.0))])
        for reg in (0.01, 0.1)
    ]


engine_params_generator = _Gen()
""")
        env_eval = dict(env,
                        PYTHONPATH=f"{workdir}:{env['PYTHONPATH']}")
        proc, dt = run_cli(env_eval, "eval",
                           "northstar_eval:evaluation",
                           "northstar_eval:engine_params_generator")
        result["eval_s"] = round(dt, 1)
        result["eval_scale"] = args.eval_scale
        checkpoint_result()
        out_lines = proc.stdout.strip().splitlines()
        result["eval_one_liner"] = out_lines[-1] if out_lines else \
            "(eval produced no stdout)"

    # device probe in a CHILD with the same env the CLI stages ran
    # under (reports what they actually used; every chip-using child
    # above has exited, so the chip is free), bounded so it cannot eat
    # a finished run
    try:
        probe = subprocess.run(
            [sys.executable, "-c",
             "import jax; print(jax.devices()[0].device_kind)"],
            env=env, capture_output=True, text=True, timeout=180)
        result["device"] = probe.stdout.strip().splitlines()[-1] \
            if probe.returncode == 0 and probe.stdout.strip() \
            else "unknown"
    except Exception:  # noqa: BLE001 — timeout/crash: don't die
        result["device"] = "unknown"
    result["measured_at"] = time.strftime("%Y-%m-%dT%H:%M:%SZ",
                                          time.gmtime())
    print(json.dumps(result))


if __name__ == "__main__":
    main()
