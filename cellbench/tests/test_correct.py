"""``correct`` has been shown to fail: the lower-precision controls and
a broken timed path, each driven through the rest of a run at the
rehearsal's size on the CPU (the harness's look for a chip is skipped by
``--rehearse``). The controls at the cells' own sizes were run on the
chip; PERF.md has their readings."""

import json
import os

import numpy as np
import pytest

from cellbench import manifest, run


def run_cell(capsys, workload, seed=11, seconds=3):
    rc = run.main(["--workload", workload, "--seed", str(seed),
                   "--seconds", str(seconds), "--trace", "0", "--rehearse"])
    assert rc == 0
    lines = [ln for ln in capsys.readouterr().out.splitlines() if ln]
    checks = {}
    for ln in lines:
        if ln.startswith("check "):
            c = json.loads(ln[len("check "):])
            checks[c["name"]] = c
        elif ln.startswith("window "):
            checks["window"] = json.loads(ln[len("window "):])
    return json.loads(lines[-1]), checks


def with_config(monkeypatch, **extra):
    """The configuration as run, with a field of the program's own
    lower-precision path switched on."""
    real = manifest.read_json

    def patched(path):
        cfg = real(path)
        if "als-" in os.path.basename(path):
            for group, fields in extra.items():
                cfg[group] = {**cfg.get(group, {}), **fields}
        return cfg

    monkeypatch.setattr(manifest, "read_json", patched)


def test_sound_training_is_correct(capsys, train_cell):
    result, checks = run_cell(capsys, "ml20m-r64.train")
    assert result["correct"] is True and result["metrics"] == {}
    assert result["device"]["platform"] == "cpu"
    assert set(checks) == {"item_row_residual_mean", "user_row_gap_mean"}


def test_bf16_gather_control_fails_training(capsys, monkeypatch, train_cell):
    with_config(monkeypatch, params={"gather_dtype": "bfloat16"})
    result, checks = run_cell(capsys, "ml20m-r64.train")
    assert result["correct"] is False
    assert not checks["item_row_residual_mean"]["ok"]


def test_training_that_returns_its_state_unchanged_fails(
        capsys, monkeypatch, train_cell):
    from predictionio_tpu.models import als

    def unchanged(ratings, params, packed=None, **kw):
        import jax

        ku, ki = jax.random.split(jax.random.key(params.seed))
        return (als._init_factors(ku, packed.n_users, packed.n_users,
                                  params.rank),
                als._init_factors(ki, packed.n_items, packed.n_items,
                                  params.rank))

    monkeypatch.setattr(als, "train_als", unchanged)
    result, checks = run_cell(capsys, "ml20m-r64.train")
    assert result["correct"] is False
    assert not checks["item_row_residual_mean"]["ok"]
    assert not checks["user_row_gap_mean"]["ok"]


def test_sound_serving_is_correct(capsys):
    result, checks = run_cell(capsys, "lj-r128.serve-closed64")
    assert result["correct"] is True and result["failed"] == 0
    assert checks["answers_not_compared"]["value"] == 0
    # no client sends before the window opens, and the rate counts the
    # answers completed inside it and no others
    w = checks["window"]
    assert w["done_before_window"] == 0
    assert w["served_qps"] * 3 <= sum(w["done_per_second"]) + 1e-6
    assert w["served_qps"] * 3 >= sum(w["done_per_second"]) - 1


@pytest.mark.parametrize("quant", ["bf16", "int8"])
def test_quantised_serving_control_fails(capsys, monkeypatch, quant):
    with_config(monkeypatch, server={"serving_quant": quant})
    result, checks = run_cell(capsys, "lj-r128.serve-steady")
    assert result["correct"] is False
    assert not checks["score_gap_max"]["ok"]


def test_an_answer_altered_where_it_is_produced_fails(capsys, monkeypatch):
    from predictionio_tpu.models import als

    real = als.recommend_batch_async

    def altered(model, user_indices, k):
        resolve = real(model, user_indices, k)

        def shifted():
            ids, scores = resolve()
            return (np.asarray(ids) + 1) % model.n_items, scores
        return shifted

    monkeypatch.setattr(als, "recommend_batch_async", altered)
    result, checks = run_cell(capsys, "lj-r128.serve-closed64")
    assert result["correct"] is False
    assert not checks["score_gap_max"]["ok"]


def test_one_failed_request_fails_the_run(capsys, monkeypatch):
    from predictionio_tpu.models import als

    real, calls = als.recommend_batch_async, []

    def failing(model, user_indices, k):
        calls.append(1)
        if len(calls) == 40:
            raise RuntimeError("planted fault")
        return real(model, user_indices, k)

    monkeypatch.setattr(als, "recommend_batch_async", failing)
    result, checks = run_cell(capsys, "lj-r128.serve-closed64")
    assert result["failed"] >= 1 and result["correct"] is False
    assert not checks["failed_requests"]["ok"]


def test_no_tpu_means_no_result(capsys, monkeypatch):
    monkeypatch.setenv("JAX_PLATFORMS", "cpu")
    with pytest.raises(SystemExit) as exc:
        run.main(["--workload", "lj-r128.serve-steady", "--seed", "1",
                  "--seconds", "1", "--trace", "0"])
    assert exc.value.code not in (0, None)
    assert capsys.readouterr().out.strip() == ""
