"""CPU-side checks of the chip bring-up rules (PR 21): where the compile
cache lives, that a failed warm-up fails the deploy, and that
``chip_smoke.py`` has no CPU mode."""

import json
import os
import subprocess
import sys
import time
from datetime import datetime, timezone
from pathlib import Path

import numpy as np
import pytest

from predictionio_tpu.controller.context import Context
from predictionio_tpu.data.storage.base import App
from predictionio_tpu.data.storage.registry import Storage
from predictionio_tpu.models.als import ALSModel, ALSParams
from predictionio_tpu.utils import platform

REPO = Path(__file__).resolve().parent.parent


# -- compile cache placement -------------------------------------------------

@pytest.fixture()
def tpu_backend(monkeypatch):
    """A process whose resolved backend is not the CPU, with every
    ``jax.config.update`` recorded instead of applied."""
    import jax

    updates = {}
    monkeypatch.delenv("JAX_PLATFORMS", raising=False)
    monkeypatch.setattr(jax, "default_backend", lambda: "tpu")
    monkeypatch.setattr(jax.config, "update",
                        lambda k, v: updates.__setitem__(k, v))
    monkeypatch.setattr(platform, "_cache_enabled", False)
    return updates


class TestCompileCachePlacement:
    def test_variable_set_means_no_directory_set_in_code(
            self, tpu_backend, monkeypatch, tmp_path):
        monkeypatch.setenv("JAX_COMPILATION_CACHE_DIR", str(tmp_path / "x"))
        monkeypatch.setattr(platform, "COMPILE_CACHE_DIR",
                            str(tmp_path / "in_checkout"))
        platform.enable_compilation_cache()
        assert "jax_compilation_cache_dir" not in tpu_backend
        assert not (tmp_path / "in_checkout").exists()
        # every compile is cached either way: a second run adds nothing
        assert tpu_backend[
            "jax_persistent_cache_min_compile_time_secs"] == 0.0

    def test_unset_means_the_fixed_in_checkout_directory(
            self, tpu_backend, monkeypatch, tmp_path):
        monkeypatch.delenv("JAX_COMPILATION_CACHE_DIR", raising=False)
        monkeypatch.setenv("PIO_HOME", str(tmp_path / "home"))
        monkeypatch.setenv("PIO_COMPILE_CACHE", str(tmp_path / "old"))
        monkeypatch.setattr(platform, "COMPILE_CACHE_DIR",
                            str(tmp_path / "in_checkout"))
        platform.enable_compilation_cache()
        assert tpu_backend["jax_compilation_cache_dir"] \
            == str(tmp_path / "in_checkout")
        assert (tmp_path / "in_checkout").is_dir()
        assert not (tmp_path / "home").exists()
        assert not (tmp_path / "old").exists()  # PIO_COMPILE_CACHE is gone

    def test_path_is_in_the_checkout_whatever_the_process_or_home(
            self, tmp_path):
        assert Path(platform.COMPILE_CACHE_DIR) == REPO / ".jax_cache"
        seen = set()
        for home in ("a", "b"):
            env = dict(os.environ, PIO_HOME=str(tmp_path / home),
                       HOME=str(tmp_path / home), PYTHONPATH=str(REPO))
            env.pop("JAX_COMPILATION_CACHE_DIR", None)
            seen.add(subprocess.run(
                [sys.executable, "-c",
                 "from predictionio_tpu.utils.platform import "
                 "COMPILE_CACHE_DIR; print(COMPILE_CACHE_DIR)"],
                env=env, cwd=str(tmp_path), capture_output=True,
                text=True, check=True).stdout.strip())
        assert seen == {str(REPO / ".jax_cache")}
        ignored = (REPO / ".gitignore").read_text().split()
        assert ".jax_cache/" in ignored

    def test_failure_to_enable_on_an_accelerator_is_an_error(
            self, tpu_backend, monkeypatch, tmp_path):
        monkeypatch.delenv("JAX_COMPILATION_CACHE_DIR", raising=False)
        blocker = tmp_path / "file"
        blocker.write_text("not a directory")
        monkeypatch.setattr(platform, "COMPILE_CACHE_DIR",
                            str(blocker / "cache"))
        with pytest.raises(OSError):
            platform.enable_compilation_cache()

    def test_cpu_backend_is_left_alone(self, monkeypatch):
        import jax

        updates = {}
        monkeypatch.setattr(jax.config, "update",
                            lambda k, v: updates.__setitem__(k, v))
        monkeypatch.setattr(platform, "_cache_enabled", False)
        platform.enable_compilation_cache()  # conftest pins the CPU
        assert updates == {}


# -- a failed warm-up fails the deploy --------------------------------------

def _server(cfg):
    from predictionio_tpu.data.storage.base import (
        STATUS_COMPLETED,
        EngineInstance,
    )
    from predictionio_tpu.server.engineserver import QueryServer
    from predictionio_tpu.templates.recommendation import (
        default_engine_params,
        recommendation_engine,
    )

    rng = np.random.default_rng(0)
    from predictionio_tpu import BiMap

    model = ALSModel(
        user_factors=rng.normal(size=(12, 8)).astype(np.float32),
        item_factors=rng.normal(size=(20, 8)).astype(np.float32),
        n_users=12, n_items=20,
        user_ids=BiMap({f"u{i}": i for i in range(12)}),
        item_ids=BiMap({f"i{i}": i for i in range(20)}),
        params=ALSParams(rank=8))
    storage = Storage(env={"PIO_STORAGE_SOURCES_MEM_TYPE": "memory"})
    storage.apps().insert(App(0, "bringup"))
    ctx = Context(app_name="bringup", _storage=storage)
    now = datetime.now(timezone.utc)
    inst = EngineInstance(
        id="b", status=STATUS_COMPLETED, start_time=now, end_time=now,
        engine_id="b", engine_version="1", engine_variant="e.json",
        engine_factory="f")
    return QueryServer(ctx, recommendation_engine(),
                       default_engine_params("bringup", rank=8), [model],
                       inst, cfg)


class TestFailedWarmupFailsTheDeploy:
    def test_warm_error_is_recorded_and_the_listener_stops(self):
        from predictionio_tpu.server.engineserver import (
            ServerConfig,
            create_engine_server,
        )

        server = _server(ServerConfig(warm_start=False))

        def broken(model, max_batch):
            raise RuntimeError("shape does not compile")

        server.algorithms[0].warm_serving = broken
        server.warm_done.clear()
        srv = create_engine_server(server, "127.0.0.1", 0)
        srv.start_background()
        server._warm_serving(server._warm_gen)
        assert not server.warm_done.is_set()
        assert "shape does not compile" in server.warm_error
        assert "shape does not compile" in server._warm_report["error"]
        deadline = time.monotonic() + 10
        while srv._thread.is_alive() and time.monotonic() < deadline:
            time.sleep(0.05)
        assert not srv._thread.is_alive()  # the deploy is over


# -- chip_smoke.py has no CPU mode -------------------------------------------

def test_chip_smoke_fails_at_once_without_a_tpu():
    t0 = time.monotonic()
    proc = subprocess.run(
        [sys.executable, str(REPO / "chip_smoke.py")],
        env=dict(os.environ, JAX_PLATFORMS="cpu"), cwd=str(REPO),
        capture_output=True, text=True, timeout=60)
    assert proc.returncode != 0
    assert time.monotonic() - t0 < 20
    assert "no TPU" in proc.stderr
    assert '"ok"' not in proc.stdout  # no result line


def test_chip_smoke_result_line_has_the_contract_keys_and_no_others():
    import importlib.util
    spec = importlib.util.spec_from_file_location(
        "chip_smoke", REPO / "chip_smoke.py")
    smoke = importlib.util.module_from_spec(spec)
    spec.loader.exec_module(smoke)
    line = smoke.result_line(
        {"platform": "tpu", "kind": "TPU v5 lite", "count": 1})
    assert "\n" not in line
    assert json.loads(line) == {"ok": True, "device": {
        "platform": "tpu", "kind": "TPU v5 lite", "count": 1}}
