"""Core workflow: train and evaluation drivers with metadata bookkeeping.

Capability parity with ``workflow/CoreWorkflow.scala``: ``run_train``
mirrors ``runTrain`` (:45-102 — EngineInstance INIT→COMPLETED, model blob
insert at :76-81) and ``run_evaluation`` mirrors ``runEvaluation``
(:104-164 — EvaluationInstance INIT→EVALCOMPLETED with one-liner/HTML/JSON
results). The spark-submit process boundary (``tools/Runner.scala:185``)
does not exist here: training runs in-process against the mesh.
"""

from __future__ import annotations

import logging
from datetime import datetime, timezone
from typing import Any, List, Optional, Sequence

from ..controller.base import PersistentModelManifest
from ..controller.context import Context
from ..controller.engine import Engine
from ..controller.evaluation import (
    Evaluation,
    MetricEvaluator,
    MetricEvaluatorResult,
)
from ..controller.params import EngineParams, params_to_json
from ..data.storage.base import (
    EngineInstance,
    EvaluationInstance,
    Model,
    STATUS_COMPLETED,
    STATUS_EVALCOMPLETED,
    STATUS_INIT,
)
from . import persistence

log = logging.getLogger(__name__)


def _now() -> datetime:
    return datetime.now(timezone.utc)


def run_train(ctx: Context, engine: Engine, engine_params: EngineParams,
              engine_id: str = "default", engine_version: str = "1",
              engine_variant: str = "engine.json",
              engine_factory: str = "") -> str:
    """Train and persist: returns the COMPLETED engine-instance id.

    Multihost (``jax.process_count() > 1``): run_train is SPMD — every
    process executes the collective parts (training, the replicating
    ``to_host`` inside ``make_persistent_model``) — but process 0 is
    the SINGLE WRITER of engine-instance metadata and the model blob
    (the driver-program role of ``CoreWorkflow.scala:45-102``): the
    instance transitions INIT→COMPLETED exactly once however many
    hosts train."""
    import json as _json

    import jax

    from ..parallel.multihost import broadcast_str

    is_writer = jax.process_count() == 1 or jax.process_index() == 0
    storage = ctx.storage
    instances = storage.engine_instances()
    ep = engine_params
    instance_id = ""
    if is_writer:
        instance = EngineInstance(
            id="", status=STATUS_INIT, start_time=_now(),
            end_time=_now(),
            engine_id=engine_id, engine_version=engine_version,
            engine_variant=engine_variant, engine_factory=engine_factory,
            batch=ctx.batch,
            data_source_params=_json.dumps(
                {ep.datasource[0]: params_to_json(ep.datasource[1])}),
            preparator_params=_json.dumps(
                {ep.preparator[0]: params_to_json(ep.preparator[1])}),
            algorithms_params=_json.dumps(
                [{name: params_to_json(p)} for name, p in ep.algorithms]),
            serving_params=_json.dumps(
                {ep.serving[0]: params_to_json(ep.serving[1])}))
        instance_id = instances.insert(instance)
    instance_id = broadcast_str(instance_id)
    log.info("engine instance %s: training started", instance_id)

    # warm the device runtime (backend init + one tiny D2H) in the
    # background while the datasource reads from storage: the FIRST
    # device→host fetch of a process pays the runtime's start-up, and
    # overlapping it with the storage read makes it free
    import threading as _threading
    import time as _time

    def _warm_device():
        try:
            import numpy as _np

            import jax.numpy as _jnp

            _np.asarray(_jnp.ones((8, 128), _jnp.float32) * 2)
        except Exception:  # noqa: BLE001 — warmup must never kill a train
            pass

    warm = _threading.Thread(target=_warm_device, daemon=True,
                             name="device-warmup")
    warm.start()

    result = engine.train(ctx, engine_params)
    if ctx.stop_after_read or ctx.stop_after_prepare:
        log.info("workflow stopped early (stop-after flag); instance %s "
                 "left in INIT", instance_id)
        return instance_id

    t0 = _time.monotonic()
    algos = engine.make_algorithms(engine_params)
    stored: List[Any] = []
    for i, (algo, model) in enumerate(zip(algos, result.models)):
        # collective on every process (replicates sharded leaves)
        stored.append(algo.make_persistent_model(model, instance_id, i))
    if is_writer:
        storage.models().insert(
            Model(id=instance_id, models=persistence.dumps_models(stored)))
        done = instances.get(instance_id)
        assert done is not None
        instances.update(done.copy(status=STATUS_COMPLETED,
                                   end_time=_now()))
    ctx.stage_timings["persist_s"] = round(_time.monotonic() - t0, 2)
    # which backend trained (the `pio_build_info` label set plus the
    # device kind) — `ptpu train` prints it beside the stages
    from ..obs.runtime import build_info

    ctx.extra["train_build_info"] = dict(
        build_info("train"), device_kind=jax.devices()[0].device_kind)
    # one parseable line: the stage breakdown, the backend, and what
    # the kernels' "auto" modes resolved to (with the compiler's
    # message for anything skipped)
    log.info("engine instance %s: training completed; stages=%s "
             "build_info=%s kernels=%s",
             instance_id, _json.dumps(ctx.stage_timings),
             _json.dumps(ctx.extra["train_build_info"]),
             _json.dumps(ctx.extra.get("train_kernels")))
    return instance_id


def load_models_for_deploy(ctx: Context, engine: Engine,
                           instance: EngineInstance,
                           engine_params: EngineParams) -> List[Any]:
    """Invert persisted blobs into live models (``CreateServer.scala:202-206``
    + ``Engine.prepareDeploy`` :198-267)."""
    blob = ctx.storage.models().get(instance.id)
    if blob is None:
        raise RuntimeError(f"no persisted models for instance {instance.id}")
    stored = persistence.loads_models(blob.models)
    return engine.prepare_deploy(ctx, engine_params, stored, instance.id)


def run_evaluation(ctx: Context, evaluation: Evaluation,
                   params_list: Sequence[EngineParams],
                   evaluation_class: str = "",
                   params_generator_class: str = "",
                   parallelism: int = 1) -> MetricEvaluatorResult:
    """Evaluate the search grid and record the winner.

    ``parallelism>1`` walks the grid with a thread pool (the reference's
    ``.par`` grid walk, ``MetricEvaluator.scala:224-231``); packing and
    fold prefixes are compute-once, so threads overlap host work with
    device dispatches."""
    storage = ctx.storage
    instances = storage.evaluation_instances()
    instance_id = instances.insert(EvaluationInstance(
        id="", status=STATUS_INIT, start_time=_now(), end_time=_now(),
        evaluation_class=evaluation_class,
        engine_params_generator_class=params_generator_class,
        batch=ctx.batch))
    log.info("evaluation instance %s: started (%d params sets)",
             instance_id, len(params_list))

    evaluator = MetricEvaluator(evaluation, parallelism=parallelism)
    result = evaluator.evaluate(ctx, params_list)

    done = instances.get(instance_id)
    assert done is not None
    instances.update(done.copy(
        status=STATUS_EVALCOMPLETED, end_time=_now(),
        evaluator_results=result.to_one_liner(),
        evaluator_results_html=result.to_html(),
        evaluator_results_json=result.to_json()))
    log.info("evaluation instance %s: %s", instance_id, result.to_one_liner())
    return result


def get_latest_completed(ctx: Context, engine_id: str = "default",
                         engine_version: str = "1",
                         engine_variant: str = "engine.json"
                         ) -> Optional[EngineInstance]:
    return ctx.storage.engine_instances().get_latest_completed(
        engine_id, engine_version, engine_variant)
