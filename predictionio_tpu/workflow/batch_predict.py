"""Batch prediction: JSON-lines queries in → JSON-lines results out.

Capability parity with the reference ``BatchPredict``
(``workflow/BatchPredict.scala:145-235``): each input line is a query;
output lines are self-descriptive ``{"query": …, "prediction": …}``
objects (:218-227). Where the reference map-partitions an RDD, here the
queries are batched through ``Algorithm.batch_predict`` so a vectorized
(vmapped/jitted) implementation sees device-sized batches instead of one
query per dispatch.
"""

from __future__ import annotations

import json
from contextlib import nullcontext
from typing import (
    Any,
    Callable,
    ContextManager,
    Iterable,
    Iterator,
    List,
    Optional,
)

from ..controller.base import Serving
from ..controller.context import Context
from ..controller.engine import Engine
from ..controller.params import EngineParams
from ..data.storage.base import EngineInstance
from ..obs.runtime import name_os_thread
from ..utils.jsonutil import from_jsonable, to_jsonable

_dispatch_pool = None


def _algo_pool():
    """Shared executor for concurrent per-algorithm dispatches (the
    reference's ``CreateServer.scala:507-510`` "TODO: Parallelize" —
    per-algorithm predictions are independent by the DASE contract).
    Module-level so multi-algorithm engines don't pay pool setup per
    coalesced batch."""
    global _dispatch_pool
    if _dispatch_pool is None:
        from concurrent.futures import ThreadPoolExecutor

        _dispatch_pool = ThreadPoolExecutor(
            max_workers=8, thread_name_prefix="algo-batch-dispatch",
            initializer=name_os_thread)
    return _dispatch_pool


#: the ways a batch is supplemented: what :func:`supplement_batch`
#: returns third, and the label values of
#: ``pio_pipeline_supplement_batches_total{way}``
SUPPLEMENT_WAYS = ("identity", "serial", "pool")


def _inherits_supplement(serving: Any) -> bool:
    """Whether ``serving.supplement`` is the one :class:`Serving`
    defines (``return query``): true of an instance of any subclass
    that does not override it; false for an override, for a duck-typed
    serving that is no ``Serving`` and for an instance whose attribute
    was replaced."""
    return getattr(getattr(serving, "supplement", None),
                   "__func__", None) is Serving.supplement


def supplement_batch(serving: Any, queries: List[Any], out: List[Any],
                     guard: Callable[[], ContextManager] = nullcontext
                     ) -> tuple:
    """Supplement each query (the assemble-stage host work). Returns
    ``(supplemented, live, way)``, ``way`` one of
    :data:`SUPPLEMENT_WAYS`; per-query supplement failures land as the
    raised exception in that query's ``out`` slot.

    ``identity``: the serving inherits ``Serving.supplement``
    (:func:`_inherits_supplement`), so the queries ARE the supplemented
    queries: a new list of the same objects, every index live, no call,
    no pool, ``guard`` not entered. (A pool round trip a query to copy
    a list was the largest host part of a saturated 21-query batch:
    PERF.md finding 46.1.)

    Any other supplement runs inside ``guard()``. ``serial``: at most
    one query, on the calling thread. ``pool``: with more than one
    query the supplements run CONCURRENTLY on the shared dispatch pool:
    for templates whose supplement reads the event store (seen/
    constraint lookups), a serial loop made a 128-query batch pay 128
    sequential storage round trips before the device saw anything.
    Futures are drained in query order, so result order and per-query
    error slots are exactly the serial loop's."""
    if _inherits_supplement(serving):
        return list(queries), list(range(len(queries))), "identity"
    supplemented: List[Any] = []
    live: List[int] = []
    with guard():
        if len(queries) > 1:
            way = "pool"
            pool = _algo_pool()
            futures = [pool.submit(serving.supplement, q) for q in queries]
            for i, f in enumerate(futures):
                try:
                    supplemented.append(f.result())
                    live.append(i)
                except Exception as e:  # noqa: BLE001 — isolate per query
                    out[i] = e
        else:
            way = "serial"
            for i, q in enumerate(queries):
                try:
                    supplemented.append(serving.supplement(q))
                    live.append(i)
                except Exception as e:  # noqa: BLE001 — isolate per query
                    out[i] = e
    return supplemented, live, way


def dispatch_batch(algorithms: List[Any], models: List[Any],
                   supplemented: List[Any]) -> List[Any]:
    """Per-algorithm device DISPATCH without readback (ISSUE 9):
    returns one no-arg resolver per algorithm; calling it blocks until
    that algorithm's predictions are host-real. Algorithms exposing
    ``batch_predict_async`` (the dispatch/readback split — e.g. ALS)
    enqueue here and block only in their resolver, which is what lets
    the serving pipeline launch batch k+1 before batch k's results
    exist. Algorithms without the hook run their full (blocking)
    ``batch_predict`` on the shared pool — the resolver blocks on the
    future — preserving the concurrent multi-algorithm dispatch and
    still overlapping host stages of OTHER batches.

    A dispatch-time failure raises out of this call (the caller fills
    every live slot — one dispatch, whole batch); resolver-time
    failures raise out of the resolver the same way."""
    resolvers: List[Any] = []
    for a, m in zip(algorithms, models):
        async_fn = getattr(a, "batch_predict_async", None)
        if async_fn is not None:
            resolvers.append(async_fn(m, supplemented))
        else:
            resolvers.append(_algo_pool().submit(
                a.batch_predict, m, supplemented).result)
    return resolvers


class PendingBatch:
    """An in-flight coalesced batch: device dispatches enqueued, host
    results not yet read back. Assembled from parts by the engine
    server's dispatch stage (and by :func:`predict_serve_batch`);
    :meth:`wait` blocks on the device arrays and :meth:`serve` finishes
    the per-query serving — the readback stage's work, which stamps
    between the two (``server/engineserver.py::_AssembledBatch``)."""

    __slots__ = ("queries", "serving", "out", "live", "resolvers")

    def __init__(self, queries: List[Any], serving: Any, out: List[Any],
                 live: List[int], resolvers: List[Any]):
        self.queries = queries
        self.serving = serving
        self.out = out
        self.live = live
        self.resolvers = resolvers

    def wait(self) -> Optional[List[Any]]:
        """Block until every algorithm's predictions are host-real and
        return them, one list per algorithm. ``None`` when nothing is
        live or a readback failed (a per-algorithm readback failure
        fills every live slot: it is one dispatch)."""
        if not self.live:
            return None
        try:
            return [r() for r in self.resolvers]
        except Exception as e:  # noqa: BLE001 — one dispatch, whole batch
            for i in self.live:
                self.out[i] = e
            return None

    def serve(self, per_algo: Optional[List[Any]]) -> List[Any]:
        """Serve per query from what :meth:`wait` returned; a per-query
        serve failure fills only its own slot."""
        out = self.out
        if per_algo is None:
            return out
        for row, i in enumerate(self.live):
            try:
                # serve sees the original query (CreateServer.scala:511)
                out[i] = self.serving.serve(
                    self.queries[i], [preds[row] for preds in per_algo])
            except Exception as e:  # noqa: BLE001
                out[i] = e
        return out


def predict_serve_batch(algorithms: List[Any], models: List[Any],
                        serving: Any, queries: List[Any]) -> List[Any]:
    """The batch-predict job's batch: supplement each query, ONE
    ``batch_predict`` device dispatch per algorithm, then serve per
    query. Per-query failures (supplement/serve) come back as the raised
    exception in that query's slot; a ``batch_predict`` failure fills
    every live slot (it is one dispatch). Built from the functions the
    engine server's stages call (:func:`supplement_batch`,
    :func:`dispatch_batch`, :class:`PendingBatch`), resolved at once,
    so the job and the server can never diverge."""
    out: List[Any] = [None] * len(queries)
    supplemented, live, _ = supplement_batch(serving, queries, out)
    resolvers: List[Any] = []
    if live:
        try:
            resolvers = dispatch_batch(algorithms, models, supplemented)
        except Exception as e:  # noqa: BLE001 — one dispatch, whole batch
            for i in live:
                out[i] = e
            live = []
    pending = PendingBatch(queries, serving, out, live, resolvers)
    return pending.serve(pending.wait())


def batch_predict_lines(engine: Engine,
                        engine_params: EngineParams, models: List[Any],
                        query_lines: Iterable[str],
                        batch_size: int = 1024,
                        ctx: Optional[Context] = None) -> Iterator[str]:
    """Yield one JSON result line per non-empty input query line."""
    algorithms = engine.make_algorithms(engine_params)
    if ctx is not None:
        for algo in algorithms:
            algo.bind_serving(ctx)
    # same placement fix as the engine server's bind: device-resident
    # factors once, not a host re-transfer per flushed batch
    models = [a.prepare_serving_model(m, batch_size)
              for a, m in zip(algorithms, models)]
    serving = engine.make_serving(engine_params)
    query_cls = algorithms[0].query_class

    def flush(raw_batch: List[Any]) -> Iterator[str]:
        queries = [from_jsonable(query_cls, q) for q in raw_batch]
        results = predict_serve_batch(algorithms, models, serving, queries)
        for i, prediction in enumerate(results):
            if isinstance(prediction, Exception):
                raise prediction  # a batch job fails loudly
            yield json.dumps({"query": to_jsonable(raw_batch[i]),
                              "prediction": to_jsonable(prediction)})

    raw_batch: List[Any] = []
    for line in query_lines:
        line = line.strip()
        if not line:
            continue
        raw_batch.append(json.loads(line))
        if len(raw_batch) >= batch_size:
            yield from flush(raw_batch)
            raw_batch = []
    if raw_batch:
        yield from flush(raw_batch)


def run_batch_predict(ctx: Context, engine: Engine,
                      engine_params: EngineParams,
                      input_path: str, output_path: str,
                      engine_id: str = "default", engine_version: str = "1",
                      engine_variant: str = "engine.json",
                      instance: Optional[EngineInstance] = None,
                      batch_size: int = 1024) -> int:
    """The ``pio batchpredict`` flow: load the latest COMPLETED instance's
    models, stream the input file, write the output file. Returns the
    number of predictions written."""
    from . import core as wf

    if instance is None:
        instance = ctx.storage.engine_instances().get_latest_completed(
            engine_id, engine_version, engine_variant)
        if instance is None:
            raise RuntimeError("No COMPLETED engine instance; train first.")
    models = wf.load_models_for_deploy(ctx, engine, instance, engine_params)
    n = 0
    with open(input_path, "r", encoding="utf-8") as fin, \
            open(output_path, "w", encoding="utf-8") as fout:
        for line in batch_predict_lines(engine, engine_params, models,
                                        fin, batch_size=batch_size,
                                        ctx=ctx):
            fout.write(line + "\n")
            n += 1
    return n
