"""DASE controller contracts: DataSource, Preparator, Algorithm, Serving.

Capability parity with the reference's controller API
(``core/.../core/BaseDataSource.scala:34-55``, ``BasePreparator.scala:33-45``,
``BaseAlgorithm.scala:58-126``, ``BaseServing.scala:31-54``), with the
L/P/P2L split collapsed: the reference needed three flavors of every
controller because models lived either on the Spark driver (L), across
executors as RDDs (P), or were trained parallel and collected local (P2L)
(``controller/{LAlgorithm,PAlgorithm,P2LAlgorithm}.scala``). Here a model is
a pytree of (possibly sharded) ``jax.Array``s; mesh size 1..N covers all
three cases with one API.

Type parameters used informally throughout (Python generics kept light):
TD training data, PD prepared data, M model, Q query, P prediction,
A actual (ground truth), EI eval info.
"""

from __future__ import annotations

import abc
from typing import Any, Generic, List, Optional, Sequence, Tuple, TypeVar

from .context import Context

TD = TypeVar("TD")
PD = TypeVar("PD")
M = TypeVar("M")
Q = TypeVar("Q")
P = TypeVar("P")
A = TypeVar("A")
EI = TypeVar("EI")

#: One evaluation fold: (training data, eval info, [(query, actual)]).
EvalFold = Tuple[TD, EI, List[Tuple[Q, A]]]


class SanityCheck(abc.ABC):
    """Optional self-check hook on data/model objects
    (``controller/SanityCheck.scala``); the workflow calls it after read,
    prepare, and train unless skipped."""

    @abc.abstractmethod
    def sanity_check(self) -> None:
        """Raise if the object is malformed (e.g. empty training data)."""


class DataSource(abc.ABC, Generic[TD, EI, Q, A]):
    """Reads training and evaluation data from the event store
    (``core/BaseDataSource.scala:43,54``)."""

    @abc.abstractmethod
    def read_training(self, ctx: Context) -> TD:
        ...

    def read_eval(self, ctx: Context) -> List[EvalFold]:
        """Folds of (TD, EI, [(Q, A)]) for evaluation; default: none."""
        return []


class Preparator(abc.ABC, Generic[TD, PD]):
    """Transforms training data into algorithm input
    (``core/BasePreparator.scala:44``)."""

    @abc.abstractmethod
    def prepare(self, ctx: Context, training_data: TD) -> PD:
        ...


class IdentityPreparator(Preparator):
    """Pass-through preparator (``controller/IdentityPreparator.scala``)."""

    def __init__(self, params: Any = None):
        pass

    def prepare(self, ctx: Context, training_data):
        return training_data


class Algorithm(abc.ABC, Generic[PD, M, Q, P]):
    """The train/predict contract (``core/BaseAlgorithm.scala:69-126``).

    Models should be pytrees of arrays (sharded over ``ctx.mesh`` when
    large); ``predict`` should be thin host glue around jitted device code
    so serving stays low-latency.
    """

    #: batches of this algorithm's that the serving surface keeps on the
    #: device at once: the engine server writes its pipeline's depth here
    #: before ``warm_serving``, for an algorithm whose batches hold state
    #: there to size it (the generative template's check of residency)
    batches_in_flight: int = 1

    @abc.abstractmethod
    def train(self, ctx: Context, prepared_data: PD) -> M:
        ...

    @abc.abstractmethod
    def predict(self, model: M, query: Q) -> P:
        ...

    def batch_predict(self, model: M, queries: Sequence[Q]) -> List[P]:
        """Bulk prediction for eval/batch jobs
        (``core/BaseAlgorithm.scala:81``). Override with a vectorized/vmapped
        implementation where shapes allow; default is a host loop."""
        return [self.predict(model, q) for q in queries]

    # -- persistence flavor (core/BaseAlgorithm.scala:111-115) -------------
    def make_persistent_model(self, model: M, engine_instance_id: str,
                              algo_index: int) -> Any:
        """Decide how ``model`` persists. Return values:

        - the model itself (or any picklable stand-in): stored in the
          MODELDATA blob (reference default, Kryo → here pickled numpy
          pytrees);
        - a :class:`PersistentModelManifest`: the algorithm saved the model
          itself (custom checkpoint dir, Orbax, ...), only the manifest is
          stored;
        - ``None``: nothing persists; deploy retrains (reference ``Unit``
          model semantics, ``controller/Engine.scala:210-232``).

        Models implementing
        :class:`~predictionio_tpu.controller.persistent.PersistentModel`
        save themselves and persist as a manifest automatically
        (``Engine.makeSerializableModels`` :284).
        """
        from ..workflow.persistence import to_host
        from .persistent import PersistentModel, manifest_for
        if isinstance(model, PersistentModel):
            manifest = manifest_for(model, engine_instance_id, algo_index)
            if manifest is not None:
                return manifest
        return to_host(model)

    def bind_serving(self, ctx: Context) -> None:
        """Called on the instances that will actually serve queries (engine
        server bind/reload, batch predict) with the serving Context.
        Override to capture serving-time resources — e.g. the e-commerce
        template grabs ``ctx.event_store`` so its realtime filter reads hit
        the deployed storage, not the process-global default. No-op here."""

    def prepare_serving_model(self, model: M, max_batch: int = 1) -> M:
        """Called once per model when it binds to a serving surface
        (engine server bind/reload, batch predict) with the largest
        batch that surface coalesces. Override to fix the model's
        device placement — e.g. the recommendation template moves
        re-materialized factor matrices into HBM so the serving jits
        don't re-transfer host arrays on every query. Identity here."""
        return model

    def load_persistent_model(self, ctx: Context, stored: Any) -> M:
        """Invert :meth:`make_persistent_model` at deploy time."""
        from ..workflow.persistence import to_device
        from .persistent import load_from_manifest
        if isinstance(stored, PersistentModelManifest) and stored.class_name:
            return load_from_manifest(stored)
        return to_device(stored)

    #: Optional dataclass type for typed query parsing at the REST boundary
    #: (the reference's queryClass via reflection, BaseAlgorithm.scala:93).
    query_class: Optional[type] = None


class Serving(abc.ABC, Generic[Q, P]):
    """Combines per-algorithm predictions into the served result
    (``core/BaseServing.scala:41,53``)."""

    def supplement(self, query: Q) -> Q:
        """Pre-predict query enrichment (``BaseServing.supplementBase``).

        The batched paths never call this identity
        (``workflow/batch_predict.py::supplement_batch`` recognises it
        on a serving that does not override it). An override runs once
        a query, concurrently on a thread pool where a batch holds more
        than one, so it may block on storage."""
        return query

    @abc.abstractmethod
    def serve(self, query: Q, predictions: Sequence[P]) -> P:
        ...


class FirstServing(Serving):
    """Serve the first algorithm's prediction
    (``controller/LFirstServing.scala``)."""

    def __init__(self, params: Any = None):
        pass

    def serve(self, query, predictions):
        return predictions[0]


class AverageServing(Serving):
    """Average numeric predictions (``controller/LAverageServing.scala``)."""

    def __init__(self, params: Any = None):
        pass

    def serve(self, query, predictions):
        return sum(predictions) / len(predictions)


class PersistentModelManifest:
    """Marker stored in place of a model blob when the algorithm persists
    its own model (``workflow/PersistentModelManifest``); records how to
    find it again. ``class_name`` (``module:QualName``) names a
    :class:`~predictionio_tpu.controller.persistent.PersistentModel`
    whose ``load`` inverts the save; ``location``/``extra`` cover ad-hoc
    layouts handled by a custom ``load_persistent_model`` override."""

    def __init__(self, class_name: str = "", engine_instance_id: str = "",
                 algo_index: int = 0, location: str = "",
                 extra: Optional[dict] = None):
        self.class_name = class_name
        self.engine_instance_id = engine_instance_id
        self.algo_index = algo_index
        self.location = location
        self.extra = extra or {}

    def __repr__(self):
        return (f"PersistentModelManifest({self.class_name!r}, "
                f"{self.engine_instance_id!r}, {self.algo_index}, "
                f"{self.location!r})")
