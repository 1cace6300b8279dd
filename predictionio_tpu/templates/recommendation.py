"""Recommendation engine template — the north-star workload.

Capability parity with the reference's bundled recommendation engine
(``tests/pio_tests/engines/recommendation-engine/src/main/scala/``):
DataSource reads ``rate``/``buy`` events (``DataSource.scala:47-52``,
k-fold readEval :83-105), the ALS algorithm trains factor models
(``ALSAlgorithm.scala:51-93``) and serves top-N via factor dot products
(:95-109), queries/results use the same JSON shapes the reference's
engine server speaks:

    POST /queries.json  {"user": "1", "num": 4}
    → {"itemScores": [{"item": "22", "score": 4.07}, ...]}
"""

from __future__ import annotations

from dataclasses import dataclass
from typing import Dict, List, Optional, Sequence, Tuple

import numpy as np

from ..controller import (
    Algorithm,
    AverageMetric,
    Context,
    DataSource,
    Engine,
    EngineParams,
    FirstServing,
    IdentityPreparator,
    SanityCheck,
)
from ..controller.metric import ndcg_at_k, precision_at_k
from ..models.als import (
    ALSModel,
    ALSParams,
    RatingsCOO,
    pack_ratings_cached,
    recommend_batch,
    recommend_products,
    train_als,
    training_report,
)
from ..models.data import kfold_split, ratings_from_columnar


# -- query/result schema (reference Query.scala / PredictedResult) ----------

@dataclass(frozen=True)
class Query:
    """``Query.scala``; ``black_list`` is the blacklist-items variant's
    added field (``examples/scala-parallel-recommendation/blacklist-items/
    src/main/scala/Engine.scala:26``)."""
    user: str
    num: int = 10
    black_list: Optional[Tuple[str, ...]] = None

    def __post_init__(self):
        if self.black_list is not None:
            object.__setattr__(self, "black_list", tuple(self.black_list))


@dataclass(frozen=True)
class ItemScore:
    item: str
    score: float


@dataclass(frozen=True)
class PredictedResult:
    item_scores: Tuple[ItemScore, ...] = ()

    def to_json(self) -> dict:
        return {"itemScores": [{"item": s.item, "score": s.score}
                               for s in self.item_scores]}


# -- training data -----------------------------------------------------------

@dataclass
class TrainingData(SanityCheck):
    #: a :class:`RatingsCOO`, or (multihost) a sharded ratings source
    #: (``read_rows``/``row_counts`` — duck-typed through the pack)
    ratings: RatingsCOO
    user_ids: object  # BiMap
    item_ids: object  # BiMap

    def sanity_check(self):
        r = self.ratings
        nnz = (int(np.asarray(r.row_counts("user")).sum())
               if hasattr(r, "row_counts") else r.users.size)
        if nnz == 0:
            raise ValueError("TrainingData has no ratings; check that "
                             "rate/buy events exist for the app")


@dataclass(frozen=True)
class DataSourceParams:
    app_name: str = ""
    channel_name: Optional[str] = None
    eval_k: int = 0              # folds for read_eval (0 = no eval data)
    eval_query_num: int = 10     # N per eval query
    eval_rating_threshold: float = 2.0  # "relevant" cutoff for actuals
    seed: int = 3
    #: event name → fixed rating (None ⇒ read the ``rating`` property).
    #: Default replays the quickstart (rate + buy=4.0); the
    #: reading-custom-events / train-with-view-event variants configure
    #: e.g. {"like": 5.0, "dislike": 1.0} or {"view": 1.0} here instead
    #: of editing the DataSource (``examples/scala-parallel-recommendation/
    #: {reading-custom-events,train-with-view-event}/…/DataSource.scala:50``).
    event_weights: Optional[Dict[str, Optional[float]]] = None


@dataclass(frozen=True)
class EvalInfo:
    fold: int
    rating_threshold: float


@dataclass(frozen=True)
class ActualResult:
    """Ground truth for one eval query: the user's held-out rated items."""
    ratings: Tuple[Tuple[str, float], ...]  # (item, rating)


class RecommendationDataSource(DataSource):
    def __init__(self, params: DataSourceParams = DataSourceParams()):
        self.params = params

    def _read_ratings(self, ctx: Context):
        import jax

        weights = self.params.event_weights
        multihost = jax.process_count() > 1
        batch = ctx.event_store.find_columnar(
            self.params.app_name or ctx.app_name,
            channel_name=self.params.channel_name,
            entity_type="user", target_entity_type="item",
            event_names=(list(weights) if weights is not None
                         else ["rate", "buy"]),
            # a bulk COO build needs neither time order nor raw JSON
            ordered=False, with_props=False,
            # multihost: the storage layer hands this process ONLY its
            # shard (shard pushdown — a remote backend ships 1/N of the
            # bytes); the sharded source below re-assembles per-factor-
            # row triples over the collective fabric
            host_sharded=multihost)
        if multihost:
            from ..models.data import ShardedColumnarRatingsSource
            src = ShardedColumnarRatingsSource(
                batch, event_weights=weights)
            return src, src.user_ids, src.item_ids
        return ratings_from_columnar(batch, event_weights=weights)

    def read_training(self, ctx: Context) -> TrainingData:
        ratings, user_ids, item_ids = self._read_ratings(ctx)
        return TrainingData(ratings, user_ids, item_ids)

    def read_eval(self, ctx: Context):
        """K-fold split over rating entries (reference ``DataSource.scala:
        83-105``): train on k-1 folds, hold out one; queries ask top-N for
        each user present in the held-out fold, actuals are their held-out
        items."""
        p = self.params
        if p.eval_k <= 1:
            raise ValueError("eval_k must be >= 2 for read_eval")
        ratings, user_ids, item_ids = self._read_ratings(ctx)
        if hasattr(ratings, "to_coo"):
            # k-fold splitting slices entry arrays; materialize the
            # global COO (collective under multihost — eval is not the
            # memory-bound path training is)
            ratings = ratings.to_coo()
        # dense inverse-lookup arrays: at ML-20M scale a fold holds ~10M
        # test entries, and per-entry dict lookups + numpy-scalar
        # unboxing in a Python loop cost minutes on one core — the
        # grouping below is numpy lexsort + slicing instead
        inv_u_arr = np.empty(ratings.n_users, dtype=object)
        for s, j in user_ids.items():
            inv_u_arr[j] = s
        inv_i_arr = np.empty(ratings.n_items, dtype=object)
        for s, j in item_ids.items():
            inv_i_arr[j] = s
        folds = []
        for f, (train_mask, test_mask) in enumerate(
                kfold_split(len(ratings.users), p.eval_k, p.seed)):
            td = TrainingData(
                RatingsCOO(ratings.users[train_mask],
                           ratings.items[train_mask],
                           ratings.ratings[train_mask],
                           ratings.n_users, ratings.n_items),
                user_ids, item_ids)
            te_u = ratings.users[test_mask]
            order = np.lexsort((np.arange(len(te_u)), te_u))
            u_s = te_u[order]
            i_names = inv_i_arr[ratings.items[test_mask][order]]
            r_s = ratings.ratings[test_mask][order].astype(float)
            starts = np.flatnonzero(
                np.r_[True, u_s[1:] != u_s[:-1]]) if len(u_s) else \
                np.empty(0, np.int64)
            bounds = np.r_[starts, len(u_s)]
            qa = []
            for b in range(len(starts)):
                lo, hi = bounds[b], bounds[b + 1]
                qa.append((
                    Query(user=inv_u_arr[u_s[lo]],
                          num=p.eval_query_num),
                    ActualResult(tuple(zip(i_names[lo:hi].tolist(),
                                           r_s[lo:hi].tolist())))))
            folds.append((td, EvalInfo(fold=f,
                                       rating_threshold=p.eval_rating_threshold),
                          qa))
        return folds


# -- algorithm ---------------------------------------------------------------

class ALSAlgorithm(Algorithm):
    """Explicit-feedback ALS (``ALSAlgorithm.scala:39-150``); set
    ``implicit_prefs=True`` for the trainImplicit variants."""

    query_class = Query

    def __init__(self, params: ALSParams = ALSParams()):
        self.params = params

    def train(self, ctx: Context, td: TrainingData) -> ALSModel:
        mesh = ctx.mesh
        packed = pack_ratings_cached(td.ratings, self.params, mesh=mesh)
        U, V = train_als(td.ratings, self.params, mesh=mesh, packed=packed)
        # what "auto" resolved to on this backend, and the compiler's
        # message for any kernel it skipped — `ptpu train` prints it
        ctx.extra["train_kernels"] = training_report(self.params, packed,
                                                     mesh)
        return ALSModel(user_factors=U, item_factors=V,
                        n_users=td.ratings.n_users,
                        n_items=td.ratings.n_items,
                        user_ids=td.user_ids, item_ids=td.item_ids,
                        params=self.params)

    def predict(self, model: ALSModel, query: Query) -> PredictedResult:
        return self._predict_impl(model, query, pinned=None)

    def _predict_impl(self, model: ALSModel, query: Query,
                      pinned) -> PredictedResult:
        uidx = model.user_ids.get(query.user) if model.user_ids else None
        if uidx is None:
            return PredictedResult()  # unknown user (reference returns empty)
        black = {model.item_ids[i] for i in (query.black_list or ())
                 if i in model.item_ids}
        # over-fetch by the blacklist size, then filter (the variant's
        # recommendProductsWithFilter, blacklist-items ALSAlgorithm.scala:
        # 102-104)
        if pinned is not None:
            from ..models.als import recommend_pinned

            table, slot = pinned
            ids, scores = recommend_pinned(model, table, slot,
                                           query.num + len(black))
        else:
            ids, scores = recommend_products(model, int(uidx),
                                             query.num + len(black))
        inv = model.item_ids.inverse
        out = [(int(i), float(s)) for i, s in zip(ids, scores)
               if int(i) not in black][: query.num]
        return PredictedResult(tuple(
            ItemScore(item=inv[i], score=s) for i, s in out))

    # -- hot-entity tier hooks (ISSUE 4) ------------------------------------
    def pin_hot_entities(self, model: ALSModel,
                         entity_keys: Sequence[str],
                         devices: Optional[Sequence] = None):
        """Pin the hottest users' factor rows as ONE device-resident
        table (:func:`~..models.als.pin_user_rows`); returns
        ``({user: (table, slot)}, nbytes)``. Host-served models return
        empty — there is no transfer to skip. The pinned table is
        padded to a pow2 capacity and its k-ladder warmed here (on the
        refresh thread), so the first hot-path query after a refresh
        never pays a compile.

        With ``devices`` (replicated-mode lanes, ISSUE 6) the pinned
        table is committed to EVERY lane device
        (:func:`~..models.als.pin_user_rows_lanes`) and the handle
        carries the per-device tuple — hot serves stay local to a lane.
        Sharded models pin a mesh-replicated table instead (the rows
        are fetched through the collective gather)."""
        from ..models.als import (
            pin_user_rows,
            pin_user_rows_lanes,
            recommend_pinned,
        )

        known = [(e, int(model.user_ids[e])) for e in entity_keys
                 if model.user_ids and e in model.user_ids]
        if not known:
            return {}, 0
        cap = 1
        while cap < len(known):
            cap *= 2
        if devices and getattr(model, "mesh", None) is None:
            table, nbytes = pin_user_rows_lanes(
                model, [u for _, u in known], cap, devices)
        else:
            table, nbytes = pin_user_rows(model, [u for _, u in known],
                                          cap)
        if table is None:
            return {}, 0
        ks, k = [], 8
        while k <= min(128, model.n_items):
            ks.append(k)
            k *= 2
        for k in ks or [min(8, model.n_items)]:
            recommend_pinned(model, table, 0, k)
        return {e: (table, slot)
                for slot, (e, _) in enumerate(known)}, nbytes

    def predict_pinned(self, model: ALSModel, query: Query,
                       handle) -> PredictedResult:
        """Serve one query off a pinned hot-user row (the device-
        resident hot tier's fast path)."""
        return self._predict_impl(model, query, pinned=handle)

    def prepare_serving_model(self, model: ALSModel,
                              max_batch: int = 1) -> ALSModel:
        from ..models.als import ensure_device_resident

        return ensure_device_resident(model, max_batch)

    def quantize_serving_model(self, model: ALSModel,
                               quant: str) -> ALSModel:
        """Row-quantize the serving factor tables (ISSUE 13,
        ``ServerConfig.serving_quant``): int8/bf16 storage with
        per-row scales and f32 accumulation, behind the deploy-time
        NDCG@10 parity probe — a model whose rank/scale cannot take
        the quantization keeps its f32 tables (auto-off)."""
        from ..models.als import quantize_serving_model

        return quantize_serving_model(model, quant)

    # -- mesh-wide serving placement hooks (ISSUE 6) ------------------------
    def replicate_serving_model(self, model: ALSModel,
                                device) -> ALSModel:
        """One full factor-table copy committed to ``device`` — a
        replicated-mode lane's model (per-device compiled executables,
        no cross-device sync on the serve path)."""
        from ..models.als import replicate_model

        return replicate_model(model, device)

    def shard_serving_model(self, model: ALSModel, mesh) -> ALSModel:
        """Row-shard both factor tables over the serving mesh
        (``NamedSharding``, ALX layout) — the >1-HBM model placement;
        serving routes through the mesh ranking program."""
        from ..models.als import shard_model

        return shard_model(model, mesh)

    def warm_serving(self, model: ALSModel, max_batch: int = 1) -> None:
        """Pre-compile the serving device kernels for the single-query
        path and every pow2 batch size the micro-batcher can produce
        (each novel shape is a fresh XLA compile; cf.
        ``ServerConfig.warm_start``)."""
        if model.user_ids is None or len(model.user_ids) == 0:
            return
        from ..models.als import recommend_batch, recommend_products

        # k ladder: batch_predict fetches k = num + blacklist-length,
        # and each pow2 k bucket is its own compiled shape
        ks = []
        k = 8
        while k <= min(128, model.n_items):
            ks.append(k)
            k *= 2
        ks = ks or [min(8, model.n_items)]
        for k in ks:
            recommend_products(model, 0, k)
        b = 1
        top = max(max_batch, 1)
        while True:
            for k in ks:
                recommend_batch(model, np.zeros(b, dtype=np.int64), k)
            if b >= top:  # b is the pow2 ceiling of max_batch: every
                break     # runtime batch pads to a warmed shape
            b *= 2

    def batch_predict_async(self, model: ALSModel,
                            queries: Sequence[Query]):
        """Dispatch half of :meth:`batch_predict` (ISSUE 9): enqueues
        the batched device top-k and returns a no-arg resolver that
        blocks on the device arrays and builds the per-query results.
        The staged serving pipeline's dispatch stage calls this and
        hands the resolver to the readback stage, so the NEXT batch
        launches while this one's results are still on device
        (docs/serving-pipeline.md)."""
        from ..models.als import recommend_batch_async

        known = [(qi, int(model.user_ids[q.user])) for qi, q in
                 enumerate(queries) if model.user_ids
                 and q.user in model.user_ids]
        out: List[PredictedResult] = [PredictedResult()] * len(queries)
        if not known:
            return lambda: out
        max_black = max((len(q.black_list or ()) for q in queries),
                        default=0)
        num = max(q.num for q in queries) + max_black
        idx = np.array([u for _, u in known], dtype=np.int64)
        handle = recommend_batch_async(model, idx, num)

        def resolve() -> List[PredictedResult]:
            ids, scores = handle()
            inv = model.item_ids.inverse
            for row, (qi, _) in enumerate(known):
                q = queries[qi]
                black = {model.item_ids[i] for i in (q.black_list or ())
                         if i in model.item_ids}
                picked = [(int(i), float(s))
                          for i, s in zip(ids[row], scores[row])
                          if int(i) not in black][: q.num]
                out[qi] = PredictedResult(tuple(
                    ItemScore(item=inv[i], score=s) for i, s in picked))
            return out

        return resolve

    def batch_predict(self, model: ALSModel, queries: Sequence[Query]
                      ) -> List[PredictedResult]:
        """One batched device dispatch for all known users
        (the reference's cartesian batchPredict, ``ALSAlgorithm.scala:
        113-150``, without the shuffle). Dispatch + immediate readback
        of :meth:`batch_predict_async` — the two must never diverge."""
        return self.batch_predict_async(model, queries)()


class RecommendationServing(FirstServing):
    pass


@dataclass(frozen=True)
class FileBlacklistServingParams:
    """``ServingParams(filepath)`` of the customize-serving variant."""
    filepath: str = ""


class FileBlacklistServing(RecommendationServing):
    """Drop items listed (one per line) in a file re-read per request —
    the customize-serving variant (``examples/scala-parallel-
    recommendation/customize-serving/src/main/scala/Serving.scala:28-44``)."""

    def __init__(self, params: FileBlacklistServingParams
                 = FileBlacklistServingParams()):
        self.params = params

    def serve(self, query: Query,
              predictions) -> PredictedResult:
        disabled = set()
        if self.params.filepath:
            with open(self.params.filepath, "r", encoding="utf-8") as f:
                disabled = {line.strip() for line in f if line.strip()}
        first = predictions[0]
        return PredictedResult(tuple(
            s for s in first.item_scores if s.item not in disabled))


@dataclass(frozen=True)
class ExcludeItemsPreparatorParams:
    """The customize-data-prep variant's exclusion list: items read from
    a file (one per line) or given inline are dropped before training
    (``examples/scala-parallel-recommendation/customize-data-prep/src/
    main/scala/Preparator.scala``)."""
    filepath: str = ""
    items: Tuple[str, ...] = ()


class ExcludeItemsPreparator(IdentityPreparator):
    def __init__(self, params: ExcludeItemsPreparatorParams
                 = ExcludeItemsPreparatorParams()):
        self.params = params

    def prepare(self, ctx: Context, td: TrainingData) -> TrainingData:
        excluded = set(self.params.items)
        if self.params.filepath:
            with open(self.params.filepath, "r", encoding="utf-8") as f:
                excluded |= {line.strip() for line in f if line.strip()}
        bad_idx = {td.item_ids[i] for i in excluded if i in td.item_ids}
        if not bad_idx:
            return td
        # excluded items leave the model ENTIRELY (re-indexed out), so
        # they can never be recommended — matching the reference, where a
        # filtered item simply has no MLlib factor entry
        from ..data.bimap import BiMap

        new_item_ids = BiMap.string_int(
            k for k in td.item_ids.keys() if k not in excluded)
        remap = np.full(td.ratings.n_items, -1, dtype=np.int64)
        for old_key, new_i in new_item_ids.items():
            remap[td.item_ids[old_key]] = new_i
        keep = ~np.isin(td.ratings.items, list(bad_idx))
        return TrainingData(
            RatingsCOO(td.ratings.users[keep],
                       remap[td.ratings.items[keep]].astype(
                           td.ratings.items.dtype),
                       td.ratings.ratings[keep], td.ratings.n_users,
                       len(new_item_ids)),
            td.user_ids, new_item_ids)


def recommendation_engine() -> Engine:
    """Engine factory (the template's ``EngineFactory`` object)."""
    return Engine(
        datasource_classes=RecommendationDataSource,
        preparator_classes={"": IdentityPreparator,
                            "exclude": ExcludeItemsPreparator},
        algorithm_classes={"als": ALSAlgorithm, "": ALSAlgorithm},
        serving_classes={"": RecommendationServing,
                         "fileblacklist": FileBlacklistServing},
        datasource_params_class=DataSourceParams,
        preparator_params_class={"exclude": ExcludeItemsPreparatorParams},
        algorithm_params_classes={"als": ALSParams, "": ALSParams},
        serving_params_class={"fileblacklist": FileBlacklistServingParams},
    )


# -- evaluation metrics (reference Evaluation.scala:32-89) -------------------

class PrecisionAtK(AverageMetric):
    """Precision@K with a relevance threshold (``Evaluation.scala:32-51``)."""

    def __init__(self, k: int = 10, rating_threshold: float = 2.0):
        self.k = k
        self.rating_threshold = rating_threshold

    @property
    def header(self) -> str:
        return f"Precision@{self.k} (threshold={self.rating_threshold})"

    def calculate_point(self, ei, q: Query, p: PredictedResult,
                        a: ActualResult):
        relevant = {item for item, r in a.ratings
                    if r >= self.rating_threshold}
        return precision_at_k([s.item for s in p.item_scores], relevant,
                              self.k)


class NDCGAtK(AverageMetric):
    """Binary NDCG@K — the BASELINE.md quality target."""

    def __init__(self, k: int = 10, rating_threshold: float = 2.0):
        self.k = k
        self.rating_threshold = rating_threshold

    @property
    def header(self) -> str:
        return f"NDCG@{self.k} (threshold={self.rating_threshold})"

    def calculate_point(self, ei, q: Query, p: PredictedResult,
                        a: ActualResult):
        relevant = {item for item, r in a.ratings
                    if r >= self.rating_threshold}
        return ndcg_at_k([s.item for s in p.item_scores], relevant, self.k)


class PositiveCount(AverageMetric):
    """Average number of relevant actuals per query
    (``Evaluation.scala:53-61``) — a sanity diagnostic, not a target."""

    def __init__(self, rating_threshold: float = 2.0):
        self.rating_threshold = rating_threshold

    @property
    def header(self) -> str:
        return f"PositiveCount (threshold={self.rating_threshold})"

    def calculate_point(self, ei, q, p, a: ActualResult):
        return float(sum(1 for _, r in a.ratings
                         if r >= self.rating_threshold))


def query_from_json(obj: dict) -> Query:
    return Query(user=str(obj["user"]), num=int(obj.get("num", 10)))


def default_engine_params(app_name: str, **als_kw) -> EngineParams:
    return EngineParams(
        datasource=("", DataSourceParams(app_name=app_name)),
        preparator=("", None),
        algorithms=(("als", ALSParams(**als_kw)),),
        serving=("", None))
