"""Engine/workflow semantics tests.

Strategy parity with the reference's fixture engine family
(`core/src/test/.../controller/SampleEngine.scala`): numbered
DataSource/Preparator/Algorithm/Serving components whose outputs encode
their params and inputs, so tests assert the exact data flow of
Engine.train/eval, the evaluator's model selection, and prefix memoization
(`FastEvalEngineTest` cache-hit counting).
"""

from dataclasses import dataclass

import pytest

from predictionio_tpu.controller import (
    Algorithm,
    AverageMetric,
    Context,
    DataSource,
    Engine,
    EngineParams,
    Evaluation,
    FirstServing,
    MetricEvaluator,
    Preparator,
    SanityCheck,
    Serving,
    engine_params_from_variant,
)
from predictionio_tpu.controller.engine import SimpleEngine

CALLS = {"read": 0, "prepare": 0, "train": 0}


def reset_calls():
    for k in CALLS:
        CALLS[k] = 0


@dataclass(frozen=True)
class DSParams:
    id: int = 0
    folds: int = 2
    error: bool = False


@dataclass(frozen=True)
class TD(SanityCheck):
    """Training data that self-checks (like the reference's sample TDs)."""

    id: int
    error: bool = False

    def sanity_check(self):
        if self.error:
            raise ValueError("datasource error flag")


class DS(DataSource):
    def __init__(self, params: DSParams = DSParams()):
        self.params = params

    def read_training(self, ctx):
        CALLS["read"] += 1
        return TD(self.params.id, self.params.error)

    def read_eval(self, ctx):
        CALLS["read"] += 1
        return [(TD(self.params.id), ("ei", f),
                 [((f, q), (f, q)) for q in range(3)])
                for f in range(self.params.folds)]


@dataclass(frozen=True)
class PParams:
    id: int = 0


class Prep(Preparator):
    def __init__(self, params: PParams = PParams()):
        self.params = params

    def prepare(self, ctx, td):
        CALLS["prepare"] += 1
        return ("pd", td, self.params.id)


@dataclass(frozen=True)
class AParams:
    id: int = 0


class Algo(Algorithm):
    def __init__(self, params: AParams = AParams()):
        self.params = params

    def train(self, ctx, pd):
        CALLS["train"] += 1
        return ("model", pd, self.params.id)

    def predict(self, model, q):
        return ("pred", model[2], q)


class Algo2(Algo):
    pass


class ServeSum(Serving):
    def serve(self, query, predictions):
        return ("served", query, tuple(p[1] for p in predictions))


def make_engine():
    return Engine(
        datasource_classes=DS,
        preparator_classes=Prep,
        algorithm_classes={"a1": Algo, "a2": Algo2},
        serving_classes=ServeSum,
        datasource_params_class=DSParams,
        preparator_params_class=PParams,
        algorithm_params_classes={"a1": AParams, "a2": AParams},
    )


def _recommendation_engine():
    from predictionio_tpu.templates.recommendation import (
        recommendation_engine,
    )
    return recommendation_engine()


def ep(ds=0, prep=0, algos=(("a1", 0),)):
    return EngineParams(
        datasource=("", DSParams(id=ds)),
        preparator=("", PParams(id=prep)),
        algorithms=tuple((name, AParams(id=i)) for name, i in algos),
        serving=("", None))


class TestEngineTrain:
    def test_dataflow(self):
        reset_calls()
        r = make_engine().train(Context(), ep(ds=3, prep=5,
                                              algos=(("a1", 7), ("a2", 9))))
        assert r.models == [
            ("model", ("pd", TD(3), 5), 7),
            ("model", ("pd", TD(3), 5), 9),
        ]
        assert CALLS == {"read": 1, "prepare": 1, "train": 2}

    def test_sanity_check_raises(self):
        with pytest.raises(ValueError, match="datasource error flag"):
            make_engine().train(
                Context(), ep().copy(datasource=("", DSParams(error=True))))

    def test_sanity_check_skipped(self):
        r = make_engine().train(
            Context(skip_sanity_check=True),
            ep().copy(datasource=("", DSParams(error=True))))
        assert len(r.models) == 1

    def test_stop_after_read(self):
        reset_calls()
        r = make_engine().train(Context(stop_after_read=True), ep())
        assert r.models == []
        assert CALLS == {"read": 1, "prepare": 0, "train": 0}

    def test_unknown_algorithm_name(self):
        with pytest.raises(KeyError, match="algorithm"):
            make_engine().train(Context(), ep(algos=(("nope", 0),)))


class TestEngineEval:
    def test_eval_structure(self):
        res = make_engine().eval(Context(), ep(ds=1, algos=(("a1", 2),
                                                            ("a2", 4))))
        assert len(res) == 2  # folds
        ei, qpa = res[0]
        assert ei == ("ei", 0)
        assert len(qpa) == 3
        q, p, a = qpa[0]
        # serving combined both algorithms' params ids
        assert p == ("served", (0, 0), (2, 4))
        assert a == (0, 0)


class PrecisionMetric(AverageMetric):
    """Score 1.0 when the served prediction carries the query, else 0."""

    def calculate_point(self, ei, q, p, a):
        return 1.0 if p[1] == q else 0.0


class ParamSensitiveMetric(AverageMetric):
    """Higher algorithm param id ⇒ better score (to test selection)."""

    def calculate_point(self, ei, q, p, a):
        return float(sum(p[2]))


class TestMetricEvaluator:
    def test_best_selection(self):
        engine = make_engine()
        grid = [ep(algos=(("a1", i),)) for i in (1, 5, 3)]
        ev = Evaluation(engine=engine, metric=ParamSensitiveMetric())
        result = MetricEvaluator(ev).evaluate(Context(), grid)
        assert result.best_index == 1
        assert result.best_score == 5.0
        assert result.best_engine_params.algorithms[0][1].id == 5
        assert "best variant 1" in result.to_one_liner()

    def test_prefix_memoization(self):
        # same datasource+preparator across 3 params sets: read/prepare once;
        # two distinct algo params: 2 trainings per fold, not 3
        reset_calls()
        engine = make_engine()
        grid = [ep(algos=(("a1", 1),)), ep(algos=(("a1", 2),)),
                ep(algos=(("a1", 1),))]
        ev = Evaluation(engine=engine, metric=ParamSensitiveMetric())
        MetricEvaluator(ev).evaluate(Context(), grid)
        assert CALLS["read"] == 1
        assert CALLS["prepare"] == 2       # once per fold
        assert CALLS["train"] == 4         # 2 distinct params × 2 folds

    def test_other_metrics_reported(self):
        engine = make_engine()
        ev = Evaluation(engine=engine, metric=ParamSensitiveMetric(),
                        other_metrics=[PrecisionMetric()])
        result = MetricEvaluator(ev).evaluate(Context(), [ep()])
        assert result.scores[0].other_scores == [1.0]
        assert result.other_metric_headers == ["PrecisionMetric"]


class TestVariantParsing:
    def test_engine_json_shape(self):
        variant = {
            "id": "default",
            "engineFactory": "my.Engine",
            "datasource": {"params": {"id": 4}},
            "preparator": {"params": {"id": 2}},
            "algorithms": [
                {"name": "a1", "params": {"id": 9}},
                {"name": "a2", "params": {"id": 1}},
            ],
        }
        engine = make_engine()
        parsed = engine.params_from_variant(variant)
        assert parsed.datasource[1] == DSParams(id=4)
        assert parsed.preparator[1] == PParams(id=2)
        assert parsed.algorithms == (("a1", AParams(id=9)),
                                     ("a2", AParams(id=1)))

    @pytest.mark.parametrize("engine, variant, named", [
        (make_engine, {"datasource": {"params": {"nope": 1}}}, "nope"),
        # a removed ALSParams option (PR 47) is an unknown key like any
        # other: refused by name, never accepted and ignored
        (_recommendation_engine,
         {"algorithms": [{"name": "als",
                          "params": {"rank": 8, "gramMode": "einsum"}}]},
         "gramMode"),
    ], ids=["datasource", "als-gramMode"])
    def test_unknown_param_rejected(self, engine, variant, named):
        with pytest.raises(ValueError,
                           match=f"unknown field.*{named}"):
            engine().params_from_variant(variant)

    def test_simple_engine(self):
        se = SimpleEngine(datasource_class=DS, algorithm_class=Algo)
        r = se.train(Context(), EngineParams())
        assert r.models == [("model", TD(0), 0)]
        assert isinstance(se.make_serving(EngineParams()), FirstServing)


class TestRetrainOnDeploy:
    def test_none_persistent_model_retrains(self):
        """An algorithm whose make_persistent_model returns None (the
        reference's Unit-model semantics) must be retrained by
        prepare_deploy (controller/Engine.scala:210-232)."""
        calls = {"train": 0}

        class EphemeralAlgo(Algo):
            def make_persistent_model(self, model, iid, ax):
                return None

            def train(self, ctx, pd):
                calls["train"] += 1
                return super().train(ctx, pd)

        engine = Engine(
            datasource_classes=DS,
            preparator_classes=Prep,
            algorithm_classes={"a1": EphemeralAlgo},
            serving_classes=ServeSum,
            datasource_params_class=DSParams,
            preparator_params_class=PParams,
        )
        params = ep()
        ctx = Context()
        result = engine.train(ctx, params)
        algo = engine.make_algorithms(params)[0]
        stored = algo.make_persistent_model(result.models[0], "iid", 0)
        assert stored is None
        trained_before = calls["train"]
        models = engine.prepare_deploy(ctx, params, [None], "iid")
        assert calls["train"] == trained_before + 1  # retrained
        assert models[0] is not None
