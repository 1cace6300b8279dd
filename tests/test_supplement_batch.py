"""``workflow/batch_predict.py::supplement_batch`` (ISSUE 46): a serving
that inherits ``Serving.supplement`` is never called and never reaches
the shared pool; any other supplement keeps the pooled behaviour to the
letter (order, per-query error slots)."""

import pytest

from predictionio_tpu.controller.base import FirstServing, Serving
from predictionio_tpu.workflow import batch_predict as bp


class _Query:
    def __init__(self, n):
        self.n = n


class _Bad(_Query):
    """A query whose supplement raises."""


def _mark(query):
    if isinstance(query, _Bad):
        raise ValueError(f"bad query {query.n}")
    return ("supplemented", query.n)


class _Inheriting(Serving):
    def serve(self, query, predictions):
        return predictions[0]


class _Grandchild(_Inheriting):
    """Two classes below ``Serving`` and still no override."""


class _Overriding(FirstServing):
    def supplement(self, query):
        return _mark(query)


class _OverridingBack(_Overriding):
    """Overrides an override with the identity's body: still its own
    function, so still called."""

    def supplement(self, query):
        return query


class _DuckTyped:
    """No ``Serving`` at all (tests/test_pipeline.py's wedge is one)."""

    def supplement(self, query):
        return _mark(query)

    def serve(self, query, predictions):
        return predictions[0]


def _patched_instance():
    serving = FirstServing()
    serving.supplement = _mark
    return serving


def _bound_elsewhere():
    """An instance attribute that IS a bound method, of another object."""
    serving = FirstServing()
    serving.supplement = _Overriding().supplement
    return serving


class _SpyPool:
    """Stands where ``_algo_pool`` stands: counts the pools asked for
    and the submissions made, and runs them on the real pool."""

    def __init__(self, real):
        self.real = real
        self.asked = 0
        self.submitted = []

    def __call__(self):
        self.asked += 1
        return self

    def submit(self, fn, *args):
        self.submitted.append(args)
        return self.real().submit(fn, *args)


@pytest.fixture
def spy(monkeypatch):
    spy = _SpyPool(bp._algo_pool)
    monkeypatch.setattr(bp, "_algo_pool", spy)
    return spy


@pytest.fixture
def no_pool_yet(monkeypatch):
    """The module as a fresh process has it: no executor made."""
    monkeypatch.setattr(bp, "_dispatch_pool", None)


class _Guard:
    def __init__(self):
        self.entered = 0

    def __call__(self):
        return self

    def __enter__(self):
        self.entered += 1

    def __exit__(self, *exc):
        return False


INHERITING = [
    pytest.param(FirstServing, id="FirstServing"),
    pytest.param(_Inheriting, id="subclass"),
    pytest.param(_Grandchild, id="grandchild"),
]
OTHERS = [
    pytest.param(_Overriding, id="override"),
    pytest.param(_DuckTyped, id="duck-typed"),
    pytest.param(_patched_instance, id="instance-attribute"),
    pytest.param(_bound_elsewhere, id="another-objects-method"),
]


@pytest.mark.parametrize("n", [0, 1, 2, 21, 128])
@pytest.mark.parametrize("make", INHERITING)
def test_an_inherited_supplement_is_never_called(make, n, spy, no_pool_yet):
    queries = [_Query(i) for i in range(n)]
    given = list(queries)
    out = [None] * n
    guard = _Guard()
    supplemented, live, way = bp.supplement_batch(
        make(), queries, out, guard=guard)
    assert way == "identity"
    assert supplemented is not queries  # a new list ...
    assert len(supplemented) == n and all(
        s is q for s, q in zip(supplemented, given))  # ... of the same
    assert queries == given  # the argument as it was
    assert live == list(range(n))
    assert out == [None] * n
    assert spy.asked == 0 and spy.submitted == []
    assert bp._dispatch_pool is None  # no executor, so no thread
    assert guard.entered == 0


@pytest.mark.parametrize("n", [2, 5, 21])
@pytest.mark.parametrize("make", OTHERS)
def test_any_other_supplement_takes_the_pool_in_order(make, n, spy):
    queries = [_Query(i) for i in range(n)]
    out = [None] * n
    guard = _Guard()
    supplemented, live, way = bp.supplement_batch(
        make(), queries, out, guard=guard)
    assert way == "pool"
    assert supplemented == [("supplemented", i) for i in range(n)]
    assert live == list(range(n))
    assert out == [None] * n
    assert spy.submitted == [(q,) for q in queries]  # one a query
    assert guard.entered == 1


@pytest.mark.parametrize("make", OTHERS)
def test_a_raising_query_fills_its_own_slot(make, spy):
    queries = [_Query(0), _Bad(1), _Query(2), _Bad(3), _Query(4)]
    out = [None] * len(queries)
    supplemented, live, way = bp.supplement_batch(make(), queries, out)
    assert way == "pool"
    assert supplemented == [("supplemented", i) for i in (0, 2, 4)]
    assert live == [0, 2, 4]
    assert [type(o) for o in out] == [
        type(None), ValueError, type(None), ValueError, type(None)]
    assert str(out[1]) == "bad query 1" and str(out[3]) == "bad query 3"
    assert len(spy.submitted) == len(queries)


@pytest.mark.parametrize("n", [0, 1])
@pytest.mark.parametrize("make", OTHERS)
def test_at_most_one_query_stays_on_the_calling_thread(make, n, spy):
    queries = [_Query(i) for i in range(n)]
    out = [None] * n
    guard = _Guard()
    supplemented, live, way = bp.supplement_batch(
        make(), queries, out, guard=guard)
    assert way == "serial"
    assert supplemented == [("supplemented", i) for i in range(n)]
    assert live == list(range(n))
    assert spy.asked == 0
    assert guard.entered == 1


@pytest.mark.parametrize("make", OTHERS)
def test_one_raising_query_serially(make, spy):
    out = [None]
    supplemented, live, way = bp.supplement_batch(make(), [_Bad(7)], out)
    assert (supplemented, live, way) == ([], [], "serial")
    assert isinstance(out[0], ValueError) and spy.asked == 0


def test_an_override_with_the_identitys_body_is_still_called(spy):
    queries = [_Query(0), _Query(1)]
    supplemented, live, way = bp.supplement_batch(
        _OverridingBack(), queries, [None, None])
    assert way == "pool" and supplemented == queries
    assert len(spy.submitted) == 2


@pytest.mark.parametrize("make,pooled", [
    pytest.param(FirstServing, 0, id="inherited"),
    pytest.param(_OverridingBack, 3, id="overridden"),
])
def test_predict_serve_batch_follows_the_same_rule(make, pooled, spy):
    """The batch-predict job's batch is built from the same function: a
    chunk of queries makes a pool submission each only for a supplement
    that is not the inherited one."""

    class Algo:
        def batch_predict_async(self, model, supplemented):
            return lambda: [q.n * model for q in supplemented]

    queries = [_Query(i) for i in range(3)]
    got = bp.predict_serve_batch([Algo()], [10], make(), queries)
    assert got == [0, 10, 20]
    assert len(spy.submitted) == pooled
