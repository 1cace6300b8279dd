"""`ptpu check` static-analysis tests: every rule's positive, negative,
and pragma-suppressed cases; the repo-wide clean gate; the CLI contract;
and the runtime complement (transfer guard + recompile sentinel)."""

import os
import textwrap
from dataclasses import dataclass

import pytest

from predictionio_tpu.analysis import (
    RULES,
    check_project,
    check_source,
    run_check,
)
from predictionio_tpu.cli import main

PKG = os.path.join(os.path.dirname(os.path.dirname(
    os.path.abspath(__file__))), "predictionio_tpu")

HOT = "predictionio_tpu/server/hot.py"    # host-sync rule applies
COLD = "predictionio_tpu/models/cold.py"  # ...and here it does not


def rules_of(findings):
    return [f.rule for f in findings]


def src(text):
    return textwrap.dedent(text)


class TestHostSyncInHotPath:
    def test_positive_all_sync_forms(self):
        code = src("""
            import numpy as np
            import jax
            import jax.numpy as jnp

            def handler(arr, dev):
                a = np.asarray(arr)
                b = np.ascontiguousarray(arr)
                c = jax.device_get(dev)
                d = dev.item()
                e = dev.tolist()
                dev.block_until_ready()
                f = float(jnp.sum(dev))
                return a, b, c, d, e, f
        """)
        findings = check_source(code, path=HOT)
        assert rules_of(findings) == ["host-sync-in-hot-path"] * 7

    def test_negative_outside_hot_packages(self):
        code = src("""
            import numpy as np

            def handler(arr):
                return np.asarray(arr)
        """)
        assert check_source(code, path=COLD) == []

    def test_negative_module_level_is_not_hot(self):
        # import-time code runs once; only function bodies are hot
        code = src("""
            import numpy as np

            TABLE = np.asarray([1, 2, 3])
        """)
        assert check_source(code, path=HOT) == []

    def test_pragma_suppresses(self):
        code = src("""
            import numpy as np

            def handler(arr):
                # ptpu: allow[host-sync-in-hot-path] — test justification
                return np.asarray(arr)
        """)
        assert check_source(code, path=HOT) == []

    def test_pragma_in_comment_block_above(self):
        code = src("""
            import numpy as np

            def handler(arr):
                # a multi-line justification whose marker sits on the
                # first line: ptpu: allow[host-sync-in-hot-path]
                # and more prose after it
                return np.asarray(arr)
        """)
        assert check_source(code, path=HOT) == []


class TestRecompileHazard:
    def test_positive_unhashable_static_arg(self):
        code = src("""
            import jax

            def f(x, cfg):
                return x

            g = jax.jit(f, static_argnames=("cfg",))

            def call(x):
                return g(x, cfg=[1, 2])
        """)
        findings = check_source(code, path=COLD)
        assert rules_of(findings) == ["recompile-hazard"]
        assert "unhashable" in findings[0].message

    def test_positive_closure_over_jnp_array(self):
        code = src("""
            import jax
            import jax.numpy as jnp

            def build(vals):
                w = jnp.asarray(vals)
                return jax.jit(lambda x: x + w)
        """)
        findings = check_source(code, path=COLD)
        assert rules_of(findings) == ["recompile-hazard"]
        assert "closes over" in findings[0].message

    def test_positive_python_if_on_traced_arg(self):
        code = src("""
            import functools
            import jax

            @functools.partial(jax.jit, static_argnames=("flag",))
            def f(x, n, flag):
                if n > 0:
                    return x
                return -x
        """)
        findings = check_source(code, path=COLD)
        assert rules_of(findings) == ["recompile-hazard"]
        assert "traced argument" in findings[0].message

    def test_negative_static_branch_and_hashable_call(self):
        code = src("""
            import functools
            import jax
            import jax.numpy as jnp

            @functools.partial(jax.jit, static_argnames=("flag", "n"))
            def f(x, n, flag):
                if flag:
                    return x * n
                return jnp.where(x > 0, x, -x)

            def call(x):
                return f(x, n=4, flag=True)
        """)
        assert check_source(code, path=COLD) == []

    def test_pragma_suppresses(self):
        code = src("""
            import jax
            import jax.numpy as jnp

            def build(vals):
                w = jnp.asarray(vals)
                # ptpu: allow[recompile-hazard] — built once, cached
                return jax.jit(lambda x: x + w)
        """)
        assert check_source(code, path=COLD) == []


class TestMissingDonation:
    def test_positive_rebound_without_donation(self):
        code = src("""
            import jax

            @jax.jit
            def step(w, g):
                return w - g

            def train(w, g):
                w = step(w, g)
                return w
        """)
        findings = check_source(code, path=COLD)
        assert rules_of(findings) == ["missing-donation"]
        assert "`w`" in findings[0].message

    def test_positive_tuple_rebind(self):
        code = src("""
            import functools
            import jax

            @functools.partial(jax.jit, donate_argnums=(0,))
            def step(w, m, g):
                return w - g, m * g

            def train(w, m, g):
                w, m = step(w, m, g)
                return w, m
        """)
        findings = check_source(code, path=COLD)
        # w (argnum 0) is donated; m (argnum 1) is not
        assert rules_of(findings) == ["missing-donation"]
        assert "`m`" in findings[0].message

    def test_negative_donated(self):
        code = src("""
            import functools
            import jax

            @functools.partial(jax.jit, donate_argnums=(0, 1))
            def step(w, m, g):
                return w - g, m * g

            def train(w, m, g):
                w, m = step(w, m, g)
                return w, m
        """)
        assert check_source(code, path=COLD) == []

    def test_negative_no_rebind(self):
        code = src("""
            import jax

            @jax.jit
            def score(w, x):
                return w @ x

            def run(w, x):
                s = score(w, x)
                return s
        """)
        assert check_source(code, path=COLD) == []

    def test_pragma_suppresses(self):
        code = src("""
            import jax

            @jax.jit
            def step(w, g):
                return w - g

            def train(w, g):
                # ptpu: allow[missing-donation] — tiny buffers, test only
                w = step(w, g)
                return w
        """)
        assert check_source(code, path=COLD) == []


class TestShardingMismatch:
    def test_positive_undeclared_axis(self):
        code = src("""
            from jax.sharding import PartitionSpec as P

            SPEC = P("bogus_axis", None)
        """)
        findings = check_source(code, path=COLD)
        assert rules_of(findings) == ["sharding-mismatch"]
        assert "bogus_axis" in findings[0].message

    def test_positive_undeclared_axis_in_tuple(self):
        code = src("""
            from jax.sharding import PartitionSpec

            SPEC = PartitionSpec(("data", "oops"))
        """)
        findings = check_source(code, path=COLD)
        assert rules_of(findings) == ["sharding-mismatch"]
        assert "oops" in findings[0].message

    def test_negative_declared_axes(self):
        code = src("""
            from jax.sharding import PartitionSpec as P

            A = P("data", None)
            B = P(("data", "model"))
            C = P()
        """)
        assert check_source(code, path=COLD) == []

    def test_pragma_suppresses(self):
        code = src("""
            from jax.sharding import PartitionSpec as P

            # ptpu: allow[sharding-mismatch] — external mesh contract
            SPEC = P("expert")
        """)
        assert check_source(code, path=COLD) == []

    def test_positive_collective_axis(self):
        # ISSUE 6: a typo'd axis handed to a lax collective fails at
        # trace time on a real mesh exactly like a bad PartitionSpec
        code = src("""
            from jax import lax

            def half_step(g):
                return lax.psum(g, "modle")
        """)
        findings = check_source(code, path=COLD)
        assert rules_of(findings) == ["sharding-mismatch"]
        assert "modle" in findings[0].message

    def test_positive_collective_axis_kwarg_and_index(self):
        code = src("""
            import jax

            def who(x):
                i = jax.lax.axis_index("bogus")
                return jax.lax.all_gather(x, axis_name="nope")
        """)
        findings = check_source(code, path=COLD)
        assert rules_of(findings) == ["sharding-mismatch"] * 2

    def test_negative_collectives_on_declared_axes(self):
        # "batch" is the serving-mesh axis declared by parallel/mesh.py
        # (BATCH_AXIS) — NamedSharding-annotated serving entry points
        # and their collectives land clean without pragmas
        code = src("""
            import jax
            from jax import lax
            from jax.sharding import NamedSharding, PartitionSpec as P

            def rank(scores, mesh):
                spec = NamedSharding(mesh, P(("batch", "model")))
                s = lax.all_gather(scores, ("batch", "model"), tiled=True)
                return s, spec, lax.axis_index("batch")
        """)
        assert check_source(code, path=COLD) == []

    def test_negative_collective_variable_axis(self):
        # a variable axis name is resolved at run time — not lintable
        code = src("""
            from jax import lax

            def reduce_over(x, axis):
                return lax.psum(x, axis)
        """)
        assert check_source(code, path=COLD) == []


class TestConfigDrift:
    def test_positive_update_outside_platform(self):
        code = src("""
            import jax

            def setup():
                jax.config.update("jax_enable_x64", True)
        """)
        findings = check_source(code, path=COLD)
        assert rules_of(findings) == ["config-drift"]
        assert "jax_enable_x64" in findings[0].message

    def test_negative_platform_module_owns_config(self):
        code = src("""
            import jax

            def setup():
                jax.config.update("jax_enable_x64", True)
        """)
        path = "predictionio_tpu/utils/platform.py"
        assert check_source(code, path=path) == []

    def test_pragma_suppresses(self):
        code = src("""
            import jax

            def setup():
                # ptpu: allow[config-drift] — init-time, owns this flag
                jax.config.update("jax_enable_x64", True)
        """)
        assert check_source(code, path=COLD) == []


class TestUnboundedRetry:
    RETRY = "predictionio_tpu/streaming/loop.py"  # in-scope dir

    def test_positive_hot_spin_retry(self):
        code = src("""
            def tail(store):
                while True:
                    try:
                        return store.read()
                    except Exception:
                        continue
        """)
        findings = check_source(code, path=self.RETRY)
        assert rules_of(findings) == ["unbounded-retry"]
        assert "retry_call" in findings[0].message

    def test_positive_itertools_count(self):
        code = src("""
            import itertools

            def tail(store):
                for _ in itertools.count():
                    try:
                        return store.read()
                    except OSError:
                        pass
        """)
        findings = check_source(code, path=self.RETRY)
        assert rules_of(findings) == ["unbounded-retry"]

    def test_negative_backoff_sleep(self):
        code = src("""
            import time

            def tail(store):
                while True:
                    try:
                        return store.read()
                    except Exception:
                        time.sleep(0.5)
        """)
        assert check_source(code, path=self.RETRY) == []

    def test_negative_bounded_attempts(self):
        code = src("""
            def tail(store):
                for attempt in range(5):
                    try:
                        return store.read()
                    except Exception:
                        continue
                raise RuntimeError("gave up")
        """)
        assert check_source(code, path=self.RETRY) == []

    def test_negative_blocking_get_paces(self):
        code = src("""
            def drain(q, store):
                while True:
                    item = q.get()
                    try:
                        store.write(item)
                    except Exception:
                        continue
        """)
        assert check_source(code, path=self.RETRY) == []

    def test_negative_nowait_does_not_pace(self):
        code = src("""
            def drain(q, store):
                while True:
                    try:
                        store.write(q.get_nowait())
                    except Exception:
                        continue
        """)
        findings = check_source(code, path=self.RETRY)
        assert rules_of(findings) == ["unbounded-retry"]

    def test_negative_reraise_escapes(self):
        code = src("""
            def tail(store):
                while True:
                    try:
                        return store.read()
                    except Exception:
                        raise
        """)
        assert check_source(code, path=self.RETRY) == []

    def test_negative_retry_call_helper(self):
        code = src("""
            from predictionio_tpu.utils.retrying import retry_call

            def tail(store):
                while True:
                    try:
                        return retry_call(store.read)
                    except Exception:
                        continue
        """)
        assert check_source(code, path=self.RETRY) == []

    def test_negative_out_of_scope_dir(self):
        code = src("""
            def tail(store):
                while True:
                    try:
                        return store.read()
                    except Exception:
                        continue
        """)
        assert check_source(code, path=COLD) == []

    def test_pragma_suppresses(self):
        code = src("""
            def tail(store):
                while True:
                    try:
                        return store.read()
                    except Exception:  # ptpu: allow[unbounded-retry]
                        continue
        """)
        assert check_source(code, path=self.RETRY) == []


class TestPragmaGeneral:
    def test_wildcard_allows_every_rule(self):
        code = src("""
            import jax

            def setup():
                jax.config.update("jax_enable_x64", True)  # ptpu: allow[*]
        """)
        assert check_source(code, path=COLD) == []

    def test_pragma_for_other_rule_does_not_suppress(self):
        code = src("""
            import jax

            def setup():
                # ptpu: allow[missing-donation] — wrong rule on purpose
                jax.config.update("jax_enable_x64", True)
        """)
        assert rules_of(check_source(code, path=COLD)) == ["config-drift"]


class TestMaterializedGather:
    """`table[indices]` advanced-indexing gathers inside jitted
    train/serve hot-path functions (ISSUE 7): the [B, L, r]-shaped HBM
    temps behind the BENCH_r05 roofline bound."""

    def test_positive_jitted_gather(self):
        code = src("""
            import functools
            import jax

            @functools.partial(jax.jit, static_argnames=("k",))
            def half_step(table, indices, w, k):
                F = table[indices]
                return (F * w[..., None]).sum(-2)
        """)
        assert rules_of(check_source(code, path=COLD)) \
            == ["materialized-gather"]

    def test_positive_jit_of_lambda(self):
        code = src("""
            import jax

            def make(table):
                return jax.jit(lambda tab, idx: tab[idx])
        """)
        assert rules_of(check_source(code, path=COLD)) \
            == ["materialized-gather"]

    def test_negative_unjitted_host_helper(self):
        # host-side numpy gathers pay once, not per dispatch
        code = src("""
            import numpy as np

            def pack(table, indices):
                return np.asarray(table)[indices]
        """)
        assert check_source(code, path=COLD) == []

    def test_negative_static_index_and_scatter_builder(self):
        code = src("""
            import functools
            import jax

            @functools.partial(jax.jit, static_argnames=("sel",))
            def pick(table, acc, ids, sel):
                part = table[sel]                 # static: no temp
                return acc.at[ids].add(part)      # scatter, not gather
        """)
        assert check_source(code, path=COLD) == []

    def test_negative_outside_hot_packages(self):
        code = src("""
            import jax

            @jax.jit
            def gather(table, indices):
                return table[indices]
        """)
        assert check_source(code,
                            path="predictionio_tpu/rollout/x.py") == []

    def test_pragma_suppresses(self):
        code = src("""
            import jax

            @jax.jit
            def serve(table, idx):
                # ptpu: allow[materialized-gather] — [B, r] row fetch
                return table[idx]
        """)
        assert check_source(code, path=COLD) == []


class TestRepoWide:
    def test_package_is_clean(self):
        findings = run_check([PKG])
        assert findings == [], "\n".join(f.format() for f in findings)

    def test_unknown_rule_rejected(self):
        with pytest.raises(ValueError, match="unknown rule"):
            run_check([PKG], rule_names=["not-a-rule"])

    def test_rule_catalogue_complete(self):
        assert set(RULES) == {
            "host-sync-in-hot-path", "recompile-hazard",
            "missing-donation", "sharding-mismatch", "config-drift",
            "materialized-gather", "unbounded-retry",
            "unguarded-shared-state", "lock-order-inversion",
            "blocking-under-lock", "callback-under-lock",
            "vmem-overbudget", "dma-unwaited",
            "low-precision-accumulator", "missing-interpret-fallback",
            "implicit-reshard", "shard-map-spec-mismatch",
            "unsharded-capture", "missing-donation-sharded",
            "low-precision-reduction", "dequant-outside-funnel",
            "quantize-without-parity-gate", "unguarded-domain",
            "requant-torn-pair", "metric-catalog-drift",
            "leaked-thread", "missing-timeout", "non-atomic-persist",
            "unbounded-queue", "hot-spin-loop"}

    def test_kernel_files_clean_under_kernel_rules(self):
        # the acceptance bar: the real Pallas kernels pass the rules
        # that were written because of them
        findings = run_check(
            [os.path.join(PKG, "ops")],
            rule_names=["vmem-overbudget", "dma-unwaited",
                        "low-precision-accumulator",
                        "missing-interpret-fallback"])
        assert findings == [], "\n".join(f.format() for f in findings)

    def test_benchmarks_and_examples_clean(self):
        root = os.path.dirname(PKG)
        findings = run_check([os.path.join(root, "benchmarks"),
                              os.path.join(root, "examples")])
        assert findings == [], "\n".join(f.format() for f in findings)

    def test_full_run_wall_time_budget(self):
        # CI enforces 60 s over predictionio_tpu+benchmarks+examples;
        # guard the interprocedural pass from quadratic blowup with
        # headroom for slow runners
        import time

        t0 = time.time()
        run_check([PKG])
        assert time.time() - t0 < 30

    def test_parse_error_is_reported_not_raised(self):
        findings = check_source("def broken(:", path=COLD)
        assert rules_of(findings) == ["parse-error"]


class TestCheckCLI:
    def test_findings_exit_1(self, tmp_path, capsys):
        bad = tmp_path / "server" / "bad.py"
        bad.parent.mkdir()
        bad.write_text(src("""
            import numpy as np

            def handler(arr):
                return np.asarray(arr)
        """))
        # the hot-path rule keys off path parts, so check the parent dir
        assert main(["check", str(tmp_path)]) == 1
        out = capsys.readouterr()
        assert "host-sync-in-hot-path" in out.out
        assert "1 finding(s)" in out.err

    def test_clean_exit_0(self, tmp_path, capsys):
        good = tmp_path / "fine.py"
        good.write_text("X = 1\n")
        assert main(["check", str(good)]) == 0
        assert "clean" in capsys.readouterr().out

    def test_rule_filter_and_list(self, tmp_path, capsys):
        bad = tmp_path / "drift.py"
        bad.write_text(src("""
            import jax

            def setup():
                jax.config.update("jax_enable_x64", True)
        """))
        assert main(["check", str(bad), "--rule", "missing-donation"]) == 0
        assert main(["check", str(bad), "--rule", "config-drift"]) == 1
        assert main(["check", "--list-rules"]) == 0
        assert "config-drift" in capsys.readouterr().out


# ---------------------------------------------------------------------------
# concurrency rule family (ISSUE 5)
# ---------------------------------------------------------------------------

UNGUARDED = src("""
    import threading

    class Counter:
        def __init__(self):
            self._lock = threading.Lock()
            self._n = 0

        def inc(self):
            with self._lock:
                self._n += 1

        def read(self):
            return self._n
""")


class TestUnguardedSharedState:
    def test_positive_read_outside_lock(self):
        findings = check_source(UNGUARDED, path=COLD)
        assert rules_of(findings) == ["unguarded-shared-state"]
        assert "`self._n`" in findings[0].message
        assert "_lock" in findings[0].message

    def test_positive_write_outside_lock(self):
        code = UNGUARDED.replace("return self._n", "self._n = 0")
        findings = check_source(code, path=COLD)
        assert rules_of(findings) == ["unguarded-shared-state"]
        assert "written" in findings[0].message

    def test_negative_init_is_exempt_and_locked_access_clean(self):
        code = src("""
            import threading

            class Counter:
                def __init__(self):
                    self._lock = threading.Lock()
                    self._n = 0

                def inc(self):
                    with self._lock:
                        self._n += 1

                def read(self):
                    with self._lock:
                        return self._n
        """)
        assert check_source(code, path=COLD) == []

    def test_negative_unlocked_attrs_are_not_tracked(self):
        # attrs never written under a lock have no inferred guard
        code = src("""
            import threading

            class C:
                def __init__(self):
                    self._lock = threading.Lock()
                    self.hits = 0

                def bump(self):
                    self.hits += 1
        """)
        assert check_source(code, path=COLD) == []

    def test_guarded_by_on_access_line_suppresses(self):
        code = UNGUARDED.replace(
            "return self._n",
            "# ptpu: guarded-by[_lock] — caller holds it\n"
            "            return self._n")
        assert check_source(code, path=COLD) == []

    def test_guarded_by_on_def_line_covers_whole_method(self):
        code = UNGUARDED.replace(
            "def read(self):",
            "def read(self):  # ptpu: guarded-by[_lock] — private "
            "helper, every caller locks")
        assert check_source(code, path=COLD) == []

    def test_guarded_by_declaration_in_init_tracks_attr(self):
        # _gen is NEVER written under a with-lock, but the declaration
        # annotation forces it into the guarded set
        code = src("""
            import threading

            class C:
                def __init__(self):
                    self._lock = threading.Lock()
                    self._gen = 0  # ptpu: guarded-by[_lock]

                def read(self):
                    return self._gen
        """)
        findings = check_source(code, path=COLD)
        assert rules_of(findings) == ["unguarded-shared-state"]
        assert "`self._gen`" in findings[0].message

    def test_guarded_by_wrong_lock_does_not_suppress(self):
        code = UNGUARDED.replace(
            "return self._n",
            "# ptpu: guarded-by[_other_lock] — wrong lock on purpose\n"
            "            return self._n")
        assert rules_of(check_source(code, path=COLD)) == [
            "unguarded-shared-state"]

    def test_nested_function_resets_lock_context(self):
        # a closure defined under the lock runs later, unlocked
        code = src("""
            import threading

            class C:
                def __init__(self):
                    self._lock = threading.Lock()
                    self._n = 0

                def set(self):
                    with self._lock:
                        self._n = 1

                def deferred(self):
                    with self._lock:
                        def later():
                            return self._n
                        return later
        """)
        findings = check_source(code, path=COLD)
        assert rules_of(findings) == ["unguarded-shared-state"]
        assert "deferred" in findings[0].message

    def test_pragma_suppresses(self):
        code = UNGUARDED.replace(
            "return self._n",
            "# ptpu: allow[unguarded-shared-state] — test justification\n"
            "            return self._n")
        assert check_source(code, path=COLD) == []


LOCK_CYCLE = src("""
    import threading

    A_LOCK = threading.Lock()
    B_LOCK = threading.Lock()

    def f():
        with A_LOCK:
            with B_LOCK:
                pass

    def g():
        with B_LOCK:
            with A_LOCK:
                pass
""")


class TestLockOrderInversion:
    def test_positive_two_lock_cycle(self):
        findings = check_source(LOCK_CYCLE, path=COLD)
        assert rules_of(findings) == ["lock-order-inversion"]
        assert "A_LOCK" in findings[0].message
        assert "B_LOCK" in findings[0].message
        assert "deadlock" in findings[0].message

    def test_positive_cycle_across_classes(self):
        code = src("""
            import threading

            class P:
                def __init__(self):
                    self._lock = threading.Lock()
                    self.q = None

                def a(self):
                    with self._lock:
                        with self.q.qlock:
                            pass

            class Q:
                def __init__(self):
                    self.qlock = threading.Lock()
                    self.p = None

                def b(self):
                    with self.qlock:
                        with self.p.plock:
                            pass
        """)
        # P._lock → mod:?.qlock and mod:self.qlock → mod:?.plock do
        # not close a cycle (naming is conservative); make a real one:
        code = src("""
            import threading

            class P:
                def __init__(self):
                    self._lock_a = threading.Lock()
                    self._lock_b = threading.Lock()

                def a(self):
                    with self._lock_a:
                        with self._lock_b:
                            pass

                def b(self):
                    with self._lock_b:
                        with self._lock_a:
                            pass
        """)
        findings = check_source(code, path=COLD)
        assert rules_of(findings) == ["lock-order-inversion"]
        assert "P._lock_a" in findings[0].message

    def test_negative_consistent_order(self):
        code = src("""
            import threading

            A_LOCK = threading.Lock()
            B_LOCK = threading.Lock()

            def f():
                with A_LOCK:
                    with B_LOCK:
                        pass

            def g():
                with A_LOCK:
                    with B_LOCK:
                        pass
        """)
        assert check_source(code, path=COLD) == []

    def test_negative_sequential_not_nested(self):
        code = src("""
            import threading

            A_LOCK = threading.Lock()
            B_LOCK = threading.Lock()

            def f():
                with A_LOCK:
                    pass
                with B_LOCK:
                    pass

            def g():
                with B_LOCK:
                    pass
                with A_LOCK:
                    pass
        """)
        assert check_source(code, path=COLD) == []

    def test_multi_item_with_is_ordered(self):
        code = src("""
            import threading

            A_LOCK = threading.Lock()
            B_LOCK = threading.Lock()

            def f():
                with A_LOCK, B_LOCK:
                    pass

            def g():
                with B_LOCK, A_LOCK:
                    pass
        """)
        findings = check_source(code, path=COLD)
        assert rules_of(findings) == ["lock-order-inversion"]

    def test_pragma_suppresses_at_anchor_edge(self):
        # the finding anchors at the cycle's first edge site — the
        # inner `with B_LOCK` in f(); the pragma must cover that line
        code = LOCK_CYCLE.replace(
            "    with A_LOCK:\n        with B_LOCK:",
            "    with A_LOCK:\n"
            "        # ptpu: allow[lock-order-inversion] — test fixture\n"
            "        with B_LOCK:")
        assert check_source(code, path=COLD) == []


class TestBlockingUnderLock:
    HOT_SRV = "predictionio_tpu/server/hot.py"

    def _code(self, body):
        return src("""
            import threading
            import time
            import urllib.request

            class S:
                def __init__(self):
                    self._lock = threading.Lock()

                def m(self, dev, t, fut):
                    with self._lock:
                        {body}
        """).replace("{body}", body)

    def test_positive_sleep(self):
        findings = check_source(self._code("time.sleep(1)"),
                                path=self.HOT_SRV)
        assert rules_of(findings) == ["blocking-under-lock"]
        assert "S._lock" in findings[0].message

    def test_positive_block_until_ready_and_join_and_http(self):
        for body in ("dev.block_until_ready()", "t.join()",
                     "urllib.request.urlopen('http://x')",
                     "fut.result()"):
            findings = check_source(self._code(body), path=self.HOT_SRV)
            # block_until_ready also trips host-sync-in-hot-path (both
            # rules are right: it is a sync AND it is under a lock)
            assert "blocking-under-lock" in rules_of(findings), body

    def test_positive_storage_io(self):
        code = src("""
            import threading

            class S:
                def __init__(self):
                    self._lock = threading.Lock()
                    self.storage = None

                def m(self, event, app_id):
                    with self._lock:
                        self.storage.events().insert(event, app_id)
        """)
        findings = check_source(code, path=self.HOT_SRV)
        assert rules_of(findings) == ["blocking-under-lock"]

    def test_negative_outside_lock_or_outside_serving_stack(self):
        code = src("""
            import threading
            import time

            class S:
                def __init__(self):
                    self._lock = threading.Lock()

                def m(self):
                    with self._lock:
                        x = 1
                    time.sleep(0.01)
                    return x
        """)
        assert check_source(code, path=self.HOT_SRV) == []
        # same blocking code outside server/cache/rollout: not flagged
        assert check_source(self._code("time.sleep(1)"), path=COLD) == []

    def test_negative_str_join_with_args_not_flagged(self):
        findings = check_source(self._code("','.join(['a', 'b'])"),
                                path=self.HOT_SRV)
        assert findings == []

    def test_negative_deferred_closure_not_flagged(self):
        # defining a function under the lock is not calling it
        body = ("def later():\n"
                "                    time.sleep(1)")
        assert check_source(self._code(body), path=self.HOT_SRV) == []

    def test_pragma_suppresses(self):
        body = ("# ptpu: allow[blocking-under-lock] — test fixture\n"
                "            time.sleep(1)")
        assert check_source(self._code(body), path=self.HOT_SRV) == []


class TestCallbackUnderLock:
    BUS = src("""
        import threading

        class Bus:
            def __init__(self):
                self._lock = threading.Lock()
                self._subs = []

            def publish(self, x):
                with self._lock:
                    for fn in self._subs:
                        fn(x)
    """)

    def test_positive_loop_variable_callback(self):
        findings = check_source(self.BUS, path=COLD)
        assert rules_of(findings) == ["callback-under-lock"]
        assert "`fn(…)`" in findings[0].message

    def test_positive_param_callback(self):
        code = src("""
            import threading

            class C:
                def __init__(self):
                    self._lock = threading.Lock()

                def run(self, hook):
                    with self._lock:
                        hook()
        """)
        findings = check_source(code, path=COLD)
        assert rules_of(findings) == ["callback-under-lock"]

    def test_positive_publish_method_under_lock(self):
        code = src("""
            import threading

            class C:
                def __init__(self):
                    self._lock = threading.Lock()
                    self.bus = None

                def ingest(self, ev):
                    with self._lock:
                        self.bus.publish(ev)
        """)
        findings = check_source(code, path=COLD)
        assert rules_of(findings) == ["callback-under-lock"]
        assert ".publish" in findings[0].message

    def test_negative_snapshot_then_call_outside(self):
        # the invalidation-bus pattern: copy under lock, call outside
        code = src("""
            import threading

            class Bus:
                def __init__(self):
                    self._lock = threading.Lock()
                    self._subs = []

                def publish(self, x):
                    with self._lock:
                        subs = list(self._subs)
                    for fn in subs:
                        fn(x)
        """)
        assert check_source(code, path=COLD) == []

    def test_negative_nested_def_called_under_lock(self):
        # a locally-defined function's body is statically known
        code = src("""
            import threading

            class C:
                def __init__(self):
                    self._lock = threading.Lock()

                def run(self):
                    def helper():
                        return 1

                    with self._lock:
                        return helper()
        """)
        assert check_source(code, path=COLD) == []

    def test_pragma_suppresses(self):
        code = self.BUS.replace(
            "fn(x)",
            "# ptpu: allow[callback-under-lock] — test fixture\n"
            "                    fn(x)")
        assert check_source(code, path=COLD) == []


class TestCheckFormatsAndBaseline:
    BAD = src("""
        import numpy as np

        def handler(arr):
            return np.asarray(arr)
    """)

    def _bad_dir(self, tmp_path):
        d = tmp_path / "server"
        d.mkdir()
        (d / "bad.py").write_text(self.BAD)
        return tmp_path

    def test_format_json(self, tmp_path, capsys):
        import json

        target = self._bad_dir(tmp_path)
        assert main(["check", str(target), "--format", "json"]) == 1
        doc = json.loads(capsys.readouterr().out)
        assert doc["count"] == 1
        f = doc["findings"][0]
        assert f["rule"] == "host-sync-in-hot-path"
        assert f["line"] == 5 and f["path"].endswith("bad.py")

    def test_format_sarif(self, tmp_path, capsys):
        import json

        target = self._bad_dir(tmp_path)
        assert main(["check", str(target), "--format", "sarif"]) == 1
        doc = json.loads(capsys.readouterr().out)
        assert doc["version"] == "2.1.0"
        run = doc["runs"][0]
        assert run["tool"]["driver"]["name"] == "ptpu-check"
        declared = {r["id"] for r in run["tool"]["driver"]["rules"]}
        from predictionio_tpu.analysis import RULES as rules

        assert set(rules) <= declared
        result = run["results"][0]
        assert result["ruleId"] == "host-sync-in-hot-path"
        loc = result["locations"][0]["physicalLocation"]
        assert loc["region"]["startLine"] == 5
        assert loc["artifactLocation"]["uri"].endswith("bad.py")

    def test_sarif_clean_run_is_valid(self, tmp_path, capsys):
        import json

        good = tmp_path / "fine.py"
        good.write_text("X = 1\n")
        assert main(["check", str(good), "--format", "sarif"]) == 0
        doc = json.loads(capsys.readouterr().out)
        assert doc["runs"][0]["results"] == []

    def test_baseline_write_then_gate(self, tmp_path, capsys):
        target = self._bad_dir(tmp_path)
        bl = tmp_path / "baseline.json"
        assert main(["check", str(target),
                     "--baseline", str(bl), "--write-baseline"]) == 0
        assert bl.exists()
        # baselined finding no longer fails the gate
        assert main(["check", str(target), "--baseline", str(bl)]) == 0
        out = capsys.readouterr()
        assert "baselined" in out.out
        # a NEW finding still fails, and is the only one printed
        (target / "server" / "bad2.py").write_text(self.BAD)
        assert main(["check", str(target), "--baseline", str(bl)]) == 1
        out = capsys.readouterr()
        assert "bad2.py" in out.out
        assert "bad.py:" not in out.out.replace("bad2.py:", "")
        assert "new finding" in out.err

    def test_baseline_counts_per_key(self, tmp_path):
        # two identical findings in one file, baseline records both;
        # a third instance of the same (path, rule, message) fails
        d = tmp_path / "server"
        d.mkdir()
        two = ("import numpy as np\n\n"
               "def handler(arr):\n"
               "    a = np.asarray(arr)\n"
               "    b = np.asarray(arr)\n"
               "    return a, b\n")
        (d / "bad.py").write_text(two)
        bl = tmp_path / "bl.json"
        assert main(["check", str(tmp_path),
                     "--baseline", str(bl), "--write-baseline"]) == 0
        assert main(["check", str(tmp_path), "--baseline", str(bl)]) == 0
        three = two.replace("return a, b",
                            "c = np.asarray(arr)\n    return a, b, c")
        (d / "bad.py").write_text(three)
        assert main(["check", str(tmp_path), "--baseline", str(bl)]) == 1

    def test_missing_baseline_file_is_an_error(self, tmp_path, capsys):
        good = tmp_path / "fine.py"
        good.write_text("X = 1\n")
        assert main(["check", str(good),
                     "--baseline", str(tmp_path / "nope.json")]) == 2
        assert "cannot read baseline" in capsys.readouterr().err

    def test_write_baseline_requires_path(self, tmp_path, capsys):
        good = tmp_path / "fine.py"
        good.write_text("X = 1\n")
        assert main(["check", str(good), "--write-baseline"]) == 2


# ---------------------------------------------------------------------------
# interprocedural layer: call graph + effect summaries (ISSUE 8)
# ---------------------------------------------------------------------------

class TestInterprocedural:
    def test_two_hop_host_sync_reported_at_hot_site(self):
        findings = check_project({
            "pkg/utils/convert.py": src("""
                import numpy as np

                def land(x):
                    return np.asarray(x)
            """),
            "pkg/lib/middle.py": src("""
                from pkg.utils.convert import land

                def shuttle(x):
                    return land(x) + 1
            """),
            "pkg/server/handler.py": src("""
                from pkg.lib.middle import shuttle

                def handle(q):
                    return shuttle(q)
            """),
        })
        assert rules_of(findings) == ["host-sync-in-hot-path"]
        f = findings[0]
        # anchored at the HOT call site, not in the helpers
        assert f.path == "pkg/server/handler.py"
        # ...with the full chain in the message
        assert "shuttle" in f.message and "land" in f.message
        assert "np.asarray" in f.message
        # ...and the hop locations machine-readable for SARIF
        assert [p for p, _, _ in f.related] == [
            "pkg/lib/middle.py", "pkg/utils/convert.py"]

    def test_helper_in_hot_package_not_double_reported(self):
        # the helper's own body gets the direct finding; the call site
        # must not add a second one
        findings = check_project({
            "pkg/server/helper.py": src("""
                import numpy as np

                def land(x):
                    return np.asarray(x)
            """),
            "pkg/server/handler.py": src("""
                from pkg.server.helper import land

                def handle(q):
                    return land(q)
            """),
        })
        assert rules_of(findings) == ["host-sync-in-hot-path"]
        assert findings[0].path == "pkg/server/helper.py"

    def test_pragma_at_direct_site_stops_propagation(self):
        # blessing the one named D2H helper blesses its callers
        findings = check_project({
            "pkg/utils/convert.py": src("""
                import numpy as np

                def land(x):
                    # ptpu: allow[host-sync-in-hot-path] — blessed
                    return np.asarray(x)
            """),
            "pkg/server/handler.py": src("""
                from pkg.utils.convert import land

                def handle(q):
                    return land(q)
            """),
        })
        assert findings == []

    def test_pragma_at_call_site_suppresses(self):
        findings = check_project({
            "pkg/utils/convert.py": src("""
                import numpy as np

                def land(x):
                    return np.asarray(x)
            """),
            "pkg/server/handler.py": src("""
                from pkg.utils.convert import land

                def handle(q):
                    # ptpu: allow[host-sync-in-hot-path] — one-shot
                    return land(q)
            """),
        })
        assert findings == []

    def test_recursion_and_cycles_handled(self):
        # mutual recursion must neither crash nor lose the effect
        findings = check_project({
            "pkg/utils/recur.py": src("""
                import numpy as np

                def a(x):
                    return b(x)

                def b(x):
                    if x:
                        return a(x)
                    return np.asarray(x)
            """),
            "pkg/server/h.py": src("""
                from pkg.utils.recur import a

                def handle(q):
                    return a(q)
            """),
        })
        assert rules_of(findings) == ["host-sync-in-hot-path"]
        assert findings[0].path == "pkg/server/h.py"

    def test_self_recursion_no_crash(self):
        assert check_project({
            "pkg/lib/r.py": "def f(x):\n    return f(x - 1)\n",
        }) == []

    def test_method_vs_function_resolution(self):
        # a module FUNCTION named like a method of another class must
        # not satisfy a self.X() call — only the enclosing class's own
        # method does
        findings = check_project({
            "pkg/utils/sink.py": src("""
                import numpy as np

                def flush(x):
                    return np.asarray(x)
            """),
            "pkg/server/srv.py": src("""
                from pkg.utils.sink import flush

                class Handler:
                    def flush(self, x):
                        return x  # clean method, same name

                    def a(self, q):
                        return self.flush(q)   # clean: own method

                    def b(self, q):
                        return flush(q)        # dirty: module func
            """),
        })
        assert rules_of(findings) == ["host-sync-in-hot-path"]
        assert "in hot function `b`" in findings[0].message \
            or "`Handler.b`" in findings[0].message

    def test_relative_import_resolution(self):
        findings = check_project({
            "predictionio_tpu/utils/conv.py": src("""
                import numpy as np

                def land(x):
                    return np.asarray(x)
            """),
            "predictionio_tpu/server/web.py": src("""
                from ..utils.conv import land

                def handle(q):
                    return land(q)
            """),
        })
        assert rules_of(findings) == ["host-sync-in-hot-path"]
        assert findings[0].path == "predictionio_tpu/server/web.py"

    def test_ambiguous_suffix_resolves_to_nothing(self):
        # two modules define helper(); the call must not guess
        findings = check_project({
            "pkg/a/util.py": src("""
                import numpy as np

                def helper(x):
                    return np.asarray(x)
            """),
            "pkg/b/util.py": src("""
                def helper(x):
                    return x
            """),
            "pkg/server/h.py": src("""
                from util import helper

                def handle(q):
                    return helper(q)
            """),
        })
        assert findings == []

    def test_gather_sink_through_helper(self):
        findings = check_project({
            "pkg/ops/helper.py": src("""
                def fetch_rows(table, ids):
                    return table[ids]
            """),
            "pkg/models/train.py": src("""
                import jax
                from pkg.ops.helper import fetch_rows

                @jax.jit
                def step(table, idx):
                    return fetch_rows(table, idx)
            """),
        }, rule_names=["materialized-gather"])
        assert rules_of(findings) == ["materialized-gather"]
        assert findings[0].path == "pkg/models/train.py"
        assert "fetch_rows" in findings[0].message

    def test_gather_sink_two_hops_and_kwarg(self):
        findings = check_project({
            "pkg/ops/inner.py": src("""
                def raw(table, ids):
                    return table[ids]
            """),
            "pkg/ops/outer.py": src("""
                from pkg.ops.inner import raw

                def fetch(table, rows):
                    return raw(table, rows)
            """),
            "pkg/models/train.py": src("""
                import jax
                from pkg.ops.outer import fetch

                @jax.jit
                def step(table, idx):
                    return fetch(table, rows=idx)
            """),
        }, rule_names=["materialized-gather"])
        assert rules_of(findings) == ["materialized-gather"]
        assert findings[0].path == "pkg/models/train.py"

    def test_blocking_chain_under_lock(self):
        findings = check_project({
            "pkg/server/srv.py": src("""
                import threading

                class Server:
                    def __init__(self):
                        self._lock = threading.Lock()

                    def _slow(self):
                        import time
                        time.sleep(1)

                    def tick(self):
                        with self._lock:
                            self._slow()
            """),
        }, rule_names=["blocking-under-lock"])
        assert rules_of(findings) == ["blocking-under-lock"]
        assert "_slow" in findings[0].message
        assert "time.sleep" in findings[0].message

    def test_callback_delivery_chain_under_lock(self):
        findings = check_project({
            "pkg/cache/bus.py": src("""
                import threading

                class Bus:
                    def __init__(self):
                        self._lock = threading.Lock()
                        self.subs = []

                    def _deliver(self, ev):
                        self.bus.publish(ev)

                    def ingest(self, ev):
                        with self._lock:
                            self._deliver(ev)
            """),
        }, rule_names=["callback-under-lock"])
        assert rules_of(findings) == ["callback-under-lock"]
        assert "_deliver" in findings[0].message

    def test_callable_passed_into_invoking_helper_under_lock(self):
        findings = check_project({
            "pkg/cache/run.py": src("""
                import threading

                def run_hook(fn, ev):
                    return fn(ev)

                class Cache:
                    def __init__(self):
                        self._lock = threading.Lock()

                    def fire(self, hook, ev):
                        with self._lock:
                            run_hook(hook, ev)
            """),
        }, rule_names=["callback-under-lock"])
        assert rules_of(findings) == ["callback-under-lock"]
        assert "run_hook" in findings[0].message

    def test_lock_order_edge_through_call(self):
        # with a: self._refill() where _refill takes b, elsewhere
        # with b: takes a — a cycle with no lexical nesting of a and b
        findings = check_project({
            "pkg/cache/two.py": src("""
                import threading

                class Two:
                    def __init__(self):
                        self._a_lock = threading.Lock()
                        self._b_lock = threading.Lock()

                    def _refill(self):
                        with self._b_lock:
                            pass

                    def forward(self):
                        with self._a_lock:
                            self._refill()

                    def backward(self):
                        with self._b_lock:
                            with self._a_lock:
                                pass
            """),
        }, rule_names=["lock-order-inversion"])
        assert rules_of(findings) == ["lock-order-inversion"]
        assert "Two._a_lock" in findings[0].message
        assert "Two._b_lock" in findings[0].message

    def test_cli_reports_two_hop_sync(self, tmp_path, capsys):
        # the acceptance-criteria path: a seeded two-call-deep host
        # sync surfaces through the real `ptpu check` entry point
        (tmp_path / "utils").mkdir()
        (tmp_path / "lib").mkdir()
        (tmp_path / "server").mkdir()
        (tmp_path / "utils" / "conv.py").write_text(src("""
            import numpy as np

            def land(x):
                return np.asarray(x)
        """))
        (tmp_path / "lib" / "mid.py").write_text(src("""
            from utils.conv import land

            def shuttle(x):
                return land(x)
        """))
        (tmp_path / "server" / "web.py").write_text(src("""
            from lib.mid import shuttle

            def handle(q):
                return shuttle(q)
        """))
        assert main(["check", str(tmp_path)]) == 1
        out = capsys.readouterr().out
        assert "host-sync-in-hot-path" in out
        assert "shuttle" in out and "land" in out
        assert "web.py" in out


class TestTakeGather:
    def test_jnp_take_positive(self):
        code = src("""
            import jax
            import jax.numpy as jnp

            @jax.jit
            def step(table, idx):
                return jnp.take(table, idx)
        """)
        findings = check_source(code, path=COLD)
        assert rules_of(findings) == ["materialized-gather"]
        assert "jnp.take" in findings[0].message

    def test_jnp_take_along_axis_kwarg(self):
        code = src("""
            import jax
            import jax.numpy as jnp

            @jax.jit
            def step(table, idx):
                return jnp.take_along_axis(table, indices=idx, axis=0)
        """)
        findings = check_source(code, path=COLD)
        assert rules_of(findings) == ["materialized-gather"]

    def test_jnp_take_static_index_negative(self):
        code = src("""
            import functools
            import jax
            import jax.numpy as jnp

            @functools.partial(jax.jit, static_argnames=("idx",))
            def step(table, idx):
                return jnp.take(table, idx)
        """)
        assert check_source(code, path=COLD) == []

    def test_jnp_take_outside_hot_dirs_negative(self):
        code = src("""
            import jax
            import jax.numpy as jnp

            @jax.jit
            def step(table, idx):
                return jnp.take(table, idx)
        """)
        assert check_source(code,
                            path="predictionio_tpu/obs/x.py") == []

    def test_jnp_take_pragma(self):
        code = src("""
            import jax
            import jax.numpy as jnp

            @jax.jit
            def serve(table, idx):
                # ptpu: allow[materialized-gather] — [B, r] row fetch
                return jnp.take(table, idx)
        """)
        assert check_source(code, path=COLD) == []


# ---------------------------------------------------------------------------
# Pallas kernel-safety rules (ISSUE 8)
# ---------------------------------------------------------------------------

KERNEL_PRELUDE = src("""
    import functools

    import jax
    import jax.numpy as jnp
    from jax.experimental import pallas as pl
    from jax.experimental.pallas import tpu as pltpu
""")


def ksrc(text):
    """Kernel-test source: the pallas prelude + a dedented body (the
    two halves dedent separately — their literal indents differ)."""
    return KERNEL_PRELUDE + src(text)


class TestVmemOverbudget:
    def test_seeded_overbudget_kernel(self):
        code = ksrc("""
            def kern(x_ref, o_ref):
                o_ref[:] = x_ref[:]

            def big(x):
                return pl.pallas_call(
                    kern,
                    grid=(8,),
                    in_specs=[pl.BlockSpec((4096, 4096),
                                           lambda i: (i, 0),
                                           memory_space=pltpu.VMEM)],
                    out_specs=pl.BlockSpec((4096, 4096),
                                           lambda i: (i, 0),
                                           memory_space=pltpu.VMEM),
                    out_shape=jax.ShapeDtypeStruct((4096, 4096),
                                                   jnp.float32),
                    interpret=True,
                )(x)
        """)
        findings = check_source(code, path="ops/k.py",
                                rule_names=["vmem-overbudget"])
        assert rules_of(findings) == ["vmem-overbudget"]
        assert "16 MiB" in findings[0].message

    def test_rank_scenario_from_autotune_grid(self):
        # r is free → bound to the autotune rank grid; 128·chunk·r·4B
        # double-buffered clears the budget only at r=128
        code = ksrc("""
            def kern(x_ref, o_ref, acc):
                o_ref[:] = x_ref[:]

            def run(x, r):
                return pl.pallas_call(
                    kern,
                    grid=(4,),
                    in_specs=[pl.BlockSpec((128, 512, r),
                                           lambda i: (i, 0, 0),
                                           memory_space=pltpu.VMEM)],
                    out_specs=pl.BlockSpec((128, 512, r),
                                           lambda i: (i, 0, 0),
                                           memory_space=pltpu.VMEM),
                    out_shape=jax.ShapeDtypeStruct((512, 512, r),
                                                   jnp.float32),
                    scratch_shapes=[pltpu.VMEM((r, r), jnp.float32)],
                    interpret=True,
                )(x)
        """)
        findings = check_source(code, path="ops/k.py",
                                rule_names=["vmem-overbudget"])
        assert rules_of(findings) == ["vmem-overbudget"]
        assert "rank 128" in findings[0].message

    def test_constraint_makes_scenario_infeasible(self):
        # the block clears the budget at rank 64 and would blow it at
        # rank 128 — but an enclosing bound excludes r=128 (the
        # solve.py scratch-variant pattern), so the call is clean
        code = ksrc("""
            _RP_MAX = 64

            def kern(x_ref, o_ref):
                o_ref[:] = x_ref[:]

            def run(x, r):
                if r <= _RP_MAX:
                    return pl.pallas_call(
                        kern,
                        grid=(4,),
                        in_specs=[pl.BlockSpec((48, 512, r),
                                               lambda i: (i, 0, 0),
                                               memory_space=pltpu.VMEM)],
                        out_specs=pl.BlockSpec((8, 128),
                                               lambda i: (i, 0),
                                               memory_space=pltpu.VMEM),
                        out_shape=jax.ShapeDtypeStruct((32, 128),
                                                       jnp.float32),
                        interpret=True,
                    )(x)
        """)
        assert check_source(code, path="ops/k.py",
                            rule_names=["vmem-overbudget"]) == []

    def test_same_shapes_without_constraint_flagged_at_128(self):
        # the twin of the test above minus the bound: rank 128 is now
        # feasible and 25 MiB of double-buffered block exceeds budget
        code = ksrc("""
            def kern(x_ref, o_ref):
                o_ref[:] = x_ref[:]

            def run(x, r):
                return pl.pallas_call(
                    kern,
                    grid=(4,),
                    in_specs=[pl.BlockSpec((48, 512, r),
                                           lambda i: (i, 0, 0),
                                           memory_space=pltpu.VMEM)],
                    out_specs=pl.BlockSpec((8, 128),
                                           lambda i: (i, 0),
                                           memory_space=pltpu.VMEM),
                    out_shape=jax.ShapeDtypeStruct((32, 128),
                                                   jnp.float32),
                    interpret=True,
                )(x)
        """)
        findings = check_source(code, path="ops/k.py",
                                rule_names=["vmem-overbudget"])
        assert rules_of(findings) == ["vmem-overbudget"]
        assert "rank 128" in findings[0].message

    def test_any_memory_space_not_counted(self):
        # the fused_gram idiom: the big table stays in HBM (ANY) and
        # rows stream via DMA — only VMEM residents count
        code = ksrc("""
            def kern(t_ref, o_ref):
                o_ref[:] = o_ref[:]

            def run(table):
                return pl.pallas_call(
                    kern,
                    grid=(4,),
                    in_specs=[pl.BlockSpec(memory_space=pltpu.ANY)],
                    out_specs=pl.BlockSpec((8, 128),
                                           lambda i: (i, 0),
                                           memory_space=pltpu.VMEM),
                    out_shape=jax.ShapeDtypeStruct((32, 128),
                                                   jnp.float32),
                    interpret=True,
                )(table)
        """)
        assert check_source(code, path="ops/k.py",
                            rule_names=["vmem-overbudget"]) == []

    def test_pragma_suppresses(self):
        code = ksrc("""
            def kern(x_ref, o_ref):
                o_ref[:] = x_ref[:]

            def big(x):
                # ptpu: allow[vmem-overbudget] — measured: fits
                return pl.pallas_call(
                    kern,
                    grid=(8,),
                    in_specs=[pl.BlockSpec((4096, 4096),
                                           lambda i: (i, 0),
                                           memory_space=pltpu.VMEM)],
                    out_specs=pl.BlockSpec((4096, 4096),
                                           lambda i: (i, 0),
                                           memory_space=pltpu.VMEM),
                    out_shape=jax.ShapeDtypeStruct((4096, 4096),
                                                   jnp.float32),
                    interpret=True,
                )(x)
        """)
        assert check_source(code, path="ops/k.py",
                            rule_names=["vmem-overbudget"]) == []


class TestDmaUnwaited:
    def test_start_without_wait(self):
        code = ksrc("""
            def kern(h_ref, o_ref, buf, sem):
                pltpu.make_async_copy(h_ref.at[0], buf.at[0],
                                      sem.at[0]).start()
                o_ref[:] = buf[0]
        """)
        findings = check_source(code, path="ops/k.py",
                                rule_names=["dma-unwaited"])
        assert rules_of(findings) == ["dma-unwaited"]
        assert "no matching .wait()" in findings[0].message

    def test_var_start_wait_pair_clean(self):
        code = ksrc("""
            def kern(h_ref, o_ref, buf, sem):
                c = pltpu.make_async_copy(h_ref.at[0], buf.at[0],
                                          sem.at[0])
                c.start()
                c.wait()
                o_ref[:] = buf[0]
        """)
        assert check_source(code, path="ops/k.py",
                            rule_names=["dma-unwaited"]) == []

    def test_split_start_and_wait_matched_by_semaphore(self):
        # the fused_gram pipeline idiom: issue in one nested helper,
        # drain in a sibling — matched through the semaphore slot
        code = ksrc("""
            def kern(h_ref, o_ref, buf, sems):
                def issue(slot):
                    pltpu.make_async_copy(h_ref.at[slot],
                                          buf.at[slot],
                                          sems.at[slot]).start()

                def drain(slot):
                    pltpu.make_async_copy(h_ref.at[slot],
                                          buf.at[slot],
                                          sems.at[slot]).wait()

                issue(0)
                drain(0)
                o_ref[:] = buf[0]
        """)
        assert check_source(code, path="ops/k.py",
                            rule_names=["dma-unwaited"]) == []

    def test_slot_restarted_before_wait(self):
        code = ksrc("""
            def kern(h_ref, o_ref, buf, sem):
                pltpu.make_async_copy(h_ref.at[0], buf.at[0],
                                      sem.at[0]).start()
                pltpu.make_async_copy(h_ref.at[1], buf.at[1],
                                      sem.at[0]).start()
                pltpu.make_async_copy(h_ref.at[0], buf.at[0],
                                      sem.at[0]).wait()
                o_ref[:] = buf[0]
        """)
        findings = check_source(code, path="ops/k.py",
                                rule_names=["dma-unwaited"])
        assert rules_of(findings) == ["dma-unwaited"]
        assert "restarted before its wait" in findings[0].message


class TestLowPrecisionAccumulator:
    BF16 = ksrc("""
        def kern(x_ref, o_ref, acc):
            acc[:] = acc[:] + x_ref[:]
            o_ref[:] = acc[:]

        def run(x):
            return pl.pallas_call(
                kern,
                in_specs=[pl.BlockSpec(memory_space=pltpu.VMEM)],
                out_specs=pl.BlockSpec(memory_space=pltpu.VMEM),
                out_shape=jax.ShapeDtypeStruct((8, 128), jnp.float32),
                scratch_shapes=[pltpu.VMEM((8, 128), jnp.bfloat16)],
                interpret=True,
            )(x)
    """)

    def test_bf16_accumulation_flagged(self):
        findings = check_source(
            self.BF16, path="ops/k.py",
            rule_names=["low-precision-accumulator"])
        assert rules_of(findings) == ["low-precision-accumulator"]
        assert "bfloat16" in findings[0].message

    def test_f32_accumulator_clean(self):
        code = self.BF16.replace("jnp.bfloat16)],", "jnp.float32)],")
        assert check_source(
            code, path="ops/k.py",
            rule_names=["low-precision-accumulator"]) == []

    def test_augassign_and_dot_into_bf16(self):
        code = ksrc("""
            def kern(x_ref, o_ref, acc):
                acc[:] += x_ref[:]
                acc[:] = jax.lax.dot_general(
                    x_ref[:], x_ref[:], (((0,), (0,)), ((), ())))
                o_ref[:] = acc[:]

            def run(x):
                return pl.pallas_call(
                    kern,
                    in_specs=[pl.BlockSpec(memory_space=pltpu.VMEM)],
                    out_specs=pl.BlockSpec(memory_space=pltpu.VMEM),
                    out_shape=jax.ShapeDtypeStruct((8, 128),
                                                   jnp.float32),
                    scratch_shapes=[pltpu.VMEM((128, 128),
                                               jnp.float16)],
                    interpret=True,
                )(x)
        """)
        findings = check_source(
            code, path="ops/k.py",
            rule_names=["low-precision-accumulator"])
        assert rules_of(findings) == ["low-precision-accumulator"] * 2

    def test_partial_bound_kernel_mapping(self):
        # functools.partial-bound leading args shift the ref mapping —
        # the fused_gram wiring shape
        code = ksrc("""
            def kern(n, x_ref, o_ref, acc):
                acc[:] = acc[:] + x_ref[:]
                o_ref[:] = acc[:]

            def run(x):
                k = functools.partial(kern, 4)
                return pl.pallas_call(
                    k,
                    in_specs=[pl.BlockSpec(memory_space=pltpu.VMEM)],
                    out_specs=pl.BlockSpec(memory_space=pltpu.VMEM),
                    out_shape=jax.ShapeDtypeStruct((8, 128),
                                                   jnp.float32),
                    scratch_shapes=[pltpu.VMEM((8, 128),
                                               jnp.bfloat16)],
                    interpret=True,
                )(x)
        """)
        findings = check_source(
            code, path="ops/k.py",
            rule_names=["low-precision-accumulator"])
        assert rules_of(findings) == ["low-precision-accumulator"]


class TestMissingInterpretFallback:
    def test_no_interpret_kwarg_flagged(self):
        code = ksrc("""
            def kern(x_ref, o_ref):
                o_ref[:] = x_ref[:]

            def run(x):
                return pl.pallas_call(
                    kern,
                    in_specs=[pl.BlockSpec(memory_space=pltpu.VMEM)],
                    out_specs=pl.BlockSpec(memory_space=pltpu.VMEM),
                    out_shape=jax.ShapeDtypeStruct((8, 128),
                                                   jnp.float32),
                )(x)
        """)
        findings = check_source(
            code, path="ops/k.py",
            rule_names=["missing-interpret-fallback"])
        assert rules_of(findings) == ["missing-interpret-fallback"]
        assert "support-gated dispatcher" in findings[0].message

    def test_interpret_param_clean(self):
        code = ksrc("""
            def kern(x_ref, o_ref):
                o_ref[:] = x_ref[:]

            def run(x, interpret=False):
                return pl.pallas_call(
                    kern,
                    in_specs=[pl.BlockSpec(memory_space=pltpu.VMEM)],
                    out_specs=pl.BlockSpec(memory_space=pltpu.VMEM),
                    out_shape=jax.ShapeDtypeStruct((8, 128),
                                                   jnp.float32),
                    interpret=interpret,
                )(x)
        """)
        assert check_source(
            code, path="ops/k.py",
            rule_names=["missing-interpret-fallback"]) == []

    def test_interpret_false_literal_flagged(self):
        code = ksrc("""
            def kern(x_ref, o_ref):
                o_ref[:] = x_ref[:]

            def run(x):
                return pl.pallas_call(
                    kern,
                    in_specs=[pl.BlockSpec(memory_space=pltpu.VMEM)],
                    out_specs=pl.BlockSpec(memory_space=pltpu.VMEM),
                    out_shape=jax.ShapeDtypeStruct((8, 128),
                                                   jnp.float32),
                    interpret=False,
                )(x)
        """)
        findings = check_source(
            code, path="ops/k.py",
            rule_names=["missing-interpret-fallback"])
        assert rules_of(findings) == ["missing-interpret-fallback"]

    def test_non_pallas_module_ignored(self):
        assert check_source(
            "def pallas_call(x):\n    return x\n",
            path="ops/k.py",
            rule_names=["missing-interpret-fallback"]) == []


# ---------------------------------------------------------------------------
# checker robustness: broken files become findings, never crashes
# ---------------------------------------------------------------------------

class TestCheckerRobustness:
    def test_syntax_error_file_is_per_file_finding(self, tmp_path):
        d = tmp_path / "server"
        d.mkdir()
        (d / "broken.py").write_text("def broken(:\n")
        (d / "bad.py").write_text(src("""
            import numpy as np

            def handler(arr):
                return np.asarray(arr)
        """))
        findings = run_check([str(tmp_path)])
        rules = rules_of(findings)
        # the broken file reports, AND the rest of the tree still runs
        assert "parse-error" in rules
        assert "host-sync-in-hot-path" in rules

    def test_undecodable_file_is_per_file_finding(self, tmp_path):
        d = tmp_path / "server"
        d.mkdir()
        (d / "binary.py").write_bytes(b"\xff\xfe\x00\x00garbage")
        (d / "fine.py").write_text("X = 1\n")
        findings = run_check([str(tmp_path)])
        assert rules_of(findings) == ["parse-error"]
        assert "binary.py" in findings[0].path

    def test_cli_exit_code_on_broken_fixture(self, tmp_path, capsys):
        (tmp_path / "broken.py").write_text("def broken(:\n")
        assert main(["check", str(tmp_path)]) == 1
        assert "parse-error" in capsys.readouterr().out


# ---------------------------------------------------------------------------
# baseline ratchet (ISSUE 8 satellite)
# ---------------------------------------------------------------------------

class TestBaselineRatchet:
    TWO = src("""
        import numpy as np

        def handler(arr):
            a = np.asarray(arr)
            b = np.asarray(arr)
            return a, b
    """)
    ONE = src("""
        import numpy as np

        def handler(arr):
            return np.asarray(arr)
    """)

    def _write(self, tmp_path, text):
        d = tmp_path / "server"
        d.mkdir(exist_ok=True)
        (d / "bad.py").write_text(text)

    def test_gate_prints_shrinkable_entries(self, tmp_path, capsys):
        self._write(tmp_path, self.TWO)
        bl = tmp_path / "bl.json"
        assert main(["check", str(tmp_path), "--baseline", str(bl),
                     "--write-baseline"]) == 0
        self._write(tmp_path, self.ONE)
        assert main(["check", str(tmp_path),
                     "--baseline", str(bl)]) == 0
        err = capsys.readouterr().err
        assert "ratchet down" in err
        assert "recorded 2, found 1" in err

    def test_write_baseline_auto_tightens(self, tmp_path, capsys):
        from predictionio_tpu.analysis import load_baseline

        self._write(tmp_path, self.TWO)
        bl = tmp_path / "bl.json"
        assert main(["check", str(tmp_path), "--baseline", str(bl),
                     "--write-baseline"]) == 0
        self._write(tmp_path, self.ONE)
        assert main(["check", str(tmp_path), "--baseline", str(bl),
                     "--write-baseline"]) == 0
        recorded = load_baseline(str(bl))
        assert sum(recorded.values()) == 1  # 2 → 1: ratcheted

    def test_write_baseline_refuses_new_debt(self, tmp_path, capsys):
        self._write(tmp_path, self.ONE)
        bl = tmp_path / "bl.json"
        assert main(["check", str(tmp_path), "--baseline", str(bl),
                     "--write-baseline"]) == 0
        # a NEW kind of finding appears; the ratchet must not absorb it
        (tmp_path / "server" / "drift.py").write_text(src("""
            import jax

            def setup():
                jax.config.update("jax_enable_x64", True)
        """))
        assert main(["check", str(tmp_path), "--baseline", str(bl),
                     "--write-baseline"]) == 1
        err = capsys.readouterr().err
        assert "NOT absorbed" in err
        # the baseline still gates: the new finding fails the gate
        assert main(["check", str(tmp_path),
                     "--baseline", str(bl)]) == 1

    def test_baseline_grow_records_new_debt(self, tmp_path, capsys):
        self._write(tmp_path, self.ONE)
        bl = tmp_path / "bl.json"
        assert main(["check", str(tmp_path), "--baseline", str(bl),
                     "--write-baseline"]) == 0
        (tmp_path / "server" / "drift.py").write_text(src("""
            import jax

            def setup():
                jax.config.update("jax_enable_x64", True)
        """))
        assert main(["check", str(tmp_path), "--baseline", str(bl),
                     "--write-baseline", "--baseline-grow"]) == 0
        assert main(["check", str(tmp_path),
                     "--baseline", str(bl)]) == 0

    def test_shrinkable_entries_api(self):
        from predictionio_tpu.analysis import shrinkable_entries

        findings = check_source(self.ONE,
                                path="predictionio_tpu/server/s.py")
        assert len(findings) == 1
        key = (findings[0].path, findings[0].rule, findings[0].message)
        shrink = shrinkable_entries(findings, {key: 3})
        assert shrink == [(key, 3, 1)]
        assert shrinkable_entries(findings, {key: 1}) == []


# ---------------------------------------------------------------------------
# runtime complement: recompile sentinel + transfer guard wiring
# ---------------------------------------------------------------------------

@dataclass
class _EchoQuery:
    v: int = 0


class _EchoAlgo:
    query_class = _EchoQuery

    def bind_serving(self, ctx):
        pass

    def prepare_serving_model(self, model, max_batch):
        return model

    def predict(self, model, query):
        return {"doubled": query.v * 2}


class _EchoServing:
    def supplement(self, query):
        return query

    def serve(self, query, predictions):
        return predictions[0]


class _EchoEngine:
    def make_algorithms(self, engine_params):
        return [_EchoAlgo()]

    def make_serving(self, engine_params):
        return _EchoServing()


def _make_query_server(**config_kwargs):
    from predictionio_tpu.data.storage.base import EngineInstance
    from predictionio_tpu.server.engineserver import (
        QueryServer,
        ServerConfig,
    )

    class _Ctx:
        storage = None

    from predictionio_tpu.data.event import utcnow

    now = utcnow()
    instance = EngineInstance(id="i1", status="COMPLETED",
                              start_time=now, end_time=now,
                              engine_id="echo", engine_version="1",
                              engine_variant="engine.json",
                              engine_factory="tests:echo")
    cfg = ServerConfig(warm_start=False, **config_kwargs)
    return QueryServer(_Ctx(), _EchoEngine(), engine_params=None,
                       models=[None], instance=instance, config=cfg)


class TestRecompileSentinel:
    def test_counts_fresh_compiles_after_arm(self):
        import jax
        import jax.numpy as jnp

        from predictionio_tpu.server.stats import RecompileSentinel

        sentinel = RecompileSentinel()
        assert not sentinel.armed
        assert sentinel.since_armed == 0
        sentinel.arm()
        # a never-before-seen shape forces a fresh XLA compile
        jax.jit(lambda x: x * 3 + 1)(jnp.ones(11))
        snap = sentinel.snapshot()
        assert snap["available"] and snap["armed"]
        assert snap["compilesSinceWarm"] >= 1
        assert snap["compilesTotal"] >= snap["compilesSinceWarm"]

    def test_rearm_resets_baseline(self):
        from predictionio_tpu.server.stats import RecompileSentinel

        sentinel = RecompileSentinel()
        sentinel.arm()
        sentinel.arm()
        assert sentinel.since_armed == 0


class TestServingRuntimeWiring:
    def test_sentinel_armed_and_query_guarded(self):
        import contextlib

        server = _make_query_server(transfer_guard="log")
        assert server.warm_done.is_set()
        assert server.recompile_sentinel.armed
        # post-warmup with a level set: a real jax guard context
        guard = server._transfer_guard()
        assert not isinstance(guard, contextlib.nullcontext)
        result = server.query({"v": 21})
        assert result == {"doubled": 42}

    def test_guard_off_is_noop_context(self):
        import contextlib

        server = _make_query_server(transfer_guard="off")
        assert isinstance(server._transfer_guard(),
                          contextlib.nullcontext)
        server2 = _make_query_server(transfer_guard=None)
        assert isinstance(server2._transfer_guard(),
                          contextlib.nullcontext)

    def test_guard_waits_for_warmup(self):
        import contextlib

        server = _make_query_server(transfer_guard="log")
        server.warm_done.clear()
        assert isinstance(server._transfer_guard(),
                          contextlib.nullcontext)

    def test_status_json_exposes_sentinel_and_guard(self):
        from predictionio_tpu.server.engineserver import build_app

        server = _make_query_server(transfer_guard="log")
        app = build_app(server)
        route = next(h for m, _, _, h in app._routes
                     if getattr(h, "__name__", "") == "status")
        doc = route(None).body
        assert doc["transferGuard"] == "log"
        assert doc["recompile"]["armed"] is True
        assert "compilesSinceWarm" in doc["recompile"]

    def test_disallowed_transfer_rejected_under_guard(self):
        import jax.numpy as jnp
        import numpy as np

        server = _make_query_server(transfer_guard="disallow")
        with pytest.raises(Exception):
            with server._transfer_guard():
                np.asarray(jnp.ones(13) + 1)  # implicit D2H


# ---------------------------------------------------------------------------
# SPMD sharding-flow rule family (ISSUE 14)
# ---------------------------------------------------------------------------

class TestShardingMismatchGeneralized:
    """ISSUE 14 satellite: bare P() literals the alias table cannot
    resolve, and shard_map in_specs=/out_specs= keyword forms."""

    def test_positive_bare_jax_p(self):
        code = src("""
            import jax

            SPEC = jax.P("bogus")
        """)
        findings = check_source(code, path=COLD)
        assert rules_of(findings) == ["sharding-mismatch"]
        assert "bogus" in findings[0].message

    def test_positive_star_import_p(self):
        code = src("""
            from jax.sharding import *

            SPEC = P("nope")
        """)
        findings = check_source(code, path=COLD)
        assert "sharding-mismatch" in rules_of(findings)

    def test_positive_shard_map_kwarg_specs(self):
        code = src("""
            from jax.sharding import PartitionSpec as P
            from predictionio_tpu.parallel.collectives import \\
                shard_map_compat

            def build(mesh, body):
                return shard_map_compat(body, mesh,
                                        in_specs=(P("typo_axis"),),
                                        out_specs=P())
        """)
        findings = check_source(code, path=COLD)
        assert rules_of(findings) == ["sharding-mismatch"]
        assert "typo_axis" in findings[0].message

    def test_positive_shard_map_bare_string_specs(self):
        # a compat wrapper accepting bare axis strings in the spec
        # kwarg — no P() call anywhere, still checked
        code = src("""
            from predictionio_tpu.parallel.collectives import \\
                shard_map_compat

            def build(mesh, body):
                return shard_map_compat(body, mesh,
                                        in_specs=("wrong",),
                                        out_specs=("model",))
        """)
        findings = check_source(code, path=COLD)
        assert rules_of(findings) == ["sharding-mismatch"]
        assert "wrong" in findings[0].message

    def test_negative_declared_axes_every_form(self):
        code = src("""
            import jax
            from predictionio_tpu.parallel.collectives import \\
                shard_map_compat

            A = jax.P("batch")

            def build(mesh, body):
                return shard_map_compat(body, mesh,
                                        in_specs=(jax.P("model"),),
                                        out_specs=jax.P())
        """)
        assert check_source(code, path=COLD) == []

    def test_no_double_report_p_inside_shard_map_kwarg(self):
        # one bad axis inside a resolvable P inside in_specs= must
        # yield exactly ONE finding, not one per covering branch
        code = src("""
            from jax.sharding import PartitionSpec as P
            from predictionio_tpu.parallel.collectives import \\
                shard_map_compat

            def build(mesh, body):
                return shard_map_compat(body, mesh,
                                        in_specs=(P("oops"),),
                                        out_specs=(P(),))
        """)
        findings = check_source(code, path=COLD)
        assert rules_of(findings) == ["sharding-mismatch"]


class TestShardMapSpecMismatch:
    def test_positive_in_specs_arity(self):
        code = src("""
            from jax.sharding import PartitionSpec as P
            from predictionio_tpu.parallel.collectives import \\
                shard_map_compat

            def build(mesh, x):
                def body(a, b):
                    return a + b
                fn = shard_map_compat(body, mesh,
                                      in_specs=(P("model"),),
                                      out_specs=P())
                return fn(x)
        """)
        findings = check_source(code, path=COLD)
        assert rules_of(findings) == ["shard-map-spec-mismatch"]
        assert "in_specs carries 1" in findings[0].message

    def test_positive_out_specs_arity(self):
        code = src("""
            from jax.sharding import PartitionSpec as P
            from predictionio_tpu.parallel.collectives import \\
                shard_map_compat

            def build(mesh, x):
                def body(a):
                    return a, a
                return shard_map_compat(body, mesh,
                                        in_specs=(P("model"),),
                                        out_specs=P())(x)
        """)
        findings = check_source(code, path=COLD)
        assert rules_of(findings) == ["shard-map-spec-mismatch"]
        assert "2-tuple" in findings[0].message

    def test_positive_axis_group_mixing(self):
        # "data" (training mesh) with "batch" (serving mesh): both
        # declared, but no single mesh carries both
        code = src("""
            import jax
            from jax.sharding import PartitionSpec as P
            from predictionio_tpu.parallel.collectives import \\
                shard_map_compat

            def build(mesh, x):
                def body(a):
                    return jax.lax.psum(a, "data")
                return shard_map_compat(body, mesh,
                                        in_specs=(P("batch"),),
                                        out_specs=(P("batch"),))(x)
        """)
        findings = check_source(code, path=COLD)
        assert rules_of(findings) == ["shard-map-spec-mismatch"]
        assert "different declared meshes" in findings[0].message

    def test_negative_coherent_site(self):
        code = src("""
            import jax
            from jax.sharding import PartitionSpec as P
            from predictionio_tpu.parallel.collectives import \\
                shard_map_compat

            def build(mesh, x, y):
                def body(a, b):
                    return jax.lax.psum(a + b, "model"), a
                return shard_map_compat(body, mesh,
                                        in_specs=(P("model"), P()),
                                        out_specs=(P(), P("model")))(x, y)
        """)
        assert check_source(code, path=COLD) == []

    def test_negative_rows_spec_symbolic(self):
        # rows_spec(mesh) is mesh-agnostic — no static arity/axis claim
        # beyond the spec count itself
        code = src("""
            from jax.sharding import PartitionSpec as P
            from predictionio_tpu.parallel.collectives import \\
                shard_map_compat
            from predictionio_tpu.parallel.mesh import rows_spec

            def build(mesh, x, y):
                spec = rows_spec(mesh)
                def body(a, b):
                    return a + b
                return shard_map_compat(body, mesh,
                                        in_specs=(P(), spec),
                                        out_specs=spec)(x, y)
        """)
        assert check_source(code, path=COLD) == []

    def test_pragma_suppresses(self):
        code = src("""
            from jax.sharding import PartitionSpec as P
            from predictionio_tpu.parallel.collectives import \\
                shard_map_compat

            def build(mesh, x):
                def body(a, b):
                    return a + b
                # ptpu: allow[shard-map-spec-mismatch] — b is bound by
                # functools.partial upstream of this wrapper
                fn = shard_map_compat(body, mesh,
                                      in_specs=(P("model"),),
                                      out_specs=P())
                return fn(x)
        """)
        assert check_source(code, path=COLD) == []


class TestImplicitReshard:
    def test_positive_direct(self):
        code = src("""
            import jax
            from jax.sharding import NamedSharding, PartitionSpec as P
            from predictionio_tpu.parallel.collectives import \\
                shard_map_compat
            from predictionio_tpu.parallel.mesh import rows_spec

            def run(mesh, host):
                table = jax.device_put(
                    host, NamedSharding(mesh, rows_spec(mesh)))
                def body(t):
                    return t.sum()
                fn = shard_map_compat(body, mesh, in_specs=(P(),),
                                      out_specs=P())
                return fn(table)
        """)
        findings = check_source(code, path=COLD)
        assert rules_of(findings) == ["implicit-reshard"]
        assert "rows(*)" in findings[0].message
        assert "P()" in findings[0].message

    def test_positive_interprocedural_with_chain(self):
        files = {
            "predictionio_tpu/models/helper.py": src("""
                from jax.sharding import PartitionSpec as P
                from predictionio_tpu.parallel.collectives import \\
                    shard_map_compat

                def consume(table, mesh):
                    def body(t):
                        return t.sum()
                    fn = shard_map_compat(body, mesh, in_specs=(P(),),
                                          out_specs=P())
                    return fn(table)
            """),
            "predictionio_tpu/models/train.py": src("""
                import jax
                from jax.sharding import NamedSharding
                from predictionio_tpu.parallel.mesh import rows_spec
                from predictionio_tpu.models.helper import consume

                def step(mesh, host):
                    U = jax.device_put(
                        host, NamedSharding(mesh, rows_spec(mesh)))
                    return consume(U, mesh)
            """),
        }
        findings = check_project(files)
        assert rules_of(findings) == ["implicit-reshard"]
        f = findings[0]
        assert f.path == "predictionio_tpu/models/train.py"
        assert "consume" in f.message and "rows(*)" in f.message
        # the chain walks down to the shard_map boundary
        assert f.related and \
            f.related[-1][0] == "predictionio_tpu/models/helper.py"

    def test_negative_matching_specs(self):
        code = src("""
            import jax
            from jax.sharding import NamedSharding, PartitionSpec as P
            from predictionio_tpu.parallel.collectives import \\
                shard_map_compat
            from predictionio_tpu.parallel.mesh import rows_spec

            def run(mesh, host):
                table = jax.device_put(
                    host, NamedSharding(mesh, rows_spec(mesh)))
                spec = rows_spec(mesh)
                def body(t):
                    return t.sum()
                fn = shard_map_compat(body, mesh, in_specs=(spec,),
                                      out_specs=P())
                return fn(table)
        """)
        assert check_source(code, path=COLD) == []

    def test_negative_full_group_literal_equals_rows(self):
        # P(("data","model")) IS rows_spec on the training mesh — the
        # two spellings must not count as a reshard
        code = src("""
            import jax
            from jax.sharding import NamedSharding, PartitionSpec as P
            from predictionio_tpu.parallel.collectives import \\
                shard_map_compat
            from predictionio_tpu.parallel.mesh import rows_spec

            def run(mesh, host):
                table = jax.device_put(
                    host, NamedSharding(mesh, rows_spec(mesh)))
                def body(t):
                    return t.sum()
                fn = shard_map_compat(
                    body, mesh, in_specs=(P(("data", "model")),),
                    out_specs=P())
                return fn(table)
        """)
        assert check_source(code, path=COLD) == []

    def test_pragma_at_boundary_blesses_callers(self):
        files = {
            "predictionio_tpu/models/helper.py": src("""
                from jax.sharding import PartitionSpec as P
                from predictionio_tpu.parallel.collectives import \\
                    shard_map_compat

                def consume(table, mesh):
                    def body(t):
                        return t.sum()
                    fn = shard_map_compat(body, mesh, in_specs=(P(),),
                                          out_specs=P())
                    # ptpu: allow[implicit-reshard] — the table enters
                    # replicated by design (same all-gather the GSPMD
                    # gather pays); documented boundary
                    return fn(table)
            """),
            "predictionio_tpu/models/train.py": src("""
                import jax
                from jax.sharding import NamedSharding
                from predictionio_tpu.parallel.mesh import rows_spec
                from predictionio_tpu.models.helper import consume

                def step(mesh, host):
                    U = jax.device_put(
                        host, NamedSharding(mesh, rows_spec(mesh)))
                    return consume(U, mesh)
            """),
        }
        assert check_project(files) == []


class TestUnshardedCapture:
    def test_positive_shard_map_closure(self):
        code = src("""
            import jax
            from jax.sharding import NamedSharding, PartitionSpec as P
            from predictionio_tpu.parallel.collectives import \\
                shard_map_compat
            from predictionio_tpu.parallel.mesh import rows_spec

            def run(mesh, host, idx):
                table = jax.device_put(
                    host, NamedSharding(mesh, rows_spec(mesh)))
                def body(i):
                    return table[i]
                fn = shard_map_compat(body, mesh,
                                      in_specs=(P("model"),),
                                      out_specs=P("model"))
                return fn(idx)
        """)
        findings = check_source(code, path=COLD)
        assert rules_of(findings) == ["unsharded-capture"]
        assert "table" in findings[0].message

    def test_positive_jit_closure(self):
        code = src("""
            import jax
            from jax.sharding import NamedSharding
            from predictionio_tpu.parallel.mesh import rows_spec

            def build(mesh, host):
                table = jax.device_put(
                    host, NamedSharding(mesh, rows_spec(mesh)))
                @jax.jit
                def score(v):
                    return v @ table.T
                return score
        """)
        findings = check_source(code, path=COLD)
        assert "unsharded-capture" in rules_of(findings)

    def test_negative_passed_as_argument(self):
        code = src("""
            import jax
            from jax.sharding import NamedSharding, PartitionSpec as P
            from predictionio_tpu.parallel.collectives import \\
                shard_map_compat
            from predictionio_tpu.parallel.mesh import rows_spec

            def run(mesh, host, idx):
                table = jax.device_put(
                    host, NamedSharding(mesh, rows_spec(mesh)))
                spec = rows_spec(mesh)
                def body(t, i):
                    return t[i]
                fn = shard_map_compat(body, mesh,
                                      in_specs=(spec, P("model")),
                                      out_specs=P("model"))
                return fn(table, idx)
        """)
        assert check_source(code, path=COLD) == []

    def test_negative_replicated_capture_fine(self):
        code = src("""
            import jax
            from jax.sharding import NamedSharding, PartitionSpec as P
            from predictionio_tpu.parallel.collectives import \\
                shard_map_compat

            def run(mesh, host, idx):
                g = jax.device_put(host, NamedSharding(mesh, P()))
                def body(i):
                    return g[i]
                fn = shard_map_compat(body, mesh,
                                      in_specs=(P("model"),),
                                      out_specs=P("model"))
                return fn(idx)
        """)
        assert check_source(code, path=COLD) == []

    def test_pragma_suppresses(self):
        code = src("""
            import jax
            from jax.sharding import NamedSharding, PartitionSpec as P
            from predictionio_tpu.parallel.collectives import \\
                shard_map_compat
            from predictionio_tpu.parallel.mesh import rows_spec

            def run(mesh, host, idx):
                table = jax.device_put(
                    host, NamedSharding(mesh, rows_spec(mesh)))
                def body(i):
                    return table[i]
                # ptpu: allow[unsharded-capture] — [k, r] pinned tile,
                # deliberately replicated per device
                fn = shard_map_compat(body, mesh,
                                      in_specs=(P("model"),),
                                      out_specs=P("model"))
                return fn(idx)
        """)
        assert check_source(code, path=COLD) == []


class TestMissingDonationSharded:
    FILES = {
        "predictionio_tpu/models/stepmod.py": src("""
            import jax

            @jax.jit
            def half_step(U, hist):
                return U * 2
        """),
        "predictionio_tpu/models/train2.py": src("""
            import jax
            from jax.sharding import NamedSharding
            from predictionio_tpu.parallel.mesh import rows_spec
            from predictionio_tpu.models.stepmod import half_step

            def train(mesh, host, hist):
                U = jax.device_put(
                    host, NamedSharding(mesh, rows_spec(mesh)))
                for _ in range(4):
                    U = half_step(U, hist)
                return U
        """),
    }

    def test_positive_cross_module_rebind(self):
        findings = check_project(self.FILES)
        assert rules_of(findings) == ["missing-donation-sharded"]
        f = findings[0]
        assert f.path == "predictionio_tpu/models/train2.py"
        assert "half_step" in f.message and "rows(*)" in f.message
        # related points at the jit site missing the donation
        assert f.related and \
            f.related[0][0] == "predictionio_tpu/models/stepmod.py"

    def test_negative_donated(self):
        files = dict(self.FILES)
        files["predictionio_tpu/models/stepmod.py"] = src("""
            import functools

            import jax

            @functools.partial(jax.jit, donate_argnums=(0,))
            def half_step(U, hist):
                return U * 2
        """)
        assert check_project(files) == []

    def test_negative_same_module_is_plain_rules_job(self):
        # same-module rebinds are missing-donation's (which fires);
        # the sharded rule must not double-report
        code = src("""
            import jax
            from jax.sharding import NamedSharding
            from predictionio_tpu.parallel.mesh import rows_spec

            @jax.jit
            def half_step(U, hist):
                return U * 2

            def train(mesh, host, hist):
                U = jax.device_put(
                    host, NamedSharding(mesh, rows_spec(mesh)))
                U = half_step(U, hist)
                return U
        """)
        findings = check_source(code, path=COLD)
        assert rules_of(findings) == ["missing-donation"]

    def test_pragma_suppresses(self):
        files = dict(self.FILES)
        files["predictionio_tpu/models/train2.py"] = src("""
            import jax
            from jax.sharding import NamedSharding
            from predictionio_tpu.parallel.mesh import rows_spec
            from predictionio_tpu.models.stepmod import half_step

            def train(mesh, host, hist):
                U = jax.device_put(
                    host, NamedSharding(mesh, rows_spec(mesh)))
                for _ in range(4):
                    # ptpu: allow[missing-donation-sharded] — U is
                    # checkpoint-retained across steps by design
                    U = half_step(U, hist)
                return U
        """)
        assert check_project(files) == []


class TestShardingPragmaCensus:
    def test_counts_per_rule(self, tmp_path):
        from predictionio_tpu.analysis import count_sharding_pragmas

        (tmp_path / "a.py").write_text(src("""
            # ptpu: allow[implicit-reshard] — documented boundary
            x = 1
            # ptpu: allow[unsharded-capture,sharding-mismatch] — tile
            y = 2
            # ptpu: allow[host-sync-in-hot-path] — not sharding
            z = 3
        """))
        counts = count_sharding_pragmas(str(tmp_path))
        assert counts == {"implicit-reshard": 1,
                          "unsharded-capture": 1,
                          "sharding-mismatch": 1}

    def test_repo_census_matches_gauge_source(self):
        # whatever the tree carries, the census is non-negative ints
        # keyed by family rules only
        from predictionio_tpu.analysis import (
            SHARDING_RULES,
            count_sharding_pragmas,
        )

        counts = count_sharding_pragmas()
        assert all(rule in SHARDING_RULES for rule in counts)
        assert all(isinstance(n, int) and n > 0
                   for n in counts.values())


# ---------------------------------------------------------------------------
# the resource-lifecycle family (ISSUE 20)
# ---------------------------------------------------------------------------

SRV = "predictionio_tpu/server/svc.py"     # thread/queue/spin scopes
FLEET = "predictionio_tpu/fleet/scrape.py"  # net-timeout scope
SLO = "predictionio_tpu/slo/persist.py"     # durable-state scope


class TestLeakedThread:
    def test_positive_looping_daemon_never_joined(self):
        code = src("""
            import threading
            import time

            class Poller:
                def start(self):
                    self._t = threading.Thread(
                        target=self._run, daemon=True)
                    self._t.start()

                def stop(self):
                    pass

                def _run(self):
                    while True:
                        time.sleep(0.1)
        """)
        findings = check_source(code, path=SRV)
        assert rules_of(findings) == ["leaked-thread"]
        assert "_run" in findings[0].message
        assert "join" in findings[0].message

    def test_positive_stop_event_loop_without_join(self):
        # signalling the event without joining still abandons the
        # thread mid-iteration — the join half is required too
        code = src("""
            import threading

            class Poller:
                def __init__(self):
                    self._stop = threading.Event()

                def start(self):
                    self._t = threading.Thread(
                        target=self._run, daemon=True)
                    self._t.start()

                def close(self):
                    self._stop.set()

                def _run(self):
                    while not self._stop.is_set():
                        self._stop.wait(0.1)
        """)
        findings = check_source(code, path=SRV)
        assert rules_of(findings) == ["leaked-thread"]

    def test_negative_joined_in_close(self):
        code = src("""
            import threading

            class Poller:
                def __init__(self):
                    self._stop = threading.Event()

                def start(self):
                    self._t = threading.Thread(
                        target=self._run, daemon=True)
                    self._t.start()

                def close(self):
                    self._stop.set()
                    self._t.join()

                def _run(self):
                    while not self._stop.is_set():
                        self._stop.wait(0.1)
        """)
        assert check_source(code, path=SRV) == []

    def test_negative_one_shot_target(self):
        # a warmup thread ends on its own: no loop, no finding
        code = src("""
            import threading

            class Server:
                def start(self):
                    threading.Thread(
                        target=self._warm, daemon=True).start()

                def _warm(self):
                    self.model.warm()
        """)
        assert check_source(code, path=SRV) == []

    def test_negative_appended_to_roster_joined_elsewhere(self):
        # handles stored via self._workers.append and joined through
        # `for t in self._workers` in another method
        code = src("""
            import threading
            import time

            class Pool:
                def __init__(self):
                    self._workers = []

                def start(self):
                    for _ in range(2):
                        self._workers.append(threading.Thread(
                            target=self._run, daemon=True))
                    for t in self._workers:
                        t.start()

                def close(self):
                    for t in self._workers:
                        t.join()

                def _run(self):
                    while True:
                        time.sleep(1)
        """)
        assert check_source(code, path=SRV) == []

    def test_negative_handle_returned_to_caller(self):
        code = src("""
            import threading
            import time

            class Spawner:
                def spawn(self):
                    t = threading.Thread(
                        target=self._run, daemon=True)
                    t.start()
                    return t

                def _run(self):
                    while True:
                        time.sleep(1)
        """)
        assert check_source(code, path=SRV) == []

    def test_negative_joiner_helper_via_call_graph(self):
        # a helper that joins its parameter blesses the spawner that
        # hands it the handle
        findings = check_project({
            "pkg/server/stop.py": src("""
                def reap(t, timeout):
                    t.join(timeout=timeout)
            """),
            "pkg/server/spawn.py": src("""
                import threading
                import time

                from pkg.server.stop import reap

                class Box:
                    def run_once(self):
                        t = threading.Thread(
                            target=self._run, daemon=True)
                        t.start()
                        reap(t, 5.0)

                    def _run(self):
                        while True:
                            time.sleep(1)
            """),
        })
        assert findings == []

    def test_negative_outside_scope(self):
        code = src("""
            import threading
            import time

            class Poller:
                def start(self):
                    self._t = threading.Thread(
                        target=self._run, daemon=True)
                    self._t.start()

                def _run(self):
                    while True:
                        time.sleep(0.1)
        """)
        assert check_source(code, path=COLD) == []

    def test_pragma_suppresses(self):
        code = src("""
            import threading
            import time

            class Poller:
                def start(self):
                    # ptpu: allow[leaked-thread] — process-lifetime
                    # metrics pump by design
                    self._t = threading.Thread(
                        target=self._run, daemon=True)
                    self._t.start()

                def _run(self):
                    while True:
                        time.sleep(0.1)
        """)
        assert check_source(code, path=SRV) == []


class TestMissingTimeout:
    def test_positive_urlopen_no_timeout(self):
        code = src("""
            import urllib.request

            def scrape(url):
                with urllib.request.urlopen(url) as resp:
                    return resp.read()
        """)
        findings = check_source(code, path=FLEET)
        assert rules_of(findings) == ["missing-timeout"]
        assert "urlopen" in findings[0].message

    def test_positive_create_connection_no_timeout(self):
        code = src("""
            import socket

            def probe(addr):
                return socket.create_connection(addr)
        """)
        findings = check_source(code, path=FLEET)
        assert rules_of(findings) == ["missing-timeout"]

    def test_positive_http_connection_ctor(self):
        code = src("""
            import http.client

            def connect(host):
                return http.client.HTTPConnection(host)
        """)
        findings = check_source(code, path=FLEET)
        assert rules_of(findings) == ["missing-timeout"]

    def test_negative_timeout_keyword(self):
        code = src("""
            import urllib.request

            def scrape(url):
                with urllib.request.urlopen(url, timeout=5.0) as r:
                    return r.read()
        """)
        assert check_source(code, path=FLEET) == []

    def test_negative_timeout_positional(self):
        code = src("""
            import socket

            def probe(addr):
                return socket.create_connection(addr, 3.0)
        """)
        assert check_source(code, path=FLEET) == []

    def test_negative_outside_scope(self):
        code = src("""
            import urllib.request

            def fetch(url):
                return urllib.request.urlopen(url)
        """)
        assert check_source(code, path=COLD) == []

    def test_two_hop_chain_reported_at_fleet_site(self):
        # the hang sits two helpers away; the finding lands at the
        # in-scope call site with the chain down to the direct call
        findings = check_project({
            "pkg/net/raw.py": src("""
                import urllib.request

                def fetch(url):
                    return urllib.request.urlopen(url)
            """),
            "pkg/lib/client.py": src("""
                from pkg.net.raw import fetch

                def pull(url):
                    return fetch(url)
            """),
            "pkg/fleet/scrape.py": src("""
                from pkg.lib.client import pull

                def scrape(url):
                    return pull(url)
            """),
        })
        assert rules_of(findings) == ["missing-timeout"]
        f = findings[0]
        assert f.path == "pkg/fleet/scrape.py"
        assert "pull" in f.message and "fetch" in f.message
        assert [p for p, _, _ in f.related] == [
            "pkg/lib/client.py", "pkg/net/raw.py"]

    def test_pragma_at_direct_site_stops_propagation(self):
        # blessing the helper blesses its callers: the net_wait
        # effect dies at the pragma'd direct site
        findings = check_project({
            "pkg/net/raw.py": src("""
                import urllib.request

                def fetch(url):
                    # ptpu: allow[missing-timeout] — caller sets
                    # socket.setdefaulttimeout at boot
                    return urllib.request.urlopen(url)
            """),
            "pkg/fleet/scrape.py": src("""
                from pkg.net.raw import fetch

                def scrape(url):
                    return fetch(url)
            """),
        })
        assert findings == []

    def test_pragma_suppresses_direct(self):
        code = src("""
            import urllib.request

            def scrape(url):
                # ptpu: allow[missing-timeout] — bounded by the
                # caller's deadline wrapper
                return urllib.request.urlopen(url)
        """)
        assert check_source(code, path=FLEET) == []


class TestNonAtomicPersist:
    def test_positive_plain_rewrite(self):
        code = src("""
            import json

            def save(path, state):
                with open(path, "w") as fh:
                    json.dump(state, fh)
        """)
        findings = check_source(code, path=SLO)
        assert rules_of(findings) == ["non-atomic-persist"]
        assert "os.replace" in findings[0].message

    def test_negative_tmp_plus_replace_funnel(self):
        code = src("""
            import json
            import os

            def save(path, state):
                tmp = path + ".tmp"
                with open(tmp, "w") as fh:
                    json.dump(state, fh)
                    fh.flush()
                    os.fsync(fh.fileno())
                os.replace(tmp, path)
        """)
        assert check_source(code, path=SLO) == []

    def test_negative_append_only_log(self):
        # append-only tears at most the trailing record; replay
        # truncates it — a legitimate durable pattern
        code = src("""
            def log_event(path, line):
                with open(path, "a") as fh:
                    fh.write(line)
        """)
        assert check_source(code, path=SLO) == []

    def test_negative_read_mode(self):
        code = src("""
            import json

            def load(path):
                with open(path, "r") as fh:
                    return json.load(fh)
        """)
        assert check_source(code, path=SLO) == []

    def test_negative_outside_scope(self):
        code = src("""
            def save(path, text):
                with open(path, "w") as fh:
                    fh.write(text)
        """)
        assert check_source(code, path=COLD) == []

    def test_pragma_suppresses(self):
        code = src("""
            def save(path, text):
                # ptpu: allow[non-atomic-persist] — scratch file on
                # tmpfs, rebuilt from scratch on boot
                with open(path, "w") as fh:
                    fh.write(text)
        """)
        assert check_source(code, path=SLO) == []


class TestUnboundedQueue:
    def test_positive_queue_and_deque(self):
        code = src("""
            import collections
            import queue

            class Batcher:
                def __init__(self):
                    self.q = queue.Queue()
                    self.window = collections.deque()
        """)
        findings = check_source(code, path=SRV)
        assert rules_of(findings) == ["unbounded-queue"] * 2
        assert "maxsize" in findings[0].message
        assert "maxlen" in findings[1].message

    def test_positive_explicit_zero_bound(self):
        # maxsize=0 means infinite — same finding
        code = src("""
            import queue

            class Batcher:
                def __init__(self):
                    self.q = queue.Queue(maxsize=0)
        """)
        findings = check_source(code, path=SRV)
        assert rules_of(findings) == ["unbounded-queue"]

    def test_negative_bounded(self):
        code = src("""
            import collections
            import queue

            class Batcher:
                def __init__(self):
                    self.q = queue.Queue(maxsize=128)
                    self.window = collections.deque(maxlen=32)
        """)
        assert check_source(code, path=SRV) == []

    def test_negative_outside_scope(self):
        code = src("""
            import queue

            class Batcher:
                def __init__(self):
                    self.q = queue.Queue()
        """)
        assert check_source(code, path=COLD) == []

    def test_pragma_suppresses(self):
        code = src("""
            import queue

            class Batcher:
                def __init__(self):
                    # ptpu: allow[unbounded-queue] — depth bounded by
                    # the HTTP worker pool blocked on done-Events
                    self.q = queue.Queue()
        """)
        assert check_source(code, path=SRV) == []


class TestHotSpinLoop:
    def test_positive_busy_poll(self):
        code = src("""
            def pump(q):
                while True:
                    if q.empty():
                        continue
                    handle(q.get_nowait())
        """)
        findings = check_source(code, path=SRV)
        assert rules_of(findings) == ["hot-spin-loop"]
        assert "stop-event" in findings[0].message

    def test_positive_itertools_count(self):
        code = src("""
            import itertools

            def spin(work):
                for i in itertools.count():
                    work(i)
        """)
        findings = check_source(code, path=SRV)
        assert rules_of(findings) == ["hot-spin-loop"]

    def test_negative_blocking_get_paces(self):
        code = src("""
            def pump(q):
                while True:
                    handle(q.get())
        """)
        assert check_source(code, path=SRV) == []

    def test_negative_sleep_paces(self):
        code = src("""
            import time

            def tick(step):
                while True:
                    step()
                    time.sleep(1.0)
        """)
        assert check_source(code, path=SRV) == []

    def test_negative_stop_event_checked(self):
        code = src("""
            def run(stop, step):
                while True:
                    if stop.is_set():
                        return
                    step()
        """)
        assert check_source(code, path=SRV) == []

    def test_negative_generator_is_consumer_paced(self):
        code = src("""
            def feed(it):
                while True:
                    yield next(it)
        """)
        assert check_source(code, path=SRV) == []

    def test_negative_outside_scope(self):
        code = src("""
            def pump(q):
                while True:
                    if q.empty():
                        continue
                    handle(q.get_nowait())
        """)
        assert check_source(code, path=COLD) == []

    def test_pragma_suppresses(self):
        code = src("""
            def pump(q):
                # ptpu: allow[hot-spin-loop] — benchmark harness
                # measuring poll latency on purpose
                while True:
                    if q.empty():
                        continue
                    handle(q.get_nowait())
        """)
        assert check_source(code, path=SRV) == []
