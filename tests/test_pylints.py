"""Repo AST-lint suite (flink_tpu/analysis/pylints.py): fixture
sources with deliberate tracer leaks prove every lint fires at the
right line, and trace-static idioms (shape reads, len(), `is None`,
static_argnums) prove it stays quiet — the false-positive budget of
the dogfood gate is ZERO, so the negatives matter as much as the
positives (tier-1)."""
import os
import textwrap

import pytest

from flink_tpu.analysis.pylints import (
    DEFAULT_LINT_PATHS,
    LINT_CATALOG,
    LINT_RULES,
    lint_paths,
    lint_source,
    repo_root,
)

pytestmark = pytest.mark.analysis


def lint(src):
    return lint_source(textwrap.dedent(src), "fixture.py")


def rules_of(findings):
    return [f.rule for f in findings]


# -- tracer leaks: host conversions -----------------------------------------

class TestTracerHostCall:
    def test_float_on_traced_param(self):
        fs = lint("""
            import jax

            @jax.jit
            def kernel(x):
                return float(x)
        """)
        assert rules_of(fs) == ["TRACER_HOST_CALL"]
        assert fs[0].line == 6
        assert fs[0].severity == "error"
        assert "kernel" in fs[0].message

    def test_np_asarray_one_assignment_hop(self):
        fs = lint("""
            import jax
            import numpy as np

            @jax.jit
            def kernel(x):
                y = x * 2
                return np.asarray(y)
        """)
        assert rules_of(fs) == ["TRACER_HOST_CALL"]
        assert "np.asarray" in fs[0].message

    def test_item_method_on_traced(self):
        fs = lint("""
            from jax import jit

            @jit
            def kernel(x):
                return x.sum().item()
        """)
        assert rules_of(fs) == ["TRACER_HOST_CALL"]

    def test_reassignment_clears_taint(self):
        fs = lint("""
            import jax

            @jax.jit
            def kernel(x):
                x = 3
                return float(x)
        """)
        assert fs == []

    def test_untainted_conversion_is_fine(self):
        fs = lint("""
            import jax

            @jax.jit
            def kernel(x, n):
                return x * int(x.shape[0])
        """)
        assert fs == []


# -- tracer leaks: host control flow ----------------------------------------

class TestTracerBranch:
    def test_if_on_traced_value(self):
        fs = lint("""
            import jax

            @jax.jit
            def kernel(x):
                if x > 0:
                    return x
                return -x
        """)
        assert rules_of(fs) == ["TRACER_BRANCH"]
        assert fs[0].line == 6

    def test_while_and_ternary(self):
        fs = lint("""
            import jax

            @jax.jit
            def kernel(x):
                while x > 0:
                    x = x - 1
                return x if x > 0 else -x
        """)
        assert rules_of(fs) == ["TRACER_BRANCH", "TRACER_BRANCH"]

    def test_range_over_traced_trip_count(self):
        fs = lint("""
            import jax

            @jax.jit
            def kernel(x, n):
                for i in range(n):
                    x = x + i
                return x
        """)
        assert rules_of(fs) == ["TRACER_BRANCH"]
        assert "range()" in fs[0].message

    def test_static_idioms_stay_quiet(self):
        # shape/ndim/dtype/size reads, len(), `is None`, `in` — all
        # static under tracing; flagging any of them would poison the
        # dogfood gate with false positives
        fs = lint("""
            import jax

            @jax.jit
            def kernel(x, data):
                if x.shape[0] > 4:
                    x = x[:4]
                if x.ndim == 2:
                    x = x.sum(0)
                if len(data) > 1:
                    x = x * 2
                if x is None:
                    return x
                if "col" in data:
                    x = x + 1
                for i in range(x.shape[0]):
                    x = x + i
                return x
        """)
        assert fs == []

    def test_static_argnums_excludes_param(self):
        fs = lint("""
            import jax
            from functools import partial

            @partial(jax.jit, static_argnums=(1,))
            def kernel(x, n):
                if n > 4:
                    return x[:n]
                return x
        """)
        assert fs == []

    def test_static_argnames_excludes_param(self):
        fs = lint("""
            import jax

            @partial(jax.jit, static_argnames=("n",))
            def kernel(x, n):
                return x[:n] if n > 4 else x
        """)
        assert fs == []

    def test_nested_def_params_shadow_taint(self):
        fs = lint("""
            import jax

            @jax.jit
            def kernel(x):
                def helper(x):
                    # this x is the helper's own (concrete) parameter
                    return float(x)
                return x
        """)
        assert fs == []

    def test_jit_call_form_on_local_def(self):
        fs = lint("""
            import jax

            def step(x):
                if x > 0:
                    return x
                return -x

            fn = jax.jit(step)
        """)
        assert rules_of(fs) == ["TRACER_BRANCH"]

    def test_jit_of_shard_map_call_form(self):
        fs = lint("""
            import jax
            from flink_tpu.utils.jaxcompat import shard_map

            def shard(x):
                return bool(x.sum())

            fn = jax.jit(shard_map(shard, mesh=None, in_specs=(),
                                   out_specs=()))
        """)
        assert rules_of(fs) == ["TRACER_HOST_CALL"]

    def test_plain_function_is_not_a_kernel(self):
        fs = lint("""
            def host_side(x):
                if x > 0:
                    return float(x)
                return x
        """)
        assert fs == []


# -- registry drift ---------------------------------------------------------

class TestRegistryDrift:
    def test_unknown_fault_point_literal(self):
        fs = lint("""
            from flink_tpu import faults

            def save():
                faults.fire("checkpoint.storage.wrte")
        """)
        assert rules_of(fs) == ["FAULT_POINT_DRIFT"]
        assert "checkpoint.storage.wrte" in fs[0].message

    def test_known_fault_point_is_quiet(self):
        fs = lint("""
            from flink_tpu import faults

            def save():
                faults.fire("checkpoint.storage.write")
        """)
        assert fs == []

    def test_undeclared_get_raw_key(self):
        fs = lint("""
            def f(config):
                return config.get_raw("execution.checkpontng.interval")
        """)
        assert rules_of(fs) == ["CONFIG_KEY_DRIFT"]

    def test_dynamic_prefix_key_is_declared(self):
        fs = lint("""
            def f(config):
                return config.get_raw("test.n-batches", 6)
        """)
        assert fs == []

    def test_configuration_dict_literal_keys(self):
        fs = lint("""
            from flink_tpu.config import Configuration

            conf = Configuration({
                "state.num-key-shards": 8,
                "state.num-key-shrads": 8,
            })
        """)
        assert rules_of(fs) == ["CONFIG_KEY_DRIFT"]
        assert "shrads" in fs[0].message

    def test_metric_name_grammar(self):
        fs = lint("""
            def register(group):
                group.counter("checkpointCount")
                group.counter("checkpoint_count")
        """)
        assert rules_of(fs) == ["METRIC_NAME_INVALID"]
        assert fs[0].severity == "warn"


class TestHostpoolSharedWrite:
    """The concurrency lint plane: shared-mutable-state writes inside
    closures submitted to HostPool.run_tasks without a lock/merge
    discipline — the exact race shape PR 5 fixed by hand in
    obs/metrics.py (Counter's `self._v += n`)."""

    def test_unlocked_counter_in_lambda_list_fires(self):
        fs = lint("""
            class Op:
                def __init__(self, pool):
                    self.pool = pool
                    self.total = 0

                def absorb(self, chunks):
                    self.pool.run_tasks(
                        [lambda c=c: self._bump(c) for c in chunks])

                def _bump(self, c):
                    pass

            def drive(pool, chunks, counter):
                def task(c):
                    counter["n"] += len(c)   # racy subscript write
                    return len(c)
                pool.run_tasks([lambda c=c: task(c) for c in chunks])
        """)
        assert rules_of(fs) == ["HOSTPOOL_SHARED_WRITE"]
        assert fs[0].severity == "warn"
        assert "counter" in fs[0].message and fs[0].fix

    def test_unlocked_self_attribute_fires_one_call_hop_deep(self):
        fs = lint("""
            class Op:
                def absorb(self, chunks):
                    def merge(c):
                        self.total += len(c)   # racy attribute RMW
                    self.pool.run_tasks(
                        [lambda c=c: merge(c) for c in chunks])
        """)
        assert rules_of(fs) == ["HOSTPOOL_SHARED_WRITE"]
        assert "self.total" in fs[0].message

    def test_nonlocal_accumulator_through_append_fires(self):
        fs = lint("""
            def drive(pool, chunks):
                done = 0
                tasks = []
                for c in chunks:
                    def task(c=c):
                        nonlocal done
                        done += 1
                    tasks.append(task)
                pool.run_tasks(tasks)
        """)
        assert rules_of(fs) == ["HOSTPOOL_SHARED_WRITE"]

    def test_named_def_bound_through_list_literal_fires(self):
        """Review regression: `tasks = [merge]` (a NAMED local def, not
        a lambda) must resolve to the def — the obs/metrics.py race
        class must not escape through a plain list binding."""
        fs = lint("""
            class Op:
                def absorb(self, chunks):
                    def merge():
                        self.total += 1
                    tasks = [merge]
                    self.pool.run_tasks(tasks)
        """)
        assert rules_of(fs) == ["HOSTPOOL_SHARED_WRITE"]

    def test_annotated_and_walrus_locals_are_silent(self):
        """Review regression: `n: int = 0` and `(n := ...)` bind LOCALS
        — they must never read as shared writes."""
        fs = lint("""
            def drive(pool, chunks):
                def task(c):
                    n: int = 0
                    n += len(c)
                    if (m := len(c)) > 2:
                        m += 1
                    return n + m
                pool.run_tasks([lambda c=c: task(c) for c in chunks])
        """)
        assert fs == []

    def test_lock_guarded_write_is_silent(self):
        fs = lint("""
            import threading

            class Op:
                def __init__(self, pool):
                    self.pool = pool
                    self.total = 0
                    self._lock = threading.Lock()

                def absorb(self, chunks):
                    def task(c):
                        with self._lock:
                            self.total += len(c)
                        return len(c)
                    self.pool.run_tasks(
                        [lambda c=c: task(c) for c in chunks])
        """)
        assert fs == []

    def test_merge_discipline_returning_partials_is_silent(self):
        fs = lint("""
            def drive(pool, chunks):
                parts = pool.run_tasks(
                    [lambda c=c: sum(c) for c in chunks])
                total = sum(parts)   # combine on the CALLER: fine
                return total
        """)
        assert fs == []

    def test_local_writes_inside_tasks_are_silent(self):
        fs = lint("""
            def drive(pool, chunks):
                def task(c):
                    acc = {}
                    acc["n"] = len(c)     # local dict: per-task state
                    acc["n"] += 1
                    return acc
                pool.run_tasks([lambda c=c: task(c) for c in chunks])
        """)
        assert fs == []

    def test_obs_metrics_as_shipped_is_silent(self):
        """The PR 5 fix itself (lock-guarded primitives) must never be
        re-flagged — and neither may the shipped pool clients."""
        import os

        from flink_tpu.analysis.pylints import repo_root

        for rel in ("flink_tpu/obs/metrics.py", "flink_tpu/state/spill.py",
                    "flink_tpu/ops/session.py"):
            with open(os.path.join(repo_root(), rel)) as f:
                fs = [x for x in lint_source(f.read(), rel)
                      if x.rule == "HOSTPOOL_SHARED_WRITE"]
            assert fs == [], f"{rel}: {[x.render() for x in fs]}"


class TestLintPaths:
    def test_duplicate_option_declaration_across_files(self, tmp_path):
        a = tmp_path / "a.py"
        b = tmp_path / "b.py"
        a.write_text('X = ConfigOption("dup.key", 1, "first")\n')
        b.write_text('Y = ConfigOption("dup.key", 2, "second")\n')
        fs = lint_paths([str(a), str(b)], root=str(tmp_path))
        assert rules_of(fs) == ["CONFIG_OPTION_DUP"]
        assert fs[0].file == "b.py"
        assert "a.py:1" in fs[0].message

    def test_walks_directories_and_skips_pycache(self, tmp_path):
        pkg = tmp_path / "pkg"
        (pkg / "__pycache__").mkdir(parents=True)
        (pkg / "__pycache__" / "junk.py").write_text("syntax error ][")
        (pkg / "mod.py").write_text(
            "import jax\n\n@jax.jit\ndef k(x):\n    return float(x)\n")
        fs = lint_paths(["pkg"], root=str(tmp_path))
        assert rules_of(fs) == ["TRACER_HOST_CALL"]
        assert fs[0].file == "pkg/mod.py"

    def test_nonexistent_path_fails_loudly(self, tmp_path):
        # a typo'd CI path silently linting nothing would leave the
        # drift gate green while checking nothing
        with pytest.raises(ValueError, match="does not exist"):
            lint_paths(["no/such/dir"], root=str(tmp_path))

    def test_rule_table_covers_every_emitted_rule(self):
        assert {r for r, _ in LINT_RULES} >= {
            "TRACER_HOST_CALL", "TRACER_BRANCH", "FAULT_POINT_DRIFT",
            "CONFIG_KEY_DRIFT", "CONFIG_OPTION_DUP",
            "METRIC_NAME_INVALID"}

    def test_default_paths_cover_the_shipped_surface(self):
        assert "flink_tpu" in DEFAULT_LINT_PATHS
        assert "tools" in DEFAULT_LINT_PATHS
        assert "chip_smoke.py" in DEFAULT_LINT_PATHS
        root = repo_root()
        for p in DEFAULT_LINT_PATHS:  # nothing named that is gone
            assert os.path.exists(os.path.join(root, p)), p


# -- one seeded violation per catalog rule ----------------------------------
#
# rule id -> (relpath, source). Each seed is the SMALLEST program that
# trips exactly its rule through the real lint_paths entry point (tmp
# tree + relpath, so the durability plane sees a durable-module path
# and CONFIG_OPTION_DUP sees the cross-file declaration scan). The
# coverage test below pins set(LINT_SEEDS) == the catalog: a rule
# cannot be de-registered (or added) without this suite noticing.

LINT_SEEDS = {
    "TRACER_HOST_CALL": ("seed.py", """
        import jax

        @jax.jit
        def kernel(x):
            return float(x)
    """),
    "TRACER_BRANCH": ("seed.py", """
        import jax

        @jax.jit
        def kernel(x):
            if x > 0:
                return x
            return -x
    """),
    "FAULT_POINT_DRIFT": ("seed.py", """
        from flink_tpu import faults

        def save():
            faults.fire("seed.not.registered")
    """),
    "FAULT_POINT_UNFIRED": ("seed.py", """
        KNOWN_FAULT_POINTS = frozenset(("seed.never.fired",))
    """),
    "CONFIG_KEY_DRIFT": ("seed.py", """
        def f(config):
            return config.get_raw("seed.key.typo")
    """),
    "CONFIG_OPTION_DUP": ("seed.py", """
        X = ConfigOption("seed.dup.key", 1, "first")
        Y = ConfigOption("seed.dup.key", 2, "second")
    """),
    "METRIC_NAME_INVALID": ("seed.py", """
        def register(group):
            group.counter("seedCamelCase")
    """),
    "HOSTPOOL_SHARED_WRITE": ("seed.py", """
        def drive(pool, chunks):
            done = 0
            def task(c):
                nonlocal done
                done += 1
            pool.run_tasks([lambda c=c: task(c) for c in chunks])
    """),
    "DURABILITY_SEAM_BYPASS": ("flink_tpu/log/topic.py", """
        def save(path, data):
            with open(path, "w") as f:
                f.write(data)
    """),
    "LOCK_ORDER_CYCLE": ("seed.py", """
        import threading

        MU_A = threading.Lock()
        MU_B = threading.Lock()

        def forward():
            with MU_A:
                with MU_B:
                    pass

        def backward():
            with MU_B:
                with MU_A:
                    pass
    """),
    "FENCE_UNVERIFIED_PUBLISH": ("seed.py", """
        class Cleaner:
            def __init__(self, store, lease):
                self.store = store
                self.lease = lease

            def heartbeat(self):
                self.lease.verify()

            def publish(self):
                self.store.write_atomic("status.json", b"{}")
    """),
}


class TestLintCatalogSeeds:
    """Every registered rule has a seeded violation that fires through
    lint_paths — the catalog and the engine cannot drift apart, and a
    rule silently dropped from _lint_graph fails here, not in prod."""

    def _run_seed(self, tmp_path, rule):
        relpath, src = LINT_SEEDS[rule]
        p = tmp_path / relpath
        p.parent.mkdir(parents=True, exist_ok=True)
        p.write_text(textwrap.dedent(src))
        return lint_paths([relpath], root=str(tmp_path))

    @pytest.mark.parametrize("rule", sorted(LINT_SEEDS))
    def test_seed_trips_exactly_its_rule(self, tmp_path, rule):
        fs = self._run_seed(tmp_path, rule)
        assert rules_of(fs) == [rule], [f.render() for f in fs]
        assert fs[0].fix  # every finding ships an actionable fix hint

    def test_every_catalog_rule_has_a_seed(self):
        assert set(LINT_SEEDS) == {r for r, *_ in LINT_CATALOG}

    def test_catalog_planes_are_complete(self):
        from flink_tpu.analysis.pylints import LINT_PLANES

        assert set(LINT_PLANES) == set(LINT_SEEDS)
        assert {LINT_PLANES[r] for r in (
            "LOCK_ORDER_CYCLE", "FENCE_UNVERIFIED_PUBLISH",
            "DURABILITY_SEAM_BYPASS", "FAULT_POINT_UNFIRED")} == {
            "locking", "fencing", "durability", "registry"}


# -- interprocedural tracer taint -------------------------------------------

class TestInterproceduralTracer:
    """PR 19 tentpole: taint follows traced ARGUMENTS through resolved
    call edges to arbitrary depth — the helper-extraction refactor that
    used to launder a host round-trip out of sight of the lint."""

    def test_host_call_two_helpers_deep(self):
        fs = lint("""
            import jax

            def convert(v):
                return float(v)

            def relay(v):
                return convert(v)

            @jax.jit
            def kernel(x):
                return relay(x)
        """)
        assert rules_of(fs) == ["TRACER_HOST_CALL"]
        assert "helper 'convert'" in fs[0].message
        assert "kernel" in fs[0].message

    def test_branch_inside_method_helper(self):
        fs = lint("""
            import jax

            class Op:
                def decide(self, v):
                    if v > 0:
                        return v
                    return -v

                def build(self):
                    @jax.jit
                    def kernel(x):
                        return self.decide(x)
                    return kernel
        """)
        assert rules_of(fs) == ["TRACER_BRANCH"]
        assert "helper 'decide'" in fs[0].message

    def test_static_actual_does_not_taint_the_helper(self):
        # only x.shape[0] (static under tracing) flows in — the
        # helper's branch is host-side control flow on a concrete int
        fs = lint("""
            import jax

            def pick(n):
                if n > 4:
                    return 4
                return n

            @jax.jit
            def kernel(x):
                return x[:pick(x.shape[0])]
        """)
        assert fs == []

    def test_helper_rebind_clears_taint_before_host_call(self):
        fs = lint("""
            import jax

            def convert(v):
                v = 3
                return float(v)

            @jax.jit
            def kernel(x):
                return convert(x)
        """)
        assert fs == []


# -- interprocedural hostpool writes ----------------------------------------

class TestInterproceduralHostpool:
    """PR 19 tentpole: the shared-write walk follows resolved calls out
    of the submitted closure, binding-type lock recognition included."""

    def test_unlocked_write_two_call_hops_deep(self):
        fs = lint("""
            class Op:
                def absorb(self, chunks):
                    def task(c):
                        return self._merge(c)
                    self.pool.run_tasks(
                        [lambda c=c: task(c) for c in chunks])

                def _merge(self, c):
                    return self._commit(len(c))

                def _commit(self, n):
                    self.total += n   # racy RMW, two hops from the task
        """)
        assert rules_of(fs) == ["HOSTPOOL_SHARED_WRITE"]
        assert "self.total" in fs[0].message

    def test_binding_typed_lock_without_lock_in_the_name(self):
        """The `with self._mu:` fix: a guard is recognized by its
        BINDING (threading.Lock assigned in __init__), not by 'lock'
        appearing in the attribute name."""
        fs = lint("""
            import threading

            class Op:
                def __init__(self, pool):
                    self.pool = pool
                    self.total = 0
                    self._mu = threading.Lock()

                def absorb(self, chunks):
                    def task(c):
                        with self._mu:
                            self.total += len(c)
                    self.pool.run_tasks(
                        [lambda c=c: task(c) for c in chunks])
        """)
        assert fs == []

    def test_binding_typed_lock_guards_the_callee_too(self):
        fs = lint("""
            import threading

            class Op:
                def __init__(self, pool):
                    self.pool = pool
                    self.total = 0
                    self._mu = threading.RLock()

                def absorb(self, chunks):
                    def task(c):
                        self._merge(c)
                    self.pool.run_tasks(
                        [lambda c=c: task(c) for c in chunks])

                def _merge(self, c):
                    with self._mu:
                        self.total += len(c)
        """)
        assert fs == []

    def test_shared_formal_rebind_and_tuple_unpack_are_local(self):
        """Python scoping regression (the ops/session.py FP class):
        a bare rebind of a shared-bound formal is LOCAL, and
        tuple-unpack targets bind locals — neither mutates the
        caller's object."""
        fs = lint("""
            class Op:
                def absorb(self, chunks):
                    def task(c):
                        return self._count(c)
                    self.pool.run_tasks(
                        [lambda c=c: task(c) for c in chunks])

                def _count(self, c):
                    c = c[1:]
                    lo, hi = 0, len(c)
                    lo += hi
                    return lo
        """)
        assert fs == []

    def test_mutation_through_shared_formal_still_fires(self):
        # the flip side of the scoping rule: a subscript store THROUGH
        # the shared formal reaches the caller's object
        fs = lint("""
            class Op:
                def absorb(self, chunks):
                    def task(c):
                        self._count(c, self.totals)
                    self.pool.run_tasks(
                        [lambda c=c: task(c) for c in chunks])

                def _count(self, c, totals):
                    totals["n"] = len(c)
        """)
        assert rules_of(fs) == ["HOSTPOOL_SHARED_WRITE"]


# -- reverse registry drift: unfired fault points ---------------------------

class TestFaultPointUnfired:
    """PR 19 satellite: a registered point with no fire site is a dead
    chaos target — warn, with resolution through string literals,
    module constants, and one parameter-forwarding hop."""

    def test_never_fired_point_warns_at_the_registry_line(self):
        fs = lint("""
            KNOWN_FAULT_POINTS = frozenset((
                "seed.never.fired",
            ))
        """)
        assert rules_of(fs) == ["FAULT_POINT_UNFIRED"]
        assert fs[0].severity == "warn"
        assert "seed.never.fired" in fs[0].message

    def test_constant_and_param_forwarded_fires_resolve(self):
        # fs.fsync fires through a module constant; fs.rename through
        # one parameter-forwarding hop — both real registry names, so
        # FAULT_POINT_DRIFT stays quiet too
        fs = lint("""
            from flink_tpu import faults

            KNOWN_FAULT_POINTS = frozenset(("fs.fsync", "fs.rename"))
            FSYNC_POINT = "fs.fsync"

            def fire_it(point):
                faults.fire(point)

            def go():
                faults.fire(FSYNC_POINT)
                fire_it("fs.rename")
        """)
        assert fs == []

    def test_allowlist_suppresses_the_warning(self):
        fs = lint("""
            KNOWN_FAULT_POINTS = frozenset(("seed.allowed.quiet",))
            UNFIRED_ALLOWLIST = frozenset(("seed.allowed.quiet",))
        """)
        assert fs == []

    def test_no_registry_in_the_linted_set_is_quiet(self):
        # linting a subtree that fires points but does not DEFINE the
        # registry must not claim every un-fired registry entry
        fs = lint("""
            from flink_tpu import faults

            def go():
                faults.fire("fs.fsync")
        """)
        assert fs == []


# -- durability seam (promoted from tests/test_architecture.py) -------------

class TestDurabilitySeamLint:
    """PR 19 satellite: the TestDurableWriteSeam scan is now the
    DURABILITY_SEAM_BYPASS rule — same construct set, same allowed
    residue, keyed off the module RELPATH."""

    def test_raw_open_and_os_replace_in_durable_module(self):
        src = """
            import os

            def save(path, data):
                with open(path, "w") as f:
                    f.write(data)
                os.replace(path + ".tmp", path)
        """
        fs = lint_source(textwrap.dedent(src), "flink_tpu/log/topic.py")
        assert rules_of(fs) == ["DURABILITY_SEAM_BYPASS"] * 2
        assert fs[0].severity == "error"
        assert "flink_tpu/log/topic.py" in fs[0].message

    def test_same_source_outside_the_durable_tier_is_quiet(self):
        src = """
            import os

            def save(path, data):
                with open(path, "w") as f:
                    f.write(data)
                os.replace(path + ".tmp", path)
        """
        assert lint_source(textwrap.dedent(src), "fixture.py") == []

    def test_lock_to_grave_rename_residue_is_exempt(self):
        # the documented local-lock-primitive residue: os.rename of
        # lock/lease bookkeeping files is never durable payload
        src = """
            import os

            def expire(lock_path, grave_path):
                os.rename(lock_path, grave_path)
        """
        assert lint_source(textwrap.dedent(src),
                           "flink_tpu/log/topic.py") == []

    def test_roster_matches_the_architecture_contract(self):
        from flink_tpu.analysis.pylints import DURABLE_MODULES

        assert "flink_tpu/log/topic.py" in DURABLE_MODULES
        assert "flink_tpu/checkpoint/storage.py" in DURABLE_MODULES
        assert "flink_tpu/state/lsm.py" in DURABLE_MODULES


# -- lock-order cycles ------------------------------------------------------

class TestLockOrderCycle:
    """PR 19 tentpole: ABBA detection over the acquisition-order graph,
    with call-hop edges and both witness paths named in the finding."""

    def test_direct_abba_names_both_paths(self):
        fs = lint("""
            import threading

            MU_A = threading.Lock()
            MU_B = threading.Lock()

            def forward():
                with MU_A:
                    with MU_B:
                        pass

            def backward():
                with MU_B:
                    with MU_A:
                        pass
        """)
        assert rules_of(fs) == ["LOCK_ORDER_CYCLE"]
        msg = fs[0].message
        assert "one path acquires" in msg
        assert "the opposite path acquires" in msg
        assert "forward" in msg and "backward" in msg

    def test_cycle_through_a_call_hop(self):
        # one leg nests directly; the other acquires the second lock
        # inside a CALLEE while holding the first
        fs = lint("""
            import threading

            class Store:
                def __init__(self):
                    self._index_mu = threading.Lock()
                    self._flush_mu = threading.Lock()

                def _seal(self):
                    with self._flush_mu:
                        pass

                def put(self):
                    with self._index_mu:
                        self._seal()

                def compact(self):
                    with self._flush_mu:
                        with self._index_mu:
                            pass
        """)
        assert rules_of(fs) == ["LOCK_ORDER_CYCLE"]
        assert "via the call" in fs[0].message

    def test_consistent_global_order_is_quiet(self):
        fs = lint("""
            import threading

            MU_A = threading.Lock()
            MU_B = threading.Lock()

            def one():
                with MU_A:
                    with MU_B:
                        pass

            def two():
                with MU_A:
                    with MU_B:
                        pass
        """)
        assert fs == []

    def test_rlock_reentry_is_not_a_self_edge(self):
        fs = lint("""
            import threading

            class Op:
                def __init__(self):
                    self._mu = threading.RLock()

                def outer(self):
                    with self._mu:
                        self.inner()

                def inner(self):
                    with self._mu:
                        pass
        """)
        assert fs == []


# -- fence discipline on leased publishers ----------------------------------

class TestFencePublish:
    """PR 19 tentpole: a fenced-record publication reachable from a
    leased class's public method with no verify()/renew on the path is
    a post-takeover write a deposed leaseholder could still make."""

    SEED = """
        class Cleaner:
            def __init__(self, store, lease):
                self.store = store
                self.lease = lease

            def heartbeat(self):
                self.lease.verify()

            def publish(self):
                self.store.write_atomic("status.json", b"{}")
    """

    def test_unverified_status_publish_fires(self):
        fs = lint(self.SEED)
        assert rules_of(fs) == ["FENCE_UNVERIFIED_PUBLISH"]
        assert fs[0].severity == "error"
        assert "status" in fs[0].message
        assert "Cleaner.publish()" in fs[0].message

    def test_verify_before_publish_is_quiet(self):
        fs = lint("""
            class Cleaner:
                def __init__(self, store, lease):
                    self.store = store
                    self.lease = lease

                def publish(self):
                    self.lease.verify()
                    self.store.write_atomic("status.json", b"{}")
        """)
        assert fs == []

    def test_verify_inside_a_called_helper_counts(self):
        # the fence gate may live in a private helper — the walk
        # threads the verified flag through resolved calls
        fs = lint("""
            class Cleaner:
                def __init__(self, store, lease):
                    self.store = store
                    self.lease = lease

                def _gate(self):
                    self.lease.verify()

                def publish(self):
                    self._gate()
                    self.store.write_atomic("marker.json", b"{}")
        """)
        assert fs == []

    def test_lease_record_publication_is_the_fence_itself(self):
        fs = lint("""
            class Cleaner:
                def __init__(self, store, lease):
                    self.store = store
                    self.lease = lease

                def heartbeat(self):
                    self.lease.verify()

                def claim(self):
                    self.store.put_if("cleaner.lease", b"{}", None)
        """)
        assert fs == []

    def test_unleased_class_is_out_of_scope(self):
        # no self.<attr>.verify() signature anywhere: the class holds
        # no epoch-fenced lease, so its publications are unconstrained
        fs = lint("""
            class Writer:
                def __init__(self, store):
                    self.store = store

                def publish(self):
                    self.store.write_atomic("status.json", b"{}")
        """)
        assert fs == []
