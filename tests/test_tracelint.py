"""Trace-discipline analyzer tests (ISSUE 12, docs/ANALYSIS.md).

Fixture-based known-good/known-bad snippets per tracelint rule,
call-graph resolution through `instrumented_jit` builders and the
`parallel.shard_map` shim, allowlist burn-down semantics,
`analysis.specs.canonicalize_spec` against jax's real normalization
behavior, and the runtime guards (compile-count watchdog + transfer
guard + metric wiring).
"""
import os
import textwrap

import numpy as np
import pytest

from paddle_tpu.analysis import guards, specs, tracelint
from paddle_tpu.analysis.rules import RULES

REPO = os.path.dirname(os.path.dirname(os.path.abspath(__file__)))


def lint_pkg(tmp_path, sources):
    """Write {relpath: source} under a fake package root and lint
    it. Returns the finding list."""
    root = tmp_path / "fakepkg"
    root.mkdir(exist_ok=True)
    (root / "__init__.py").write_text("")
    for rel, src in sources.items():
        p = root / rel
        p.parent.mkdir(parents=True, exist_ok=True)
        init = p.parent / "__init__.py"
        if not init.exists():
            init.write_text("")
        p.write_text(textwrap.dedent(src))
    return tracelint.run_tracelint(str(root))


def rules_of(findings):
    return sorted({f.rule for f in findings})


# ------------------------------------------------------------ per-rule


class TestTraceRules:
    def test_host_call_in_jitted_fn_flagged(self, tmp_path):
        fs = lint_pkg(tmp_path, {"m.py": """
            import time
            import jax

            def f(x):
                t = time.time()
                return x * t

            g = jax.jit(f)
        """})
        assert [f.rule for f in fs] == ["TL101"]
        assert fs[0].qualname == "f"

    def test_host_call_outside_trace_is_clean(self, tmp_path):
        fs = lint_pkg(tmp_path, {"m.py": """
            import time
            import jax

            def host_loop(x):
                return time.time()

            def f(x):
                return x + 1

            g = jax.jit(f)
        """})
        assert fs == []

    def test_np_random_and_env_reads(self, tmp_path):
        fs = lint_pkg(tmp_path, {"m.py": """
            import os
            import numpy as np
            import jax

            def f(x):
                noise = np.random.randn(4)
                flag = os.environ.get("X", "")
                return x + noise.sum()

            g = jax.jit(f)
        """})
        assert [f.rule for f in fs] == ["TL101", "TL101"]

    def test_item_and_float_cast_flagged(self, tmp_path):
        fs = lint_pkg(tmp_path, {"m.py": """
            import jax

            def f(x, n):
                s = x.sum().item()
                m = float(n)
                return x * s * m

            g = jax.jit(f)
        """})
        assert rules_of(fs) == ["TL102"]
        assert len(fs) == 2

    def test_static_param_cast_is_clean(self, tmp_path):
        # n is static_argnums -> int(n) is host config, not a traced
        # materialization
        fs = lint_pkg(tmp_path, {"m.py": """
            import jax

            def f(x, n):
                return x * int(n)

            g = jax.jit(f, static_argnums=(1,))
        """})
        assert fs == []

    def test_branch_on_traced_value_flagged(self, tmp_path):
        fs = lint_pkg(tmp_path, {"m.py": """
            import jax

            def f(x, n):
                if n > 0:
                    return x + n
                return x

            g = jax.jit(f)
        """})
        assert [f.rule for f in fs] == ["TL103"]

    def test_branch_on_traced_method_flagged(self, tmp_path):
        # x.any()/x.max() READ the traced value — only the static
        # metadata attrs (shape/ndim/dtype/size) are exempt
        fs = lint_pkg(tmp_path, {"m.py": """
            import jax

            def f(x):
                if x.any():
                    return x + 1
                return x

            g = jax.jit(f)
        """})
        assert [f.rule for f in fs] == ["TL103"]

    def test_cast_of_traced_reduction_flagged(self, tmp_path):
        fs = lint_pkg(tmp_path, {"m.py": """
            import jax

            def f(x):
                return x * float(x.sum())

            g = jax.jit(f)
        """})
        assert [f.rule for f in fs] == ["TL102"]

    def test_branch_on_shape_is_clean(self, tmp_path):
        # x.ndim / x.shape are trace-time static — must not trip TL103
        fs = lint_pkg(tmp_path, {"m.py": """
            import jax

            def f(x):
                if x.ndim == 2:
                    return x.sum(axis=1)
                return x

            g = jax.jit(f)
        """})
        assert fs == []

    def test_closure_mutation_flagged_memo_clean(self, tmp_path):
        fs = lint_pkg(tmp_path, {"m.py": """
            import jax

            _log = []
            _memo = {}

            def f(x):
                _log.append(1)             # per-call state: flagged
                cfg = _memo.get("k")
                if cfg is None:
                    cfg = _memo["k"] = 2   # memo idiom: exempt
                return x * cfg

            g = jax.jit(f)
        """})
        assert [f.rule for f in fs] == ["TL104"]
        assert "_log" in fs[0].message

    def test_contextmanager_push_pop_exempt(self, tmp_path):
        fs = lint_pkg(tmp_path, {"m.py": """
            import contextlib
            import jax

            _stack = []

            @contextlib.contextmanager
            def scope(v):
                _stack.append(v)
                try:
                    yield
                finally:
                    _stack.pop()

            def f(x):
                with scope(1):
                    return x + 1

            g = jax.jit(f)
        """})
        assert fs == []

    def test_list_static_arg_flagged_tuple_clean(self, tmp_path):
        fs = lint_pkg(tmp_path, {"m.py": """
            import jax

            def f(x, pad):
                return x

            g = jax.jit(f, static_argnums=(1,))

            def caller_bad(x):
                return g(x, [1, 2])

            def caller_good(x):
                return g(x, (1, 2))
        """})
        assert [f.rule for f in fs] == ["TL105"]
        assert fs[0].qualname == "caller_bad"

    def test_donated_buffer_reuse_flagged(self, tmp_path):
        fs = lint_pkg(tmp_path, {"m.py": """
            import jax

            def f(buf, x):
                return buf + x

            step = jax.jit(f, donate_argnums=(0,))

            def caller_bad(buf, x):
                out = step(buf, x)
                return out + buf           # buf was donated

            def caller_good(buf, x):
                buf = step(buf, x)
                return buf + 1
        """})
        assert [f.rule for f in fs] == ["TL106"]
        assert fs[0].qualname == "caller_bad"

    def test_weak_type_literal_flagged(self, tmp_path):
        fs = lint_pkg(tmp_path, {"m.py": """
            import jax

            def f(x, lr):
                return x * lr

            step = jax.jit(f)

            def caller(x):
                return step(x, 0.5)
        """})
        assert [f.rule for f in fs] == ["RH203"]

    # ------------------------------------------ TL107: device loops
    def test_host_call_in_while_loop_body_flagged(self, tmp_path):
        fs = lint_pkg(tmp_path, {"m.py": """
            import time
            import jax
            from jax import lax

            def cond(s):
                return s[0] < 4

            def body(s):
                t = time.time()
                return (s[0] + 1, s[1] * t)

            def run(x):
                return lax.while_loop(cond, body, (0, x))
        """})
        # the host call draws TL101 (traced fn) AND TL107 (loop body)
        assert rules_of(fs) == ["TL101", "TL107"]
        tl107 = [f for f in fs if f.rule == "TL107"]
        assert [f.qualname for f in tl107] == ["body"]

    def test_device_get_in_scan_body_flagged(self, tmp_path):
        fs = lint_pkg(tmp_path, {"m.py": """
            import jax
            from jax import lax

            def body(carry, x):
                y = carry + x
                jax.device_get(y)
                return y, y

            def run(xs):
                return lax.scan(body, 0.0, xs)
        """})
        assert [f.rule for f in fs] == ["TL107"]

    def test_item_in_loop_reachable_callee_flagged(self, tmp_path):
        """The hazard propagates: a helper CALLED from a while_loop
        body is loop-reachable even though it is not the direct
        trace-entry argument."""
        fs = lint_pkg(tmp_path, {"m.py": """
            import jax
            from jax import lax

            def helper(y):
                return y.item()

            def body(s):
                return s + helper(s)

            def run(x):
                return lax.while_loop(lambda s: s < 9, body, x)
        """})
        assert {(f.rule, f.qualname) for f in fs} >= {
            ("TL107", "helper"), ("TL102", "helper")}

    def test_block_until_ready_in_scan_flagged(self, tmp_path):
        fs = lint_pkg(tmp_path, {"m.py": """
            import jax
            from jax import lax

            def body(c, x):
                y = (c + x).block_until_ready()
                return y, y

            def run(xs):
                return lax.scan(body, 0.0, xs)
        """})
        assert [f.rule for f in fs] == ["TL107"]

    def test_clean_loop_body_and_jit_only_fn_pass(self, tmp_path):
        """A pure loop body is clean, and host-ish attribute calls in
        a plain jitted function (NOT loop-reachable) stay out of
        TL107's scope."""
        fs = lint_pkg(tmp_path, {"m.py": """
            import jax
            import jax.numpy as jnp
            from jax import lax

            def body(s):
                return (s[0] + 1, jnp.where(s[1] > 0, s[1], 0.0))

            def run(x):
                return lax.while_loop(lambda s: s[0] < 4, body,
                                      (0, x))

            def f(x):
                return x.copy_to_host_async()

            g = jax.jit(f)
        """})
        assert [f.rule for f in fs if f.rule == "TL107"] == []


class TestRecompileHazards:
    def test_trailing_none_out_sharding_flagged(self, tmp_path):
        fs = lint_pkg(tmp_path, {"m.py": """
            import jax
            from jax.sharding import NamedSharding, PartitionSpec as P

            def build(f, mesh):
                return jax.jit(
                    f, out_shardings=NamedSharding(mesh, P("a", None)))
        """})
        assert [f.rule for f in fs] == ["RH201"]

    def test_all_none_spec_flagged(self, tmp_path):
        fs = lint_pkg(tmp_path, {"m.py": """
            from jax.sharding import NamedSharding, PartitionSpec as P

            def make(mesh):
                return NamedSharding(mesh, P(None))
        """})
        assert [f.rule for f in fs] == ["RH202"]

    def test_canonical_and_wrapped_are_clean(self, tmp_path):
        fs = lint_pkg(tmp_path, {"m.py": """
            import jax
            from jax.sharding import NamedSharding, PartitionSpec as P
            from paddle_tpu.analysis.specs import canonicalize_spec

            def build(f, mesh):
                a = jax.jit(f, out_shardings=NamedSharding(
                    mesh, P(None, "a")))
                b = jax.jit(f, out_shardings=NamedSharding(
                    mesh, canonicalize_spec(P("a", None), mesh)))
                return a, b
        """})
        assert fs == []

    def test_inner_shard_map_specs_not_flagged(self, tmp_path):
        # in_specs/out_specs of a shard_map are NOT jit-boundary cache
        # identity — P("a", None) there must not fire RH201
        fs = lint_pkg(tmp_path, {"m.py": """
            from paddle_tpu.parallel import shard_map
            from jax.sharding import PartitionSpec as P

            def build(body, mesh):
                return shard_map(body, mesh=mesh,
                                 in_specs=(P("a", None),),
                                 out_specs=P("a", None))
        """})
        assert [f.rule for f in fs if f.rule.startswith("RH")] == []


# ------------------------------------------- call-graph resolution


class TestCallGraphResolution:
    def test_through_instrumented_jit_builder_chain(self, tmp_path):
        """The serving-engine pattern: instrumented_jit(self._build())
        where _build returns self._body(cfg) which returns the nested
        step — host calls inside step AND inside its callees flag."""
        fs = lint_pkg(tmp_path, {"m.py": """
            import time
            from paddle_tpu.jit.functional import instrumented_jit

            def helper(x):
                return x * time.perf_counter()

            class Engine:
                def _body(self, cfg):
                    def step(x):
                        return helper(x) + cfg
                    return step

                def _build(self):
                    return self._body(3)

                def __init__(self):
                    self._fn = instrumented_jit(self._build(), "s")
        """})
        assert [f.rule for f in fs] == ["TL101"]
        assert fs[0].qualname == "helper"

    def test_through_shard_map_shim(self, tmp_path):
        fs = lint_pkg(tmp_path, {"m.py": """
            import numpy as np
            from paddle_tpu.parallel import shard_map as _shard_map

            def build(mesh, specs):
                def body(x):
                    return x + np.random.rand()
                return _shard_map(body, mesh=mesh, in_specs=specs,
                                  out_specs=specs)
        """})
        assert [f.rule for f in fs] == ["TL101"]
        assert fs[0].qualname.endswith("body")

    def test_lax_scan_body(self, tmp_path):
        fs = lint_pkg(tmp_path, {"m.py": """
            import os
            import jax

            def run(xs):
                def body(carry, x):
                    return carry + x, os.getenv("HOME")
                return jax.lax.scan(body, 0.0, xs)
        """})
        # the scan body's host call is both a TL101 (traced fn) and,
        # since ISSUE 18, a TL107 (device-loop body)
        assert [f.rule for f in fs] == ["TL101", "TL107"]

    def test_cross_module_propagation(self, tmp_path):
        fs = lint_pkg(tmp_path, {
            "helpers.py": """
                import time

                def leaf(x):
                    return x * time.time()
            """,
            "m.py": """
                import jax
                from .helpers import leaf

                def f(x):
                    return leaf(x)

                g = jax.jit(f)
            """})
        assert [(f.rule, f.relpath) for f in fs] == \
            [("TL101", "helpers.py")]

    def test_relative_import_in_package_init(self, tmp_path):
        """`from .helpers import leaf` inside a subpackage __init__
        resolves against the PACKAGE itself, not its parent — the
        off-by-one that silently dropped trace roots routed through
        package re-exports."""
        fs = lint_pkg(tmp_path, {
            "sub/helpers.py": """
                import time

                def leaf(x):
                    return x * time.time()
            """,
            "sub/__init__.py": """
                import jax
                from .helpers import leaf

                def f(x):
                    return leaf(x)

                g = jax.jit(f)
            """})
        assert [(f.rule, f.relpath) for f in fs] == \
            [("TL101", os.path.join("sub", "helpers.py"))]

    def test_functools_partial_resolution(self, tmp_path):
        fs = lint_pkg(tmp_path, {"m.py": """
            import functools
            import time
            import jax

            def f(cfg, x):
                return x + time.monotonic()

            g = jax.jit(functools.partial(f, 3))
        """})
        assert [f.rule for f in fs] == ["TL101"]

    def test_partial_bound_config_param_not_traced(self, tmp_path):
        """`jit(partial(init_params, cfg))`: cfg is closed over
        host-side — branching on it is legitimate trace-time config,
        while the REAL traced param stays checked."""
        fs = lint_pkg(tmp_path, {"m.py": """
            import functools
            import jax

            def f(cfg, x):
                if cfg.flag:               # host config: clean
                    x = x * 2
                if x > 0:                  # traced: flagged
                    x = x + 1
                return x

            g = jax.jit(functools.partial(f, 3))
        """})
        assert [f.rule for f in fs] == ["TL103"]
        assert "`x`" in fs[0].message


# --------------------------------------------------- allowlist semantics


class TestAllowlist:
    def _findings(self, tmp_path, n_bad=1):
        src = "import time\nimport jax\n\ndef f(x):\n"
        for i in range(n_bad):
            src += f"    t{i} = time.time()\n"
        src += "    return x\n\ng = jax.jit(f)\n"
        return lint_pkg(tmp_path, {"m.py": src})

    def test_new_finding_fails(self, tmp_path):
        fs = self._findings(tmp_path)
        rep = tracelint.reconcile(fs, {})
        assert not rep["ok"] and len(rep["new"]) == 1

    def test_allowlisted_passes(self, tmp_path):
        fs = self._findings(tmp_path)
        allow = {fs[0].key: {"count": 1, "reason": "test"}}
        rep = tracelint.reconcile(fs, allow)
        assert rep["ok"] and rep["new"] == [] and not rep["burndown"]

    def test_regression_over_count_fails(self, tmp_path):
        fs = self._findings(tmp_path, n_bad=2)
        allow = {fs[0].key: {"count": 1, "reason": "test"}}
        rep = tracelint.reconcile(fs, allow)
        assert not rep["ok"]
        assert list(rep["over"].values()) == [(2, 1)]

    def test_burndown_under_count_passes_with_nudge(self, tmp_path):
        fs = self._findings(tmp_path, n_bad=1)
        allow = {fs[0].key: {"count": 3, "reason": "test"},
                 "TL101:gone.py:f": {"count": 2, "reason": "stale"}}
        rep = tracelint.reconcile(fs, allow)
        assert rep["ok"]
        assert rep["burndown"][fs[0].key] == (1, 3)
        assert rep["burndown"]["TL101:gone.py:f"] == (0, 2)

    def test_shipped_allowlist_entries_all_have_reasons(self):
        allow = tracelint.load_allowlist(
            os.path.join(REPO, "tools", "tracelint_allowlist.json"))
        assert allow, "shipped allowlist should exist"
        for key, e in allow.items():
            assert e["reason"].strip(), f"{key} has no justification"


# ------------------------------------------------- canonicalize_spec


class TestCanonicalizeSpec:
    def test_trailing_none_trimmed(self):
        from jax.sharding import PartitionSpec as P
        assert specs.canonicalize_spec(P("a", None)) == P("a")
        assert specs.canonicalize_spec(P(None, "a", None)) == \
            P(None, "a")

    def test_all_none_collapses(self):
        from jax.sharding import PartitionSpec as P
        assert specs.canonicalize_spec(P(None, None)) == P()
        assert specs.canonicalize_spec(P()) == P()

    def test_size1_axis_dropped(self):
        from jax.sharding import PartitionSpec as P
        m = {"mp": 1, "ep": 2}
        # the tp_engine._pool_spec cases, single-sourced
        assert specs.canonicalize_spec(
            P(None, None, None, "mp"), m) == P()
        assert specs.canonicalize_spec(
            P(None, None, None, "mp"), {"mp": 2}) == \
            P(None, None, None, "mp")
        assert specs.canonicalize_spec(P(("ep", "mp"), None), m) == \
            P("ep")

    def test_idempotent_and_placement_preserved(self):
        import jax
        from jax.sharding import Mesh, NamedSharding
        from jax.sharding import PartitionSpec as P
        devs = np.array(jax.devices("cpu")[:2]).reshape(2, 1)
        mesh = Mesh(devs, ("a", "b"))     # b has size 1
        for spec in (P("a", None), P(None, "b"), P(("a", "b"), None),
                     P(None, None), P("a", "b")):
            canon = specs.canonicalize_spec(spec, mesh)
            assert specs.canonicalize_spec(canon, mesh) == canon
            ns, cs = NamedSharding(mesh, spec), \
                NamedSharding(mesh, canon)
            assert ns.is_equivalent_to(cs, 2), (spec, canon)

    def test_pool_spec_single_sourced(self):
        """tp_engine._pool_spec == canonicalize_spec of the written
        form — the satellite's shared-definition contract."""
        import inspect

        from paddle_tpu.serving.distributed import tp_engine
        src = inspect.getsource(tp_engine.TPServingEngine._pool_spec)
        assert "canonicalize_spec" in src

    def test_rule_and_runtime_agree(self):
        """literal_is_canonical mirrors canonicalize_spec for the
        mesh-independent transforms (the rule/runtime no-drift
        contract)."""
        from jax.sharding import PartitionSpec as P
        cases = [((None,), False), (("a", None), False),
                 ((("a",),), False), (("a",), True),
                 ((None, "a"), True), ((), True)]
        for entries, want_ok in cases:
            ok, _ = specs.literal_is_canonical(entries)
            assert ok == want_ok, entries
            if not ok:
                canon = specs.canonicalize_spec(P(*entries))
                assert tuple(canon) != tuple(entries)


# ------------------------------------------------------ runtime guards


class TestGuards:
    def test_watchdog_budget_violation_recorded(self):
        from paddle_tpu.jit.functional import instrumented_jit
        import jax.numpy as jnp
        with guards.sanitize(transfer_guard=None,
                             budgets={"wd_test": 1}) as wd:
            f = instrumented_jit(lambda x: x + 1, "wd_test")
            f(jnp.zeros((2,)))
            assert wd.violations == []
            f(jnp.zeros((3,)))            # second signature
        v = wd.consume_violations()
        assert len(v) == 1 and v[0].name == "wd_test" \
            and v[0].count == 2
        from paddle_tpu.profiler import metrics as pm
        assert pm.COMPILE_WATCHDOG_BUDGET_EXCEEDED.labels(
            "wd_test").value >= 1

    def test_persistent_recompile_one_violation(self):
        """A persistently-recompiling instance yields ONE violation
        (count kept current) and ONE metric tick — not a duplicate
        per step."""
        from paddle_tpu.jit.functional import instrumented_jit
        from paddle_tpu.profiler import metrics as pm
        import jax.numpy as jnp
        before = pm.COMPILE_WATCHDOG_BUDGET_EXCEEDED.labels(
            "wd_persist").value
        with guards.sanitize(transfer_guard=None,
                             budgets={"wd_persist": 1}) as wd:
            f = instrumented_jit(lambda x: x + 1, "wd_persist")
            for n in (2, 3, 4, 5):        # 4 distinct signatures
                f(jnp.zeros((n,)))
        v = wd.consume_violations()
        assert len(v) == 1 and v[0].count == 4
        assert pm.COMPILE_WATCHDOG_BUDGET_EXCEEDED.labels(
            "wd_persist").value == before + 1

    def test_per_instance_budgets_isolated(self):
        """Two wrappers under one name each get their own budget —
        N engines compiling once each is NOT a violation."""
        from paddle_tpu.jit.functional import instrumented_jit
        import jax.numpy as jnp
        with guards.sanitize(transfer_guard=None,
                             budgets={"wd_iso": 1}) as wd:
            for _ in range(3):
                f = instrumented_jit(lambda x: x * 2, "wd_iso")
                f(jnp.zeros((4,)))
            assert wd.violations == []

    def test_nested_sanitize_both_record(self):
        from paddle_tpu.jit.functional import instrumented_jit
        import jax.numpy as jnp
        with guards.sanitize(transfer_guard=None,
                             budgets={"wd_nest": 0}) as outer:
            with guards.sanitize(transfer_guard=None,
                                 budgets={"wd_nest": 0}) as inner:
                f = instrumented_jit(lambda x: x - 1, "wd_nest")
                f(jnp.zeros((2,)))
            assert len(inner.consume_violations()) == 1
        assert len(outer.consume_violations()) == 1

    def test_transfer_guard_trip_counted(self):
        """Full-scope disallow + a deliberate implicit h2d: the error
        crosses the sanitize boundary and the trip counter moves."""
        import jax.numpy as jnp
        from paddle_tpu.profiler import metrics as pm
        before = pm.TRANSFER_GUARD_TRIPS.value
        with pytest.raises(Exception, match="[Dd]isallow"):
            with guards.sanitize(guard_scope=("all",), watchdog=False):
                _ = jnp.ones((3,)) * 2.0   # h2d constant -> trip
        assert pm.TRANSFER_GUARD_TRIPS.value == before + 1

    def test_note_exception_counts_guard_errors_only(self):
        """The conftest makereport hook's counting path: a pytest
        test-body exception never unwinds through the yield fixture,
        so trips are reported via note_exception off the test
        report."""
        from paddle_tpu.profiler import metrics as pm
        before = pm.TRANSFER_GUARD_TRIPS.value
        exc = RuntimeError("Disallowed host-to-device transfer: ...")
        assert guards.note_exception(exc) is True
        assert pm.TRANSFER_GUARD_TRIPS.value == before + 1
        # idempotent per exception object: a trip seen by both an
        # inner sanitize scope and the makereport hook counts once
        assert guards.note_exception(exc) is True
        assert pm.TRANSFER_GUARD_TRIPS.value == before + 1
        assert guards.note_exception(ValueError("unrelated")) is False
        assert guards.note_exception(None) is False
        assert pm.TRANSFER_GUARD_TRIPS.value == before + 1

    def test_from_env(self, monkeypatch):
        monkeypatch.setenv("PADDLE_TPU_GUARDS", "0")
        assert guards.from_env() is None
        monkeypatch.setenv("PADDLE_TPU_GUARDS", "1")
        assert guards.from_env() == {}
        monkeypatch.setenv("PADDLE_TPU_GUARDS", "nan")
        assert guards.from_env() == {"nan_debug": True}
        monkeypatch.delenv("PADDLE_TPU_GUARDS")
        assert guards.from_env() == {}

    def test_default_budgets_cover_one_compile_contracts(self):
        assert guards.DEFAULT_BUDGETS["serving_mixed_step"] == 1
        assert guards.DEFAULT_BUDGETS["serving_prefix_cow"] == 1


class TestWatchdogCatchesEngineRecompile:
    def test_second_mixed_step_compile_fails_the_test(self):
        """The acceptance demo: a one-compile serving engine whose
        mixed step is forced into a SECOND compile (an int16 where the
        packed plan is always int32 — exactly the signature-drift
        bug class) is caught by the suite-wide conftest watchdog; the
        violation is consumed here so this test documents the failure
        instead of failing itself."""
        wd = guards.current()
        if wd is None:
            pytest.skip("PADDLE_TPU_GUARDS=0")
        import jax
        import jax.numpy as jnp
        from paddle_tpu.models.gpt import GPTForGeneration
        from paddle_tpu.serving.engine import ServingEngine
        import paddle_tpu as paddle
        paddle.seed(0)
        model = GPTForGeneration(vocab_size=97, hidden_size=16,
                                 num_layers=1, num_attention_heads=2,
                                 max_position_embeddings=64,
                                 compute_dtype="float32")
        eng = ServingEngine(model, max_slots=2, block_size=4,
                            max_seq_len=32, cache_dtype="float32")
        eng.generate_batch([[5, 6, 7]], max_new_tokens=2)
        assert wd.violations == []      # one compile: in budget
        bad = eng._step_fn(
            eng._arrays, eng.kv.k_pool, eng.kv.v_pool,
            # the packed plan: int32 by contract
            jnp.zeros((eng.plan_layout.size,), jnp.int16),
            # the tokens of the step before (an engine that dispatches
            # ahead takes them beside the plan)
            eng._prev_tokens,
            jax.random.PRNGKey(0))
        del bad
        v = wd.consume_violations()
        assert len(v) == 1
        assert v[0].name == "serving_mixed_step"
        assert v[0].count == 2 and v[0].budget == 1


# ------------------------------------------------------------ meta


class TestRuleCatalog:
    def test_every_rule_id_documented(self):
        doc = open(os.path.join(REPO, "docs", "ANALYSIS.md")).read()
        for rule in RULES:
            assert rule in doc, f"rule {rule} missing from ANALYSIS.md"

    def test_every_finding_rule_is_registered(self, tmp_path):
        fs = lint_pkg(tmp_path, {"m.py": """
            import time
            import jax

            def f(x):
                return x * time.time()

            g = jax.jit(f)
        """})
        for f in fs:
            assert f.rule in RULES
