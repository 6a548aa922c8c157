"""Tests for tools/rxgblint: per-rule true-positive + clean-negative
fixtures, pragma and baseline behavior, and the tier-1 gate asserting the
shipped package lints clean (a future regression fails here, same pattern
as the bench tripwires).

Pure-stdlib: the linter never imports the package under analysis, so these
tests run without jax.
"""

import json
import os
import sys
import textwrap

import pytest

sys.path.insert(0, os.path.dirname(os.path.dirname(os.path.abspath(__file__))))

from tools.rxgblint import (  # noqa: E402
    BaselineError,
    RULES,
    lint_source,
    report_to_json,
    run_lint,
)
from tools.rxgblint.baseline import DEFAULT_BASELINE  # noqa: E402
from tools.rxgblint.catalog import REPO_ROOT  # noqa: E402

PKG = os.path.join(REPO_ROOT, "xgboost_ray_tpu")


def codes(findings, include_suppressed=False):
    return [
        f.rule for f in findings if include_suppressed or not f.suppressed
    ]


def lint(src, path="mod.py", **kw):
    return lint_source(textwrap.dedent(src), path=path, **kw)


# ---------------------------------------------------------------------------
# SPMD001 — collectives under rank-dependent control flow
# ---------------------------------------------------------------------------


def test_spmd001_true_positive_rank_branch():
    findings = lint("""
        import jax
        def f(x, rank):
            if rank == 0:
                return jax.lax.psum(x, "actors")
            return x
    """)
    assert codes(findings) == ["SPMD001"]
    assert "hang" in findings[0].message


def test_spmd001_true_positive_process_index_call():
    findings = lint("""
        import jax
        def f(x):
            if jax.process_index() == 0:
                x = jax.lax.all_gather(x, "actors")
            return x
    """)
    assert "SPMD001" in codes(findings)


def test_spmd001_clean_uniform_branch_and_hoisted_collective():
    findings = lint("""
        import jax
        def f(x, n_actors, rank):
            s = jax.lax.psum(x, "actors")      # unconditional: fine
            if n_actors > 1:                    # world-uniform condition
                s = jax.lax.pmax(s, "actors")
            idx = jax.lax.axis_index("actors")  # divergence-safe primitive
            if rank == 0:
                s = s + idx                     # no collective in branch
            return s
    """)
    assert codes(findings) == []


# ---------------------------------------------------------------------------
# SPMD002 — axis names from the mesh catalog
# ---------------------------------------------------------------------------


def test_spmd002_true_positive_unknown_axis():
    findings = lint("""
        import jax
        def f(x):
            return jax.lax.psum(x, "actorz")
    """)
    assert codes(findings) == ["SPMD002"]
    assert "actorz" in findings[0].message


def test_spmd002_clean_catalog_axis_and_axis_name_param():
    findings = lint("""
        import jax
        def helper(x, axis_name):
            return jax.lax.psum(x, axis_name)
        def f(x):
            return jax.lax.pmax(helper(x, "actors"), "actors")
    """)
    assert codes(findings) == []


def test_spmd002_opaque_variable_axis_flagged():
    findings = lint("""
        import jax
        def f(x, ax):
            return jax.lax.psum(x, ax)
    """)
    assert codes(findings) == ["SPMD002"]


# ---------------------------------------------------------------------------
# DET001 — nondeterminism sources
# ---------------------------------------------------------------------------


def test_det001_true_positive_global_np_random():
    findings = lint("""
        import numpy as np
        def f(n):
            return np.random.rand(n)
    """)
    assert codes(findings) == ["DET001"]


def test_det001_true_positive_time_in_traced():
    findings = lint("""
        import jax, time
        def f(x):
            return x + time.time()
        g = jax.jit(f)
    """)
    assert codes(findings) == ["DET001"]
    assert "trace time" in findings[0].message


def test_det001_true_positive_unsalted_fold_literal():
    findings = lint("""
        import jax
        def f(key):
            return jax.random.fold_in(key, 1234)
    """)
    assert codes(findings) == ["DET001"]
    assert "SALT_" in findings[0].message


def test_det001_true_positive_prngkey_from_clock():
    findings = lint("""
        import jax, time
        def f():
            return jax.random.PRNGKey(time.time_ns())
    """)
    assert "DET001" in codes(findings)


def test_det001_true_positive_set_iteration():
    findings = lint("""
        def f(items):
            out = []
            for x in set(items):
                out.append(x)
            return out
    """)
    assert codes(findings) == ["DET001"]
    assert "sorted" in findings[0].message


def test_det001_clean_seeded_and_salted():
    findings = lint("""
        import jax, time
        import numpy as np
        from xgboost_ray_tpu.ops.grow import SALT_BYTREE
        def f(params, iteration, items):
            rng = np.random.RandomState(0)           # seeded: fine
            key = jax.random.PRNGKey(params.seed)     # from a seed: fine
            key = jax.random.fold_in(key, iteration)  # non-literal: fine
            key = jax.random.fold_in(key, SALT_BYTREE)
            t0 = time.time()                          # host code: fine
            return sorted(set(items)), key, t0
    """)
    assert codes(findings) == []


def test_det001_sr_salt_catalogued_and_neighbors_still_flag():
    """SALT_SR (stochastic gh rounding, gh_precision) is auto-extracted
    into the DET001 salt domain — its literal value folds clean without a
    pragma — while an uncatalogued neighbor value still flags: the domain
    grew by exactly the declared constant, not by becoming vacuous."""
    from tools.rxgblint import catalog

    assert 0x51D6 in catalog.salt_values()  # SALT_SR (ops/grow.py)
    clean = lint("""
        import jax
        def f(key):
            return jax.random.fold_in(key, 0x51D6)
    """)
    assert codes(clean) == []
    flagged = lint("""
        import jax
        def f(key):
            return jax.random.fold_in(key, 0x51D7)
    """)
    assert codes(flagged) == ["DET001"]
    assert "SALT_" in flagged[0].message


# ---------------------------------------------------------------------------
# SYNC001 — host syncs in traced code
# ---------------------------------------------------------------------------


def test_sync001_true_positive_float_and_item_in_traced():
    findings = lint("""
        import jax
        import numpy as np
        def f(x):
            a = float(x.sum())
            b = x.max().item()
            c = np.asarray(x)
            return a + b + c[0]
        g = jax.jit(f)
    """)
    assert codes(findings) == ["SYNC001"] * 3


def test_sync001_true_positive_shard_map_closure():
    findings = lint("""
        import jax
        def build(mesh, specs):
            def fn(x):
                return bool(x.any())
            return jax.shard_map(fn, mesh=mesh, in_specs=specs,
                                 out_specs=specs)
    """)
    assert codes(findings) == ["SYNC001"]


def test_sync001_clean_host_code_and_jnp():
    findings = lint("""
        import jax
        import jax.numpy as jnp
        import numpy as np
        def f(x):
            return jnp.asarray(x) + 1
        g = jax.jit(f)
        def host(result):
            return float(np.asarray(result).sum())  # untraced: fine
    """)
    assert codes(findings) == []


def test_sync001_clean_literal_args_in_traced():
    # float("inf")/bool(0) sentinels inside traced code touch no traced
    # value — flagging them would force pragmas on idiomatic init code
    findings = lint("""
        import jax
        import jax.numpy as jnp
        def f(x):
            lo = jnp.full(x.shape, float("-inf"))
            return jnp.maximum(x, lo) + float("inf") * 0
        g = jax.jit(f)
    """)
    assert codes(findings) == []


def test_sync001_method_name_collision_is_not_traced():
    # a method sharing its name with a traced inner closure elsewhere must
    # not inherit traced status (lexical scoping, not global name match)
    findings = lint("""
        import jax
        class Engine:
            def _make(self):
                def step(x):
                    return x
                return jax.jit(step)
            def step(self, x):
                return float(x)  # host-side driver method: fine
    """)
    assert codes(findings) == []


# ---------------------------------------------------------------------------
# LOCK001 — shared state outside the lock
# ---------------------------------------------------------------------------


def test_lock001_true_positive_unguarded_write():
    findings = lint("""
        import threading
        class C:
            def __init__(self):
                self._lock = threading.Lock()
                self._n = 0
            def inc(self):
                with self._lock:
                    self._n += 1
            def smash(self):
                self._n = 0
    """)
    assert codes(findings) == ["LOCK001"]
    assert "write" in findings[0].message
    assert findings[0].scope == "C.smash"


def test_lock001_true_positive_unguarded_read_and_condition_lock():
    findings = lint("""
        import threading
        class C:
            def __init__(self):
                self._cond = threading.Condition(threading.Lock())
                self._depth = 0
            def push(self):
                with self._cond:
                    self._depth += 1
            def peek(self):
                return self._depth
    """)
    assert codes(findings) == ["LOCK001"]
    assert "read" in findings[0].message


def test_lock001_locked_suffix_contract_both_ends():
    findings = lint("""
        import threading
        class C:
            def __init__(self):
                self._lock = threading.Lock()
                self._n = 0
            def _bump_locked(self):
                self._n += 1     # exempt: caller holds the lock
            def ok(self):
                with self._lock:
                    self._bump_locked()
            def bad(self):
                self._bump_locked()   # contract breach: no lock held
    """)
    assert codes(findings) == ["LOCK001"]
    assert "_locked" in findings[0].message
    assert findings[0].scope == "C.bad"


def test_lock001_clean_guarded_class_and_lockless_class():
    findings = lint("""
        import threading
        class Guarded:
            def __init__(self):
                self._lock = threading.Lock()
                self._n = 0
            def inc(self):
                with self._lock:
                    self._n += 1
            def get(self):
                with self._lock:
                    return self._n
        class Plain:  # no lock declared: not subject to the rule
            def __init__(self):
                self._n = 0
            def inc(self):
                self._n += 1
    """)
    assert codes(findings) == []


def test_lock001_wrong_lock_flagged_nested_locks_clean():
    # holding SOME lock of the class is not holding THE lock that guards
    # the attribute's writes — a wrong-lock read tears just like no lock
    findings = lint("""
        import threading
        class TwoLocks:
            def __init__(self):
                self._lock = threading.Lock()
                self._other = threading.Lock()
                self._x = 0
            def inc(self):
                with self._lock:
                    self._x += 1
            def get(self):
                with self._other:
                    return self._x
    """)
    assert codes(findings) == ["LOCK001"]
    assert "wrong lock" in findings[0].message
    # nested acquisition (outer serializer + inner guard) stays clean:
    # the owning lock IS among those held (the ModelRegistry.load shape)
    findings = lint("""
        import threading
        class Nested:
            def __init__(self):
                self._outer = threading.Lock()
                self._lock = threading.Lock()
                self._x = 0
            def swap(self):
                with self._outer:
                    with self._lock:
                        self._x += 1
            def get(self):
                with self._lock:
                    return self._x
    """)
    assert codes(findings) == []


# ---------------------------------------------------------------------------
# FAULT001 — fault sites must come from faults.SITES
# ---------------------------------------------------------------------------


def test_fault001_true_positive_typo_site():
    findings = lint("""
        from xgboost_ray_tpu import faults
        def f():
            faults.fire("actor.train_rund", round=1)
    """)
    assert codes(findings) == ["FAULT001"]
    assert "actor.train_rund" in findings[0].message


def test_fault001_true_positive_dynamic_site():
    findings = lint("""
        from xgboost_ray_tpu import faults
        def f(site):
            faults.fire(site, round=1)
    """)
    assert codes(findings) == ["FAULT001"]


def test_fault001_clean_catalogued_sites():
    findings = lint("""
        from xgboost_ray_tpu import faults
        def f(path):
            faults.fire("actor.train_round", round=1)
            faults.fire_file("checkpoint.save", path, round=2)
            return faults.plan_targets("serve.predict")
    """)
    assert codes(findings) == []


def test_fault001_reverse_coverage(tmp_path):
    # a catalogued site with no call site anywhere is a finding anchored
    # at faults.py
    pkg = tmp_path / "xgboost_ray_tpu"
    pkg.mkdir()
    (pkg / "__init__.py").write_text("")
    (pkg / "faults.py").write_text(
        'SITES = ("used.site", "orphan.site")\n'
        "def fire(site, **ctx):\n    pass\n"
    )
    (pkg / "user.py").write_text(
        "from xgboost_ray_tpu import faults\n"
        'def f():\n    faults.fire("used.site")\n'
    )
    report = run_lint([str(pkg)], root=str(tmp_path), baseline_path="")
    msgs = [f.message for f in report["open"] if f.rule == "FAULT001"]
    assert len(msgs) == 1 and "orphan.site" in msgs[0]


# ---------------------------------------------------------------------------
# OBS001 — span/event names from the trace-name catalog
# ---------------------------------------------------------------------------


def test_obs001_true_positive_uncatalogued_and_fstring():
    findings = lint("""
        from xgboost_ray_tpu import obs
        def f(i):
            obs.get_tracer().event("unknown_name_xyz")
            obs.get_tracer().event(f"round.{i}")
    """)
    assert codes(findings) == ["OBS001", "OBS001"]
    assert "TRACE_NAMES" in findings[0].message
    assert "f-string" in findings[1].message


def test_obs001_true_positive_bad_shape():
    findings = lint("""
        def f(tracer):
            tracer.event("Not A Valid Name")
    """)
    assert codes(findings) == ["OBS001"]
    assert "shape" in findings[0].message


def test_obs001_clean_catalogued_names_and_conditional_literal():
    findings = lint("""
        def f(tracer, kind):
            tracer.event("recovered")
            tracer.event("world.shrink" if kind == "shrink" else "world.grow")
            with tracer.span("round", round=3):
                pass
    """)
    assert codes(findings) == []


def test_obs001_reverse_coverage(tmp_path):
    pkg = tmp_path / "xgboost_ray_tpu"
    (pkg / "obs").mkdir(parents=True)
    (pkg / "__init__.py").write_text("")
    (pkg / "obs" / "__init__.py").write_text("")
    (pkg / "obs" / "trace.py").write_text(
        'TRACE_NAMES = frozenset({"used.name", "orphan.name"})\n'
    )
    (pkg / "emitter.py").write_text(
        'def f(tracer):\n    tracer.event("used.name")\n'
    )
    report = run_lint([str(pkg)], root=str(tmp_path), baseline_path="")
    msgs = [f.message for f in report["open"] if f.rule == "OBS001"]
    assert len(msgs) == 1 and "orphan.name" in msgs[0]


# ---------------------------------------------------------------------------
# EXP001 — export consistency
# ---------------------------------------------------------------------------


def test_exp001_true_positive_unresolved_export():
    findings = lint("""
        x = 1
        __all__ = ["x", "ghost"]
    """, path="pkg/__init__.py")
    assert codes(findings) == ["EXP001"]
    assert "ghost" in findings[0].message


def test_exp001_true_positive_missing_required_export():
    findings = lint("""
        train = object()
        __all__ = ["train"]
    """, path="xgboost_ray_tpu/__init__.py")
    assert any(
        f.rule == "EXP001" and "recovery_time_s" in f.message
        for f in findings
    )


def test_exp001_clean_conditional_imports_and_extend():
    findings = lint("""
        from os import path
        try:
            from json import dumps
        except ImportError:
            pass
        __all__ = ["path"]
        __all__ += ["dumps"]
    """, path="pkg/__init__.py")
    assert codes(findings) == []


def test_exp001_function_local_is_not_a_module_binding():
    # a name bound only inside a function body must not satisfy __all__ —
    # `from pkg import *` would still raise AttributeError at runtime
    findings = lint("""
        __all__ = ["helper"]
        def factory():
            helper = 1
            return helper
    """, path="pkg/__init__.py")
    assert codes(findings) == ["EXP001"]
    # ...but module-level conditional/try bindings DO count
    findings = lint("""
        __all__ = ["helper", "fallback"]
        try:
            from fast import helper
        except ImportError:
            def helper():
                pass
        if True:
            fallback = 1
    """, path="pkg/__init__.py")
    assert codes(findings) == []


def test_exp001_non_init_files_ignored():
    findings = lint('__all__ = ["ghost"]\n', path="pkg/module.py")
    assert codes(findings) == []


# ---------------------------------------------------------------------------
# pragmas
# ---------------------------------------------------------------------------


def test_pragma_same_line_suppresses_named_rule():
    findings = lint("""
        import numpy as np
        def f(n):
            return np.random.rand(n)  # rxgblint: disable=DET001 - fixture
    """)
    assert codes(findings) == []
    assert codes(findings, include_suppressed=True) == ["DET001"]
    assert findings[0].suppressed == "pragma"


def test_pragma_next_line_and_all():
    findings = lint("""
        import numpy as np
        def f(n):
            # rxgblint: disable-next-line=DET001
            a = np.random.rand(n)
            # rxgblint: disable-next-line=all
            b = np.random.rand(n)
            return a + b
    """)
    assert codes(findings) == []
    assert len(codes(findings, include_suppressed=True)) == 2


def test_pragma_wrong_rule_does_not_suppress():
    findings = lint("""
        import numpy as np
        def f(n):
            return np.random.rand(n)  # rxgblint: disable=SPMD001
    """)
    assert codes(findings) == ["DET001"]


def test_pragma_inside_string_literal_does_not_suppress():
    # pragma-shaped text in a string/docstring (e.g. a module documenting
    # the pragma syntax) must never silently disable rules on its line
    findings = lint("""
        import numpy as np
        def f(n):
            return np.random.rand(n), "see  # rxgblint: disable=DET001"
    """)
    assert codes(findings) == ["DET001"]
    findings = lint('''
        import numpy as np
        def f(n):
            """Suppress with  # rxgblint: disable-next-line=all  above."""
            return np.random.rand(n)
    ''')
    assert codes(findings) == ["DET001"]


# ---------------------------------------------------------------------------
# baseline
# ---------------------------------------------------------------------------


def _write_module_with_finding(tmp_path):
    pkg = tmp_path / "xgboost_ray_tpu"
    pkg.mkdir()
    (pkg / "__init__.py").write_text("")
    (pkg / "mod.py").write_text(
        "import numpy as np\n"
        "def f(n):\n"
        "    return np.random.rand(n)\n"
    )
    return pkg


def test_baseline_suppresses_with_justification(tmp_path):
    pkg = _write_module_with_finding(tmp_path)
    baseline = tmp_path / "baseline.json"
    baseline.write_text(json.dumps({"entries": [{
        "rule": "DET001",
        "path": "xgboost_ray_tpu/mod.py",
        "scope": "f",
        "why": "fixture: accepted finding",
    }]}))
    report = run_lint(
        [str(pkg)], root=str(tmp_path), baseline_path=str(baseline)
    )
    assert report["open"] == []
    assert report["baselined"] == 1
    assert report["stale_baseline"] == []


def test_baseline_requires_justification(tmp_path):
    pkg = _write_module_with_finding(tmp_path)
    baseline = tmp_path / "baseline.json"
    baseline.write_text(json.dumps({"entries": [{
        "rule": "DET001",
        "path": "xgboost_ray_tpu/mod.py",
        "scope": "f",
        "why": "   ",
    }]}))
    with pytest.raises(BaselineError):
        run_lint([str(pkg)], root=str(tmp_path), baseline_path=str(baseline))


def test_baseline_stale_entry_reported(tmp_path):
    pkg = _write_module_with_finding(tmp_path)
    baseline = tmp_path / "baseline.json"
    baseline.write_text(json.dumps({"entries": [{
        "rule": "LOCK001",
        "path": "xgboost_ray_tpu/gone.py",
        "scope": "C.m",
        "why": "matches nothing anymore",
    }]}))
    report = run_lint(
        [str(pkg)], root=str(tmp_path), baseline_path=str(baseline)
    )
    assert len(report["stale_baseline"]) == 1
    assert codes(report["open"]) == ["DET001"]  # nothing wrongly eaten


# ---------------------------------------------------------------------------
# the tier-1 gate: the shipped package lints clean
# ---------------------------------------------------------------------------


def test_shipped_package_lints_clean():
    report = run_lint([PKG], baseline_path=DEFAULT_BASELINE)
    open_findings = report["open"]
    assert open_findings == [], (
        "rxgblint regression — new findings:\n"
        + "\n".join(f.render() for f in open_findings)
    )


def test_shipped_baseline_is_small_and_justified():
    with open(DEFAULT_BASELINE) as f:
        entries = json.load(f)["entries"]
    assert len(entries) <= 5, "baseline should shrink, not grow"
    for e in entries:
        assert len(e["why"].strip()) > 10


def test_single_file_lint_skips_whole_package_checks():
    # reverse coverage (orphan fault sites / trace names) and stale-baseline
    # reporting are whole-package properties: linting one file must not
    # claim the rest of the package's call sites don't exist
    report = run_lint(
        [os.path.join(PKG, "util.py")], baseline_path=DEFAULT_BASELINE
    )
    assert report["files"] == 1
    assert codes(report["open"]) == []
    assert report["stale_baseline"] == []
    assert not any(
        f.rule in ("FAULT001", "OBS001")
        for f in report["findings"]
    )


def test_json_report_shape():
    report = run_lint([PKG], baseline_path=DEFAULT_BASELINE)
    doc = json.loads(report_to_json(report))
    assert doc["tool"] == "rxgblint"
    assert set(RULES) <= set(doc["rules"])
    assert isinstance(doc["findings"], list)
    assert doc["files"] > 40
    for f in doc["findings"]:
        assert {"rule", "path", "line", "scope", "message"} <= set(f)


def test_rule_catalog_documented():
    for code in ("SPMD001", "SPMD002", "DET001", "SYNC001", "LOCK001",
                 "FAULT001", "OBS001", "EXP001"):
        assert code in RULES and len(RULES[code]) > 20


def test_missing_or_empty_target_is_a_usage_error(tmp_path):
    # a typo'd path must not make the tier-1 gate pass vacuously: 0 files
    # linted has to be a loud exit-2 usage error, never "0 findings"
    from tools.rxgblint.__main__ import main
    from tools.rxgblint.runner import TargetError

    with pytest.raises(TargetError):
        run_lint([str(tmp_path / "nonexistent_typo")])
    with pytest.raises(TargetError):  # existing file, but not Python
        notpy = tmp_path / "data.json"
        notpy.write_text("{}")
        run_lint([str(notpy)])
    assert main([str(tmp_path / "nonexistent_typo")]) == 2
    empty = tmp_path / "emptydir"
    empty.mkdir()
    assert main([str(empty)]) == 2


def test_broken_pipe_does_not_mask_findings(tmp_path):
    # `rxgblint ... | head -0` closing stdout early must not flip a
    # findings run (exit 1) into a pass (exit 0)
    import subprocess

    bad = tmp_path / "bad.py"
    bad.write_text("import numpy as np\nx = np.random.rand(3)\n")
    proc = subprocess.run(
        f"{sys.executable} -m tools.rxgblint {bad} | head -0; "
        f"exit ${{PIPESTATUS[0]}}",
        shell=True, executable="/bin/bash", cwd=REPO_ROOT,
        capture_output=True,
    )
    assert proc.returncode == 1, proc.stderr.decode()


# ---------------------------------------------------------------------------
# runtime counterpart: validate_trace_records(known_names=...)
# ---------------------------------------------------------------------------


def test_validate_trace_records_known_names():
    from xgboost_ray_tpu.obs import TRACE_NAMES, validate_trace_records

    rec = {"kind": "event", "name": "recovered", "ts": 1.0, "t0_s": 1.0,
           "seq": 1}
    bad = {"kind": "event", "name": "not.catalogued", "ts": 2.0, "t0_s": 2.0,
           "seq": 2}
    assert validate_trace_records([rec, bad]) == []  # default: schema only
    problems = validate_trace_records([rec, bad], known_names=TRACE_NAMES)
    assert len(problems) == 1 and "not.catalogued" in problems[0]
