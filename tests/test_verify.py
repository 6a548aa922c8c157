"""tools/rxgbverify: jaxpr-level verifier tests.

Fixture programs are hand-built ``progreg.ProgramRecord``s traced through
the real walker — every true-positive below is a program that would pass
rxgblint's AST rules (the hazard lives in the traced jaxpr, which is the
whole point of the second layer). The quick-matrix test is the tier-1 gate
that the SHIPPED package verifies clean, mirroring test_lint's
shipped-package-lints-clean pattern.
"""

import json
import os

import numpy as np
import pytest

import jax
import jax.numpy as jnp
from jax.sharding import Mesh, PartitionSpec as P

from tools.rxgblint import catalog
from tools.rxgbverify import checks, walker
from tools.rxgbverify.matrix import trace_matrix
from xgboost_ray_tpu import progreg
from xgboost_ray_tpu.constants import AXIS_ACTORS
from xgboost_ray_tpu.engine import TpuEngine
from xgboost_ray_tpu.ops.histogram import quantized_hist_allreduce
from xgboost_ray_tpu.params import parse_params

MESH_AXES = catalog.mesh_axes()


def _meta(**over):
    meta = {
        "world": 4, "grower": "depthwise", "hist_quant": "none",
        "sampling": "none", "n_outputs": 1, "max_depth": 3, "max_leaves": 0,
    }
    meta.update(over)
    return meta


def _trace(fn, avals, name="engine.step", donate=(), **meta_over):
    rec = progreg.ProgramRecord(
        name=name, fn=fn, abstract_args=tuple(avals),
        donate_argnums=tuple(donate), meta=_meta(**meta_over),
        source=(os.path.abspath(__file__), 1),
    )
    return walker.trace_record(rec)


def _mesh(n=4):
    return Mesh(np.array(jax.devices()[:n]), (AXIS_ACTORS,))


def _sharded(body, n=4, n_in=1):
    specs = tuple(P(AXIS_ACTORS) for _ in range(n_in))
    return jax.shard_map(
        body, mesh=_mesh(n),
        in_specs=specs if n_in > 1 else specs[0],
        out_specs=P(AXIS_ACTORS),
        check_vma=False,
    )


F32V = jax.ShapeDtypeStruct((8, 16), "float32")


# ---------------------------------------------------------------------------
# walker
# ---------------------------------------------------------------------------

def test_walker_extracts_ordered_schedule():
    def body(x):
        s = jax.lax.psum(x, AXIS_ACTORS)
        m = jax.lax.pmax(x, AXIS_ACTORS)
        return s + m

    t = _trace(_sharded(body), (F32V,))
    assert t.ok, t.error
    prims = [c.prim for c in t.analysis.collectives]
    assert prims == ["psum", "pmax"]
    for c in t.analysis.collectives:
        assert c.axes == (AXIS_ACTORS,)
        assert c.dtype == "float32"
        assert "shard_map" in c.path


def test_walker_recurses_scan_and_flags_cond():
    def body(x):
        def step(carry, _):
            return jax.lax.psum(carry, AXIS_ACTORS), ()

        x, _ = jax.lax.scan(step, x, None, length=3)
        # a collective only SOME ranks reach: the cond-branch hazard
        return jax.lax.cond(
            x[0, 0] > 0,
            lambda v: jax.lax.pmax(v, AXIS_ACTORS),
            lambda v: v,
            x,
        )

    t = _trace(_sharded(body), (F32V,))
    assert t.ok, t.error
    by_prim = {c.prim: c for c in t.analysis.collectives}
    assert "scan" in by_prim["psum"].path and not by_prim["psum"].in_cond
    assert by_prim["pmax"].in_cond
    findings = checks.check_cond_collectives([t])
    assert [f.rule for f in findings] == ["VER002"]
    assert "cond branch" in findings[0].message


def test_fingerprint_stable_and_sensitive():
    body = _sharded(lambda x: jax.lax.psum(x, AXIS_ACTORS))
    t1 = _trace(body, (F32V,))
    t2 = _trace(body, (F32V,))
    assert t1.fingerprint == t2.fingerprint  # same program -> same hash
    bigger = jax.ShapeDtypeStruct((16, 16), "float32")
    t3 = _trace(body, (bigger,))
    assert t3.fingerprint != t1.fingerprint  # aval drift is visible
    # donation is part of the program identity
    assert walker.fingerprint(t1.closed_jaxpr, (0,)) != t1.fingerprint


# ---------------------------------------------------------------------------
# VER001 cross-world schedule identity (true positive + clean negative)
# ---------------------------------------------------------------------------

def _world_pair(body2, body4):
    t2 = _trace(_sharded(body2, n=2), (F32V,), world=2)
    t4 = _trace(_sharded(body4, n=4), (F32V,), world=4)
    return [t2, t4]


def test_schedule_identity_true_positive():
    # world=2 runs psum->pmax, world=4 runs pmax->psum: on an elastic
    # grow-back these two compiled programs would interleave mismatched
    # collectives — the torn-allreduce hang. Shapes/AST are identical.
    def b2(x):
        return jax.lax.pmax(jax.lax.psum(x, AXIS_ACTORS), AXIS_ACTORS)

    def b4(x):
        return jax.lax.psum(jax.lax.pmax(x, AXIS_ACTORS), AXIS_ACTORS)

    findings = checks.check_schedule_identity(_world_pair(b2, b4))
    assert [f.rule for f in findings] == ["VER001"]
    assert "world=4" in findings[0].message
    # the true positive fails the gate end to end
    assert checks.run_checks(_world_pair(b2, b4), MESH_AXES)


def test_schedule_identity_clean_across_shard_extents():
    # identical schedule, different world (so different shard extents after
    # shard_map division): must NOT alarm — that is exactly the legitimate
    # shrink/grow recompile delta
    def body(x):
        return jax.lax.psum(x * 2, AXIS_ACTORS)

    assert checks.check_schedule_identity(_world_pair(body, body)) == []


def test_schedule_identity_dtype_drift_is_flagged():
    def b2(x):
        return jax.lax.psum(x, AXIS_ACTORS)

    def b4(x):
        return jax.lax.psum(x.astype(jnp.bfloat16), AXIS_ACTORS).astype(
            jnp.float32
        )

    findings = checks.check_schedule_identity(_world_pair(b2, b4))
    assert [f.rule for f in findings] == ["VER001"]


# ---------------------------------------------------------------------------
# VER003 axis catalog / VER005 f64 / VER006 donation / TRACE
# ---------------------------------------------------------------------------

def test_axis_name_true_positive():
    mesh = Mesh(np.array(jax.devices()[:4]), ("workers",))
    body = jax.shard_map(
        lambda x: jax.lax.psum(x, "workers"), mesh=mesh,
        in_specs=P("workers"), out_specs=P("workers"),
        check_vma=False,
    )
    t = _trace(body, (F32V,))
    findings = checks.check_axis_names([t], MESH_AXES)
    assert [f.rule for f in findings] == ["VER003"]
    assert "workers" in findings[0].message


def test_axis_catalog_accepts_declared_axis():
    t = _trace(_sharded(lambda x: jax.lax.psum(x, AXIS_ACTORS)), (F32V,))
    assert checks.check_axis_names([t], MESH_AXES) == []


def test_no_f64_true_positive():
    def body(x):
        return x.astype(jnp.float64).sum()

    with jax.enable_x64(True):
        t = _trace(body, (F32V,))
    assert t.ok, t.error
    findings = checks.check_no_f64([t])
    assert [f.rule for f in findings] == ["VER005"]
    assert "float64" in findings[0].message


def test_donation_unused_true_positive():
    # donated [8,16] f32 input, but the only output is a scalar: XLA can
    # alias nothing — the donation only invalidates the caller's buffer
    t = _trace(lambda x: x.sum(), (F32V,), donate=(0,))
    findings = checks.check_donation([t])
    assert [f.rule for f in findings] == ["VER006"]
    assert "matches no output" in findings[0].message
    # matching shape+dtype output: clean
    t2 = _trace(lambda x: x * 2, (F32V,), donate=(0,))
    assert checks.check_donation([t2]) == []


def test_trace_failure_is_a_finding():
    def broken(x):
        raise ValueError("planted")

    t = _trace(broken, (F32V,))
    assert not t.ok
    findings = checks.check_trace_failures([t])
    assert [f.rule for f in findings] == ["TRACE"]
    assert "planted" in findings[0].message


# ---------------------------------------------------------------------------
# VER004 precision flow (true positives + the golden int8 schedule)
# ---------------------------------------------------------------------------

def _quant_body(mode, n, upcast=False):
    def body(h):
        if upcast:
            # the planted defect: one convert_element_type -> f32 before
            # the wire, silently re-inflating every quantized byte
            q = jnp.clip(jnp.round(h), -127, 127).astype(jnp.int8)
            w = q.astype(jnp.float32)
            out = jax.lax.all_to_all(w.reshape(n, -1), AXIS_ACTORS, 0, 0)
            acc = out.sum(0).astype(jnp.int8)
            g = jax.lax.all_gather(acc, AXIS_ACTORS, tiled=True)
            return g.astype(jnp.float32).reshape(h.shape)
        return quantized_hist_allreduce(h, AXIS_ACTORS, mode, n, None,
                                        min_bytes=0)

    return body


_HIST = jax.ShapeDtypeStruct((8, 7, 16, 2), "float32")  # sharded dim0 by 4


def test_precision_flow_upcast_true_positive():
    t = _trace(_sharded(_quant_body("int8", 4, upcast=True)), (_HIST,),
               hist_quant="int8")
    findings = checks.check_precision_flow([t])
    assert any(f.rule == "VER004" and "upcast before the wire" in f.message
               for f in findings)
    assert checks.run_checks([t], MESH_AXES)  # fails the gate


def test_precision_flow_fallback_psum_true_positive():
    # hist_quant=int8 config whose program still psums the full f32
    # histogram (the min_bytes fallback engaging where it must not): the
    # quantization was silently defeated
    def body(h):
        return jax.lax.psum(h, AXIS_ACTORS)

    t = _trace(_sharded(body), (_HIST,), hist_quant="int8")
    findings = checks.check_precision_flow([t])
    rules = {f.rule for f in findings}
    assert rules == {"VER004"}
    assert any("f32 histogram psum survives" in f.message for f in findings)


def test_precision_flow_ignores_unquantized_programs():
    def body(h):
        return jax.lax.psum(h, AXIS_ACTORS)

    t = _trace(_sharded(body), (_HIST,), hist_quant="none")
    assert checks.check_precision_flow([t]) == []


@pytest.mark.parametrize("mode,narrow", [("int8", "int8"), ("int16", "int16")])
def test_quantized_hist_allreduce_golden_schedule(mode, narrow):
    """Golden jaxpr schedule for ops/histogram.py's quantized path: exactly
    pmax(f32 scales) -> all_to_all(narrow) -> all_gather(narrow), with NO
    psum of the main payload — the program-level proof that the int8 wire
    format of PR 1 is what actually ships."""
    t = _trace(_sharded(_quant_body(mode, 4)), (_HIST,), hist_quant=mode)
    assert t.ok, t.error
    sched = [(c.prim, c.dtype) for c in t.analysis.collectives]
    assert sched == [
        ("pmax", "float32"),       # shared per-(node,feature) scales
        ("all_to_all", narrow),    # reduce-scatter, narrow wire
        ("all_gather", narrow),    # requantized gather (scales ride inside)
    ]
    assert checks.check_precision_flow([t]) == []


def test_unquantized_hist_allreduce_golden_schedule():
    t = _trace(_sharded(_quant_body("none", 4)), (_HIST,), hist_quant="none")
    sched = [(c.prim, c.dtype) for c in t.analysis.collectives]
    assert sched == [("psum", "float32")]


@pytest.mark.parametrize(
    "mode,narrow", [("int8_block", "int8"), ("int16_block", "int16")]
)
def test_block_hist_allreduce_golden_schedule(mode, narrow):
    """Golden jaxpr schedule for the block-scaled (EQuARX) path: exactly
    n-1 narrow ppermute ring hops then one narrow all_gather — NO absmax
    pmax pre-pass, NO all_to_all, NO psum of the payload. The deleted
    full-latency collective is pinned absent at the program level."""
    t = _trace(_sharded(_quant_body(mode, 4)), (_HIST,), hist_quant=mode)
    assert t.ok, t.error
    sched = [(c.prim, c.dtype) for c in t.analysis.collectives]
    assert sched == [("ppermute", narrow)] * 3 + [("all_gather", narrow)]
    assert checks.check_precision_flow([t]) == []


def test_block_precision_flow_row_program_claiming_block_meta():
    """Planted lie, direction 1: a ROW-scale program shipped under block
    meta must flag every way — the pmax pre-pass survives, the ring is
    missing, and the row all_to_all survives."""
    t = _trace(_sharded(_quant_body("int8", 4)), (_HIST,),
               hist_quant="int8_block")
    findings = checks.check_precision_flow([t])
    msgs = [f.message for f in findings]
    assert all(f.rule == "VER004" for f in findings)
    assert any("pmax pre-pass survives" in m for m in msgs)
    assert any("no ppermute" in m for m in msgs)
    assert any("all_to_all reduce-scatter survives" in m for m in msgs)
    assert checks.run_checks([t], MESH_AXES)  # fails the gate


def test_block_precision_flow_block_program_claiming_row_meta():
    """Planted lie, direction 2: a BLOCK-scale program shipped under row
    meta must flag too — the row contract's all_to_all stage is missing."""
    t = _trace(_sharded(_quant_body("int8_block", 4)), (_HIST,),
               hist_quant="int8")
    findings = checks.check_precision_flow([t])
    assert any(f.rule == "VER004" and "no all_to_all" in f.message
               for f in findings)


def test_block_precision_flow_upcast_ring_true_positive():
    """A ppermute ring whose hop payload was upcast to f32 defeats the
    narrow wire — flagged per hop."""
    def body(h):
        perm = [(i, (i + 1) % 4) for i in range(4)]
        cur = h.reshape(-1)
        for _ in range(3):
            q = jnp.clip(jnp.round(cur), -127, 127).astype(jnp.int8)
            cur = jax.lax.ppermute(
                q.astype(jnp.float32), AXIS_ACTORS, perm
            )
        g = jax.lax.all_gather(
            cur.astype(jnp.int8), AXIS_ACTORS, tiled=True
        )
        return g.astype(jnp.float32)[:h.size].reshape(h.shape)

    t = _trace(_sharded(body), (_HIST,), hist_quant="int8_block")
    assert t.ok, t.error
    findings = checks.check_precision_flow([t])
    assert any(
        f.rule == "VER004" and "ppermute hop payload is float32" in f.message
        for f in findings
    )


def test_schedule_identity_collapses_ring_hops_across_worlds():
    """VER001 canonicalization: world 2 traces 1 ring hop, world 4 traces 3
    — the same collapsed pattern, NOT a divergence (the hop count derives
    from the axis size every rank agrees on). A dtype drift inside the ring
    still flags."""
    def ring(n, dtype):
        def body(h):
            perm = [(i, (i + 1) % n) for i in range(n)]
            cur = jnp.clip(jnp.round(h.reshape(-1)), -127, 127).astype(dtype)
            for _ in range(n - 1):
                cur = jax.lax.ppermute(cur, AXIS_ACTORS, perm)
            g = jax.lax.all_gather(cur, AXIS_ACTORS, tiled=True)
            return g.astype(jnp.float32)[:h.size].reshape(h.shape)
        return body

    shard2 = jax.ShapeDtypeStruct((16, 7, 16, 2), "float32")
    t2 = _trace(_sharded(ring(2, jnp.int8), n=2), (shard2,), world=2,
                hist_quant="int8_block")
    t4 = _trace(_sharded(ring(4, jnp.int8)), (_HIST,), world=4,
                hist_quant="int8_block")
    assert t2.ok and t4.ok, (t2.error, t4.error)
    assert checks.check_schedule_identity([t2, t4]) == []

    t4_wide = _trace(_sharded(ring(4, jnp.int16)), (_HIST,), world=4,
                     hist_quant="int8_block")
    findings = checks.check_schedule_identity([t2, t4_wide])
    assert [f.rule for f in findings] == ["VER001"]


# ---------------------------------------------------------------------------
# registry + engine integration
# ---------------------------------------------------------------------------

def _tiny_shards(rows=32, feats=4, seed=3):
    rng = np.random.RandomState(seed)
    x = rng.rand(rows, feats).astype(np.float32)
    y = (rng.rand(rows) > 0.5).astype(np.float32)
    return [{"data": x, "label": y}]


_TINY_PARAMS = {"objective": "binary:logistic", "max_depth": 2,
                "eval_metric": ["logloss"]}


def test_registry_capture_gating():
    shards = _tiny_shards()
    progreg.clear()
    eng = TpuEngine(shards, parse_params(_TINY_PARAMS), num_actors=4)
    eng.build_programs()
    assert progreg.records() == []  # capture off: production pays nothing
    with progreg.capture():
        progreg.clear()
        eng2 = TpuEngine(shards, parse_params(_TINY_PARAMS), num_actors=4)
        eng2.build_programs()
        names = {r.name for r in progreg.records()}
    progreg.clear()
    assert "engine.step" in names and "engine.sketch_cuts" in names


def test_growback_same_record_same_fingerprint():
    """The elastic no-silent-recompile pin: (a) ``reset_from_booster`` — the
    engine-cache grow-back path — reuses the SAME compiled step program
    object, and (b) rebuilding the same config over the same shard layout
    re-registers into the SAME registry record (registrations bump, no new
    key) whose abstract re-trace yields the IDENTICAL fingerprint."""
    shards = _tiny_shards()
    with progreg.capture():
        progreg.clear()
        eng = TpuEngine(shards, parse_params(_TINY_PARAMS), num_actors=4)
        eng.step(0)
        rec1 = [r for r in progreg.records() if r.name == "engine.step"]
        assert len(rec1) == 1
        fp1 = walker.trace_record(rec1[0]).fingerprint
        assert fp1 and not fp1.startswith("trace-error")

        # (a) in-place grow-back: compiled program survives the reset
        step_fn = eng._step_fn
        eng.reset_from_booster(shards, [], eng.get_booster())
        assert eng._step_fn is step_fn
        eng.step(1)  # still dispatches (and re-registers nothing new)

        # (b) cache-miss rebuild of the same world: dedupes onto the record
        eng2 = TpuEngine(shards, parse_params(_TINY_PARAMS), num_actors=4)
        eng2.build_programs()
        rec2 = [r for r in progreg.records() if r.name == "engine.step"]
        assert len(rec2) == 1 and rec2[0].registrations >= 2
        assert walker.trace_record(rec2[0]).fingerprint == fp1
    progreg.clear()


def test_quick_matrix_ships_clean():
    """Tier-1 gate: the shipped package's programs verify clean over the
    quick matrix (depthwise f32 + int8, worlds 2 and 4)."""
    traced = trace_matrix(quick=True)
    assert traced and all(t.ok for t in traced), [t.error for t in traced]
    findings = checks.run_checks(traced, MESH_AXES, root=catalog.REPO_ROOT)
    assert findings == [], [f.render() for f in findings]
    # guard against a vacuous pass: the VER001 grouping must actually see
    # multiple worlds of the same config, and VER004 must see int8 programs
    worlds = {t.record.meta["world"] for t in traced
              if t.record.name == "engine.step"}
    assert {2, 4} <= worlds
    assert any(t.record.meta.get("hist_quant") == "int8" for t in traced)
    # and the int8 rows really carry the narrow wire the check certifies
    int8_steps = [t for t in traced
                  if t.record.name == "engine.step"
                  and t.record.meta.get("hist_quant") == "int8"]
    assert int8_steps
    for t in int8_steps:
        assert any(c.prim == "all_to_all" and c.dtype == "int8"
                   for c in t.analysis.collectives)
    # the gh_precision rows really carry the quantized gradient plane the
    # VER004 gh sub-checks certify: int8 avals present, and the histogram
    # merge is the exact int32 psum (not a silent f32 upcast)
    int8gh_steps = [t for t in traced
                    if t.record.name == "engine.step"
                    and t.record.meta.get("gh_precision") == "int8"]
    assert int8gh_steps
    for t in int8gh_steps:
        assert "int8" in t.analysis.dtypes
        assert any(c.prim == "psum" and c.dtype == "int32"
                   and len(c.shape) >= 4
                   for c in t.analysis.collectives)
        assert not any(c.prim == "psum" and c.dtype == "float32"
                       and len(c.shape) >= 4
                       for c in t.analysis.collectives)


# ---------------------------------------------------------------------------
# RXGB_STRICT runtime transfer guard (the SYNC001 runtime counterpart)
# ---------------------------------------------------------------------------

@pytest.mark.parametrize("booster", ["gbtree", "dart"])
def test_strict_guard_clean_training(monkeypatch, booster):
    # dart pins the per-round scalar uploads (drop weights, tree index)
    # being built BEFORE the guard — they are legitimate dispatch inputs,
    # not smuggled syncs
    monkeypatch.setenv("RXGB_STRICT", "1")
    shards = _tiny_shards()
    params = parse_params({**_TINY_PARAMS, "booster": booster})
    eng = TpuEngine(shards, params, num_actors=4,
                    **({"total_rounds": 3} if booster == "dart" else {}))
    for i in range(3):  # cold compile + two guarded warm rounds
        eng.step(i)
    pred = eng.get_booster().predict(shards[0]["data"])
    assert np.all(np.isfinite(pred))


def test_strict_guard_trips_on_planted_host_sync(monkeypatch):
    """A smuggled host round-trip in the round dispatch (read a device
    value to host, feed the host copy back) must raise under RXGB_STRICT=1
    on the warm path — and pass silently without the knob (the bug class
    this guards: every round quietly re-uploading, serializing the
    pipeline)."""
    shards = _tiny_shards()
    eng = TpuEngine(shards, parse_params(_TINY_PARAMS), num_actors=4)
    eng.step(0)  # warm: arms the guard for subsequent dispatches

    real_fn = eng._step_fn

    def smuggled(*args):
        args = list(args)
        args[4] = np.asarray(args[4])  # .item()-style host read of margins
        return real_fn(*args)  # ...fed back: an implicit re-upload per round

    eng._step_fn = smuggled
    monkeypatch.delenv("RXGB_STRICT", raising=False)
    eng.step(1)  # without the knob the sync passes silently
    monkeypatch.setenv("RXGB_STRICT", "1")
    with pytest.raises(Exception, match="[Dd]isallowed.*transfer"):
        eng.step(2)
    eng._step_fn = real_fn
    eng.step(2)  # un-smuggled engine recovers under the same knob


# ---------------------------------------------------------------------------
# SARIF output (golden-file + CLI)
# ---------------------------------------------------------------------------

_GOLDEN = os.path.join(os.path.dirname(__file__), "goldens",
                       "sarif_golden.json")


def test_sarif_golden_file():
    """Byte-stable SARIF shape shared by both tools: serialization drift
    (key order, schema uri, location shape) breaks annotation consumers
    silently, so the exact document is pinned."""
    from tools.sarif import to_sarif_json

    doc = to_sarif_json(
        "rxgbverify",
        {"VER001": "schedule mismatch", "VER004": "precision flow"},
        [
            {"rule": "VER004", "message": "upcast before the wire",
             "path": "xgboost_ray_tpu/engine.py", "line": 42},
            {"rule": "XXX999", "message": "unknown rule keeps no index",
             "path": "a.py", "line": 0, "level": "warning"},
        ],
    )
    with open(_GOLDEN) as fh:
        assert json.loads(doc) == json.load(fh)
        fh.seek(0)
        assert doc + "\n" == fh.read()  # byte-for-byte, trailing newline


def test_rxgblint_cli_sarif(tmp_path):
    from tools.rxgblint.__main__ import main as lint_main

    bad = tmp_path / "bad.py"
    bad.write_text(
        "import jax\n\n"
        "def f(rank, h):\n"
        "    if rank == 0:\n"
        "        return jax.lax.psum(h, 'actors')\n"
        "    return h\n"
    )
    out = tmp_path / "out.sarif"
    rc = lint_main([str(bad), "--baseline", "", "--sarif", str(out)])
    assert rc == 1
    doc = json.loads(out.read_text())
    assert doc["version"] == "2.1.0"
    run = doc["runs"][0]
    assert run["tool"]["driver"]["name"] == "rxgblint"
    results = run["results"]
    assert results and results[0]["ruleId"] == "SPMD001"
    loc = results[0]["locations"][0]["physicalLocation"]
    assert loc["artifactLocation"]["uri"].endswith("bad.py")
    assert loc["region"]["startLine"] == 5


def test_rxgbverify_cli_quick(tmp_path):
    """End-to-end CLI over the quick matrix: exit 0, JSON artifact carries
    fingerprints + collectives per program, SARIF is empty-but-valid."""
    from tools.rxgbverify.__main__ import main as verify_main

    j = tmp_path / "v.json"
    s = tmp_path / "v.sarif"
    fp = tmp_path / "fp.json"
    rc = verify_main(["--quick", "--json", str(j), "--sarif", str(s),
                      "--fingerprints", str(fp)])
    assert rc == 0
    doc = json.loads(j.read_text())
    assert doc["tool"] == "rxgbverify" and doc["findings"] == []
    assert doc["programs"]
    for entry in doc["programs"].values():
        assert entry["fingerprint"]
    fps = json.loads(fp.read_text())["programs"]
    assert set(fps) == set(doc["programs"])
    sarif_doc = json.loads(s.read_text())
    assert sarif_doc["runs"][0]["results"] == []
