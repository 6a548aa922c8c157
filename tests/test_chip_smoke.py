"""``chip_smoke.py`` off the chip: it must refuse a CPU unless asked to
rehearse, the rehearsal must run every step, and the compile cache must be
placeable from outside.
"""

import json
import os
import subprocess
import sys

from xgboost_ray_tpu.util import compile_cache_dir

_ROOT = os.path.dirname(os.path.dirname(os.path.abspath(__file__)))
_SMOKE = os.path.join(_ROOT, "chip_smoke.py")


def _run(args, cache_dir, **env_extra):
    env = dict(os.environ, JAX_PLATFORMS="cpu",
               JAX_COMPILATION_CACHE_DIR=str(cache_dir), **env_extra)
    env.pop("XLA_FLAGS", None)  # one CPU device, like a one-chip machine
    env.pop("PYTEST_CURRENT_TEST", None)
    return subprocess.run(
        [sys.executable, _SMOKE, *args], cwd=_ROOT, env=env,
        capture_output=True, text=True, timeout=600,
    )


def test_no_chip_fails_before_compiling(tmp_path):
    res = _run([], tmp_path / "cache", JAX_LOG_COMPILES="1")
    assert res.returncode not in (0, 1), res
    assert "platform 'cpu'" in res.stderr
    # no result line, and nothing was compiled or cached on the way out
    assert res.stdout.strip() == ""
    assert "Compiling" not in res.stderr
    assert not (tmp_path / "cache").exists()


def test_cpu_rehearsal_runs_every_step(tmp_path):
    res = _run(["--rehearse-cpu", "--rows", "20000"], tmp_path / "cache")
    assert res.returncode == 0, res.stderr[-3000:]
    lines = res.stdout.strip().splitlines()
    # the last line is the chip check's result line: exactly these keys
    assert json.loads(lines[-1]) == {
        "ok": True,
        "device": {"platform": "cpu", "kind": "cpu", "count": 1},
    }
    # the line before it carries the steps and observations
    tag = "[smoke] summary "
    assert lines[-2].startswith(tag)
    doc = json.loads(lines[-2][len(tag):])
    assert doc["failed_step"] is None
    assert list(doc["steps"]) == [
        "data", "train_batched", "mesh", "train_per_round", "predict",
        "serve", "histogram",
    ]
    assert all(step["ok"] for step in doc["steps"].values())
    # the externally placed cache was used, not the in-checkout default
    assert doc["observations"]["device"]["compile_cache"] == str(
        tmp_path / "cache"
    )
    assert os.listdir(tmp_path / "cache")


def test_compile_cache_dir_env_wins_else_fixed_default(monkeypatch):
    monkeypatch.setenv("JAX_COMPILATION_CACHE_DIR", "/some/dir")
    assert compile_cache_dir() == "/some/dir"
    monkeypatch.delenv("JAX_COMPILATION_CACHE_DIR")
    assert compile_cache_dir() == os.path.join(_ROOT, ".jax_cache")
