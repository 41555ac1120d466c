"""The example server's entry point and the compile-cache placement it
shares with ``chip_smoke.py`` and ``benchmarks/run.py``."""
import os
import subprocess
import sys
from pathlib import Path

import jax
import pytest

from repro.data import mot
from repro.data.synthetic import SceneConfig, generate_scene
from repro.launch import compile_cache

ROOT = Path(__file__).resolve().parents[1]
NUM_SEQS = 20   # past TrackingService's default per-client bound of 16


@pytest.fixture(scope="module")
def served(tmp_path_factory):
    """One ``--serve`` run over NUM_SEQS short det files from one client,
    with the compile cache placed from outside."""
    tmp = tmp_path_factory.mktemp("serve_cli")
    dets = tmp / "dets"
    dets.mkdir()
    for i in range(NUM_SEQS):
        _, _, db, dm = generate_scene(SceneConfig(num_frames=6, max_objects=3,
                                                  seed=i))
        mot.write_det_file(dets / f"seq{i:02d}.txt", db, dm)
    env = {"PATH": os.environ.get("PATH", "/usr/bin:/bin"),
           "PYTHONPATH": str(ROOT / "src"), "JAX_PLATFORMS": "cpu",
           "JAX_COMPILATION_CACHE_DIR": str(tmp / "cache"),
           "JAX_PERSISTENT_CACHE_MIN_COMPILE_TIME_SECS": "0"}
    r = subprocess.run(
        [sys.executable, str(ROOT / "examples" / "tracking_service.py"),
         "--serve", "--det-dir", str(dets), "--out", str(tmp / "out"),
         "--lanes", "4", "--chunk", "4"],
        capture_output=True, text=True, timeout=600, env=env, cwd=tmp)
    return r, tmp


def test_serve_cli_fills_lanes_past_default_admission_bounds(served):
    r, tmp = served
    assert r.returncode == 0, r.stderr[-3000:]
    assert len(list((tmp / "out").glob("seq*.txt"))) == NUM_SEQS
    assert f"{NUM_SEQS} sequences" in r.stdout


def test_compile_cache_lands_in_the_env_dir(served):
    r, tmp = served
    assert r.returncode == 0, r.stderr[-3000:]
    assert any((tmp / "cache").iterdir())


def test_compile_cache_defaults_to_the_checkout(monkeypatch):
    from jax.experimental.compilation_cache import compilation_cache

    monkeypatch.delenv(compile_cache.ENV_VAR, raising=False)
    was = jax.config.jax_compilation_cache_dir
    try:
        assert compile_cache.enable_compile_cache() == str(ROOT / ".jax_cache")
        assert jax.config.jax_compilation_cache_dir == str(ROOT / ".jax_cache")
    finally:
        jax.config.update("jax_compilation_cache_dir", was)
        compilation_cache.reset_cache()
    assert ".jax_cache/" in (ROOT / ".gitignore").read_text().split()
