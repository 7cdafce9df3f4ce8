"""Paths that need the chip fail without one, and the runtime seam
(``cpzk_tpu/jaxrt.py``) states what the process got."""

import os
import shutil
import subprocess
import sys

import jax

from cpzk_tpu import jaxrt

REPO = os.path.dirname(os.path.dirname(os.path.abspath(__file__)))


def _child_env(**extra):
    env = dict(os.environ, JAX_PLATFORMS="cpu", CPZK_NO_NATIVE_BUILD="1")
    env.pop("CPZK_BENCH_PLATFORM", None)
    env.update(extra)
    return env


def test_chip_smoke_alone_fails_without_an_ok_line(tmp_path):
    shutil.copy(os.path.join(REPO, "chip_smoke.py"), tmp_path)
    proc = subprocess.run(
        [sys.executable, "chip_smoke.py"], cwd=tmp_path, env=_child_env(),
        capture_output=True, text=True, timeout=60,
    )
    assert proc.returncode != 0
    assert '"ok"' not in proc.stdout
    assert "cpzk_tpu" in proc.stderr


def test_bench_without_a_tpu_fails(tmp_path):
    proc = subprocess.run(
        [sys.executable, os.path.join(REPO, "bench.py")], cwd=tmp_path,
        env=_child_env(JAX_COMPILATION_CACHE_DIR=str(tmp_path / "cache")),
        capture_output=True, text=True, timeout=120,
    )
    assert proc.returncode != 0
    assert proc.stdout == ""
    assert "no TPU found" in proc.stderr


def test_compile_cache_env_wins(monkeypatch, tmp_path):
    before = jax.config.jax_compilation_cache_dir
    monkeypatch.setenv(jaxrt.CACHE_ENV, str(tmp_path))
    assert jaxrt.enable_compile_cache() == str(tmp_path)
    assert jax.config.jax_compilation_cache_dir == before  # jax reads the env


def test_compile_cache_defaults_to_the_checkout(monkeypatch):
    monkeypatch.delenv(jaxrt.CACHE_ENV, raising=False)
    path = jaxrt.enable_compile_cache()
    assert path == os.path.join(REPO, ".jax_cache")
    assert jax.config.jax_compilation_cache_dir == path


def test_compile_cache_writes_only_under_the_env_dir(tmp_path):
    code = (
        "import jax; from cpzk_tpu import jaxrt; jaxrt.enable_compile_cache();"
        "print(jax.jit(lambda x: x * 3 + 1)(2))"
    )
    cache = tmp_path / "cache"
    proc = subprocess.run(
        [sys.executable, "-c", code], cwd=REPO, capture_output=True,
        text=True, timeout=120,
        env=_child_env(JAX_COMPILATION_CACHE_DIR=str(cache),
                       JAX_PERSISTENT_CACHE_MIN_COMPILE_TIME_SECS="0"),
    )
    assert proc.returncode == 0, proc.stderr
    assert any(cache.iterdir())


def test_describe_states_the_cpu_backend():
    d = jaxrt.describe()
    assert d["platform"] == "cpu"
    assert d["count"] == jax.device_count()
    assert isinstance(d["native"], bool)
    assert jaxrt.memory() == []  # XLA CPU keeps no allocator stats


def test_daemon_device_status_per_backend():
    from cpzk_tpu.server.__main__ import device_status
    from cpzk_tpu.server.config import ServerConfig

    cfg = ServerConfig()
    assert device_status(cfg)["platform"] is None  # inline CPU path: no jax
    cfg.tpu.backend = "tpu"
    status = device_status(cfg)
    assert status["platform"] == "cpu" and status["memory"] == []
