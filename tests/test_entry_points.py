"""Entry-point rules: the chip smoke test never runs off the TPU, the
persistent compilation cache lands where it is told and nowhere else, and
the benchmark runner starts no JAX child on an accelerator host."""

import json
import os
import subprocess
import sys

import pytest

HERE = os.path.dirname(os.path.abspath(__file__))
REPO = os.path.dirname(HERE)


def _env(**extra):
    env = dict(os.environ)
    env["JAX_PLATFORMS"] = "cpu"
    env["PYTHONPATH"] = os.path.join(REPO, "src")
    env.pop("JAX_COMPILATION_CACHE_DIR", None)
    env.update(extra)
    return env


def test_chip_smoke_refuses_cpu():
    out = subprocess.run([sys.executable, os.path.join(REPO, "chip_smoke.py")],
                         env=_env(), cwd=REPO, capture_output=True,
                         text=True, timeout=120)
    assert out.returncode != 0
    assert out.stdout.strip() == ""           # no phase ran, no result line
    assert "no TPU" in out.stderr


class TestCompileCache:
    def test_default_is_fixed_repo_path(self, monkeypatch):
        from repro.compile_cache import cache_dir
        monkeypatch.delenv("JAX_COMPILATION_CACHE_DIR", raising=False)
        assert cache_dir() == os.path.join(REPO, "artifacts", "jax_cache")

    def test_env_dir_is_used_and_only_it(self, tmp_path):
        """A compile after ``enable_compile_cache`` writes its entry into
        ``JAX_COMPILATION_CACHE_DIR``, and JAX is pointed at no other."""
        code = (
            "import jax, jax.numpy as jnp, json\n"
            "from repro.compile_cache import enable_compile_cache\n"
            "path = enable_compile_cache()\n"
            "jax.config.update("
            "'jax_persistent_cache_min_compile_time_secs', 0)\n"
            "jax.jit(lambda x: jnp.sin(x) @ x)(jnp.ones((64, 64)))"
            ".block_until_ready()\n"
            "print(json.dumps([path,"
            " jax.config.jax_compilation_cache_dir]))\n")
        cache = tmp_path / "cache"
        out = subprocess.run(
            [sys.executable, "-c", code], capture_output=True, text=True,
            env=_env(JAX_COMPILATION_CACHE_DIR=str(cache)), timeout=120)
        assert out.returncode == 0, out.stderr[-2000:]
        path, configured = json.loads(out.stdout.strip().splitlines()[-1])
        assert path == configured == str(cache)
        assert any(cache.iterdir())


def test_benchmark_child_skipped_on_accelerator(monkeypatch):
    """On a host whose JAX backend is an accelerator, this process holds
    the chip: the forced-host-device child bench is skipped, not run."""
    import jax

    monkeypatch.syspath_prepend(REPO)
    from benchmarks import common

    def no_child(*a, **kw):
        raise AssertionError("a JAX child process was started")

    monkeypatch.setattr(jax, "default_backend", lambda: "tpu")
    monkeypatch.setattr(common.subprocess, "run", no_child)
    with pytest.raises(common.BenchSkipped, match="one process per chip"):
        common.run_subprocess_bench("benchmarks.bench_tuner")
