"""Shared benchmark plumbing: CSV emission (name,us_per_call,derived),
subprocess running for benches that need multiple host devices, and the
run-metadata stamp every emitted ``BENCH_*.json`` carries (commit SHA,
timestamp, machine fingerprint, repeat count) so the bench-history
sentinel can join runs across commits and keep noise bands per-machine."""

from __future__ import annotations

import hashlib
import json
import os
import platform
import subprocess
import sys
import time

REPO = os.path.dirname(os.path.dirname(os.path.abspath(__file__)))
ART = os.path.join(REPO, "artifacts")

#: repeat count the emitters report in their stamp (env-overridable so a
#: CI matrix leg that runs each bench N times can say so).
DEFAULT_REPEATS = int(os.environ.get("REPRO_BENCH_REPEATS", "1"))


def git_commit() -> str:
    """Current commit SHA — CI env vars first (works in shallow/exported
    checkouts), then git, else ""."""
    for var in ("REPRO_BENCH_COMMIT", "GITHUB_SHA"):
        sha = os.environ.get(var)
        if sha:
            return sha
    try:
        out = subprocess.run(["git", "rev-parse", "HEAD"], cwd=REPO,
                             capture_output=True, text=True, timeout=10)
        if out.returncode == 0:
            return out.stdout.strip()
    except OSError:
        pass
    return ""


def machine_fingerprint() -> str:
    """Short stable id of the *host* running the benches (distinct from
    the model's ``Machine.fingerprint()``, which names a calibrated
    profile).  Same host + toolchain -> same id; history noise bands are
    only computed within one id.  ``REPRO_BENCH_FINGERPRINT`` overrides
    (CI sets one per runner class)."""
    env = os.environ.get("REPRO_BENCH_FINGERPRINT")
    if env:
        return env
    blob = "|".join([
        platform.machine(), platform.system(), platform.processor(),
        str(os.cpu_count()), platform.python_version(),
    ])
    return hashlib.sha256(blob.encode()).hexdigest()[:12]


def run_meta(repeats: int = DEFAULT_REPEATS) -> dict:
    """The ``_meta`` stamp written into every bench JSON."""
    return {
        "commit": git_commit(),
        "timestamp": time.time(),
        "fingerprint": machine_fingerprint(),
        "repeats": int(repeats),
        "python": platform.python_version(),
        "platform": f"{platform.system()}-{platform.machine()}",
    }


def emit(name: str, us_per_call: float, derived: str = ""):
    print(f"{name},{us_per_call:.3f},{derived}", flush=True)


class BenchSkipped(Exception):
    """A bench that cannot run in this process; the message says why."""


def run_subprocess_bench(module: str, n_devices: int = 8,
                         timeout: int = 560) -> dict:
    """Run `python -m {module}` with forced host devices; the module prints
    a single JSON object on its last stdout line.

    These benches are CPU rehearsals.  On an accelerator host the calling
    process holds the chip, and a child that starts JAX there cannot open
    it (one process per chip), so the bench is skipped instead."""
    import jax

    backend = jax.default_backend()
    if backend != "cpu":
        raise BenchSkipped(
            f"{module} runs on forced CPU host devices in a child process; "
            f"this process holds the {backend} (one process per chip)")
    env = dict(os.environ)
    env["XLA_FLAGS"] = (env.get("XLA_FLAGS", "") +
                        f" --xla_force_host_platform_device_count={n_devices}").strip()
    env["PYTHONPATH"] = os.path.join(REPO, "src") + os.pathsep + \
        env.get("PYTHONPATH", "")
    out = subprocess.run([sys.executable, "-m", module], env=env, cwd=REPO,
                         capture_output=True, text=True, timeout=timeout)
    if out.returncode != 0:
        raise RuntimeError(f"{module} failed:\n{out.stderr[-2000:]}")
    return json.loads(out.stdout.strip().splitlines()[-1])


class Timer:
    def __enter__(self):
        self.t0 = time.perf_counter()
        return self

    def __exit__(self, *a):
        self.seconds = time.perf_counter() - self.t0
