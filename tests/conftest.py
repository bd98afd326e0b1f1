"""Test-session guards.

The dry-run forces 512 host devices via XLA_FLAGS — that env var must NEVER be
set here: smoke tests and benches are written for the default 1-device CPU
client, and multi-device suites spawn their own subprocesses with their own
flags (tests/mdev/*).

If `hypothesis` is not installed (the pinned container has no network), a
deterministic stub (tests/_hypothesis_stub.py) is registered so the property
tests still collect and run over a fixed sample. CI installs the real engine
from requirements-dev.txt and never hits the stub.
"""

import importlib.util
import os
import pathlib
import subprocess
import sys

# Fail fast if a stray XLA_FLAGS from a dry-run shell would skew every test.
_flags = os.environ.get("XLA_FLAGS", "")
if "xla_force_host_platform_device_count" in _flags:
    raise RuntimeError(
        "XLA_FLAGS forces a host device count; unset it before running pytest "
        "(the multi-device tests manage their own subprocess flags)")

MDEV_DIR = pathlib.Path(__file__).parent / "mdev"
SRC_DIR = str(pathlib.Path(__file__).parents[1] / "src")


def run_mdev(script: str, timeout: int = 1200, args=()) -> str:
    """Run a tests/mdev/ check in a subprocess (own XLA_FLAGS / device count)
    and return its stdout; asserts a zero exit. The child is held to the CPU:
    it must not reach for an accelerator another process may hold."""
    proc = subprocess.run(
        [sys.executable, str(MDEV_DIR / script), *args],
        capture_output=True, text=True, timeout=timeout,
        env={"PYTHONPATH": SRC_DIR,
             "PATH": os.environ.get("PATH", "/usr/bin:/bin"),
             "JAX_PLATFORMS": "cpu",
             "HOME": os.environ.get("HOME", "/root")},
    )
    assert proc.returncode == 0, \
        f"{script} failed:\n{proc.stdout[-3000:]}\n{proc.stderr[-3000:]}"
    return proc.stdout


try:
    import hypothesis  # noqa: F401  — prefer the real engine when present
except ImportError:
    _spec = importlib.util.spec_from_file_location(
        "hypothesis", pathlib.Path(__file__).parent / "_hypothesis_stub.py")
    _stub = importlib.util.module_from_spec(_spec)
    _spec.loader.exec_module(_stub)
    sys.modules["hypothesis"] = _stub
    sys.modules["hypothesis.strategies"] = _stub.strategies
