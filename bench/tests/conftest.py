"""Tests of the benchmark itself, on the CPU at small sizes:

    JAX_PLATFORMS=cpu python -m pytest -q bench/tests

Two virtual CPU devices stand in for two workers."""

import os
import pathlib
import sys

os.environ.setdefault("JAX_PLATFORMS", "cpu")
os.environ["XLA_FLAGS"] = (os.environ.get("XLA_FLAGS", "")
                           + " --xla_force_host_platform_device_count=2")

BENCH = pathlib.Path(__file__).resolve().parents[1]
sys.path.insert(0, str(BENCH.parent / "src"))
sys.path.insert(0, str(BENCH))
