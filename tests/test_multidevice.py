"""Multi-device integration tests, each in a subprocess with 8 forced host
devices (the main pytest process must keep jax at 1 device for the smoke tests).

  check_step_simple      — mesh train step == explicit M-worker oracle (bitwise);
                           EF server; tau=2 local updates.
  check_step_streamed    — streamed(FSDP) == simple (bitwise); EF; shard check.
  check_wires            — all three vote wires bitwise-equal to the vote_psum
                           stream, simple AND streamed, jnp AND interpret.
  check_fault_tolerance  — crash/restart bitwise replay; elastic mesh restore;
                           elastic-participation parity (weighted vote at full
                           participation == legacy, every wire mode, both
                           backends); chaos (50% per-round report dropout on
                           every gather wire); M-invariance of the normalized
                           vote (4- vs 2-worker fleets on identical data).
"""

import concurrent.futures

import pytest

from conftest import run_mdev as _run

#: test -> the check processes whose output it reads: (script, args, timeout)
RUNS = {
    "test_simple_step_equivalence_and_variants": [("check_step_simple.py", (), 1200)],
    "test_streamed_step_equivalence": [("check_step_streamed.py", (), 1200)],
    "test_wire_equivalence_all_modes": [("check_wires.py", ("simple",), 2400),
                                        ("check_wires.py", ("streamed",), 2400)],
    "test_fault_tolerance_and_elastic": [("check_fault_tolerance.py", (), 1200)],
}


@pytest.fixture(scope="module")
def mdev(request):
    """Starts the check processes of every selected test of this file at once
    (they are independent), so the file takes about as long as the slowest
    check rather than the sum; each test then reads its own outputs."""
    selected = {item.name for item in request.session.items
                if item.module is request.module}
    runs = [(name, run) for name, rs in RUNS.items() if name in selected for run in rs]
    with concurrent.futures.ThreadPoolExecutor(max_workers=max(1, len(runs))) as pool:
        futures = {}
        for name, (script, args, timeout) in runs:
            futures.setdefault(name, []).append(pool.submit(_run, script, timeout, args))
        yield futures


def _output(mdev, request) -> str:
    return "".join(f.result() for f in mdev[request.node.name])


@pytest.mark.slow
def test_simple_step_equivalence_and_variants(mdev, request):
    out = _output(mdev, request)
    assert "OK simple-step == 4-worker oracle" in out
    assert "OK engine interpret backend == pre-refactor oracle" in out
    assert "OK EF server" in out
    assert "OK local-update (tau=2)" in out


@pytest.mark.slow
def test_streamed_step_equivalence(mdev, request):
    out = _output(mdev, request)
    assert "0/" in out and "coords differ" in out
    assert "OK FSDP sharding" in out
    assert "OK streamed EF" in out


@pytest.mark.slow
def test_wire_equivalence_all_modes(mdev, request):
    out = _output(mdev, request)
    assert "OK simple-mode wires bitwise-equal (3 wires x 2 backends)" in out
    assert "OK streamed-mode wires bitwise-equal (3 wires x 2 backends)" in out


@pytest.mark.slow
def test_fault_tolerance_and_elastic(mdev, request):
    out = _output(mdev, request)
    assert "OK crash/restart" in out
    assert "OK elastic" in out
    for tag in ("votes/psum", "votes/gather", "pack8/gather", "decoded/psum"):
        assert f"OK elastic parity {tag}" in out
    for tag in ("votes/gather", "pack8/gather", "golomb/gather"):
        assert f"OK chaos {tag}" in out
    assert out.count("OK M-invariance") == 2
