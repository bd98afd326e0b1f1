"""A run with the timed path broken underneath must come out not correct.

The harness runs as on the chip, without the look for a TPU, at a small
size on the CPU in float32, where the trainer and the reference agree to
about 1e-7 in the loss and a coordinate or two in the votes; so the
limits here are 1e-3. Each fault a training cell can have
is put in the trainer's place: a step that returns its state unchanged,
and half of the batch left out."""

import time

import jax
import pytest

import harness
import small

LIMITS = {"loss_gap_step0": 1e-3, "loss_gap_step1": 1e-3, "loss_gap_step2": 1e-3,
          "first_update_gap": 1e-3, "first_flip_share": 1e-3, "change_gap": 1e-3}
SEED = 2**31 + 12345


def run(workers=1, wrapper=None):
    cell = small.small_cell(workers=workers, limits=LIMITS)
    cell.traffic["method"]["budget"] = 100.0   # a few percent of coordinates move
    return harness.run(cell, [], SEED, 0.5, False, time.perf_counter(),
                       platform="cpu", step_wrapper=wrapper)


def unchanged(step):
    def f(state, batch):
        _, metrics = step(jax.tree_util.tree_map(lambda x: x.copy(), state), batch)
        return state, metrics
    return f


def half_batch(step):
    def f(state, batch):
        n = batch["inputs"].shape[0] // 2
        return step(state, {k: v[:n] for k, v in batch.items()})
    return f


def test_sound_run_is_correct():
    r = run()
    assert r["correct"], r["compared"]
    assert r["failed"] == 0 and r["attempted"] > 0


@pytest.mark.parametrize("fault", [unchanged, half_batch])
def test_fault_is_not_correct(fault):
    r = run(wrapper=fault)
    assert not r["correct"], r["compared"]
