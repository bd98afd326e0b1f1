"""Vote-collective equivalence on 8 forced host devices.

Properties (the paper's server sum must not depend on HOW it is carried):
  1. vote_allgather_packed(v) == vote_psum(v)  on a (4 data, 2 model) mesh,
  2. vote_psum_hier == vote_psum               on a (2 pod, 2 data, 2 model) mesh,
  3. both equal a numpy per-worker oracle sum,
  4. worker_index/worker_count enumerate [0, M) in mesh row-major order.
"""
import os
os.environ["XLA_FLAGS"] = "--xla_force_host_platform_device_count=8"

import jax
import jax.numpy as jnp
import numpy as np
from jax.sharding import PartitionSpec as P

from repro.dist import collectives
from repro.launch.mesh import make_mesh

SHAPE = (3, 257)  # deliberately unaligned with the pack2bit canonical view


def worker_votes(n_workers, seed=0):
    rng = np.random.RandomState(seed)
    return rng.randint(-1, 2, (n_workers,) + SHAPE).astype(np.int8)


def main():
    assert jax.device_count() == 8, jax.device_count()

    # ---- flat mesh: psum vs packed all-gather vs oracle --------------------
    mesh = make_mesh((4, 2), ("data", "model"))
    votes = worker_votes(4, seed=1)
    stacked = jnp.asarray(votes.reshape(4 * SHAPE[0], SHAPE[1]))

    def body(v):
        n = collectives.worker_count(("data",))
        assert n == 4
        a = collectives.vote_psum(v, ("data",), n)
        b = collectives.vote_allgather_packed(v, ("data",), n)
        i = collectives.worker_index(("data",))
        gi = jax.lax.all_gather(i, ("data",), axis=0)
        return a.astype(jnp.int32), b.astype(jnp.int32), gi

    step = jax.jit(jax.shard_map(
        body, mesh=mesh,
        in_specs=P("data"),
        out_specs=(P(), P(), P()),
        axis_names={"data"}, check_vma=False))
    a, b, gi = step(stacked)
    oracle = votes.astype(np.int32).sum(0)
    assert np.array_equal(np.asarray(a), oracle), "psum != oracle"
    assert np.array_equal(np.asarray(b), oracle), "allgather_packed != oracle"
    assert sorted(np.asarray(gi).tolist()) == [0, 1, 2, 3], np.asarray(gi)
    print("OK vote_psum == vote_allgather_packed == oracle (4 workers)")

    # ---- hierarchical mesh: two-level psum vs flat -------------------------
    mesh3 = make_mesh((2, 2, 2), ("pod", "data", "model"))
    votes8 = worker_votes(4, seed=2)  # 4 workers = pod x data
    stacked8 = jnp.asarray(votes8.reshape(4 * SHAPE[0], SHAPE[1]))

    def body3(v):
        axes = ("pod", "data")
        n = collectives.worker_count(axes)
        assert n == 4
        flat = collectives.vote_psum(v, axes, n)
        hier = collectives.vote_psum_hier(
            v, "data", "pod",
            jax.lax.axis_size("data"), jax.lax.axis_size("pod"))
        packed = collectives.vote_allgather_packed(v, axes, n)
        idx = collectives.worker_index(axes)
        gi = jax.lax.all_gather(idx, axes, axis=0)
        return (flat.astype(jnp.int32), hier.astype(jnp.int32),
                packed.astype(jnp.int32), gi)

    step3 = jax.jit(jax.shard_map(
        body3, mesh=mesh3,
        in_specs=P(("pod", "data")),
        out_specs=(P(), P(), P(), P()),
        axis_names={"pod", "data"}, check_vma=False))
    flat, hier, packed, gi = step3(stacked8)
    oracle8 = votes8.astype(np.int32).sum(0)
    assert np.array_equal(np.asarray(flat), oracle8), "flat psum != oracle"
    assert np.array_equal(np.asarray(hier), np.asarray(flat)), "hier != flat"
    assert np.array_equal(np.asarray(packed), np.asarray(flat)), "packed != flat"
    assert sorted(np.asarray(gi).tolist()) == [0, 1, 2, 3], np.asarray(gi)
    print("OK vote_psum_hier == vote_psum == packed (2x2 pod/data workers)")


if __name__ == "__main__":
    main()
