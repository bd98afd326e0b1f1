"""repro.dist — the distributed substrate.

Two modules:

- ``sharding``:    logical-axis -> mesh PartitionSpec rules (TP/EP/SP for
  params, activations, decode caches) and the placement sanitizer that keeps
  every spec divisible on the actual dims.
- ``collectives``: the worker-axis vote exchange — the paper's "M workers send
  ternary messages, the server sums" step, as shard_map collectives in three
  wire-equivalent variants (flat int psum, hierarchical pod/data psum,
  2-bit-packed all-gather).
"""

from repro.dist import collectives, sharding

__all__ = ["collectives", "sharding"]
