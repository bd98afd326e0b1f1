"""Wire-equivalence at the train-step level, on 8 forced host devices.

Mesh (2 pod, 2 data, 2 model), worker_axes=('pod','data') -> M=4 workers, so
all three wires are exercisable in one program. Property: the per-round param
update must not depend on HOW the vote sum is carried — for each mode
(simple, streamed) and each backend (jnp, interpret), the hier and
allgather_packed wires are bitwise-equal to the vote_psum stream of the SAME
mode+backend; and the interpret stream equals the jnp stream (engine
contract), so all 12 combinations collapse onto one oracle.

The packed wire runs the fused compress->pack2bit uplink kernels and the
fused unpack+accumulate decode on the interpret backend — this is the
acceptance check that the fused wire is bitwise-honest end-to-end.

Beyond sparsign, the non-sparsign ternary compressors run the same 3-wire x
2-backend sweep in simple mode: noisy_sign exercises the generic ternary
kernel template on the votes wire, terngrad exercises the scaled_votes wire
(magnitude-shared s_t pmax'd over ('pod','data'), ternary votes + one scalar
on the fabric, mean-server decode). Streamed mode runs the terngrad
scaled_votes sweep too — all four wire modes now run in both train modes.

qsgd8 (the FedCom 8-bit baseline) sweeps its two wires in BOTH modes: the
decoded fp32 psum (vote_impl=psum — the oracle stream) vs the pack8 gather
(vote_impl=allgather_packed: 1 B/coord int8 sign*level payloads + per-worker
f32 scales, fused dequantize-sum). Bitwise equality of a FLOAT sum across
wires holds because every implementation associates the adds in worker-index
order, which is also how the host-platform psum reduces; the pack8 kernel
rounds each decoded product through a VMEM scratch to pin the same rounding
points (see kernels/pack8). On a real TPU pod the psum association is the
runtime's choice, so there this check pins the gather wires against each
other rather than against psum.

sparsign_golomb sweeps the entropy-coded wire in BOTH modes: the int8 psum
(its fall-back wire, and the oracle stream) vs the Golomb/RLE coded gather
(vote_impl=allgather_packed: fused sparsign->coded-byte-stream uplink,
in-kernel decode-sum in strict worker order) — the acceptance check that the
sub-2-bit wire carries the exact same votes.

The ring-pipelined gather (ring_chunk_rows set on the allgather_packed
configs) re-runs the gather-wire streams with the payload chunked around the
M-hop ppermute ring instead of one monolithic all_gather. The integer wires
(pack2, golomb) accumulate int32 chunk sums — addition commutes exactly, so
the ring stream is BITWISE the monolithic one. The pack8 wire sums f32
dequantized chunks in ring-arrival order (self, rank-1, rank-2, ...), a
different association than the monolithic worker-order decode — the ring
stream is run-twice deterministic and allclose, not bitwise (same caveat
class as TPU psum association, see ROADMAP). Bucketed ring configs chunk the
multi-leaf bucket buffers, exercising genuinely multi-chunk rings at
RING_CHUNK_ROWS=32.
"""
import os
import sys
os.environ["XLA_FLAGS"] = "--xla_force_host_platform_device_count=8"

import jax
import jax.numpy as jnp
import numpy as np

from repro.launch.mesh import make_mesh
from repro.configs.registry import get_config
from repro.core.algorithm import CompressionConfig
from repro.core.budgets import BudgetConfig
from repro.models.model import Model
from repro.train.state import LrSchedule, init_state
from repro.train.step_simple import TrainStepConfig, build_train_step
from repro.train.step_streamed import (StreamedStepConfig,
                                       build_streamed_train_step,
                                       fsdp_param_shardings)

AXES = ("pod", "data")
WIRES = ("psum", "hier", "allgather_packed")
BACKENDS = ("jnp", "interpret")
PARTS = ("simple", "streamed")
RING_CHUNK_ROWS = 32   # smallest legal chunk -> forces multi-chunk rings on
                       # the bucketed plans (per-leaf smoke leaves fit in one)


def make_batch(cfg, b, s, key=0):
    rng = np.random.RandomState(key)
    return {
        "inputs": jnp.array(rng.randint(0, cfg.vocab_size, (b, s)), jnp.int32),
        "labels": jnp.array(rng.randint(0, cfg.vocab_size, (b, s)), jnp.int32),
        "positions": jnp.broadcast_to(jnp.arange(s), (b, s)).astype(jnp.int32),
    }


def flat_np(tree):
    return [np.asarray(x) for x in jax.tree_util.tree_leaves(
        jax.tree_util.tree_map(np.asarray, tree))]


def check_mode(mode, mesh, model, params, batch, comp, lr, wires=WIRES):
    ref, ref_label = None, None
    for backend in BACKENDS:
        for wire in wires:
            if mode == "simple":
                scfg = TrainStepConfig(compression=comp, lr=lr, worker_axes=AXES,
                                       vote_impl=wire, donate=False, backend=backend)
                step = build_train_step(model, scfg, mesh)
                state = init_state(params, server=comp.server, seed=42)
            else:
                scfg = StreamedStepConfig(compression=comp, lr=lr, worker_axes=AXES,
                                          fsdp_axis="data", vote_impl=wire,
                                          donate=False, backend=backend)
                step = build_streamed_train_step(model, scfg, mesh)
                state = init_state(params, server=comp.server, seed=42)
            with jax.sharding.set_mesh(mesh):
                out, metrics = step(state, batch)
            got = flat_np(out.params)
            label = f"{mode}/{wire}/{backend}"
            if ref is None:
                ref, ref_label = got, label
                print(f"  oracle stream: {label} "
                      f"(wire_bytes/device={float(metrics['wire_bytes_per_device']):.0f})")
                continue
            ndiff = sum(int((a != b).sum()) for a, b in zip(got, ref))
            assert ndiff == 0, f"{label} != {ref_label}: {ndiff} coords differ"
            print(f"  OK {label} == {ref_label} bitwise "
                  f"(wire_bytes/device={float(metrics['wire_bytes_per_device']):.0f})")


def _build(mode, mesh, model, comp, lr, backend, *, ring=None, bucketed=False):
    if mode == "simple":
        scfg = TrainStepConfig(compression=comp, lr=lr, worker_axes=AXES,
                               vote_impl="allgather_packed", donate=False,
                               backend=backend, bucketed=bucketed,
                               ring_chunk_rows=ring)
        return build_train_step(model, scfg, mesh)
    scfg = StreamedStepConfig(compression=comp, lr=lr, worker_axes=AXES,
                              fsdp_axis="data", vote_impl="allgather_packed",
                              donate=False, backend=backend, bucketed=bucketed,
                              ring_chunk_rows=ring)
    return build_streamed_train_step(model, scfg, mesh)


def check_ring(mode, mesh, model, params, batch, comp, lr, *,
               equality="bitwise", bucketed=False):
    """Ring-pipelined gather vs the monolithic all_gather, same mode+backend.

    equality="bitwise" for the integer wires (pack2, golomb: int32 chunk adds
    commute); "allclose" for pack8 (f32 sums associate in ring-arrival order
    — deterministic, pinned by a second execution, but not bitwise vs the
    worker-order monolithic decode)."""
    for backend in BACKENDS:
        outs = []
        for ring in (None, RING_CHUNK_ROWS):
            step = _build(mode, mesh, model, comp, lr, backend,
                          ring=ring, bucketed=bucketed)
            state = init_state(params, server=comp.server, seed=42)
            with jax.sharding.set_mesh(mesh):
                out, metrics = step(state, batch)
            if ring is not None:
                # run-twice determinism of the ring stream
                state2 = init_state(params, server=comp.server, seed=42)
                with jax.sharding.set_mesh(mesh):
                    out2, _ = step(state2, batch)
                nd = sum(int((a != b).sum()) for a, b in
                         zip(flat_np(out.params), flat_np(out2.params)))
                assert nd == 0, \
                    f"{mode}/ring/{backend} nondeterministic: {nd} coords"
            outs.append((flat_np(out.params), metrics))
        (mono, mm), (ringed, rm) = outs
        hbm = (float(mm["gather_hbm_bytes"]), float(rm["gather_hbm_bytes"]))
        assert hbm[1] <= hbm[0], hbm
        label = f"{mode}{'/bucketed' if bucketed else ''}/ring/{backend}"
        if equality == "bitwise":
            nd = sum(int((a != b).sum()) for a, b in zip(ringed, mono))
            assert nd == 0, f"{label} != monolithic: {nd} coords differ"
            rel = "bitwise =="
        else:
            for a, b in zip(ringed, mono):
                np.testing.assert_allclose(a, b, rtol=2e-5, atol=2e-6)
            rel = "allclose ~="
        print(f"  OK {label} {rel} monolithic "
              f"(gather_hbm {hbm[0]:.0f} -> {hbm[1]:.0f} B)")


def check_ring_permute(mesh):
    """ring_permute over the 2-axis worker group rotates the flat
    (row-major) worker ring by one."""
    from jax.sharding import PartitionSpec as P
    from repro.dist import collectives

    x = np.arange(4 * 8, dtype=np.int32).reshape(4, 8)
    expect = np.roll(x, 1, axis=0)   # worker w receives worker w-1's slice

    g = jax.jit(jax.shard_map(lambda v: collectives.ring_permute(v, AXES),
                              mesh=mesh, in_specs=P(AXES), out_specs=P(AXES),
                              axis_names=set(AXES), check_vma=False))
    with jax.sharding.set_mesh(mesh):
        np.testing.assert_array_equal(np.asarray(g(jnp.asarray(x))), expect)
    print("  OK ring_permute tuple-axis rotation")


def main(parts=PARTS):
    """Run the named halves (``simple``, ``streamed``); the test runs them as
    two processes side by side."""
    assert jax.device_count() == 8, jax.device_count()
    mesh = make_mesh((2, 2, 2), ("pod", "data", "model"))
    comp = CompressionConfig(compressor="sparsign",
                             budget=BudgetConfig(kind="fixed", value=2.0),
                             server="majority_vote")
    lr = LrSchedule(base=0.01)

    # qsgd8 on the pack8 wire vs its decoded-psum oracle stream (the FedCom
    # 8-bit baseline, Appendix B): vote_impl=psum negotiates the fp32 decoded
    # wire, allgather_packed the 1 B/coord pack8 gather — same round bitwise
    comp_q = CompressionConfig(compressor="qsgd8",
                               budget=BudgetConfig(kind="fixed", value=1.0),
                               server="mean")

    # sparsign_golomb: same Def. 1 compressor, entropy-coded uplink. The psum
    # wire negotiates plain int8 votes (a fabric psum cannot reduce
    # variable-length byte streams — engine.wire_payload_format's fallback)
    # and is the oracle stream; allgather_packed rides the Golomb/RLE coded
    # byte wire (fused sparsign->coded-stream kernel + in-kernel decode-sum
    # on the interpret backend). Bitwise equality across them is the
    # acceptance check that the sub-2-bit wire is lossless end-to-end.
    comp_g = CompressionConfig(
        compressor="sparsign_golomb",
        budget=BudgetConfig(kind="target_sparsity", value=0.05),
        server="majority_vote")

    if "simple" in parts:
        check_simple(mesh, comp, comp_q, comp_g, lr)
    if "streamed" in parts:
        check_streamed(mesh, comp, comp_q, comp_g, lr)


def check_simple(mesh, comp, comp_q, comp_g, lr):
    cfg_s = get_config("qwen1.5-4b", smoke=True)
    model_s = Model(cfg_s)
    params_s = model_s.init(jax.random.PRNGKey(0))
    print("simple mode (qwen1.5-4b smoke):")
    check_mode("simple", mesh, model_s, params_s, make_batch(cfg_s, 8, 16), comp, lr)
    print("OK simple-mode wires bitwise-equal (3 wires x 2 backends)")

    # non-sparsign ternary compressors: same wire-invariance sweep through the
    # generic ternary kernel template (simple mode)
    for name, server, value in (("noisy_sign", "majority_vote", 0.5),
                                ("terngrad", "mean", 1.0)):
        comp_n = CompressionConfig(compressor=name,
                                   budget=BudgetConfig(kind="fixed", value=value),
                                   server=server)
        print(f"simple mode ({name} / {server}):")
        check_mode("simple", mesh, model_s, params_s,
                   make_batch(cfg_s, 8, 16), comp_n, lr)
        print(f"OK {name} wires bitwise-equal (3 wires x 2 backends)")

    print("simple mode (qsgd8 / mean — decoded-psum oracle vs pack8 gather):")
    check_mode("simple", mesh, model_s, params_s, make_batch(cfg_s, 8, 16),
               comp_q, lr, wires=("psum", "allgather_packed"))
    print("OK qsgd8 pack8 wire bitwise-equal to the decoded psum (2 backends)")

    print("simple mode (sparsign_golomb — int8-psum oracle vs golomb gather):")
    check_mode("simple", mesh, model_s, params_s, make_batch(cfg_s, 8, 16),
               comp_g, lr, wires=("psum", "hier", "allgather_packed"))
    print("OK sparsign_golomb wires bitwise-equal (3 wires x 2 backends)")

    # ring-pipelined gather vs the monolithic all_gather (simple mode): the
    # integer wires pin bitwise, pack8 pins deterministic + allclose; the
    # bucketed variants chunk the multi-leaf bucket buffers (multi-chunk ring)
    print("ring_permute:")
    check_ring_permute(mesh)
    batch_s = make_batch(cfg_s, 8, 16)
    print("simple mode ring (sparsign pack2):")
    check_ring("simple", mesh, model_s, params_s, batch_s, comp, lr)
    check_ring("simple", mesh, model_s, params_s, batch_s, comp, lr,
               bucketed=True)
    print("simple mode ring (qsgd8 pack8):")
    check_ring("simple", mesh, model_s, params_s, batch_s, comp_q, lr,
               equality="allclose")
    check_ring("simple", mesh, model_s, params_s, batch_s, comp_q, lr,
               equality="allclose", bucketed=True)
    print("simple mode ring (sparsign_golomb):")
    check_ring("simple", mesh, model_s, params_s, batch_s, comp_g, lr)
    check_ring("simple", mesh, model_s, params_s, batch_s, comp_g, lr,
               bucketed=True)
    print("OK simple-mode ring == monolithic (3 wires x 2 backends, "
          "per-leaf + bucketed)")


def check_streamed(mesh, comp, comp_q, comp_g, lr):
    cfg_t = get_config("qwen2-moe-a2.7b", smoke=True)
    model_t = Model(cfg_t)
    params_t = model_t.init(jax.random.PRNGKey(0))
    shardings = fsdp_param_shardings(model_t, mesh, "data")
    params_t = jax.tree_util.tree_map(jax.device_put, params_t, shardings)
    print("streamed mode (qwen2-moe-a2.7b smoke, FSDP over data):")
    check_mode("streamed", mesh, model_t, params_t, make_batch(cfg_t, 8, 16), comp, lr)
    print("OK streamed-mode wires bitwise-equal (3 wires x 2 backends)")

    # streamed mode is no longer pinned to vote servers: the terngrad
    # scaled_votes wire (integer votes + ONE shared scale, mean decode on the
    # FSDP shard) and the qsgd8 pack8/decoded wires run the same sweeps
    comp_tg = CompressionConfig(compressor="terngrad",
                                budget=BudgetConfig(kind="fixed", value=1.0),
                                server="mean")
    print("streamed mode (terngrad / mean — scaled_votes):")
    check_mode("streamed", mesh, model_t, params_t, make_batch(cfg_t, 8, 16),
               comp_tg, lr)
    print("OK streamed terngrad scaled_votes wires bitwise-equal "
          "(3 wires x 2 backends)")

    print("streamed mode (qsgd8 / mean — decoded-psum oracle vs pack8 gather):")
    check_mode("streamed", mesh, model_t, params_t, make_batch(cfg_t, 8, 16),
               comp_q, lr, wires=("psum", "allgather_packed"))
    print("OK streamed qsgd8 pack8 wire bitwise-equal to the decoded psum "
          "(2 backends)")

    print("streamed mode (sparsign_golomb — int8-psum oracle vs golomb gather):")
    check_mode("streamed", mesh, model_t, params_t, make_batch(cfg_t, 8, 16),
               comp_g, lr, wires=("psum", "allgather_packed"))
    print("OK streamed sparsign_golomb golomb wire bitwise-equal to the int8 "
          "psum (2 backends)")

    # streamed-mode ring sweep (per-leaf, plus one bucketed double-buffered
    # config — the bucketed scan exchanges ride the same wire.exchange_bucket)
    batch_t = make_batch(cfg_t, 8, 16)
    print("streamed mode ring (sparsign pack2):")
    check_ring("streamed", mesh, model_t, params_t, batch_t, comp, lr)
    check_ring("streamed", mesh, model_t, params_t, batch_t, comp, lr,
               bucketed=True)
    print("streamed mode ring (qsgd8 pack8):")
    check_ring("streamed", mesh, model_t, params_t, batch_t, comp_q, lr,
               equality="allclose")
    print("streamed mode ring (sparsign_golomb):")
    check_ring("streamed", mesh, model_t, params_t, batch_t, comp_g, lr)
    print("OK streamed-mode ring == monolithic (3 wires x 2 backends)")


if __name__ == "__main__":
    main(tuple(sys.argv[1:]) or PARTS)
