"""What a per-layer metric reader is given: the cell, the reduced trace, the
chip's published peaks, and the leaves of the configuration's parameters."""

from __future__ import annotations

import json
import pathlib

BENCH = pathlib.Path(__file__).resolve().parent


class Context:
    def __init__(self, *, cell, reduced, devices):
        import jax

        self.cell = cell
        self.reduced = reduced
        self.chips = len(devices)
        peaks = json.loads((BENCH / "peaks.json").read_text())
        kind = devices[0].device_kind
        if kind not in peaks:
            raise SystemExit(f"bench: no published peaks for device kind {kind!r} "
                             f"in bench/peaks.json")
        self.peak = peaks[kind]
        shapes = jax.eval_shape(
            lambda: cell.reference.init_params(jax.random.key(0), cell.config))
        # (coordinates, bytes per coordinate) of every parameter leaf
        self.leaves = [(x.size, x.dtype.itemsize)
                       for x in jax.tree_util.tree_leaves(shapes)]

    def layer_ms(self, key: str):
        """Device milliseconds per step of one layer; None where the trace
        holds no operation of it."""
        s = self.reduced.layer_s.get(key, 0.0)
        return s / self.reduced.steps * 1e3 if s > 0 else None

    def roofline(self, key: str, bytes_per_step: float):
        """Share (%) of the HBM roofline: least time for the bytes over the
        layer's time per step."""
        ms = self.layer_ms(key)
        if ms is None:
            return None
        return bytes_per_step / self.peak["hbm_bytes_per_s"] / (ms * 1e-3) * 100.0
