"""The comparison that decides ``correct`` for a training cell.

The numbers, each against a limit from the cell's file
(``bench/workloads/<cell>.json``), set from the trainer's readings over a
dozen seeds or more and from the control's and the faults' (PERF.md gives
them); a number the file gives no limit is read but not compared:

    loss_gap_step<k>  |loss - reference loss| / reference loss at set-up
                      step k
    first_update_gap  the first gradient as the optimizer gets it, worked
                      out from the state after one step: |p0 - p1| / lr0
    first_flip_share  of the coordinates the reference's first update moves,
                      the share that the trainer's moves otherwise (the
                      other way, or not at all), over all leaves. Both
                      draw the same counter-hash stream, so a coordinate
                      moves differently only where the two gradients
                      straddle its draw: the share reads the gradient's
                      relative L1 error, steadily over millions of
                      coordinates, where a norm of the votes cannot
    change_gap        the parameters' change after the set-up steps:
                      |p_n - p0|

The two norms are taken leaf by leaf, and the number is the worst leaf's gap
between the trainer's norm and the reference's, over the reference's norm of
that leaf. A leaf counts where the reference's first update moves MIN_MOVED
coordinates or more: a vote moves a coordinate with the chance |g| * budget,
so the count is the leaf's gradient in L1 as drawn, and where it is a
handful, one coordinate moved otherwise shifts the leaf's norm by a tenth.
"""

from __future__ import annotations

import numpy as np

MIN_MOVED = 1000


def counted(want) -> np.ndarray:
    """Which leaves enter the two norm gaps."""
    keep = np.array([np.count_nonzero(b) >= MIN_MOVED for b in want.first_moves])
    if not keep.any():
        raise ValueError(f"the reference's first update moves {MIN_MOVED} "
                         f"coordinates in no leaf")
    return keep


def per_leaf_gaps(got, want) -> np.ndarray:
    got, want = np.asarray(got, np.float64), np.asarray(want, np.float64)
    return np.abs(got - want) / np.where(want > 0, want, np.nan)


def leaf_table(got, want) -> list:
    """Per leaf: its name and size, the reference's gradient norm, the
    coordinates each side's first update moves and those it moves otherwise,
    each side's two norms, and the leaf's gap in each (None where the leaf is
    left out)."""
    keep = counted(want)
    gaps = {k: per_leaf_gaps(getattr(got, k), getattr(want, k))
            for k in ("first_update", "change")}
    rows = []
    for i, (a, b) in enumerate(zip(got.first_moves, want.first_moves)):
        rows.append({
            "leaf": got.names[i], "size": int(b.size),
            "ref_grad": float(want.grad_norms[i]),
            "ref_moved": int(np.count_nonzero(b)),
            "moved": int(np.count_nonzero(a)),
            "moved_otherwise": int(np.count_nonzero(a != b)),
            "change_moved": [int(got.change_moved[i]), int(want.change_moved[i])],
            "first_update": [float(got.first_update[i]), float(want.first_update[i])],
            "change": [float(got.change[i]), float(want.change[i])],
            "first_update_gap": float(gaps["first_update"][i]) if keep[i] else None,
            "change_gap": float(gaps["change"][i]) if keep[i] else None})
    return rows


def readings(got, want) -> dict:
    keep = counted(want)
    out = {f"loss_gap_step{k}": (float(abs(a - b) / abs(b)) if np.isfinite(a)
                                 else float("inf"))
           for k, (a, b) in enumerate(zip(got.losses, want.losses))}
    out["first_update_gap"] = float(np.max(
        per_leaf_gaps(got.first_update, want.first_update)[keep]))
    differ = sum(int(np.count_nonzero(a != b))
                 for a, b in zip(got.first_moves, want.first_moves))
    moved = sum(int(np.count_nonzero(b)) for b in want.first_moves)
    out["first_flip_share"] = (differ / moved if moved
                               else (float("inf") if differ else 0.0))
    out["change_gap"] = float(np.max(per_leaf_gaps(got.change, want.change)[keep]))
    return out


def compare(got, want, limits: dict) -> dict:
    """The numbers that have a limit, each beside it."""
    if not limits:
        raise ValueError("the cell's file gives no limit")
    values = readings(got, want)
    return {k: {"value": values[k], "limit": limits[k]} for k in limits}


def passed(compared: dict) -> bool:
    """Every number finite and at or under its limit."""
    return all(np.isfinite(c["value"]) and c["value"] <= c["limit"]
               for c in compared.values())
