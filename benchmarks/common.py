"""Shared benchmark plumbing: CSV emission + the standard algorithm grid."""

from __future__ import annotations

import sys

from repro.core.algorithm import CompressionConfig
from repro.core.budgets import BudgetConfig


def emit(row: dict, file=sys.stdout):
    print(",".join(f"{k}={v}" for k, v in row.items()), file=file, flush=True)


def csv_header(cols, file=sys.stdout):
    print(",".join(cols), file=file, flush=True)


def csv_row(vals, file=sys.stdout):
    print(",".join(str(v) for v in vals), file=file, flush=True)


# The paper's §6 algorithm grid (Tables 1-2)
ALGORITHMS = {
    "signSGD": CompressionConfig(compressor="sign", server="majority_vote"),
    "scaled_signSGD": CompressionConfig(compressor="scaled_sign", server="mean"),
    "noisy_signSGD": CompressionConfig(compressor="noisy_sign",
                                       budget=BudgetConfig(value=0.01),
                                       server="majority_vote"),
    "qsgd_1bit_l2": CompressionConfig(compressor="qsgd_1bit_l2", server="mean"),
    "qsgd_1bit_linf": CompressionConfig(compressor="qsgd_1bit_linf", server="mean"),
    "terngrad": CompressionConfig(compressor="terngrad", server="mean"),
    "sparsignSGD_B1": CompressionConfig(compressor="sparsign",
                                        budget=BudgetConfig(value=1.0),
                                        server="majority_vote"),
    "ef_sparsignSGD": CompressionConfig(compressor="sparsign",
                                        budget=BudgetConfig(value=1.0),
                                        server="scaled_sign_ef"),
}
