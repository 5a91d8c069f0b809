"""Plain reference of the monitors a bulk job reports, in NumPy float64
with SciPy's special functions: per-row Mahalanobis outlier flags and the
per-feature drift score ``1 - p`` (chi-squared on a 2 x K table for
categoricals; two-sample Kolmogorov-Smirnov with Stephens' small-sample
correction for numerics, as the program's documentation states).

``precision="low"`` is the control, one step below what the configuration
states for the monitors (float32 values; products at XLA's default
precision, which on a TPU rounds both operands to bfloat16): every input
is rounded to bfloat16, and both operands of every product of the
Mahalanobis distance to the 4 significant bits of an 8-bit float (e4m3),
the step below bfloat16 products. Sums are kept wide.
"""

from __future__ import annotations

import numpy as np
from scipy import special


def _round(x, precision: str):
    if precision == "f32":
        return np.asarray(x, np.float64)
    if precision == "low":
        import ml_dtypes

        return np.asarray(x, np.float32).astype(ml_dtypes.bfloat16).astype(np.float64)
    raise ValueError(f"unknown precision {precision!r}")


def _operand(x, precision: str):
    """An operand of a product: as it is, or cut to e4m3's 4 significant bits."""
    if precision == "f32":
        return x
    mantissa, exponent = np.frexp(x)
    return np.ldexp(np.round(mantissa * 16.0) / 16.0, exponent)


def mahalanobis_sq(num, monitor: dict, precision: str = "f32"):
    centered = _round(num, precision) - _round(monitor["out_mean"], precision)
    matrix = _round(monitor["out_precision"], precision)
    half = _operand(centered, precision) @ _operand(matrix, precision)
    return (_operand(half, precision) * _operand(centered, precision)).sum(axis=1)


def outlier_flags(num, monitor: dict, precision: str = "f32"):
    dist = mahalanobis_sq(num, monitor, precision)
    return (dist > float(monitor["out_threshold"])).astype(np.float32), dist


def chi2_drift(ref_counts, batch_counts):
    total = ref_counts + batch_counts
    keep = total > 0
    ref, batch, total = ref_counts[keep], batch_counts[keep], total[keep]
    grand = total.sum()
    stat = 0.0
    for observed in (ref, batch):
        expected = observed.sum() * total / grand
        stat += ((observed - expected) ** 2 / expected).sum()
    dof = max(int(keep.sum()) - 1, 1)
    return 1.0 - special.gammaincc(dof / 2.0, stat / 2.0)


def ks_drift(ref_sorted, batch):
    r, b = ref_sorted.size, batch.size
    batch_sorted = np.sort(batch)
    pooled = np.concatenate([ref_sorted, batch_sorted])
    gap = np.abs(
        np.searchsorted(ref_sorted, pooled, side="right") / r
        - np.searchsorted(batch_sorted, pooled, side="right") / b
    ).max()
    en = np.sqrt(r * b / (r + b))
    return 1.0 - special.kolmogorov((en + 0.12 + 0.11 / en) * gap)


def drift_scores(cat, num, monitor: dict, cards, precision: str = "f32"):
    """[C + M] scores in schema order over the rows given."""
    scores = []
    for j, card in enumerate(cards):
        counts = np.bincount(cat[:, j], minlength=card).astype(np.float64)
        scores.append(chi2_drift(monitor["cat_ref_counts"][j, :card].astype(np.float64), counts))
    x = _round(num, precision)
    ref = _round(monitor["num_ref_sorted"], precision)
    for j in range(x.shape[1]):
        scores.append(ks_drift(np.sort(ref[j]), x[:, j]))
    return np.asarray(scores, np.float64)
