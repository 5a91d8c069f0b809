"""Fitted monitor state (a pytree) + the jittable scoring functions."""

from __future__ import annotations

from pathlib import Path

import jax
import jax.numpy as jnp
import numpy as np
from flax import struct

from mlops_tpu.config import MonitorConfig
from mlops_tpu.data.encode import EncodedDataset
from mlops_tpu.ops.drift import (
    chi2_two_sample,
    ks_two_sample,
    ks_two_sample_masked,
    ks_two_sample_small_masked,
)
from mlops_tpu.ops.outlier import fit_mahalanobis, mahalanobis_sq
from mlops_tpu.schema.features import SCHEMA


class MonitorState(struct.PyTreeNode):
    """Everything the fused predict needs, as fixed-shape device arrays.

    - ``cat_ref_counts``  f32 [C, max_card]: training category counts per
      categorical feature, zero-padded to the max cardinality.
    - ``num_ref_sorted``  f32 [M, R]: sorted training reference sample per
      numeric feature (subsampled to ``drift_ref_size``).
    - ``num_ref_cdf``     f32 [M, R]: each reference's own right-continuous
      ECDF values (tie-aware) — a fit-time constant that lets the grouped
      serving path run K-S without per-slot sorts (`ops/drift.py`).
    - ``out_mean/out_precision/out_threshold``: Mahalanobis detector.
    """

    cat_ref_counts: jnp.ndarray
    num_ref_sorted: jnp.ndarray
    num_ref_cdf: jnp.ndarray
    out_mean: jnp.ndarray
    out_precision: jnp.ndarray
    out_threshold: jnp.ndarray

    # ------------------------------------------------------------ serialize
    def to_arrays(self) -> dict[str, np.ndarray]:
        return {
            "cat_ref_counts": np.asarray(self.cat_ref_counts),
            "num_ref_sorted": np.asarray(self.num_ref_sorted),
            "num_ref_cdf": np.asarray(self.num_ref_cdf),
            "out_mean": np.asarray(self.out_mean),
            "out_precision": np.asarray(self.out_precision),
            "out_threshold": np.asarray(self.out_threshold),
        }

    @classmethod
    def from_arrays(cls, arrays: dict[str, np.ndarray]) -> "MonitorState":
        arrays = dict(arrays)
        if "num_ref_cdf" not in arrays:  # bundles saved before the field
            arrays["num_ref_cdf"] = _ref_cdf(
                np.asarray(arrays["num_ref_sorted"])
            )
        return cls(
            **{k: jnp.asarray(arrays[k]) for k in (
                "cat_ref_counts",
                "num_ref_sorted",
                "num_ref_cdf",
                "out_mean",
                "out_precision",
                "out_threshold",
            )}
        )

    def save(self, path: str | Path) -> None:
        np.savez(Path(path).with_suffix(".npz"), **self.to_arrays())

    @classmethod
    def load(cls, path: str | Path) -> "MonitorState":
        with np.load(Path(path).with_suffix(".npz")) as data:
            return cls.from_arrays({k: data[k] for k in data.files})


def _ref_cdf(ref_sorted: np.ndarray) -> np.ndarray:
    """Right-continuous ECDF of each sorted reference row at its own
    points (ties collapse to the last occurrence, matching
    ``searchsorted(..., side="right")``)."""
    m, r = ref_sorted.shape
    out = np.empty((m, r), dtype=np.float32)
    for j in range(m):
        out[j] = np.searchsorted(
            ref_sorted[j], ref_sorted[j], side="right"
        ) / float(r)
    return out


def abstract_monitor_state(config: MonitorConfig | None = None) -> MonitorState:
    """Shape-only MonitorState (ShapeDtypeStruct leaves) for abstract
    tracing and AOT cache keys: the monitor's array shapes are fully
    determined by the schema and ``drift_ref_size``, so the tpulint
    Layer-2 registry (`analysis/entrypoints.py`) and the compile-cache
    warmup CLI (`compilecache/warmup.py`) can lower the serving programs
    without a fitted monitor — and produce the exact keys a fitted one
    would."""
    config = config or MonitorConfig()
    S = jax.ShapeDtypeStruct
    ref = config.drift_ref_size
    return MonitorState(
        cat_ref_counts=S((SCHEMA.num_categorical, max(SCHEMA.cards)), jnp.float32),
        num_ref_sorted=S((SCHEMA.num_numeric, ref), jnp.float32),
        num_ref_cdf=S((SCHEMA.num_numeric, ref), jnp.float32),
        out_mean=S((SCHEMA.num_numeric,), jnp.float32),
        out_precision=S((SCHEMA.num_numeric, SCHEMA.num_numeric), jnp.float32),
        out_threshold=S((), jnp.float32),
    )


def fit_monitor(
    ds: EncodedDataset, config: MonitorConfig | None = None, seed: int = 0
) -> MonitorState:
    """Host-side fit on the encoded TRAINING split.

    Mirrors the reference's fit inputs: drift reference = full feature
    matrix, outlier detector = numeric features only
    (`02-register-model.ipynb:225-233`).
    """
    config = config or MonitorConfig()
    max_card = max(SCHEMA.cards)
    counts = np.zeros((SCHEMA.num_categorical, max_card), dtype=np.float32)
    for j, feat in enumerate(SCHEMA.categorical):
        binc = np.bincount(ds.cat_ids[:, j], minlength=feat.card)
        counts[j, : feat.card] = binc

    rng = np.random.default_rng(seed)
    n = ds.numeric.shape[0]
    size = min(config.drift_ref_size, n)
    idx = rng.choice(n, size=size, replace=False)
    ref = np.sort(ds.numeric[idx].astype(np.float32), axis=0).T  # [M, R]

    mean, precision, threshold = fit_mahalanobis(
        ds.numeric, quantile=config.outlier_quantile
    )
    return MonitorState(
        cat_ref_counts=jnp.asarray(counts),
        num_ref_sorted=jnp.asarray(ref),
        num_ref_cdf=jnp.asarray(_ref_cdf(ref)),
        out_mean=jnp.asarray(mean),
        out_precision=jnp.asarray(precision),
        out_threshold=jnp.asarray(threshold, dtype=jnp.float32),
    )


class MonitorAccumulator(struct.PyTreeNode):
    """Device-resident running aggregate of the serving monitors.

    The seed path derived /metrics totals on the HOST from every response
    (sum the outlier flags, copy the drift dict — per request, on the hot
    path). Here the aggregate lives on the device and is folded INSIDE the
    fused predict program (`ops/predict.py make_packed_*`): the request
    path never fetches it, a telemetry task reads it every K requests /
    T seconds (`serve/server.py`). All leaves are f32 so the whole state
    rides one tiny D2H transfer — and each read RESETS the device window
    (`serve/engine.py monitor_snapshot` folds it into exact host-side f64
    totals), so the f32 counters never approach 2^24, where integer
    increments would silently stop.

    - ``rows``      f32 []:  valid (non-padding) rows scored
    - ``outliers``  f32 []:  outlier flags raised
    - ``batches``   f32 []:  dispatches folded (grouped slots count one
      per non-empty request slot)
    - ``drift_sum`` f32 [D]: per-feature sum of batch drift scores (mean
      drift = drift_sum / batches)
    - ``drift_last``f32 [D]: drift of the most recently folded dispatch
      (grouped dispatches fold the mean over their non-empty slots)
    """

    rows: jnp.ndarray
    outliers: jnp.ndarray
    batches: jnp.ndarray
    drift_sum: jnp.ndarray
    drift_last: jnp.ndarray


def init_accumulator() -> MonitorAccumulator:
    # DISTINCT arrays per leaf (never alias one zeros scalar): the engine
    # threads the accumulator as a donated argument where the backend
    # allows, and donating one buffer under two leaves is an XLA error
    # ("attempt to donate the same buffer twice").
    d = SCHEMA.num_categorical + SCHEMA.num_numeric
    return MonitorAccumulator(
        rows=jnp.zeros((), jnp.float32),
        outliers=jnp.zeros((), jnp.float32),
        batches=jnp.zeros((), jnp.float32),
        drift_sum=jnp.zeros((d,), jnp.float32),
        drift_last=jnp.zeros((d,), jnp.float32),
    )


def abstract_accumulator() -> MonitorAccumulator:
    """Shape-only accumulator (ShapeDtypeStruct leaves) — the tracing /
    AOT-cache-key twin of ``init_accumulator`` (same role as
    ``abstract_monitor_state``): shapes depend only on the schema."""
    d = SCHEMA.num_categorical + SCHEMA.num_numeric
    S = jax.ShapeDtypeStruct
    return MonitorAccumulator(
        rows=S((), jnp.float32),
        outliers=S((), jnp.float32),
        batches=S((), jnp.float32),
        drift_sum=S((d,), jnp.float32),
        drift_last=S((d,), jnp.float32),
    )


def fold_accumulator(
    acc: MonitorAccumulator,
    flags: jnp.ndarray,
    drift: jnp.ndarray,
    mask: jnp.ndarray,
) -> MonitorAccumulator:
    """Fold one padded batch into the running aggregate (jittable; called
    inside the fused predict). ``flags`` are already mask-zeroed
    (`outlier_flags`); an all-padding batch contributes nothing — not even
    to ``drift_last`` (an empty batch has no drift signal, the same
    invariant the engine's empty-request path keeps)."""
    n_valid = mask.astype(jnp.float32).sum()
    nonempty = (n_valid > 0).astype(jnp.float32)
    # Select, don't multiply: drift over ZERO valid rows can be NaN (the
    # chi-squared path divides by the row count) and NaN * 0 is still
    # NaN — a multiplicative mask would poison the running sum forever.
    safe_drift = jnp.where(nonempty > 0, drift, jnp.zeros_like(drift))
    return MonitorAccumulator(
        rows=acc.rows + n_valid,
        outliers=acc.outliers + flags.sum(),
        batches=acc.batches + nonempty,
        drift_sum=acc.drift_sum + safe_drift,
        drift_last=jnp.where(nonempty > 0, drift, acc.drift_last),
    )


def fold_accumulator_grouped(
    acc: MonitorAccumulator,
    flags: jnp.ndarray,
    drift: jnp.ndarray,
    mask: jnp.ndarray,
) -> MonitorAccumulator:
    """Grouped-dispatch fold: ``flags``/``mask`` are [S, R], ``drift`` is
    [S, D]. Padding SLOTS (mask all-false) are excluded everywhere; each
    non-empty slot counts as one batch and ``drift_last`` takes the mean
    drift over this dispatch's non-empty slots."""
    slot_rows = mask.astype(jnp.float32).sum(axis=1)  # [S]
    slot_valid = (slot_rows > 0).astype(jnp.float32)
    n_slots = slot_valid.sum()
    # Select, don't multiply: PADDING slots compute drift over zero rows,
    # where the chi-squared path divides by zero and yields NaN — and
    # NaN * 0 is still NaN, so a multiplicative mask would poison
    # drift_sum (and mean_drift) forever.
    safe_drift = jnp.where(slot_valid[:, None] > 0, drift, 0.0)
    drift_total = safe_drift.sum(axis=0)
    mean_drift = drift_total / jnp.maximum(n_slots, 1.0)
    return MonitorAccumulator(
        rows=acc.rows + slot_rows.sum(),
        outliers=acc.outliers + flags.sum(),
        batches=acc.batches + n_slots,
        drift_sum=acc.drift_sum + drift_total,
        drift_last=jnp.where(n_slots > 0, mean_drift, acc.drift_last),
    )


def merge_accumulators(
    older: MonitorAccumulator, newer: MonitorAccumulator
) -> MonitorAccumulator:
    """Combine two accumulator windows: counters and sums add;
    ``drift_last`` takes the newer window's unless it folded no batches.
    Used by `serve/engine.py monitor_snapshot` to fold an un-fetched
    window back into the live accumulator when a telemetry fetch fails —
    a transient device error must DELAY the counts, not drop them.

    Lock discipline: callers invoke this UNDER the engine's ``_acc_lock``
    (see TPULINT_LOCK_ORDER in serve/engine.py) so no dispatch can donate
    either operand mid-merge — which is safe under tpulint TPU403 because
    the merge is an eager device ENQUEUE, never a host-blocking fetch."""
    return MonitorAccumulator(
        rows=older.rows + newer.rows,
        outliers=older.outliers + newer.outliers,
        batches=older.batches + newer.batches,
        drift_sum=older.drift_sum + newer.drift_sum,
        drift_last=jnp.where(
            newer.batches > 0, newer.drift_last, older.drift_last
        ),
    )


@jax.named_scope("drift")  # the scope its operations carry in a device trace
def drift_scores(
    state: MonitorState,
    cat_ids: jnp.ndarray,
    numeric: jnp.ndarray,
    mask: jnp.ndarray | None = None,
) -> jnp.ndarray:
    """Per-feature drift scores ``1 - p_val`` in schema order ([C+M]).

    Categorical: chi-squared contingency vs training counts. Numeric:
    two-sample K-S vs the stored reference sample. Both vmapped across
    features — the entire drift pass is one fused computation. ``mask``
    (bool [N]) excludes padded rows when serving pads to bucket sizes.
    """
    max_card = state.cat_ref_counts.shape[1]
    one_hot = jax.nn.one_hot(cat_ids, max_card, dtype=jnp.float32)  # [N, C, K]
    if mask is not None:
        one_hot = one_hot * mask.astype(jnp.float32)[:, None, None]
    batch_counts = one_hot.sum(axis=0)  # [C, K]
    _, cat_p = jax.vmap(chi2_two_sample)(state.cat_ref_counts, batch_counts)

    if mask is None:
        _, num_p = jax.vmap(ks_two_sample)(state.num_ref_sorted, numeric.T)
    elif numeric.shape[0] <= 64:
        # Small (serving / grouped) batches: dense-comparison K-S — no
        # per-call sorts or gathers, which dominate vmapped-per-request
        # dispatches on TPU (see ops/drift.py).
        _, num_p = jax.vmap(
            ks_two_sample_small_masked, in_axes=(0, 0, 0, None)
        )(state.num_ref_sorted, state.num_ref_cdf, numeric.T, mask)
    else:
        _, num_p = jax.vmap(ks_two_sample_masked, in_axes=(0, 0, None))(
            state.num_ref_sorted, numeric.T, mask
        )
    return 1.0 - jnp.concatenate([cat_p, num_p])


@jax.named_scope("outlier")
def outlier_flags(
    state: MonitorState, numeric: jnp.ndarray, mask: jnp.ndarray | None = None
) -> jnp.ndarray:
    """Per-row 0/1 outlier flags (reference contract: `app/model.py:69`)."""
    distances = mahalanobis_sq(numeric, state.out_mean, state.out_precision)
    flags = (distances > state.out_threshold).astype(jnp.float32)
    if mask is not None:
        flags = flags * mask.astype(jnp.float32)
    return flags
