"""Two-sample drift statistics as jittable JAX functions.

Parity targets (alibi-detect ``TabularDrift(p_val=.05)``,
`02-register-model.ipynb:225-230`; scored at serve time in
`02-register-model.ipynb:330-353` as ``1 - p_val`` per feature):

- categorical features -> two-sample chi-squared contingency test
- numeric features     -> two-sample Kolmogorov-Smirnov test (asymptotic
  p-value with the Stephens small-sample correction; matches
  ``scipy.stats.ks_2samp(method="asymp")`` to ~1e-6)

Everything is fixed-shape: categorical counts are padded to a common
``max_card`` with masked cells, so one vmap covers all 9 features and the
whole drift pass is a handful of fused reductions — no per-feature Python.
"""

from __future__ import annotations

import jax
import jax.numpy as jnp


def chi2_two_sample(
    ref_counts: jnp.ndarray,  # f32 [K] category counts from training
    batch_counts: jnp.ndarray,  # f32 [K] category counts from the batch
) -> tuple[jnp.ndarray, jnp.ndarray]:
    """Chi-squared contingency test on a 2 x K table with empty-cell masking.

    Returns ``(statistic, p_value)``. Categories absent from BOTH samples are
    dropped from the table (and from the degrees of freedom), mirroring how a
    dense implementation would build the contingency table only over observed
    categories.
    """
    ref_counts = ref_counts.astype(jnp.float32)
    batch_counts = batch_counts.astype(jnp.float32)
    col_total = ref_counts + batch_counts
    valid = col_total > 0
    n_ref = ref_counts.sum()
    n_batch = batch_counts.sum()
    grand = n_ref + n_batch

    expected_ref = n_ref * col_total / jnp.maximum(grand, 1.0)
    expected_batch = n_batch * col_total / jnp.maximum(grand, 1.0)
    safe_ref = jnp.where(valid, expected_ref, 1.0)
    safe_batch = jnp.where(valid, expected_batch, 1.0)
    stat = jnp.sum(
        jnp.where(valid, (ref_counts - expected_ref) ** 2 / safe_ref, 0.0)
    ) + jnp.sum(
        jnp.where(valid, (batch_counts - expected_batch) ** 2 / safe_batch, 0.0)
    )
    df = jnp.maximum(valid.sum() - 1, 1).astype(jnp.float32)
    # chi2 survival function: Q(df/2, stat/2) via the regularized upper
    # incomplete gamma function.
    p_value = jax.scipy.special.gammaincc(df / 2.0, stat / 2.0)
    return stat, p_value


def _kolmogorov_sf(t: jnp.ndarray, terms: int = 32) -> jnp.ndarray:
    """Kolmogorov distribution survival function Q(t).

    Two jit-safe branches: the alternating series
    ``2*sum (-1)^{k-1} e^{-2k^2 t^2}`` converges fast for large ``t`` but
    diverges as ``t -> 0``, so small ``t`` uses the Jacobi-theta dual form
    ``1 - sqrt(2*pi)/t * sum e^{-(2k-1)^2 pi^2 / (8 t^2)}``.
    """
    t_safe = jnp.maximum(t, 1e-8)
    k = jnp.arange(1, terms + 1, dtype=jnp.float32)
    signs = jnp.where(k % 2 == 1, 1.0, -1.0)
    large = 2.0 * jnp.sum(signs * jnp.exp(-2.0 * (k**2) * (t_safe**2)))
    odd = 2.0 * k - 1.0
    # f32-pinned constant: under jax_enable_x64 (the gbm-tensor tier traces
    # its whole program in an x64 context — ops/gbm_tensor.py) the bare
    # Python-float expression would promote to f64 and drag the drift
    # branch with it; the monitors are f32 by contract on every tier.
    small = 1.0 - jnp.sqrt(jnp.float32(2.0 * jnp.pi)) / t_safe * jnp.sum(
        jnp.exp(-(odd**2) * (jnp.pi**2) / (8.0 * t_safe**2))
    )
    return jnp.clip(jnp.where(t_safe < 1.0, small, large), 0.0, 1.0)


def ks_two_sample(
    ref_sorted: jnp.ndarray,  # f32 [R] training reference sample, ASCENDING
    batch: jnp.ndarray,  # f32 [B] serve-time batch (unsorted)
) -> tuple[jnp.ndarray, jnp.ndarray]:
    """Two-sample K-S test. Returns ``(statistic, p_value)``.

    The supremum of |ECDF_ref - ECDF_batch| is attained at sample points; we
    evaluate both ECDFs at the batch's sorted points (from both sides) and at
    the reference points via ``searchsorted`` — fixed-shape, O((R+B) log)
    work that XLA fuses into a few sorts and gathers.
    """
    r = ref_sorted.shape[0]
    b = batch.shape[0]
    batch_sorted = jnp.sort(batch.astype(jnp.float32))
    ref_sorted = ref_sorted.astype(jnp.float32)

    # Evaluate both ECDFs (right-continuous) at every sample point of the
    # pooled sample. This is tie-safe: the supremum of |F_ref - F_batch| over
    # x is attained just after some sample point, and the left-limit at any
    # point equals the value just after the previous distinct point — also a
    # sample point.
    pooled = jnp.concatenate([ref_sorted, batch_sorted])
    # Integer-count / integer-size divisions are f32-pinned: under
    # jax_enable_x64 searchsorted yields int64 and the true division would
    # otherwise produce f64 statistics (the gbm-tensor tier traces this
    # program inside an x64 context; bit-identical in f32 mode).
    ref_cdf = (
        jnp.searchsorted(ref_sorted, pooled, side="right") / r
    ).astype(jnp.float32)
    batch_cdf = (
        jnp.searchsorted(batch_sorted, pooled, side="right") / b
    ).astype(jnp.float32)
    statistic = jnp.abs(ref_cdf - batch_cdf).max()
    en = jnp.sqrt(r * b / jnp.asarray(r + b, jnp.float32))
    # Stephens correction (as used by scipy's asymptotic two-sample mode).
    p_value = _kolmogorov_sf((en + 0.12 + 0.11 / en) * statistic)
    return statistic, p_value


def ks_small_masked_statistic(
    ref_sorted: jnp.ndarray,  # f32 [R] ascending
    ref_cdf: jnp.ndarray,  # f32 [R] ECDF_ref at its own points (right-cont.)
    batch: jnp.ndarray,  # f32 [B] possibly padded, B small
    mask: jnp.ndarray,  # bool [B] True for real rows
) -> jnp.ndarray:
    """The dense masked K-S STATISTIC alone — split from the p-value so
    the Pallas fused kernel (`ops/quant_kernel.py`) can run the heavy
    [B,R]/[R,B] comparison planes in-kernel while the Kolmogorov survival
    function stays outside (its series builds ``arange`` constants, which
    a Pallas kernel body cannot capture)."""
    r = ref_sorted.shape[0]
    ref_sorted = ref_sorted.astype(jnp.float32)
    bvals = jnp.where(mask, batch.astype(jnp.float32), jnp.inf)
    n_valid = jnp.maximum(mask.sum().astype(jnp.float32), 1.0)

    # ECDFs at batch points ([B,R] and [B,B] comparisons). The count
    # division is f32-pinned (x64-context tracing — see ks_two_sample).
    f_ref_b = (
        (ref_sorted[None, :] <= bvals[:, None]).sum(axis=1) / r
    ).astype(jnp.float32)
    cnt_b = (bvals[None, :] <= bvals[:, None]).sum(axis=1).astype(jnp.float32)
    f_b_b = jnp.minimum(cnt_b, n_valid) / n_valid
    d_b = jnp.where(
        jnp.isfinite(bvals), jnp.abs(f_ref_b - f_b_b), 0.0
    ).max()

    # ECDFs at reference points ([R,B] comparisons; ECDF_ref precomputed).
    cnt_r = (bvals[None, :] <= ref_sorted[:, None]).sum(axis=1)
    f_b_r = jnp.minimum(cnt_r.astype(jnp.float32), n_valid) / n_valid
    d_r = jnp.abs(ref_cdf - f_b_r).max()

    return jnp.where(mask.any(), jnp.maximum(d_b, d_r), 0.0)


def ks_two_sample_small_masked(
    ref_sorted: jnp.ndarray,  # f32 [R] ascending
    ref_cdf: jnp.ndarray,  # f32 [R] ECDF_ref at its own points (right-cont.)
    batch: jnp.ndarray,  # f32 [B] possibly padded, B small
    mask: jnp.ndarray,  # bool [B] True for real rows
) -> tuple[jnp.ndarray, jnp.ndarray]:
    """K-S for SMALL batches as dense comparisons — the grouped-serving
    hot path.

    ``ks_two_sample_masked`` sorts the pooled R+B points; vmapped per
    request-slot that is a sort per slot (its earlier ``searchsorted``
    form took ~4-5 ms per slot on v5e — it dominated grouped dispatch).
    For B << R the supremum over pooled points splits into batch points
    and reference points, and every ECDF evaluation becomes a ``<=`` outer
    comparison ([B,R] and [R,B] elementwise reductions, MXU/VPU-friendly),
    with ECDF_ref at reference points a fit-time constant (``ref_cdf``).
    Identical statistics to the pooled form, including ties and padding
    (+inf rows contribute 0 everywhere).
    """
    r = ref_sorted.shape[0]
    statistic = ks_small_masked_statistic(ref_sorted, ref_cdf, batch, mask)
    n_valid = jnp.maximum(mask.sum().astype(jnp.float32), 1.0)
    en = jnp.sqrt(r * n_valid / (r + n_valid))
    p_value = _kolmogorov_sf((en + 0.12 + 0.11 / en) * statistic)
    return statistic, p_value


def ks_two_sample_masked(
    ref_sorted: jnp.ndarray,  # f32 [R] ascending
    batch: jnp.ndarray,  # f32 [B] possibly padded
    mask: jnp.ndarray,  # bool [B] True for real rows
) -> tuple[jnp.ndarray, jnp.ndarray]:
    """K-S test ignoring padded rows — serving pads batches to fixed bucket
    sizes for compile-cache stability, and padding must not perturb the
    statistics.

    Padded entries are replaced with +inf so they sort to the tail; the batch
    ECDF denominator is the number of REAL rows, so at every finite pooled
    point both ECDFs agree with the unpadded computation.

    The ECDFs come from ONE sort of the pooled values, each carrying a flag
    of its origin, and a running count of the flags: at the LAST position
    of a run of equal values the counts so far are the reference and batch
    rows ``<=`` that value, exactly what ``searchsorted(side="right")``
    gives at every pooled point, so the statistic is the same bits. No
    ``searchsorted``: vmapped over features it lowers to a ``while`` loop
    of scalar gathers, which a TPU runs slowly (bulk jobs sample 65,536
    rows).
    """
    r = ref_sorted.shape[0]
    ref_sorted = ref_sorted.astype(jnp.float32)
    bvals = jnp.where(mask, batch.astype(jnp.float32), jnp.inf)
    n_valid = jnp.maximum(mask.sum().astype(jnp.float32), 1.0)

    pooled, from_ref = jax.lax.sort(
        (
            jnp.concatenate([ref_sorted, bvals]),
            jnp.concatenate(
                [jnp.ones(r, jnp.int32), jnp.zeros(bvals.shape[0], jnp.int32)]
            ),
        ),
        num_keys=1,
    )
    ref_counts = jnp.cumsum(from_ref)
    batch_counts = jnp.arange(1, pooled.shape[0] + 1) - ref_counts
    # f32-pinned count divisions (x64-context tracing — see ks_two_sample;
    # there ``arange`` yields int64).
    ref_cdf = (ref_counts / r).astype(jnp.float32)
    batch_cdf = jnp.minimum(batch_counts.astype(jnp.float32), n_valid) / n_valid
    last = jnp.concatenate([pooled[1:] != pooled[:-1], jnp.ones(1, bool)])
    read = last & jnp.isfinite(pooled)
    statistic = jnp.where(read, jnp.abs(ref_cdf - batch_cdf), 0.0).max()
    # All-padded batch: no data, no signal.
    statistic = jnp.where(mask.any(), statistic, 0.0)

    en = jnp.sqrt(r * n_valid / (r + n_valid))
    p_value = _kolmogorov_sf((en + 0.12 + 0.11 / en) * statistic)
    return statistic, p_value
