"""Pallas-fused packed predict for the quantized student tier.

The exact tier's packed program (`ops/predict.py make_packed_predict_base`)
is already ONE XLA computation, but XLA still materializes the student
activations, the one-hot tables, and the [B,R] K-S comparison planes in
HBM between fusions. Here the whole per-request body — student forward
(int8 dequant in VMEM), Mahalanobis outlier flags, categorical batch
counts, and the dense masked K-S statistics — is a single hand-written
`pltpu` kernel in the `ops/attention.py` style: operands stream through
VMEM once, int8/bf16 weights stay quantized in HBM, and nothing round-
trips between fusion islands.

Split of labor (shared by kernel AND composite; Mosaic and XLA order and
round the same expressions differently — and run the f32 matmuls at
different precisions on the chip — so the contract between the two is a
tolerance, `KERNEL_COMPOSITE_ATOL` below, not bit equality):

- IN the kernel: student logits -> calibrated probabilities, outlier
  flags, per-feature categorical one-hot COUNTS, and the numeric K-S
  STATISTICS (dense masked form — `ops/drift.py ks_small_masked_statistic`
  — for EVERY bucket; the sort-based large-batch form does not lower on
  Mosaic, and the dense form is mathematically identical).
- OUTSIDE (plain jnp, fuses around the pallas_call): the chi-squared and
  Kolmogorov p-values over the tiny [C, max_card] / [M] aggregates,
  drift assembly (``1 - p``), and the accumulator fold — scalar series
  math (whose ``arange`` constants a kernel body cannot capture), not
  worth kernel bytes.

Routing (`ops/kernel_gate.py`): with ``use_kernel=None`` — production —
serve buckets up to QUANT_KERNEL_MAX_ROWS take the COMPILED kernel where
the program is lowered for a TPU, and the jnp COMPOSITE (the same
`_fused_core` called directly, also the parity reference) on every other
platform; larger buckets are the composite everywhere. ``use_kernel=True``
is the pallas_call unconditionally — compiled, unless the caller (a CPU
test) passes ``interpret=True`` itself. The packed calling convention,
layout (`packed_layout`), and accumulator fold are identical to the exact
tier, so `serve/engine.py` runs this tier through the SAME exec tables,
buckets, and swap/rollback machinery.
"""

from __future__ import annotations

from typing import Any, Callable

import jax
import jax.numpy as jnp
from jax.experimental import pallas as pl
from jax.experimental.pallas import tpu as pltpu

from mlops_tpu.monitor.state import (
    MonitorAccumulator,
    MonitorState,
    fold_accumulator,
    fold_accumulator_grouped,
)
from mlops_tpu.ops.kernel_gate import tpu_kernel_or
from mlops_tpu.ops.drift import (
    _kolmogorov_sf,
    chi2_two_sample,
    ks_small_masked_statistic,
)
from mlops_tpu.ops.quant import dequantize_dense, one_hot_2d

# Largest row bucket the kernel serves. 256 is the top serve bucket; the
# dense K-S working set at B=256 (a [256, 2048] f32 comparison plane per
# numeric feature, features walked sequentially) stays a few MB — well
# inside VMEM.
QUANT_KERNEL_MAX_ROWS = 256

# The kernel-vs-composite contract, stated once (tests/test_quant.py on
# the CPU in interpret mode, chip_smoke.py compiled on the chip): max abs
# difference of any packed value or accumulator entry. In interpret mode
# the two differ by an ulp (1.5e-8); compiled on a v5e the largest
# difference was 2.7e-3, on a probability of the 256-row body (chip runs,
# PR 22) — the tolerance is that with headroom for another bundle.
KERNEL_COMPOSITE_ATOL = 1e-2


def _fused_core(
    embed,  # bf16 [C, K, E]
    w1_q,  # int8 [Din, H]
    w1_s_row,  # f32 (1, H)
    b1_row,  # f32 (1, H)
    w2_q_col,  # int8 (H, 1)
    w2_s,  # f32 (1, 1)
    b2,  # f32 (1, 1)
    ref_sorted,  # f32 [M, R]
    ref_cdf,  # f32 [M, R]
    mean_row,  # f32 (1, M)
    precision,  # f32 [M, M]
    threshold,  # f32 (1, 1)
    temperature,  # f32 (1, 1)
    cat_ids,  # int32 [B, C]
    numeric,  # f32 [B, M]
    maskf_row,  # f32 (1, B)
):
    """The ONE fused-body definition — executed verbatim by the Pallas
    kernel (on refs' loaded values) and by the jnp composite (on arrays),
    which is what makes kernel-vs-composite parity structural rather than
    aspirational. Everything stays 2-D (Mosaic's preferred rank).

    Returns ``(preds (1,B), flags (1,B), cat_counts [C,K], ks_stat (1,M))``.
    """
    c, k = embed.shape[0], embed.shape[1]
    m = numeric.shape[1]
    numeric = numeric.astype(jnp.float32)
    # Transposes, not reshapes, for the (1,B)<->(B,1) flips: at B=1 a
    # same-shape jnp.reshape is elided from the jaxpr, which would make
    # bucket 1 a different primitive sequence than the rest of its
    # declared TPU304 family (analysis/entrypoints.py).
    maskf_col = maskf_row.T  # (B, 1)
    mask_bool = maskf_row[0] > 0  # [B]

    # Student forward: one-hot embed matmuls (the one-hot doubles as the
    # categorical drift count table), int8 dequant, dense/relu/dense.
    feats = []
    counts = []
    for j in range(c):
        oh = one_hot_2d(cat_ids[:, j], k)  # [B, K]
        feats.append(oh @ embed[j].astype(jnp.float32))  # [B, E]
        counts.append((oh * maskf_col).sum(axis=0, keepdims=True))  # (1, K)
    x = jnp.concatenate(feats + [numeric], axis=1)  # [B, Din]
    cat_counts = jnp.concatenate(counts, axis=0)  # [C, K]

    w1 = dequantize_dense(w1_q, w1_s_row[0])  # f32 [Din, H]
    h = jnp.maximum(x @ w1 + b1_row, 0.0)  # [B, H]
    w2_col = w2_q_col.astype(jnp.float32) * w2_s  # (H, 1)
    logits_col = h @ w2_col + b2  # (B, 1)
    preds = jax.nn.sigmoid(logits_col / temperature).T  # (1, B)

    # Mahalanobis outlier flags (explicit 2-D form of ops/outlier's
    # einsum; mask-zeroed like `monitor.state.outlier_flags`).
    diff = numeric - mean_row  # [B, M]
    d2_col = ((diff @ precision) * diff).sum(axis=1, keepdims=True)  # (B, 1)
    flags = (
        (d2_col > threshold).astype(jnp.float32).T * maskf_row
    )  # (1, B)

    # Numeric drift: dense masked K-S statistics per feature, features
    # walked sequentially so only one [B, R] comparison plane is live at
    # a time (the survival function runs outside the kernel).
    ks_stats = []
    for j in range(m):
        stat = ks_small_masked_statistic(
            ref_sorted[j], ref_cdf[j], numeric[:, j], mask_bool
        )
        ks_stats.append(stat.reshape(1, 1))
    ks_stat = jnp.concatenate(ks_stats, axis=1)  # (1, M)

    return preds, flags, cat_counts, ks_stat


def _fused_kernel(
    embed_ref, w1q_ref, w1s_ref, b1_ref, w2q_ref, w2s_ref, b2_ref,
    refsort_ref, refcdf_ref, mean_ref, prec_ref, thr_ref, temp_ref,
    cat_ref, num_ref, maskf_ref,
    preds_ref, flags_ref, counts_ref, ksp_ref,
):
    """Whole-problem kernel (grid=()): serve buckets fit VMEM outright, so
    there is no tiling loop — the win is fusion (one pass, no HBM
    round-trips between the student, the outlier score, and the drift
    planes), not streaming."""
    preds, flags, cat_counts, ks_stat = _fused_core(
        embed_ref[...], w1q_ref[...], w1s_ref[...], b1_ref[...],
        w2q_ref[...], w2s_ref[0, 0], b2_ref[0, 0],
        refsort_ref[...], refcdf_ref[...], mean_ref[...], prec_ref[...],
        thr_ref[0, 0], temp_ref[0, 0],
        cat_ref[...], num_ref[...], maskf_ref[...],
    )
    preds_ref[...] = preds
    flags_ref[...] = flags
    counts_ref[...] = cat_counts
    ksp_ref[...] = ks_stat


def quant_fused(
    qparams: dict[str, Any],
    monitor: MonitorState,
    temperature: jnp.ndarray,
    cat_ids: jnp.ndarray,
    numeric: jnp.ndarray,
    mask: jnp.ndarray,
    use_kernel: bool | None = None,
    interpret: bool = False,
) -> tuple[jnp.ndarray, jnp.ndarray, jnp.ndarray]:
    """Fused quant predict for one padded request:
    ``(preds [B], flags [B], drift [D])`` — the same triple the exact
    tier's packed body produces, with the heavy body routed through the
    Pallas kernel or its jnp composite (module docstring: Routing)."""
    b = cat_ids.shape[0]
    maskf_row = mask.astype(jnp.float32)[None, :]
    temp_11 = jnp.asarray(temperature, jnp.float32).reshape(1, 1)
    core_args = (
        qparams["embed"], qparams["w1_q"],
        qparams["w1_s"][None, :], qparams["b1"][None, :],
        qparams["w2_q"][:, None],
        qparams["w2_s"].reshape(1, 1), qparams["b2"].reshape(1, 1),
        monitor.num_ref_sorted, monitor.num_ref_cdf,
        monitor.out_mean[None, :], monitor.out_precision,
        monitor.out_threshold.reshape(1, 1), temp_11,
        cat_ids, numeric, maskf_row,
    )
    c, k = qparams["embed"].shape[0], qparams["embed"].shape[1]
    m = numeric.shape[1]

    def kernel(*args):
        # Scalars ride SMEM; every tensor operand is a whole-array VMEM
        # block (grid=() — no index maps).
        smem = {5, 6, 11, 12}  # w2_s, b2, threshold, temperature
        return tuple(
            pl.pallas_call(
                _fused_kernel,
                in_specs=[
                    pl.BlockSpec(
                        memory_space=pltpu.SMEM if i in smem else pltpu.VMEM
                    )
                    for i in range(len(args))
                ],
                out_specs=[
                    pl.BlockSpec(memory_space=pltpu.VMEM) for _ in range(4)
                ],
                out_shape=[
                    jax.ShapeDtypeStruct((1, b), jnp.float32),
                    jax.ShapeDtypeStruct((1, b), jnp.float32),
                    jax.ShapeDtypeStruct((c, k), jnp.float32),
                    jax.ShapeDtypeStruct((1, m), jnp.float32),
                ],
                interpret=interpret,
                name="quant_fused",
            )(*args)
        )

    if use_kernel:
        outs = kernel(*core_args)
    elif use_kernel is None and b <= QUANT_KERNEL_MAX_ROWS:
        outs = tpu_kernel_or(kernel, _fused_core, *core_args)
    else:
        outs = _fused_core(*core_args)
    preds, flags, cat_counts, ks_stat = outs

    # P-value assembly + drift: tiny scalar math on [C,K]/[M] aggregates,
    # shared by both routes (same `1 - p` order as
    # `monitor.state.drift_scores`: cat then num). The Kolmogorov sf here
    # is exactly `ks_two_sample_small_masked`'s tail, applied outside the
    # kernel because its series constants can't live in one.
    _, cat_p = jax.vmap(chi2_two_sample)(monitor.cat_ref_counts, cat_counts)
    r = monitor.num_ref_sorted.shape[1]
    n_valid = jnp.maximum(mask.astype(jnp.float32).sum(), 1.0)
    en = jnp.sqrt(r * n_valid / (r + n_valid))
    ks_p = jax.vmap(
        lambda s: _kolmogorov_sf((en + 0.12 + 0.11 / en) * s)
    )(ks_stat[0])
    drift = 1.0 - jnp.concatenate([cat_p, ks_p])
    return preds[0], flags[0], drift


def make_quant_packed_base(
    use_kernel: bool | None = None, interpret: bool = False
) -> Callable:
    """Quant twin of `ops/predict.py make_packed_predict_base`: identical
    7-argument cacheable signature and ``f32[2B + D]`` packed layout
    (`packed_layout` slices it), with ``variables`` = the quant param
    dict. The engine serves it through the same exec tables, donation
    gate, and fetch paths as the exact tier."""

    def predict(
        qparams: dict[str, Any],
        monitor: MonitorState,
        acc: MonitorAccumulator,
        temperature: jnp.ndarray,
        cat_ids: jnp.ndarray,
        numeric: jnp.ndarray,
        mask: jnp.ndarray,
    ):
        preds, flags, drift = quant_fused(
            qparams, monitor, temperature, cat_ids, numeric, mask, use_kernel,
            interpret,
        )
        packed = jnp.concatenate([preds, flags, drift])
        return packed, fold_accumulator(acc, flags, drift, mask)

    return predict


def make_quant_grouped_base(
    use_kernel: bool | None = None, interpret: bool = False
) -> Callable:
    """Quant twin of `make_packed_grouped_base`: ``f32[S, 2R+D]`` packed
    group output, per-request drift over each slot's OWN rows (the vmap
    batches the pallas_call over slots), accumulator folded outside the
    vmap."""

    def single(qparams, monitor, temperature, cat_ids, numeric, mask):
        return quant_fused(
            qparams, monitor, temperature, cat_ids, numeric, mask, use_kernel,
            interpret,
        )

    def grouped(
        qparams: dict[str, Any],
        monitor: MonitorState,
        acc: MonitorAccumulator,
        temperature: jnp.ndarray,
        cat_ids: jnp.ndarray,
        numeric: jnp.ndarray,
        mask: jnp.ndarray,
    ):
        preds, flags, drift = jax.vmap(
            single, in_axes=(None, None, None, 0, 0, 0)
        )(qparams, monitor, temperature, cat_ids, numeric, mask)
        packed = jnp.concatenate([preds, flags, drift], axis=1)
        return packed, fold_accumulator_grouped(acc, flags, drift, mask)

    return grouped
