"""Gated promotion: evaluate the shadow evidence, then hot-swap (or not).

Three gates, all spelled out in the decision so an operator can read WHY
a candidate shipped or died (`lifecycle.*` knobs in config.py):

- **AUC**: candidate ROC-AUC on the labeled holdout may trail the
  incumbent's by at most ``max_auc_drop`` (the epsilon) — a candidate
  failing this gate never swaps in.
- **Calibration**: candidate expected calibration error (ECE, equal-width
  bins) must stay under ``max_ece`` — honest probabilities are part of
  the serving contract (the bundle ships temperature-scaled).
- **Latency**: candidate p99 on the mirrored/holdout request shapes must
  stay within ``max_p99_ratio`` x the incumbent's p99 on the same shapes
  (relative, so the gate is meaningful on any backend).

Promotion itself is `InferenceEngine.swap_bundle` — an in-place exec
table + params ref-swap under the engine's existing ``_compile_lock`` ->
``_acc_lock`` discipline, bit-stable for in-flight requests, with the
outgoing state retained so ``rollback_engine`` restores it in one call.

The metric helpers are numpy-only (no jax import) so the gate math runs
identically in the serve process, the offline ``mlops-tpu lifecycle``
pass, and the tests.
"""

from __future__ import annotations

import dataclasses

import numpy as np

from mlops_tpu.config import LifecycleConfig
from mlops_tpu.lifecycle.shadow import ShadowEngine, ShadowReport


def roc_auc_np(scores: np.ndarray, labels: np.ndarray) -> float:
    """ROC-AUC via the Mann-Whitney U statistic with average ranks for
    ties — the numpy twin of `train/metrics.py roc_auc` (same semantics,
    no device program), for gate evaluation off the compiled path."""
    scores = np.asarray(scores, np.float64)
    labels = np.asarray(labels, np.float64)
    n = scores.shape[0]
    if n == 0:
        return 0.5
    order = np.argsort(scores, kind="mergesort")
    sorted_scores = scores[order]
    first = np.searchsorted(sorted_scores, scores, side="left")
    last = np.searchsorted(sorted_scores, scores, side="right")
    ranks = (first + last + 1.0) / 2.0
    n_pos = labels.sum()
    n_neg = n - n_pos
    if n_pos == 0 or n_neg == 0:
        return 0.5
    u = float((ranks * labels).sum()) - n_pos * (n_pos + 1.0) / 2.0
    return float(u / (n_pos * n_neg))


def expected_calibration_error(
    probs: np.ndarray, labels: np.ndarray, bins: int = 10
) -> float:
    """ECE over equal-width probability bins: sum_b (n_b/N) *
    |mean confidence_b - empirical rate_b| — the standard gap between
    what the model says and what happens."""
    probs = np.asarray(probs, np.float64)
    labels = np.asarray(labels, np.float64)
    if probs.size == 0:
        return 0.0
    edges = np.linspace(0.0, 1.0, bins + 1)
    idx = np.clip(np.digitize(probs, edges[1:-1]), 0, bins - 1)
    ece = 0.0
    for b in range(bins):
        sel = idx == b
        n_b = int(sel.sum())
        if not n_b:
            continue
        ece += (n_b / probs.size) * abs(
            float(probs[sel].mean()) - float(labels[sel].mean())
        )
    return float(ece)


@dataclasses.dataclass(frozen=True)
class GateDecision:
    passed: bool
    reasons: tuple[str, ...]  # every FAILED gate, named with its numbers

    def as_dict(self) -> dict:
        return {"passed": self.passed, "reasons": list(self.reasons)}


def evaluate_gates(
    report: ShadowReport, config: LifecycleConfig
) -> GateDecision:
    """The three gates over one shadow report. Latency is skipped (passes)
    when neither side has samples — the offline CLI pass has no mirrored
    traffic and must still be able to grade AUC/ECE."""
    reasons: list[str] = []
    if report.auc_delta < -config.max_auc_drop:
        reasons.append(
            f"auc: candidate {report.auc_candidate:.4f} trails incumbent "
            f"{report.auc_incumbent:.4f} by {-report.auc_delta:.4f} > "
            f"epsilon {config.max_auc_drop:g}"
        )
    if report.ece_candidate > config.max_ece:
        reasons.append(
            f"calibration: candidate ECE {report.ece_candidate:.4f} > "
            f"bound {config.max_ece:g}"
        )
    if report.p99_incumbent_ms > 0 and (
        report.p99_candidate_ms
        > config.max_p99_ratio * report.p99_incumbent_ms
    ):
        reasons.append(
            f"latency: candidate p99 {report.p99_candidate_ms:.2f} ms > "
            f"{config.max_p99_ratio:g}x incumbent "
            f"{report.p99_incumbent_ms:.2f} ms"
        )
    return GateDecision(passed=not reasons, reasons=tuple(reasons))


def quant_tier_gates(
    fidelity: dict[str, float], config: LifecycleConfig
) -> GateDecision:
    """The promotion-gate discipline applied to the QUANTIZED student tier
    at packaging time (`train/distill.py distill_quant_student`).

    Same knobs, same semantics as `evaluate_gates`, different evidence:
    the quant tier never shadows live traffic — its AUC delta vs the
    teacher and its calibrated ECE come from the held-out validation
    split, post-quantization. The decision is STAMPED into the bundle's
    quant manifest block, and `serve/engine.py` refuses to serve (or
    auto-route to) a quant tier whose stamped decision failed — the gate
    runs once where the labels are, not on every engine boot. Latency has
    no gate here: the tier exists to be faster, and no cell of the
    benchmark measures it yet (ROADMAP C6)."""
    reasons: list[str] = []
    delta = fidelity.get("roc_auc_delta")
    if delta is None:
        reasons.append(
            "auc: no labeled validation split — the quant tier cannot be "
            "graded and must not serve"
        )
    elif delta < -config.max_auc_drop:
        reasons.append(
            f"auc: quant student trails the teacher by {-delta:.4f} > "
            f"epsilon {config.max_auc_drop:g}"
        )
    ece = fidelity.get("ece")
    if ece is not None and ece > config.max_ece:
        reasons.append(
            f"calibration: quant ECE {ece:.4f} > bound {config.max_ece:g}"
        )
    return GateDecision(passed=not reasons, reasons=tuple(reasons))


def promote_engine(live, shadow: ShadowEngine) -> int:
    """Install the shadowed candidate into the live engine (zero-downtime
    ref-swap; the candidate engine's device state and warmed exec table
    move in wholesale). Returns the new bundle generation."""
    return live.swap_bundle(shadow.engine)


def rollback_engine(live) -> int:
    """One-call instant rollback to the retained previous bundle."""
    return live.rollback()
