"""Hummingbird-style tensorization of the HistGBM baseline (ISSUE 19).

The gbm family (`models/gbm.py SklearnBaseline`) was the one `models/`
family outside the packed serving contract: trees scored on host CPU
through `make_hybrid_predict_fn` while every Flax family (and the quant
student) rode the one packed 7-arg cacheable program. Hummingbird
(PAPERS.md, arxiv 2010.04804) shows tree ensembles compile to pure
tensor programs; this module does that for the fitted
``HistGradientBoostingClassifier``:

- ``extract_gbm`` flattens the fitted ensemble into padded per-tree node
  arrays (value / threshold / child pointers / leaf + categorical flags)
  plus a per-node 256-entry categorical go-left LUT built from the
  estimator's raw category bitsets — pure data, shaped ``[T, Nmax]``.
- ``make_gbm_packed_base`` / ``make_gbm_grouped_base`` are the packed
  program builders in the SAME cacheable 7-arg form as
  `ops/predict.py make_packed_predict_base`: the tree tensors are the
  ``variables`` ARGUMENT (never a closure), the monitors fuse alongside,
  one flat f32 output buffer + the device monitor accumulator.

Traversal is a depth-many static gather loop: each step gathers every
tree's current node fields at once (``[B, T]`` advanced indexing),
resolves the split (numeric ``x <= threshold``; categorical via the LUT
with sklearn's unknown-category -> missing_go_to_left rule; NaN ->
missing side), and advances the node index — leaves self-loop, so a
ragged ensemble needs no per-tree control flow.

BIT PARITY: sklearn compares raw f64 feature values against f64
thresholds and accumulates f64 leaf values tree-by-tree onto the
baseline, then ``expit``s. The program reproduces exactly that — f64
compares, the SAME serial tree-accumulation order (XLA preserves the
explicit add chain), ``1/(1+exp(-s))`` on the f64 score — so
``predictions.astype(f32)`` is bit-identical to
``SklearnBaseline.predict_proba`` (pinned in tests/test_gbm_tensor.py),
including unknown / out-of-range / non-integer category values. The f64
compute requires tracing, lowering, AND ``device_put`` of the tree
tensors inside a ``jax.enable_x64(True)`` context (thread-local —
concurrent f32 dispatches on other threads are unaffected); the compiled executable itself runs fine outside it. The
monitors stay f32 by the explicit dtype pins in `ops/drift.py` /
`ops/outlier.py`, so the packed buffer is one f32 vector exactly like
the other tiers.
"""

from __future__ import annotations

import contextlib
import dataclasses
from typing import Any, Callable

import numpy as np

# The tensorized layout's format tag: part of the compile-cache config
# hash (compilecache/warmup.py serve_gbm_jobs) so a layout change here can
# never collide with a persisted executable of the old layout.
GBM_FORMAT = "gbm-gather-v1"

# Raw category ids the LUT covers — HistGBM itself bins categories into
# [0, 255] (its bitsets are 8x uint32 words), so any raw value outside
# the LUT range is by construction unknown -> missing_go_to_left.
_CAT_LUT_SIZE = 256


@dataclasses.dataclass(frozen=True)
class GbmGeometry:
    """Static shape facts of one tensorized ensemble — everything the
    traced program's structure depends on beyond the aval shapes. Rides
    the compile-cache config hash."""

    n_trees: int
    max_nodes: int
    depth: int  # static traversal iterations = deepest decision path


def x64_context():
    """The thread-local double-precision context every gbm-tensor trace,
    lowering, and ``device_put`` of tree tensors must run inside (entering
    it inside an f32 trace is a type error; committed f64 arrays fed to a
    non-x64 jit silently downcast)."""
    import jax

    return jax.enable_x64(True)


def device_put_x64(tree: Any) -> Any:
    """``jax.device_put`` under the x64 context — f64 leaves stay f64."""
    import jax

    with x64_context():
        return jax.device_put(tree)


def _unwrap(estimator: Any) -> Any:
    """Accept either the raw sklearn estimator or the zoo's
    `models/gbm.py SklearnBaseline` wrapper (what bundles expose)."""
    return getattr(estimator, "estimator", estimator)


def supports_gbm_tensorization(estimator: Any) -> bool:
    """True when ``estimator`` is (or wraps) a fitted binary
    HistGradientBoostingClassifier this module can lower (the rf family
    keeps the host hybrid path: unbinned deep forests explode Nmax)."""
    estimator = _unwrap(estimator)
    predictors = getattr(estimator, "_predictors", None)
    if not predictors:
        return False
    classes = getattr(estimator, "classes_", None)
    if classes is None or len(classes) != 2:
        return False
    return hasattr(estimator, "_bin_mapper")


def _bit(bitset_row: np.ndarray, value: int) -> bool:
    return bool((int(bitset_row[value // 32]) >> (value % 32)) & 1)


def extract_gbm(estimator: Any) -> tuple[dict[str, np.ndarray], GbmGeometry]:
    """Fitted HistGBM -> (tree-tensor ``variables`` pytree, geometry).

    The returned dict is the packed program's ``variables`` argument:

    - ``value``      f64  [T, N]  leaf values (0 on decision/pad nodes)
    - ``threshold``  f64  [T, N]  numeric split thresholds
    - ``feature``    i32  [T, N]  split feature column in [cat | numeric]
    - ``left/right`` i32  [T, N]  child node indices
    - ``is_leaf``    bool [T, N]  (padding nodes are leaves: they
                                   self-loop harmlessly, value 0, and are
                                   unreachable from node 0 anyway)
    - ``is_cat``     bool [T, N]  categorical split?
    - ``mgtl``       bool [T, N]  missing_go_to_left
    - ``cat_go_left`` bool [T, N, 256] per-node LUT: go left for raw
      category v? sklearn semantics baked in: v in the split's raw
      bitset if v is a KNOWN category of that feature, else the missing
      side (unknown categories follow missing_go_to_left)
    - ``baseline``   f64  []     the ensemble's baseline prediction
    """
    estimator = _unwrap(estimator)
    predictors = [trees[0] for trees in estimator._predictors]
    baseline = float(np.asarray(estimator._baseline_prediction).ravel()[0])
    known_bitsets, f_idx_map = (
        estimator._bin_mapper.make_known_categories_bitsets()
    )

    n_trees = len(predictors)
    max_nodes = max(p.nodes.shape[0] for p in predictors)
    value = np.zeros((n_trees, max_nodes), np.float64)
    threshold = np.zeros((n_trees, max_nodes), np.float64)
    feature = np.zeros((n_trees, max_nodes), np.int32)
    left = np.zeros((n_trees, max_nodes), np.int32)
    right = np.zeros((n_trees, max_nodes), np.int32)
    is_leaf = np.ones((n_trees, max_nodes), bool)  # padding = leaf
    is_cat = np.zeros((n_trees, max_nodes), bool)
    mgtl = np.zeros((n_trees, max_nodes), bool)
    cat_go_left = np.zeros((n_trees, max_nodes, _CAT_LUT_SIZE), bool)

    depth = 1
    for t, pred in enumerate(predictors):
        nodes = pred.nodes
        n = nodes.shape[0]
        value[t, :n] = nodes["value"]
        threshold[t, :n] = nodes["num_threshold"]
        feature[t, :n] = nodes["feature_idx"]
        left[t, :n] = nodes["left"]
        right[t, :n] = nodes["right"]
        is_leaf[t, :n] = nodes["is_leaf"].astype(bool)
        mgtl[t, :n] = nodes["missing_go_to_left"].astype(bool)
        cat_mask = nodes["is_categorical"].astype(bool)
        is_cat[t, :n] = cat_mask
        for i in np.nonzero(cat_mask)[0]:
            raw_bits = pred.raw_left_cat_bitsets[int(nodes["bitset_idx"][i])]
            known_row = known_bitsets[int(f_idx_map[nodes["feature_idx"][i]])]
            miss = bool(nodes["missing_go_to_left"][i])
            for v in range(_CAT_LUT_SIZE):
                cat_go_left[t, i, v] = (
                    _bit(raw_bits, v) if _bit(known_row, v) else miss
                )
        # Decision depth of this tree: longest root->leaf path.
        node_depth = np.zeros(n, np.int32)
        for i in range(n):  # parents precede children in the node array
            if not is_leaf[t, i]:
                for child in (int(left[t, i]), int(right[t, i])):
                    node_depth[child] = max(
                        node_depth[child], node_depth[i] + 1
                    )
        depth = max(depth, int(node_depth.max()))

    variables = {
        "value": value,
        "threshold": threshold,
        "feature": feature,
        "left": left,
        "right": right,
        "is_leaf": is_leaf,
        "is_cat": is_cat,
        "mgtl": mgtl,
        "cat_go_left": cat_go_left,
        "baseline": np.float64(baseline),
    }
    return variables, GbmGeometry(
        n_trees=n_trees, max_nodes=max_nodes, depth=depth
    )


def gbm_raw_scores(variables: dict, depth: int, cat_ids, numeric):
    """The tensorized ensemble's raw f64 decision scores for one batch —
    the gather/compare traversal described in the module docstring. Must
    be traced under ``x64_context()``."""
    import jax.numpy as jnp

    # Exactly models/gbm.py _design_matrix_arrays: [cat_ids | numeric] as
    # f64 (int32 ids and f32 numerics widen exactly, so the compares see
    # bit-for-bit sklearn's inputs).
    from jax import lax

    xall = jnp.concatenate(
        [cat_ids.astype(jnp.float64), numeric.astype(jnp.float64)], axis=1
    )
    n_trees = variables["value"].shape[0]
    # [B, T] tree-axis gather index, broadcast EXPLICITLY (lax, not jnp:
    # jnp.broadcast_to short-circuits at B=1, eliding the broadcast eqn
    # and making the traced program bucket-polymorphic — TPU304).
    rows = lax.broadcast_in_dim(
        jnp.arange(n_trees, dtype=jnp.int32),
        (xall.shape[0], n_trees),
        (1,),
    )
    idx = jnp.zeros((xall.shape[0], n_trees), jnp.int32)
    for _ in range(depth):
        leaf = variables["is_leaf"][rows, idx]
        feat = variables["feature"][rows, idx]
        thr = variables["threshold"][rows, idx]
        miss = variables["mgtl"][rows, idx]
        cat = variables["is_cat"][rows, idx]
        xv = jnp.take_along_axis(xall, feat, axis=1)
        # Categorical resolution: integral raw values inside the LUT
        # range read the per-node LUT (which already encodes the
        # unknown-category -> missing rule); anything else is unknown.
        vi = jnp.clip(xv, 0, _CAT_LUT_SIZE - 1).astype(jnp.int32)
        in_range = (
            (xv >= 0) & (xv < _CAT_LUT_SIZE) & (xv == jnp.floor(xv))
        )
        cat_go = variables["cat_go_left"][rows, idx, vi]
        go_left = jnp.where(
            jnp.isnan(xv),
            miss,
            jnp.where(
                cat,
                jnp.where(in_range, cat_go, miss),
                xv <= thr,
            ),
        )
        nxt = jnp.where(
            go_left, variables["left"][rows, idx], variables["right"][rows, idx]
        )
        idx = jnp.where(leaf, idx, nxt)
    leaf_values = variables["value"][rows, idx]  # [B, T] f64
    # Serial accumulation in tree order — sklearn adds one iteration's
    # predictions at a time onto the baseline, and XLA preserves this
    # explicit add chain, so the f64 sum is bit-identical (a tree-axis
    # reduction could reassociate).
    score = variables["baseline"] + leaf_values[:, 0]
    for t in range(1, n_trees):
        score = score + leaf_values[:, t]
    return score


def _gbm_predictions(variables, depth, temperature, cat_ids, numeric):
    """Raw traversal -> the hybrid path's EXACT f32 probabilities.

    The host hybrid (`ops/predict.py make_hybrid_predict_fn`) computes
    ``apply_temperature(predict_proba(X), T)`` — expit of the raw f64
    score, one narrowing cast to f32, and then (only when T != 1.0) the
    clipped-logit rescale ``sigmoid(logit(clip(p)) / T)`` of
    `train/calibrate.py`, narrowed again on assignment into the f32
    output. This reproduces both branches bit-for-bit; ``temperature``
    is a traced argument, so the T==1 shortcut becomes a select. The
    engine passes T as a f64 scalar (the gbm tier's one dtype deviation
    from the packed contract): the host hybrid divides by the FULL
    python float, and an f32 rounding of T shifts tempered
    probabilities by one ulp."""
    import jax.numpy as jnp

    from mlops_tpu.train.calibrate import PROB_EPS

    raw = gbm_raw_scores(variables, depth, cat_ids, numeric)
    # expit on the f64 raw score (sklearn's exact arithmetic), then one
    # narrowing cast — bit-identical to predict_proba's f32 view.
    p32 = (1.0 / (1.0 + jnp.exp(-raw))).astype(jnp.float32)
    t64 = temperature.astype(jnp.float64)
    p64 = jnp.clip(p32.astype(jnp.float64), PROB_EPS, 1.0 - PROB_EPS)
    logits = jnp.log(p64) - jnp.log1p(-p64)
    tempered = (1.0 / (1.0 + jnp.exp(-logits / t64))).astype(jnp.float32)
    return jnp.where(temperature == jnp.float32(1.0), p32, tempered)


def make_gbm_packed_base(depth: int) -> Callable:
    """The gbm-tensor tier's packed program in the one cacheable 7-arg
    serving form (`ops/predict.py make_packed_predict_base` contract):
    tree tensors as ``variables``, one flat ``f32[2B + D]`` output, the
    monitor accumulator folded on device. ``depth`` is static program
    structure (GbmGeometry — part of the cache config hash)."""
    import jax.numpy as jnp

    from mlops_tpu.monitor.state import (
        drift_scores,
        fold_accumulator,
        outlier_flags,
    )

    def predict(
        variables: dict,
        monitor,
        acc,
        temperature,
        cat_ids,
        numeric,
        mask,
    ):
        preds = _gbm_predictions(variables, depth, temperature, cat_ids, numeric)
        flags = outlier_flags(monitor, numeric, mask)
        drift = drift_scores(monitor, cat_ids, numeric, mask)
        packed = jnp.concatenate([preds, flags, drift])
        return packed, fold_accumulator(acc, flags, drift, mask)

    return predict


def make_gbm_grouped_base(depth: int) -> Callable:
    """Packed grouped (vmapped) form — `make_packed_grouped_base` shape
    contract: ``f32[S, 2R + D]`` slots, accumulator folded across the
    group outside the vmap."""
    import jax
    import jax.numpy as jnp

    from mlops_tpu.monitor.state import (
        drift_scores,
        fold_accumulator_grouped,
        outlier_flags,
    )

    def single(variables, monitor, temperature, cat_ids, numeric, mask):
        return (
            _gbm_predictions(variables, depth, temperature, cat_ids, numeric),
            outlier_flags(monitor, numeric, mask),
            drift_scores(monitor, cat_ids, numeric, mask),
        )

    def grouped(variables, monitor, acc, temperature, cat_ids, numeric, mask):
        preds, flags, drift = jax.vmap(
            single, in_axes=(None, None, None, 0, 0, 0)
        )(variables, monitor, temperature, cat_ids, numeric, mask)
        packed = jnp.concatenate([preds, flags, drift], axis=1)
        return packed, fold_accumulator_grouped(acc, flags, drift, mask)

    return grouped


def abstract_gbm_variables(geometry: GbmGeometry) -> dict:
    """ShapeDtypeStruct twin of `extract_gbm`'s variables tree at one
    geometry — what the Layer-2 analyzer traces against (the compile-cache
    warmers use real fitted trees: the geometry is a fact of the fitted
    ensemble, so there is no config-only abstract warmup)."""
    import jax

    S = jax.ShapeDtypeStruct
    t, n = geometry.n_trees, geometry.max_nodes
    return {
        "value": S((t, n), np.float64),
        "threshold": S((t, n), np.float64),
        "feature": S((t, n), np.int32),
        "left": S((t, n), np.int32),
        "right": S((t, n), np.int32),
        "is_leaf": S((t, n), np.bool_),
        "is_cat": S((t, n), np.bool_),
        "mgtl": S((t, n), np.bool_),
        "cat_go_left": S((t, n, _CAT_LUT_SIZE), np.bool_),
        "baseline": S((), np.float64),
    }


def gbm_reference_proba(
    variables: dict, geometry: GbmGeometry, cat_ids, numeric
) -> np.ndarray:
    """The jnp-composite reference: run the traversal eagerly under the
    x64 context and return f32 probabilities — the bit-parity bridge the
    tests pin against BOTH `SklearnBaseline.predict_proba` and the
    compiled packed program."""
    import jax.numpy as jnp

    with x64_context():
        raw = gbm_raw_scores(
            variables,
            geometry.depth,
            jnp.asarray(np.asarray(cat_ids, np.int32)),
            jnp.asarray(np.asarray(numeric, np.float32)),
        )
        return np.asarray((1.0 / (1.0 + jnp.exp(-raw))).astype(jnp.float32))


def gbm_fingerprint(geometry: GbmGeometry) -> str:
    """Compile-cache config hash for the gbm entries: the layout format
    tag + the static geometry the traced program bakes in, plus an
    explicit x64 marker (the programs are lowered inside the x64 context,
    while `keys.environment_fingerprint` reads the ambient flag — the
    marker keeps f64 artifacts keyed apart regardless of when the key was
    computed relative to the context)."""
    from mlops_tpu.compilecache.keys import model_fingerprint

    return model_fingerprint(
        ("gbm-tensor", GBM_FORMAT, "x64", dataclasses.asdict(geometry))
    )


@contextlib.contextmanager
def _noop():
    yield


def trace_context(tier: str):
    """The tracing/lowering context a tier's programs require: the x64
    context for the gbm-tensor tier, a no-op for everything else — the
    engine and warmup wrap compiles in this so tier routing stays one
    code path."""
    return x64_context() if tier == "gbm" else _noop()
