"""The fused predict function — the serving hot path.

The reference's hot path runs three detectors **serially** on CPU inside
``CustomModel.predict`` (`02-register-model.ipynb:330-353`: classifier
``predict_proba``, then ``drift.predict``, then ``outliers.predict``). Here
all three are one XLA computation: the classifier's matmuls dominate, the
Mahalanobis score shares the same batch in registers/VMEM, and the drift
reductions fuse alongside — a single dispatch, a single host->device->host
round trip per request batch.
"""

from __future__ import annotations

from typing import Any, Callable

import jax
import jax.numpy as jnp
import numpy as np

from mlops_tpu.monitor.state import (
    MonitorAccumulator,
    MonitorState,
    drift_scores,
    fold_accumulator,
    fold_accumulator_grouped,
    outlier_flags,
)
from mlops_tpu.train.calibrate import apply_temperature


def make_predict_fn(
    bundle,
) -> Callable[[jnp.ndarray, jnp.ndarray], dict[str, jnp.ndarray]]:
    """Build the jitted fused predict for a loaded (flax-flavor) bundle:
    (cat_ids, numeric) -> response arrays.

    Returns a function producing the reference's response fields
    (`app/model.py:64-70`): ``predictions`` (P(default) per row),
    ``outliers`` (0/1 per row), ``feature_drift_batch`` (per-feature
    ``1 - p_val`` scores for the batch). Takes the whole bundle so the
    fitted calibration temperature (train/calibrate.py) cannot be
    forgotten — the lower-level ``make_*_predict_fn`` builders are for
    the engine, which resolves it once.
    """
    model, variables, monitor = bundle.model, bundle.variables, bundle.monitor
    temperature = bundle.temperature

    @jax.jit
    def predict(cat_ids: jnp.ndarray, numeric: jnp.ndarray):
        logits = model.apply(variables, cat_ids, numeric, train=False)
        return {
            "predictions": jax.nn.sigmoid(logits / temperature),
            "outliers": outlier_flags(monitor, numeric),
            "feature_drift_batch": drift_scores(monitor, cat_ids, numeric),
        }

    return predict


def make_padded_predict_base(model) -> Callable:
    """The serving hot-path program in its CACHEABLE form: everything the
    executable depends on beyond the model architecture — params, monitor
    state, calibration temperature — is an ARGUMENT, never a closure. A
    closed-over array would be baked into the serialized executable as a
    constant, and a persistent compile cache (`compilecache/`) keyed on
    shapes alone would then silently serve a stale model; with args, the
    abstract signature carries the shapes and the values flow per call.
    """

    def predict(
        variables: Any,
        monitor: MonitorState,
        temperature: jnp.ndarray,
        cat_ids: jnp.ndarray,
        numeric: jnp.ndarray,
        mask: jnp.ndarray,
    ):
        logits = model.apply(variables, cat_ids, numeric, train=False)
        return {
            "predictions": jax.nn.sigmoid(logits / temperature),
            "outliers": outlier_flags(monitor, numeric, mask),
            "feature_drift_batch": drift_scores(monitor, cat_ids, numeric, mask),
        }

    return predict


def make_grouped_predict_base(model) -> Callable:
    """Cacheable form of the micro-batcher's vmapped program (same
    argument discipline as ``make_padded_predict_base``): params/monitor/
    temperature broadcast across the request axis, per-request drift stays
    computed over each request's OWN rows."""

    def single(variables, monitor, temperature, cat_ids, numeric, mask):
        logits = model.apply(variables, cat_ids, numeric, train=False)
        return {
            "predictions": jax.nn.sigmoid(logits / temperature),
            "outliers": outlier_flags(monitor, numeric, mask),
            "feature_drift_batch": drift_scores(monitor, cat_ids, numeric, mask),
        }

    def grouped(variables, monitor, temperature, cat_ids, numeric, mask):
        return jax.vmap(single, in_axes=(None, None, None, 0, 0, 0))(
            variables, monitor, temperature, cat_ids, numeric, mask
        )

    return grouped


def make_packed_predict_base(model) -> Callable:
    """The serving hot path's ZERO-WASTE form: one contiguous f32 output
    buffer plus the device-resident monitor aggregate.

    The dict form (`make_padded_predict_base`) returns a 3-leaf pytree, so
    every request pays THREE device->host transfers (`serve/engine.py`).
    Here the program emits a single ``f32[2*B + D]`` vector laid out as

        [0 : B]        predictions  (P(default) per padded row)
        [B : 2B]       outlier flags (0/1, mask-zeroed)
        [2B : 2B + D]  per-batch drift scores in schema order

    sliced host-side by `packed_layout`, so the whole response is ONE D2H
    buffer — and the running monitor aggregate (`MonitorAccumulator`) is
    folded in the same fused program and STAYS on the device (the second
    output; the engine threads it through as a donated argument where the
    backend's donation gate allows). Same cacheable argument discipline as
    the dict form: everything beyond the architecture is an ARGUMENT.

    Numerics are bit-identical to the dict form: the three sub-programs
    are unchanged, the concatenation is layout only (pinned by the packed
    parity test)."""

    def predict(
        variables: Any,
        monitor: MonitorState,
        acc: MonitorAccumulator,
        temperature: jnp.ndarray,
        cat_ids: jnp.ndarray,
        numeric: jnp.ndarray,
        mask: jnp.ndarray,
    ):
        logits = model.apply(variables, cat_ids, numeric, train=False)
        flags = outlier_flags(monitor, numeric, mask)
        drift = drift_scores(monitor, cat_ids, numeric, mask)
        packed = jnp.concatenate(
            [jax.nn.sigmoid(logits / temperature), flags, drift]
        )
        return packed, fold_accumulator(acc, flags, drift, mask)

    return predict


def make_packed_grouped_base(model) -> Callable:
    """Packed form of the micro-batcher's vmapped program: ``f32[S, 2R+D]``
    (each slot's predictions ‖ outliers ‖ drift), monitor aggregate folded
    across the group's non-empty slots outside the vmap. Per-request drift
    stays computed over each request's OWN rows, exactly as the dict form."""

    def single(variables, monitor, temperature, cat_ids, numeric, mask):
        logits = model.apply(variables, cat_ids, numeric, train=False)
        return (
            jax.nn.sigmoid(logits / temperature),
            outlier_flags(monitor, numeric, mask),
            drift_scores(monitor, cat_ids, numeric, mask),
        )

    def grouped(
        variables: Any,
        monitor: MonitorState,
        acc: MonitorAccumulator,
        temperature: jnp.ndarray,
        cat_ids: jnp.ndarray,
        numeric: jnp.ndarray,
        mask: jnp.ndarray,
    ):
        preds, flags, drift = jax.vmap(
            single, in_axes=(None, None, None, 0, 0, 0)
        )(variables, monitor, temperature, cat_ids, numeric, mask)
        packed = jnp.concatenate([preds, flags, drift], axis=1)
        return packed, fold_accumulator_grouped(acc, flags, drift, mask)

    return grouped


def packed_layout(rows: int) -> tuple[slice, slice, slice]:
    """(predictions, outliers, drift) slices of a packed row vector of
    ``rows`` padded rows — the ONE definition of the buffer layout shared
    by the engine's host-side unpack and the tests."""
    from mlops_tpu.schema import SCHEMA

    d = SCHEMA.num_categorical + SCHEMA.num_numeric
    return (
        slice(0, rows),
        slice(rows, 2 * rows),
        slice(2 * rows, 2 * rows + d),
    )


# Donation argnums of the packed programs: the monitor accumulator
# (position 2) updates in place.
ACC_DONATION = (2,)


def _bind_serving_args(base: Callable, variables, monitor, temperature):
    """Close a base program over one bundle's state, jitted, preserving the
    old ``(cat_ids, numeric, mask)`` call surface. ``__wrapped__`` exposes
    the unjitted bound function (checkify audits re-wrap it).

    The bound state is ``device_put`` ONCE here: params/monitor are now
    per-call ARGUMENTS (the cacheable form), and host numpy arrays would
    re-pay the full host->device param transfer on EVERY request —
    committed device arrays transfer once and are passed by reference.
    (No-op when the caller already placed them, e.g. the engine.)"""
    jitted = jax.jit(base)
    variables = jax.device_put(variables)
    monitor = jax.device_put(monitor)
    t = jax.device_put(np.float32(temperature))

    def predict(cat_ids, numeric, mask):
        return jitted(variables, monitor, t, cat_ids, numeric, mask)

    def raw(cat_ids, numeric, mask):
        return base(variables, monitor, t, cat_ids, numeric, mask)

    predict.__wrapped__ = raw
    return predict


def make_padded_predict_fn(
    model, variables: Any, monitor: MonitorState, temperature: float = 1.0
) -> Callable[[jnp.ndarray, jnp.ndarray, jnp.ndarray], dict[str, jnp.ndarray]]:
    """Fused predict for serving: takes a row-validity mask so batches padded
    to fixed bucket sizes produce statistics identical to the unpadded batch
    (one compiled program per bucket size, zero recompiles in steady state).
    Built on ``make_padded_predict_base`` so the engine's AOT compile-cache
    path and this bound convenience form share ONE program definition.
    """
    return _bind_serving_args(
        make_padded_predict_base(model), variables, monitor, temperature
    )


def make_grouped_predict_fn(
    model, variables: Any, monitor: MonitorState, temperature: float = 1.0
) -> Callable[[jnp.ndarray, jnp.ndarray, jnp.ndarray], dict[str, jnp.ndarray]]:
    """Vmapped fused predict for the micro-batching queue: R concurrent
    requests ride ONE device dispatch as ``[R, B, ...]`` stacks, and the
    per-request vmap keeps every request's drift statistics computed over
    its OWN rows — identical responses to R separate calls, ~1 dispatch
    instead of R. (The reference serves strictly one request per model
    call, `app/main.py:72`.)
    """
    return _bind_serving_args(
        make_grouped_predict_base(model), variables, monitor, temperature
    )


def make_hybrid_predict_fn(
    estimator, monitor: MonitorState, temperature: float = 1.0
) -> Callable[[jnp.ndarray, jnp.ndarray, jnp.ndarray], dict[str, Any]]:
    """Fused predict for the sklearn-flavor bundle (BASELINE config 1 floor).

    The tree ensemble scores on host CPU (trees don't map to the MXU) while
    the drift + outlier monitors stay one jitted device computation — same
    response contract and padding/mask semantics as the Flax path, so the
    engine serves both flavors identically.
    """

    @jax.jit
    def monitors(cat_ids: jnp.ndarray, numeric: jnp.ndarray, mask: jnp.ndarray):
        return {
            "outliers": outlier_flags(monitor, numeric, mask),
            "feature_drift_batch": drift_scores(monitor, cat_ids, numeric, mask),
        }

    def predict(cat_ids, numeric, mask):
        import numpy as np

        out = dict(monitors(cat_ids, numeric, mask))
        # Score only valid rows on the host (padding would waste tree
        # inference); scatter back so the output length matches the bucket.
        valid = np.asarray(mask)
        probs = np.zeros(valid.shape[0], np.float32)
        p = estimator.predict_proba(
            np.asarray(cat_ids)[valid], np.asarray(numeric)[valid]
        )
        probs[valid] = apply_temperature(p, temperature)
        out["predictions"] = probs
        return out

    return predict
