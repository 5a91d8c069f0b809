"""The framework's registered jitted entry points for the trace layer.

Each entry wraps a REAL production builder (not a re-implementation) so the
jaxpr the analyzer inspects is the program production compiles:

- ``train-step-dense``   — `train/loop.py make_train_window` (the scan the
  `train` CLI runs), traced at two dataset sizes.
- ``train-step-tp``      — `parallel/steps.py make_sharded_train_step`
  (the DP×TP pjit step); needs a multi-device mesh, skipped (loudly) on
  single-device hosts.
- ``serve-predict-packed`` — `ops/predict.py make_packed_predict_base`
  (the serving hot path in its packed single-buffer cacheable form: one
  flat f32 output + the device monitor accumulator), traced at every
  warmup bucket the engine compiles. The lifecycle shadow's candidate
  warmup (`lifecycle/shadow.py`) is THIS entry too: params ride as
  arguments, so an identical-architecture candidate shares the
  incumbent's executables outright, and an architecture change warms
  through `compilecache/warmup.py serve_predict_jobs` — the same
  registered entry id, so the warmers/registry sync test keeps pinning
  ``CACHE_ENTRY_IDS`` with no lifecycle-private program anywhere.
- ``serve-predict-group-packed`` — `ops/predict.py
  make_packed_grouped_base` (the micro-batcher's packed vmapped
  dispatch), traced across slot buckets.
- ``serve-predict-quant-packed`` / ``serve-predict-quant-group-packed`` —
  `ops/quant_kernel.py make_quant_packed_base` /
  ``make_quant_grouped_base`` (the int8/bf16 quantized student tier in
  the same packed 7-arg cacheable form; Pallas-fused on TPU, traced here
  through the jnp composite route, which is the same program family the
  parity tests pin bit-identical).
- ``serve-predict-gbm-packed`` / ``serve-predict-gbm-group-packed`` —
  `ops/gbm_tensor.py make_gbm_packed_base` / ``make_gbm_grouped_base``
  (the Hummingbird-style HistGBM tensorization in the same packed 7-arg
  form; f64 tree compares by bit-parity contract, so these entries
  declare ``x64=True`` and trace inside the x64 context).
- ``bulk-score-chunk``   — `parallel/bulk.py make_bulk_fused` (the fused
  chunk program the pipelined bulk/stream scorers dispatch per chunk),
  traced at two chunk sizes with the production int8 categorical ids.

Everything is built from ``jax.ShapeDtypeStruct`` pytrees: params come from
``jax.eval_shape(model.init, ...)``, batches from the SCHEMA shapes, so the
whole registry traces abstractly — no parameter materialization, no device
execution. Adding an entry point = appending to ``registered_entry_points``
(see docs/static-analysis.md "Registering a Layer-2 entry point").

``--numeric`` additionally runs the serve entry through `utils/debug.py
checked()` (checkify float checks) on tiny CONCRETE batches — that one
executes on the current backend, so it is opt-in, not part of the gate.
"""

from __future__ import annotations

from typing import Any

from mlops_tpu.analysis.traces import EntryPoint, ShardingLink


def _schema_batch(batch: int):
    import jax
    import jax.numpy as jnp

    from mlops_tpu.schema import SCHEMA

    S = jax.ShapeDtypeStruct
    return (
        S((batch, SCHEMA.num_categorical), jnp.int32),
        S((batch, SCHEMA.num_numeric), jnp.float32),
    )


def _tiny_model_config():
    from mlops_tpu.config import ModelConfig

    # Smallest real family: the analyzer checks program STRUCTURE, which
    # width does not change, so keep tracing cheap.
    return ModelConfig(family="mlp", hidden_dims=(8,), embed_dim=4)


def _abstract_variables(model) -> Any:
    """Variable shapes via eval_shape — one shared definition
    (`models.abstract_variables`) so the compile cache derives the exact
    signatures this registry traces."""
    from mlops_tpu.models import abstract_variables

    return abstract_variables(model)


def _abstract_monitor():
    # Shared with the compile-cache warmup (`compilecache/warmup.py`): the
    # same abstract monitor produces the same cache keys.
    from mlops_tpu.monitor.state import abstract_monitor_state

    return abstract_monitor_state()


def _abstract_train_state(model, optimizer):
    import jax
    import jax.numpy as jnp

    from mlops_tpu.train.loop import TrainState

    variables = _abstract_variables(model)
    params = variables["params"]
    S = jax.ShapeDtypeStruct
    return TrainState(
        params=params,
        opt_state=jax.eval_shape(optimizer.init, params),
        step=S((), jnp.int32),
        rng=S((2,), jnp.uint32),
        ema=None,
    )


# --------------------------------------------------------------- builders
def _build_train_step_dense():
    import jax
    import jax.numpy as jnp

    from mlops_tpu.config import TrainConfig
    from mlops_tpu.models import build_model
    from mlops_tpu.train.loop import make_optimizer, make_train_window

    model = build_model(_tiny_model_config())
    config = TrainConfig(batch_size=32, steps=8, eval_every=4)
    optimizer = make_optimizer(config)
    window = make_train_window(model, optimizer, config, window=4)
    state = _abstract_train_state(model, optimizer)

    def args(rows: int):
        cat, num = _schema_batch(rows)
        lab = jax.ShapeDtypeStruct((rows,), jnp.float32)
        return (state, cat, num, lab)

    # Two dataset sizes: the scan must be the same program at any row
    # count (minibatches are gathered from indices, never data-dependent).
    return window, {256: args(256), 512: args(512)}


def _build_train_step_tp():
    import jax
    import jax.numpy as jnp

    from mlops_tpu.config import TrainConfig
    from mlops_tpu.models import build_model
    from mlops_tpu.parallel import make_mesh
    from mlops_tpu.parallel.steps import make_sharded_train_step
    from mlops_tpu.train.loop import make_optimizer

    model = build_model(_tiny_model_config())
    config = TrainConfig(batch_size=32, steps=8, eval_every=4)
    optimizer = make_optimizer(config)
    mesh = make_mesh(jax.device_count())
    params = _abstract_variables(model)["params"]
    step_fn, _ = make_sharded_train_step(
        model, optimizer, config, mesh, params
    )
    state = _abstract_train_state(model, optimizer)

    def args(rows: int):
        cat, num = _schema_batch(rows)
        lab = jax.ShapeDtypeStruct((rows,), jnp.float32)
        rng = jax.ShapeDtypeStruct((2,), jnp.uint32)
        return (state, cat, num, lab, rng)

    return step_fn, {64: args(64), 128: args(128)}


def _abstract_accumulator():
    # Shared with the compile-cache warmup: the same abstract accumulator
    # produces the same cache keys (monitor/state.py).
    from mlops_tpu.monitor.state import abstract_accumulator

    return abstract_accumulator()


def _build_serve_predict():
    import jax
    import jax.numpy as jnp

    from mlops_tpu.config import ServeConfig
    from mlops_tpu.models import build_model
    from mlops_tpu.ops.predict import make_packed_predict_base

    model = build_model(_tiny_model_config())
    variables = _abstract_variables(model)
    monitor = _abstract_monitor()
    # The CACHEABLE packed program form (params/monitor/accumulator/
    # temperature as arguments — see ops/predict.py
    # make_packed_predict_base): the jaxpr traced here is byte-for-byte
    # the program the compile cache persists.
    entry = make_packed_predict_base(model)

    def args(bucket: int):
        cat, num = _schema_batch(bucket)
        mask = jax.ShapeDtypeStruct((bucket,), jnp.bool_)
        temp = jax.ShapeDtypeStruct((), jnp.float32)
        return (variables, monitor, _abstract_accumulator(), temp, cat, num, mask)

    # Trace at every bucket the engine warms: the padded-bucket serving
    # contract ("zero steady-state recompiles") is exactly TPU304.
    buckets = ServeConfig().warmup_batch_sizes
    return entry, {b: args(b) for b in buckets}


def _build_serve_predict_group():
    import jax
    import jax.numpy as jnp

    from mlops_tpu.models import build_model
    from mlops_tpu.ops.predict import make_packed_grouped_base
    from mlops_tpu.schema import SCHEMA
    from mlops_tpu.serve.engine import GROUP_ROW_BUCKET, GROUP_SLOT_BUCKETS

    model = build_model(_tiny_model_config())
    variables = _abstract_variables(model)
    monitor = _abstract_monitor()
    entry = make_packed_grouped_base(model)

    S = jax.ShapeDtypeStruct

    def args(slots: int):
        rows = GROUP_ROW_BUCKET
        return (
            variables,
            monitor,
            _abstract_accumulator(),
            S((), jnp.float32),
            S((slots, rows, SCHEMA.num_categorical), jnp.int32),
            S((slots, rows, SCHEMA.num_numeric), jnp.float32),
            S((slots, rows), jnp.bool_),
        )

    smallest, largest = GROUP_SLOT_BUCKETS[0], GROUP_SLOT_BUCKETS[-1]
    return entry, {smallest: args(smallest), largest: args(largest)}


def _build_serve_quant():
    import jax
    import jax.numpy as jnp

    from mlops_tpu.config import ServeConfig
    from mlops_tpu.ops.quant import abstract_quant_params
    from mlops_tpu.ops.quant_kernel import make_quant_packed_base

    qparams = abstract_quant_params()
    monitor = _abstract_monitor()
    # use_kernel=False: the analyzer traces the jnp composite route — the
    # Pallas route is the same math (parity-pinned to a tolerance) but
    # its jaxpr hides the body inside a pallas_call, which Layer-2's
    # structural checks cannot see through.
    entry = make_quant_packed_base(use_kernel=False)

    def args(bucket: int):
        cat, num = _schema_batch(bucket)
        mask = jax.ShapeDtypeStruct((bucket,), jnp.bool_)
        temp = jax.ShapeDtypeStruct((), jnp.float32)
        return (qparams, monitor, _abstract_accumulator(), temp, cat, num, mask)

    buckets = ServeConfig().warmup_batch_sizes
    return entry, {b: args(b) for b in buckets}


def _build_serve_quant_group():
    import jax
    import jax.numpy as jnp

    from mlops_tpu.ops.quant import abstract_quant_params
    from mlops_tpu.ops.quant_kernel import make_quant_grouped_base
    from mlops_tpu.schema import SCHEMA
    from mlops_tpu.serve.engine import GROUP_ROW_BUCKET, GROUP_SLOT_BUCKETS

    qparams = abstract_quant_params()
    monitor = _abstract_monitor()
    entry = make_quant_grouped_base(use_kernel=False)

    S = jax.ShapeDtypeStruct

    def args(slots: int):
        rows = GROUP_ROW_BUCKET
        return (
            qparams,
            monitor,
            _abstract_accumulator(),
            S((), jnp.float32),
            S((slots, rows, SCHEMA.num_categorical), jnp.int32),
            S((slots, rows, SCHEMA.num_numeric), jnp.float32),
            S((slots, rows), jnp.bool_),
        )

    smallest, largest = GROUP_SLOT_BUCKETS[0], GROUP_SLOT_BUCKETS[-1]
    return entry, {smallest: args(smallest), largest: args(largest)}


def _build_serve_gbm():
    import jax
    import jax.numpy as jnp

    from mlops_tpu.config import ServeConfig
    from mlops_tpu.ops.gbm_tensor import (
        GbmGeometry,
        abstract_gbm_variables,
        make_gbm_packed_base,
    )

    # Smallest real geometry: the traced STRUCTURE depends on the static
    # depth (gather-loop iterations) and tree count (the serial add
    # chain), not on node width — keep tracing cheap. The entry declares
    # ``x64=True``, so the analyzer traces it inside the x64 context
    # exactly as production lowers it.
    geometry = GbmGeometry(n_trees=4, max_nodes=7, depth=2)
    variables = abstract_gbm_variables(geometry)
    monitor = _abstract_monitor()
    entry = make_gbm_packed_base(geometry.depth)

    def args(bucket: int):
        import numpy as np

        cat, num = _schema_batch(bucket)
        mask = jax.ShapeDtypeStruct((bucket,), jnp.bool_)
        # f64 temperature — the gbm tier's one dtype deviation from the
        # packed contract (bit-parity with the host hybrid's full-float
        # logit division, compilecache/warmup.py _gbm_serve_avals).
        temp = jax.ShapeDtypeStruct((), np.float64)
        return (variables, monitor, _abstract_accumulator(), temp, cat, num, mask)

    buckets = ServeConfig().warmup_batch_sizes
    return entry, {b: args(b) for b in buckets}


def _build_serve_gbm_group():
    import jax
    import jax.numpy as jnp

    from mlops_tpu.ops.gbm_tensor import (
        GbmGeometry,
        abstract_gbm_variables,
        make_gbm_grouped_base,
    )
    from mlops_tpu.schema import SCHEMA
    from mlops_tpu.serve.engine import GROUP_ROW_BUCKET, GROUP_SLOT_BUCKETS

    geometry = GbmGeometry(n_trees=4, max_nodes=7, depth=2)
    variables = abstract_gbm_variables(geometry)
    monitor = _abstract_monitor()
    entry = make_gbm_grouped_base(geometry.depth)

    import numpy as np

    S = jax.ShapeDtypeStruct

    def args(slots: int):
        rows = GROUP_ROW_BUCKET
        return (
            variables,
            monitor,
            _abstract_accumulator(),
            S((), np.float64),  # see _build_serve_gbm
            S((slots, rows, SCHEMA.num_categorical), jnp.int32),
            S((slots, rows, SCHEMA.num_numeric), jnp.float32),
            S((slots, rows), jnp.bool_),
        )

    smallest, largest = GROUP_SLOT_BUCKETS[0], GROUP_SLOT_BUCKETS[-1]
    return entry, {smallest: args(smallest), largest: args(largest)}


def _build_bulk_score_chunk():
    import jax
    import jax.numpy as jnp

    from mlops_tpu.models import build_model
    from mlops_tpu.parallel.bulk import make_bulk_fused
    from mlops_tpu.schema import SCHEMA

    model = build_model(_tiny_model_config())
    variables = _abstract_variables(model)
    monitor = _abstract_monitor()
    entry = make_bulk_fused(model)

    S = jax.ShapeDtypeStruct

    def args(chunk: int):
        # int8 categorical ids: the bulk path narrows on the host and
        # widens in-jit (parallel/bulk.py), so the traced signature must
        # match what the pipelined chunk scorer actually dispatches.
        return (
            variables,
            monitor,
            S((), jnp.float32),
            S((chunk, SCHEMA.num_categorical), jnp.int8),
            S((chunk, SCHEMA.num_numeric), jnp.float32),
            S((chunk,), jnp.bool_),
        )

    # Two chunk sizes: the streaming executors compile ONE program per
    # sweep, so the program must be the same at any chunk shape (TPU304).
    return entry, {4096: args(4096), 16_384: args(16_384)}


def registered_entry_points() -> list[EntryPoint]:
    return [
        EntryPoint(
            name="train-step-dense",
            build=_build_train_step_dense,
            # Dense training packages replicated (host) params.
            params_out_spec=None,
        ),
        EntryPoint(
            name="train-step-tp",
            build=_build_train_step_tp,
            min_devices=2,
            # The TP product loop (train/tensor_parallel.py) merges the
            # PARAM_RULES-sharded tree back to a dense servable tree at
            # packaging — declared here as replicated-after-merge.
            params_out_spec=None,
        ),
        EntryPoint(
            name="serve-predict-packed",
            build=_build_serve_predict,
            # The engine loads bundle params replicated on the serving chip.
            params_in_spec=None,
            # Two DECLARED program families (monitor/state.py drift_scores):
            # buckets <= 64 rows run the dense small-batch K-S, larger ones
            # the sort-based K-S. Each bucket still compiles exactly once
            # at warmup; what TPU304 guards is NEW polymorphism inside a
            # family.
            bucket_families=((1, 8, 64), (256,)),
        ),
        EntryPoint(
            name="serve-predict-group-packed",
            build=_build_serve_predict_group,
            params_in_spec=None,
        ),
        EntryPoint(
            name="serve-predict-quant-packed",
            build=_build_serve_quant,
            params_in_spec=None,
            # ONE program family: the quant tier runs the dense masked K-S
            # statistic at EVERY bucket (ops/quant_kernel.py — the
            # sort-based large-batch form does not lower on Mosaic, and
            # the dense form is mathematically identical), so there is no
            # 64→256 family split like the exact tier's.
            bucket_families=((1, 8, 64, 256),),
        ),
        EntryPoint(
            name="serve-predict-quant-group-packed",
            build=_build_serve_quant_group,
            params_in_spec=None,
        ),
        EntryPoint(
            name="serve-predict-gbm-packed",
            build=_build_serve_gbm,
            params_in_spec=None,
            # f64 is this entry's CONTRACT (bit-parity with sklearn's f64
            # tree compares — ops/gbm_tensor.py): traced inside the x64
            # context, TPU301 suppressed, f64-endpoint cast round-trips
            # allowed (the calibration boundary's narrowing semantics).
            x64=True,
            # Same monitor family split as the exact tier: dense masked
            # K-S at buckets <= 64, the sort-based form at 256.
            bucket_families=((1, 8, 64), (256,)),
        ),
        EntryPoint(
            name="serve-predict-gbm-group-packed",
            build=_build_serve_gbm_group,
            params_in_spec=None,
            x64=True,
        ),
        EntryPoint(
            name="bulk-score-chunk",
            build=_build_bulk_score_chunk,
            # The pipelined bulk scorers load bundle params replicated.
            params_in_spec=None,
        ),
    ]


# Packaged-params handoffs the sharding check guards (TPU305).
LINKS = [
    ShardingLink("train-step-dense", "serve-predict-packed"),
    ShardingLink(
        "train-step-tp", "serve-predict-packed", transport="merge-to-dense"
    ),
]


class NumericAuditError(Exception):
    """A numeric-audit failure tagged with the entry point that tripped.
    `analysis/cli.py` turns this into the TPU307 finding; a raw
    ``checkify.JaxRuntimeError`` (or AssertionError) escaping instead
    would crash the analyzer with exit 2 rather than gate with exit 1."""

    def __init__(self, entry: str, detail: str):
        self.entry = entry
        super().__init__(detail)


def numeric_audit() -> list[str]:
    """Opt-in one-shot numeric audit (``analyze --numeric``): run the
    PACKED serve programs — the production hot path, accumulator fold
    included — through `utils/debug.py checked()` (checkify float checks)
    on tiny CONCRETE synthetic batches. This executes on the current
    backend (CPU under JAX_PLATFORMS=cpu), so it is not part of the
    abstract gate.

    The solo form runs with PADDING rows (the serving reality: requests
    pad up to their bucket); the grouped form runs full slots — a padding
    SLOT computes drift over zero rows, where the chi-squared path yields
    NaN by construction before the fold selects it away
    (`monitor/state.py fold_accumulator_grouped`), and checkify flags NaN
    at the op that produces it regardless of later masking, so that case
    is pinned by value in `tests/test_packed_parity.py` instead.

    Returns human-readable result lines; raises ``NumericAuditError``
    (naming the entry that tripped) if a NaN/Inf escapes the fused
    predict or the accumulator leaves the audit non-finite.
    """
    import jax
    import numpy as np
    from jax.experimental import checkify

    from mlops_tpu.data import Preprocessor, generate_synthetic
    from mlops_tpu.models import build_model, init_params
    from mlops_tpu.monitor.state import fit_monitor, init_accumulator
    from mlops_tpu.ops.predict import (
        make_packed_grouped_base,
        make_packed_predict_base,
        packed_layout,
    )
    from mlops_tpu.utils.debug import checked

    columns, labels = generate_synthetic(512, seed=0)
    prep = Preprocessor.fit(columns)
    ds = prep.encode(columns, labels)
    model = build_model(_tiny_model_config())
    variables = init_params(model, jax.random.PRNGKey(0))
    monitor = fit_monitor(ds)
    temp = np.float32(1.0)

    bucket, valid = 8, 5  # padding rows exercise the masked drift path
    solo = checked(make_packed_predict_base(model), jit=True)
    try:
        packed, acc = solo(
            variables,
            monitor,
            init_accumulator(),
            temp,
            ds.cat_ids[:bucket],
            ds.numeric[:bucket].astype(np.float32),
            np.arange(bucket) < valid,
        )
    except checkify.JaxRuntimeError as err:
        raise NumericAuditError(
            "serve-predict-packed", f"checkify float checks tripped: {err}"
        ) from err
    p, _, _ = packed_layout(bucket)
    preds = np.asarray(packed)[p][:valid]

    slots, rows = 2, 1  # full slots: every slot folds real drift
    grouped = checked(make_packed_grouped_base(model), jit=True)
    try:
        _, acc = grouped(
            variables,
            monitor,
            acc,
            temp,
            ds.cat_ids[: slots * rows].reshape(slots, rows, -1),
            ds.numeric[: slots * rows]
            .astype(np.float32)
            .reshape(slots, rows, -1),
            np.ones((slots, rows), bool),
        )
    except checkify.JaxRuntimeError as err:
        raise NumericAuditError(
            "serve-predict-group-packed",
            f"checkify float checks tripped: {err}",
        ) from err
    if not all(
        np.isfinite(np.asarray(leaf)).all()
        for leaf in jax.tree_util.tree_leaves(acc)
    ):
        raise NumericAuditError(
            "serve-predict-group-packed",
            "monitor accumulator left the numeric audit non-finite",
        )
    return [
        f"numeric audit: serve-predict-packed {valid}/{bucket} padded rows "
        f"under checkify float_checks — clean "
        f"(p50 prediction {float(np.median(preds)):.4f})",
        f"numeric audit: serve-predict-group-packed {slots}x{rows} slots + "
        "accumulator fold — clean (aggregate finite)",
    ]
