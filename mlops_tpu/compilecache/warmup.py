"""AOT warmup: job builders per registered entry point + the parallel runner.

Every production call site that compiles a hot program (the serving
engine's bucketed/grouped warmup, the bulk chunk scorer, the dense train
window, the TP pjit step) builds its `CacheJob` HERE, and the warmup CLI
(`mlops-tpu warmup`) enumerates the tpulint Layer-2 entry-point registry
(`analysis/entrypoints.py registered_entry_points`) through the same
builders — one definition per entry point, so a cache pre-populated at
container build time produces byte-for-byte the keys the serving process
probes. ``warm_entry_points`` raises on a registered entry point without a
warmer: the analyzer and the cache can never disagree about what the hot
programs are.

Misses compile IN PARALLEL: XLA compilation releases the GIL, so a small
thread pool over buckets turns the serial ~54 s cold warmup into
max-of-compiles instead of sum-of-compiles even with an empty cache.
"""

from __future__ import annotations

import concurrent.futures
import os
import time
from typing import Any, Callable

from mlops_tpu.compilecache.cache import CacheJob, CompileCache, execute_once
from mlops_tpu.compilecache.keys import (
    model_fingerprint,
    train_fingerprint,
    tree_avals,
)
from mlops_tpu.compilecache.registry import CACHE_ENTRY_IDS


def _is_concrete(tree: Any) -> bool:
    import jax

    leaves = jax.tree_util.tree_leaves(tree)
    return bool(leaves) and not isinstance(leaves[0], jax.ShapeDtypeStruct)


def _schema_avals(batch_shape: tuple[int, ...], cat_dtype=None):
    import jax
    import jax.numpy as jnp

    from mlops_tpu.schema import SCHEMA

    S = jax.ShapeDtypeStruct
    return (
        S((*batch_shape, SCHEMA.num_categorical), cat_dtype or jnp.int32),
        S((*batch_shape, SCHEMA.num_numeric), jnp.float32),
        S(batch_shape, jnp.bool_),
    )


def _schema_zeros(batch_shape: tuple[int, ...], cat_dtype=None):
    import numpy as np

    from mlops_tpu.schema import SCHEMA

    return (
        np.zeros((*batch_shape, SCHEMA.num_categorical), cat_dtype or np.int32),
        np.zeros((*batch_shape, SCHEMA.num_numeric), np.float32),
        np.ones(batch_shape, bool),
    )


def _temp_aval():
    import jax
    import jax.numpy as jnp

    return jax.ShapeDtypeStruct((), jnp.float32)


# ----------------------------------------------------------- serve entries
def _acc_aval():
    from mlops_tpu.monitor.state import abstract_accumulator

    return tree_avals(abstract_accumulator())


def _acc_zeros():
    import jax

    from mlops_tpu.monitor.state import init_accumulator

    return jax.device_get(init_accumulator())


def _serve_avals(variables, monitor, batch_shape, mesh, placement=None):
    """The 7-arg serving signature's avals, optionally PLACEMENT-PINNED
    (ISSUE 13): with a ('model',) mesh (``serve.model_shards``) the
    param/monitor avals carry the engine's live committed shardings and
    the accumulator/temperature/batch avals pin to full replication;
    with a single-device ``placement`` (a replica's own device) every
    aval pins there. AOT lowering then bakes the layout into the
    artifact, and the cache key's mesh_shape/device_tag axes keep
    differently-placed binaries apart."""
    import jax

    var_avals, mon_avals = tree_avals(variables), tree_avals(monitor)
    acc_aval, temp_aval = _acc_aval(), _temp_aval()
    batch_avals = _schema_avals(batch_shape)
    if mesh is None and placement is None:
        return (var_avals, mon_avals, acc_aval, temp_aval, *batch_avals)
    from mlops_tpu.parallel.sharding import replicated_avals, sharded_avals

    if mesh is not None:
        return (
            sharded_avals(variables),
            sharded_avals(monitor),
            replicated_avals(acc_aval, mesh),
            replicated_avals(temp_aval, mesh),
            *replicated_avals(batch_avals, mesh),
        )

    def pin(tree):
        return jax.tree_util.tree_map(
            lambda a: jax.ShapeDtypeStruct(
                a.shape, a.dtype, sharding=placement
            ),
            tree,
        )

    return (
        sharded_avals(variables),  # committed leaves carry the placement
        sharded_avals(monitor),
        pin(acc_aval),
        pin(temp_aval),
        *pin(batch_avals),
    )


def serve_predict_jobs(
    model,
    model_config,
    variables,
    monitor,
    buckets: tuple[int, ...],
    temperature: float = 1.0,
    mesh=None,
    placement=None,
    device_tag: str = "",
) -> list[CacheJob]:
    """One job per warmup bucket of the PACKED serving predict (entry
    ``serve-predict-packed``: one flat f32 output buffer + the device
    monitor accumulator threaded as the gated-donation argument —
    `ops/predict.py make_packed_predict_base`). ``variables``/``monitor``
    may be concrete (the engine: jobs also execute once to pay
    first-dispatch allocation) or ShapeDtypeStruct trees (the warmup CLI:
    compile+persist only). ``mesh`` (a ('model',) serve mesh) requires
    CONCRETE committed trees — their live shardings become the lowered
    layout and the cache key grows the mesh shape. ``placement``/
    ``device_tag`` pin an engine replica's own device into the lowering
    and the key (serve.engine_replicas on a shared-visibility host)."""
    import jax
    import numpy as np

    from mlops_tpu.ops.predict import ACC_DONATION, make_packed_predict_base

    concrete = _is_concrete(variables)
    if (mesh is not None or placement is not None) and not concrete:
        raise ValueError(
            "placed serve warmup needs committed device trees (their "
            "shardings are the lowered layout)"
        )
    config_hash = model_fingerprint(model_config) + device_tag
    mesh_shape = tuple(mesh.devices.shape) if mesh is not None else None
    jobs = []
    for bucket in buckets:
        jobs.append(
            CacheJob(
                entry_id="serve-predict-packed",
                # A fresh jit per job: AOT lowering never reuses the jit
                # dispatch cache, and per-job objects keep the thread pool
                # free of shared mutable state.
                jitted=jax.jit(
                    make_packed_predict_base(model), donate_argnums=ACC_DONATION
                ),
                abstract_args=_serve_avals(
                    variables, monitor, (bucket,), mesh, placement
                ),
                config_hash=config_hash,
                mesh_shape=mesh_shape,
                donated=True,
                label=f"serve-predict-packed/b{bucket}",
                meta={"bucket": bucket},
                execute_args=(
                    (variables, monitor, _acc_zeros(),
                     np.float32(temperature), *_schema_zeros((bucket,)))
                    if concrete
                    else None
                ),
            )
        )
    return jobs


def serve_group_jobs(
    model,
    model_config,
    variables,
    monitor,
    grid: list[tuple[int, int]],
    temperature: float = 1.0,
    mesh=None,
    placement=None,
    device_tag: str = "",
) -> list[CacheJob]:
    """One job per (slots, rows) shape of the micro-batcher's PACKED
    vmapped dispatch (entry ``serve-predict-group-packed``).
    ``mesh``/``placement``/``device_tag``: see `serve_predict_jobs`."""
    import jax
    import numpy as np

    from mlops_tpu.ops.predict import ACC_DONATION, make_packed_grouped_base

    concrete = _is_concrete(variables)
    if (mesh is not None or placement is not None) and not concrete:
        raise ValueError(
            "placed serve warmup needs committed device trees (their "
            "shardings are the lowered layout)"
        )
    config_hash = model_fingerprint(model_config) + device_tag
    mesh_shape = tuple(mesh.devices.shape) if mesh is not None else None
    jobs = []
    for slots, rows in grid:
        jobs.append(
            CacheJob(
                entry_id="serve-predict-group-packed",
                jitted=jax.jit(
                    make_packed_grouped_base(model), donate_argnums=ACC_DONATION
                ),
                abstract_args=_serve_avals(
                    variables, monitor, (slots, rows), mesh, placement
                ),
                config_hash=config_hash,
                mesh_shape=mesh_shape,
                donated=True,
                label=f"serve-predict-group-packed/g{slots}x{rows}",
                meta={"slots": slots, "rows": rows},
                execute_args=(
                    (variables, monitor, _acc_zeros(),
                     np.float32(temperature), *_schema_zeros((slots, rows)))
                    if concrete
                    else None
                ),
            )
        )
    return jobs


def serve_quant_jobs(
    qparams,
    monitor,
    buckets: tuple[int, ...],
    temperature: float = 1.0,
    placement=None,
    device_tag: str = "",
) -> list[CacheJob]:
    """One job per warmup bucket of the QUANTIZED packed predict (entry
    ``serve-predict-quant-packed`` — `ops/quant_kernel.py
    make_quant_packed_base`). Same 7-arg signature and packed layout as
    the exact tier; ``qparams`` may be the bundle's concrete int8/bf16
    tree or the `ops/quant.py abstract_quant_params` twin. The quant tier
    is single-device by contract (the engine refuses quant + model
    shards), so there is no ``mesh`` axis — only the replica
    ``placement``/``device_tag`` pin."""
    import jax
    import numpy as np

    from mlops_tpu.ops.predict import ACC_DONATION
    from mlops_tpu.ops.quant import QUANT_FORMAT, quant_params_geometry
    from mlops_tpu.ops.quant_kernel import make_quant_packed_base

    concrete = _is_concrete(qparams)
    if placement is not None and not concrete:
        raise ValueError(
            "placed quant warmup needs committed device trees (their "
            "shardings are the lowered layout)"
        )
    embed_dim, hidden = quant_params_geometry(qparams)
    config_hash = (
        model_fingerprint((QUANT_FORMAT, embed_dim, hidden)) + device_tag
    )
    jobs = []
    for bucket in buckets:
        jobs.append(
            CacheJob(
                entry_id="serve-predict-quant-packed",
                jitted=jax.jit(
                    make_quant_packed_base(), donate_argnums=ACC_DONATION
                ),
                abstract_args=_serve_avals(
                    qparams, monitor, (bucket,), None, placement
                ),
                config_hash=config_hash,
                donated=True,
                label=f"serve-predict-quant-packed/b{bucket}",
                meta={"bucket": bucket},
                execute_args=(
                    (qparams, monitor, _acc_zeros(),
                     np.float32(temperature), *_schema_zeros((bucket,)))
                    if concrete
                    else None
                ),
            )
        )
    return jobs


def serve_quant_group_jobs(
    qparams,
    monitor,
    grid: list[tuple[int, int]],
    temperature: float = 1.0,
    placement=None,
    device_tag: str = "",
) -> list[CacheJob]:
    """One job per (slots, rows) shape of the quant tier's vmapped
    grouped dispatch (entry ``serve-predict-quant-group-packed``)."""
    import jax
    import numpy as np

    from mlops_tpu.ops.predict import ACC_DONATION
    from mlops_tpu.ops.quant import QUANT_FORMAT, quant_params_geometry
    from mlops_tpu.ops.quant_kernel import make_quant_grouped_base

    concrete = _is_concrete(qparams)
    if placement is not None and not concrete:
        raise ValueError(
            "placed quant warmup needs committed device trees (their "
            "shardings are the lowered layout)"
        )
    embed_dim, hidden = quant_params_geometry(qparams)
    config_hash = (
        model_fingerprint((QUANT_FORMAT, embed_dim, hidden)) + device_tag
    )
    jobs = []
    for slots, rows in grid:
        jobs.append(
            CacheJob(
                entry_id="serve-predict-quant-group-packed",
                jitted=jax.jit(
                    make_quant_grouped_base(), donate_argnums=ACC_DONATION
                ),
                abstract_args=_serve_avals(
                    qparams, monitor, (slots, rows), None, placement
                ),
                config_hash=config_hash,
                donated=True,
                label=f"serve-predict-quant-group-packed/g{slots}x{rows}",
                meta={"slots": slots, "rows": rows},
                execute_args=(
                    (qparams, monitor, _acc_zeros(),
                     np.float32(temperature), *_schema_zeros((slots, rows)))
                    if concrete
                    else None
                ),
            )
        )
    return jobs


def _gbm_serve_avals(variables, monitor, batch_shape, placement):
    """`_serve_avals` with the gbm tier's ONE dtype deviation: a f64
    temperature argument. The host hybrid this tier must match bit-for-bit
    divides logits by the FULL python float (`train/calibrate.py
    apply_temperature`); an f32 rounding of T shifts tempered
    probabilities by one ulp."""
    import jax
    import numpy as np

    avals = list(_serve_avals(variables, monitor, batch_shape, None, placement))
    avals[3] = (
        jax.ShapeDtypeStruct((), np.float64)
        if placement is None
        else jax.ShapeDtypeStruct((), np.float64, sharding=placement)
    )
    return tuple(avals)


class _X64Lowered:
    """See `_X64Jitted` — the lowering-side half of the wrapper."""

    def __init__(self, lowered):
        self._lowered = lowered

    def compile(self):
        from mlops_tpu.ops.gbm_tensor import x64_context

        with x64_context():
            return self._lowered.compile()


class _X64Jitted:
    """A jitted program whose AOT ``lower``/``compile`` must run inside
    the thread-local x64 context (the gbm-tensor tier: f64 tree compares
    — `ops/gbm_tensor.py`). Both cache consumers only ever call
    ``job.jitted.lower(*avals).compile()`` (`cache.py
    CompileCache._compile` and `run_jobs`'s cacheless path), and
    ``compile()`` returns the REAL compiled executable — persistence
    (executable serialize) and execution see a plain jax object, never
    this wrapper."""

    def __init__(self, jitted):
        self._jitted = jitted

    def lower(self, *args):
        from mlops_tpu.ops.gbm_tensor import x64_context

        with x64_context():
            return _X64Lowered(self._jitted.lower(*args))


def serve_gbm_jobs(
    variables,
    monitor,
    buckets: tuple[int, ...],
    geometry=None,
    temperature: float = 1.0,
    placement=None,
    device_tag: str = "",
) -> list[CacheJob]:
    """One job per warmup bucket of the GBM-TENSOR packed predict (entry
    ``serve-predict-gbm-packed`` — `ops/gbm_tensor.py
    make_gbm_packed_base`): the tensorized HistGBM ensemble in the same
    packed 7-arg form. The tree tensors are f64 and the program lowers
    inside the x64 context, so the jobs carry the `_X64Jitted` wrapper;
    the ensemble's static ``geometry`` rides the config hash
    (`gbm_fingerprint` — with an explicit x64 marker). Single-device by
    contract like the quant tier: only the replica ``placement``/
    ``device_tag`` pin, no mesh axis. ``variables`` must be COMMITTED
    under the x64 context (or host f64 numpy) so the avals stay f64."""
    import jax
    import numpy as np

    from mlops_tpu.ops.gbm_tensor import (
        device_put_x64,
        gbm_fingerprint,
        make_gbm_packed_base,
    )
    from mlops_tpu.ops.predict import ACC_DONATION

    concrete = _is_concrete(variables)
    if placement is not None and not concrete:
        raise ValueError(
            "placed gbm warmup needs committed device trees (their "
            "shardings are the lowered layout)"
        )
    config_hash = gbm_fingerprint(geometry) + device_tag
    # Committed f64 scalar: a host np.float64 fed to the compiled
    # executable outside the x64 context would canonicalize to f32 and
    # miss the f64 temperature signature.
    temp = device_put_x64(np.float64(temperature)) if concrete else None
    jobs = []
    for bucket in buckets:
        jobs.append(
            CacheJob(
                entry_id="serve-predict-gbm-packed",
                jitted=_X64Jitted(
                    jax.jit(
                        make_gbm_packed_base(geometry.depth),
                        donate_argnums=ACC_DONATION,
                    )
                ),
                abstract_args=_gbm_serve_avals(
                    variables, monitor, (bucket,), placement
                ),
                config_hash=config_hash,
                donated=True,
                label=f"serve-predict-gbm-packed/b{bucket}",
                meta={"bucket": bucket},
                execute_args=(
                    (variables, monitor, _acc_zeros(),
                     temp, *_schema_zeros((bucket,)))
                    if concrete
                    else None
                ),
            )
        )
    return jobs


def serve_gbm_group_jobs(
    variables,
    monitor,
    grid: list[tuple[int, int]],
    geometry=None,
    temperature: float = 1.0,
    placement=None,
    device_tag: str = "",
) -> list[CacheJob]:
    """One job per (slots, rows) shape of the gbm-tensor tier's vmapped
    grouped dispatch (entry ``serve-predict-gbm-group-packed``)."""
    import jax
    import numpy as np

    from mlops_tpu.ops.gbm_tensor import (
        device_put_x64,
        gbm_fingerprint,
        make_gbm_grouped_base,
    )
    from mlops_tpu.ops.predict import ACC_DONATION

    concrete = _is_concrete(variables)
    if placement is not None and not concrete:
        raise ValueError(
            "placed gbm warmup needs committed device trees (their "
            "shardings are the lowered layout)"
        )
    config_hash = gbm_fingerprint(geometry) + device_tag
    temp = device_put_x64(np.float64(temperature)) if concrete else None
    jobs = []
    for slots, rows in grid:
        jobs.append(
            CacheJob(
                entry_id="serve-predict-gbm-group-packed",
                jitted=_X64Jitted(
                    jax.jit(
                        make_gbm_grouped_base(geometry.depth),
                        donate_argnums=ACC_DONATION,
                    )
                ),
                abstract_args=_gbm_serve_avals(
                    variables, monitor, (slots, rows), placement
                ),
                config_hash=config_hash,
                donated=True,
                label=f"serve-predict-gbm-group-packed/g{slots}x{rows}",
                meta={"slots": slots, "rows": rows},
                execute_args=(
                    (variables, monitor, _acc_zeros(),
                     temp, *_schema_zeros((slots, rows)))
                    if concrete
                    else None
                ),
            )
        )
    return jobs


# ------------------------------------------------------------- bulk entry
def bulk_chunk_job(
    model,
    model_config,
    variables,
    monitor,
    chunk_rows: int,
    mesh=None,
    path_label: str = "exact",
    jitted: Callable | None = None,
) -> CacheJob:
    """The fused bulk chunk program (entry ``bulk-score-chunk``) at one
    chunk shape, with the production int8 categorical ids. ``path_label``
    keys the exact-ensemble and distilled-student programs apart (their
    architectures differ even when their signatures happen to match)."""
    import jax.numpy as jnp

    from mlops_tpu.parallel.bulk import make_bulk_jit

    return CacheJob(
        entry_id="bulk-score-chunk",
        jitted=jitted if jitted is not None else make_bulk_jit(model, mesh),
        abstract_args=(
            tree_avals(variables),
            tree_avals(monitor),
            _temp_aval(),
            *_schema_avals((chunk_rows,), cat_dtype=jnp.int8),
        ),
        config_hash=model_fingerprint((path_label, model_config)),
        mesh_shape=tuple(mesh.devices.shape) if mesh is not None else None,
        label=f"bulk-score-chunk/{path_label}-c{chunk_rows}",
        meta={"chunk_rows": chunk_rows, "path": path_label},
    )


def bulk_quant_chunk_job(
    qparams,
    monitor,
    chunk_rows: int,
    mesh=None,
    jitted: Callable | None = None,
) -> CacheJob:
    """The quant-tier bulk chunk program — same ``bulk-score-chunk``
    entry, keyed apart by ``path_label="quant"`` plus the quant FORMAT and
    geometry (the serve quant jobs' fingerprint discipline: the flax model
    config says nothing about this program — the int8/bf16 packing scheme
    and the (embed_dim, hidden) widths do)."""
    import jax.numpy as jnp

    from mlops_tpu.ops.quant import QUANT_FORMAT, quant_params_geometry
    from mlops_tpu.parallel.bulk import make_bulk_quant_jit

    embed_dim, hidden = quant_params_geometry(qparams)
    return CacheJob(
        entry_id="bulk-score-chunk",
        jitted=jitted if jitted is not None else make_bulk_quant_jit(mesh),
        abstract_args=(
            tree_avals(qparams),
            tree_avals(monitor),
            _temp_aval(),
            *_schema_avals((chunk_rows,), cat_dtype=jnp.int8),
        ),
        config_hash=model_fingerprint(
            ("quant", QUANT_FORMAT, embed_dim, hidden)
        ),
        mesh_shape=tuple(mesh.devices.shape) if mesh is not None else None,
        label=f"bulk-score-chunk/quant-c{chunk_rows}",
        meta={"chunk_rows": chunk_rows, "path": "quant"},
    )


# ------------------------------------------------------------ train entries
def train_window_job(
    model,
    optimizer,
    train_config,
    window: int,
    state,
    cat,
    num,
    lab,
    jitted: Callable | None = None,
) -> CacheJob:
    """The dense scan window (entry ``train-step-dense``) at one (window,
    dataset-shape) signature. The train state is donated."""
    import jax

    from mlops_tpu.train.loop import make_train_window

    if jitted is None:
        jitted = make_train_window(model, optimizer, train_config, window)
    args = tuple(tree_avals(a) for a in (state, cat, num, lab))
    rows = jax.tree_util.tree_leaves(args[1])[0].shape[0]
    return CacheJob(
        entry_id="train-step-dense",
        jitted=jitted,
        abstract_args=args,
        config_hash=train_fingerprint(model, train_config, f"window={window}"),
        donated=True,
        label=f"train-step-dense/w{window}xn{rows}",
        meta={"window": window, "rows": rows},
    )


def tp_step_job(
    model,
    optimizer,
    train_config,
    mesh,
    state,
    batch_size: int,
    jitted: Callable,
) -> CacheJob:
    """The DP×TP pjit step (entry ``train-step-tp``) at the configured
    per-step batch. ``jitted`` is the REAL step from
    `parallel/steps.py make_sharded_train_step` — the cache wraps
    production programs, never re-implementations."""
    import jax
    import jax.numpy as jnp


    S = jax.ShapeDtypeStruct
    cat_a, num_a, _ = _schema_avals((batch_size,))
    return CacheJob(
        entry_id="train-step-tp",
        jitted=jitted,
        abstract_args=(
            tree_avals(state),
            cat_a,
            num_a,
            S((batch_size,), jnp.float32),
            S((2,), jnp.uint32),
        ),
        config_hash=train_fingerprint(model, train_config, "tp"),
        mesh_shape=tuple(mesh.devices.shape),
        donated=True,
        label=f"train-step-tp/b{batch_size}",
        meta={"batch_size": batch_size},
    )


# --------------------------------------------------------------- execution
def default_workers(n_jobs: int, configured: int = 0) -> int:
    if configured > 0:
        return min(configured, n_jobs)
    return max(1, min(8, os.cpu_count() or 1, n_jobs))


def run_jobs(
    jobs: list[CacheJob],
    cache: CompileCache | None = None,
    workers: int = 0,
) -> list[tuple[CacheJob, Callable]]:
    """Load/compile every job on a small thread pool (misses overlap; hits
    deserialize in milliseconds each). Without a cache the jobs still AOT
    compile in parallel — the cacheless cold start gets max-of-compiles
    too, it just cannot persist."""

    def one(job: CacheJob) -> Callable:
        if cache is not None:
            return cache.load_or_compile(job)
        fn = job.jitted.lower(*job.abstract_args).compile()
        execute_once(job, fn)
        return fn

    if not jobs:
        return []
    n = default_workers(len(jobs), workers)
    if n == 1:
        return [(job, one(job)) for job in jobs]
    with concurrent.futures.ThreadPoolExecutor(
        max_workers=n, thread_name_prefix="aot-warmup"
    ) as pool:
        compiled = list(pool.map(one, jobs))
    return list(zip(jobs, compiled))


# ------------------------------------------------------------- CLI warmers
def _serve_model_state(config, bundle):
    """(model, model_config, variables, monitor, temperature) for the serve
    entries — the bundle's real state when given (exact keys for that
    deployment), else abstract state derived purely from the config (what a
    container build can warm before any training ran)."""
    from mlops_tpu.models import build_model

    if bundle is not None:
        return (
            bundle.model,
            bundle.model_config,
            bundle.variables,
            bundle.monitor,
            bundle.temperature,
        )
    from mlops_tpu.models import abstract_variables
    from mlops_tpu.monitor.state import abstract_monitor_state

    model = build_model(config.model)
    return (
        model,
        config.model,
        abstract_variables(model),
        abstract_monitor_state(config.monitor),
        1.0,
    )


def _warm_serve_predict(config, bundle) -> list[CacheJob]:
    model, mcfg, variables, monitor, temp = _serve_model_state(config, bundle)
    return serve_predict_jobs(
        model, mcfg, variables, monitor,
        tuple(config.serve.warmup_batch_sizes), temperature=temp,
    )


def _warm_serve_group(config, bundle) -> list[CacheJob]:
    if config.serve.batch_window_ms <= 0:
        return []  # grouping disabled: the engine never builds these shapes
    from mlops_tpu.serve.engine import GROUP_ROW_BUCKETS, GROUP_SLOT_BUCKETS

    model, mcfg, variables, monitor, temp = _serve_model_state(config, bundle)
    grid = [(s, r) for r in GROUP_ROW_BUCKETS for s in GROUP_SLOT_BUCKETS]
    return serve_group_jobs(
        model, mcfg, variables, monitor, grid, temperature=temp
    )


def _quant_serve_state(config, bundle):
    """(qparams, monitor, temperature) for the quant serve entries, or
    None when this deployment will never dispatch them: ``serve_tier``
    "exact" (the knob that routes tiers — `serve/engine.py`), or a bundle
    whose quant tier is absent/ungated (`bundle.quant_gates_passed`)."""
    if config.serve.serve_tier == "exact":
        return None
    if bundle is not None:
        if not (bundle.has_quant and bundle.quant_gates_passed):
            return None
        return bundle.quant_params, bundle.monitor, bundle.quant_temperature
    from mlops_tpu.monitor.state import abstract_monitor_state
    from mlops_tpu.ops.quant import abstract_quant_params

    return (
        abstract_quant_params(),
        abstract_monitor_state(config.monitor),
        1.0,
    )


def _warm_serve_quant(config, bundle) -> list[CacheJob]:
    state = _quant_serve_state(config, bundle)
    if state is None:
        return []
    qparams, monitor, temp = state
    return serve_quant_jobs(
        qparams, monitor,
        tuple(config.serve.warmup_batch_sizes), temperature=temp,
    )


def _warm_serve_quant_group(config, bundle) -> list[CacheJob]:
    state = _quant_serve_state(config, bundle)
    if state is None or config.serve.batch_window_ms <= 0:
        return []
    from mlops_tpu.serve.engine import GROUP_ROW_BUCKETS, GROUP_SLOT_BUCKETS

    qparams, monitor, temp = state
    grid = [(s, r) for r in GROUP_ROW_BUCKETS for s in GROUP_SLOT_BUCKETS]
    return serve_quant_group_jobs(
        qparams, monitor, grid, temperature=temp
    )


def _gbm_serve_state(config, bundle):
    """(tree variables, monitor, geometry, temperature) for the gbm-tensor
    serve entries, or None when this deployment never dispatches them.
    Unlike the flax/quant entries there is NO config-only abstract mode:
    the traced program's structure (GbmGeometry) is a fact of the FITTED
    ensemble, so a container build warms these from a bundle or not at
    all — `warm_entry_points` reports the entry as skipped."""
    if bundle is None or bundle.flavor != "sklearn":
        return None
    from mlops_tpu.ops.gbm_tensor import (
        device_put_x64,
        extract_gbm,
        supports_gbm_tensorization,
    )

    if not supports_gbm_tensorization(bundle.estimator):
        return None  # the rf family keeps the host hybrid path
    variables, geometry = extract_gbm(bundle.estimator)
    # Committed under the x64 context so the f64 leaves survive both the
    # aval derivation and the execute-once pass.
    return (
        device_put_x64(variables),
        bundle.monitor,
        geometry,
        bundle.temperature,
    )


def _warm_serve_gbm(config, bundle) -> list[CacheJob]:
    state = _gbm_serve_state(config, bundle)
    if state is None:
        return []
    variables, monitor, geometry, temp = state
    return serve_gbm_jobs(
        variables, monitor,
        tuple(config.serve.warmup_batch_sizes),
        geometry=geometry, temperature=temp,
    )


def _warm_serve_gbm_group(config, bundle) -> list[CacheJob]:
    state = _gbm_serve_state(config, bundle)
    if state is None or config.serve.batch_window_ms <= 0:
        return []
    from mlops_tpu.serve.engine import GROUP_ROW_BUCKETS, GROUP_SLOT_BUCKETS

    variables, monitor, geometry, temp = state
    grid = [(s, r) for r in GROUP_ROW_BUCKETS for s in GROUP_SLOT_BUCKETS]
    return serve_gbm_group_jobs(
        variables, monitor, grid, geometry=geometry, temperature=temp
    )


def _warm_bulk(config, bundle) -> list[CacheJob]:
    import jax

    from mlops_tpu.monitor.state import abstract_monitor_state
    from mlops_tpu.parallel import make_mesh
    from mlops_tpu.parallel.bulk import mesh_chunk_rows, use_distilled_bulk

    mesh = make_mesh(jax.device_count()) if jax.device_count() > 1 else None
    # The SAME rounding rule the scoring paths apply — a divergence here
    # is a guaranteed cache-key miss at run time.
    model_config = bundle.model_config if bundle is not None else config.model
    chunk = mesh_chunk_rows(
        config.score.chunk_rows, mesh, model_config.history_rows
    )
    jobs = []
    if bundle is not None:
        monitor = bundle.monitor
        variants = [("exact", bundle.model, bundle.model_config, bundle.variables)]
        if use_distilled_bulk(bundle):
            variants.append(
                ("distilled", bundle.bulk_model,
                 bundle.model_config, bundle.bulk_variables)
            )
    else:
        from mlops_tpu.models import abstract_variables, build_model

        model = build_model(config.model)
        monitor = abstract_monitor_state(config.monitor)
        variants = [("exact", model, config.model, abstract_variables(model))]
    for path_label, model, mcfg, variables in variants:
        jobs.append(
            bulk_chunk_job(
                model, mcfg, variables, monitor, chunk, mesh,
                path_label=path_label,
            )
        )
    if (
        bundle is not None
        and bundle.flavor != "sklearn"
        and bundle.has_quant
        and bundle.quant_gates_passed
    ):
        # Gate-passed quant tree present: warm its chunk program too, so a
        # `score --tier quant` sweep deserializes instead of compiling.
        jobs.append(
            bulk_quant_chunk_job(bundle.quant_params, monitor, chunk, mesh)
        )
    return jobs


def _abstract_train_state(config, model, optimizer):
    """Abstract TrainState matching what ``fit`` will build — including the
    EMA accumulator when ``train.ema_decay`` is on (its presence changes
    the pytree structure and therefore the key)."""
    import jax
    import jax.numpy as jnp

    from mlops_tpu.models import abstract_variables
    from mlops_tpu.train.loop import TrainState

    variables = abstract_variables(model)
    params = variables["params"]
    S = jax.ShapeDtypeStruct
    return TrainState(
        params=params,
        opt_state=jax.eval_shape(optimizer.init, params),
        step=S((), jnp.int32),
        rng=S((2,), jnp.uint32),
        ema=params if config.train.ema_decay else None,
    )


def _warm_train_dense(config, bundle) -> list[CacheJob]:
    import jax
    import jax.numpy as jnp

    from mlops_tpu.models import build_model
    from mlops_tpu.train.loop import make_optimizer

    if config.model.family in ("gbm", "rf"):
        return []  # sklearn families have no jitted train step
    model = build_model(config.model)
    optimizer = make_optimizer(config.train)
    state = _abstract_train_state(config, model, optimizer)
    # The scan consumes the TRAIN SPLIT arrays — mirror split_dataset's
    # arithmetic so a later `train` run with this config is an exact hit.
    n = config.data.rows
    n_train = n - int(n * config.data.valid_fraction)
    cat, num, _ = _schema_avals((n_train,))
    lab = jax.ShapeDtypeStruct((n_train,), jnp.float32)
    base = max(1, min(config.train.eval_every, config.train.steps))
    windows = {base}
    if config.train.steps % base:
        windows.add(config.train.steps % base)  # the shrunk final window
    return [
        train_window_job(model, optimizer, config.train, w, state, cat, num, lab)
        for w in sorted(windows)
    ]


def _warm_train_tp(config, bundle) -> list[CacheJob]:
    import dataclasses

    import jax

    if jax.device_count() < 2:
        return []  # reported as skipped by warm_entry_points
    if config.model.family in ("gbm", "rf"):
        return []
    from mlops_tpu.models import build_model
    from mlops_tpu.parallel import make_mesh
    from mlops_tpu.parallel.steps import make_sharded_train_step
    from mlops_tpu.train.loop import make_optimizer

    k = config.model.tensor_parallel
    mesh = make_mesh(jax.device_count(), model_parallel=k) if k >= 2 else (
        make_mesh(jax.device_count())
    )
    # TP is a layout, not a different network (train/tensor_parallel.py):
    # the step compiles against the PLAIN dense family.
    model = build_model(dataclasses.replace(config.model, tensor_parallel=0))
    optimizer = make_optimizer(config.train)
    state = _abstract_train_state(config, model, optimizer)
    step_fn, _ = make_sharded_train_step(
        model, optimizer, config.train, mesh, state.params
    )
    return [
        tp_step_job(
            model, optimizer, config.train, mesh, state,
            config.train.batch_size, step_fn,
        )
    ]


_WARMERS: dict[str, Callable] = {
    "serve-predict-packed": _warm_serve_predict,
    "serve-predict-group-packed": _warm_serve_group,
    "serve-predict-quant-packed": _warm_serve_quant,
    "serve-predict-quant-group-packed": _warm_serve_quant_group,
    "serve-predict-gbm-packed": _warm_serve_gbm,
    "serve-predict-gbm-group-packed": _warm_serve_gbm_group,
    "bulk-score-chunk": _warm_bulk,
    "train-step-dense": _warm_train_dense,
    "train-step-tp": _warm_train_tp,
}


def warm_entry_points(config, cache: CompileCache, bundle=None) -> dict:
    """Pre-populate ``cache`` with every registered entry point's hot
    programs (the `mlops-tpu warmup` CLI body). The enumeration IS the
    tpulint Layer-2 registry; an entry point registered there without a
    warmer here is a hard error, not a silent gap."""
    from mlops_tpu.analysis.entrypoints import registered_entry_points

    if set(_WARMERS) != set(CACHE_ENTRY_IDS):  # survives python -O
        raise RuntimeError(
            "compilecache warmers out of sync with registry.CACHE_ENTRY_IDS: "
            f"{sorted(set(_WARMERS) ^ set(CACHE_ENTRY_IDS))}"
        )
    t0 = time.perf_counter()
    jobs: list[CacheJob] = []
    entries: dict[str, dict] = {}
    for entry in registered_entry_points():
        warmer = _WARMERS.get(entry.name)
        if warmer is None:
            raise RuntimeError(
                f"entry point {entry.name!r} has no compile-cache warmer — "
                "register one in mlops_tpu/compilecache/warmup.py and add it "
                "to registry.CACHE_ENTRY_IDS"
            )
        entry_jobs = warmer(config, bundle)
        entries[entry.name] = {"programs": len(entry_jobs)}
        if not entry_jobs:
            entries[entry.name]["skipped"] = True
        jobs.extend(entry_jobs)
    run_jobs(jobs, cache=cache, workers=config.cache.warmup_workers)
    return {
        "cache_dir": str(cache.directory),
        "mode": "bundle" if bundle is not None else "config",
        "entries": entries,
        "programs": len(jobs),
        "warmup_s": round(time.perf_counter() - t0, 3),
        "cache": cache.stats(),
    }
