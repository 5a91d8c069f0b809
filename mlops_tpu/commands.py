"""CLI subcommand implementations."""

from __future__ import annotations

import argparse
import json

from mlops_tpu.config import load_config


def run(args: argparse.Namespace) -> int:
    if args.command == "analyze":
        # Static analysis BEFORE any jax import: no config tree, no
        # distributed init, no backend warmup — `analyze --no-trace` must
        # run identically on a JAX-less machine.
        from mlops_tpu.analysis.cli import run_analyze

        return run_analyze(args)
    if args.command == "flightrec":
        # Flight-recorder timeline render (mlops_tpu/slo/flightrec.py):
        # jax-free, takes dump paths rather than config — intercepted
        # like `analyze` so a post-mortem box needs no backend at all.
        return _flightrec_paths(list(getattr(args, "paths", [])))
    config = load_config(args.config, overrides=getattr(args, "overrides", []))
    # `warmup --cache-dir X` is sugar for `warmup cache.dir=X` (the flag
    # form is the documented container-build invocation).
    if getattr(args, "cache_dir", None):
        config.cache.dir = args.cache_dir
    # `serve --workers N` is sugar for `serve serve.workers=N` (the flag
    # form is the documented deployment invocation).
    if getattr(args, "workers", None) is not None:
        config.serve.workers = args.workers
    # `serve --tenants tenants.toml` is sugar for
    # `serve serve.tenants_path=<file>` (the multi-tenant fleet form).
    if getattr(args, "tenants", None):
        config.serve.tenants_path = args.tenants
    # `trace-report --tenant NAME` is sugar for `trace.tenant=NAME`.
    if getattr(args, "tenant", None):
        config.trace.tenant = args.tenant
    # `trace-report --replica N` is sugar for `trace.replica=N` (the
    # engine-replica slice, ISSUE 13).
    if getattr(args, "replica", None) is not None:
        config.trace.replica = args.replica
    # `serve --replicas E` is sugar for `serve.engine_replicas=E` (the
    # engine replica set, ISSUE 13).
    if getattr(args, "replicas", None) is not None:
        config.serve.engine_replicas = args.replicas
    # `trace-report --ledger` is sugar for `trace.ledger=true` (the
    # device-time cost ledger ranking, ISSUE 14).
    if getattr(args, "ledger", False):
        config.trace.ledger = True
    handler = _HANDLERS.get(args.command)
    if handler is None:
        raise SystemExit(f"subcommand {args.command!r} is not implemented yet")
    # `serve --workers N` runs a jax-free supervisor (serve/frontend.py):
    # its ENGINE child, the one process that owns the chip, sets JAX up
    # after the fork. Every other command computes in this process.
    if not (args.command == "serve" and config.serve.workers > 1):
        _init_jax(config)
    return handler(config) or 0


def _init_jax(config) -> None:
    """Before the first compile: place JAX's persistent compilation cache
    (`compilecache/location.py`), then wire up DCN for multi-host launches
    (GKE JobSet / TPU pod; single-host is a no-op —
    `parallel/distributed.py`)."""
    from mlops_tpu.compilecache.location import enable_persistent_cache
    from mlops_tpu.parallel.distributed import initialize as distributed_init

    enable_persistent_cache(aot_store_on=bool(config.cache.dir))
    distributed_init()


def _synth(config) -> int:
    from mlops_tpu.data import generate_synthetic, write_csv_columns

    path = config.data.train_path or "data/curated.csv"
    columns, labels = generate_synthetic(config.data.rows, seed=config.data.seed)
    write_csv_columns(path, columns, labels)
    print(f"wrote {config.data.rows} rows -> {path}")
    return 0


def _train(config) -> int:
    from mlops_tpu.train.pipeline import run_layout_training, run_training

    run_name = config.registry.run_name or None
    if config.model.uses_layout_trainer:
        # Multi-device training layouts (GPipe / DP×TP Megatron sharding /
        # ring-attention documents) run through their dedicated trainers
        # on a mesh built from the available devices
        # (train/pipeline.py run_layout_training).
        result = run_layout_training(config, run_name=run_name)
    else:
        result = run_training(config, run_name=run_name)
    print(
        json.dumps(
            {
                "bundle": str(result.bundle_dir) if result.bundle_dir else None,
                "model_uri": result.model_uri,
                "run_dir": str(result.run_dir),
                "steps": result.train_result.steps,
                "packaged_step": result.train_result.packaged_step,
                "metrics": result.train_result.metrics,
            }
        )
    )
    return 0


def _pretrain(config) -> int:
    """Masked-feature pretraining on unlabeled rows (BASELINE config 5's
    'fine-tune' implies a pretrain stage; labels are never read). Output:
    a params file consumable via ``train train.init_params=<path>``."""
    from mlops_tpu.data import Preprocessor, generate_synthetic, load_table_columns
    from mlops_tpu.train.pipeline import new_run_dir
    from mlops_tpu.train.pretrain import pretrain_bert, save_pretrained

    if config.model.family != "bert":
        raise SystemExit("pretrain supports model.family=bert")
    if config.model.uses_layout_trainer:
        raise SystemExit(
            "pretrain runs the dense single-record masked-LM; unset the "
            "layout knobs (model.pipeline_stages / seq_parallel / "
            "doc_records>1)"
        )
    if config.data.train_path:
        columns, _ = load_table_columns(config.data.train_path)
    else:
        columns, _ = generate_synthetic(config.data.rows, seed=config.data.seed)
    prep = Preprocessor.fit(columns)
    ds = prep.encode(columns)

    result = pretrain_bert(
        config.model,
        ds,
        steps=config.train.steps,
        batch_size=config.train.batch_size,
        learning_rate=config.train.learning_rate,
        seed=config.train.seed,
    )
    out = new_run_dir(config) / "pretrained.msgpack"
    save_pretrained(result, out)
    print(
        json.dumps(
            {"pretrained": str(out), "rows": ds.n, "loss_curve": result.losses}
        )
    )
    return 0


def _tune(config) -> int:
    import jax

    from mlops_tpu.parallel import make_mesh
    from mlops_tpu.train.pipeline import run_tuning

    # Shard the trial axis across every available chip; single-device runs
    # (laptops, 1-chip CI) skip the mesh and train trials vmapped in-place.
    mesh = make_mesh(jax.device_count()) if jax.device_count() > 1 else None
    result, hpo_result = run_tuning(
        config, run_name=config.registry.run_name or None, mesh=mesh
    )
    print(
        json.dumps(
            {
                "bundle": str(result.bundle_dir),
                "model_uri": result.model_uri,
                "best_trial": hpo_result.best_index,
                "best_hyperparams": hpo_result.best_hyperparams,
                "metrics": hpo_result.best_metrics,
                "trials": len(hpo_result.trials),
            }
        )
    )
    return 0


def _register(config) -> int:
    """Register an existing bundle directory (data.train_path doubles as the
    bundle path argument: ``mlops-tpu register data.train_path=<dir>``)."""
    from mlops_tpu.bundle import ModelRegistry

    bundle_dir = config.data.train_path
    if not bundle_dir:
        raise SystemExit("pass the bundle dir via data.train_path=<dir>")
    registry = ModelRegistry(config.registry.root)
    uri = registry.register(config.registry.model_name, bundle_dir)
    print(uri)
    return 0


def _promote(config) -> int:
    """Stage promotion (`mlops-tpu promote registry.promote_version=3
    registry.promote_stage=production`) — the registry-level half of the
    reference's staging->production gate (the image-level half lives in the
    deploy workflow's Production environment review)."""
    from mlops_tpu.bundle import ModelRegistry

    version = config.registry.promote_version
    stage = config.registry.promote_stage
    if not version:
        raise SystemExit(
            "pass registry.promote_version=<n> [registry.promote_stage=staging]"
        )
    registry = ModelRegistry(config.registry.root)
    registry.set_stage(config.registry.model_name, int(version), stage)
    print(
        json.dumps(
            {"model": config.registry.model_name, "version": int(version),
             "stage": stage}
        )
    )
    return 0


def _validate(config) -> int:
    """Lint a CSV/Parquet before training/scoring — streamed, so any size.

    Counts values the pipeline would silently degrade (OOV categoricals
    -> the OOV bucket; missing/unparseable numerics -> median imputation)
    and pre-flights label parseability the way training will see it
    (fail-fast semantics). Exit 2 when anything is flagged. (The
    reference's only data validation is Spark's inferSchema plus whatever
    breaks at train time.)"""
    import numpy as np

    from mlops_tpu.data.stream import iter_table_chunks
    from mlops_tpu.schema import SCHEMA

    path = config.data.train_path
    if not path:
        raise SystemExit("pass the dataset via data.train_path=<csv|parquet>")

    rows = 0
    oov = dict.fromkeys((f.name for f in SCHEMA.categorical), 0)
    vocabs = {f.name: set(f.vocab) for f in SCHEMA.categorical}
    degraded_numeric = dict.fromkeys((f.name for f in SCHEMA.numeric), 0)
    for columns, _ in iter_table_chunks(path, chunk_rows=65_536):
        rows += len(columns[SCHEMA.categorical[0].name])
        for feat in SCHEMA.categorical:
            vocab = vocabs[feat.name]
            oov[feat.name] += sum(
                1 for v in columns[feat.name] if v not in vocab
            )
        for feat in SCHEMA.numeric:
            raw = np.asarray(columns[feat.name], dtype=np.float64)
            degraded_numeric[feat.name] += int((~np.isfinite(raw)).sum())

    # Label pre-flight: replay training's strict parse (one bad value
    # fails `train` fast); "absent" is fine for scoring-only files.
    try:
        for _ in iter_table_chunks(path, chunk_rows=65_536, require_target=True):
            pass
        labels = "ok"
    except ValueError as err:
        labels = "absent" if "missing target column" in str(err) else str(err)

    report = {
        "path": path,
        "rows": rows,
        "oov_categorical": {k: v for k, v in oov.items() if v},
        # missing AND unparseable cells both impute to the median — the
        # pipeline handles them; the count is the lint signal.
        "numeric_imputed": {k: v for k, v in degraded_numeric.items() if v},
        "labels": labels,
        "ok": (
            not any(oov.values())
            and not any(degraded_numeric.values())
            and labels in ("ok", "absent")
        ),
    }
    print(json.dumps(report))
    return 0 if report["ok"] else 2


def _gc(config) -> int:
    """Prune crash orphans (and, with registry.gc_keep=N, old unstaged
    versions) for the configured model."""
    from mlops_tpu.bundle import ModelRegistry

    registry = ModelRegistry(config.registry.root)
    try:
        removed = registry.gc(
            config.registry.model_name, keep_unstaged=config.registry.gc_keep
        )
    except ValueError as err:  # gs:// root: clean message, no traceback
        raise SystemExit(str(err))
    print(json.dumps({"model": config.registry.model_name, **removed}))
    return 0


def _versions(config) -> int:
    from mlops_tpu.bundle import ModelRegistry

    registry = ModelRegistry(config.registry.root)
    print(
        json.dumps(registry.list_versions(config.registry.model_name), indent=2)
    )
    return 0


def _predict_file(config) -> int:
    """Batch-score a schema CSV offline with the full fused predict (works
    for every bundle flavor — flax on device, family evabyte's per-record
    history scorer among them; sklearn floor on host; and ``doc``
    long-context bundles, which group consecutive rows into record
    histories and emit one prediction per document)."""
    from mlops_tpu.bundle import load_bundle
    from mlops_tpu.native import encode_csv
    from mlops_tpu.serve import InferenceEngine

    source = config.data.train_path
    if not source:
        raise SystemExit("pass the input csv via data.train_path=<csv>")
    bundle = load_bundle(_resolve_bundle(config))
    ds = encode_csv(source, bundle.preprocessor)
    if bundle.flavor == "doc":
        print(json.dumps(
            _predict_documents(bundle, ds, config.serve.max_batch)
        ))
        return 0
    engine = InferenceEngine(bundle, buckets=(config.serve.max_batch,))
    print(json.dumps(engine.predict_arrays(ds.cat_ids, ds.numeric)))
    return 0


def _predict_documents(bundle, ds, max_batch: int = 256) -> dict:
    """Score a record-history dataset with a doc bundle: consecutive rows
    group into ``doc_records``-length documents (the training-time
    `make_documents` convention: the prediction targets the LAST record's
    default) and the calibrated per-document probabilities come back with
    the grouping accounted for. Documents stream through one jitted
    forward in ``max_batch``-sized chunks (the tail chunk pads up to the
    same shape) — this is the doc flavor's bulk surface, so a 1M-row
    history file must not materialize one giant forward."""
    import jax
    import jax.numpy as jnp
    import numpy as np

    from mlops_tpu.train.long_context import group_documents

    r = bundle.model_config.doc_records
    if ds.cat_ids.shape[0] < r:
        raise SystemExit(
            f"doc bundle needs at least doc_records={r} rows per document; "
            f"file has {ds.cat_ids.shape[0]}"
        )
    cat, num = group_documents(ds.cat_ids, ds.numeric, r)
    docs = cat.shape[0]
    chunk = max(1, min(int(max_batch), docs))
    forward = jax.jit(
        lambda c, x: bundle.model.apply(
            {"params": bundle.variables["params"]}, c, x, train=False
        )
    )
    probs = np.empty(docs, np.float32)
    for lo in range(0, docs, chunk):
        hi = min(lo + chunk, docs)
        pad = chunk - (hi - lo)  # pad the tail to the compiled shape
        c = np.pad(cat[lo:hi], ((0, pad), (0, 0), (0, 0)))
        x = np.pad(num[lo:hi], ((0, pad), (0, 0), (0, 0)))
        logits = forward(jnp.asarray(c), jnp.asarray(x))
        probs[lo:hi] = np.asarray(
            jax.nn.sigmoid(logits / bundle.temperature), np.float32
        )[: hi - lo]
    dropped = int(ds.cat_ids.shape[0] - docs * r)
    return {
        "predictions": [round(float(p), 6) for p in probs],
        "documents": int(docs),
        "records_per_document": r,
        "rows_dropped": dropped,  # tail rows short of a full document
    }


def _score_batch(config) -> int:
    """Bulk-score a large dataset data-parallel over every chip (BASELINE
    config 4). Input: ``data.train_path=<csv>`` or synthetic ``data.rows``."""
    import jax
    import numpy as np

    from mlops_tpu.bundle import load_bundle
    from mlops_tpu.data import generate_synthetic
    from mlops_tpu.native import encode_csv
    from mlops_tpu.parallel import make_mesh
    from mlops_tpu.parallel.bulk import score_dataset

    bundle = load_bundle(_resolve_bundle(config))
    if bundle.flavor == "doc":
        raise SystemExit(
            "doc bundles (family bert with doc_records > 1: 3-D record "
            "histories in, ONE answer a document) score via `predict-file "
            "data.train_path=<history csv>`; the bulk scorer's per-record "
            "contract does not apply. Family evabyte also reads histories "
            "but answers every record: its (flax) bundles are scored here"
        )
    if config.score.streaming:
        # Out-of-core path (the Spark-scale analogue): the dataset never
        # materializes; peak memory is one chunk, each chunk data-parallel
        # over the mesh like the in-memory path (data/stream.py).
        if not config.data.train_path:
            raise SystemExit("score.streaming requires data.train_path=<csv>")
        from mlops_tpu.compilecache.cache import from_config
        from mlops_tpu.data.stream import score_csv_stream

        mesh = make_mesh(jax.device_count()) if jax.device_count() > 1 else None
        stats = score_csv_stream(
            bundle,
            config.data.train_path,
            out_path=config.score.output_path or None,
            chunk_rows=config.score.chunk_rows,
            mesh=mesh,
            exact=True if config.score.exact else None,
            pipeline_depth=config.score.pipeline_depth,
            compile_cache=from_config(config),
        )
        print(json.dumps(stats))
        return 0
    if config.data.train_path:
        from mlops_tpu.data.parquet import is_parquet, load_parquet_columns

        if is_parquet(config.data.train_path):
            # Columnar path: the C++ kernel is CSV-byte-oriented, so
            # Parquet encodes through the Python pipeline.
            columns, _ = load_parquet_columns(config.data.train_path)
            ds = bundle.preprocessor.encode(columns)
        else:
            # Native one-pass parse+encode when built (the 1M-row hot
            # path); transparent Python fallback otherwise.
            ds = encode_csv(config.data.train_path, bundle.preprocessor)
    else:
        columns, _ = generate_synthetic(config.data.rows, seed=config.data.seed)
        ds = bundle.preprocessor.encode(columns)

    from mlops_tpu.compilecache.cache import from_config

    mesh = make_mesh(jax.device_count()) if jax.device_count() > 1 else None
    result = score_dataset(
        bundle,
        ds,
        mesh=mesh,
        chunk_rows=config.score.chunk_rows,
        drift_sample=config.score.drift_sample,
        seed=config.data.seed,
        exact=True if config.score.exact else None,
        pipeline_depth=config.score.pipeline_depth,
        compile_cache=from_config(config),
    )
    if config.score.output_path:
        np.savez(
            config.score.output_path,
            predictions=result.predictions,
            outliers=result.outliers,
        )
    print(
        json.dumps(
            {
                "devices": jax.device_count(),
                "mesh": list(mesh.devices.shape) if mesh is not None else [1],
                **result.summary(),
            }
        )
    )
    return 0


def _looks_like_dir(value: str) -> bool:
    from pathlib import Path

    return Path(value).is_dir()


def _resolve_bundle(config, model_dir: str | None = None) -> str:
    """One rule for every command: a value that is an existing directory is
    the bundle itself; anything else (version number, stage, "latest")
    resolves through the registry."""
    model_dir = model_dir or config.serve.model_directory
    if _looks_like_dir(model_dir):
        return model_dir
    # Imported only for a registry lookup: mlops_tpu.bundle pulls jax in
    # (the module, never a backend), and the `serve --workers N`
    # supervisor given a bundle DIRECTORY stays free even of that.
    from mlops_tpu.bundle import ModelRegistry

    return ModelRegistry(config.registry.root).resolve(
        config.registry.model_name, model_dir
    )


def _serve(config) -> int:
    """Serve a bundle over HTTP.

    Env contract parity with the reference (`app/main.py:27,36`):
    ``MODEL_DIRECTORY`` points at a bundle dir (or a registry
    version/stage/"latest"), ``SERVICE_NAME`` names the service in logs.
    """
    import logging
    import os

    logging.basicConfig(level=logging.INFO, format="%(message)s")
    model_dir = os.environ.get("MODEL_DIRECTORY", config.serve.model_directory)
    config.serve.service_name = os.environ.get(
        "SERVICE_NAME", config.serve.service_name
    )
    # Inconsistent worker/ring geometry (or trace/slo knobs) fails the
    # rollout HERE with the constraint named, before anything binds or
    # warms.
    config.serve.validate()
    config.trace.validate()
    config.slo.validate()
    config.autotune.validate()
    if config.autotune.enabled:
        # Cross-section contract, named HERE before anything warms: the
        # gridtuner's demand input is the tracewire shape table and its
        # cost input is the device-time ledger — without both armed the
        # loop would tick forever disarmed.
        if not config.trace.enabled:
            raise SystemExit(
                "autotune.enabled requires trace.enabled (the shape "
                "histograms are the demand input)"
            )
        if not config.slo.ledger_dir:
            raise SystemExit(
                "autotune.enabled requires slo.ledger_dir (the cost "
                "ledger is the cost-model input)"
            )
        if config.serve.tenants_path:
            # One tunable grid per plane: a tenant fleet shares ONE
            # shape table across engines with per-tenant grids, so
            # per-tenant demand cannot be attributed — named here for
            # BOTH planes, not silently mistuned.
            raise SystemExit(
                "autotune.enabled supports single-tenant planes only "
                "(the shared shape table cannot attribute demand per "
                "tenant grid)"
            )
    if config.serve.workers > 1:
        # Multi-worker plane: N SO_REUSEPORT front-end processes + one
        # ENGINE child process, all forked and supervised by this
        # (jax-free) parent over the shared-memory ring
        # (serve/frontend.py). Nothing jax-flavored may import before
        # this branch: the supervisor must stay thread-free and
        # backend-free so every fork — initial and respawn, front end
        # and engine — is safe.
        from mlops_tpu.serve.frontend import serve_multi_worker

        # A tenants.toml names every bundle itself — resolving
        # serve.model_directory (default "latest") against the registry
        # would fail a fleet-only deployment that never registered a
        # "default" model.
        bundle_dir = (
            "" if config.serve.tenants_path
            else _resolve_bundle(config, model_dir)
        )
        return serve_multi_worker(config, bundle_dir)
    from mlops_tpu.bundle import load_bundle
    from mlops_tpu.compilecache.cache import from_config
    from mlops_tpu.serve import InferenceEngine, serve_forever

    registry = None
    if config.serve.tenants_path:
        # Multi-tenant fleet on the single-process plane
        # (mlops_tpu/tenancy/): N bundles behind one HTTP server, with
        # architecture-identical tenants sharing compiled entries.
        from mlops_tpu.tenancy import TenantRegistry, load_tenants_toml

        try:
            tenancy = load_tenants_toml(
                config.serve.tenants_path
            ).validate()
        except ValueError as err:
            raise SystemExit(str(err))
        registry = TenantRegistry(
            tenancy,
            buckets=tuple(config.serve.warmup_batch_sizes),
            service_name=config.serve.service_name,
            enable_grouping=config.serve.batch_window_ms > 0,
            compile_cache=from_config(config),
            warmup_workers=config.cache.warmup_workers,
            model_shards=config.serve.model_shards,
            serve_tier=config.serve.serve_tier,
            tier_routing=config.serve.tier_routing,
        )
        engine = registry.default_engine
    else:
        bundle = load_bundle(_resolve_bundle(config, model_dir))
        engine = InferenceEngine(
            bundle,
            buckets=tuple(config.serve.warmup_batch_sizes),
            service_name=config.serve.service_name,
            enable_grouping=config.serve.batch_window_ms > 0,
            # cache.dir set (or MLOPS_TPU_CACHE_DIR, e.g. baked into the
            # Docker image by `warmup`): readiness deserializes
            # executables instead of recompiling them — restarts in
            # seconds, not minutes.
            compile_cache=from_config(config),
            warmup_workers=config.cache.warmup_workers,
            model_shards=config.serve.model_shards,
            serve_tier=config.serve.serve_tier,
            tier_routing=config.serve.tier_routing,
        )
    lifecycle = None
    if config.lifecycle.enabled:
        # Serve-integrated closed loop (mlops_tpu/lifecycle/): the
        # controller thread watches the monitor aggregates, retrains off
        # the hot path, shadow-mirrors, and hot-promotes through gates —
        # ONE controller PER TENANT on a multi-tenant plane (each on a
        # tenant-namespaced state dir; tenant A drifting retrains and
        # promotes A alone).
        from mlops_tpu.lifecycle import LifecycleController

        if registry is not None:
            from mlops_tpu.tenancy import tenant_scoped_config

            # The 1-tenant "default" fleet keeps the UN-NAMESPACED state
            # dir — same guard as the ring plane's _engine_main, so a
            # deployment migrating between a bare model_directory and a
            # one-tenant tenants.toml (or between planes) never abandons
            # its reservoir/candidates/generation state.
            single_default = (
                len(registry) == 1 and registry.names[0] == "default"
            )
            lifecycle = [
                LifecycleController(
                    eng,
                    config if single_default
                    else tenant_scoped_config(config, name),
                )
                for name, eng in zip(registry.names, registry.engines)
            ]
        else:
            lifecycle = LifecycleController(engine, config)
    autotune = None
    if config.autotune.enabled:
        # gridtuner (mlops_tpu/autotune/): periodic cost-model fit +
        # grid search + hot regrid on the live engine (single-tenant —
        # the tenants_path guard above already ran).
        from mlops_tpu.autotune import AutotuneController

        autotune = AutotuneController(engine, config.autotune)
    serve_forever(
        engine, config.serve, lifecycle=lifecycle, trace=config.trace,
        registry=registry, slo=config.slo, autotune=autotune,
    )
    return 0


def _warmup(config) -> int:
    """Pre-populate the AOT executable cache for every registered entry
    point (`mlops-tpu warmup --cache-dir <dir>`): run once at container
    build time and the image ships with its executables baked in — staging
    warms the artifact, prod inherits it, and process warmup becomes
    deserialization instead of compilation.

    With a resolvable bundle (serve.model_directory / MODEL_DIRECTORY /
    registry), the serve + bulk programs warm against that bundle's exact
    state. Without one, everything derives abstractly from the config —
    lowering needs only shapes, so no training has to exist yet.
    """
    import os

    from mlops_tpu.compilecache.cache import CompileCache
    from mlops_tpu.compilecache.warmup import warm_entry_points

    if not config.cache.dir:
        raise SystemExit("pass --cache-dir <dir> (or cache.dir=<dir>)")
    bundle = None
    model_dir = os.environ.get("MODEL_DIRECTORY", config.serve.model_directory)
    try:
        bundle_dir = _resolve_bundle(config, model_dir)
    # No bundle anywhere (fresh checkout, image built before training):
    # config-mode warmup is the documented degradation — announced, so a
    # Docker bake that EXPECTED bundle keys is debuggable from the log.
    except Exception as err:  # tpulint: disable=TPU201
        import sys

        print(
            f"warmup: no bundle at {model_dir!r} ({err}); warming "
            "config-derived programs instead",
            file=sys.stderr,
        )
        bundle_dir = None
    if bundle_dir is not None:
        # A bundle that RESOLVES but fails to load (corrupt weights, bad
        # schema fingerprint) must fail the build loudly — a silently
        # config-keyed cache would make every prod replica miss.
        from mlops_tpu.bundle import load_bundle

        bundle = load_bundle(bundle_dir)
    report = warm_entry_points(config, CompileCache(config.cache.dir), bundle)
    print(json.dumps(report))
    return 0


def _lifecycle(config) -> int:
    """One-shot OFFLINE lifecycle pass (the CI/cron twin of the
    serve-integrated loop): incumbent bundle + labeled window ->
    retrained candidate -> AUC/calibration gates (no mirrored traffic
    offline, so the latency gate auto-passes) -> register on pass. Exit
    0 = promoted/registered, 3 = gates rejected the candidate, SystemExit
    on a window that cannot produce a candidate at all."""
    from mlops_tpu.bundle import ModelRegistry, load_bundle
    from mlops_tpu.lifecycle import (
        LifecycleError,
        ShadowEngine,
        evaluate_gates,
        run_retrain,
    )
    from mlops_tpu.serve import InferenceEngine

    incumbent = load_bundle(_resolve_bundle(config))
    try:
        result = run_retrain(incumbent, config, generation=2)
    except LifecycleError as err:
        raise SystemExit(f"lifecycle: {err}")
    # Grade through the REAL packed serving programs (bucket-shaped
    # chunks), exactly what the serve-integrated shadow does — small
    # bucket grid, no grouping: this is a batch pass, not a server.
    live = InferenceEngine(
        incumbent,
        buckets=tuple(config.serve.warmup_batch_sizes),
        enable_grouping=False,
    )
    live.warmup()
    shadow = ShadowEngine(live, result.bundle)
    shadow.warm()
    report = shadow.evaluate(result.holdout, result.holdout_incumbent)
    decision = evaluate_gates(report, config.lifecycle)
    model_uri = None
    if decision.passed and config.lifecycle.auto_promote:
        registry = ModelRegistry(config.registry.root)
        model_uri = registry.register(
            config.registry.model_name,
            result.candidate_dir,
            tags={"lifecycle": "gated-promotion"},
        )
    print(
        json.dumps(
            {
                "candidate": str(result.candidate_dir),
                "labeled_rows": result.labeled_rows,
                "retrain_wall_s": result.wall_s,
                "auc_candidate": round(report.auc_candidate, 6),
                "auc_incumbent": round(report.auc_incumbent, 6),
                "auc_delta": round(report.auc_delta, 6),
                "ece_candidate": round(report.ece_candidate, 6),
                "gates": decision.as_dict(),
                "model_uri": model_uri,
            }
        )
    )
    return 0 if decision.passed else 3


def _autotune(config) -> int:
    """One-shot OFFLINE gridtuner pass (the CI/cron twin of the
    serve-integrated loop, `lifecycle`'s discipline): persisted ledger
    shards + optional span history in -> one plan JSON line on stdout.
    Exit 0 = a regrid is warranted (plan emitted), 3 = the searched grid
    does not clear ``autotune.min_gain_pct`` (plan still printed for the
    audit trail), SystemExit when the telemetry cannot produce a model
    at all. jax-free end to end — runs anywhere the ledger dir mounts."""
    from mlops_tpu.autotune import demand_from_spans, fit_cost_model
    from mlops_tpu.autotune.search import search_plan
    from mlops_tpu.slo import ledger_report

    config.autotune.validate()
    if not config.slo.ledger_dir:
        raise SystemExit(
            "autotune needs slo.ledger_dir (the directory a served "
            "plane's cost ledger flushed into)"
        )
    report = ledger_report(config.slo.ledger_dir)
    rows = report.get("entries", [])
    model = fit_cost_model(rows)
    if model is None:
        raise SystemExit(
            "autotune: no solo bucket_N entries in the ledger — serve "
            "traffic with slo.ledger_dir armed first"
        )
    # Demand: span history when the trace dir has it (exact per-request
    # rows), else the ledger's per-entry mean rows per dispatch (coarse
    # — one point per warmed bucket — but measured).
    demand = []
    if config.trace.dir:
        from mlops_tpu.trace import load_spans

        try:
            demand = demand_from_spans(load_spans(config.trace.dir))
        except OSError:
            demand = []
    if not demand:
        demand = [
            (
                max(1, int(round(r["rows"] / r["dispatches"]))),
                float(r["dispatches"]),
            )
            for r in rows
            if str(r.get("entry", "")).startswith("bucket_")
            and float(r.get("dispatches", 0)) > 0
        ]
    if not demand:
        raise SystemExit("autotune: no demand observations")
    plan = search_plan(
        demand,
        model,
        tuple(config.serve.warmup_batch_sizes),
        config.autotune.max_entries,
    )
    doc = plan.as_dict()
    warranted = (
        plan.buckets != plan.baseline_buckets
        and plan.predicted_gain_pct >= config.autotune.min_gain_pct
    )
    doc["regrid_warranted"] = warranted
    print(json.dumps(doc))
    return 0 if warranted else 3


def _trace_report(config) -> int:
    """Aggregate a traced server's span JSONL (`mlops-tpu trace-report
    [trace.dir=<dir>]`): p50/p99 per stage per compiled entry — the local
    twin of the reference repo's Kusto latency queries, answering the
    question its logs never could (where did THIS latency go). Prints the
    human table on stderr and the JSON report on stdout (the CLI's
    one-JSON-line discipline). Exit 2 when the dir holds no spans."""
    import sys

    from mlops_tpu.trace import format_report, load_spans, stage_report

    if config.trace.ledger:
        # `--ledger`: rank the device-time cost ledger (slo.ledger_dir —
        # mlops_tpu/slo/ledger.py) by cost_ms_per_row instead of
        # aggregating span files. Same print discipline: human table on
        # stderr, JSON on stdout, exit 2 when the ledger is empty.
        from mlops_tpu.slo import ledger_report
        from mlops_tpu.slo.ledger import format_ledger_report

        if not config.slo.ledger_dir:
            raise SystemExit(
                "trace-report --ledger needs slo.ledger_dir (the "
                "directory a served plane's cost ledger flushed into)"
            )
        report = ledger_report(config.slo.ledger_dir)
        print(format_ledger_report(report), file=sys.stderr)
        print(json.dumps(report))
        return 0 if report["entries"] else 2
    spans = load_spans(config.trace.dir)
    if config.trace.tenant:
        # Per-tenant slice (`--tenant` / trace.tenant): multi-tenant
        # planes stamp every span with its tenant label; spans written
        # before tenancy carry none and count as "default".
        spans = [
            span for span in spans
            if span.get("tenant", "default") == config.trace.tenant
        ]
    if config.trace.replica >= 0:
        # Per-replica slice (`--replica` / trace.replica): the ring
        # plane stamps every span with the engine replica that served
        # it (ISSUE 13); pre-replica spans count as replica 0.
        spans = [
            span for span in spans
            if int(span.get("replica", 0)) == config.trace.replica
        ]
    report = stage_report(spans)
    print(format_report(report), file=sys.stderr)
    print(json.dumps(report))
    return 0 if spans else 2


def _flightrec_paths(paths: list[str]) -> int:
    """`mlops-tpu flightrec <dump.json>...`: render flight-recorder
    dumps into human timelines (stderr) + a JSON summary (stdout — the
    CLI's one-JSON-line discipline). Exit 2 with no readable dumps."""
    import sys

    from mlops_tpu.slo.flightrec import format_timeline, load_dump

    summaries = []
    for path in paths:
        try:
            dump = load_dump(path)
        except (OSError, ValueError) as err:
            print(f"flightrec: unreadable dump {path}: {err}",
                  file=sys.stderr)
            continue
        print(format_timeline(dump), file=sys.stderr)
        summaries.append(
            {
                "path": str(path),
                "reason": dump.get("reason"),
                "source": dump.get("source"),
                "worker": dump.get("worker"),
                "pid": dump.get("pid"),
                "events": len(dump.get("events", [])),
            }
        )
    print(json.dumps(summaries))
    return 0 if summaries else 2


def _flightrec(config) -> int:
    """Handler-table entry for parser/handler sync (tests/test_cli.py);
    ``run()`` intercepts `flightrec` before config loading (it takes
    dump PATHS, not config), so this shim only runs when dispatched
    directly — nothing to render without paths."""
    raise SystemExit("flightrec takes dump paths: mlops-tpu flightrec "
                     "runs/flightrec-*.json")


def _analyze(config) -> int:
    """Handler-table entry for parser/handler sync (tests/test_cli.py);
    ``run()`` intercepts `analyze` before config loading, so this shim only
    runs when dispatched directly — lint the package with defaults."""
    from mlops_tpu.analysis.cli import run_analyze

    return run_analyze(argparse.Namespace())


_HANDLERS = {
    "synth": _synth,
    "analyze": _analyze,
    "train": _train,
    "pretrain": _pretrain,
    "tune": _tune,
    "register": _register,
    "promote": _promote,
    "versions": _versions,
    "gc": _gc,
    "validate": _validate,
    "predict-file": _predict_file,
    "score-batch": _score_batch,
    "serve": _serve,
    "lifecycle": _lifecycle,
    "autotune": _autotune,
    "warmup": _warmup,
    "trace-report": _trace_report,
    "flightrec": _flightrec,
}
