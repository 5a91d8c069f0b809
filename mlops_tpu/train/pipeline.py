"""End-to-end training pipeline: data -> train -> monitor -> bundle -> registry.

This is the TPU-native restatement of the reference's two-notebook job
(`train_register_model_job`: notebook 01 trains + selects, notebook 02 fits
detectors + packages + registers — SURVEY.md SS3.2). One process, one data
read, typed artifacts instead of ``dbutils.jobs.taskValues`` handoffs.
"""

from __future__ import annotations

import dataclasses
import logging
import time
from pathlib import Path
from typing import Any

import jax
import numpy as np

from mlops_tpu.bundle import ModelRegistry, save_bundle
from mlops_tpu.config import Config
from mlops_tpu.data import (
    EncodedDataset,
    Preprocessor,
    generate_synthetic,
    load_table_columns,
)
from mlops_tpu.models import build_model
from mlops_tpu.models.gbm import SKLEARN_FAMILIES, SklearnBaseline
from mlops_tpu.monitor import fit_monitor
from mlops_tpu.train.loop import TrainResult, fit

logger = logging.getLogger("mlops_tpu.train")


@dataclasses.dataclass
class PipelineResult:
    bundle_dir: Path | None  # None only when this process is not the
    # multi-host coordinator (every trained model otherwise packages —
    # doc models as the 'doc' bundle flavor)
    model_uri: str | None
    train_result: TrainResult
    run_dir: Path


def new_run_dir(config: Config, run_name: str | None = None) -> Path:
    """The one place the run-directory convention lives:
    ``<registry.run_root>/<timestamp-or-name>/`` (used by train, tune and
    pretrain alike)."""
    run_dir = Path(config.registry.run_root) / (
        run_name or time.strftime("%Y%m%d-%H%M%S")
    )
    run_dir.mkdir(parents=True, exist_ok=True)
    return run_dir


def load_training_data(config: Config) -> tuple[dict[str, list], np.ndarray]:
    """CSV/Parquet if configured, else the synthetic generator (data layer
    contract; format dispatch on extension)."""
    if config.data.train_path:
        columns, labels = load_table_columns(
            config.data.train_path, require_target=True
        )
        return columns, labels
    return generate_synthetic(config.data.rows, seed=config.data.seed)


def split_dataset(
    ds: EncodedDataset, valid_fraction: float, seed: int = 2024
) -> tuple[EncodedDataset, EncodedDataset]:
    """Shuffled split (parity: ``train_test_split(random_state=2024)``,
    `01-train-model.ipynb` cell 7)."""
    rng = np.random.default_rng(seed)
    perm = rng.permutation(ds.n)
    n_valid = int(ds.n * valid_fraction)
    return ds.slice(perm[n_valid:]), ds.slice(perm[:n_valid])


def _fit_calibration(
    valid_ds: EncodedDataset, params: Any, model=None
) -> dict[str, float]:
    """Temperature-scale on the held-out split (train/calibrate.py): the
    bundle serves ``sigmoid(logit / T)`` instead of the reference's raw
    ``predict_proba`` (`02-register-model.ipynb:330-353` has no
    calibration step). ``model=None`` means the sklearn flavor, where
    ``params`` is the estimator and logits come from its probabilities."""
    import jax.numpy as jnp

    from mlops_tpu.train.calibrate import calibration_record, probs_to_logits

    if model is None:
        logits = probs_to_logits(
            params.predict_proba(valid_ds.cat_ids, valid_ds.numeric)
        )
    else:
        logits = np.asarray(
            model.apply(
                {"params": params},
                jnp.asarray(valid_ds.cat_ids),
                jnp.asarray(valid_ds.numeric),
                train=False,
            )
        )
    return calibration_record(logits, valid_ds.labels)


def _package_and_register(
    config: Config,
    run_dir: Path,
    params: Any,
    preprocessor: Preprocessor,
    train_ds: EncodedDataset,
    metrics: dict[str, float],
    bundle_tags: dict[str, str],
    registry_tags: dict[str, str],
    register: bool,
    calibration: dict[str, float] | None = None,
    model_config=None,
    bulk=None,
    quant=None,
) -> tuple[Path, str | None]:
    """Shared packaging tail: fit monitors, write the bundle, register it
    (notebook 02's role — `02-register-model.ipynb` cells 6-15).

    Multi-host cohorts (JobSet over DCN): every process computes
    identically, but only the coordinator writes the bundle and registry
    entry — N hosts registering N duplicate versions (and racing the
    index write) is the multi-host failure mode this guards.
    """
    from mlops_tpu.parallel.distributed import is_coordinator

    bundle_dir = run_dir / "bundle"
    if not is_coordinator():
        return bundle_dir, None
    monitor = fit_monitor(train_ds, config.monitor, seed=config.data.seed)
    save_bundle(
        bundle_dir,
        model_config if model_config is not None else config.model,
        params,
        preprocessor,
        monitor,
        metrics=metrics,
        tags=bundle_tags,
        calibration=calibration,
        bulk=bulk,
        quant=quant,
    )
    model_uri = None
    if register:
        registry = ModelRegistry(config.registry.root)
        model_uri = registry.register(
            config.registry.model_name, bundle_dir, tags=registry_tags
        )
    return bundle_dir, model_uri


_DISTILL_FAMILIES = ("ft_transformer", "moe", "bert")


def _maybe_distill(config, model_config, model, params, train_ds, valid_ds):
    """Package-time distillation gate: models whose per-row FLOPs lose CPU
    bulk scoring to the sklearn floor — ensembles (K× a small MLP) and
    the transformer families — get a bulk student (train/distill.py)
    unless train.distill_bulk turned it off. ``model`` is None on the
    sklearn path, which never distills (it IS the floor)."""
    expensive = (
        model_config.ensemble_size > 1
        or model_config.family in _DISTILL_FAMILIES
    )
    if model is None or not expensive or not config.train.distill_bulk:
        return None
    from mlops_tpu.train.distill import distill_for_bulk

    return distill_for_bulk(
        model,
        {"params": params},
        model_config,
        train_ds,
        valid_ds,
        seed=config.train.seed,
    )


def _maybe_distill_quant(config, model, params, train_ds, valid_ds):
    """Package-time quant-tier gate: opt-in (``train.distill_quant``),
    flax teachers only. The quantized student ships with its own fidelity
    record, refit temperature, and a STAMPED promotion decision
    (`lifecycle/promote.py quant_tier_gates`) — the engine admits the
    tier from the stamp alone."""
    if model is None or not config.train.distill_quant:
        return None
    from mlops_tpu.train.distill import distill_quant_student

    return distill_quant_student(
        model,
        {"params": params},
        train_ds,
        valid_ds,
        seed=config.train.seed,
        lifecycle=config.lifecycle,
    )


def run_training(
    config: Config,
    register: bool = True,
    run_name: str | None = None,
) -> PipelineResult:
    """Train one model per config and package it as a bundle.

    Steps (each replacing a reference stage):
      1. read + encode data once (vs per-trial Spark re-reads)
      2. ``fit`` the model (notebook 01's role)
      3. fit drift + outlier monitors on the training split (notebook 02
         cell 6)
      4. write the bundle (notebook 02's pyfunc ``log_model``)
      5. register it (notebook 02's ``register_model``), returning a
         ``models:/<name>/<version>`` URI
    """
    if config.model.uses_layout_trainer:
        # Loud, not silent: this entrypoint trains the single-record dense
        # model; a multi-device layout knob left set would otherwise train
        # a plain model without the requested parallelism and no warning.
        raise ValueError(
            "run_training trains the single-record dense model; "
            "multi-device training layouts have dedicated trainers "
            "(model.doc_records/seq_parallel -> train/long_context.py, "
            "model.pipeline_stages -> train/pipeline_parallel.py) — call "
            "run_layout_training, which the `train` CLI dispatches to "
            "automatically"
        )
    run_name = run_name or time.strftime("%Y%m%d-%H%M%S")
    run_dir = new_run_dir(config, run_name)

    columns, labels = load_training_data(config)
    preprocessor = Preprocessor.fit(columns)
    ds = preprocessor.encode(columns, labels)
    train_ds, valid_ds = split_dataset(ds, config.data.valid_fraction)

    calibration_model = None
    if config.model.family in SKLEARN_FAMILIES:
        # BASELINE config 1: the CPU tree-ensemble comparison floor, trained
        # and packaged through the exact same pipeline tail as the TPU models.
        baseline = SklearnBaseline.train(config.model, config.train, train_ds)
        result = TrainResult(
            params=baseline,
            metrics=baseline.evaluate(valid_ds),
            history=[],
            steps=config.model.n_estimators,
        )
    else:
        model = build_model(config.model)
        init_variables = None
        if config.train.init_params and config.model.ensemble_size > 1:
            raise ValueError(
                "train.init_params grafts a pretrained trunk by parameter "
                "name, which cannot target the vmapped member axis of an "
                "ensemble — use ensemble_size=1 for fine-tuning runs"
            )
        # Fine-tune from masked-feature pretraining (`pretrain` CLI):
        # trunk comes from the MLM run, heads stay freshly initialized.
        init_variables = _load_init_variables(config, model) or init_variables
        from mlops_tpu.compilecache.cache import from_config

        result = fit(
            model,
            train_ds,
            valid_ds,
            config.train,
            init_variables=init_variables,
            metrics_path=run_dir / "metrics.jsonl",
            checkpoint_dir=run_dir / "checkpoints",
            # cache.dir set -> the window scan deserializes from the
            # persistent executable cache instead of recompiling per run.
            compile_cache=from_config(config),
        )
        calibration_model = model

    calibration = _fit_calibration(valid_ds, result.params, calibration_model)
    bulk = _maybe_distill(
        config, config.model, calibration_model, result.params, train_ds, valid_ds
    )
    quant = _maybe_distill_quant(
        config, calibration_model, result.params, train_ds, valid_ds
    )
    bundle_dir, model_uri = _package_and_register(
        config,
        run_dir,
        result.params,
        preprocessor,
        train_ds,
        metrics=result.metrics,
        bundle_tags={
            "run_name": run_name,
            "experiment": config.registry.experiment_name,
        },
        registry_tags={
            "run_name": run_name,
            **{k: f"{v:.6f}" for k, v in result.metrics.items()},
        },
        register=register,
        calibration=calibration,
        bulk=bulk,
        quant=quant,
    )
    return PipelineResult(
        bundle_dir=bundle_dir,
        model_uri=model_uri,
        train_result=result,
        run_dir=run_dir,
    )


def run_layout_training(
    config: Config,
    register: bool = True,
    run_name: str | None = None,
) -> PipelineResult:
    """Real training runs for the multi-device layout configs the dense
    entrypoint rejects (the `train` CLI dispatches here automatically):

    - ``model.pipeline_stages=S``: GPipe trainer on a ``('data','stage')``
      mesh (`train/pipeline_parallel.py`). After training, the
      stage-stacked params MERGE back into the dense bert tree and flow
      through the normal calibrate → distill → package → register tail —
      a PP-trained model serves like any other bert bundle.
    - ``model.doc_records>1``: document-BERT trainer
      (`train/long_context.py`), on a ``('data','seq')`` ring mesh when
      ``seq_parallel`` is set. Document models read record HISTORIES, not
      the single-record serving contract, so the run saves params
      (msgpack) + metrics.jsonl instead of a serving bundle.

    Needs enough devices to host the mesh (a v5e-8 / JobSet in
    production, the fake 8-device CPU env in tests/CI); raises with the
    required count otherwise.
    """
    if not config.model.uses_layout_trainer:
        # The mirror of run_training's guard: a dense config routed here
        # would silently train a 1-record "document" model.
        raise ValueError(
            "run_layout_training needs a layout knob set "
            "(model.pipeline_stages / seq_parallel / doc_records>1); "
            "dense configs train via run_training"
        )
    _check_layout_knobs(config)
    if config.train.init_params:
        # Fail BEFORE the run dir and data load: an incompatible graft
        # must not leave an orphan run directory or pay the encode.
        if not (config.model.pipeline_stages or config.model.tensor_parallel):
            raise ValueError(
                "train.init_params is not supported for document training: "
                "the pretrained pos_embed covers one 48-token record, not "
                "a 2+46R document sequence"
            )
        if config.model.family != "bert":
            raise ValueError(
                "train.init_params grafts a bert masked-LM trunk; "
                f"family {config.model.family!r} shares no trunk with it"
            )
    run_name = run_name or time.strftime("%Y%m%d-%H%M%S")
    run_dir = new_run_dir(config, run_name)
    columns, labels = load_training_data(config)
    preprocessor = Preprocessor.fit(columns)
    ds = preprocessor.encode(columns, labels)
    train_ds, valid_ds = split_dataset(ds, config.data.valid_fraction)
    if config.model.pipeline_stages:
        return _run_pp_training(
            config, run_dir, run_name, preprocessor, train_ds, valid_ds, register
        )
    if config.model.tensor_parallel:
        return _run_tp_training(
            config, run_dir, run_name, preprocessor, train_ds, valid_ds, register
        )
    return _run_doc_training(
        config, run_dir, run_name, preprocessor, train_ds, valid_ds, register
    )


def _check_layout_knobs(config: Config) -> None:
    """Reject layout-knob combinations that have no trainer. Without this,
    the dispatch order would win silently and a config asking for two
    layouts would train only one — the silent-route class every other
    entry point (run_training / run_tuning / pretrain) guards loudly
    against."""
    knobs = {
        "pipeline_stages": bool(config.model.pipeline_stages),
        "tensor_parallel": bool(config.model.tensor_parallel),
        "doc_records>1/seq_parallel": (
            config.model.reads_documents or config.model.seq_parallel
        ),
    }
    active = [name for name, on in knobs.items() if on]
    if len(active) > 1:
        raise ValueError(
            f"layout knobs {active} cannot combine: each selects its own "
            "trainer (PP / DP×TP / DP×SP documents); set exactly one"
        )


def _journal_max_step(path: Path) -> int:
    """Highest step already recorded in a metrics.jsonl (0 when absent):
    a resumed run must not append duplicate rows for eval steps that were
    journaled after the checkpoint it restored from. Bad lines are
    skipped per-line — a write truncated by the preemption itself must
    not blind the scan to the intact records before it."""
    import json

    best = 0
    try:
        lines = path.read_text().splitlines()
    except OSError:
        return 0
    for line in lines:
        try:
            best = max(best, int(json.loads(line)["step"]))
        except (ValueError, KeyError, TypeError):
            continue
    return best


def _batch_indices(n_rows: int, batch: int, seed: int, step: int) -> np.ndarray:
    """Minibatch indices for ONE step, seeded by (seed, step): the data
    order is a pure function of the step counter, so a checkpoint-resumed
    run sees exactly the batches the preempted run would have."""
    return np.random.default_rng((seed, step)).integers(0, n_rows, batch)


def _load_init_variables(config: Config, model) -> Any | None:
    """Graft the pretrained masked-LM trunk (``train.init_params``) into a
    fresh init of ``model``; None when unset. One helper for the dense
    and pipeline-parallel fine-tune paths."""
    if not config.train.init_params:
        return None
    from mlops_tpu.models import init_params as fresh_init
    from mlops_tpu.train.pretrain import load_pretrained_variables

    return load_pretrained_variables(
        config.train.init_params,
        config.model,
        fresh_init(model, jax.random.PRNGKey(config.train.seed)),
    )


def _layout_run_setup(tcfg, run_dir: Path, trainer):
    """The shared resume preamble for both layout loops: eval/checkpoint
    cadences (checkpoint_every=0 falls back to the eval window, as in
    ``fit``), state restore from the newest checkpoint, and the journal
    floor that suppresses duplicate metric rows on resume."""
    eval_every = max(1, min(tcfg.eval_every, tcfg.steps))
    ckpt_every = max(1, tcfg.checkpoint_every or eval_every)
    ckpt_dir = run_dir / "checkpoints"
    params, opt_state, ema, start_step = _restore_layout_state(
        ckpt_dir, trainer.params, trainer.opt_state, trainer.ema
    )
    journal_floor = _journal_max_step(run_dir / "metrics.jsonl")
    return (
        eval_every,
        ckpt_every,
        ckpt_dir,
        params,
        opt_state,
        ema,
        start_step,
        journal_floor,
    )


def _metric_writers(run_dir: Path, tcfg):
    """The layout loops' metric sinks — the ONE shared contract
    (`train/loop.py metric_writers`, also used by ``fit``): metrics.jsonl
    always, TensorBoard when ``train.tensorboard_dir`` is set."""
    from mlops_tpu.train.loop import metric_writers

    return metric_writers(run_dir / "metrics.jsonl", tcfg)


def _maybe_checkpoint(ckpt_dir, params, opt_state, ema, step, ckpt_every, steps):
    from mlops_tpu.train.checkpoint import save_checkpoint

    if step % ckpt_every == 0 or step == steps:
        state = {"params": params, "opt_state": opt_state}
        if ema is not None:
            # Only when enabled: the key's presence must match the resume
            # template, which is derived from the same config toggle.
            state["ema"] = ema
        save_checkpoint(ckpt_dir, jax.device_get(state), step)


def _final_validation_metrics(history, steps, fallback):
    """The loop's last eval IS the final metric set on any run that
    reached the step budget; ``fallback`` covers the zero-iteration
    resume (checkpoint already at/past the budget)."""
    if history and history[-1]["step"] == steps:
        return {
            k: v for k, v in history[-1].items() if k.startswith("validation_")
        }
    return fallback()


def _restore_layout_state(ckpt_dir, params, opt_state, ema=None):
    """Resume {params, opt_state[, ema]} from the newest checkpoint,
    re-placing host arrays onto each template leaf's sharding
    (stage-sharded PP leaves included). ``ema`` joins the template only
    when the trainer carries one (train.ema_decay > 0). Returns
    (params, opt_state, ema, start_step)."""
    import jax.numpy as jnp
    from jax.sharding import NamedSharding

    from mlops_tpu.train.checkpoint import CKPT_GLOB, load_checkpoint

    ckpt_dir = Path(ckpt_dir)
    if not (ckpt_dir / "latest.json").exists() and not any(
        ckpt_dir.glob(CKPT_GLOB)
    ):
        # Fresh start (the common case): skip building the host template —
        # it would device_get params + the 2x-sized adam state for nothing.
        return params, opt_state, ema, 0
    template = {"params": params, "opt_state": opt_state}
    if ema is not None:
        template["ema"] = ema
    loaded = load_checkpoint(ckpt_dir, jax.device_get(template))
    if loaded is None:
        # Checkpoints EXIST (the early return above covers the fresh-start
        # case) but none matched the current template — load_checkpoint
        # warned loudly with the per-file errors and the likely cause
        # (toggling train.ema_decay changes the pytree structure, ADVICE
        # r5) instead of silently discarding the run's progress.
        return params, opt_state, ema, 0
    host_state, step = loaded

    def put(t, h):
        # Mesh-laid-out leaves (params, adam mu/nu) go back to their
        # NamedSharding; scalar counters etc. stay UNCOMMITTED like
        # optax's own init leaves them — committing those to one device
        # would conflict with the mesh-committed arguments under jit.
        if isinstance(t.sharding, NamedSharding):
            return jax.device_put(h, t.sharding)
        return jnp.asarray(h)

    return (
        jax.tree.map(put, params, host_state["params"]),
        jax.tree.map(put, opt_state, host_state["opt_state"]),
        (
            jax.tree.map(put, ema, host_state["ema"])
            if ema is not None
            else None
        ),
        step,
    )


def _run_pp_training(
    config, run_dir, run_name, preprocessor, train_ds, valid_ds, register
) -> PipelineResult:
    import jax.numpy as jnp

    from mlops_tpu.parallel import make_nd_mesh
    from mlops_tpu.train.loop import evaluate
    from mlops_tpu.train.pipeline_parallel import (
        make_pp_train_step,
        merge_bert_params,
    )

    stages = config.model.pipeline_stages
    n_dev = len(jax.devices())
    if n_dev % stages:
        raise ValueError(
            f"model.pipeline_stages={stages} needs the device count to be a "
            f"multiple of it; have {n_dev} (run on a v5e pod slice or the "
            f"fake {stages}-device env)"
        )
    mesh = make_nd_mesh({"data": n_dev // stages, "stage": stages})
    dense_model = build_model(
        dataclasses.replace(config.model, pipeline_stages=0)
    )
    # Pretrain -> PP fine-tune: graft the masked-LM trunk into a fresh
    # dense tree (the shared helper; run_layout_training fail-fasts the
    # incompatible cases), then split into the stage layout.
    trainer = make_pp_train_step(
        config.model,
        config.train,
        mesh,
        seed=config.train.seed,
        init_variables=_load_init_variables(config, dense_model),
    )
    tcfg = config.train
    (
        eval_every,
        ckpt_every,
        ckpt_dir,
        params,
        opt_state,
        ema,
        start_step,
        journal_floor,
    ) = _layout_run_setup(tcfg, run_dir, trainer)

    def packaged_params(step):
        # Metrics must describe the params that will be PACKAGED — the
        # debiased EMA when enabled (fit keeps the same invariant).
        from mlops_tpu.train.loop import packaged_or_raw

        pp = packaged_or_raw(ema, params, tcfg.ema_decay, step)
        return merge_bert_params(jax.device_get(pp))

    history: list[dict] = []
    merged = None
    with _metric_writers(run_dir, tcfg) as emit:
        for step in range(start_step + 1, tcfg.steps + 1):
            idx = _batch_indices(train_ds.n, tcfg.batch_size, tcfg.seed, step)
            params, opt_state, ema, loss = trainer.step_fn(
                params,
                opt_state,
                ema,
                jnp.asarray(train_ds.cat_ids[idx]),
                jnp.asarray(train_ds.numeric[idx]),
                jnp.asarray(train_ds.labels[idx]),
            )
            if step % eval_every == 0 or step == tcfg.steps:
                merged = packaged_params(step)
                metrics = evaluate(dense_model, merged, valid_ds)
                record = {"step": step, "loss": round(float(loss), 6), **metrics}
                if step > journal_floor:  # no duplicate rows on resume
                    emit(record)
                history.append(record)
            _maybe_checkpoint(
                ckpt_dir, params, opt_state, ema, step, ckpt_every, tcfg.steps
            )

    def fresh_eval():
        nonlocal merged
        merged = packaged_params(start_step)
        return evaluate(dense_model, merged, valid_ds)

    final = _final_validation_metrics(history, tcfg.steps, fresh_eval)
    result = TrainResult(
        params=merged,
        metrics=final,
        history=history,
        steps=tcfg.steps,
        packaged_step=tcfg.steps,
    )
    calibration = _fit_calibration(valid_ds, merged, dense_model)
    bulk = _maybe_distill(
        config, config.model, dense_model, merged, train_ds, valid_ds
    )
    bundle_dir, model_uri = _package_and_register(
        config,
        run_dir,
        merged,
        preprocessor,
        train_ds,
        metrics=final,
        bundle_tags={
            "run_name": run_name,
            "experiment": config.registry.experiment_name,
            "trained_with": f"pipeline_parallel dp{mesh.shape['data']}xpp{stages}",
        },
        registry_tags={
            "run_name": run_name,
            **{k: f"{v:.6f}" for k, v in final.items()},
        },
        register=register,
        calibration=calibration,
        bulk=bulk,
    )
    return PipelineResult(
        bundle_dir=bundle_dir,
        model_uri=model_uri,
        train_result=result,
        run_dir=run_dir,
    )


def _run_tp_training(
    config, run_dir, run_name, preprocessor, train_ds, valid_ds, register
) -> PipelineResult:
    """DP×TP product training (`model.tensor_parallel=K`): the Megatron-
    laid-out sharded step over a ('data','model') mesh, with the same
    checkpoint/resume, EMA, and packaging tail as the PP path. The params
    are the DENSE family tree (TP is a layout), so the packaged bundle
    serves through the standard engine unchanged."""
    import jax.numpy as jnp

    from mlops_tpu.train.loop import evaluate, packaged_or_raw
    from mlops_tpu.train.tensor_parallel import make_tp_trainer

    dense_model_cfg = dataclasses.replace(config.model, tensor_parallel=0)
    trainer = make_tp_trainer(
        config,
        init_variables=_load_init_variables(
            config, build_model(dense_model_cfg)
        ),
    )
    tcfg = config.train
    (
        eval_every,
        ckpt_every,
        ckpt_dir,
        params,
        opt_state,
        ema,
        start_step,
        journal_floor,
    ) = _layout_run_setup(tcfg, run_dir, trainer)
    state = trainer.state.replace(
        params=params,
        opt_state=opt_state,
        ema=ema,
        step=jnp.asarray(start_step, jnp.int32),
    )
    # Deterministic dropout stream, pure in the step counter — a resumed
    # run sees exactly the per-step rngs the preempted run would have.
    drop_key = jax.random.fold_in(
        jax.random.PRNGKey(tcfg.seed), 0x7EA50000
    )

    def packaged_params(step_count):
        return jax.device_get(
            packaged_or_raw(state.ema, state.params, tcfg.ema_decay, step_count)
        )

    history: list[dict] = []
    packaged = None
    with _metric_writers(run_dir, tcfg) as emit:
        for step in range(start_step + 1, tcfg.steps + 1):
            idx = _batch_indices(train_ds.n, tcfg.batch_size, tcfg.seed, step)
            state, loss = trainer.step_fn(
                state,
                jnp.asarray(train_ds.cat_ids[idx]),
                jnp.asarray(train_ds.numeric[idx]),
                jnp.asarray(train_ds.labels[idx]),
                jax.random.fold_in(drop_key, step),
            )
            if step % eval_every == 0 or step == tcfg.steps:
                packaged = packaged_params(step)
                metrics = evaluate(trainer.model, packaged, valid_ds)
                record = {"step": step, "loss": round(float(loss), 6), **metrics}
                if step > journal_floor:  # no duplicate rows on resume
                    emit(record)
                history.append(record)
            _maybe_checkpoint(
                ckpt_dir, state.params, state.opt_state, state.ema,
                step, ckpt_every, tcfg.steps,
            )

    def fresh_eval():
        nonlocal packaged
        packaged = packaged_params(start_step)
        return evaluate(trainer.model, packaged, valid_ds)

    final = _final_validation_metrics(history, tcfg.steps, fresh_eval)
    result = TrainResult(
        params=packaged,
        metrics=final,
        history=history,
        steps=tcfg.steps,
        packaged_step=tcfg.steps,
    )
    calibration = _fit_calibration(valid_ds, packaged, trainer.model)
    bulk = _maybe_distill(
        config, dense_model_cfg, trainer.model, packaged, train_ds, valid_ds
    )
    mesh_shape = dict(
        zip(trainer.mesh.axis_names, trainer.mesh.devices.shape)
    )
    bundle_dir, model_uri = _package_and_register(
        config,
        run_dir,
        packaged,
        preprocessor,
        train_ds,
        metrics=final,
        bundle_tags={
            "run_name": run_name,
            "experiment": config.registry.experiment_name,
            "trained_with": (
                f"tensor_parallel dp{mesh_shape.get('data', 1)}x"
                f"tp{mesh_shape.get('model', 1)}"
            ),
        },
        registry_tags={
            "run_name": run_name,
            **{k: f"{v:.6f}" for k, v in final.items()},
        },
        register=register,
        calibration=calibration,
        bulk=bulk,
    )
    return PipelineResult(
        bundle_dir=bundle_dir,
        model_uri=model_uri,
        train_result=result,
        run_dir=run_dir,
    )


def _run_doc_training(
    config, run_dir, run_name, preprocessor, train_ds, valid_ds, register
) -> PipelineResult:
    import jax.numpy as jnp

    from mlops_tpu.parallel import make_nd_mesh
    from mlops_tpu.train.checkpoint import tree_bytes
    from mlops_tpu.train.long_context import make_doc_train_step, make_documents
    from mlops_tpu.train.metrics import binary_metrics
    from mlops_tpu.utils.io import atomic_write

    n_dev = len(jax.devices())
    mesh = None
    dp = 1
    if config.model.seq_parallel:
        from mlops_tpu.train.long_context import build_doc_model

        # The authoritative length (BertDocEncoder.doc_seq_len), not a
        # copy of its formula.
        seq = build_doc_model(
            dataclasses.replace(config.model, seq_parallel=False)
        ).doc_seq_len
        sp = max(
            (d for d in range(1, n_dev + 1) if n_dev % d == 0 and seq % d == 0),
            default=1,
        )
        if sp == 1:
            raise ValueError(
                f"seq_parallel needs the document length (2 + 46*doc_records "
                f"= {seq}) to share a factor with the device count {n_dev}; "
                f"pick doc_records accordingly (11 -> 508 works on 2/4-way)"
            )
        mesh = make_nd_mesh({"data": n_dev // sp, "seq": sp})
        dp = n_dev // sp
    trainer = make_doc_train_step(
        config.model, config.train, mesh=mesh, seed=config.train.seed
    )
    dcat, dnum, dlab = make_documents(train_ds, config.model.doc_records)
    vcat, vnum, vlab = make_documents(valid_ds, config.model.doc_records)
    tcfg = config.train
    batch = max(dp, tcfg.batch_size - tcfg.batch_size % dp)

    def valid_doc_logits(params) -> jnp.ndarray:
        # Pad the valid docs to a multiple of the 'data' axis (the ring's
        # shard_map requires an even batch split), then slice back.
        n = vcat.shape[0]
        pad = (-n) % dp
        return trainer.model.apply(
            {"params": params},
            jnp.asarray(np.pad(vcat, ((0, pad), (0, 0), (0, 0)))),
            jnp.asarray(np.pad(vnum, ((0, pad), (0, 0), (0, 0)))),
            train=False,
        )[:n]

    def doc_eval(params) -> dict[str, float]:
        metrics = binary_metrics(valid_doc_logits(params), jnp.asarray(vlab))
        return {f"validation_{k}_score": round(float(v), 6) for k, v in metrics.items()}

    (
        eval_every,
        ckpt_every,
        ckpt_dir,
        params,
        opt_state,
        ema,
        start_step,
        journal_floor,
    ) = _layout_run_setup(tcfg, run_dir, trainer)

    def packaged_doc_params(step):
        # Same invariant as fit/PP: evals and the shipped artifact use the
        # debiased EMA when enabled.
        from mlops_tpu.train.loop import packaged_or_raw

        return packaged_or_raw(ema, params, tcfg.ema_decay, step)

    history: list[dict] = []
    with _metric_writers(run_dir, tcfg) as emit:
        for step in range(start_step + 1, tcfg.steps + 1):
            idx = _batch_indices(dcat.shape[0], batch, tcfg.seed, step)
            params, opt_state, ema, loss = trainer.step_fn(
                params,
                opt_state,
                ema,
                jnp.asarray(dcat[idx]),
                jnp.asarray(dnum[idx]),
                jnp.asarray(dlab[idx]),
            )
            if step % eval_every == 0 or step == tcfg.steps:
                record = {
                    "step": step,
                    "loss": round(float(loss), 6),
                    **doc_eval(packaged_doc_params(step)),
                }
                if step > journal_floor:  # no duplicate rows on resume
                    emit(record)
                history.append(record)
            _maybe_checkpoint(
                ckpt_dir, params, opt_state, ema, step, ckpt_every, tcfg.steps
            )

    final_params = packaged_doc_params(max(start_step, tcfg.steps))
    params_host = jax.device_get(final_params)
    # Kept alongside the bundle for backward compatibility with round-4
    # tooling that read the raw tree.
    atomic_write(run_dir / "doc_params.msgpack", tree_bytes(params_host))
    final = _final_validation_metrics(
        history, tcfg.steps, lambda: doc_eval(final_params)
    )
    result = TrainResult(
        params=params_host,
        metrics=final,
        history=history,
        steps=tcfg.steps,
        packaged_step=tcfg.steps,
    )
    # Deployment path (VERDICT r4 #4): every trained model becomes a
    # servable, versioned artifact — doc models package as the 'doc'
    # bundle flavor (params + preprocessor + doc layout in the manifest)
    # and register a models:/ URI; scoring runs offline via
    # `predict-file` over record-history CSVs
    # (ref: `02-register-model.ipynb:431-440` invariant).
    from mlops_tpu.train.calibrate import calibration_record

    calibration = calibration_record(
        np.asarray(valid_doc_logits(final_params)), np.asarray(vlab)
    )
    mesh_desc = (
        f"long_context dp{dp}xsp{mesh.shape['seq']}" if mesh is not None
        else "long_context dense"
    )
    bundle_dir, model_uri = _package_and_register(
        config,
        run_dir,
        params_host,
        preprocessor,
        train_ds,
        metrics=final,
        bundle_tags={
            "run_name": run_name or run_dir.name,
            "experiment": config.registry.experiment_name,
            "trained_with": mesh_desc,
        },
        registry_tags={
            "run_name": run_name or run_dir.name,
            **{k: f"{v:.6f}" for k, v in final.items()},
        },
        register=register,
        calibration=calibration,
    )
    return PipelineResult(
        bundle_dir=bundle_dir,
        model_uri=model_uri,
        train_result=result,
        run_dir=run_dir,
    )


def run_tuning(
    config: Config,
    register: bool = True,
    run_name: str | None = None,
    mesh=None,
) -> tuple[PipelineResult, "Any"]:
    """HPO sweep -> package the winning trial (the reference's notebook-01
    select-best-child-run flow, `01-train-model.ipynb` cells 8-10 +
    notebook-02 packaging, in one process).
    """
    import json

    from mlops_tpu.train.hpo import run_architecture_hpo
    from mlops_tpu.utils.io import atomic_write

    if config.model.family in SKLEARN_FAMILIES:
        raise ValueError(
            "sklearn baseline families (gbm/rf) train via `train`; the "
            "vmapped/sharded `tune` sweep applies to the Flax families only"
        )
    if config.model.uses_layout_trainer:
        # Same loud guard as run_training: the sweep trains dense models,
        # so a layout knob left set would silently drop the requested
        # parallelism from every trial.
        raise ValueError(
            "`tune` sweeps dense single-record models; layout knobs "
            "(model.pipeline_stages / seq_parallel / doc_records>1) train "
            "via `train` -> run_layout_training"
        )

    run_name = run_name or time.strftime("%Y%m%d-%H%M%S") + "-tune"
    run_dir = Path(config.registry.run_root) / run_name
    run_dir.mkdir(parents=True, exist_ok=True)

    columns, labels = load_training_data(config)
    preprocessor = Preprocessor.fit(columns)
    ds = preprocessor.encode(columns, labels)
    train_ds, valid_ds = split_dataset(ds, config.data.valid_fraction)

    # Architecture groups (hpo.architectures) loop outside; the continuous
    # space vmaps inside each group. win_model is the structural winner's
    # ModelConfig — calibration and the packaged bundle must describe THAT
    # architecture, not the base config's.
    win_model, hpo_result = run_architecture_hpo(
        config.model,
        config.train,
        config.hpo,
        train_ds,
        valid_ds,
        mesh=mesh,
        # Architecture groups persist as they finish; a retried job with a
        # stable registry.run_name recomputes only unfinished groups.
        resume_dir=run_dir,
    )
    # Full atomic rewrite, NOT append: the record set always covers every
    # trial (restored groups included), so appending on a retried run
    # would duplicate all rows.
    atomic_write(
        run_dir / "trials.jsonl",
        "".join(
            json.dumps({"trial": i, **trial}, default=float) + "\n"
            for i, trial in enumerate(hpo_result.trials)
        ).encode(),
    )
    (run_dir / "best.json").write_text(
        json.dumps(
            {
                "best_index": hpo_result.best_index,
                "hyperparams": hpo_result.best_hyperparams,
                "metrics": hpo_result.best_metrics,
            },
            indent=2,
        )
    )

    win_module = build_model(win_model)
    calibration = _fit_calibration(valid_ds, hpo_result.best_params, win_module)
    bulk = _maybe_distill(
        config, win_model, win_module, hpo_result.best_params, train_ds, valid_ds
    )
    bundle_dir, model_uri = _package_and_register(
        config,
        run_dir,
        hpo_result.best_params,
        preprocessor,
        train_ds,
        metrics=hpo_result.best_metrics,
        bundle_tags={
            "run_name": run_name,
            "best_trial": str(hpo_result.best_index),
            # Structural winners (family/hidden_dims/...) surface as strings.
            **{
                k: (f"{v:.6g}" if isinstance(v, float) else str(v))
                for k, v in hpo_result.best_hyperparams.items()
            },
        },
        registry_tags={
            "run_name": run_name,
            "best_trial": str(hpo_result.best_index),
        },
        register=register,
        calibration=calibration,
        model_config=win_model,
        bulk=bulk,
    )
    result = PipelineResult(
        bundle_dir=bundle_dir,
        model_uri=model_uri,
        train_result=TrainResult(
            params=hpo_result.best_params,
            metrics=hpo_result.best_metrics,
            history=[],
            steps=config.hpo.steps,
        ),
        run_dir=run_dir,
    )
    return result, hpo_result
