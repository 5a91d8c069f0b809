"""The training loop: one compiled ``lax.scan`` per eval window.

Replaces the reference's per-trial sklearn ``pipeline.fit`` driven from
Python (`01-train-model.ipynb:252-330`). TPU-first structure:

- the encoded dataset is placed on device **once** (the reference re-reads
  Spark every trial);
- minibatches are gathered on device from uniform random indices inside the
  scan body — no host->device transfer in the hot loop;
- ``eval_every`` steps run as a single ``lax.scan`` under ``jit`` with the
  train state donated, so Python dispatch cost is paid once per window, not
  per step;
- metrics parity: each eval computes the reference's five validation metrics
  (`01-train-model.ipynb:296-304`) on the held-out split.
"""

from __future__ import annotations

import contextlib
import dataclasses
from pathlib import Path
from typing import Any, Callable

import jax
import jax.numpy as jnp
import numpy as np
import optax
from flax import struct

from mlops_tpu.config import TrainConfig
from mlops_tpu.data.encode import EncodedDataset
from mlops_tpu.train import checkpoint as ckpt
from mlops_tpu.train.metrics import binary_metrics


class TrainState(struct.PyTreeNode):
    params: Any
    opt_state: Any
    step: jnp.ndarray
    rng: jnp.ndarray
    # Raw (biased) EMA accumulator when train.ema_decay > 0, else None.
    # Zero-initialized; consumers debias via ``ema_debiased``.
    ema: Any = None


def update_ema(ema: Any, params: Any, decay: float) -> Any:
    """One Polyak step of the raw (biased) accumulator — THE recurrence
    every trainer shares (dense scan, sharded step, vmapped HPO,
    long-context, pipeline parallel); a fix here fixes all of them."""
    return jax.tree_util.tree_map(
        lambda e, q: decay * e + (1.0 - decay) * q, ema, params
    )


def packaged_or_raw(ema: Any, params: Any, decay: float, step) -> Any:
    """What ships/evals: the debiased EMA when enabled and at least one
    step has run (a zero-step run's all-zeros accumulator would debias to
    0/0), else the raw params. Shared by the layout-loop packaging
    closures."""
    return debias_ema(ema, decay, step) if decay and step > 0 else params


def debias_ema(ema: Any, decay: float, step) -> Any:
    """Bias-corrected Polyak average: ``ema / (1 - decay^step)`` — exact
    from step 1, so short runs (a few hundred steps) are not dragged
    toward the zero init the raw accumulator starts from. ``step`` may be
    a traced array or a plain int (the layout loops' Python counter)."""
    correction = 1.0 - decay ** jnp.asarray(step, jnp.float32)
    return jax.tree_util.tree_map(lambda e: e / correction, ema)


def ema_debiased(state: TrainState, decay: float):
    return debias_ema(state.ema, decay, state.step)


@dataclasses.dataclass
class TrainResult:
    params: Any
    metrics: dict[str, float]  # metrics of the PACKAGED params (with
    # keep_best that is the best eval window, not necessarily the final)
    history: list[dict[str, float]]
    steps: int  # total steps trained
    packaged_step: int = 0  # the eval step the packaged params came from


def sigmoid_bce(
    logits: jnp.ndarray, labels: jnp.ndarray, pos_weight: float = 1.0
) -> jnp.ndarray:
    """Weighted sigmoid binary cross-entropy (mean).

    ``pos_weight`` scales the positive-class term for class imbalance — the
    reference leaves imbalance unhandled (SURVEY.md SS7 hard parts).
    """
    labels = labels.astype(jnp.float32)
    softplus = jax.nn.softplus
    per_example = pos_weight * labels * softplus(-logits) + (1.0 - labels) * softplus(
        logits
    )
    return per_example.mean()


def training_loss(
    model,
    params: Any,
    cat: jnp.ndarray,
    num: jnp.ndarray,
    lab: jnp.ndarray,
    dropout_rng: jnp.ndarray,
    pos_weight: float = 1.0,
) -> jnp.ndarray:
    """BCE plus every auxiliary the model sows into ``aux_losses`` (e.g.
    the MoE load-balance term, `models/moe.py`) — the one loss definition
    shared by the local scan trainer, the sharded pjit step and the
    vmapped HPO trials, so trainers never need to know which families
    carry auxiliaries (they sow pre-scaled values; non-MoE families sow
    nothing and pay nothing)."""
    logits, aux_state = model.apply(
        {"params": params},
        cat,
        num,
        train=True,
        rngs={"dropout": dropout_rng},
        mutable=["aux_losses"],
    )
    loss = sigmoid_bce(logits, lab, pos_weight)
    for leaf in jax.tree_util.tree_leaves(aux_state):
        loss = loss + jnp.mean(leaf)
    return loss


@contextlib.contextmanager
def metric_writers(metrics_path, config: TrainConfig):
    """THE metric-sink contract, shared by ``fit`` and every layout loop
    (train/pipeline.py): jsonl when a path is given, TensorBoard when
    ``train.tensorboard_dir`` is set — no trainer may silently ignore
    either knob. Yields ``emit(record)``; both sinks close on every exit
    (the TB writer buffers events, and a mid-run crash must not lose
    exactly the records a debugging session needs)."""
    from mlops_tpu.utils.jsonl import JsonlWriter

    writer = JsonlWriter(metrics_path) if metrics_path else None
    tb = None
    if config.tensorboard_dir:
        from mlops_tpu.utils.tboard import TensorBoardWriter

        tb = TensorBoardWriter(config.tensorboard_dir)

    def emit(record: dict) -> None:
        if writer is not None:
            writer.write(record)
        if tb is not None:
            tb.write(record)

    try:
        yield emit
    finally:
        if writer is not None:
            writer.close()
        if tb is not None:
            tb.close()


def make_optimizer(config: TrainConfig) -> optax.GradientTransformation:
    schedule = optax.warmup_cosine_decay_schedule(
        init_value=0.0,
        peak_value=config.learning_rate,
        warmup_steps=config.warmup_steps,
        decay_steps=max(config.steps, config.warmup_steps + 1),
        end_value=config.learning_rate * 0.05,
    )
    return optax.chain(
        optax.clip_by_global_norm(1.0),
        optax.adamw(schedule, weight_decay=config.weight_decay),
    )


def _device_put_dataset(ds: EncodedDataset, sharding=None):
    put = (lambda x: jax.device_put(x, sharding)) if sharding else jax.device_put
    return (
        put(jnp.asarray(ds.cat_ids)),
        put(jnp.asarray(ds.numeric)),
        put(jnp.asarray(ds.labels, dtype=jnp.float32)),
    )


def make_train_window(
    model,
    optimizer: optax.GradientTransformation,
    config: TrainConfig,
    window: int,
) -> Callable:
    """Build the jitted scan running ``window`` steps on device.

    The train state is donated: parameter/optimizer buffers are updated in
    place in HBM rather than reallocated each window.
    """

    def run_window(state: TrainState, cat, num, lab):
        n = cat.shape[0]

        def one_step(state: TrainState, _):
            step_rng = jax.random.fold_in(state.rng, state.step)
            idx_rng, dropout_rng = jax.random.split(step_rng)
            idx = jax.random.randint(idx_rng, (config.batch_size,), 0, n)

            def loss_of(params):
                return training_loss(
                    model,
                    params,
                    cat[idx],
                    num[idx],
                    lab[idx],
                    dropout_rng,
                    config.pos_weight,
                )

            loss, grads = jax.value_and_grad(loss_of)(state.params)
            updates, opt_state = optimizer.update(
                grads, state.opt_state, state.params
            )
            params = optax.apply_updates(state.params, updates)
            ema = state.ema
            if config.ema_decay:  # static at trace time
                ema = update_ema(ema, params, config.ema_decay)
            new_state = state.replace(
                params=params, opt_state=opt_state, step=state.step + 1, ema=ema
            )
            return new_state, loss

        state, losses = jax.lax.scan(one_step, state, xs=None, length=window)
        return state, losses.mean()

    # The train state is donated: it updates in place in HBM.
    return jax.jit(run_window, donate_argnums=(0,))


def make_eval_fn(model) -> Callable:
    """Jitted full-split eval; build once per model and reuse across calls."""

    @jax.jit
    def _eval(params, cat, num, lab):
        logits = model.apply({"params": params}, cat, num, train=False)
        return binary_metrics(logits, lab)

    return _eval


def evaluate(model, params, ds: EncodedDataset) -> dict[str, float]:
    """One-shot eval with the reference's metric names (standalone use;
    inside ``fit`` the jitted eval fn and device data are cached instead)."""
    cat, num, lab = _device_put_dataset(ds)
    metrics = make_eval_fn(model)(params, cat, num, lab)
    return {f"validation_{k}_score": float(v) for k, v in metrics.items()}


def fit(
    model,
    train_ds: EncodedDataset,
    valid_ds: EncodedDataset,
    config: TrainConfig,
    init_variables: Any | None = None,
    metrics_path: str | Path | None = None,
    checkpoint_dir: str | Path | None = None,
    compile_cache=None,
) -> TrainResult:
    """Train ``model`` on an encoded dataset; resume from checkpoints if any."""
    from mlops_tpu.models import init_params

    rng = jax.random.PRNGKey(config.seed)
    init_rng, loop_rng = jax.random.split(rng)
    variables = init_variables or init_params(model, init_rng)
    params = variables["params"]
    if init_variables is not None:
        # Donation safety: run_window donates the TrainState, deleting its
        # input buffers in place. Caller-provided init arrays (a pretrained
        # trunk fine-tuned several times, ablation loops) must not be
        # consumed — copy them into fresh buffers the donation may eat.
        params = jax.tree_util.tree_map(jnp.array, params)
    optimizer = make_optimizer(config)
    state = TrainState(
        params=params,
        opt_state=optimizer.init(params),
        step=jnp.asarray(0, jnp.int32),
        rng=loop_rng,
        ema=(
            jax.tree_util.tree_map(jnp.zeros_like, params)
            if config.ema_decay
            else None
        ),
    )

    start_step = 0
    if checkpoint_dir is not None:
        restored = ckpt.load_checkpoint(checkpoint_dir, state)
        if restored is not None:
            state, start_step = restored

    base_window = max(1, min(config.eval_every, config.steps))
    window_fns: dict[int, Callable] = {}
    cat, num, lab = _device_put_dataset(train_ds)
    eval_fn = make_eval_fn(model)
    vcat, vnum, vlab = _device_put_dataset(valid_ds)

    # Best-eval tracking (train.keep_best): snapshot the params of the
    # highest-AUC eval window so long runs cannot ship an overfit tail.
    # The snapshot persists NEXT TO the checkpoints so a crash-resume
    # continues the comparison instead of restarting it at -inf (which
    # would re-ship the overfit tail the feature exists to prevent).
    best_auc = float("-inf")
    best_params = None
    best_record: dict | None = None
    if config.keep_best and checkpoint_dir is not None:
        restored_best = ckpt.load_best(Path(checkpoint_dir), params)
        if restored_best is not None:
            best_params, best_record = restored_best
            best_auc = best_record["validation_roc_auc_score"]

    history: list[dict[str, float]] = []
    step = start_step
    last_ckpt = start_step
    with metric_writers(metrics_path, config) as emit:
        while step < config.steps:
            # Final window shrinks so the step budget is honored exactly even
            # when steps % eval_every != 0 or when resuming mid-window.
            window = min(base_window, config.steps - step)
            run_window = window_fns.get(window)
            if run_window is None:
                run_window = make_train_window(model, optimizer, config, window)
                if compile_cache is not None:
                    # AOT-load the window scan through the persistent
                    # executable cache (entry ``train-step-dense``): repeat
                    # runs of a config deserialize instead of re-tracing +
                    # re-XLA-compiling per process.
                    from mlops_tpu.compilecache.warmup import train_window_job

                    run_window = compile_cache.load_or_compile(
                        train_window_job(
                            model, optimizer, config, window,
                            state, cat, num, lab, jitted=run_window,
                        )
                    )
                window_fns[window] = run_window
            state, mean_loss = run_window(state, cat, num, lab)
            step = int(state.step)
            # Metrics must describe the params that will be PACKAGED —
            # the debiased EMA when enabled (a promotion decision made on
            # raw-param metrics would grade a model that never ships).
            eval_params = (
                ema_debiased(state, config.ema_decay)
                if config.ema_decay
                else state.params
            )
            record = {"step": step, "train_loss": float(mean_loss)}
            record.update(
                {
                    f"validation_{k}_score": float(v)
                    for k, v in eval_fn(eval_params, vcat, vnum, vlab).items()
                }
            )
            if (
                config.keep_best
                and record["validation_roc_auc_score"] > best_auc
            ):
                # strict >: a plateaued run must not re-pay the full
                # device->host params copy every tying window
                best_auc = record["validation_roc_auc_score"]
                best_params = jax.device_get(eval_params)
                best_record = record
                if checkpoint_dir is not None:
                    ckpt.save_best(Path(checkpoint_dir), best_params, best_record)
            history.append(record)
            emit(record)
            if (
                checkpoint_dir is not None
                and step - last_ckpt >= config.checkpoint_every
            ):
                ckpt.save_checkpoint(checkpoint_dir, state, step)
                last_ckpt = step
        if checkpoint_dir is not None and step > last_ckpt:
            ckpt.save_checkpoint(checkpoint_dir, state, step)

    # step == 0 (eval-only / fully-resumed-with-no-new-steps runs that never
    # entered the loop THIS process but restored step>0 are fine; a literal
    # zero-step run has an all-zeros accumulator and a 1-d^0 = 0 correction)
    # falls back to the raw params instead of packaging 0/0 = NaN.
    serving_params = (
        ema_debiased(state, config.ema_decay)
        if config.ema_decay and int(state.step) > 0
        else state.params
    )
    if best_params is not None:
        # Metrics and params come from the SAME (best) eval window — the
        # bundle always grades exactly what it serves.
        final, packaged = best_record, best_params
    else:
        final = (
            history[-1]
            if history
            else {
                f"validation_{k}_score": float(v)
                for k, v in eval_fn(serving_params, vcat, vnum, vlab).items()
            }
        )
        packaged = jax.device_get(serving_params)
    return TrainResult(
        params=packaged,
        metrics={k: v for k, v in final.items() if k.startswith("validation_")},
        history=history,
        steps=step,
        packaged_step=int(final.get("step", step)),
    )
