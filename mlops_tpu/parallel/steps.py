"""Distributed train step + batch scorer via jit-with-shardings (pjit).

The idiomatic TPU recipe (scaling-book style): annotate input/output
shardings on a jit'd function over a Mesh and let XLA insert the collectives
— gradient psums over 'data', activation all-gathers/reduce-scatters over
'model' — riding ICI. No hand-written communication.
"""

from __future__ import annotations

from typing import Any, Callable

import jax
import jax.numpy as jnp
import optax
from jax.sharding import Mesh

from mlops_tpu.config import TrainConfig
from mlops_tpu.parallel.sharding import batch_sharding, param_shardings, replicated
from mlops_tpu.train.loop import TrainState, training_loss, update_ema


def make_sharded_train_step(
    model,
    optimizer: optax.GradientTransformation,
    config: TrainConfig,
    mesh: Mesh,
    params_template: Any,
) -> tuple[Callable, Any]:
    """Build a pjit train step: data-parallel batch, tensor-parallel params.

    Returns ``(step_fn, state_shardings)``. ``step_fn(state, cat, num, lab,
    rng) -> (state, loss)`` with the batch sharded over 'data' and params
    laid out per ``PARAM_RULES`` over 'model'. Gradients reduce over ICI via
    XLA-inserted psums.
    """
    p_shard = param_shardings(mesh, params_template)
    # Optimizer state mirrors the param layout (adamw: mu/nu per param);
    # so does the EMA accumulator — one shadow copy per param shard, no
    # extra collectives (the update is elementwise on co-located tiles).
    state_shardings = TrainState(
        params=p_shard,
        opt_state=_opt_shardings(optimizer, params_template, p_shard, mesh),
        step=replicated(mesh),
        rng=replicated(mesh),
        ema=p_shard if config.ema_decay else None,
    )
    data_in = batch_sharding(mesh)
    label_in = batch_sharding(mesh, ndim=1)

    def step(state: TrainState, cat, num, lab, dropout_rng):
        def loss_of(params):
            return training_loss(
                model, params, cat, num, lab, dropout_rng, config.pos_weight
            )

        loss, grads = jax.value_and_grad(loss_of)(state.params)
        updates, opt_state = optimizer.update(grads, state.opt_state, state.params)
        params = optax.apply_updates(state.params, updates)
        ema = state.ema
        if config.ema_decay:  # static at trace time
            ema = update_ema(ema, params, config.ema_decay)
        return (
            state.replace(
                params=params, opt_state=opt_state, step=state.step + 1, ema=ema
            ),
            loss,
        )

    step_fn = jax.jit(
        step,
        in_shardings=(state_shardings, data_in, data_in, label_in, replicated(mesh)),
        out_shardings=(state_shardings, replicated(mesh)),
        donate_argnums=(0,),
    )
    return step_fn, state_shardings


def _opt_shardings(optimizer, params_template, p_shard, mesh):
    """Optimizer-state shardings: leaves shaped like a param adopt its spec
    (adam mu/nu), everything else (counts, scalars) replicates."""
    opt_state = optimizer.init(params_template)
    param_leaves = jax.tree_util.tree_leaves(params_template)
    shard_leaves = jax.tree_util.tree_leaves(p_shard)
    by_shape: dict[tuple, Any] = {}
    for leaf, shard in zip(param_leaves, shard_leaves):
        by_shape.setdefault(leaf.shape, shard)

    def assign(leaf):
        if hasattr(leaf, "shape") and leaf.shape in by_shape and leaf.ndim > 0:
            return by_shape[leaf.shape]
        return replicated(mesh)

    return jax.tree_util.tree_map(assign, opt_state)


def make_sharded_batch_scorer(model, mesh: Mesh) -> Callable:
    """Data-parallel bulk scorer (BASELINE config 4: 1M-row batch scoring).

    ``score(variables, cat, num) -> probabilities`` with the batch sharded
    across 'data'; params replicated. Call with row counts divisible by the
    data-axis size (pad the tail chunk).
    """
    data_in = batch_sharding(mesh)

    def score(variables, cat, num):
        logits = model.apply(variables, cat, num, train=False)
        return jax.nn.sigmoid(logits)

    # Not compile-cached: the production bulk path is make_bulk_jit
    # (parallel/bulk.py, entry ``bulk-score-chunk``); this probabilities-only
    # scorer is the library/test surface and compiles once per process use.
    return jax.jit(  # tpulint: disable=TPU203
        score,
        in_shardings=(replicated(mesh), data_in, data_in),
        out_shardings=batch_sharding(mesh, ndim=1),
    )
