"""Tensor-parallel training as a product configuration.

``model.tensor_parallel = K`` promotes the DP×TP library step
(`parallel/steps.py make_sharded_train_step` — Megatron column/row
PARAM_RULES over a ('data','model') mesh) to a first-class training
config, the way ``pipeline_stages`` promotes GPipe: the CLI `train`
dispatches here, checkpoints resume onto the mesh layout, and the result
packages into a normal servable bundle.

The reference's analogue is single-process sklearn — no distributed
training exists there (SURVEY.md §2.7 notes the gap); this is the
TPU-native capability the survey's §2.7 TP row obligates: "pjit +
NamedSharding over a ('data','model') mesh for the FT-Transformer/BERT
configs" (SURVEY.md:190).
"""

from __future__ import annotations

import dataclasses
from typing import Any, Callable

import jax
import jax.numpy as jnp

from mlops_tpu.config import Config
from mlops_tpu.parallel.mesh import make_mesh
from mlops_tpu.parallel.steps import make_sharded_train_step
from mlops_tpu.train.loop import TrainState, make_optimizer

# Families with Flax param trees the PARAM_RULES know how to lay out.
# gbm/rf are CPU tree baselines with no param tree to shard.
TP_FAMILIES = ("mlp", "linear", "ft_transformer", "bert", "moe")


@dataclasses.dataclass
class TPTrainer:
    """Everything the TP training loop + dryrun need from one builder, so
    the product path and the driver's multichip dryrun compile the SAME
    config-derived program."""

    model: Any
    step_fn: Callable  # (TrainState, cat, num, lab, dropout_rng) -> (state, loss)
    state: TrainState  # initial (or graft-initialized) state
    shardings: TrainState  # NamedSharding tree matching ``state``
    mesh: Any

    # _layout_run_setup compatibility: the shared resume helper restores
    # {params, opt_state[, ema]} via these attributes.
    @property
    def params(self):
        return self.state.params

    @property
    def opt_state(self):
        return self.state.opt_state

    @property
    def ema(self):
        return self.state.ema


def make_tp_trainer(
    config: Config,
    mesh=None,
    init_variables: Any | None = None,
    compile_cache: Any | None = None,
) -> TPTrainer:
    """Build the DP×TP trainer for a ``model.tensor_parallel=K`` config.

    The mesh defaults to ('data', 'model') over ALL visible devices with
    the 'model' axis sized K — on a v5e slice the TP collectives
    (column/row all-gathers and reduce-scatters) ride ICI between
    adjacent chips. ``init_variables`` grafts a pretrained dense tree
    (same mechanism as the dense/PP fine-tune paths).
    """
    from mlops_tpu.models import build_model, init_params

    mcfg = config.model
    k = mcfg.tensor_parallel
    if k < 2:
        raise ValueError(
            f"make_tp_trainer needs model.tensor_parallel >= 2, got {k}"
        )
    if mcfg.family not in TP_FAMILIES:
        raise ValueError(
            f"tensor_parallel applies to the Flax families {TP_FAMILIES}, "
            f"not {mcfg.family!r}"
        )
    if mesh is None:
        n_dev = len(jax.devices())
        if n_dev % k:
            raise ValueError(
                f"model.tensor_parallel={k} needs the device count to be a "
                f"multiple of it; have {n_dev} (run on a v5e slice or the "
                f"fake {k}-device env)"
            )
        mesh = make_mesh(n_dev, model_parallel=k)
    elif mesh.shape.get("model", 1) != k:
        raise ValueError(
            f"config tensor_parallel={k} != mesh 'model' axis "
            f"{mesh.shape.get('model', 1)}"
        )
    dp = mesh.shape.get("data", 1)
    if config.train.batch_size % dp:
        # Fail with a named error before any training state exists — the
        # sharded step would otherwise die mid-run with an opaque XLA
        # "dimension not divisible" error (the PP trainer's guard class).
        raise ValueError(
            f"train.batch_size={config.train.batch_size} must divide by "
            f"the mesh 'data' axis {dp} (devices / tensor_parallel)"
        )

    # The MODEL is the plain dense family — TP is a layout, not a
    # different network (the same invariant the PP path pins with
    # forward-equality tests). Build it WITHOUT the layout knob so the
    # packaged bundle serves through the standard dense engine.
    model = build_model(dataclasses.replace(mcfg, tensor_parallel=0))
    variables = init_variables or init_params(
        model, jax.random.PRNGKey(config.train.seed)
    )
    params = variables["params"]
    optimizer = make_optimizer(config.train)
    step_fn, shardings = make_sharded_train_step(
        model, optimizer, config.train, mesh, params
    )
    state = TrainState(
        params=params,
        opt_state=optimizer.init(params),
        step=jnp.asarray(0, jnp.int32),
        rng=jax.random.PRNGKey(config.train.seed),
        ema=(
            jax.tree_util.tree_map(jnp.zeros_like, params)
            if config.train.ema_decay
            else None
        ),
    )
    if compile_cache is None:
        from mlops_tpu.compilecache.cache import from_config

        compile_cache = from_config(config)
    if compile_cache is not None:
        # AOT-load the pjit step through the persistent executable cache
        # (entry ``train-step-tp``), keyed by mesh shape + state/batch
        # signature; any OTHER batch shape falls back to the jitted step
        # so the cached executable is never fed a novel signature.
        from mlops_tpu.compilecache.warmup import tp_step_job

        batch = config.train.batch_size
        aot_step = compile_cache.load_or_compile(
            tp_step_job(
                model, optimizer, config.train, mesh, state, batch, step_fn
            )
        )
        jit_step = step_fn

        def step_fn(state, cat, num, lab, rng):  # noqa: F811 - guarded swap
            run = aot_step if cat.shape[0] == batch else jit_step
            return run(state, cat, num, lab, rng)

    return TPTrainer(
        model=model, step_fn=step_fn, state=state, shardings=shardings,
        mesh=mesh,
    )
