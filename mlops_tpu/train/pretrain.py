"""Masked-feature pretraining for the BERT family (BASELINE config 5).

The reference trains supervised-only (sklearn on labeled rows). The BERT
stretch config says "fine-tune", which implies something to fine-tune FROM:
this loop pretrains the encoder trunk on unlabeled rows with the
masked-feature objective (``models.bert.BertMaskedLM``) — 15% of value
tokens masked per row, cross-entropy on the masked positions only — then
``fine_tune_params`` grafts the trunk into the classifier for the standard
supervised trainer. Jitted scan over steps, data-parallel-ready (the step
is pure; shard the batch axis like any other step).
"""

from __future__ import annotations

import dataclasses
from functools import partial
from typing import Any

import jax
import jax.numpy as jnp
import numpy as np
import optax

from mlops_tpu.config import ModelConfig, TrainConfig
from mlops_tpu.data.encode import EncodedDataset
from mlops_tpu.models.bert import BertMaskedLM, transfer_encoder_params
from mlops_tpu.schema.features import SCHEMA

MASK_FRACTION = 0.15


@dataclasses.dataclass
class PretrainResult:
    params: Any  # trunk + mlm head
    losses: list[float]  # per-eval-interval mean masked-token loss


def build_mlm(config: ModelConfig) -> BertMaskedLM:
    dtype = {"bf16": jnp.bfloat16, "f32": jnp.float32}[config.precision]
    return BertMaskedLM(
        cards=SCHEMA.cards,
        num_numeric=SCHEMA.num_numeric,
        hidden=config.token_dim,
        depth=config.depth,
        heads=config.heads,
        dropout=config.dropout,
        dtype=dtype,
    )


def masked_loss(logits, targets, mask):
    """Mean cross-entropy over masked positions only."""
    logp = jax.nn.log_softmax(logits, axis=-1)
    nll = -jnp.take_along_axis(logp, targets[..., None], axis=-1)[..., 0]
    denom = jnp.maximum(mask.sum(), 1)
    return jnp.where(mask, nll, 0.0).sum() / denom


def pretrain_bert(
    model_config: ModelConfig,
    ds: EncodedDataset,
    steps: int = 1000,
    batch_size: int = 256,
    learning_rate: float = 3e-4,
    seed: int = 0,
) -> PretrainResult:
    """Pretrain on an encoded (unlabeled) dataset; returns MLM params."""
    model = build_mlm(model_config)
    value_pos = jnp.asarray(model.value_positions())
    seq_len = model.layout.seq_len

    rng = jax.random.PRNGKey(seed)
    rng, init_rng = jax.random.split(rng)
    n = ds.n
    batch_size = min(batch_size, n)

    cat = jnp.asarray(ds.cat_ids)
    num = jnp.asarray(ds.numeric)

    init_mask = jnp.zeros((2, seq_len), bool)
    variables = model.init(
        {"params": init_rng}, cat[:2], num[:2], init_mask, train=False
    )
    params = variables["params"]
    tx = optax.adamw(learning_rate)
    opt_state = tx.init(params)

    def sample_mask(rng, batch):
        """Bernoulli(0.15) over value positions; guarantee >=1 mask/row by
        forcing one uniformly-chosen value position when none drew."""
        r1, r2 = jax.random.split(rng)
        draw = (
            jax.random.uniform(r1, (batch, value_pos.shape[0]))
            < MASK_FRACTION
        )
        forced = jax.nn.one_hot(
            jax.random.randint(r2, (batch,), 0, value_pos.shape[0]),
            value_pos.shape[0],
            dtype=bool,
        )
        draw = jnp.where(draw.any(axis=1, keepdims=True), draw, forced)
        mask = jnp.zeros((batch, seq_len), bool)
        return mask.at[:, value_pos].set(draw)

    # Scan body — run() below owns (and donates) the carry buffers; a
    # second donation here would double-free them.
    @jax.jit
    def step(carry, _):  # tpulint: disable=TPU105
        params, opt_state, rng = carry
        rng, bkey, mkey, dkey = jax.random.split(rng, 4)
        idx = jax.random.randint(bkey, (batch_size,), 0, n)
        mask = sample_mask(mkey, batch_size)

        def loss_fn(p):
            logits, targets = model.apply(
                {"params": p}, cat[idx], num[idx], mask,
                train=True, rngs={"dropout": dkey},
            )
            return masked_loss(logits, targets, mask)

        loss, grads = jax.value_and_grad(loss_fn)(params)
        updates, opt_state = tx.update(grads, opt_state, params)
        params = optax.apply_updates(params, updates)
        return (params, opt_state, rng), loss

    # The initial carry is never reused after the call: donate it so the
    # params + adam moments update in place in HBM instead of
    # double-buffering (tpulint TPU105).
    @partial(jax.jit, static_argnums=1, donate_argnums=(0,))
    def run(carry, n_steps):
        return jax.lax.scan(step, carry, None, length=n_steps)

    (params, opt_state, rng), losses = run((params, opt_state, rng), steps)
    losses = np.asarray(jax.device_get(losses))
    # Coarse loss curve (10 buckets) for logging/tests.
    chunks = np.array_split(losses, min(10, len(losses)))
    return PretrainResult(
        params=params, losses=[float(c.mean()) for c in chunks]
    )


def fine_tune_params(pretrain: PretrainResult, classifier_variables) -> Any:
    """Graft the pretrained trunk into freshly-initialized classifier
    variables (heads keep their init); feed to the standard trainer."""
    params = dict(classifier_variables["params"])
    merged = transfer_encoder_params(dict(pretrain.params), params)
    return {**classifier_variables, "params": merged}


def save_pretrained(result: PretrainResult, path) -> None:
    from pathlib import Path

    from mlops_tpu.train.checkpoint import tree_bytes
    from mlops_tpu.utils.io import atomic_write

    atomic_write(Path(path), tree_bytes(result.params))


def load_pretrained_variables(
    path, model_config: ModelConfig, classifier_variables
) -> Any:
    """Load saved MLM params and graft them into classifier variables."""
    from pathlib import Path

    from mlops_tpu.train.checkpoint import restore_tree

    if model_config.family != "bert":
        # The graft matches subtrees by NAME. mlp/linear share nothing
        # (the graft would be a silent no-op and "fine-tuning" would
        # start from a fresh model); ft_transformer shares the block_i
        # names and would silently absorb bert-pretrained blocks. Every
        # caller must hit this, so the check lives here, not per site.
        raise ValueError(
            "train.init_params grafts a bert masked-LM trunk by name; "
            f"family {model_config.family!r} shares no trunk with it"
        )

    mlm = build_mlm(model_config)
    seq_len = mlm.layout.seq_len
    template = mlm.init(
        {"params": jax.random.PRNGKey(0)},
        jnp.zeros((2, SCHEMA.num_categorical), jnp.int32),
        jnp.zeros((2, SCHEMA.num_numeric), jnp.float32),
        jnp.zeros((2, seq_len), bool),
        train=False,
    )["params"]
    params = restore_tree(template, Path(path).read_bytes())
    return fine_tune_params(
        PretrainResult(params=params, losses=[]), classifier_variables
    )
