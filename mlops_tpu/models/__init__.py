"""Model zoo — Flax replacements for the reference's sklearn pipeline.

The reference's only model family is
``SimpleImputer + OneHotEncoder + RandomForestClassifier``
(`01-train-model.ipynb:195-227`). Tree ensembles don't map onto the MXU, so
the TPU-native zoo is:

- ``linear``          embedding-sum logistic regression (fast floor)
- ``mlp``             embeddings + residual MLP (flagship for serving)
- ``ft_transformer``  feature-tokenized transformer (BASELINE.json config 3)
- ``bert``            tabular-as-text BERT encoder with jit-fused
  tokenization (BASELINE.json config 5, the stretch)
- ``evabyte``         byte-level causal decoder (EVA chunked linear
  attention) over record HISTORIES rendered as text in-jit: consecutive
  rows are one history, every record gets its own answer
- ``kimi_k2``         token-level causal decoder with latent attention
  (MLA) and sparse routed experts beside a shared one, told which experts
  it holds (one chip's share of an expert-parallel layer); histories as
  ``evabyte`` reads them, a record the 48 tokens ``bert`` reads; the
  first family whose parameters may be stored in bfloat16
- ``lfm2_moe``        token-level causal hybrid decoder: each layer's token
  mixer, by a published per-layer list, a double-gated short convolution
  or grouped-query attention with normed heads; two dense layers, then
  sparse routed experts with no shared one (the expert layer of
  ``kimi_k2``, told which experts it holds); histories, tokens and
  bfloat16 parameters as ``kimi_k2``
- ``exaone_moe``      token-level causal decoder whose every layer is
  grouped-query attention with normed heads of a width of their own, by a
  published per-layer list over a sliding window (turned by rotary
  positions) or over every key so far (unturned); one dense layer, then
  sparse routed experts beside a shared one (the expert layer of
  ``kimi_k2``, the attention of ``lfm2_moe``); histories, tokens and
  bfloat16 parameters as ``kimi_k2``
- ``falcon_h1``       token-level causal hybrid decoder whose every layer
  runs a Mamba-2 state-space mixer (a chunked selective scan behind a
  plain causal short convolution) and grouped-query attention (unnormed
  heads, turned) side by side on one normed input and sums them, each
  path and projection under a muP multiplier of the configuration; a
  dense SwiGLU in every layer, no experts; histories, tokens and bfloat16
  parameters as ``kimi_k2``
- ``nemotron_h``      token-level causal hybrid decoder whose every layer
  is ONE kind by a published pattern: the Mamba-2 mixer of ``falcon_h1``
  (no multipliers), sparse routed experts of ``down(relu(up h)^2)``
  beside a shared one of its own width (the expert layer of ``kimi_k2``,
  told which experts it holds), or grouped-query attention (unnormed,
  unturned heads, a group of 16); histories, tokens and bfloat16
  parameters as ``kimi_k2``

All families share one calling convention:
``model.apply(vars, cat_ids[int32 N,C], numeric[f32 N,M], train=...) ->
logits[f32 N]`` so the trainer, bundle, and server are family-agnostic.
"""

from __future__ import annotations

import dataclasses

import jax
import jax.numpy as jnp
from flax import linen as nn

from mlops_tpu.config import ModelConfig
from mlops_tpu.models.bert import BertEncoder
from mlops_tpu.models.ensemble import DeepEnsemble
from mlops_tpu.models.evabyte import EvaByteScorer
from mlops_tpu.models.exaone_moe import ExaoneMoeScorer
from mlops_tpu.models.falcon_h1 import FalconH1Scorer
from mlops_tpu.models.ft_transformer import FTTransformer
from mlops_tpu.models.kimi_k2 import KimiK2Scorer
from mlops_tpu.models.lfm2_moe import Lfm2MoeScorer
from mlops_tpu.models.mlp import MLP, LinearModel
from mlops_tpu.models.moe import MoETransformer
from mlops_tpu.models.nemotron_h import NemotronHScorer
from mlops_tpu.schema.features import SCHEMA

FAMILIES = (
    "linear", "mlp", "ft_transformer", "moe", "bert", "evabyte", "kimi_k2", "lfm2_moe",
    "exaone_moe", "falcon_h1", "nemotron_h",
)
# the token-level decoders, whose parameters may be STORED in bfloat16
BF16_PARAM_FAMILIES = ("kimi_k2", "lfm2_moe", "exaone_moe", "falcon_h1", "nemotron_h")


def build_model(config: ModelConfig) -> nn.Module:
    """Instantiate a model family from config (embedding sizes from SCHEMA).

    ``ensemble_size > 1`` wraps the family in a vmapped deep ensemble
    (models/ensemble.py) — same calling convention, K× the params with a
    leading member axis.
    """
    if config.ensemble_size > 1:
        single = dataclasses.replace(config, ensemble_size=1)
        return DeepEnsemble(member=build_model(single), size=config.ensemble_size)
    dtype = {"bf16": jnp.bfloat16, "f32": jnp.float32}[config.precision]
    if config.param_dtype != "f32" and config.family not in BF16_PARAM_FAMILIES:
        raise ValueError(
            f"family {config.family!r} keeps float32 parameters; "
            f"model.param_dtype={config.param_dtype!r} is for {BF16_PARAM_FAMILIES}"
        )
    param_dtype = {"bf16": jnp.bfloat16, "f32": jnp.float32}[config.param_dtype]
    if config.family == "linear":
        return LinearModel(cards=SCHEMA.cards, dtype=dtype)
    if config.family == "mlp":
        return MLP(
            cards=SCHEMA.cards,
            embed_dim=config.embed_dim,
            hidden_dims=tuple(config.hidden_dims),
            dropout=config.dropout,
            dtype=dtype,
        )
    if config.family == "ft_transformer":
        return FTTransformer(
            cards=SCHEMA.cards,
            num_numeric=SCHEMA.num_numeric,
            token_dim=config.token_dim,
            depth=config.depth,
            heads=config.heads,
            dropout=config.dropout,
            dtype=dtype,
        )
    if config.family == "moe":
        return MoETransformer(
            cards=SCHEMA.cards,
            num_numeric=SCHEMA.num_numeric,
            token_dim=config.token_dim,
            depth=config.depth,
            heads=config.heads,
            num_experts=config.num_experts,
            dropout=config.dropout,
            dtype=dtype,
        )
    if config.family == "bert":
        return BertEncoder(
            cards=SCHEMA.cards,
            num_numeric=SCHEMA.num_numeric,
            hidden=config.token_dim,
            depth=config.depth,
            heads=config.heads,
            dropout=config.dropout,
            dtype=dtype,
        )
    if config.family == "evabyte":
        return EvaByteScorer(
            cards=SCHEMA.cards,
            num_numeric=SCHEMA.num_numeric,
            hidden=config.token_dim,
            depth=config.depth,
            heads=config.heads,
            ffn_dim=config.ffn_dim,
            window=config.attn_window,
            chunk=config.attn_chunk,
            rope_theta=config.rope_theta,
            records_per_history=config.doc_records,
            dtype=dtype,
        )
    if config.family == "kimi_k2":
        return KimiK2Scorer(
            cards=SCHEMA.cards,
            num_numeric=SCHEMA.num_numeric,
            hidden=config.token_dim,
            depth=config.depth,
            heads=config.heads,
            q_lora_rank=config.q_lora_rank,
            kv_lora_rank=config.kv_lora_rank,
            qk_nope_head_dim=config.qk_nope_head_dim,
            qk_rope_head_dim=config.qk_rope_head_dim,
            v_head_dim=config.v_head_dim,
            ffn_dim=config.ffn_dim,
            moe_ffn_dim=config.moe_ffn_dim,
            num_experts=config.num_experts,
            experts_per_token=config.experts_per_token,
            first_expert=config.first_expert,
            experts_held=config.experts_held or config.num_experts,
            vocab_rows=config.vocab_rows,
            records_per_history=config.doc_records,
            rope_theta=config.rope_theta,
            dtype=dtype,
            param_dtype=param_dtype,
        )
    if config.family == "lfm2_moe":
        return Lfm2MoeScorer(
            cards=SCHEMA.cards,
            num_numeric=SCHEMA.num_numeric,
            layer_types=tuple(config.layer_types),
            hidden=config.token_dim,
            depth=config.depth,
            heads=config.heads,
            kv_heads=config.kv_heads or config.heads,
            conv_width=config.conv_width,
            ffn_dim=config.ffn_dim,
            moe_ffn_dim=config.moe_ffn_dim,
            num_experts=config.num_experts,
            experts_per_token=config.experts_per_token,
            first_expert=config.first_expert,
            experts_held=config.experts_held or config.num_experts,
            vocab_rows=config.vocab_rows,
            records_per_history=config.doc_records,
            dense_layers=config.dense_layers,
            rope_theta=config.rope_theta,
            dtype=dtype,
            param_dtype=param_dtype,
        )
    if config.family == "exaone_moe":
        return ExaoneMoeScorer(
            cards=SCHEMA.cards,
            num_numeric=SCHEMA.num_numeric,
            layer_types=tuple(config.layer_types),
            hidden=config.token_dim,
            depth=config.depth,
            heads=config.heads,
            kv_heads=config.kv_heads or config.heads,
            head_dim=config.head_dim or config.token_dim // config.heads,
            window=config.attn_window,
            ffn_dim=config.ffn_dim,
            moe_ffn_dim=config.moe_ffn_dim,
            num_experts=config.num_experts,
            experts_per_token=config.experts_per_token,
            first_expert=config.first_expert,
            experts_held=config.experts_held or config.num_experts,
            vocab_rows=config.vocab_rows,
            records_per_history=config.doc_records,
            dense_layers=config.dense_layers,
            rope_theta=config.rope_theta,
            dtype=dtype,
            param_dtype=param_dtype,
        )
    if config.family == "falcon_h1":
        return FalconH1Scorer(
            cards=SCHEMA.cards,
            num_numeric=SCHEMA.num_numeric,
            hidden=config.token_dim,
            depth=config.depth,
            heads=config.heads,
            kv_heads=config.kv_heads or config.heads,
            head_dim=config.head_dim or config.token_dim // config.heads,
            ffn_dim=config.ffn_dim,
            ssm_dim=config.ssm_dim,
            ssm_heads=config.ssm_heads,
            ssm_state=config.ssm_state,
            ssm_groups=config.ssm_groups,
            ssm_chunk=config.ssm_chunk,
            conv_width=config.conv_width,
            vocab_rows=config.vocab_rows,
            records_per_history=config.doc_records,
            rope_theta=config.rope_theta,
            embedding_multiplier=config.embedding_multiplier,
            attention_in_multiplier=config.attention_in_multiplier,
            attention_out_multiplier=config.attention_out_multiplier,
            key_multiplier=config.key_multiplier,
            ssm_in_multiplier=config.ssm_in_multiplier,
            ssm_out_multiplier=config.ssm_out_multiplier,
            ssm_multipliers=tuple(config.ssm_multipliers),
            mlp_multipliers=tuple(config.mlp_multipliers),
            dtype=dtype,
            param_dtype=param_dtype,
        )
    if config.family == "nemotron_h":
        return NemotronHScorer(
            cards=SCHEMA.cards,
            num_numeric=SCHEMA.num_numeric,
            layer_types=tuple(config.layer_types),
            hidden=config.token_dim,
            depth=config.depth,
            heads=config.heads,
            kv_heads=config.kv_heads or config.heads,
            head_dim=config.head_dim or config.token_dim // config.heads,
            ssm_dim=config.ssm_dim,
            ssm_heads=config.ssm_heads,
            ssm_state=config.ssm_state,
            ssm_groups=config.ssm_groups,
            ssm_chunk=config.ssm_chunk,
            conv_width=config.conv_width,
            moe_ffn_dim=config.moe_ffn_dim,
            shared_ffn_dim=config.shared_ffn_dim or config.moe_ffn_dim,
            num_experts=config.num_experts,
            experts_per_token=config.experts_per_token,
            first_expert=config.first_expert,
            experts_held=config.experts_held or config.num_experts,
            vocab_rows=config.vocab_rows,
            records_per_history=config.doc_records,
            dtype=dtype,
            param_dtype=param_dtype,
        )
    from mlops_tpu.models.gbm import SKLEARN_FAMILIES

    if config.family in SKLEARN_FAMILIES:
        raise ValueError(
            f"family {config.family!r} is the CPU sklearn baseline (BASELINE "
            "config 1) — it has no Flax module; train it via `run_training` / "
            "the `train` CLI, which packages it as a sklearn-flavor bundle"
        )
    raise ValueError(f"unknown model family {config.family!r}; one of {FAMILIES}")


def init_params(model: nn.Module, rng: jax.Array, batch: int = 2):
    """Initialize variables with dummy fixed-shape inputs."""
    cat = jnp.zeros((batch, SCHEMA.num_categorical), jnp.int32)
    num = jnp.zeros((batch, SCHEMA.num_numeric), jnp.float32)
    return model.init({"params": rng}, cat, num, train=False)


def abstract_variables(model: nn.Module, batch: int = 2):
    """Variable SHAPES via ``jax.eval_shape`` — init never runs, no
    parameters materialize. The one definition shared by tpulint's Layer-2
    entry-point registry (`analysis/entrypoints.py`) and the compile-cache
    warmup (`compilecache/warmup.py`): both must derive identical abstract
    signatures or the analyzer and the cache disagree about the programs.
    """

    def init():
        return init_params(model, jax.random.PRNGKey(0), batch=batch)

    return jax.eval_shape(init)


__all__ = [
    "FAMILIES",
    "BertEncoder",
    "DeepEnsemble",
    "EvaByteScorer",
    "ExaoneMoeScorer",
    "FTTransformer",
    "FalconH1Scorer",
    "KimiK2Scorer",
    "Lfm2MoeScorer",
    "LinearModel",
    "MLP",
    "MoETransformer",
    "NemotronHScorer",
    "build_model",
    "init_params",
]
