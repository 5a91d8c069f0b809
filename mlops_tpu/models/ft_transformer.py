"""FT-Transformer: feature-tokenized transformer for tabular data.

BASELINE.json config 3 ("FT-Transformer tabular model on credit-default").
Each of the 23 features becomes one token: categoricals via embedding lookup,
numerics via a learned per-feature direction scaled by the standardized
value. A CLS token aggregates; pre-LN transformer blocks; the head reads CLS.

TPU notes: sequence length is 24 (23 features + CLS) — attention here is a
small batched matmul, ideal MXU shape when heads*head_dim is a multiple of
128; everything is bf16 compute / f32 params; no dynamic shapes anywhere.
The attention inner loop is also the framework's first Pallas candidate
(``mlops_tpu.ops.attention``) though at seq=24 XLA's fused attention is
already near-roofline.
"""

from __future__ import annotations

from typing import Callable, Sequence

import jax
import jax.numpy as jnp
from flax import linen as nn

from mlops_tpu.models.layers import MultiHeadSelfAttention


class FeatureTokenizer(nn.Module):
    """Map (cat_ids, numeric) -> token sequence [N, F+1, D] with CLS first."""

    cards: Sequence[int]
    num_numeric: int
    token_dim: int
    dtype: jnp.dtype = jnp.bfloat16

    @nn.compact
    def __call__(self, cat_ids: jnp.ndarray, numeric: jnp.ndarray) -> jnp.ndarray:
        n = cat_ids.shape[0]
        # Categorical tokens: one embedding table per feature, stacked.
        cat_tokens = []
        for j, card in enumerate(self.cards):
            table = nn.Embed(card, self.token_dim, dtype=self.dtype, name=f"cat_{j}")
            cat_tokens.append(table(cat_ids[:, j]))
        cat_tok = jnp.stack(cat_tokens, axis=1)  # [N, C, D]

        # Numeric tokens: value * learned direction + per-feature bias.
        weight = self.param(
            "num_weight",
            nn.initializers.normal(0.02),
            (self.num_numeric, self.token_dim),
        )
        bias = self.param(
            "num_bias",
            nn.initializers.zeros_init(),
            (self.num_numeric, self.token_dim),
        )
        num_tok = (
            numeric[:, :, None].astype(self.dtype) * weight.astype(self.dtype)
            + bias.astype(self.dtype)
        )  # [N, M, D]

        cls = self.param(
            "cls", nn.initializers.normal(0.02), (1, 1, self.token_dim)
        )
        cls_tok = jnp.broadcast_to(cls.astype(self.dtype), (n, 1, self.token_dim))
        return jnp.concatenate([cls_tok, cat_tok, num_tok], axis=1)


class TransformerBlock(nn.Module):
    """Pre-LN block: MHA + GELU MLP, residual, dropout.

    ``attend_fn`` (optional) overrides the attention kernel — the
    sequence-parallel BERT path injects the shard_map'd ring
    (`parallel.make_ring_attention`) through here.
    """

    heads: int
    token_dim: int
    dropout: float
    dtype: jnp.dtype = jnp.bfloat16
    attend_fn: Callable | None = None

    @nn.compact
    def __call__(self, x: jnp.ndarray, *, train: bool) -> jnp.ndarray:
        h = nn.LayerNorm(dtype=self.dtype)(x)
        h = MultiHeadSelfAttention(
            heads=self.heads,
            dtype=self.dtype,
            dropout=self.dropout,
            attend_fn=self.attend_fn,
        )(h, deterministic=not train)
        x = x + nn.Dropout(self.dropout, deterministic=not train)(h)

        # flax names what is a module (LayerNorm_1, Dense_0) in a device
        # trace; the FFN half as a whole is no module, so it is named here
        with jax.named_scope("ffn"):
            h = nn.LayerNorm(dtype=self.dtype)(x)
            # MLP on [N*S, D]: same params/numerics, but the backward's dW
            # is a single 2D GEMM instead of a two-contracting-dims
            # dot_general XLA:CPU can't run fast (see
            # MultiHeadSelfAttention's note).
            n, s, d = h.shape
            h = h.reshape(n * s, d)
            h = nn.Dense(4 * self.token_dim, dtype=self.dtype)(h)
            h = nn.gelu(h)
            h = nn.Dropout(self.dropout, deterministic=not train)(h)
            h = nn.Dense(self.token_dim, dtype=self.dtype)(h)
            return x + h.reshape(n, s, d)


def apply_ft_head(mod: nn.Module, x: jnp.ndarray, dtype: jnp.dtype) -> jnp.ndarray:
    """The FT read-out (ln_final on CLS → head logit), factored so the
    pipeline-parallel split (`train/pipeline_parallel.py`) produces a
    byte-compatible param tree."""
    cls = nn.LayerNorm(dtype=dtype, name="ln_final")(x[:, 0])
    logit = nn.Dense(1, dtype=dtype, name="head")(cls)
    return logit[:, 0].astype(jnp.float32)


class FTTransformer(nn.Module):
    cards: Sequence[int]
    num_numeric: int
    token_dim: int = 64
    depth: int = 3
    heads: int = 8
    dropout: float = 0.1
    dtype: jnp.dtype = jnp.bfloat16

    @nn.compact
    def __call__(
        self, cat_ids: jnp.ndarray, numeric: jnp.ndarray, *, train: bool = False
    ) -> jnp.ndarray:
        # Name pinned explicitly: it is a cross-file contract — the
        # pipeline-parallel split slices the dense tree by this key
        # (`train/pipeline_parallel.py` _FAMILY_SPLITS).
        tokens = FeatureTokenizer(
            self.cards,
            self.num_numeric,
            self.token_dim,
            dtype=self.dtype,
            name="FeatureTokenizer_0",
        )(cat_ids, numeric)
        for i in range(self.depth):
            tokens = TransformerBlock(
                heads=self.heads,
                token_dim=self.token_dim,
                dropout=self.dropout,
                dtype=self.dtype,
                name=f"block_{i}",
            )(tokens, train=train)
        return apply_ft_head(self, tokens, self.dtype)
