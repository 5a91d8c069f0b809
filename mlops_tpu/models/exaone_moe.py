"""A K-EXAONE-style sparse decoder (``model_type: exaone_moe``) as a
token-level history scorer: causal grouped-query attention in every
layer, by the published ``layer_types`` list either over a sliding window
or over every key so far, with a head width of its own (``heads *
head_dim`` need not be the hidden size); one leading dense SwiGLU layer,
then layers of routed experts beside one shared expert; under the zoo's
calling convention and the read-out of `models/kimi_k2.py`.

- **Rows in, an answer a row out.** ``apply(vars, cat_ids[N, C],
  numeric[N, M], train) -> logits[N]``; every ``records_per_history``
  consecutive rows (from row 0) are ONE history, the last may be shorter:
  the history scorers' rule (`ModelConfig.history_rows`). The model is
  causal, so rows padded behind a record never change its answer, and a
  window never reaches across a history's start.
- **Input.** A record is the 48 tokens `models/bert.py tokenize` gives,
  in-jit; token ``t`` of the layout's ``V`` reads row ``t * (vocab_rows //
  V)`` of the embedding slice this chip holds.
- **A layer**, pre-norm on the float32 residual stream (RMSNorm, eps
  1e-5, a plain weight): ``x += attention(attn_norm x)``; ``x +=
  FFN(ffn_norm x)``.
- **The attention** (`models/grouped_attention.py`, which
  `models/lfm2_moe.py` calls too): ``q`` (``heads`` of ``head_dim``),
  ``k``, ``v`` (``kv_heads`` of the same width), RMSNorm over each head's
  query and key, `ops/causal_attention.py causal_attend`, ``o`` (``heads *
  head_dim`` -> hidden). A ``sliding_attention`` layer turns its queries
  and keys (`ops/eva_attention.py rope`, rotate-half, plain frequencies)
  and a query sees itself and the ``window - 1`` keys before it: the op
  computes the band and nothing left of it. A ``full_attention`` layer
  sees every key up to the query and does NOT turn (no positional
  encoding where the attention is global).
- **The FFN**: a dense SwiGLU of ``ffn_dim`` in the first ``dense_layers``
  layers; after them `models/routed_experts.py` over
  `ops/moe_dispatch.py`: a sigmoid router with a selection bias,
  ``experts_per_token`` experts a token, weights normalised over the
  chosen (``+ 1e-20``) and scaled by 2.5, told ``(first_expert,
  experts_held)`` and counting into the ``routing`` collection, beside
  one shared expert of the experts' width, whole and unweighted
  (`experts_beside_a_shared_one`, as `models/kimi_k2.py`).
- **Precision.** Parameters are stored in ``param_dtype``; products take
  ``dtype`` operands and accumulate in float32; residual stream, norms,
  softmax, router and head are float32.
- **Read-out**: the final RMSNorm at each record's last token, then
  ``head`` (hidden -> 1) in float32. The last layer, of either kind,
  computes keys and values at every position and everything else at the
  read positions.

Scopes for a device trace: ``swa_qkv``, ``swa_attend``, ``swa_o`` in the
window layers and ``gqa_qkv``, ``gqa_attend``, ``gqa_o`` in the full ones
(`models/lfm2_moe.py`'s names); ``router``, ``moe_dispatch``, ``experts``,
``moe_combine``, ``shared_expert``; beside ``embed``, ``ffn`` (the dense
layer), ``head`` and ``rope``.
"""

from __future__ import annotations

from typing import Sequence

import jax
import jax.numpy as jnp
import numpy as np
from flax import linen as nn

from mlops_tpu.models.bert import TokenLayout, tokenize_histories
from mlops_tpu.models.evabyte import RMSNorm
from mlops_tpu.models.grouped_attention import GQA_SCOPES, grouped_query_attention
from mlops_tpu.models.routed_experts import (
    ROUTING,
    check_share,
    experts_beside_a_shared_one,
    routing_counts,
    swiglu,
)

LAYER_TYPES = ("sliding_attention", "full_attention")
SWA_SCOPES = ("swa_qkv", "swa_attend", "swa_o")
ROUTE_EPS = 1e-20  # the router's normaliser (DeepSeek-V3's, as `models/kimi_k2.py`)
ROUTED_SCALING = 2.5  # the source's routed_scaling_factor
# queries a block of a full layer: the fastest of 128 / 256 / 512 on the chip
# at the published widths (a job of the cell 1.794 s against 1.804 at 256; a
# layer alone 50.2 / 51.2 / 54.7 ms), at the least scratch (4.57 / 4.57 /
# 4.78 GB: 64 heads' float32 scores of 512 queries against 3,072 keys are
# 0.40 GB a history); PERF.md section 6, PR 37
FULL_QUERY_BLOCK = 128


class ExaoneBlock(nn.Module):
    """One decoder layer on the float32 residual stream ``[B, S, dim]``.
    With ``read`` (positions), the layer returns those positions only."""

    layer_type: str  # "sliding_attention" | "full_attention"
    heads: int
    kv_heads: int
    head_dim: int
    window: int
    ffn_dim: int  # the dense SwiGLU's width; 0: this is an expert layer
    moe_ffn_dim: int
    num_experts: int
    experts_per_token: int
    first_expert: int
    experts_held: int
    rope_theta: float
    dtype: jnp.dtype = jnp.bfloat16
    param_dtype: jnp.dtype = jnp.float32

    def _dense(self, features: int, name: str) -> nn.Dense:
        return nn.Dense(
            features, use_bias=False, dtype=self.dtype, param_dtype=self.param_dtype,
            name=name,
        )

    def _norm(self, name: str) -> RMSNorm:
        return RMSNorm(unit_offset=False, param_dtype=self.param_dtype, name=name)

    def _attention(self, h: jnp.ndarray, read: np.ndarray | None) -> jnp.ndarray:
        if self.layer_type == "sliding_attention":
            return grouped_query_attention(
                self, h, read, head_dim=self.head_dim, scopes=SWA_SCOPES,
                window=self.window, turn=True,
            )
        return grouped_query_attention(
            self, h, read, head_dim=self.head_dim, scopes=GQA_SCOPES,
            turn=False, query_block=FULL_QUERY_BLOCK,
        )

    @nn.compact
    def __call__(self, x: jnp.ndarray, read: np.ndarray | None = None) -> jnp.ndarray:
        mixed = self._attention(self._norm("attn_norm")(x).astype(self.dtype), read)
        if read is not None:
            x = x[:, read]
        x = x + mixed.astype(jnp.float32)
        b, seq, dim = x.shape
        h = self._norm("ffn_norm")(x).reshape(b * seq, dim)
        if self.ffn_dim:
            with jax.named_scope("ffn"):
                out = swiglu(self, h.astype(self.dtype), self.ffn_dim).astype(jnp.float32)
        else:
            out = experts_beside_a_shared_one(
                self, h, scaling=ROUTED_SCALING, eps=ROUTE_EPS
            )
        return x + out.reshape(b, seq, dim)


class ExaoneMoeScorer(nn.Module):
    """``apply(vars, cat_ids, numeric, train) -> logits[f32 N]``: the zoo
    convention, one logit a record, read at the record's last token."""

    cards: Sequence[int]
    num_numeric: int
    layer_types: Sequence[str]  # at least ``depth`` entries; layer i takes the i-th
    hidden: int = 6144
    depth: int = 48
    heads: int = 64
    kv_heads: int = 8
    head_dim: int = 128
    window: int = 128
    ffn_dim: int = 18432
    moe_ffn_dim: int = 2048
    num_experts: int = 128
    experts_per_token: int = 8
    first_expert: int = 0
    experts_held: int = 128
    vocab_rows: int = 153600
    records_per_history: int = 64
    dense_layers: int = 1  # the source's first_k_dense_replace
    rope_theta: float = 1000000.0
    num_bins: int = 32
    dtype: jnp.dtype = jnp.bfloat16
    param_dtype: jnp.dtype = jnp.float32

    # what `parallel/bulk.py` asks a model with sparse experts for
    routing_collection = ROUTING
    routing_counts = staticmethod(routing_counts)

    @property
    def layout(self) -> TokenLayout:
        return TokenLayout(tuple(self.cards), self.num_numeric, self.num_bins)

    @property
    def tokens_per_row(self) -> int:
        return self.layout.seq_len

    def _check(self, stride: int) -> None:
        if not stride:
            raise ValueError(
                f"{self.vocab_rows} embedding rows for {self.layout.vocab_size} tokens"
            )
        check_share(
            self.first_expert, self.experts_held, self.num_experts, self.experts_per_token
        )
        listed = tuple(self.layer_types[: self.depth])
        if len(listed) < self.depth or set(listed) - set(LAYER_TYPES):
            raise ValueError(
                f"layer_types names {len(listed)} of {self.depth} layers, "
                f"each one of {LAYER_TYPES}: {listed}"
            )
        if self.heads % self.kv_heads or self.head_dim % 2 or self.window < 1:
            raise ValueError(
                f"{self.heads} query heads over {self.kv_heads} key/value heads "
                f"of {self.head_dim}, a window of {self.window}"
            )

    @nn.compact
    def __call__(
        self, cat_ids: jnp.ndarray, numeric: jnp.ndarray, *, train: bool = False
    ) -> jnp.ndarray:
        layout = self.layout
        stride = self.vocab_rows // layout.vocab_size
        self._check(stride)
        n = cat_ids.shape[0]
        tokens, read = tokenize_histories(
            cat_ids, numeric, layout, self.records_per_history
        )
        with jax.named_scope("embed"):
            # rows are looked up as stored and widened after
            x = nn.Embed(
                self.vocab_rows, self.hidden, dtype=self.param_dtype,
                param_dtype=self.param_dtype, name="tok_embed",
            )(tokens * stride).astype(jnp.float32)
        for i in range(self.depth):
            x = ExaoneBlock(
                layer_type=self.layer_types[i],
                heads=self.heads,
                kv_heads=self.kv_heads,
                head_dim=self.head_dim,
                window=self.window,
                ffn_dim=self.ffn_dim if i < self.dense_layers else 0,
                moe_ffn_dim=self.moe_ffn_dim,
                num_experts=self.num_experts,
                experts_per_token=self.experts_per_token,
                first_expert=self.first_expert,
                experts_held=self.experts_held,
                rope_theta=self.rope_theta,
                dtype=self.dtype,
                param_dtype=self.param_dtype,
                name=f"block_{i}",
            )(x, read=read if i == self.depth - 1 else None)
        with jax.named_scope("head"):
            logits = nn.Dense(
                1, dtype=jnp.float32, param_dtype=self.param_dtype, name="head"
            )(RMSNorm(unit_offset=False, param_dtype=self.param_dtype, name="final_norm")(x))
        return logits.reshape(-1)[:n]
