"""An LFM2-MoE-style hybrid sparse decoder (``model_type: lfm2_moe``) as a
token-level history scorer: a token mixer chosen layer by layer from the
published ``layer_types`` list (a double-gated short convolution, or
causal grouped-query attention with RMSNorm on each head's query and
key), two leading dense SwiGLU layers, then layers of routed experts with
no shared one, under the zoo's calling convention and the read-out of
`models/kimi_k2.py`.

- **Rows in, an answer a row out.** ``apply(vars, cat_ids[N, C],
  numeric[N, M], train) -> logits[N]``; every ``records_per_history``
  consecutive rows (from row 0) are ONE history, the last may be shorter:
  the history scorers' rule (`ModelConfig.history_rows`). Both mixers are
  causal, so rows padded behind a record never change its answer, and the
  convolution pads each history on the left: it never reads across a
  history's start.
- **Input.** A record is the 48 tokens `models/bert.py tokenize` gives,
  in-jit; token ``t`` of the layout's ``V`` reads row ``t * (vocab_rows //
  V)`` of the embedding.
- **A layer**, pre-norm on the float32 residual stream (RMSNorm, eps
  1e-5, a plain weight): ``x += mixer(operator_norm x)``; ``x +=
  FFN(ffn_norm x)``.
- **The convolution** (`ops/short_conv.py`): ``in_proj`` (hidden -> 3
  hidden: in-gate, out-gate, signal), ``conv_width`` depthwise taps,
  ``out_proj``; no biases.
- **The attention**: ``q`` (``heads`` of ``hidden // heads``), ``k``,
  ``v`` (``kv_heads`` of the same width), RMSNorm over each head's query
  and key, then `ops/eva_attention.py rope` (rotate-half, plain
  frequencies), `ops/causal_attention.py causal_attend` (query head ``i``
  over key/value head ``i // (heads // kv_heads)``, keys and values never
  repeated; plain XLA in blocks of queries: two Pallas forms of it were
  slower on the chip, PERF.md section 6, PR 33), ``o``.
- **The FFN**: a dense SwiGLU of ``ffn_dim`` in the first ``dense_layers``
  layers; after them `models/routed_experts.py` over
  `ops/moe_dispatch.py`: a sigmoid router with a selection bias,
  ``experts_per_token`` experts a token, weights normalised over the
  chosen (``+ 1e-6``) and scaled by 1, told ``(first_expert,
  experts_held)`` and counting into the ``routing`` collection.
- **Precision.** Parameters are stored in ``param_dtype``; products take
  ``dtype`` operands and accumulate in float32; residual stream, norms,
  the gates' products and the taps, softmax, router and head are float32.
- **Read-out**: the final RMSNorm at each record's last token, then
  ``head`` (hidden -> 1) in float32. The last layer computes its mixer's
  inputs (the convolution whole; keys and values) at every position and
  everything behind them at the read positions.

Scopes for a device trace: ``conv_in``, ``short_conv``, ``conv_out``;
``gqa_qkv``, ``gqa_attend``, ``gqa_o``; ``router``, ``moe_dispatch``,
``experts``, ``moe_combine``; beside ``embed``, ``ffn`` (the dense layers)
and ``head``.
"""

from __future__ import annotations

from typing import Sequence

import jax
import jax.numpy as jnp
import numpy as np
from flax import linen as nn

from mlops_tpu.models.bert import TokenLayout, tokenize_histories
from mlops_tpu.models.evabyte import RMSNorm
from mlops_tpu.models.routed_experts import (
    ROUTING,
    check_share,
    routed_experts,
    routing_counts,
)
from mlops_tpu.ops.causal_attention import causal_attend
from mlops_tpu.ops.eva_attention import rope
from mlops_tpu.ops.short_conv import short_conv

LAYER_TYPES = ("conv", "full_attention")
ROUTE_EPS = 1e-6  # the router's normaliser (the source's modelling code)
ROUTED_SCALING = 1.0  # the source's routed_scaling_factor


class _Taps(nn.Module):
    """The convolution's filters, ``kernel`` ``[width, channels]``: one a
    channel (depthwise)."""

    width: int
    param_dtype: jnp.dtype

    @nn.compact
    def __call__(self, channels: int) -> jnp.ndarray:
        init = nn.initializers.lecun_normal(in_axis=0, out_axis=1)
        return self.param("kernel", init, (self.width, channels), self.param_dtype)


class Lfm2Block(nn.Module):
    """One decoder layer on the float32 residual stream ``[B, S, dim]``.
    With ``read`` (positions), the layer returns those positions only."""

    layer_type: str  # "conv" | "full_attention"
    heads: int
    kv_heads: int
    conv_width: int
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

    def _conv(self, h: jnp.ndarray, read: np.ndarray | None) -> jnp.ndarray:
        dim = h.shape[-1]
        with jax.named_scope("conv_in"):
            bcu = self._dense(3 * dim, "in_proj")(h)
        mixed = short_conv(bcu, _Taps(self.conv_width, self.param_dtype, name="conv")(dim))
        with jax.named_scope("conv_out"):
            return self._dense(dim, "out_proj")(mixed if read is None else mixed[:, read])

    def _attention(self, h: jnp.ndarray, read: np.ndarray | None) -> jnp.ndarray:
        b, seq, dim = h.shape
        width = dim // self.heads
        with jax.named_scope("gqa_qkv"):
            asked = h if read is None else h[:, read]
            q = self._dense(self.heads * width, "q")(asked).reshape(b, -1, self.heads, width)
            k = self._dense(self.kv_heads * width, "k")(h).reshape(b, seq, self.kv_heads, width)
            v = self._dense(self.kv_heads * width, "v")(h).reshape(b, seq, self.kv_heads, width)
            # a head's query and key are normed (float32) before they turn
            q = rope(self._norm("q_norm")(q), self.rope_theta, positions=read)
            k = rope(self._norm("k_norm")(k), self.rope_theta)
        with jax.named_scope("gqa_attend"):
            mixed = causal_attend(
                q.astype(self.dtype), k.astype(self.dtype), v, width**-0.5, read=read
            )
        with jax.named_scope("gqa_o"):
            return self._dense(dim, "o")(mixed.reshape(b, -1, self.heads * width))

    def _swiglu(self, h: jnp.ndarray) -> jnp.ndarray:
        gate = self._dense(self.ffn_dim, "gate")(h)
        up = self._dense(self.ffn_dim, "up")(h)
        gated = nn.silu(gate.astype(jnp.float32)) * up.astype(jnp.float32)
        return self._dense(h.shape[-1], "down")(gated.astype(self.dtype))

    @nn.compact
    def __call__(self, x: jnp.ndarray, read: np.ndarray | None = None) -> jnp.ndarray:
        h = self._norm("operator_norm")(x).astype(self.dtype)
        mixer = {"conv": self._conv, "full_attention": self._attention}[self.layer_type]
        mixed = mixer(h, read)
        if read is not None:
            x = x[:, read]
        x = x + mixed.astype(jnp.float32)
        b, seq, dim = x.shape
        h = self._norm("ffn_norm")(x).reshape(b * seq, dim)
        if self.ffn_dim:
            with jax.named_scope("ffn"):
                out = self._swiglu(h.astype(self.dtype)).astype(jnp.float32)
        else:
            out = routed_experts(self, h, scaling=ROUTED_SCALING, eps=ROUTE_EPS)
        return x + out.reshape(b, seq, dim)


class Lfm2MoeScorer(nn.Module):
    """``apply(vars, cat_ids, numeric, train) -> logits[f32 N]``: the zoo
    convention, one logit a record, read at the record's last token."""

    cards: Sequence[int]
    num_numeric: int
    layer_types: Sequence[str]  # at least ``depth`` entries; layer i takes the i-th
    hidden: int = 2048
    depth: int = 24
    heads: int = 32
    kv_heads: int = 8
    conv_width: int = 3
    ffn_dim: int = 7168
    moe_ffn_dim: int = 1792
    num_experts: int = 32
    experts_per_token: int = 4
    first_expert: int = 0
    experts_held: int = 32
    vocab_rows: int = 65536
    records_per_history: int = 64
    dense_layers: int = 2  # the source's num_dense_layers
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
        if self.heads % self.kv_heads or self.hidden % self.heads:
            raise ValueError(
                f"{self.heads} query heads over {self.kv_heads} key/value heads "
                f"in a hidden size of {self.hidden}"
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
            x = Lfm2Block(
                layer_type=self.layer_types[i],
                heads=self.heads,
                kv_heads=self.kv_heads,
                conv_width=self.conv_width,
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
