"""A DeepSeek-V3-style sparse decoder (``model_type: kimi_k2``) as a
token-level history scorer: multi-head latent attention, a leading dense
SwiGLU layer, then layers of routed experts beside one shared expert,
under the zoo's calling convention and the repo's own read-out.

- **Rows in, an answer a row out.** ``apply(vars, cat_ids[N, C],
  numeric[N, M], train) -> logits[N]``; every ``records_per_history``
  consecutive rows (from row 0) are ONE history, the last may be shorter,
  exactly `models/evabyte.py`'s rule: the model is causal, rows padded
  behind a record never change its answer, and whoever cuts rows into
  calls keeps histories whole (`ModelConfig.history_rows`).
- **Input.** A record is the 48 tokens `models/bert.py tokenize` gives
  (``[CLS] name value ... [SEP]``), in-jit. This chip holds ``vocab_rows``
  rows of the embedding (a slice of the vocabulary is a smaller
  vocabulary); token ``t`` of the layout's ``V`` reads row ``t *
  (vocab_rows // V)``, so the ids are spread over the slice.
- **A layer**, pre-norm on the float32 residual stream: ``x += MLA(norm
  x)``; ``x += FFN(norm x)``, the FFN a dense SwiGLU in the first
  ``dense_layers`` layers and the expert layer after them.
- **MLA** (`ops/mla.py`): a low-rank query path (``q_a``, norm, ``q_b``),
  keys and values expanded from a normed latent (``kv_a``, norm,
  ``kv_b``), a rotary key part shared by all heads, YaRN frequencies
  through `ops/eva_attention.py rope`, query/key width ``nope + rope``
  against value width ``v``. `mla_attend` takes the projections as they
  are written (``kv_b``'s output whole, the rotary key once a position):
  on a TPU, at shapes its rule admits, it is one Pallas kernel a layer;
  in the last layer (``read``) and everywhere else plain XLA. The
  source's de-interleaving of rotary pairs is a relabelling of weight
  columns and is left out.
- **The expert layer** (`models/routed_experts.py` over
  `ops/moe_dispatch.py`) is told ``(first_expert,
  experts_held)``: it routes over all ``num_experts``, weighs over all the
  ``experts_per_token`` chosen, and adds its own experts' part and the
  shared expert; what absent experts would have added is left out. It
  counts the assignments each held expert got into the ``routing``
  collection (`parallel/bulk.py` sums them over a job).
- **Precision.** Parameters are stored in ``param_dtype`` (bfloat16 at the
  published size: at float32 this chip's share does not fit); products
  take ``dtype`` operands and accumulate in float32; residual stream,
  norms, softmax, router and head are float32.
- **Read-out**: the final RMSNorm at each record's last token, then
  ``head`` (hidden -> 1) in float32. The last layer computes keys and
  values at every position and everything else at the read positions.

Scopes for a device trace: ``mla_q``, ``mla_kv``, ``mla_attend``,
``mla_o``, ``router``, ``moe_dispatch``, ``experts``, ``moe_combine``,
``shared_expert``, beside ``embed``, ``ffn`` (the dense layers) and
``head``.
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
    experts_beside_a_shared_one,
    routing_counts,
    swiglu,
)
from mlops_tpu.ops.eva_attention import rope
from mlops_tpu.ops.mla import mla_attend, softmax_scale, yarn_inv_freq

ROUTE_EPS = 1e-20  # the router's normaliser (DeepSeek-V3)


class KimiBlock(nn.Module):
    """One decoder layer on the float32 residual stream ``[B, S, dim]``.
    With ``read`` (positions), the layer returns those positions only."""

    heads: int
    q_lora_rank: int
    kv_lora_rank: int
    qk_nope_head_dim: int
    qk_rope_head_dim: int
    v_head_dim: int
    ffn_dim: int  # the dense SwiGLU's width; 0: this is an expert layer
    moe_ffn_dim: int
    num_experts: int
    experts_per_token: int
    first_expert: int
    experts_held: int
    routed_scaling: float
    rope_theta: float
    rope_factor: float
    rope_original_positions: int
    dtype: jnp.dtype = jnp.bfloat16
    param_dtype: jnp.dtype = jnp.float32

    def _dense(self, features: int, name: str) -> nn.Dense:
        return nn.Dense(
            features, use_bias=False, dtype=self.dtype, param_dtype=self.param_dtype,
            name=name,
        )

    def _norm(self, name: str) -> RMSNorm:
        return RMSNorm(unit_offset=False, param_dtype=self.param_dtype, name=name)

    def _attention(self, x: jnp.ndarray, read: np.ndarray | None) -> jnp.ndarray:
        b, _, dim = x.shape
        nope, rot, wide = self.qk_nope_head_dim, self.qk_rope_head_dim, self.v_head_dim
        freqs = yarn_inv_freq(
            rot, self.rope_theta, self.rope_factor, self.rope_original_positions
        )
        h = self._norm("attn_norm")(x).astype(self.dtype)
        with jax.named_scope("mla_q"):
            asked = h if read is None else h[:, read]
            c_q = self._norm("q_norm")(self._dense(self.q_lora_rank, "q_a")(asked))
            q = self._dense(self.heads * (nope + rot), "q_b")(c_q.astype(self.dtype))
            q = q.reshape(b, -1, self.heads, nope + rot)
            q_nope, q_rot = q[..., :nope], rope(q[..., nope:], freqs, positions=read)
        with jax.named_scope("mla_kv"):
            latent = self._dense(self.kv_lora_rank + rot, "kv_a")(h)
            c_kv = self._norm("kv_norm")(latent[..., : self.kv_lora_rank])
            # one rotary key a position, every head's
            k_rot = rope(latent[..., None, self.kv_lora_rank :], freqs)[:, :, 0]
            # [B, S, H * (nope + wide)], a head's keys then its values: handed
            # on as written, `mla_attend`'s kernel reads its column blocks
            kv = self._dense(self.heads * (nope + wide), "kv_b")(c_kv.astype(self.dtype))
        scale = softmax_scale(nope + rot, self.rope_factor)
        mixed = mla_attend(q_nope, q_rot, kv, k_rot, scale, read=read)
        with jax.named_scope("mla_o"):
            return self._dense(dim, "o")(mixed)

    @nn.compact
    def __call__(self, x: jnp.ndarray, read: np.ndarray | None = None) -> jnp.ndarray:
        mixed = self._attention(x, read)
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
                self, h, scaling=self.routed_scaling, eps=ROUTE_EPS
            )
        return x + out.reshape(b, seq, dim)


class KimiK2Scorer(nn.Module):
    """``apply(vars, cat_ids, numeric, train) -> logits[f32 N]``: the zoo
    convention, one logit a record, read at the record's last token."""

    cards: Sequence[int]
    num_numeric: int
    hidden: int = 7168
    depth: int = 61
    heads: int = 64
    q_lora_rank: int = 1536
    kv_lora_rank: int = 512
    qk_nope_head_dim: int = 128
    qk_rope_head_dim: int = 64
    v_head_dim: int = 128
    ffn_dim: int = 18432
    moe_ffn_dim: int = 2048
    num_experts: int = 384
    experts_per_token: int = 8
    first_expert: int = 0
    experts_held: int = 384
    vocab_rows: int = 163840
    records_per_history: int = 64
    dense_layers: int = 1  # the source's first_k_dense_replace
    routed_scaling: float = 2.827
    rope_theta: float = 50000.0
    rope_factor: float = 64.0
    rope_original_positions: int = 4096
    num_bins: int = 32
    dtype: jnp.dtype = jnp.bfloat16
    param_dtype: jnp.dtype = jnp.float32

    # what `parallel/bulk.py` asks a model with sparse experts for: the
    # collection its layers count into, and ``routing_counts`` below
    routing_collection = ROUTING

    @property
    def layout(self) -> TokenLayout:
        return TokenLayout(tuple(self.cards), self.num_numeric, self.num_bins)

    @property
    def tokens_per_row(self) -> int:
        return self.layout.seq_len

    @nn.compact
    def __call__(
        self, cat_ids: jnp.ndarray, numeric: jnp.ndarray, *, train: bool = False
    ) -> jnp.ndarray:
        layout = self.layout
        stride = self.vocab_rows // layout.vocab_size
        if not stride:
            raise ValueError(
                f"{self.vocab_rows} embedding rows for {layout.vocab_size} tokens"
            )
        check_share(
            self.first_expert, self.experts_held, self.num_experts, self.experts_per_token
        )
        n = cat_ids.shape[0]
        tokens, read = tokenize_histories(
            cat_ids, numeric, layout, self.records_per_history
        )
        with jax.named_scope("embed"):
            # rows are looked up as stored and widened after: the residual
            # stream is float32, the table is never cast whole
            x = nn.Embed(
                self.vocab_rows, self.hidden, dtype=self.param_dtype,
                param_dtype=self.param_dtype, name="tok_embed",
            )(tokens * stride).astype(jnp.float32)
        for i in range(self.depth):
            x = KimiBlock(
                heads=self.heads,
                q_lora_rank=self.q_lora_rank,
                kv_lora_rank=self.kv_lora_rank,
                qk_nope_head_dim=self.qk_nope_head_dim,
                qk_rope_head_dim=self.qk_rope_head_dim,
                v_head_dim=self.v_head_dim,
                ffn_dim=self.ffn_dim if i < self.dense_layers else 0,
                moe_ffn_dim=self.moe_ffn_dim,
                num_experts=self.num_experts,
                experts_per_token=self.experts_per_token,
                first_expert=self.first_expert,
                experts_held=self.experts_held,
                routed_scaling=self.routed_scaling,
                rope_theta=self.rope_theta,
                rope_factor=self.rope_factor,
                rope_original_positions=self.rope_original_positions,
                dtype=self.dtype,
                param_dtype=self.param_dtype,
                name=f"block_{i}",
            )(x, read=read if i == self.depth - 1 else None)
        with jax.named_scope("head"):
            logits = nn.Dense(
                1, dtype=jnp.float32, param_dtype=self.param_dtype, name="head"
            )(RMSNorm(unit_offset=False, param_dtype=self.param_dtype, name="final_norm")(x))
        return logits.reshape(-1)[:n]

    routing_counts = staticmethod(routing_counts)
