"""A Nemotron-H-style hybrid decoder (``model_type: nemotron_h``, NVIDIA)
as a token-level history scorer: every layer is ONE kind, a pre-norm and
one of a Mamba-2 state-space mixer, a sparse expert layer or causal
grouped-query attention, which kind by the published
``hybrid_override_pattern`` (``M``, ``E``, ``*``, read into
``layer_types`` as "mamba", "moe", "attention"); under the zoo's calling
convention and the read-out of `models/exaone_moe.py`.

- **Rows in, an answer a row out.** ``apply(vars, cat_ids[N, C],
  numeric[N, M], train) -> logits[N]``; every ``records_per_history``
  consecutive rows (from row 0) are ONE history, the last may be shorter:
  the history scorers' rule (`ModelConfig.history_rows`). Every layer is
  causal and none reaches across a history's start, so rows padded behind
  a record never change its answer.
- **Input.** A record is the 48 tokens `models/bert.py tokenize` gives,
  in-jit; token ``t`` of the layout's ``V`` reads row ``t * (vocab_rows //
  V)`` of the embedding.
- **A layer**, on the float32 residual stream (the source's
  ``residual_in_fp32`` is false; the zoo keeps the stream in float32, a
  departure its configuration file states): ``x += mixer(norm(x))``,
  RMSNorm eps 1e-5 with a plain weight, and ``mixer`` the layer's kind:
- ``mamba``: `models/mamba2.py mamba2_mixer`, the mixer of
  `models/falcon_h1.py` with no multipliers (groups of ``ssm_heads //
  ssm_groups`` heads share B and C; the gated norm over each group).
- ``moe``: `models/routed_experts.py` over `ops/moe_dispatch.py`: a sigmoid
  router with a selection bias (it chooses, it never weighs),
  ``experts_per_token`` experts a token with no group limit, the chosen
  weights normalised (``+ 1e-20``) and times ``ROUTED_SCALING``; each
  expert ``down(relu(up h)^2)`` of ``moe_ffn_dim``, no gate
  (``gated=False``); told ``(first_expert, experts_held)`` and counting
  into the ``routing`` collection; beside ONE shared expert of the same
  form and ``shared_ffn_dim``, whole and unweighted.
- ``attention``: `models/grouped_attention.py`, ``heads`` query heads over
  ``kv_heads`` key/value heads of ``head_dim`` (a group of 16 in the
  source), no head norm, no bias, NO rotary positions (the Mamba layers
  carry position), every key so far at scale ``head_dim ** -0.5``.
- **Precision.** Parameters are stored in ``param_dtype``; products take
  ``dtype`` operands and accumulate in float32; residual stream, norms,
  softmax, router, the mixer's recurrence and the head are float32.
- **Read-out**: the final RMSNorm at each record's last token, then
  ``head`` (hidden -> 1) in float32. The last layer, of any kind, computes
  what later positions would need at every position and its answers at the
  read positions alone (attention: keys and values at every position; the
  mixer: as `models/mamba2.py` says; experts: the read positions only).

Scopes for a device trace: one a layer by its kind, ``mamba_mixer``,
``moe_layer``, ``attention_layer`` (`LAYER_SCOPES`); within them the
mixer's ``ssm_in``, ``ssm_conv``, ``ssm_scan``, ``ssm_out``, the experts'
``router``, ``moe_dispatch``, ``relu2_experts``, ``moe_combine``,
``shared_expert``, and the attention's ``gqa_qkv``, ``gqa_attend``,
``gqa_o``; beside ``embed`` and ``head``.
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
from mlops_tpu.models.mamba2 import check_mixer, mamba2_mixer
from mlops_tpu.models.routed_experts import (
    ROUTING,
    check_share,
    experts_beside_a_shared_one,
    routing_counts,
)
from mlops_tpu.ops.ssd import ssd_scan

# the published pattern's letters, as `layer_types` names them
PATTERN_KINDS = {"M": "mamba", "E": "moe", "*": "attention"}
LAYER_TYPES = tuple(PATTERN_KINDS.values())
# a layer's scope by its kind (`benchmark/layer_metrics/` reads them)
LAYER_SCOPES = {"mamba": "mamba_mixer", "moe": "moe_layer", "attention": "attention_layer"}
ROUTE_EPS = 1e-20  # the router's normaliser (DeepSeek-V3's, as `models/kimi_k2.py`)
ROUTED_SCALING = 2.5  # the source's routed_scaling_factor


def layer_types_of(pattern: str) -> tuple[str, ...]:
    """``hybrid_override_pattern`` read as `layer_types`."""
    unknown = set(pattern) - set(PATTERN_KINDS)
    if unknown:
        raise ValueError(f"{sorted(unknown)} in the layer pattern {pattern!r}")
    return tuple(PATTERN_KINDS[letter] for letter in pattern)


class NemotronHBlock(nn.Module):
    """One layer of one kind on the float32 residual stream ``[B, S,
    dim]``. With ``read`` (positions), the layer returns those positions
    only."""

    kind: str  # "mamba" | "moe" | "attention"
    heads: int
    kv_heads: int
    head_dim: int
    ssm_dim: int
    ssm_heads: int
    ssm_state: int
    ssm_groups: int
    ssm_chunk: int
    conv_width: int
    moe_ffn_dim: int
    shared_ffn_dim: int
    num_experts: int
    experts_per_token: int
    first_expert: int
    experts_held: int
    dtype: jnp.dtype = jnp.bfloat16
    param_dtype: jnp.dtype = jnp.float32

    def _dense(self, features: int, name: str) -> nn.Dense:
        return nn.Dense(
            features, use_bias=False, dtype=self.dtype, param_dtype=self.param_dtype,
            name=name,
        )

    def _mixed(self, h: jnp.ndarray, read: np.ndarray | None) -> jnp.ndarray:
        if self.kind == "mamba":
            # ``ssd_scan`` as this module names it (`benchmark/faults/nemotron_h.py`)
            return mamba2_mixer(self, h, read, scan=ssd_scan)
        if self.kind == "attention":
            return grouped_query_attention(
                self, h.astype(self.dtype), read, head_dim=self.head_dim,
                scopes=GQA_SCOPES, turn=False, normed=False,
            )
        if read is not None:
            h = h[:, read]
        b, seq, dim = h.shape
        out = experts_beside_a_shared_one(
            self, h.reshape(b * seq, dim), scaling=ROUTED_SCALING, eps=ROUTE_EPS,
            gated=False, shared_width=self.shared_ffn_dim,
        )
        return out.reshape(b, seq, dim)

    @nn.compact
    def __call__(self, x: jnp.ndarray, read: np.ndarray | None = None) -> jnp.ndarray:
        h = RMSNorm(unit_offset=False, param_dtype=self.param_dtype, name="norm")(x)
        with jax.named_scope(LAYER_SCOPES[self.kind]):
            mixed = self._mixed(h, read)
        if read is not None:
            x = x[:, read]
        return x + mixed.astype(jnp.float32)


class NemotronHScorer(nn.Module):
    """``apply(vars, cat_ids, numeric, train) -> logits[f32 N]``: the zoo
    convention, one logit a record, read at the record's last token."""

    cards: Sequence[int]
    num_numeric: int
    layer_types: Sequence[str]  # at least ``depth`` entries; layer i takes the i-th
    hidden: int = 2688
    depth: int = 52
    heads: int = 32
    kv_heads: int = 2
    head_dim: int = 128
    ssm_dim: int = 4096
    ssm_heads: int = 64
    ssm_state: int = 128
    ssm_groups: int = 8
    ssm_chunk: int = 128
    conv_width: int = 4
    moe_ffn_dim: int = 1856
    shared_ffn_dim: int = 3712
    num_experts: int = 128
    experts_per_token: int = 6
    first_expert: int = 0
    experts_held: int = 128
    vocab_rows: int = 131072
    records_per_history: int = 64
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
        listed = tuple(self.layer_types[: self.depth])
        if len(listed) < self.depth or set(listed) - set(LAYER_TYPES):
            raise ValueError(
                f"layer_types names {len(listed)} of {self.depth} layers, "
                f"each one of {LAYER_TYPES}: {listed}"
            )
        if "moe" not in listed:
            raise ValueError(f"no expert layer among {listed}: nothing to count")
        check_share(
            self.first_expert, self.experts_held, self.num_experts, self.experts_per_token
        )
        check_mixer(self.ssm_dim, self.ssm_heads, self.ssm_groups)
        if self.heads % self.kv_heads:
            raise ValueError(
                f"{self.heads} query heads over {self.kv_heads} key/value heads "
                f"of {self.head_dim}"
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
            x = NemotronHBlock(
                kind=self.layer_types[i],
                heads=self.heads,
                kv_heads=self.kv_heads,
                head_dim=self.head_dim,
                ssm_dim=self.ssm_dim,
                ssm_heads=self.ssm_heads,
                ssm_state=self.ssm_state,
                ssm_groups=self.ssm_groups,
                ssm_chunk=self.ssm_chunk,
                conv_width=self.conv_width,
                moe_ffn_dim=self.moe_ffn_dim,
                shared_ffn_dim=self.shared_ffn_dim,
                num_experts=self.num_experts,
                experts_per_token=self.experts_per_token,
                first_expert=self.first_expert,
                experts_held=self.experts_held,
                dtype=self.dtype,
                param_dtype=self.param_dtype,
                name=f"block_{i}",
            )(x, read=read if i == self.depth - 1 else None)
        with jax.named_scope("head"):
            logits = nn.Dense(
                1, dtype=jnp.float32, param_dtype=self.param_dtype, name="head"
            )(RMSNorm(unit_offset=False, param_dtype=self.param_dtype, name="final_norm")(x))
        return logits.reshape(-1)[:n]
