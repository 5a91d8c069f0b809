"""A Falcon-H1-style hybrid decoder (``model_type: falcon_h1``, TII) as a
token-level history scorer: in EVERY layer a Mamba-2 state-space mixer and
causal grouped-query attention read the same normed input side by side
and are summed (every other hybrid of the zoo picks one mixer a layer by a
list), each path and each projection under a muP multiplier the
configuration states; then a dense SwiGLU; under the zoo's calling
convention and the read-out of `models/exaone_moe.py`.

- **Rows in, an answer a row out.** ``apply(vars, cat_ids[N, C],
  numeric[N, M], train) -> logits[N]``; every ``records_per_history``
  consecutive rows (from row 0) are ONE history, the last may be shorter:
  the history scorers' rule (`ModelConfig.history_rows`). Both mixers are
  causal and neither reaches across a history's start, so rows padded
  behind a record never change its answer.
- **Input.** A record is the 48 tokens `models/bert.py tokenize` gives,
  in-jit; token ``t`` of the layout's ``V`` reads row ``t * (vocab_rows //
  V)`` of the embedding, times ``embedding_multiplier``.
- **A layer**, pre-norm on the float32 residual stream (RMSNorm, eps
  1e-5, a plain weight; no bias but the convolution's): ``h =
  input_norm(x)``; ``x += ssm_out_multiplier * SSM(ssm_in_multiplier * h)
  + attention_out_multiplier * ATT(attention_in_multiplier * h)``; ``x +=
  MLP(ffn_norm(x))``, ``MLP(u) = mlp_multipliers[1] * down(silu(
  mlp_multipliers[0] * gate(u)) * up(u))``.
- **ATT** (`models/grouped_attention.py`, which `models/lfm2_moe.py` and
  `models/exaone_moe.py` call too): ``heads`` query heads over
  ``kv_heads`` key/value heads of ``head_dim``, NO head norm, the keys
  times ``key_multiplier``, rotate-half positions on queries and keys in
  every layer, every key so far at scale ``head_dim ** -0.5``.
- **SSM** (`models/mamba2.py mamba2_mixer`, which `models/nemotron_h.py`
  runs as a layer of its own): ``in_proj`` to ``z | x | B | C | dt``, its
  output times the muP vector (``ssm_multipliers`` on those five parts);
  the causal convolution over ``x | B | C``; ``dt = softplus(dt +
  dt_bias)``, ``A = -exp(A_log)``; `ops/ssd.py ssd_scan` with the skip ``D
  x``; the gate FIRST, then RMSNorm over each of the ``ssm_groups`` groups
  of channels (``mamba_rms_norm``, ``mamba_norm_before_gate: false``);
  ``out_proj``.
- **Precision.** Parameters are stored in ``param_dtype``; products take
  ``dtype`` operands and accumulate in float32; residual stream, norms,
  softmax, the convolution, ``dt``, the decays, the carried state, the
  gate and the head are float32. A multiplier is applied in float32.
- **Read-out**: the final RMSNorm at each record's last token, then
  ``head`` (hidden -> 1) in float32. The last layer computes what later
  positions need (keys and values; ``x``, ``B``, ``dt`` and the states) at
  every position and everything else (queries, the answers of the scan,
  ``z``, the gate and its norm, both output projections, the MLP) at the
  read positions. (``C`` is convolved beside ``x`` and ``B``, one
  projection and one convolution over the three; it is USED at the read
  positions.)

The mixer's three per-head leaves (``a_log/bias``, ``dt_bias/bias``,
``skip/scale``) are initialised as Mamba-2's reference code does
(`models/mamba2.py`).

Scopes for a device trace: ``ssm_in`` (the projection and the muP
vector), ``ssm_conv``, ``ssm_scan`` (``dt``, the decays, the scan),
``ssm_out`` (gate, grouped norm, ``out_proj``); ``gqa_qkv``,
``gqa_attend``, ``gqa_o`` (`models/lfm2_moe.py`'s names); beside
``embed``, ``ffn``, ``head`` and ``rope``. The family has no router: no
``routing`` collection.
"""

from __future__ import annotations

from typing import Sequence

import jax
import jax.numpy as jnp
import numpy as np
from flax import linen as nn

from mlops_tpu.models.bert import TokenLayout, tokenize_histories
from mlops_tpu.models.evabyte import RMS_EPS, RMSNorm  # noqa: F401  (RMS_EPS: the source's eps)
from mlops_tpu.models.grouped_attention import GQA_SCOPES, grouped_query_attention
# the gated norm under the name `benchmark/faults/falcon_h1.py` plants a fault on
from mlops_tpu.models.mamba2 import GatedGroupNorm as _GatedGroupNorm  # noqa: F401
from mlops_tpu.models.mamba2 import check_mixer, mamba2_mixer
from mlops_tpu.models.routed_experts import swiglu
from mlops_tpu.ops.ssd import ssd_scan

class FalconH1Block(nn.Module):
    """One decoder layer on the float32 residual stream ``[B, S, dim]``.
    With ``read`` (positions), the layer returns those positions only."""

    heads: int
    kv_heads: int
    head_dim: int
    ffn_dim: int
    ssm_dim: int
    ssm_heads: int
    ssm_state: int
    ssm_groups: int
    ssm_chunk: int
    conv_width: int
    rope_theta: float
    attention_in_multiplier: float
    attention_out_multiplier: float
    key_multiplier: float
    ssm_in_multiplier: float
    ssm_out_multiplier: float
    ssm_multipliers: Sequence[float]  # on z, x, B, C, dt
    mlp_multipliers: Sequence[float]  # on the gate, on the output
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
        return grouped_query_attention(
            self, h, read, head_dim=self.head_dim, scopes=GQA_SCOPES, turn=True,
            normed=False, key_scale=self.key_multiplier,
        )

    @nn.compact
    def __call__(self, x: jnp.ndarray, read: np.ndarray | None = None) -> jnp.ndarray:
        h = self._norm("input_norm")(x)  # float32: both mixers read this
        # ``ssd_scan`` as this module names it (`benchmark/faults/falcon_h1.py`)
        state_mixed = mamba2_mixer(
            self, h * self.ssm_in_multiplier, read, scan=ssd_scan,
            multipliers=self.ssm_multipliers,
        )
        attended = self._attention((h * self.attention_in_multiplier).astype(self.dtype), read)
        if read is not None:
            x = x[:, read]
        x = (
            x
            + self.ssm_out_multiplier * state_mixed.astype(jnp.float32)
            + self.attention_out_multiplier * attended.astype(jnp.float32)
        )
        b, seq, dim = x.shape
        u = self._norm("ffn_norm")(x).reshape(b * seq, dim)
        gate_scale, out_scale = self.mlp_multipliers
        with jax.named_scope("ffn"):
            out = swiglu(self, u.astype(self.dtype), self.ffn_dim, gate_scale=gate_scale)
        return x + out_scale * out.astype(jnp.float32).reshape(b, seq, dim)


class FalconH1Scorer(nn.Module):
    """``apply(vars, cat_ids, numeric, train) -> logits[f32 N]``: the zoo
    convention, one logit a record, read at the record's last token."""

    cards: Sequence[int]
    num_numeric: int
    hidden: int = 5120
    depth: int = 72
    heads: int = 20
    kv_heads: int = 4
    head_dim: int = 128
    ffn_dim: int = 21504
    ssm_dim: int = 4096
    ssm_heads: int = 32
    ssm_state: int = 256
    ssm_groups: int = 2
    ssm_chunk: int = 128
    conv_width: int = 4
    vocab_rows: int = 261120
    records_per_history: int = 64
    rope_theta: float = 1e11
    embedding_multiplier: float = 1.0
    attention_in_multiplier: float = 1.0
    attention_out_multiplier: float = 1.0
    key_multiplier: float = 1.0
    ssm_in_multiplier: float = 1.0
    ssm_out_multiplier: float = 1.0
    ssm_multipliers: Sequence[float] = (1.0, 1.0, 1.0, 1.0, 1.0)
    mlp_multipliers: Sequence[float] = (1.0, 1.0)
    num_bins: int = 32
    dtype: jnp.dtype = jnp.bfloat16
    param_dtype: jnp.dtype = jnp.float32

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
        if self.heads % self.kv_heads or self.head_dim % 2:
            raise ValueError(
                f"{self.heads} query heads over {self.kv_heads} key/value heads "
                f"of {self.head_dim}"
            )
        check_mixer(self.ssm_dim, self.ssm_heads, self.ssm_groups)
        if len(self.ssm_multipliers) != 5 or len(self.mlp_multipliers) != 2:
            raise ValueError(
                f"{len(self.ssm_multipliers)} ssm_multipliers (z, x, B, C, dt) and "
                f"{len(self.mlp_multipliers)} mlp_multipliers (gate, output)"
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
            )(tokens * stride).astype(jnp.float32) * self.embedding_multiplier
        for i in range(self.depth):
            x = FalconH1Block(
                heads=self.heads,
                kv_heads=self.kv_heads,
                head_dim=self.head_dim,
                ffn_dim=self.ffn_dim,
                ssm_dim=self.ssm_dim,
                ssm_heads=self.ssm_heads,
                ssm_state=self.ssm_state,
                ssm_groups=self.ssm_groups,
                ssm_chunk=self.ssm_chunk,
                conv_width=self.conv_width,
                rope_theta=self.rope_theta,
                attention_in_multiplier=self.attention_in_multiplier,
                attention_out_multiplier=self.attention_out_multiplier,
                key_multiplier=self.key_multiplier,
                ssm_in_multiplier=self.ssm_in_multiplier,
                ssm_out_multiplier=self.ssm_out_multiplier,
                ssm_multipliers=tuple(self.ssm_multipliers),
                mlp_multipliers=tuple(self.mlp_multipliers),
                dtype=self.dtype,
                param_dtype=self.param_dtype,
                name=f"block_{i}",
            )(x, read=read if i == self.depth - 1 else None)
        with jax.named_scope("head"):
            logits = nn.Dense(
                1, dtype=jnp.float32, param_dtype=self.param_dtype, name="head"
            )(RMSNorm(unit_offset=False, param_dtype=self.param_dtype, name="final_norm")(x))
        return logits.reshape(-1)[:n]
