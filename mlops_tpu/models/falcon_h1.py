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
- **SSM** (Mamba-2): ``in_proj`` (hidden -> ``z`` ``ssm_dim`` | ``x``
  ``ssm_dim`` | ``B`` and ``C`` ``ssm_groups * ssm_state`` each | ``dt``
  ``ssm_heads``), its output times the muP vector (``ssm_multipliers`` on
  the columns of ``z``, ``x``, ``B``, ``C``, ``dt``); ``x | B | C``
  through `ops/short_conv.py causal_conv` (depthwise, ``conv_width`` taps,
  a bias, SiLU); ``dt = softplus(dt + dt_bias)``, ``A = -exp(A_log)``, one
  a head; `ops/ssd.py ssd_scan` with the skip ``D x``; the gate FIRST (``y
  * silu(z)``), then RMSNorm over each of the ``ssm_groups`` groups of
  channels, one weight a channel (``mamba_rms_norm``,
  ``mamba_norm_before_gate: false``); ``out_proj`` (``ssm_dim`` ->
  hidden).
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

The three per-head leaves are initialised as Mamba-2's reference code
does, so that a model `init`-ed here carries state across many chunks:
``A_log = log(1..heads)``, ``dt_bias`` the inverse softplus of a ``dt``
drawn log-uniformly from [0.001, 0.1], ``D = 1``. Their leaves are
``a_log/bias``, ``dt_bias/bias`` and ``skip/scale``.

Scopes for a device trace: ``ssm_in`` (the projection and the muP
vector), ``ssm_conv``, ``ssm_scan`` (``dt``, the decays, the scan),
``ssm_out`` (gate, grouped norm, ``out_proj``); ``gqa_qkv``,
``gqa_attend``, ``gqa_o`` (`models/lfm2_moe.py`'s names); beside
``embed``, ``ffn``, ``head`` and ``rope``. The family has no router: no
``routing`` collection.
"""

from __future__ import annotations

import math
from typing import Callable, Sequence

import jax
import jax.numpy as jnp
import numpy as np
from flax import linen as nn

from mlops_tpu.models.bert import TokenLayout, tokenize_histories
from mlops_tpu.models.evabyte import RMS_EPS, RMSNorm
from mlops_tpu.models.grouped_attention import GQA_SCOPES, grouped_query_attention
from mlops_tpu.models.routed_experts import swiglu
from mlops_tpu.ops.short_conv import causal_conv
from mlops_tpu.ops.ssd import ssd_scan

DT_RANGE = (0.001, 0.1)  # Mamba-2's dt_min, dt_max


def _dt_bias_init(key, shape, dtype):
    """The inverse softplus of ``dt`` log-uniform in `DT_RANGE`."""
    low, high = (math.log(v) for v in DT_RANGE)
    dt = jnp.exp(jax.random.uniform(key, shape, jnp.float32, low, high))
    return (dt + jnp.log(-jnp.expm1(-dt))).astype(dtype)


def _a_log_init(key, shape, dtype):
    del key
    return jnp.log(jnp.arange(1, shape[0] + 1, dtype=jnp.float32)).astype(dtype)


class _PerHead(nn.Module):
    """One number a head, float32 where it is used."""

    leaf: str
    fill: Callable  # the leaf's initialiser
    param_dtype: jnp.dtype

    @nn.compact
    def __call__(self, heads: int) -> jnp.ndarray:
        return self.param(self.leaf, self.fill, (heads,), self.param_dtype).astype(jnp.float32)


class _Columns(nn.Module):
    """A projection without a bias whose output columns may be asked for in
    part (``kernel`` ``[in, features]``, an `nn.Dense`'s leaf)."""

    features: int
    dtype: jnp.dtype
    param_dtype: jnp.dtype

    @nn.compact
    def __call__(self, h: jnp.ndarray, start: int = 0, stop: int | None = None) -> jnp.ndarray:
        kernel = self.param(
            "kernel", nn.initializers.lecun_normal(), (h.shape[-1], self.features),
            self.param_dtype,
        )
        return jnp.dot(h.astype(self.dtype), kernel[:, start:stop].astype(self.dtype))


class _ConvTaps(nn.Module):
    """The convolution's filters ``kernel`` ``[width, channels]`` (one a
    channel: depthwise) and its ``bias`` ``[channels]``."""

    width: int
    param_dtype: jnp.dtype

    @nn.compact
    def __call__(self, channels: int) -> tuple[jnp.ndarray, jnp.ndarray]:
        taps = nn.initializers.lecun_normal(in_axis=0, out_axis=1)
        return (
            self.param("kernel", taps, (self.width, channels), self.param_dtype),
            self.param("bias", nn.initializers.normal(0.1), (channels,), self.param_dtype),
        )


class _GatedGroupNorm(nn.Module):
    """The source's ``FalconH1RMSNormGated`` with ``norm_before_gate``
    false: ``y * silu(z)`` FIRST, then RMSNorm over each of ``groups`` equal
    groups of the last axis's channels, one weight a channel (``scale``);
    float32."""

    groups: int
    param_dtype: jnp.dtype

    @nn.compact
    def __call__(self, y: jnp.ndarray, z: jnp.ndarray) -> jnp.ndarray:
        g = self.param("scale", nn.initializers.ones_init(), (y.shape[-1],), self.param_dtype)
        gated = (y * nn.silu(z)).reshape(*y.shape[:-1], self.groups, -1)
        rms = jax.lax.rsqrt(jnp.mean(gated * gated, axis=-1, keepdims=True) + RMS_EPS)
        return (gated * rms).reshape(y.shape) * g.astype(jnp.float32)


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

    def _per_head(self, name: str, leaf: str, fill: Callable) -> jnp.ndarray:
        return _PerHead(leaf, fill, self.param_dtype, name=name)(self.ssm_heads)

    def _ssm(self, h: jnp.ndarray, read: np.ndarray | None) -> jnp.ndarray:
        """``h`` ``[B, S, dim]`` (float32, times its multiplier) -> ``[B, S,
        dim]``, or ``[B, len(read), dim]``."""
        b, seq, dim = h.shape
        inner, heads = self.ssm_dim, self.ssm_heads
        shared = self.ssm_groups * self.ssm_state  # B's columns, and C's
        parts = (inner, inner, shared, shared, heads)  # z | x | B | C | dt
        mup = np.repeat(np.asarray(self.ssm_multipliers, np.float32), parts)
        with jax.named_scope("ssm_in"):
            project = _Columns(sum(parts), self.dtype, self.param_dtype, name="in_proj")
            if read is None:
                z, xbc, dt = jnp.split(
                    project(h).astype(jnp.float32) * mup, [inner, 2 * inner + 2 * shared], axis=-1
                )
            else:  # the gate at the read positions, the rest at every one
                z = project(h[:, read], 0, inner).astype(jnp.float32) * mup[:inner]
                xbc, dt = jnp.split(
                    project(h, inner).astype(jnp.float32) * mup[inner:], [inner + 2 * shared],
                    axis=-1,
                )
        with jax.named_scope("ssm_conv"):
            taps = _ConvTaps(self.conv_width, self.param_dtype, name="conv")
            x, b_in, c_out = jnp.split(
                causal_conv(xbc, *taps(inner + 2 * shared)), [inner, inner + shared], axis=-1
            )
        with jax.named_scope("ssm_scan"):
            dt = jax.nn.softplus(dt + self._per_head("dt_bias", "bias", _dt_bias_init))
            a = -jnp.exp(self._per_head("a_log", "bias", _a_log_init))
            skip = self._per_head("skip", "scale", nn.initializers.ones_init())
            c_out = c_out.reshape(b, seq, self.ssm_groups, self.ssm_state)
            y = ssd_scan(
                x.reshape(b, seq, heads, inner // heads), dt, a,
                b_in.reshape(b, seq, self.ssm_groups, self.ssm_state),
                c_out if read is None else c_out[:, read], skip,
                chunk=self.ssm_chunk, read=read, dtype=self.dtype,
            )
        with jax.named_scope("ssm_out"):
            normed = _GatedGroupNorm(self.ssm_groups, self.param_dtype, name="ssm_norm")(
                y.reshape(b, -1, inner), z
            )
            return self._dense(dim, "out_proj")(normed.astype(self.dtype))

    def _attention(self, h: jnp.ndarray, read: np.ndarray | None) -> jnp.ndarray:
        return grouped_query_attention(
            self, h, read, head_dim=self.head_dim, scopes=GQA_SCOPES, turn=True,
            normed=False, key_scale=self.key_multiplier,
        )

    @nn.compact
    def __call__(self, x: jnp.ndarray, read: np.ndarray | None = None) -> jnp.ndarray:
        h = self._norm("input_norm")(x)  # float32: both mixers read this
        state_mixed = self._ssm(h * self.ssm_in_multiplier, read)
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
        if self.ssm_dim % self.ssm_heads or self.ssm_heads % self.ssm_groups:
            raise ValueError(
                f"a state-space mixer of {self.ssm_dim} in {self.ssm_heads} heads "
                f"over {self.ssm_groups} groups"
            )
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
