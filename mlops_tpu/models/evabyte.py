"""EvaByte as a byte-level history scorer: the published decoder (EVA
chunked linear attention, RMSNorm with unit offset, RoPE, SwiGLU, no
biases, float32 residual stream) under the zoo's calling convention and
the repo's own read-out.

A byte model needs no tokenizer: a record is read as the text line it is,
an account's history as the concatenation of its lines.

- **Rows in, an answer a row out.** ``apply(vars, cat_ids[N, C],
  numeric[N, M], train) -> logits[N]`` like every family; every
  ``records_per_history`` consecutive rows (from row 0) are ONE history,
  the last one may be shorter. The model is causal, so record r's answer
  is conditioned on records 1..r of its history and nothing after it:
  rows padded behind a record can never change that record's answer.
  Whoever cuts rows into calls keeps histories whole
  (`parallel/bulk.py mesh_chunk_rows`, `ModelConfig.history_rows`).
- **Rendering is part of the jitted forward pass** (``render_bytes``), as
  BERT's tokenizer is: integer arithmetic, no strings. A record is
  ``RECORD_BYTES`` = 256 bytes, fixed width: 23 fields of 11 bytes (a
  6-byte name, ``=``, a 3-byte value, ``,``) and the 3-byte record end.
  A categorical value is its id as three decimal digits; a numeric one
  the sign and two digits of ``clip(round(10 x), -99, 99)``. Byte ``b``
  is token ``BYTE_OFFSET + b`` of the 320-token vocabulary.
- **Read-out**: the final RMSNorm at each record's LAST byte, then
  ``head`` (hidden -> 1). The published 320-way byte head and its
  multi-byte prediction heads are not on the scoring path.

The last block computes keys, values and summaries at every position, and
everything after the attention mix at the read positions only: nothing
else of that layer reaches an answer.
"""

from __future__ import annotations

from typing import Sequence

import jax
import jax.numpy as jnp
import numpy as np
from flax import linen as nn

from mlops_tpu.models.layers import FlatDenseGeneral
from mlops_tpu.ops.eva_attention import eva_attend, eva_prep_kv, rope

VOCAB_SIZE = 320  # 64 reserved ids, then the 256 byte values
BYTE_OFFSET = 64
RMS_EPS = 1e-5

# The 6-byte names of the schema's 23 features, in the schema's order.
FIELD_NAMES = (
    "sex___", "educat", "marrge",
    "repay1", "repay2", "repay3", "repay4", "repay5", "repay6",
    "climit", "age___",
    "bill_1", "bill_2", "bill_3", "bill_4", "bill_5", "bill_6",
    "paym_1", "paym_2", "paym_3", "paym_4", "paym_5", "paym_6",
)
RECORD_END = ";\r\n"
FIELD_BYTES = 11  # name (6) '=' value (3) ','
RECORD_BYTES = FIELD_BYTES * len(FIELD_NAMES) + len(RECORD_END)  # 256


def render_bytes(cat_ids: jnp.ndarray, numeric: jnp.ndarray) -> jnp.ndarray:
    """Records as text: (int32[N, C], f32[N, M]) -> int32[N, 256] byte
    values, e.g. ``sex___=001,educat=003,...,paym_6=-07,;\\r\\n``."""
    n, c = cat_ids.shape
    m = numeric.shape[1]
    if c + m != len(FIELD_NAMES):
        raise ValueError(f"{c} + {m} features, {len(FIELD_NAMES)} field names")
    zero = ord("0")
    ids = cat_ids.astype(jnp.int32)
    cat_value = jnp.stack(
        [zero + ids // 100 % 10, zero + ids // 10 % 10, zero + ids % 10], axis=-1
    )
    tenths = jnp.clip(jnp.round(10.0 * numeric), -99.0, 99.0).astype(jnp.int32)
    size = jnp.abs(tenths)
    num_value = jnp.stack(
        [jnp.where(tenths < 0, ord("-"), ord("+")), zero + size // 10, zero + size % 10],
        axis=-1,
    )
    values = jnp.concatenate([cat_value, num_value], axis=1)  # [N, F, 3]
    names = np.frombuffer("".join(FIELD_NAMES).encode(), np.uint8).reshape(-1, 6)
    fields = jnp.concatenate(
        [
            jnp.broadcast_to(jnp.asarray(names, jnp.int32), (n, *names.shape)),
            jnp.full((n, c + m, 1), ord("="), jnp.int32),
            values,
            jnp.full((n, c + m, 1), ord(","), jnp.int32),
        ],
        axis=-1,
    )  # [N, F, 11]
    end = np.frombuffer(RECORD_END.encode(), np.uint8).astype(np.int32)
    return jnp.concatenate(
        [fields.reshape(n, -1), jnp.broadcast_to(jnp.asarray(end), (n, end.size))],
        axis=1,
    )


class RMSNorm(nn.Module):
    """``x / rms(x) * (1 + g)`` in float32 (the source's
    ``norm_add_unit_offset``); ``g`` is the parameter ``scale``. Without
    ``unit_offset`` the weight is ``g`` itself, initialised to one
    (`models/kimi_k2.py`)."""

    unit_offset: bool = True
    param_dtype: jnp.dtype = jnp.float32

    @nn.compact
    def __call__(self, x: jnp.ndarray) -> jnp.ndarray:
        init = nn.initializers.zeros_init() if self.unit_offset else nn.initializers.ones_init()
        g = self.param("scale", init, (x.shape[-1],), self.param_dtype)
        g = g.astype(jnp.float32)
        x = x.astype(jnp.float32)
        rms = jax.lax.rsqrt(jnp.mean(x * x, axis=-1, keepdims=True) + RMS_EPS)
        return x * rms * (1.0 + g if self.unit_offset else g)


class _HeadVectors(nn.Module):
    """One learned vector a head (``adaptive_phi``, ``adaptive_mu_k``)."""

    heads: int
    head_dim: int

    @nn.compact
    def __call__(self) -> jnp.ndarray:
        return self.param(
            "bias", nn.initializers.normal(0.02), (self.heads, self.head_dim)
        )


class EvaBlock(nn.Module):
    """One decoder layer on the float32 residual stream ``[B, S, dim]``.
    With ``read`` (positions), the layer returns those positions only."""

    heads: int
    ffn_dim: int
    window: int
    chunk: int
    rope_theta: float
    dtype: jnp.dtype = jnp.bfloat16

    @nn.compact
    def __call__(self, x: jnp.ndarray, read: np.ndarray | None = None) -> jnp.ndarray:
        b, seq, dim = x.shape
        head_dim = dim // self.heads
        h = RMSNorm(name="attn_norm")(x).astype(self.dtype)
        qkv = FlatDenseGeneral(
            (dim,), (3, self.heads, head_dim), dtype=self.dtype, use_bias=False,
            name="qkv",
        )(h.reshape(b * seq, dim)).reshape(b, seq, 3, self.heads, head_dim)
        q = rope(qkv[:, :, 0], self.rope_theta)
        k = rope(qkv[:, :, 1], self.rope_theta)
        v = qkv[:, :, 2]
        k_sum, v_sum = eva_prep_kv(
            k,
            v,
            _HeadVectors(self.heads, head_dim, name="adaptive_phi")(),
            _HeadVectors(self.heads, head_dim, name="adaptive_mu_k")(),
            self.chunk,
        )
        mixed = eva_attend(q, k, v, k_sum, v_sum, self.window, self.chunk)
        mixed = mixed.reshape(b, seq, dim)
        if read is not None:
            x, mixed, seq = x[:, read], mixed[:, read], len(read)
        x = x + FlatDenseGeneral(
            (self.heads, head_dim), (dim,), dtype=self.dtype, use_bias=False,
            name="out",
        )(mixed.reshape(b * seq, dim)).reshape(b, seq, dim)

        with jax.named_scope("ffn"):
            h = RMSNorm(name="ffn_norm")(x).astype(self.dtype).reshape(b * seq, dim)
            gate = nn.Dense(self.ffn_dim, use_bias=False, dtype=self.dtype, name="gate")(h)
            up = nn.Dense(self.ffn_dim, use_bias=False, dtype=self.dtype, name="up")(h)
            gated = nn.silu(gate.astype(jnp.float32)) * up.astype(jnp.float32)
            down = nn.Dense(dim, use_bias=False, dtype=self.dtype, name="down")(
                gated.astype(self.dtype)
            )
            return x + down.reshape(b, seq, dim)


class EvaByteScorer(nn.Module):
    """``apply(vars, cat_ids, numeric, train) -> logits[f32 N]``: the zoo
    convention, one logit a record, read at the record's last byte."""

    cards: Sequence[int]
    num_numeric: int
    hidden: int = 4096
    depth: int = 32
    heads: int = 32
    ffn_dim: int = 11008
    window: int = 2048
    chunk: int = 16
    rope_theta: float = 100000.0
    records_per_history: int = 64
    dtype: jnp.dtype = jnp.bfloat16

    bytes_per_row = RECORD_BYTES  # `parallel/bulk.py` counts a job's text by it

    @nn.compact
    def __call__(
        self, cat_ids: jnp.ndarray, numeric: jnp.ndarray, *, train: bool = False
    ) -> jnp.ndarray:
        if self.hidden % self.heads or RECORD_BYTES % self.chunk or self.window % self.chunk:
            raise ValueError(
                f"hidden {self.hidden} / heads {self.heads}, chunk {self.chunk} "
                f"into a record's {RECORD_BYTES} bytes and into window {self.window}"
            )
        n = cat_ids.shape[0]
        # whole histories; fewer rows than one history are one shorter history
        records = min(self.records_per_history, n)
        histories = -(-n // records)
        pad = histories * records - n
        with jax.named_scope("embed"):
            tokens = BYTE_OFFSET + render_bytes(
                jnp.pad(cat_ids, ((0, pad), (0, 0))), jnp.pad(numeric, ((0, pad), (0, 0)))
            ).reshape(histories, records * RECORD_BYTES)
            x = nn.Embed(VOCAB_SIZE, self.hidden, dtype=jnp.float32, name="tok_embed")(
                tokens
            )  # the residual stream stays float32 (the source's fp32_skip_add)
        read = RECORD_BYTES * np.arange(1, records + 1) - 1  # each record's last byte
        for i in range(self.depth):
            x = EvaBlock(
                heads=self.heads,
                ffn_dim=self.ffn_dim,
                window=self.window,
                chunk=self.chunk,
                rope_theta=self.rope_theta,
                dtype=self.dtype,
                name=f"block_{i}",
            )(x, read=read if i == self.depth - 1 else None)
        with jax.named_scope("head"):
            # 64 positions a history: float32 costs nothing here
            logits = nn.Dense(1, dtype=jnp.float32, name="head")(
                RMSNorm(name="final_norm")(x)
            )
        return logits.reshape(histories * records)[:n]
