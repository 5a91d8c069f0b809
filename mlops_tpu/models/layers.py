"""Shared Flax layers: self-attention over the framework's kernel dispatcher.

``MultiHeadSelfAttention`` replaces ``nn.MultiHeadDotProductAttention`` so
every transformer in the zoo (FT-Transformer, BERT, MoE) runs one module:
dense XLA attention at short sequence and wherever a padding mask or
attention-weight dropout needs the materialized scores
(``ops.attention.dense_attention``), the Pallas flash kernel at BERT-length
sequence, the sequence-parallel ring where one is injected.
"""

from __future__ import annotations

import math
from typing import Callable

import jax.numpy as jnp
from flax import linen as nn
from flax.linen.dtypes import promote_dtype

from mlops_tpu.ops.attention import (
    attend,
    dense_attention,
    reference_attention,
    wants_flash,
)


class FlatDenseGeneral(nn.Module):
    """An ``nn.DenseGeneral``'s parameters (``kernel`` ``[*inputs,
    *features]`` and ``bias`` ``[*features]``: its names, shapes, dtypes
    and initial values) applied as ONE 2-D matmul, ``[rows, prod(inputs)]
    -> [rows, prod(features)]``, the kernel reshaped at use.

    Why not ``nn.DenseGeneral``: its product comes out ``[rows,
    *features]``, and for the qkv projection's ``[rows, 3, heads,
    head_dim]`` the TPU compiler assigns a layout with the ROW axis minor
    and then relayouts it physically for the attention products (a v5e
    trace: 3.5 s of a 12.6 s bulk job in that one ``reshape``, PERF.md
    section 6, PR 26). A 2-D product stays row-major with the features on
    the lanes. It is also the one clean GEMM, forward and backward, that
    XLA:CPU maps to its fast path."""

    inputs: tuple[int, ...]
    features: tuple[int, ...]
    dtype: jnp.dtype = jnp.bfloat16
    use_bias: bool = True  # False: no ``bias`` parameter (models/evabyte.py)

    @nn.compact
    def __call__(self, x: jnp.ndarray) -> jnp.ndarray:
        flat = (math.prod(self.inputs), math.prod(self.features))

        def kernel_init(rng, shape, dtype=jnp.float32):
            # DenseGeneral's: drawn for the flat shape, then shaped
            return nn.linear.default_kernel_init(rng, flat, dtype).reshape(shape)

        kernel = self.param("kernel", kernel_init, self.inputs + self.features)
        bias = (
            self.param("bias", nn.initializers.zeros_init(), self.features)
            if self.use_bias
            else None
        )
        x, kernel, bias = promote_dtype(x, kernel, bias, dtype=self.dtype)
        out = x @ kernel.reshape(flat)
        return out if bias is None else out + bias.reshape(flat[1])


class MultiHeadSelfAttention(nn.Module):
    heads: int
    dtype: jnp.dtype = jnp.bfloat16
    dropout: float = 0.0
    use_flash: bool | None = None  # None = dispatch on sequence length
    attend_fn: Callable | None = None  # override the kernel dispatcher —
    # the sequence-parallel path injects `parallel.make_ring_attention`'s
    # shard_map'd ring here so the SAME module runs dense on one chip and
    # ring-sharded over a ('data','seq') mesh. Incompatible with padding
    # masks and attention-weight dropout (both need the materialized score
    # matrix); those combinations raise rather than silently fall back.

    @nn.compact
    def __call__(
        self,
        x: jnp.ndarray,
        *,
        deterministic: bool = True,
        mask: jnp.ndarray | None = None,
    ) -> jnp.ndarray:
        n, s, dim = x.shape
        if dim % self.heads:
            raise ValueError(f"dim {dim} not divisible by heads {self.heads}")
        head_dim = dim // self.heads

        # One layout from qkv to out: [N*S, features] row-major, features on
        # the lanes. The dense path reads its heads out of it in place;
        # only the kernels that fold heads themselves (flash, the ring) are
        # handed [N, S, H, D], formed in their own branch.
        qkv = FlatDenseGeneral(
            (dim,), (3, self.heads, head_dim), dtype=self.dtype, name="qkv"
        )(x.reshape(n * s, dim))

        def heads_apart():  # [N, S, H, D] each, for a kernel that folds heads
            t = qkv.reshape(n, s, 3, self.heads, head_dim)
            return t[:, :, 0], t[:, :, 1], t[:, :, 2]

        needs_weight_dropout = self.dropout > 0.0 and not deterministic
        needs_scores = mask is not None or needs_weight_dropout
        if self.attend_fn is not None:
            if needs_scores:
                raise ValueError(
                    "attend_fn (ring attention) cannot combine with padding "
                    "masks or attention-weight dropout — both require the "
                    "materialized score matrix; train with dropout=0.0 on "
                    "the sequence-parallel path"
                )
            out = self.attend_fn(*heads_apart())
        elif needs_scores or not wants_flash(s, self.use_flash):
            out = dense_attention(
                qkv.reshape(n, s, 3 * dim),
                self.heads,
                mask=mask,  # [N, S] True = attend
                dropout_rate=self.dropout,
                dropout_rng=self.make_rng("dropout")
                if needs_weight_dropout
                else None,
            )
        else:
            out = attend(*heads_apart(), use_flash=self.use_flash)

        return FlatDenseGeneral(
            (self.heads, head_dim), (dim,), dtype=self.dtype, name="out"
        )(out.reshape(n * s, dim)).reshape(n, s, dim)


__all__ = [
    "FlatDenseGeneral",
    "MultiHeadSelfAttention",
    "attend",
    "reference_attention",
]
