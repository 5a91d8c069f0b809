"""The Mamba-2 state-space mixer (Dao and Gu, arXiv:2405.21060) as the
token-level decoders build it: `models/falcon_h1.py` runs it beside its
attention in every layer, under the muP multipliers its configuration
states; `models/nemotron_h.py` runs it as a layer of its own, with none.

``mamba2_mixer(block, h, read)`` is called inside a block's compact
``__call__``, so the parameters are the BLOCK's own leaves (``in_proj``,
``conv``, ``dt_bias``, ``a_log``, ``skip``, ``ssm_norm``, ``out_proj``)
and the sizes the block's fields (``ssm_dim``, ``ssm_heads``,
``ssm_state``, ``ssm_groups``, ``ssm_chunk``, ``conv_width``, ``dtype``,
``param_dtype``):

- ``in_proj`` (hidden -> ``z`` ``ssm_dim`` | ``x`` ``ssm_dim`` | ``B`` and
  ``C`` ``ssm_groups * ssm_state`` each | ``dt`` ``ssm_heads``), no bias,
  its output times the muP vector (``multipliers`` on the columns of
  ``z``, ``x``, ``B``, ``C``, ``dt``; all 1, the default: no multiply);
- ``x | B | C`` through `ops/short_conv.py causal_conv` (depthwise,
  ``conv_width`` taps, a bias, SiLU);
- ``dt = softplus(dt + dt_bias)`` with no clamp, ``A = -exp(A_log)``, one
  a head; `ops/ssd.py ssd_scan` (or the ``scan`` the caller passes) with
  the skip ``D x``;
- the gate FIRST (``y * silu(z)``), then RMSNorm over each of the
  ``ssm_groups`` groups of channels, one weight a channel
  (``norm_before_gate`` false in both sources);
- ``out_proj`` (``ssm_dim`` -> hidden), no bias.

Precision: products take ``dtype`` operands and accumulate in float32;
the convolution, ``dt``, the decays, the carried state, the gate and the
grouped norm are float32; a multiplier is applied in float32.

With ``read`` (positions; a model's last layer) the state's inputs (``x``,
``B``, ``dt``, the convolution) are computed at every position and the
rest (the scan's answers, ``z``, the gate and its norm, ``out_proj``) at
the read positions. ``C`` is convolved beside ``x`` and ``B``, one
projection and one convolution over the three, and USED at the read
positions.

The three per-head leaves are initialised as Mamba-2's reference code
does, so that a model `init`-ed here carries state across many chunks:
``A_log = log(1..heads)``, ``dt_bias`` the inverse softplus of a ``dt``
drawn log-uniformly from [0.001, 0.1], ``D = 1``. Their leaves are
``a_log/bias``, ``dt_bias/bias`` and ``skip/scale``.

Scopes for a device trace (`SSM_SCOPES`): ``ssm_in`` (the projection and
the muP vector), ``ssm_conv``, ``ssm_scan`` (``dt``, the decays, the
scan), ``ssm_out`` (gate, grouped norm, ``out_proj``).
"""

from __future__ import annotations

import math
from typing import Callable, Sequence

import jax
import jax.numpy as jnp
import numpy as np
from flax import linen as nn

from mlops_tpu.models.evabyte import RMS_EPS
from mlops_tpu.ops.short_conv import causal_conv
from mlops_tpu.ops.ssd import ssd_scan

DT_RANGE = (0.001, 0.1)  # Mamba-2's dt_min, dt_max
# what `benchmark/layer_metrics/` reads as the mixer's own time
SSM_SCOPES = ("ssm_in", "ssm_conv", "ssm_scan", "ssm_out")
NO_MULTIPLIERS = (1.0, 1.0, 1.0, 1.0, 1.0)  # on z, x, B, C, dt


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


class GatedGroupNorm(nn.Module):
    """The sources' gated RMSNorm with ``norm_before_gate`` false: ``y *
    silu(z)`` FIRST, then RMSNorm over each of ``groups`` equal groups of
    the last axis's channels, one weight a channel (``scale``); float32."""

    groups: int
    param_dtype: jnp.dtype

    @nn.compact
    def __call__(self, y: jnp.ndarray, z: jnp.ndarray) -> jnp.ndarray:
        g = self.param("scale", nn.initializers.ones_init(), (y.shape[-1],), self.param_dtype)
        gated = (y * nn.silu(z)).reshape(*y.shape[:-1], self.groups, -1)
        rms = jax.lax.rsqrt(jnp.mean(gated * gated, axis=-1, keepdims=True) + RMS_EPS)
        return (gated * rms).reshape(y.shape) * g.astype(jnp.float32)


def check_mixer(ssm_dim: int, ssm_heads: int, ssm_groups: int) -> None:
    """The mixer's heads divide its width, and its groups its heads."""
    if ssm_dim % ssm_heads or ssm_heads % ssm_groups:
        raise ValueError(
            f"a state-space mixer of {ssm_dim} in {ssm_heads} heads "
            f"over {ssm_groups} groups"
        )


def mamba2_mixer(
    block: nn.Module,
    h: jnp.ndarray,
    read: np.ndarray | None,
    *,
    scan: Callable = ssd_scan,
    multipliers: Sequence[float] = NO_MULTIPLIERS,
) -> jnp.ndarray:
    """``h`` ``[B, S, dim]`` (float32, normed) -> ``[B, S, dim]``, or ``[B,
    len(read), dim]``, in ``block.dtype``. ``scan`` is `ops/ssd.py
    ssd_scan`'s signature; a family passes the one its own module names, so
    that what replaces it there (`benchmark/faults/`) reaches the mixer."""
    b, seq, dim = h.shape
    inner, heads = block.ssm_dim, block.ssm_heads
    shared = block.ssm_groups * block.ssm_state  # B's columns, and C's
    parts = (inner, inner, shared, shared, heads)  # z | x | B | C | dt
    if len(multipliers) != len(parts):
        raise ValueError(f"{len(multipliers)} multipliers for z, x, B, C, dt")
    mup = (
        None if tuple(multipliers) == NO_MULTIPLIERS
        else np.repeat(np.asarray(multipliers, np.float32), parts)
    )

    def widened(t: jnp.ndarray, start: int = 0) -> jnp.ndarray:
        """Projected columns from ``start`` on, float32, times their muP."""
        t = t.astype(jnp.float32)
        return t if mup is None else t * mup[start : start + t.shape[-1]]

    def per_head(name: str, leaf: str, fill: Callable) -> jnp.ndarray:
        return _PerHead(leaf, fill, block.param_dtype, name=name)(heads)

    with jax.named_scope("ssm_in"):
        project = _Columns(sum(parts), block.dtype, block.param_dtype, name="in_proj")
        if read is None:
            z, xbc, dt = jnp.split(
                widened(project(h)), [inner, 2 * inner + 2 * shared], axis=-1
            )
        else:  # the gate at the read positions, the rest at every one
            z = widened(project(h[:, read], 0, inner))
            xbc, dt = jnp.split(
                widened(project(h, inner), inner), [inner + 2 * shared], axis=-1
            )
    with jax.named_scope("ssm_conv"):
        taps = _ConvTaps(block.conv_width, block.param_dtype, name="conv")
        x, b_in, c_out = jnp.split(
            causal_conv(xbc, *taps(inner + 2 * shared)), [inner, inner + shared], axis=-1
        )
    with jax.named_scope("ssm_scan"):
        dt = jax.nn.softplus(dt + per_head("dt_bias", "bias", _dt_bias_init))
        a = -jnp.exp(per_head("a_log", "bias", _a_log_init))
        skip = per_head("skip", "scale", nn.initializers.ones_init())
        c_out = c_out.reshape(b, seq, block.ssm_groups, block.ssm_state)
        y = scan(
            x.reshape(b, seq, heads, inner // heads), dt, a,
            b_in.reshape(b, seq, block.ssm_groups, block.ssm_state),
            c_out if read is None else c_out[:, read], skip,
            chunk=block.ssm_chunk, read=read, dtype=block.dtype,
        )
    with jax.named_scope("ssm_out"):
        normed = GatedGroupNorm(block.ssm_groups, block.param_dtype, name="ssm_norm")(
            y.reshape(b, -1, inner), z
        )
        return block._dense(dim, "out_proj")(normed.astype(block.dtype))
