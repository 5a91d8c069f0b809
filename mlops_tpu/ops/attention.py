"""Attention kernels: Pallas flash attention + XLA reference path.

The reference has no attention anywhere (sklearn trees only); attention
enters this framework through the FT-Transformer (BASELINE.json config 3)
and the BERT stretch config (config 5). Two execution paths:

- ``dense_attention`` — plain jnp softmax attention, one head at a time
  over the fused projection's own ``[B, S, 3*H*D]`` layout; the path of
  every sequence under ``FLASH_MIN_SEQ`` and of every padding mask and
  weight dropout. ``reference_attention`` is the same mathematics for
  ``[B, S, H, D]`` arguments (the flash dispatch off-TPU, and the tests'
  reference). No roofline share of either was ever measured; what a v5e
  trace shows at S=48 is in PERF.md section 5.
- ``flash_attention`` — a Pallas TPU kernel with online softmax: Q/K/V are
  streamed through VMEM in (block_q, block_k) tiles, scores never materialize
  in HBM, so activation memory is O(S·D) instead of O(S²). This is the path
  for BERT-length sequences (128–512+) and the building block the ring
  variant (``mlops_tpu.parallel.ring_attention``) reuses per-shard.

Backward: ``flash_attention`` carries a custom VJP whose backward is TWO
Pallas kernels (the FlashAttention-2 recipe): the forward
additionally emits the per-row logsumexp ``L = m + log l``; the backward
recomputes the probability tiles ``p = exp(s - L)`` from it — one kernel
walks k-blocks accumulating dq, one walks q-blocks accumulating dk/dv —
so the backward, like the forward, never materializes the O(S²) score
matrix in HBM. (Round 4 rematerialized DENSE attention in XLA here,
which walled training at the 2k–8k lengths the forward was tuned for.)

Layout convention matches Flax: ``[batch, seq, heads, head_dim]``.
"""

from __future__ import annotations

import functools
import math

import jax
import jax.numpy as jnp
from jax.experimental import pallas as pl
from jax.experimental.pallas import tpu as pltpu

from mlops_tpu.ops.kernel_gate import tpu_kernel_or

NEG_INF = -1e30


@functools.partial(jax.jit, static_argnames=("scale", "rate"))
def _one_head(q, k, v, mask=None, keep=None, *, scale, rate=0.0):
    """Softmax attention of one head, ``[B, S, D]`` each -> ``[B, S, D]``:
    operands in their own dtype into both products, scores cast to f32
    before scaling and softmax, probabilities cast to ``v.dtype``.
    ``mask`` is ``[B, S_k]`` (True = attend); ``keep`` is the weight
    dropout's ``[B, S_q, S_k]`` draw at ``rate``.

    Jitted so that a program traces and lowers it ONCE for all its heads
    and blocks (144 calls in the bert-base chunk program, which a bulk
    job re-traces at its start: on a v5e machine's host, trace + lower of
    that program take 0.53 s with the ``jit`` and 1.10 s without, 0.52 s
    before the heads were unrolled; PERF.md section 6, PR 26). XLA
    inlines the calls. The price: the one lowering carries the scope of
    the FIRST call site, so in a device trace every head's operations
    read ``.../block_0/MultiHeadSelfAttention_0/attend/...`` whatever
    block ran them. A reader that folds the block index (``program_trace.py``
    does) is unharmed; a per-block reading of ``attend`` is not to be had
    from the trace."""
    s = jnp.einsum("bqd,bkd->bqk", q, k).astype(jnp.float32) * scale
    if mask is not None:
        s = jnp.where(mask[:, None, :], s, NEG_INF)
    p = jax.nn.softmax(s, axis=-1)
    if keep is not None:
        p = jnp.where(keep, p / (1.0 - rate), 0.0)
    return jnp.einsum("bqk,bkd->bqd", p.astype(v.dtype), v)


def reference_attention(
    q: jnp.ndarray, k: jnp.ndarray, v: jnp.ndarray, scale: float | None = None
) -> jnp.ndarray:
    """Dense softmax attention, [B,S,H,D] -> [B,S,H,D]; fp32 softmax."""
    d = q.shape[-1]
    scale = scale if scale is not None else 1.0 / math.sqrt(d)
    s = jnp.einsum("bqhd,bkhd->bhqk", q, k).astype(jnp.float32) * scale
    p = jax.nn.softmax(s, axis=-1)
    return jnp.einsum("bhqk,bkhd->bqhd", p.astype(v.dtype), v)


@jax.named_scope("attend")  # the scope its operations carry in a device trace
def dense_attention(
    qkv: jnp.ndarray,
    heads: int,
    *,
    mask: jnp.ndarray | None = None,
    dropout_rate: float = 0.0,
    dropout_rng: jax.Array | None = None,
) -> jnp.ndarray:
    """Dense self-attention over the fused projection as the matmul wrote
    it: ``qkv`` is ``[B, S, 3*H*D]`` (q, k, v side by side, heads side by
    side in each), the result ``[B, S, H*D]``, ready for a 2-D output
    projection. A head is a D-wide slice of the minor axis, read in place
    by that head's products; the heads' outputs are concatenated on the
    same axis. Nothing is reshaped to ``[B, S, H, D]`` or transposed to
    ``[B, H, S, D]``: on a TPU both are physical relayouts that put S or D
    on the 128 lanes (PERF.md section 6, PR 26). ``mask``: ``[B, S]``,
    True = attend. ``dropout_rng``: where given, attention-weight dropout
    at ``dropout_rate``, one draw for all heads."""
    b, s, width = qkv.shape
    dim = width // 3
    d = dim // heads
    keep = (
        None
        if dropout_rng is None
        else jax.random.bernoulli(
            dropout_rng, 1.0 - dropout_rate, (heads, b, s, s)
        )
    )

    def head(part: int, h: int) -> jnp.ndarray:
        lo = part * dim + h * d
        return qkv[:, :, lo : lo + d]

    return jnp.concatenate(
        [
            _one_head(
                head(0, h),
                head(1, h),
                head(2, h),
                mask,
                None if keep is None else keep[h],
                scale=1.0 / math.sqrt(d),
                rate=dropout_rate,
            )
            for h in range(heads)
        ],
        axis=-1,
    )


# --------------------------------------------------------------------------
# Pallas kernel
# --------------------------------------------------------------------------


def _flash_kernel(
    q_ref, k_ref, v_ref, o_ref, lse_ref, m_ref, l_ref, acc_ref,
    *, scale, kv_len, block_k,
):
    """One (batch*head, q_block) tile; grid axis 2 walks k blocks.

    Online softmax: running max ``m``, normalizer ``l`` and unnormalized
    accumulator ``acc`` live in VMEM scratch across the k-block loop; the
    output tile is written once on the final k block.
    """
    ki = pl.program_id(2)
    nk = pl.num_programs(2)

    @pl.when(ki == 0)
    def _init():
        m_ref[:] = jnp.full_like(m_ref, NEG_INF)
        l_ref[:] = jnp.zeros_like(l_ref)
        acc_ref[:] = jnp.zeros_like(acc_ref)

    q = q_ref[0]  # [block_q, d]
    k = k_ref[0]  # [block_k, d]
    v = v_ref[0]

    s = jax.lax.dot_general(
        q,
        k,
        (((1,), (1,)), ((), ())),
        preferred_element_type=jnp.float32,
    ) * scale  # [block_q, block_k]

    # Mask key positions beyond the true sequence length (the wrapper pads
    # seq up to a block multiple; padded keys must not receive probability).
    col = ki * block_k + jax.lax.broadcasted_iota(jnp.int32, s.shape, 1)
    s = jnp.where(col < kv_len, s, NEG_INF)

    m_prev = m_ref[:, :1]  # [block_q, 1]
    l_prev = l_ref[:, :1]
    m_new = jnp.maximum(m_prev, jnp.max(s, axis=-1, keepdims=True))
    p = jnp.exp(s - m_new)
    alpha = jnp.exp(m_prev - m_new)
    l_new = alpha * l_prev + jnp.sum(p, axis=-1, keepdims=True)
    acc_ref[:] = acc_ref[:] * alpha + jax.lax.dot_general(
        p.astype(v.dtype),
        v,
        (((1,), (0,)), ((), ())),
        preferred_element_type=jnp.float32,
    )
    m_ref[:] = jnp.broadcast_to(m_new, m_ref.shape)
    l_ref[:] = jnp.broadcast_to(l_new, l_ref.shape)

    @pl.when(ki == nk - 1)
    def _finalize():
        o_ref[0] = (acc_ref[:] / l_ref[:, :1]).astype(o_ref.dtype)
        # Per-row logsumexp for the Pallas backward: p = exp(s - L)
        # reconstructs the probability tile without storing it. l == 0
        # cannot happen for real rows (kv_len >= 1 unmasked key), but
        # guard the log anyway — padded-q rows still sum real keys.
        lse_ref[0] = m_ref[:, :1] + jnp.log(
            jnp.maximum(l_ref[:, :1], 1e-30)
        )


def _fold_heads(x: jnp.ndarray) -> jnp.ndarray:
    """[B,S,H,D] -> [B*H, S, D]: batch and heads fold into one parallel
    grid axis."""
    b, s, h, d = x.shape
    return x.transpose(0, 2, 1, 3).reshape(b * h, s, d)


def _pad_seq(x: jnp.ndarray, block: int) -> jnp.ndarray:
    pad = (-x.shape[1]) % block
    return jnp.pad(x, ((0, 0), (0, pad), (0, 0))) if pad else x


def _clamp_block(block: int, seq: int) -> int:
    """A block never exceeds the sequence rounded up to the 128-lane tile
    (S=508 runs as one 512 block): every block the default sizes produce
    is a multiple of 128, which the per-row statistics' ``(1, 1, block_q)``
    row layout needs to lower."""
    return min(block, -(-seq // 128) * 128)


def _flash_forward(
    q: jnp.ndarray,
    k: jnp.ndarray,
    v: jnp.ndarray,
    scale: float,
    block_q: int,
    block_k: int,
    interpret: bool,
) -> tuple[jnp.ndarray, jnp.ndarray]:
    """Returns ``(out [B,S,H,D], lse [B*H, padded_Sq, 1])`` — the
    logsumexp stays in the folded/padded layout the backward kernels
    consume, as a COLUMN per row block: a ``(1, block_q)`` block of a 2-D
    array does not lower on Mosaic (second-to-last block dim must be a
    multiple of 8 or the whole axis), a ``(1, block_q, 1)`` block of a 3-D
    one does."""
    b, s_q, h, d = q.shape
    s_kv = k.shape[1]

    block_q = _clamp_block(block_q, s_q)
    block_k = _clamp_block(block_k, s_kv)
    qf = _pad_seq(_fold_heads(q), block_q)
    kf = _pad_seq(_fold_heads(k), block_k)
    vf = _pad_seq(_fold_heads(v), block_k)
    nq = qf.shape[1] // block_q
    nk = kf.shape[1] // block_k

    grid = (b * h, nq, nk)
    kernel = functools.partial(
        _flash_kernel, scale=scale, kv_len=s_kv, block_k=block_k
    )
    out, lse = pl.pallas_call(
        kernel,
        grid=grid,
        in_specs=[
            pl.BlockSpec((1, block_q, d), lambda bh, qi, ki: (bh, qi, 0)),
            pl.BlockSpec((1, block_k, d), lambda bh, qi, ki: (bh, ki, 0)),
            pl.BlockSpec((1, block_k, d), lambda bh, qi, ki: (bh, ki, 0)),
        ],
        out_specs=[
            pl.BlockSpec((1, block_q, d), lambda bh, qi, ki: (bh, qi, 0)),
            pl.BlockSpec((1, block_q, 1), lambda bh, qi, ki: (bh, qi, 0)),
        ],
        out_shape=[
            jax.ShapeDtypeStruct(qf.shape, q.dtype),
            jax.ShapeDtypeStruct((b * h, qf.shape[1], 1), jnp.float32),
        ],
        scratch_shapes=[
            pltpu.VMEM((block_q, 128), jnp.float32),  # running max m
            pltpu.VMEM((block_q, 128), jnp.float32),  # running normalizer l
            pltpu.VMEM((block_q, d), jnp.float32),  # unnormalized accumulator
        ],
        compiler_params=pltpu.CompilerParams(
            dimension_semantics=(
                pltpu.PARALLEL,
                pltpu.PARALLEL,
                pltpu.ARBITRARY,  # k-block loop carries scratch state
            ),
        ),
        interpret=interpret,
        name="flash_fwd",
    )(qf, kf, vf)

    out = out[:, :s_q].reshape(b, h, s_q, d).transpose(0, 2, 1, 3)
    return out, lse


def _flash_bwd_dq_kernel(
    q_ref, k_ref, v_ref, do_ref, lse_ref, delta_ref, dq_ref, dq_acc,
    *, scale, kv_len, block_k,
):
    """dq tile: grid (B*H, q blocks, k blocks); the k loop accumulates
    ``dq_i = scale * sum_j p_ij (dp_ij - delta_i) k_j`` in VMEM scratch,
    with ``p`` recomputed from the stored logsumexp."""
    ki = pl.program_id(2)
    nk = pl.num_programs(2)

    @pl.when(ki == 0)
    def _init():
        dq_acc[:] = jnp.zeros_like(dq_acc)

    q = q_ref[0]  # [bq, d]
    k = k_ref[0]  # [bk, d]
    v = v_ref[0]
    do = do_ref[0]  # [bq, d]
    lse = lse_ref[0]  # [bq, 1]
    delta = delta_ref[0]  # [bq, 1]

    s = jax.lax.dot_general(
        q, k, (((1,), (1,)), ((), ())), preferred_element_type=jnp.float32
    ) * scale  # [bq, bk]
    col = ki * block_k + jax.lax.broadcasted_iota(jnp.int32, s.shape, 1)
    s = jnp.where(col < kv_len, s, NEG_INF)
    p = jnp.exp(s - lse)  # [bq, bk]
    dp = jax.lax.dot_general(
        do, v, (((1,), (1,)), ((), ())), preferred_element_type=jnp.float32
    )  # [bq, bk]
    ds = p * (dp - delta) * scale
    dq_acc[:] += jax.lax.dot_general(
        ds.astype(k.dtype), k, (((1,), (0,)), ((), ())),
        preferred_element_type=jnp.float32,
    )

    @pl.when(ki == nk - 1)
    def _finalize():
        dq_ref[0] = dq_acc[:].astype(dq_ref.dtype)


def _flash_bwd_dkv_kernel(
    k_ref, v_ref, q_ref, do_ref, lse_ref, delta_ref, dk_ref, dv_ref,
    dk_acc, dv_acc, *, scale, kv_len, block_k,
):
    """dk/dv tiles: grid (B*H, k blocks, q blocks); the q loop accumulates
    ``dv_j = sum_i p_ij do_i`` and
    ``dk_j = scale * sum_i p_ij (dp_ij - delta_i) q_i``. Probabilities
    recompute transposed (``[bk, bq]``) from the same logsumexp."""
    qi = pl.program_id(2)
    nq = pl.num_programs(2)

    @pl.when(qi == 0)
    def _init():
        dk_acc[:] = jnp.zeros_like(dk_acc)
        dv_acc[:] = jnp.zeros_like(dv_acc)

    k = k_ref[0]  # [bk, d]
    v = v_ref[0]
    q = q_ref[0]  # [bq, d]
    do = do_ref[0]
    lse = lse_ref[0]  # [1, bq]
    delta = delta_ref[0]  # [1, bq]

    # s_t[j, i] = k_j . q_i * scale (the transposed score tile). The
    # kv_len mask lands on ROWS here; masked rows only touch dk/dv tiles
    # that are sliced off after the call, but masking keeps them zero so
    # the f32 accumulator never sees garbage.
    s_t = jax.lax.dot_general(
        k, q, (((1,), (1,)), ((), ())), preferred_element_type=jnp.float32
    ) * scale  # [bk, bq]
    row = (
        pl.program_id(1) * k.shape[0]
        + jax.lax.broadcasted_iota(jnp.int32, s_t.shape, 0)
    )
    s_t = jnp.where(row < kv_len, s_t, NEG_INF)
    p_t = jnp.exp(s_t - lse)  # [bk, bq]
    dv_acc[:] += jax.lax.dot_general(
        p_t.astype(do.dtype), do, (((1,), (0,)), ((), ())),
        preferred_element_type=jnp.float32,
    )
    dp_t = jax.lax.dot_general(
        v, do, (((1,), (1,)), ((), ())), preferred_element_type=jnp.float32
    )  # [bk, bq]
    ds_t = p_t * (dp_t - delta) * scale
    dk_acc[:] += jax.lax.dot_general(
        ds_t.astype(q.dtype), q, (((1,), (0,)), ((), ())),
        preferred_element_type=jnp.float32,
    )

    @pl.when(qi == nq - 1)
    def _finalize():
        dk_ref[0] = dk_acc[:].astype(dk_ref.dtype)
        dv_ref[0] = dv_acc[:].astype(dv_ref.dtype)


def _flash_backward(
    q, k, v, out, lse, g, scale, block_q, block_k, interpret
):
    """Assemble dq/dk/dv from the two Pallas kernels. ``lse`` arrives in
    the folded/padded ``[B*H, padded_Sq, 1]`` column layout the forward
    produced. The dq kernel broadcasts the per-row statistics (lse,
    delta) along its score tile's rows, so it reads them as columns; the
    dk/dv kernel works on the TRANSPOSED tile and reads the same numbers
    as ``[B*H, 1, padded_Sq]`` rows — a free XLA reshape outside the
    kernels instead of a relayout inside one."""
    b, s_q, h, d = q.shape
    s_kv = k.shape[1]
    block_q = _clamp_block(block_q, s_q)
    block_k = _clamp_block(block_k, s_kv)

    qf = _pad_seq(_fold_heads(q), block_q)
    kf = _pad_seq(_fold_heads(k), block_k)
    vf = _pad_seq(_fold_heads(v), block_k)
    dof = _pad_seq(_fold_heads(g), block_q)
    # delta_i = do_i . out_i (rowsum, [B*H, Sq]) — the softmax-jacobian
    # correction term; tiny, so XLA computes it outside the kernels.
    delta = _pad_seq(
        jnp.sum(
            _fold_heads(g).astype(jnp.float32)
            * _fold_heads(out).astype(jnp.float32),
            axis=-1,
            keepdims=True,
        ),
        block_q,
    )  # [B*H, padded_Sq, 1]

    bh = b * h
    nq = qf.shape[1] // block_q
    nk = kf.shape[1] // block_k
    common = dict(scale=scale, kv_len=s_kv, block_k=block_k)
    qspec = pl.BlockSpec((1, block_q, d), lambda bhi, qi, ki: (bhi, qi, 0))
    kspec = pl.BlockSpec((1, block_k, d), lambda bhi, qi, ki: (bhi, ki, 0))
    colspec = pl.BlockSpec((1, block_q, 1), lambda bhi, qi, ki: (bhi, qi, 0))

    dq = pl.pallas_call(
        functools.partial(_flash_bwd_dq_kernel, **common),
        grid=(bh, nq, nk),
        in_specs=[qspec, kspec, kspec, qspec, colspec, colspec],
        out_specs=qspec,
        out_shape=jax.ShapeDtypeStruct(qf.shape, q.dtype),
        scratch_shapes=[pltpu.VMEM((block_q, d), jnp.float32)],
        compiler_params=pltpu.CompilerParams(
            dimension_semantics=(pltpu.PARALLEL, pltpu.PARALLEL, pltpu.ARBITRARY),
        ),
        interpret=interpret,
        name="flash_bwd_dq",
    )(qf, kf, vf, dof, lse, delta)

    # dk/dv walk the grid transposed: axis 1 = k blocks, axis 2 = q loop.
    kspec_t = pl.BlockSpec((1, block_k, d), lambda bhi, ki, qi: (bhi, ki, 0))
    qspec_t = pl.BlockSpec((1, block_q, d), lambda bhi, ki, qi: (bhi, qi, 0))
    rowspec_t = pl.BlockSpec((1, 1, block_q), lambda bhi, ki, qi: (bhi, 0, qi))
    as_rows = (bh, 1, qf.shape[1])
    dk, dv = pl.pallas_call(
        functools.partial(_flash_bwd_dkv_kernel, **common),
        grid=(bh, nk, nq),
        in_specs=[kspec_t, kspec_t, qspec_t, qspec_t, rowspec_t, rowspec_t],
        out_specs=[kspec_t, kspec_t],
        out_shape=[
            jax.ShapeDtypeStruct(kf.shape, k.dtype),
            jax.ShapeDtypeStruct(vf.shape, v.dtype),
        ],
        scratch_shapes=[
            pltpu.VMEM((block_k, d), jnp.float32),
            pltpu.VMEM((block_k, d), jnp.float32),
        ],
        compiler_params=pltpu.CompilerParams(
            dimension_semantics=(pltpu.PARALLEL, pltpu.PARALLEL, pltpu.ARBITRARY),
        ),
        interpret=interpret,
        name="flash_bwd_dkv",
    )(kf, vf, qf, dof, lse.reshape(as_rows), delta.reshape(as_rows))

    def unfold(x, s):
        return x[:, :s].reshape(b, h, s, d).transpose(0, 2, 1, 3)

    return unfold(dq, s_q), unfold(dk, s_kv), unfold(dv, s_kv)


@functools.partial(jax.custom_vjp, nondiff_argnums=(3, 4, 5, 6))
def _flash_attention(q, k, v, scale, block_q, block_k, interpret):
    out, _ = _flash_forward(q, k, v, scale, block_q, block_k, interpret)
    return out


def _flash_fwd(q, k, v, scale, block_q, block_k, interpret):
    out, lse = _flash_forward(q, k, v, scale, block_q, block_k, interpret)
    return out, (q, k, v, out, lse)


def _flash_bwd(scale, block_q, block_k, interpret, residuals, g):
    q, k, v, out, lse = residuals
    return _flash_backward(
        q, k, v, out, lse, g, scale, block_q, block_k, interpret
    )


_flash_attention.defvjp(_flash_fwd, _flash_bwd)


def flash_attention(
    q: jnp.ndarray,
    k: jnp.ndarray,
    v: jnp.ndarray,
    scale: float | None = None,
    block_q: int = 1024,
    block_k: int = 1024,
    interpret: bool = False,
) -> jnp.ndarray:
    """Fused flash attention, [B,S,H,D] -> [B,S,H,D] (self- or cross-).

    Compiled by Mosaic (``interpret=False``): it lowers for a TPU and
    raises anywhere else. ``interpret=True`` is for CPU tests, which pass
    it themselves. Default blocks are 1024x1024, clamped to the sequence
    rounded up to 128 (`_clamp_block`); the f32 score tile
    (1024x1024x4 B = 4 MB) fits the forward's VMEM. Speed against XLA's
    dense attention: not measured on chip.
    """
    d = q.shape[-1]
    scale = scale if scale is not None else 1.0 / math.sqrt(d)
    return _flash_attention(q, k, v, scale, block_q, block_k, interpret)


# Below this sequence length the O(S²) score matrix fits trivially in VMEM
# and XLA's fused attention needs no kernel; above it the streaming kernel
# keeps activation memory O(S·D).
FLASH_MIN_SEQ = 128


def wants_flash(seq: int, use_flash: bool | None) -> bool:
    """The dispatch rule: ``use_flash`` where given, else by length."""
    return seq >= FLASH_MIN_SEQ if use_flash is None else use_flash


@jax.named_scope("attend")  # the scope its operations carry in a device trace
def attend(
    q: jnp.ndarray,
    k: jnp.ndarray,
    v: jnp.ndarray,
    scale: float | None = None,
    use_flash: bool | None = None,
) -> jnp.ndarray:
    """Dispatch. ``use_flash=None``: sequences of FLASH_MIN_SEQ and longer
    take the compiled flash kernel where the computation is lowered for a
    TPU and XLA's dense attention on any other platform (`kernel_gate`);
    shorter ones are dense everywhere. ``True`` is the compiled kernel
    unconditionally (a compile error off-TPU), ``False`` dense."""
    if not wants_flash(q.shape[1], use_flash):
        return reference_attention(q, k, v, scale)
    if use_flash:
        return flash_attention(q, k, v, scale)
    return tpu_kernel_or(
        functools.partial(flash_attention, scale=scale),
        functools.partial(reference_attention, scale=scale),
        q, k, v,
    )
