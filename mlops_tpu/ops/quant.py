"""Int8/bf16 quantized student — the raw-speed serving/bulk tier.

Hummingbird (arxiv 2010.04804) showed classical-model inference compiles
to pure tensor programs worth kernel-level treatment; Gemma-on-TPU serving
(arxiv 2605.25645) is the reference frame for a quantized low-precision
serving tier behind quality gates. This module is that tier's NUMERIC
core: a hand-written two-layer MLP student (no flax module — the whole
forward is a handful of explicit matmuls, which is what makes the Pallas
fusion in `ops/quant_kernel.py` tractable) stored in a quantized format:

- dense kernels:  int8 weights + per-output-channel f32 scales
  (symmetric, scale = max|w| / 127 per column)
- embedding tables: bf16 (stacked ``[C, max_card, E]``; unused tail rows
  of narrow-cardinality features stay zero and are never selected)
- biases: f32

Compute dequantizes IN-JIT and runs f32 (XLA folds the dequant into the
matmul epilogue; on CPU backends bf16 arithmetic is emulated and slow —
the f32-after-dequant rule is what buys the bulk throughput there).

Categorical lookup is a one-hot matmul, not a gather: `broadcasted_iota`
comparisons lower on Mosaic (TPU Pallas) where dynamic gathers do not,
and every consumer — the jnp composite, the Pallas kernel body, and the
bulk chunk program — calls the SAME `student_logits`, so the serve/bulk/
kernel paths are one definition (equal to f32 rounding across compilers:
`ops/quant_kernel.py KERNEL_COMPOSITE_ATOL`).

Fitting lives in `train/distill.py distill_quant_student` (the fidelity
gate) and `train/calibrate.py` (the post-hoc temperature refit); this
module is jax-math + format only.
"""

from __future__ import annotations

from typing import Any

import jax
import jax.numpy as jnp
import numpy as np

from mlops_tpu.schema import SCHEMA

# Default student geometry: embed width + hidden width. Small on purpose —
# the tier's reason to exist is FLOPs/row (~6x under the (64,64) distilled
# flax student at the credit-default widths); fidelity is enforced by the
# distillation gate, not by capacity.
QUANT_EMBED_DIM = 4
QUANT_HIDDEN = 32

# Manifest format tag: bundles carry it so a loader can refuse a quant
# blob written by a different packing scheme.
QUANT_FORMAT = "int8-dense/bf16-embed/v1"


def quantize_dense(w: np.ndarray) -> tuple[np.ndarray, np.ndarray]:
    """Per-output-channel symmetric int8 quantization of a dense kernel
    ``[in, out]`` -> ``(int8 [in, out], f32 scales [out])``. All-zero
    columns get scale 1 (nothing to represent; dequant stays exact)."""
    w = np.asarray(w, np.float32)
    absmax = np.abs(w).max(axis=0)
    scale = np.where(absmax > 0, absmax / 127.0, 1.0).astype(np.float32)
    q = np.clip(np.rint(w / scale[None, :]), -127, 127).astype(np.int8)
    return q, scale


def dequantize_dense(w_q: jnp.ndarray, scale: jnp.ndarray) -> jnp.ndarray:
    """int8 kernel + per-column scales -> f32 kernel (the in-jit inverse
    of `quantize_dense`)."""
    return w_q.astype(jnp.float32) * scale[None, :]


def quantize_student(master: dict[str, Any]) -> dict[str, jnp.ndarray]:
    """f32 master tree (from the distillation fit) -> the quantized
    serving tree. Head vector ``w2`` quantizes as a 1-column kernel."""
    w1_q, w1_s = quantize_dense(np.asarray(master["w1"]))
    w2_q, w2_s = quantize_dense(np.asarray(master["w2"])[:, None])
    return {
        "embed": jnp.asarray(master["embed"], jnp.bfloat16),
        "w1_q": jnp.asarray(w1_q),
        "w1_s": jnp.asarray(w1_s),
        "b1": jnp.asarray(master["b1"], jnp.float32),
        "w2_q": jnp.asarray(w2_q[:, 0]),
        "w2_s": jnp.asarray(w2_s[0]),
        "b2": jnp.asarray(master["b2"], jnp.float32),
    }


def quant_params_geometry(qparams: dict[str, Any]) -> tuple[int, int]:
    """(embed_dim, hidden) read back from a quant tree — the compile-cache
    key's geometry axis (`compilecache/warmup.py serve_quant_jobs`)."""
    return int(qparams["embed"].shape[2]), int(qparams["w1_q"].shape[1])


def init_quant_master(
    seed: int = 0,
    embed_dim: int = QUANT_EMBED_DIM,
    hidden: int = QUANT_HIDDEN,
) -> dict[str, jnp.ndarray]:
    """f32 master init for the distillation fit (train/distill.py)."""
    c, k = SCHEMA.num_categorical, max(SCHEMA.cards)
    d_in = c * embed_dim + SCHEMA.num_numeric
    ke = jax.random.split(jax.random.PRNGKey(seed), 3)
    return {
        "embed": 0.02 * jax.random.normal(ke[0], (c, k, embed_dim), jnp.float32),
        "w1": jax.random.normal(ke[1], (d_in, hidden), jnp.float32)
        / np.sqrt(d_in),
        "b1": jnp.zeros((hidden,), jnp.float32),
        "w2": jax.random.normal(ke[2], (hidden,), jnp.float32)
        / np.sqrt(hidden),
        "b2": jnp.zeros((), jnp.float32),
    }


def abstract_quant_params(
    embed_dim: int = QUANT_EMBED_DIM, hidden: int = QUANT_HIDDEN
) -> dict[str, jax.ShapeDtypeStruct]:
    """Shape-only quant tree for abstract tracing and AOT cache keys (the
    `abstract_monitor_state` discipline): shapes depend only on the schema
    and the (embed_dim, hidden) geometry."""
    c, k = SCHEMA.num_categorical, max(SCHEMA.cards)
    d_in = c * embed_dim + SCHEMA.num_numeric
    S = jax.ShapeDtypeStruct
    return {
        "embed": S((c, k, embed_dim), jnp.bfloat16),
        "w1_q": S((d_in, hidden), jnp.int8),
        "w1_s": S((hidden,), jnp.float32),
        "b1": S((hidden,), jnp.float32),
        "w2_q": S((hidden,), jnp.int8),
        "w2_s": S((), jnp.float32),
        "b2": S((), jnp.float32),
    }


def one_hot_2d(ids_col: jnp.ndarray, k: int) -> jnp.ndarray:
    """One-hot of an id column ``[N]`` -> f32 ``[N, k]`` via a 2-D
    broadcasted iota — the Mosaic-safe form (1-D iota does not lower on
    TPU Pallas; `jax.nn.one_hot` builds one). The ONE one-hot rule every
    quant-tier consumer shares."""
    iota = jax.lax.broadcasted_iota(jnp.int32, (ids_col.shape[0], k), 1)
    return (ids_col[:, None] == iota).astype(jnp.float32)


def student_logits(
    embed: jnp.ndarray,  # [C, K, E] any float dtype (cast to f32)
    w1: jnp.ndarray,  # f32 [C*E + M, H]
    b1: jnp.ndarray,  # f32 [H]
    w2: jnp.ndarray,  # f32 [H]
    b2: jnp.ndarray,  # f32 []
    cat_ids: jnp.ndarray,  # int32 [N, C]
    numeric: jnp.ndarray,  # f32 [N, M]
) -> jnp.ndarray:
    """The hand-written student forward, f32 end to end: per-feature
    one-hot embed matmuls (unrolled over the ~9 categorical features —
    each is a 2-D ``[N,K] @ [K,E]`` dot, the shape Mosaic wants) -> concat
    with numerics -> dense/relu/dense. Returns logits ``[N]``."""
    c, k = embed.shape[0], embed.shape[1]
    feats = [
        one_hot_2d(cat_ids[:, j], k) @ embed[j].astype(jnp.float32)
        for j in range(c)
    ]
    x = jnp.concatenate(feats + [numeric.astype(jnp.float32)], axis=1)
    h = jnp.maximum(x @ w1 + b1[None, :], 0.0)
    return h @ w2 + b2


def master_student_logits(
    master: dict[str, Any], cat_ids: jnp.ndarray, numeric: jnp.ndarray
) -> jnp.ndarray:
    """Forward through the un-quantized f32 master (the distillation fit's
    objective surface)."""
    return student_logits(
        master["embed"], master["w1"], master["b1"], master["w2"],
        master["b2"], cat_ids, numeric,
    )


def quant_student_logits(
    qparams: dict[str, Any], cat_ids: jnp.ndarray, numeric: jnp.ndarray
) -> jnp.ndarray:
    """Forward through the QUANTIZED tree: dequantize in-jit, then the
    shared f32 forward — serving, bulk, and the Pallas kernel body all
    route through here (one definition)."""
    w1 = dequantize_dense(qparams["w1_q"], qparams["w1_s"])
    w2 = qparams["w2_q"].astype(jnp.float32) * qparams["w2_s"]
    return student_logits(
        qparams["embed"], w1, qparams["b1"], w2, qparams["b2"],
        cat_ids, numeric,
    )


# --------------------------------------------------------- serialization
def quant_params_to_arrays(qparams: dict[str, Any]) -> dict[str, np.ndarray]:
    """npz-safe host arrays: numpy has no bf16, so the embed table ships
    as the f32 image of its bf16 values — bf16 -> f32 is exact and the
    f32 -> bf16 cast on load returns the original bits (round-trip
    lossless)."""
    out = {}
    for key, leaf in qparams.items():
        arr = np.asarray(
            leaf.astype(jnp.float32) if leaf.dtype == jnp.bfloat16 else leaf
        )
        out[key] = arr
    return out


def quant_params_from_arrays(
    arrays: dict[str, np.ndarray],
) -> dict[str, jnp.ndarray]:
    """Inverse of `quant_params_to_arrays` (embed goes back to bf16)."""
    out = {}
    for key, arr in arrays.items():
        if key == "embed":
            out[key] = jnp.asarray(arr, jnp.bfloat16)
        else:
            out[key] = jnp.asarray(arr)
    return out
