"""Plain reference of the ``bert`` family: the record rendered as a
48-token sentence, a pre-LN encoder, a tanh pooler on [CLS] and one logit.

Written from the description in the configuration file and the program's
docstrings, not from its code paths: no flax, no kernels, no batching
tricks. Departures of the program from BERT (pre-LN, tanh GELU, own
vocabulary) are the configuration's, listed in its file; the reference
follows the configuration.

Token ids: ``[PAD][CLS][SEP][MASK]`` | one name token per feature | a block
of value tokens per categorical feature (its cardinality) | a block of
``num_bins`` bin tokens per numeric feature, the bins being the standard
normal's quantiles. A record is ``[CLS] name value ... name value [SEP]``.
"""

from __future__ import annotations

from statistics import NormalDist

import jax
import jax.numpy as jnp
import numpy as np

from .common import gelu_tanh, in_blocks, layer_norm, product, served_probability

CLS_ID, SEP_ID, SPECIAL = 1, 2, 4


def tokenize(cat, num, cards, num_bins):
    n = cat.shape[0]
    features = len(cards) + num.shape[1]
    names = SPECIAL + jnp.arange(features, dtype=jnp.int32)
    cat_base = SPECIAL + features + np.concatenate([[0], np.cumsum(cards)[:-1]])
    bin_base = SPECIAL + features + sum(cards) + num_bins * np.arange(num.shape[1])
    edges = jnp.asarray(
        [NormalDist().inv_cdf(i / num_bins) for i in range(1, num_bins)], jnp.float32
    )
    bins = (num[:, :, None] >= edges[None, None, :]).sum(axis=-1)
    values = jnp.concatenate(
        [cat + jnp.asarray(cat_base, jnp.int32), bins + jnp.asarray(bin_base, jnp.int32)],
        axis=1,
    )
    body = jnp.stack([jnp.broadcast_to(names, (n, features)), values], axis=2)
    return jnp.concatenate(
        [
            jnp.full((n, 1), CLS_ID, jnp.int32),
            body.reshape(n, 2 * features),
            jnp.full((n, 1), SEP_ID, jnp.int32),
        ],
        axis=1,
    )


def logits(params, cat, num, spec: dict, precision: str = "f32"):
    """float32 logits [N] for int32 ``cat`` [N, C] and float32 ``num`` [N, M]."""
    p = params["params"]
    mc = spec["model_config"]
    depth = mc["depth"]
    tokens = tokenize(cat.astype(jnp.int32), num, spec["schema"]["cards"], spec["num_bins"])
    x = p["tok_embed"]["embedding"][tokens] + p["pos_embed"][None]
    x = layer_norm(x, p["ln_embed"])
    for i in range(depth):
        blk = p[f"block_{i}"]
        att = blk["MultiHeadSelfAttention_0"]
        h = layer_norm(x, blk["LayerNorm_0"])
        qkv = product("nsd,dthe->nsthe", h, att["qkv"]["kernel"], precision)
        qkv = qkv + att["qkv"]["bias"]
        q, k, v = qkv[:, :, 0], qkv[:, :, 1], qkv[:, :, 2]
        scores = product("nqhe,nkhe->nhqk", q, k, precision) / np.sqrt(q.shape[-1])
        weights = jax.nn.softmax(scores, axis=-1)
        mixed = product("nhqk,nkhe->nqhe", weights, v, precision)
        x = x + product("nqhe,hed->nqd", mixed, att["out"]["kernel"], precision) + att["out"]["bias"]
        h = layer_norm(x, blk["LayerNorm_1"])
        h = gelu_tanh(product("nsd,df->nsf", h, blk["Dense_0"]["kernel"], precision) + blk["Dense_0"]["bias"])
        x = x + product("nsf,fd->nsd", h, blk["Dense_1"]["kernel"], precision) + blk["Dense_1"]["bias"]
    cls = layer_norm(x[:, 0], p["ln_final"])
    pooled = jnp.tanh(product("nd,de->ne", cls, p["pooler"]["kernel"], precision) + p["pooler"]["bias"])
    out = product("nd,do->no", pooled, p["head"]["kernel"], precision) + p["head"]["bias"]
    return out[:, 0]


def predictions(params, cat, num, spec, temperature, precision="f32", block_rows=512):
    """Served probabilities for host arrays, computed block by block."""
    # the weights are an ARGUMENT: closed over, they would be baked into the
    # executable as 0.3 GB of constants, too large for the persistent cache
    fn = jax.jit(
        lambda p, c, x: served_probability(logits(p, c, x, spec, precision), temperature)
    )
    return in_blocks(lambda c, x: fn(params, c, x), block_rows, cat, num)
