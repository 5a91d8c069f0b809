"""Plain reference of the ``lfm2_moe`` family: credit records as 48-token
sentences, an account's history as their concatenation, an LFM2-MoE-style
hybrid decoder (a double-gated short convolution or causal grouped-query
attention by the published ``layer_types`` list; leading dense SwiGLU
layers, then routed experts with no shared one), one logit a record.

Written from the configuration file (``source_config``, ``assumed``,
``departures``) and the layer's equations, not from the program's code
paths: no flax, no kernels, no sort, no grouped products, no blocks of
queries. float32 ``jax.numpy`` with every matrix product at ``highest``
precision (``common.product``). The parameters may be stored in bfloat16;
a leaf is widened where it is used (exact), one layer at a time and, in
the expert layer, one expert at a time, and attention goes one head at a
time, so that a 3,072-token history fits beside 10.8 GB of weights. A
layer is one jitted function, called once a layer and history.

One layer, x in R^{S x d}, float32 throughout; RMSNorm(x) = x *
rsqrt(mean(x^2) + eps) * w:

1. h = RMSNorm(x). Where ``layer_types[l]`` is "conv": (B, C, u) = the
   three thirds of h W_in, in that order; z[t] = sum_{j < L} w[j] * (B *
   u)[t - (L - 1) + j] per channel, with zeros left of the history's
   start (L = ``conv_L_cache``, no bias); x <- x + (C * z) W_out.
2. Where it is "full_attention": q = h W_q in ``num_attention_heads``
   heads of d / heads, k = h W_k and v = h W_v in ``num_key_value_heads``
   heads of the same width; each head's q and k through an RMSNorm over
   the head's width (one weight vector for all heads), then RoPE
   (rotate-half pairs (i, i + width/2), inverse frequencies theta^(-i /
   (width/2)), positions 0..S-1); query head i attends causally over
   key/value head i // (heads / kv heads), scale width^-0.5; x <- x +
   concat(heads) W_o.
3. h = RMSNorm(x). Layers before ``num_dense_layers``: x <- x +
   W_down(silu(W_gate h) * W_up h). After: s = sigmoid(h W_g); the k
   experts chosen are top_k(s + b), b the selection bias (``expert_bias``:
   it chooses, it never weighs); w_i = routed_scaling_factor * s_i / (sum
   over the chosen of s + 1e-6); x <- x + sum_{i chosen AND first_expert
   <= i < first_expert + held} w_i E_i(h), every E a SwiGLU of width
   ``moe_intermediate_size``.

Read-out: final RMSNorm at each record's last token, head (d -> 1, with a
bias). The reference computes every layer at every position; the program
may skip what no answer needs (``departures``).

Departures from the published description, each also in the configuration
file: the read-out in place of the 65,536-way head; token t of the
record's own vocabulary reads row t * (rows // V) of the embedding; a
routed expert's output is summed in float32, unrounded.
"""

from __future__ import annotations

import functools

import jax
import jax.numpy as jnp
import numpy as np

from .bert import tokenize
from .common import product, served_probability
from .kimi_k2 import attention_head, rms_norm, swiglu

ROUTE_EPS = 1e-6


def rotary(x, theta: float):
    """x [S, H, r]: position p turns each pair (x_i, x_{i + r/2}) by the
    angle p * theta^(-i / (r/2))."""
    half = x.shape[-1] // 2
    inv_freq = np.asarray([float(theta) ** (-i / half) for i in range(half)], np.float32)
    angle = jnp.arange(x.shape[0], dtype=jnp.float32)[:, None] * jnp.asarray(inv_freq)[None, :]
    cos, sin = jnp.cos(angle)[:, None, :], jnp.sin(angle)[:, None, :]
    first, second = x[..., :half], x[..., half:]
    return jnp.concatenate(
        [first * cos - second * sin, second * cos + first * sin], axis=-1
    )


def short_convolution(h, p, precision: str):
    """The double-gated causal depthwise convolution of one history h
    [S, d], position by position from its definition."""
    seq = h.shape[0]
    in_gate, out_gate, signal = jnp.split(
        product("sd,df->sf", h, p["in_proj"]["kernel"], precision), 3, axis=-1
    )
    taps = p["conv"]["kernel"].astype(jnp.float32)  # [L, d]
    width = taps.shape[0]
    gated = in_gate * signal
    mixed = jnp.zeros_like(gated)
    for j in range(width):
        back = width - 1 - j  # tap j reads the position `back` before
        shifted = jnp.concatenate([jnp.zeros((back, gated.shape[1])), gated[: seq - back]])
        mixed = mixed + taps[j] * shifted
    return product("sd,df->sf", out_gate * mixed, p["out_proj"]["kernel"], precision)


def grouped_attention(h, p, z: dict, precision: str):
    """Causal grouped-query attention of one history h [S, d], one query
    head at a time against the key/value head it shares."""
    seq = h.shape[0]
    heads, kv_heads = z["heads"], z["kv_heads"]
    width = h.shape[1] // heads
    q = product("sd,df->sf", h, p["q"]["kernel"], precision).reshape(seq, heads, width)
    k = product("sd,df->sf", h, p["k"]["kernel"], precision).reshape(seq, kv_heads, width)
    v = product("sd,df->sf", h, p["v"]["kernel"], precision).reshape(seq, kv_heads, width)
    q = rotary(rms_norm(q, p["q_norm"]["scale"], z["eps"]), z["theta"])
    k = rotary(rms_norm(k, p["k_norm"]["scale"], z["eps"]), z["theta"])
    shared = np.arange(heads) // (heads // kv_heads)  # the key/value head of each query head
    per_head = jax.lax.map(
        lambda a: attention_head(*a, scale=width**-0.5, precision=precision),
        (q.transpose(1, 0, 2), k.transpose(1, 0, 2)[shared], v.transpose(1, 0, 2)[shared]),
    )  # [H, S, width], one head's scores at a time
    mixed = per_head.transpose(1, 0, 2).reshape(seq, heads * width)
    return product("sf,fd->sd", mixed, p["o"]["kernel"], precision)


def sizes(spec: dict) -> dict:
    """The sizes and constants a layer needs, from the configuration."""
    mc = spec["model_config"]
    return {
        "heads": mc["heads"],
        "kv_heads": mc["kv_heads"],
        "eps": float(spec["norm_eps"]),
        "theta": float(mc["rope_theta"]),
        "top_k": mc["experts_per_token"],
        "first": mc["first_expert"],
        "held": mc["experts_held"] or mc["num_experts"],
        "scaling": float(spec["routed_scaling_factor"]),
    }


@functools.partial(jax.jit, static_argnames=("dims", "precision"))
def layer(x, p, *, dims: tuple, precision: str):
    """One decoder layer on ONE history x [S, d] -> (x, the experts each
    token chose [S, k], every expert's score [S, E]), the last two ``None``
    for a dense layer. The token mixer is the one whose weights the layer
    holds."""
    z = dict(dims)
    h = rms_norm(x, p["operator_norm"]["scale"], z["eps"])
    if "conv" in p:
        x = x + short_convolution(h, p, precision)
    else:
        x = x + grouped_attention(h, p, z, precision)

    h = rms_norm(x, p["ffn_norm"]["scale"], z["eps"])
    if "router" not in p:
        dense = swiglu(h, p["gate"]["kernel"], p["up"]["kernel"], p["down"]["kernel"], precision)
        return x + dense, None, None
    # the router is float32 in every precision: it chooses, it is no
    # product of the configuration's stated precision
    scores = jax.nn.sigmoid(product("sd,de->se", h, p["router"]["kernel"], "f32"))
    _, chosen = jax.lax.top_k(scores + p["router"]["bias"].astype(jnp.float32), z["top_k"])
    picked = jnp.take_along_axis(scores, chosen, axis=-1)
    weights = z["scaling"] * picked / (picked.sum(axis=-1, keepdims=True) + ROUTE_EPS)

    def one_expert(total, expert):
        index, gate, up, down = expert
        mine = jnp.where(chosen == index, weights, 0.0).sum(axis=-1)  # 0 or w_i
        return total + mine[:, None] * swiglu(h, gate, up, down, precision), None

    held = jnp.arange(z["first"], z["first"] + z["held"], dtype=chosen.dtype)
    routed, _ = jax.lax.scan(
        one_expert,
        jnp.zeros_like(x),
        (held, p["experts_gate"]["kernel"], p["experts_up"]["kernel"], p["experts_down"]["kernel"]),
    )
    return x + routed, chosen, scores


def history_forward(p, tokens, spec: dict, precision: str, refit=None):
    """(float32 logits, one a record; the experts chosen [S, k] of each
    expert layer) of ONE history's token ids [S]. ``refit`` (the weights'
    generator's, ``drivers/bulk_token_histories.py``): called with an
    expert layer's name and its scores [S, E], which no bias moves, it
    returns the selection bias the layer is then computed under."""
    mc = spec["model_config"]
    per = int(spec["tokens_per_record"])
    dims = tuple(sorted(sizes(spec).items()))
    stride = p["tok_embed"]["embedding"].shape[0] // int(spec["record_vocab_size"])
    x = p["tok_embed"]["embedding"][tokens * stride].astype(jnp.float32)
    choices = []
    for i in range(mc["depth"]):
        block = p[f"block_{i}"]
        if ("conv" in block) != (mc["layer_types"][i] == "conv"):
            raise ValueError(f"block_{i} is not the {mc['layer_types'][i]!r} the list names")
        if refit is not None and "router" in block:
            scores = layer(x, block, dims=dims, precision=precision)[2]
            bias = refit(f"block_{i}", scores)
            block = {**block, "router": {**block["router"], "bias": bias}}
        x, chosen, _ = layer(x, block, dims=dims, precision=precision)
        if chosen is not None:
            choices.append(chosen)
    last = rms_norm(x[per - 1 :: per], p["final_norm"]["scale"], float(spec["norm_eps"]))
    out = product("rd,do->ro", last, p["head"]["kernel"], precision)[:, 0]
    return out + p["head"]["bias"].astype(jnp.float32)[0], choices


def forward(params, cat, num, spec: dict, precision: str = "f32", refit=None):
    """(float32 logits [N], per history the chosen experts of each expert
    layer) for int32 ``cat`` [N, C] and float32 ``num`` [N, M]: every
    ``records_per_history`` consecutive rows are one history (whole
    histories are what this takes; the last may be shorter). ``refit``: as
    ``history_forward``'s."""
    p = params["params"]
    per = int(spec["records_per_history"])
    tokens = tokenize(
        jnp.asarray(cat).astype(jnp.int32), jnp.asarray(num),
        spec["schema"]["cards"], spec["num_bins"],
    )
    out, routed = [], []
    for start in range(0, cat.shape[0], per):
        history = tokens[start : start + per].reshape(-1)
        answers, choices = history_forward(p, history, spec, precision, refit)
        out.append(answers)
        routed.append(choices)
    return jnp.concatenate(out), routed


def logits(params, cat, num, spec: dict, precision: str = "f32"):
    return forward(params, cat, num, spec, precision)[0]


def held_assignments(routed, spec: dict) -> np.ndarray:
    """int64 [expert layers, experts held]: how many (token, slot) choices
    of ``forward``'s ``routed`` fell on each held expert."""
    z = sizes(spec)
    layers = len(routed[0])
    counts = np.zeros((layers, z["held"]), np.int64)
    for choices in routed:
        for i, chosen in enumerate(choices):
            local = np.asarray(chosen).reshape(-1) - z["first"]
            local = local[(local >= 0) & (local < z["held"])]
            counts[i] += np.bincount(local, minlength=z["held"])
    return counts


def predictions(params, cat, num, spec, temperature, precision="f32", block_rows=None):
    """Served probabilities for host arrays of WHOLE histories, one history
    at a time (``block_rows`` is the family interface's; a history is the
    block here)."""
    del block_rows
    return np.asarray(
        served_probability(logits(params, cat, num, spec, precision), temperature),
        np.float32,
    )
