"""Plain reference of the ``exaone_moe`` family: credit records as 48-token
sentences, an account's history as their concatenation, a K-EXAONE-style
sparse decoder (causal grouped-query attention in every layer, over a
sliding window or over every key so far by the published ``layer_types``
list; one leading dense SwiGLU layer, then routed experts beside one
shared expert), one logit a record.

Written from the configuration file (``source_config``, ``assumed``,
``departures``) and the layer's equations, not from the program's code
paths: no flax, no kernels, no sort, no grouped products, no band and no
blocks of queries: a window is a MASK over a whole history's scores.
float32 ``jax.numpy`` with every matrix product at ``highest`` precision
(``common.product``). The parameters may be stored in bfloat16; a leaf is
widened where it is used (exact), one layer at a time and, in the expert
layer, one expert at a time, and attention goes one query head at a time
(a row of scores is summed in one order whatever the blocks), so that a
3,072-token history fits beside 11.7 GB of weights. A layer is one jitted
function, called once a layer and history.

One layer l, x in R^{S x d}, float32 throughout; RMSNorm(x) = x *
rsqrt(mean(x^2) + eps) * w:

1. h = RMSNorm(x). q = h W_q in ``num_attention_heads`` heads of
   ``head_dim`` (stated apart from d: heads * head_dim need not be d), k =
   h W_k and v = h W_v in ``num_key_value_heads`` heads of the same width;
   each head's q and k through an RMSNorm over the head's width (one
   weight vector for all heads). Where ``layer_types[l]`` is
   "sliding_attention": RoPE on q and k (rotate-half pairs (i, i +
   width/2), inverse frequencies theta^(-i / (width/2)), positions
   0..S-1), and query i sees keys j with i - ``sliding_window`` < j <= i.
   Where it is "full_attention": no RoPE, and query i sees every j <= i.
   Query head i reads key/value head i // (heads / kv heads); scale
   head_dim^-0.5; x <- x + concat(heads) W_o.
2. h = RMSNorm(x). Layers before ``first_k_dense_replace``: x <- x +
   W_down(silu(W_gate h) * W_up h). After: s = sigmoid(h W_g); the k
   experts chosen are top_k(s + b), b the selection bias (it chooses, it
   never weighs; ``n_group`` 1: no group limit); w_i =
   routed_scaling_factor * s_i / (sum over the chosen of s + 1e-20); x <-
   x + sum_{i chosen AND first_expert <= i < first_expert + held} w_i
   E_i(h) + E_shared(h), every E a SwiGLU of width
   ``moe_intermediate_size``; the shared expert is whole whatever share
   of the routed ones is held.

Read-out: final RMSNorm at each record's last token, head (d -> 1, with a
bias). The reference computes every layer at every position; the program
may skip what no answer needs (``departures``).

Departures from the published description, each also in the configuration
file: the read-out in place of the 153,600-way head; no multi-token
prediction layer; token t of the record's own vocabulary reads row t *
(rows // V) of the embedding; a routed expert's output is summed in
float32, unrounded.
"""

from __future__ import annotations

import functools

import jax
import jax.numpy as jnp
import numpy as np

from .bert import tokenize
from .common import product, served_probability
from .kimi_k2 import rms_norm, swiglu
from .lfm2_moe import rotary

ROUTE_EPS = 1e-20


def attention_head(q, k, v, scale: float, window: int, precision: str):
    """ONE head: q, k [S, e], v [S, e] -> [S, e]; query i sees keys j with
    i - window < j <= i (``window`` 0: every j <= i)."""
    seq = q.shape[0]
    scores = scale * product("qe,ke->qk", q, k, precision)
    at = np.arange(seq)
    seen = at[None, :] <= at[:, None]
    if window:
        seen &= at[None, :] > at[:, None] - window
    scores = jnp.where(seen, scores, -jnp.inf)
    return product("qk,kd->qd", jax.nn.softmax(scores, axis=-1), v, precision)


def grouped_attention(h, p, z: dict, window: int, precision: str):
    """Causal grouped-query attention of one history h [S, d], one query
    head at a time against the key/value head it shares; ``window`` 0 is a
    full layer (unturned), else a sliding one (turned)."""
    seq = h.shape[0]
    heads, kv_heads, width = z["heads"], z["kv_heads"], z["head_dim"]
    q = product("sd,df->sf", h, p["q"]["kernel"], precision).reshape(seq, heads, width)
    k = product("sd,df->sf", h, p["k"]["kernel"], precision).reshape(seq, kv_heads, width)
    v = product("sd,df->sf", h, p["v"]["kernel"], precision).reshape(seq, kv_heads, width)
    q = rms_norm(q, p["q_norm"]["scale"], z["eps"])
    k = rms_norm(k, p["k_norm"]["scale"], z["eps"])
    if window:
        q, k = rotary(q, z["theta"]), rotary(k, z["theta"])
    shared = np.arange(heads) // (heads // kv_heads)  # the key/value head of each query head
    per_head = jax.lax.map(
        lambda a: attention_head(*a, scale=width**-0.5, window=window, precision=precision),
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
        "head_dim": mc["head_dim"],
        "window": mc["attn_window"],
        "eps": float(spec["rms_norm_eps"]),
        "theta": float(mc["rope_theta"]),
        "top_k": mc["experts_per_token"],
        "first": mc["first_expert"],
        "held": mc["experts_held"] or mc["num_experts"],
        "scaling": float(spec["routed_scaling_factor"]),
    }


@functools.partial(jax.jit, static_argnames=("dims", "sliding", "precision"))
def layer(x, p, *, dims: tuple, sliding: bool, precision: str):
    """One decoder layer on ONE history x [S, d] -> (x, the experts each
    token chose [S, k], every expert's score [S, E]), the last two ``None``
    for a dense layer."""
    z = dict(dims)
    h = rms_norm(x, p["attn_norm"]["scale"], z["eps"])
    x = x + grouped_attention(h, p, z, z["window"] if sliding else 0, precision)

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
    shared = swiglu(
        h, p["shared_gate"]["kernel"], p["shared_up"]["kernel"], p["shared_down"]["kernel"], precision
    )
    return x + routed + shared, chosen, scores


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
        kind = mc["layer_types"][i]
        if kind not in ("sliding_attention", "full_attention"):
            raise ValueError(f"layer {i} is a {kind!r}")
        if ("router" in block) != (i >= mc["dense_layers"]):
            raise ValueError(f"block_{i} is not the FFN that dense_layers names")
        run = functools.partial(
            layer, dims=dims, sliding=kind == "sliding_attention", precision=precision
        )
        if refit is not None and "router" in block:
            bias = refit(f"block_{i}", run(x, block)[2])
            block = {**block, "router": {**block["router"], "bias": bias}}
        x, chosen, _ = run(x, block)
        if chosen is not None:
            choices.append(chosen)
    last = rms_norm(x[per - 1 :: per], p["final_norm"]["scale"], float(spec["rms_norm_eps"]))
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
