"""Plain reference of the ``kimi_k2`` family: credit records as 48-token
sentences, an account's history as their concatenation, a DeepSeek-V3-style
decoder (multi-head latent attention; one leading dense SwiGLU layer, then
routed experts beside a shared one), one logit a record.

Written from the configuration file (``source_config``, ``assumed``,
``departures``) and the layer's equations, not from the program's code
paths: no flax, no kernels, no sort, no grouped products. float32
``jax.numpy`` with every matrix product at ``highest`` precision
(``common.product``). The parameters may be stored in bfloat16; a leaf is
widened where it is used (exact), one layer at a time and, in the expert
layer, one expert at a time, so that a 3,072-token history fits beside
10.9 GB of weights. A layer is one jitted function, called once a layer
and history.

One layer, x in R^{S x d}, float32 throughout; RMSNorm(x) = x *
rsqrt(mean(x^2) + eps) * w:

1. h = RMSNorm(x). c_q = RMSNorm(h W_qa); per head [q_nope | q_pe] = c_q
   W_qb. [c_kv | k_pe] = h W_kva with ONE k_pe a position; c_kv =
   RMSNorm(c_kv); per head [k_nope | v] = c_kv W_kvb. q = [q_nope |
   RoPE(q_pe)], k = [k_nope | RoPE(k_pe)].
2. RoPE is YaRN's (the DeepSeek-V3 reference code): inverse frequency f_i =
   theta^(-i / (r/2)) becomes f_i / factor * ramp_i + f_i * (1 - ramp_i),
   ramp the linear ramp from the correction dimension of ``beta_fast``
   turns (floor) to that of ``beta_slow`` turns (ceil) over
   ``original_max_position_embeddings``; rotate-half pairs (i, i + r/2);
   the cos/sin factor mscale / mscale_all_dim is 1. Applied at every
   length.
3. o = softmax(q k^T * scale + causal mask) v, scale = (nope + rope)^-0.5
   * (0.1 * mscale_all_dim * ln factor + 1)^2; x <- x + o W_o.
4. h = RMSNorm(x). Layers before ``first_k_dense_replace``: x <- x +
   W_down(silu(W_gate h) * W_up h). After: s = sigmoid(h W_g); the k
   experts chosen are top_k(s + b), b the selection bias; w_i =
   routed_scaling_factor * s_i / (sum over the chosen of s + 1e-20);
   x <- x + sum_{i chosen AND first_expert <= i < first_expert + held}
   w_i E_i(h) + E_shared(h), every E a SwiGLU of width
   moe_intermediate_size. What the absent experts would have added is
   left out (the chip's share of an expert-parallel layer).

Read-out: final RMSNorm at each record's last token, head (d -> 1, with a
bias). The reference computes every layer at every position; the program
may skip what no answer needs (``departures``).

Departures from the published description, each also in the configuration
file: the read-out in place of the 163,840-way head; the embedding is a
slice of ``vocab_size`` rows and token t of the record's own vocabulary
reads row t * (rows // V); the source's de-interleaving of each rotary
pair (a fixed permutation of W_qb's and W_kva's rotary columns) is left
out; a routed expert's output is summed in float32, unrounded.
"""

from __future__ import annotations

import functools
import math

import jax
import jax.numpy as jnp
import numpy as np

from .bert import tokenize
from .common import product, served_probability

ROUTE_EPS = 1e-20


def rms_norm(x, w, eps: float):
    return x * jax.lax.rsqrt(jnp.mean(x * x, axis=-1, keepdims=True) + eps) * w.astype(jnp.float32)


def yarn_inverse_frequencies(rot: int, theta: float, scaling: dict) -> np.ndarray:
    """float32 [rot / 2], by the DeepSeek-V3 reference code's
    ``yarn_find_correction_range`` and ``yarn_linear_ramp_mask``."""
    half = rot // 2
    plain = np.asarray([float(theta) ** (-i / half) for i in range(half)], np.float64)
    original = scaling["original_max_position_embeddings"]

    def correction_dim(turns):
        return rot * math.log(original / (turns * 2 * math.pi)) / (2 * math.log(theta))

    low = max(math.floor(correction_dim(scaling["beta_fast"])), 0)
    high = min(math.ceil(correction_dim(scaling["beta_slow"])), rot - 1)
    if low == high:
        high += 0.001
    ramp = np.clip((np.arange(half) - low) / (high - low), 0.0, 1.0)
    return (plain / scaling["factor"] * ramp + plain * (1.0 - ramp)).astype(np.float32)


def rotary(x, inv_freq: np.ndarray):
    """x [S, H, r]: position p turns each pair (x_i, x_{i + r/2}) by the
    angle p * inv_freq_i."""
    half = x.shape[-1] // 2
    angle = jnp.arange(x.shape[0], dtype=jnp.float32)[:, None] * jnp.asarray(inv_freq)[None, :]
    cos, sin = jnp.cos(angle)[:, None, :], jnp.sin(angle)[:, None, :]
    first, second = x[..., :half], x[..., half:]
    return jnp.concatenate(
        [first * cos - second * sin, second * cos + first * sin], axis=-1
    )


def attention_head(q, k, v, scale: float, precision: str):
    """ONE head: q, k [S, e], v [S, d] -> [S, d], causal."""
    seq = q.shape[0]
    scores = scale * product("qe,ke->qk", q, k, precision)
    scores = jnp.where(np.tril(np.ones((seq, seq), bool)), scores, -jnp.inf)
    return product("qk,kd->qd", jax.nn.softmax(scores, axis=-1), v, precision)


def swiglu(h, gate, up, down, precision: str):
    inner = jax.nn.silu(product("sd,df->sf", h, gate, precision)) * product(
        "sd,df->sf", h, up, precision
    )
    return product("sf,fd->sd", inner, down, precision)


def sizes(spec: dict) -> dict:
    """The sizes and constants a layer needs, from the configuration."""
    mc = spec["model_config"]
    scaling = spec["rope_scaling"]
    mscale = 0.1 * scaling["mscale_all_dim"] * math.log(scaling["factor"]) + 1.0
    qk = mc["qk_nope_head_dim"] + mc["qk_rope_head_dim"]
    return {
        "heads": mc["heads"],
        "kv_rank": mc["kv_lora_rank"],
        "nope": mc["qk_nope_head_dim"],
        "rot": mc["qk_rope_head_dim"],
        "wide": mc["v_head_dim"],
        "eps": float(spec["rms_norm_eps"]),
        "scale": qk**-0.5 * mscale * mscale,
        "top_k": mc["experts_per_token"],
        "first": mc["first_expert"],
        "held": mc["experts_held"] or mc["num_experts"],
        "scaling": float(spec["routed_scaling_factor"]),
        "inv_freq": tuple(
            yarn_inverse_frequencies(mc["qk_rope_head_dim"], float(mc["rope_theta"]), scaling).tolist()
        ),
    }


@functools.partial(jax.jit, static_argnames=("dims", "precision"))
def layer(x, p, *, dims: tuple, precision: str):
    """One decoder layer on ONE history x [S, d] -> (x, the experts each
    token chose [S, k], every expert's score [S, E]), the last two ``None``
    for a dense layer."""
    z = dict(dims)
    seq = x.shape[0]
    heads, nope, rot, wide = z["heads"], z["nope"], z["rot"], z["wide"]
    inv_freq = np.asarray(z["inv_freq"], np.float32)
    h = rms_norm(x, p["attn_norm"]["scale"], z["eps"])
    c_q = rms_norm(product("sd,dr->sr", h, p["q_a"]["kernel"], precision), p["q_norm"]["scale"], z["eps"])
    q = product("sr,rf->sf", c_q, p["q_b"]["kernel"], precision).reshape(seq, heads, nope + rot)
    latent = product("sd,dr->sr", h, p["kv_a"]["kernel"], precision)
    c_kv = rms_norm(latent[:, : z["kv_rank"]], p["kv_norm"]["scale"], z["eps"])
    k_pe = rotary(latent[:, None, z["kv_rank"] :], inv_freq)  # [S, 1, rot]
    kv = product("sr,rf->sf", c_kv, p["kv_b"]["kernel"], precision).reshape(seq, heads, nope + wide)
    q = jnp.concatenate([q[..., :nope], rotary(q[..., nope:], inv_freq)], axis=-1)
    k = jnp.concatenate([kv[..., :nope], jnp.broadcast_to(k_pe, (seq, heads, rot))], axis=-1)
    per_head = jax.lax.map(
        lambda a: attention_head(*a, scale=z["scale"], precision=precision),
        (q.transpose(1, 0, 2), k.transpose(1, 0, 2), kv[..., nope:].transpose(1, 0, 2)),
    )  # [H, S, wide], one head's scores at a time
    mixed = per_head.transpose(1, 0, 2).reshape(seq, heads * wide)
    x = x + product("sf,fd->sd", mixed, p["o"]["kernel"], precision)

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
        if refit is not None and "router" in block:
            scores = layer(x, block, dims=dims, precision=precision)[2]
            bias = refit(f"block_{i}", scores)
            block = {**block, "router": {**block["router"], "bias": bias}}
        x, chosen, _ = layer(x, block, dims=dims, precision=precision)
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
