"""Plain reference of the ``nemotron_h`` family: credit records as 48-token
sentences, an account's history as their concatenation, a Nemotron-H-style
hybrid decoder (every layer ONE kind by the published
``hybrid_override_pattern``: a Mamba-2 state-space mixer, routed ReLU²
experts beside a shared one, or causal grouped-query attention with no
rotary positions), one logit a record.

Written from the configuration file (``hybrid_override_pattern``,
``routed_scaling_factor``, ``layer_norm_epsilon``, ``assumed``, ``departures``)
and the layer's equations, not from the program's code paths: no flax, no
kernels, no chunks, no sort and no grouped products. The mixer is its
RECURRENCE, one position after another (``reference/falcon_h1.py
state_space``, with every multiplier 1); attention is one masked softmax
a head; the routed experts run DENSELY, every held expert over every
token, masked by the routing. float32 ``jax.numpy`` with every matrix
product at ``highest`` precision (``common.product``). The parameters may
be stored in bfloat16; a leaf is widened where it is used (exact), one
layer at a time and, in an expert layer, one expert at a time, and
attention goes one query head at a time, so that a 3,072-token history
fits beside 7.9 GB of weights. A layer is one jitted function by its
kind, called once a layer and history.

One layer l, x in R^{S x d}, float32 throughout; RMSNorm(x) = x *
rsqrt(mean(x^2) + eps) * w; the layer's kind is letter l of the pattern:

- every kind: h = RMSNorm(x); x <- x + mixer(h).
- ``M``: (z | xBC | dt) = h W_in; xBC <- silu(conv(xBC) + bias)
  (depthwise, causal, ``conv_width`` taps, zeros left of position 0); x
  [S, H, P], B and C [S, G, N] (head h reads group h // (H / G)); dt <-
  softplus(dt + dt_bias) (no clamp), A = -exp(A_log); with the state H_h
  [P, N] zero before position 0: H_t = exp(dt_t A) H_{t-1} + (dt_t x_t)
  B_t^T, y_t = H_t C_t + D x_t; y <- y * silu(z), then RMSNorm over each
  of the G groups of channels, one weight a channel; W_out.
- ``E``: s = sigmoid(h W_g) (float32 in every precision: it chooses); the
  k experts chosen are top_k(s + b), b the selection bias (it chooses, it
  never weighs; ``n_group`` 1: no group limit); w_i =
  routed_scaling_factor * s_i / (sum over the chosen of s + 1e-20); x <- x
  + sum_{i chosen AND first_expert <= i < first_expert + held} w_i E_i(h)
  + E_shared(h), E(h) = W_down relu(W_up h)^2, a routed expert of
  ``moe_intermediate_size``, the shared one of
  ``moe_shared_expert_intermediate_size``, whole whatever share of the
  routed ones is held.
- ``*``: q = h W_q in ``num_attention_heads`` heads of ``head_dim``, k =
  h W_k and v = h W_v in ``num_key_value_heads`` heads; no head norm, NO
  rotary positions; query i sees every key j <= i at scale head_dim^-0.5;
  query head i reads key/value head i // (heads / kv heads); concat(heads)
  W_o.

Read-out: final RMSNorm at each record's last token, head (d -> 1, with a
bias). The reference computes every layer at every position; the program
may skip what no answer needs (``departures``).

Departures from the published description, each also in the configuration
file: the read-out in place of the 131,072-way head; the residual stream
in float32 (the source's ``residual_in_fp32`` is false); token t of the
record's own vocabulary reads row t * (rows // V) of the embedding; a
routed expert's output is summed in float32, unrounded.

Under the control's precision (``fp8``) both operands of every matrix
product but the router's are rounded, the recurrence's two among them;
decays, sums, norms, gates and the router stay float32.
"""

from __future__ import annotations

import functools

import jax
import jax.numpy as jnp
import numpy as np

from .bert import tokenize
from .common import product, served_probability
from .falcon_h1 import state_space
from .kimi_k2 import attention_head, rms_norm

ROUTE_EPS = 1e-20
KINDS = {"M": "mamba", "E": "moe", "*": "attention"}


def kinds(spec: dict) -> list[str]:
    """The kind of each layer the configuration runs, from the pattern."""
    mc = spec["model_config"]
    return [KINDS[letter] for letter in spec["hybrid_override_pattern"][: mc["depth"]]]


def attention(h, p, z: dict, precision: str):
    """Causal grouped-query attention of one history h [S, d], unnormed,
    unturned heads; one query head at a time against the key/value head
    it shares."""
    seq = h.shape[0]
    heads, kv_heads, width = z["heads"], z["kv_heads"], z["head_dim"]
    q = product("sd,df->sf", h, p["q"]["kernel"], precision).reshape(seq, heads, width)
    k = product("sd,df->sf", h, p["k"]["kernel"], precision).reshape(seq, kv_heads, width)
    v = product("sd,df->sf", h, p["v"]["kernel"], precision).reshape(seq, kv_heads, width)
    shared = np.arange(heads) // (heads // kv_heads)  # the key/value head of each query head
    per_head = jax.lax.map(
        lambda a: attention_head(*a, scale=width**-0.5, precision=precision),
        (q.transpose(1, 0, 2), k.transpose(1, 0, 2)[shared], v.transpose(1, 0, 2)[shared]),
    )
    mixed = per_head.transpose(1, 0, 2).reshape(seq, heads * width)
    return product("sf,fd->sd", mixed, p["o"]["kernel"], precision)


def relu2_expert(h, up, down, precision: str):
    """down(relu(h up)^2) of h [S, d]."""
    lifted = product("sd,df->sf", h, up, precision)
    return product("sf,fd->sd", jnp.square(jax.nn.relu(lifted)), down, precision)


def sizes(spec: dict) -> dict:
    """The sizes and constants a layer needs, from the configuration."""
    mc = spec["model_config"]
    return {
        "heads": mc["heads"],
        "kv_heads": mc["kv_heads"],
        "head_dim": mc["head_dim"],
        "ssm_dim": mc["ssm_dim"],
        "ssm_heads": mc["ssm_heads"],
        "ssm_groups": mc["ssm_groups"],
        "ssm_state": mc["ssm_state"],
        "ssm_multipliers": (1.0, 1.0, 1.0, 1.0, 1.0),  # the source has none
        "eps": float(spec["layer_norm_epsilon"]),
        "top_k": mc["experts_per_token"],
        "first": mc["first_expert"],
        "held": mc["experts_held"] or mc["num_experts"],
        "scaling": float(spec["routed_scaling_factor"]),
    }


@functools.partial(jax.jit, static_argnames=("dims", "kind", "precision"))
def layer(x, p, *, dims: tuple, kind: str, precision: str):
    """One layer on ONE history x [S, d] -> (x, the experts each token
    chose [S, k], every expert's score [S, E]), the last two ``None`` but in
    an expert layer."""
    z = dict(dims)
    h = rms_norm(x, p["norm"]["scale"], z["eps"])
    if kind == "mamba":
        return x + state_space(h, p, z, precision), None, None
    if kind == "attention":
        return x + attention(h, p, z, precision), None, None
    # the router is float32 in every precision: it chooses, it is no
    # product of the configuration's stated precision
    scores = jax.nn.sigmoid(product("sd,de->se", h, p["router"]["kernel"], "f32"))
    _, chosen = jax.lax.top_k(scores + p["router"]["bias"].astype(jnp.float32), z["top_k"])
    picked = jnp.take_along_axis(scores, chosen, axis=-1)
    weights = z["scaling"] * picked / (picked.sum(axis=-1, keepdims=True) + ROUTE_EPS)

    def one_expert(total, expert):
        index, up, down = expert
        mine = jnp.where(chosen == index, weights, 0.0).sum(axis=-1)  # 0 or w_i
        return total + mine[:, None] * relu2_expert(h, up, down, precision), None

    held = jnp.arange(z["first"], z["first"] + z["held"], dtype=chosen.dtype)
    routed, _ = jax.lax.scan(
        one_expert, jnp.zeros_like(x),
        (held, p["experts_up"]["kernel"], p["experts_down"]["kernel"]),
    )
    shared = relu2_expert(h, p["shared_up"]["kernel"], p["shared_down"]["kernel"], precision)
    return x + routed + shared, chosen, scores


def history_forward(p, tokens, spec: dict, precision: str, refit=None):
    """(float32 logits, one a record; the experts chosen [S, k] of each
    expert layer) of ONE history's token ids [S]. ``refit`` (the weights'
    generator's, ``drivers/bulk_token_histories.py``): called with an
    expert layer's name and its scores [S, E], which no bias moves, it
    returns the selection bias the layer is then computed under."""
    per = int(spec["tokens_per_record"])
    dims = tuple(sorted(sizes(spec).items()))
    stride = p["tok_embed"]["embedding"].shape[0] // int(spec["record_vocab_size"])
    x = p["tok_embed"]["embedding"][tokens * stride].astype(jnp.float32)
    choices = []
    for i, kind in enumerate(kinds(spec)):
        block = p[f"block_{i}"]
        if ("router" in block) != (kind == "moe") or ("a_log" in block) != (kind == "mamba"):
            raise ValueError(f"block_{i} is not the {kind} layer the pattern names")
        run = functools.partial(layer, dims=dims, kind=kind, precision=precision)
        if refit is not None and kind == "moe":
            bias = refit(f"block_{i}", run(x, block)[2])
            block = {**block, "router": {**block["router"], "bias": bias}}
        x, chosen, _ = run(x, block)
        if chosen is not None:
            choices.append(chosen)
    eps = float(spec["layer_norm_epsilon"])
    last = rms_norm(x[per - 1 :: per], p["final_norm"]["scale"], eps)
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
