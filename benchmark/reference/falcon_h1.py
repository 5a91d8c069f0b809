"""Plain reference of the ``falcon_h1`` family: credit records as 48-token
sentences, an account's history as their concatenation, a Falcon-H1-style
hybrid decoder (in every layer a Mamba-2 state-space mixer and causal
grouped-query attention read one normed input and are summed, under the
configuration's muP multipliers; then a dense SwiGLU), one logit a record.

Written from the configuration file (``source_config``, ``assumed``,
``departures``) and the layer's equations, not from the program's code
paths: no flax, no kernels, no chunks. The state-space mixer is its
RECURRENCE, one position after another in a ``lax.scan`` that carries the
state (the program computes a chunked regrouping of it; the two share no
algebra); the convolution is four shifted adds; attention is one masked
softmax a head. float32 ``jax.numpy`` with every matrix product at
``highest`` precision (``common.product``). The parameters may be stored
in bfloat16; a leaf is widened where it is used (exact). One history at a
time, a layer one jitted function, attention one query head at a time, so
that a 3,072-token history fits beside 7.8 GB of weights.

One layer, x in R^{S x d}, float32 throughout; RMSNorm(x) = x *
rsqrt(mean(x^2) + eps) * w; m.* the configuration's multipliers:

1. h = RMSNorm(x); x <- x + m.ssm_out * SSM(m.ssm_in * h) + m.attention_out
   * ATT(m.attention_in * h): both mixers read the same h.
2. ATT(u): q = u W_q in ``heads`` heads of ``head_dim``, k = m.key * (u
   W_k) and v = u W_v in ``kv_heads`` heads; no head norm; RoPE on q and k
   (rotate-half pairs (i, i + width/2), inverse frequencies theta^(-i /
   (width/2)), positions 0..S-1); query i sees every key j <= i at scale
   head_dim^-0.5; query head i reads key/value head i // (heads / kv
   heads); concat(heads) W_o.
3. SSM(u): (z | xBC | dt) = (u W_in) * muP, muP the vector that holds
   ``ssm_multipliers[0..4]`` on the columns of z, x, B, C, dt. xBC <-
   silu(conv(xBC) + bias): depthwise, causal, ``conv_width`` taps, zeros
   left of position 0. x [S, H, P], B and C [S, G, N] (head h reads group
   h // (H / G)). dt <- softplus(dt + dt_bias), A = -exp(A_log), one a
   head. With the state H_h [P, N] zero before position 0:
   H_t = exp(dt_t A) H_{t-1} + (dt_t x_t) B_t^T;  y_t = H_t C_t + D x_t.
   Then y <- y * silu(z) (the gate FIRST: ``mamba_norm_before_gate``
   false), RMSNorm over each of the G groups of channels with one weight
   a channel, and W_out.
4. u = RMSNorm(x); x <- x + m.mlp[1] * W_down(silu(m.mlp[0] * W_gate u) *
   W_up u).

Read-out: final RMSNorm at each record's last token, head (d -> 1, with a
bias). The reference computes every layer at every position; the program
may skip what no answer needs (``departures``).

Under the control's precision (``fp8``) both operands of every matrix
product are rounded, the recurrence's two among them (``dt x`` against
``B``, the state against ``C``); decays, sums, norms and gates stay
float32.
"""

from __future__ import annotations

import functools

import jax
import jax.numpy as jnp
import numpy as np

from .bert import tokenize
from .common import product, served_probability
from .kimi_k2 import attention_head, rms_norm
from .lfm2_moe import rotary


def attention(u, p, z: dict, precision: str):
    """Causal grouped-query attention of one history u [S, d], unnormed
    heads, scaled keys, turned; one query head at a time."""
    seq = u.shape[0]
    heads, kv_heads, width = z["heads"], z["kv_heads"], z["head_dim"]
    q = product("sd,df->sf", u, p["q"]["kernel"], precision).reshape(seq, heads, width)
    k = product("sd,df->sf", u, p["k"]["kernel"], precision).reshape(seq, kv_heads, width)
    v = product("sd,df->sf", u, p["v"]["kernel"], precision).reshape(seq, kv_heads, width)
    q, k = rotary(q, z["theta"]), rotary(z["key"] * k, z["theta"])
    shared = np.arange(heads) // (heads // kv_heads)  # the key/value head of each query head
    per_head = jax.lax.map(
        lambda a: attention_head(*a, scale=width**-0.5, precision=precision),
        (q.transpose(1, 0, 2), k.transpose(1, 0, 2)[shared], v.transpose(1, 0, 2)[shared]),
    )
    mixed = per_head.transpose(1, 0, 2).reshape(seq, heads * width)
    return product("sf,fd->sd", mixed, p["o"]["kernel"], precision)


def convolution(x, taps, bias):
    """silu(conv(x) + bias) of x [S, c]: tap j of ``taps`` [L, c] reads the
    position L - 1 - j before, zeros left of position 0."""
    seq, width = x.shape[0], taps.shape[0]
    mixed = jnp.zeros_like(x)
    for j in range(width):
        back = width - 1 - j
        shifted = jnp.concatenate([jnp.zeros((back, x.shape[1])), x[: seq - back]])
        mixed = mixed + taps[j].astype(jnp.float32) * shifted
    return jax.nn.silu(mixed + bias.astype(jnp.float32))


def recurrence(x, dt, a, b, c, skip, precision: str):
    """The selective state-space recurrence of one history, one position
    after another: x [S, H, P], dt [S, H] (positive), a [H] (negative), b
    and c [S, H, N] (each head its group's), skip [H] -> y [S, H, P]."""

    def position(state, now):
        x_t, dt_t, b_t, c_t = now
        fed = product("hp,hn->hpn", dt_t[:, None] * x_t, b_t, precision)
        state = jnp.exp(dt_t * a)[:, None, None] * state + fed
        return state, product("hpn,hn->hp", state, c_t, precision) + skip[:, None] * x_t

    heads, width = x.shape[1:]
    start = jnp.zeros((heads, width, b.shape[-1]), jnp.float32)
    return jax.lax.scan(position, start, (x, dt, b, c))[1]


def state_space(u, p, z: dict, precision: str):
    """The Mamba-2 mixer of one history u [S, d]."""
    seq = u.shape[0]
    inner, heads, groups, state = z["ssm_dim"], z["ssm_heads"], z["ssm_groups"], z["ssm_state"]
    shared = groups * state
    parts = (inner, inner, shared, shared, heads)
    mup = np.repeat(np.asarray(z["ssm_multipliers"], np.float32), parts)
    projected = product("sd,df->sf", u, p["in_proj"]["kernel"], precision) * mup
    gate, xbc, dt = jnp.split(projected, [inner, 2 * inner + 2 * shared], axis=-1)
    xbc = convolution(xbc, p["conv"]["kernel"], p["conv"]["bias"])
    x, b, c = jnp.split(xbc, [inner, inner + shared], axis=-1)
    of_head = np.arange(heads) // (heads // groups)  # the group of each head
    y = recurrence(
        x.reshape(seq, heads, inner // heads),
        jax.nn.softplus(dt + p["dt_bias"]["bias"].astype(jnp.float32)),
        -jnp.exp(p["a_log"]["bias"].astype(jnp.float32)),
        b.reshape(seq, groups, state)[:, of_head],
        c.reshape(seq, groups, state)[:, of_head],
        p["skip"]["scale"].astype(jnp.float32),
        precision,
    )
    gated = (y.reshape(seq, inner) * jax.nn.silu(gate)).reshape(seq, groups, inner // groups)
    normed = gated * jax.lax.rsqrt(jnp.mean(gated * gated, axis=-1, keepdims=True) + z["eps"])
    normed = normed.reshape(seq, inner) * p["ssm_norm"]["scale"].astype(jnp.float32)
    return product("sf,fd->sd", normed, p["out_proj"]["kernel"], precision)


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
        "eps": float(spec["rms_norm_eps"]),
        "theta": float(mc["rope_theta"]),
        "key": float(mc["key_multiplier"]),
        "attention_in": float(mc["attention_in_multiplier"]),
        "attention_out": float(mc["attention_out_multiplier"]),
        "ssm_in": float(mc["ssm_in_multiplier"]),
        "ssm_out": float(mc["ssm_out_multiplier"]),
        "ssm_multipliers": tuple(float(m) for m in mc["ssm_multipliers"]),
        "mlp_multipliers": tuple(float(m) for m in mc["mlp_multipliers"]),
    }


@functools.partial(jax.jit, static_argnames=("dims", "precision"))
def layer(x, p, *, dims: tuple, precision: str):
    """One decoder layer on ONE history x [S, d]."""
    z = dict(dims)
    h = rms_norm(x, p["input_norm"]["scale"], z["eps"])
    x = (
        x
        + z["ssm_out"] * state_space(z["ssm_in"] * h, p, z, precision)
        + z["attention_out"] * attention(z["attention_in"] * h, p, z, precision)
    )
    u = rms_norm(x, p["ffn_norm"]["scale"], z["eps"])
    on_gate, on_output = z["mlp_multipliers"]
    inner = jax.nn.silu(
        on_gate * product("sd,df->sf", u, p["gate"]["kernel"], precision)
    ) * product("sd,df->sf", u, p["up"]["kernel"], precision)
    return x + on_output * product("sf,fd->sd", inner, p["down"]["kernel"], precision)


def history_forward(p, tokens, spec: dict, precision: str):
    """float32 logits, one a record, of ONE history's token ids [S]."""
    mc = spec["model_config"]
    per = int(spec["tokens_per_record"])
    dims = tuple(sorted(sizes(spec).items()))
    stride = p["tok_embed"]["embedding"].shape[0] // int(spec["record_vocab_size"])
    x = p["tok_embed"]["embedding"][tokens * stride].astype(jnp.float32)
    x = x * float(mc["embedding_multiplier"])
    for i in range(mc["depth"]):
        x = layer(x, p[f"block_{i}"], dims=dims, precision=precision)
    last = rms_norm(x[per - 1 :: per], p["final_norm"]["scale"], float(spec["rms_norm_eps"]))
    out = product("rd,do->ro", last, p["head"]["kernel"], precision)[:, 0]
    return out + p["head"]["bias"].astype(jnp.float32)[0]


def forward(params, cat, num, spec: dict, precision: str = "f32"):
    """float32 logits [N] for int32 ``cat`` [N, C] and float32 ``num`` [N,
    M]: every ``records_per_history`` consecutive rows are one history
    (whole histories are what this takes; the last may be shorter)."""
    p = params["params"]
    per = int(spec["records_per_history"])
    tokens = tokenize(
        jnp.asarray(cat).astype(jnp.int32), jnp.asarray(num),
        spec["schema"]["cards"], spec["num_bins"],
    )
    return jnp.concatenate([
        history_forward(p, tokens[start : start + per].reshape(-1), spec, precision)
        for start in range(0, cat.shape[0], per)
    ])


logits = forward


def predictions(params, cat, num, spec, temperature, precision="f32", block_rows=None):
    """Served probabilities for host arrays of WHOLE histories, one history
    at a time (``block_rows`` is the family interface's; a history is the
    block here)."""
    del block_rows
    return np.asarray(
        served_probability(forward(params, cat, num, spec, precision), temperature),
        np.float32,
    )
