"""Plain reference of the ``evabyte`` family: credit records rendered as
256-byte text lines, an account's history as their concatenation, EvaByte's
decoder layer (EVA chunked linear attention), one logit a record.

Written from the configuration file (``record_format``, ``assumed``,
``departures``) and the layer's equations, not from the program's code
paths: no flax, no kernels; float32 ``jax.numpy`` with every matrix
product at ``highest`` precision (``common.product``). Attention is the
equations with the scores of ONE head and ONE window at a time, so that a
16,384-byte history fits beside 6.5 GB of weights; a layer is one jitted
function, called once a layer and history.

One layer, x in R^{S x d}, float32 throughout, s = head_dim^-0.5:

1. h = RMSNorm(x) with weight (1 + g), eps 1e-5; q, k, v = h W_q, h W_k,
   h W_v (no bias); RoPE (rotate-half, theta) on q and k.
2. Per head and chunk j (positions c j .. c j + c - 1):
   alpha_{j,m} = softmax_m(s k_m . phi); k~_j = sum_m alpha k_m + mu;
   v~_j = sum_m alpha v_m.
3. Query i in window W(i) = floor(i / w): local keys m, W(m) = W(i), m <=
   i; remote chunks j < (w / c) W(i) by their summaries; ONE softmax over
   both sets:
   o_i = (sum_local e^{s q.k_m} v_m + sum_remote e^{s q.k~_j} v~_j)
         / (sum_local e^{s q.k_m} + sum_remote e^{s q.k~_j}).
4. x <- x + o W_o; h' = RMSNorm(x); x <- x + W_down(silu(W_gate h') *
   W_up h').

Read-out: final RMSNorm at each record's last byte, head (d -> 1, with a
bias). The reference computes every layer at every position; the program
may skip what no answer needs (``departures``).
"""

from __future__ import annotations

import functools

import jax
import jax.numpy as jnp
import numpy as np

from .common import product, served_probability

RMS_EPS = 1e-5


def render(cat, num, spec: dict):
    """int32 byte values [N, record_bytes] of N encoded records, by the
    configuration's ``record_format``."""
    fmt = spec["record_format"]
    n = cat.shape[0]
    columns = []

    def digits(value, place):
        return ord("0") + (value // place) % 10

    tenths = jnp.clip(jnp.round(jnp.float32(10.0) * num), -99, 99).astype(jnp.int32)
    for j, name in enumerate(fmt["field_names"]):
        columns += [jnp.full((n,), b, jnp.int32) for b in name.encode()]
        columns.append(jnp.full((n,), ord(fmt["separator"]), jnp.int32))
        if j < cat.shape[1]:
            ident = cat[:, j].astype(jnp.int32)
            columns += [digits(ident, 100), digits(ident, 10), digits(ident, 1)]
        else:
            t = tenths[:, j - cat.shape[1]]
            columns.append(jnp.where(t >= 0, ord("+"), ord("-")))
            columns += [digits(jnp.abs(t), 10), digits(jnp.abs(t), 1)]
        columns.append(jnp.full((n,), ord(fmt["field_end"]), jnp.int32))
    columns += [jnp.full((n,), b, jnp.int32) for b in fmt["record_end"].encode()]
    if len(columns) != spec["record_bytes"]:
        raise ValueError(f"record_format gives {len(columns)} bytes a record")
    return jnp.stack(columns, axis=1)


def rms_norm(x, g):
    return x / jnp.sqrt(jnp.mean(x * x, axis=-1, keepdims=True) + RMS_EPS) * (1.0 + g)


def rotary(x, theta: float):
    """x [S, H, D]: position p turns each pair (x_i, x_{i + D/2}) by the
    angle p * theta^(-i / (D/2))."""
    seq, _, head_dim = x.shape
    half = head_dim // 2
    inv_freq = np.asarray(
        [float(theta) ** (-i / half) for i in range(half)], np.float64
    ).astype(np.float32)
    angle = jnp.arange(seq, dtype=jnp.float32)[:, None] * jnp.asarray(inv_freq)[None, :]
    cos, sin = jnp.cos(angle)[:, None, :], jnp.sin(angle)[:, None, :]
    first, second = x[..., :half], x[..., half:]
    return jnp.concatenate(
        [first * cos - second * sin, second * cos + first * sin], axis=-1
    )


def summaries(k, v, phi, mu, chunk: int):
    """k~, v~ [J, D] of ONE head's k, v [S, D] (S whole chunks)."""
    seq, head_dim = k.shape
    kc = k.reshape(seq // chunk, chunk, head_dim)
    vc = v.reshape(seq // chunk, chunk, head_dim)
    # sums of float32 products, no matrix unit: exact at any device default
    alpha = jax.nn.softmax((kc * phi).sum(axis=-1) * head_dim**-0.5, axis=1)[..., None]
    return (alpha * kc).sum(axis=1) + mu, (alpha * vc).sum(axis=1)


def eva_head(q, k, v, phi, mu, window: int, chunk: int, precision: str):
    """ONE head's attention output [S, D], one window at a time."""
    seq, head_dim = q.shape
    scale = head_dim**-0.5
    k_sum, v_sum = summaries(k, v, phi, mu, chunk)
    out = []
    for start in range(0, seq, window):
        stop = min(start + window, seq)
        qw = q[start:stop]
        seen = (start // window) * (window // chunk)  # chunks wholly in past windows
        local = scale * product("qd,kd->qk", qw, k[start:stop], precision)
        causal = np.tril(np.ones((stop - start, stop - start), bool))
        local = jnp.where(causal, local, -jnp.inf)
        remote = scale * product("qd,jd->qj", qw, k_sum[:seen], precision)
        top = jnp.maximum(local.max(axis=1), remote.max(axis=1, initial=-jnp.inf))
        e_local = jnp.exp(local - top[:, None])
        e_remote = jnp.exp(remote - top[:, None])
        numerator = product("qk,kd->qd", e_local, v[start:stop], precision) + product(
            "qj,jd->qd", e_remote, v_sum[:seen], precision
        )
        out.append(numerator / (e_local.sum(axis=1) + e_remote.sum(axis=1))[:, None])
    return jnp.concatenate(out, axis=0)


@functools.partial(jax.jit, static_argnames=("heads", "window", "chunk", "theta", "precision"))
def layer(x, p, *, heads: int, window: int, chunk: int, theta: float, precision: str):
    """One decoder layer on ONE history x [S, d]."""
    seq, dim = x.shape
    head_dim = dim // heads
    h = rms_norm(x, p["attn_norm"]["scale"])
    qkv = product("sd,dthe->sthe", h, p["qkv"]["kernel"], precision)
    q, k, v = rotary(qkv[:, 0], theta), rotary(qkv[:, 1], theta), qkv[:, 2]
    per_head = jax.lax.map(
        lambda a: eva_head(*a, window=window, chunk=chunk, precision=precision),
        (
            q.transpose(1, 0, 2),
            k.transpose(1, 0, 2),
            v.transpose(1, 0, 2),
            p["adaptive_phi"]["bias"],
            p["adaptive_mu_k"]["bias"],
        ),
    )  # [H, S, D], one head at a time
    x = x + product("hse,hed->sd", per_head, p["out"]["kernel"], precision)
    h = rms_norm(x, p["ffn_norm"]["scale"])
    gate = product("sd,df->sf", h, p["gate"]["kernel"], precision)
    up = product("sd,df->sf", h, p["up"]["kernel"], precision)
    return x + product("sf,fd->sd", jax.nn.silu(gate) * up, p["down"]["kernel"], precision)


def history_logits(p, tokens, spec: dict, precision: str):
    """float32 logits, one a record, of ONE history's token ids [S]."""
    mc = spec["model_config"]
    x = p["tok_embed"]["embedding"][tokens]
    for i in range(mc["depth"]):
        x = layer(
            x, p[f"block_{i}"], heads=mc["heads"], window=mc["attn_window"],
            chunk=mc["attn_chunk"], theta=float(mc["rope_theta"]), precision=precision,
        )
    last = x[spec["record_bytes"] - 1 :: spec["record_bytes"]]  # each record's last byte
    last = rms_norm(last, p["final_norm"]["scale"])
    return product("rd,do->ro", last, p["head"]["kernel"], precision)[:, 0] + p["head"]["bias"][0]


def logits(params, cat, num, spec: dict, precision: str = "f32"):
    """float32 logits [N] for int32 ``cat`` [N, C] and float32 ``num``
    [N, M]: every ``records_per_history`` consecutive rows are one history
    (whole histories are what this takes; the last may be shorter)."""
    p = params["params"]
    per = int(spec["records_per_history"])
    tokens = spec["byte_offset"] + render(jnp.asarray(cat), jnp.asarray(num), spec)
    out = []
    for start in range(0, cat.shape[0], per):
        history = tokens[start : start + per].reshape(-1)
        out.append(history_logits(p, history, spec, precision))
    return jnp.concatenate(out)


def predictions(params, cat, num, spec, temperature, precision="f32", block_rows=None):
    """Served probabilities for host arrays of WHOLE histories, one history
    at a time (``block_rows`` is the family interface's; a history is the
    block here)."""
    del block_rows
    return np.asarray(
        served_probability(logits(params, cat, num, spec, precision), temperature),
        np.float32,
    )
