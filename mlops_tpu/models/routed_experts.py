"""The routed-expert part of a sparse decoder layer, as the token-level
history scorers build it (`models/kimi_k2.py`, `models/lfm2_moe.py`,
`models/exaone_moe.py`, `models/nemotron_h.py`): the router's and the held
experts' parameters under the CALLING block's own names
(``router/{kernel,bias}``, ``experts_{gate,up,down}/kernel``), the
dropless dispatch of `ops/moe_dispatch.py`, and the routing counter.

A family differs in its block's fields (how many experts a token chooses,
which experts this process holds), in what it passes (the scaling and the
normaliser's epsilon of its router) and in its experts' form: a SwiGLU
(gate, up, down: every family but one) or, ``gated=False``, Nemotron-H's
``down(relu(up h)^2)`` (``experts_{up,down}``, no gate). The dense forms
of both run outside the routed experts are here too: `swiglu` (a dense
layer's FFN, and the shared expert of `models/kimi_k2.py` and
`models/exaone_moe.py`) and `relu2_mlp` (the shared expert of
`models/nemotron_h.py`, of a width of its own), each added unweighted by
`experts_beside_a_shared_one`.
"""

from __future__ import annotations

import jax
import jax.numpy as jnp
from flax import linen as nn

from mlops_tpu.ops import moe_dispatch
from mlops_tpu.ops.expert_products import relu2

ROUTING = "routing"  # the collection the expert layers count into


class _Stacked(nn.Module):
    """The held experts' weights of one projection, ``kernel`` ``[held,
    inputs, outputs]``."""

    experts: int
    inputs: int
    outputs: int
    param_dtype: jnp.dtype

    @nn.compact
    def __call__(self) -> jnp.ndarray:
        init = nn.initializers.lecun_normal(in_axis=-2, out_axis=-1, batch_axis=(0,))
        return self.param(
            "kernel", init, (self.experts, self.inputs, self.outputs), self.param_dtype
        )


class _Router(nn.Module):
    """``kernel`` ``[hidden, experts]`` and the selection ``bias``
    ``[experts]`` (the sources' ``e_score_correction_bias`` /
    ``expert_bias``)."""

    experts: int
    param_dtype: jnp.dtype

    @nn.compact
    def __call__(self, hidden: int) -> tuple[jnp.ndarray, jnp.ndarray]:
        kernel = self.param(
            "kernel", nn.initializers.lecun_normal(), (hidden, self.experts), self.param_dtype
        )
        bias = self.param(
            "bias", nn.initializers.zeros_init(), (self.experts,), self.param_dtype
        )
        return kernel, bias


def check_share(
    first_expert: int, experts_held: int, num_experts: int, experts_per_token: int
) -> None:
    """The experts a process holds are some of the layer's, and a token
    chooses no more experts than there are."""
    held_last = first_expert + experts_held
    if not 0 < experts_held or first_expert < 0 or held_last > num_experts:
        raise ValueError(f"experts {first_expert}..{held_last} of {num_experts}")
    if experts_per_token > num_experts:
        raise ValueError(f"{experts_per_token} experts a token of {num_experts}")


def routed_experts(
    block: nn.Module, h: jnp.ndarray, *, scaling: float, eps: float, gated: bool = True
) -> jnp.ndarray:
    """The held experts' weighted part of every token's result: ``h``
    float32 ``[T, dim]`` -> float32 ``[T, dim]``. Called inside ``block``'s
    compact ``__call__``: the parameters become the block's, the sizes are
    the block's own fields (``moe_ffn_dim``, ``num_experts``,
    ``experts_per_token``, ``first_expert``, ``experts_held``, ``dtype``,
    ``param_dtype``), and the assignments each held expert got are sown
    into its ``routing`` collection. ``scaling`` and ``eps`` are the
    family's router constants."""
    tokens, dim = h.shape
    held, width, top_k = block.experts_held, block.moe_ffn_dim, block.experts_per_token
    kernel, bias = _Router(block.num_experts, block.param_dtype, name="router")(dim)
    routing = moe_dispatch.route(h, kernel, bias, top_k, scaling, eps)
    planned = moe_dispatch.plan(routing.experts, block.first_expert, held)
    if not block.is_initializing():
        block.sow(ROUTING, "assignments", planned.counts)

    def stacked(inputs: int, outputs: int, name: str) -> jnp.ndarray:
        return _Stacked(held, inputs, outputs, block.param_dtype, name=name)()

    rows = moe_dispatch.segment_rows(tokens, top_k, block.num_experts, held)
    if not gated:
        return moe_dispatch.grouped_relu2(
            h.astype(block.dtype),
            routing,
            planned,
            stacked(dim, width, "experts_up"),
            stacked(width, dim, "experts_down"),
            rows,
        )
    return moe_dispatch.grouped_swiglu(
        h.astype(block.dtype),
        routing,
        planned,
        stacked(dim, width, "experts_gate"),
        stacked(dim, width, "experts_up"),
        stacked(width, dim, "experts_down"),
        rows,
    )


def swiglu(
    block: nn.Module, h: jnp.ndarray, width: int, prefix: str = "", gate_scale: float = 1.0
) -> jnp.ndarray:
    """``down(silu(gate_scale * gate h) * up h)`` of ``width`` through the
    block's own ``_dense`` layers ``<prefix>gate``, ``<prefix>up``,
    ``<prefix>down``: the gate's activation and the product in float32,
    rounded once. ``gate_scale`` is `models/falcon_h1.py`'s (a muP
    multiplier); at 1 the program has no such multiply."""
    gate = block._dense(width, f"{prefix}gate")(h).astype(jnp.float32)
    up = block._dense(width, f"{prefix}up")(h)
    if gate_scale != 1.0:
        gate = gate * gate_scale
    gated = nn.silu(gate) * up.astype(jnp.float32)
    return block._dense(h.shape[-1], f"{prefix}down")(gated.astype(block.dtype))


def relu2_mlp(block: nn.Module, h: jnp.ndarray, width: int, prefix: str = "") -> jnp.ndarray:
    """``down(relu(up h)^2)`` of ``width`` through the block's own ``_dense``
    layers ``<prefix>up``, ``<prefix>down``: the square in float32, rounded
    once (Nemotron-H's ``relu2``, ``mlp_bias`` false)."""
    up = block._dense(width, f"{prefix}up")(h).astype(jnp.float32)
    return block._dense(h.shape[-1], f"{prefix}down")(relu2(up).astype(block.dtype))


def experts_beside_a_shared_one(
    block: nn.Module,
    h: jnp.ndarray,
    *,
    scaling: float,
    eps: float,
    gated: bool = True,
    shared_width: int = 0,
) -> jnp.ndarray:
    """`routed_experts` plus ONE shared expert of the same form
    (``shared_{gate,up,down}``, or ``shared_{up,down}`` where not
    ``gated``; scope ``shared_expert``), ``shared_width`` wide (0: the
    routed experts' width), added unweighted and whole whatever share of
    the routed experts is held: ``h`` float32 ``[T, dim]`` -> float32 ``[T,
    dim]``."""
    routed = routed_experts(block, h, scaling=scaling, eps=eps, gated=gated)
    width = shared_width or block.moe_ffn_dim
    with jax.named_scope("shared_expert"):
        if gated:
            shared = swiglu(block, h.astype(block.dtype), width, "shared_")
        else:
            shared = relu2_mlp(block, h.astype(block.dtype), width, "shared_")
    return routed + shared.astype(jnp.float32)


def routing_counts(state: dict) -> jnp.ndarray:
    """int32 ``[expert layers, experts_held]`` from the ``routing``
    collection one ``apply`` filled, the layers (``block_<i>``) in order."""
    blocks = state[ROUTING]
    ordered = sorted(blocks, key=lambda name: int(name.rsplit("_", 1)[1]))
    return jnp.stack([blocks[name]["assignments"][0] for name in ordered])
