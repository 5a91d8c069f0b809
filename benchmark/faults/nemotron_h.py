"""The four faults that are family ``nemotron_h``'s own."""

from __future__ import annotations


def silu_for_relu2(monkeypatch) -> None:
    """The experts' activation read as the SwiGLU family's: silu(u) for
    relu(u)^2, in the routed experts (kernel and XLA form) and the shared
    one alike."""
    import jax

    from mlops_tpu.models import routed_experts
    from mlops_tpu.ops import expert_products, moe_dispatch

    for module in (routed_experts, moe_dispatch, expert_products):
        monkeypatch.setattr(module, "relu2", jax.nn.silu)


def routed_scaling_dropped(monkeypatch) -> None:
    """``routed_scaling_factor`` (2.5) left out: the chosen weights sum to 1."""
    from mlops_tpu.models import nemotron_h

    monkeypatch.setattr(nemotron_h, "ROUTED_SCALING", 1.0)


def rope_applied(monkeypatch) -> None:
    """Rotary positions on the queries and keys of every attention layer, at
    the config's unused ``rope_theta`` (10,000)."""
    from mlops_tpu.models import nemotron_h

    real = nemotron_h.grouped_query_attention
    monkeypatch.setattr(nemotron_h.NemotronHBlock, "rope_theta", 10000.0, raising=False)
    monkeypatch.setattr(
        nemotron_h, "grouped_query_attention",
        lambda block, h, read, **kw: real(block, h, read, **{**kw, "turn": True}),
    )


def state_not_carried(monkeypatch) -> None:
    """Every chunk of the scan from a zero state, in every M layer:
    ``falcon_h1``'s fault of that name, planted on this family's name of
    the scan."""
    from benchmark.faults import falcon_h1 as falcon_faults
    from mlops_tpu.models import falcon_h1, nemotron_h

    falcon_faults.state_not_carried(monkeypatch)  # replaces falcon_h1.ssd_scan
    monkeypatch.setattr(nemotron_h, "ssd_scan", falcon_h1.ssd_scan)


OWN_FAULTS = {
    "silu_for_relu2": silu_for_relu2,
    "routed_scaling_dropped": routed_scaling_dropped,
    "rope_applied": rope_applied,
    "state_not_carried": state_not_carried,
}
