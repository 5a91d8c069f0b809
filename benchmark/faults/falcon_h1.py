"""The three faults that are family ``falcon_h1``'s own (ISSUE 39)."""

from __future__ import annotations


def state_not_carried(monkeypatch) -> None:
    """Every chunk of the scan from a zero state, in every layer: each chunk
    scanned as a history of its own (the last layer's ``read`` form through
    the full form, its ``c`` laid at the read positions)."""
    import jax.numpy as jnp
    import numpy as np

    from mlops_tpu.models import falcon_h1

    real = falcon_h1.ssd_scan

    def chunks_alone(x, dt, a, b, c, skip, *, chunk, read, dtype):
        batch, seq = x.shape[:2]
        if read is not None:
            read = np.asarray(read)
            c = jnp.zeros((batch, seq, *c.shape[2:]), c.dtype).at[:, read].set(c)
        pad = -seq % chunk  # zeros behind the end: dt 0 neither decays nor adds

        def alone(t):  # [B, S, ...] -> [B * chunks, chunk, ...]
            t = jnp.pad(t, ((0, 0), (0, pad)) + ((0, 0),) * (t.ndim - 2))
            return t.reshape(-1, chunk, *t.shape[2:])

        y = real(alone(x), alone(dt), a, alone(b), alone(c), skip, chunk=chunk, dtype=dtype)
        y = y.reshape(batch, seq + pad, *y.shape[2:])[:, :seq]
        return y if read is None else y[:, read]

    monkeypatch.setattr(falcon_h1, "ssd_scan", chunks_alone)


def gate_after_the_norm(monkeypatch) -> None:
    """``mamba_norm_before_gate`` read the wrong way round: norm(y) * silu(z)."""
    import jax
    import jax.numpy as jnp

    from mlops_tpu.models import falcon_h1

    real = falcon_h1._GatedGroupNorm.__call__

    def norm_then_gate(self, y, z):
        # a gate of silu(1e4) = 1e4 everywhere leaves the norm's input y but for a scale
        return real(self, y, jnp.full_like(z, 1e4)) * jax.nn.silu(z)

    monkeypatch.setattr(falcon_h1._GatedGroupNorm, "__call__", norm_then_gate)


def key_multiplier_dropped(monkeypatch) -> None:
    from mlops_tpu.models import falcon_h1

    real = falcon_h1.grouped_query_attention
    monkeypatch.setattr(
        falcon_h1, "grouped_query_attention",
        lambda block, h, read, **kw: real(block, h, read, **{**kw, "key_scale": 1.0}),
    )


OWN_FAULTS = {
    "state_not_carried": state_not_carried,
    "gate_after_the_norm": gate_after_the_norm,
    "key_multiplier_dropped": key_multiplier_dropped,
}
