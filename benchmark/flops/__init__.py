"""Matrix-multiply operations a row needs, from the configuration's shapes.

Counted from what the architecture requires, never from what a compiled
program happens to do: XLA's own count moves when a PR changes the
program, counts recomputation, and counts nothing for a Pallas call. One
multiply-accumulate is two operations. Embedding lookups, LayerNorm,
softmax, GELU and the monitors are not matrix multiplies and are left out,
so a share of the peak computed from these is a little low, never high.

One module per model family beside this file, found by the configuration's
``model_config.family``: ``forward_macs_per_row(spec)``.
"""

from __future__ import annotations

import importlib


def forward_flops_per_row(spec: dict) -> int:
    family = spec["model_config"]["family"]
    try:
        counts = importlib.import_module(f"benchmark.flops.{family}")
    except ModuleNotFoundError:
        raise KeyError(f"no operation count for family {family!r}") from None
    return 2 * counts.forward_macs_per_row(spec)
