"""FLOP accounting + MFU (model FLOPs utilization) reporting.

The reference publishes no efficiency evidence at all (SURVEY.md §6); the
bench here reports latency/throughput, and this module adds the roofline
axis: how much of the chip's peak the measured path actually uses, so
"actually fast" is auditable from the bench artifact alone.

FLOP counts come from XLA's OWN cost model (`compiled.cost_analysis()`),
not hand-derived formulas — it covers every model family, includes fused
elementwise work the analytic count would miss, and matches what the
compiler actually scheduled. Peak FLOP/s is a small device-kind table
(bf16 matmul peaks from published TPU specs). A device that is not in the
table is an error, not a default: a made-up or measured-here denominator
would make the MFU meaningless. CPUs have no published peak and report no
MFU.
"""

from __future__ import annotations

import logging
from typing import Any

import jax

logger = logging.getLogger(__name__)

# Published per-chip dense matmul peaks (FLOP/s). Values are bf16 peaks for
# TPUs (the compute dtype the framework puts on the MXU) and deliberately
# None for CPUs: a portable peak for arbitrary host silicon isn't knowable
# from here, and a made-up denominator would make the MFU meaningless.
_PEAKS: tuple[tuple[str, float], ...] = (
    ("v5 lite", 197e12),  # v5e: 197 TFLOP/s bf16
    ("v5e", 197e12),
    ("v5p", 459e12),
    ("v4", 275e12),
    ("v6 lite", 918e12),  # Trillium
    ("v6e", 918e12),
)

# MXU throughput of each executing precision relative to the bf16 base
# above (published TPU ratios: int8 doubles the bf16 peak, f32 halves it).
# An MFU whose numerator is an f32 program but whose denominator is the
# bf16 peak understates utilization 2x — the ISSUE 17 `mfu_bulk` fix: the
# caller states the precision the measured program EXECUTES in, and the
# bench payload records it next to the number.
_DTYPE_SCALE: dict[str, float] = {
    "bf16": 1.0,
    "bfloat16": 1.0,
    "f32": 0.5,
    "float32": 0.5,
    "int8": 2.0,
}


def peak_flops(device: Any, dtype: str = "bf16") -> float | None:
    """Published peak FLOP/s for ``device`` at executing precision
    ``dtype`` ("bf16"/"f32"/"int8" and aliases). None for a CPU (no
    published peak: callers report no MFU there); any other device kind
    missing from the table raises."""
    if dtype not in _DTYPE_SCALE:
        raise ValueError(
            f"unknown executing dtype {dtype!r}; expected one of "
            f"{sorted(_DTYPE_SCALE)}"
        )
    if getattr(device, "platform", "") == "cpu":
        return None
    kind = getattr(device, "device_kind", "")
    for needle, peak in _PEAKS:
        if needle in kind.lower():
            return peak * _DTYPE_SCALE[dtype]
    raise ValueError(
        f"no published peak for device kind {kind!r}; add it, with its "
        "source, to utils/flops.py _PEAKS"
    )


def compile_with_flops(fn, *args) -> tuple[Any, float | None]:
    """Compile ``fn(*args)`` ONCE; return ``(executable, flops)``.

    The executable is directly callable with the same args (so callers can
    time it without a second ``jax.jit`` compile). A compile error
    propagates. ``flops`` is None when the backend exposes no cost
    analysis.
    """
    compiled = jax.jit(fn).lower(*args).compile()
    try:
        analysis = compiled.cost_analysis()
        flops = float(analysis.get("flops", 0.0))
        return compiled, (flops if flops > 0 else None)
    except (
        AttributeError,  # backend exposes no cost_analysis / returns None
        KeyError,
        TypeError,  # non-mapping analysis object
        ValueError,
        NotImplementedError,  # plugin declines the query
        RuntimeError,  # XLA-side analysis failure
    ) as err:
        logger.debug("cost_analysis unavailable: %s", err)
        return compiled, None


def compiled_flops(fn, *args) -> float | None:
    """FLOPs of one call of ``fn(*args)`` per XLA's cost analysis (None
    when unavailable)."""
    return compile_with_flops(fn, *args)[1]


def mfu(flops_per_call: float | None, calls_per_s: float, peak: float | None):
    """Fraction of peak, rounded for the bench JSON; None when either side
    is unknown."""
    if not flops_per_call or not peak or calls_per_s <= 0:
        return None
    return round(flops_per_call * calls_per_s / peak, 4)
