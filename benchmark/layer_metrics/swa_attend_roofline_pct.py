"""The time the v5e's roofline allows ``swa_attend`` over the device
seconds the scope took in the traced window
(``benchmark/rooflines/exaone_moe.py``: per window layer and history the
larger of operations / 197 TFLOP/s and bytes / 819 GB/s, from shapes
alone: the two products of every query head over the min(position + 1,
window) keys a query sees, keys and values read once a group; at the
published widths memory wins). ``None``, never 0, where no operation
carries the scope or the device kind has no peak."""

from benchmark.rooflines.exaone_moe import SWA, attend_roofline_pct


def read(facts):
    return attend_roofline_pct(facts, SWA)
