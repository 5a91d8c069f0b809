"""The time the v5e's roofline allows ``gqa_attend`` in a program of family
``nemotron_h`` over the device seconds the scope took in the traced
window (``benchmark/rooflines/nemotron_h.py``: per attention layer and
history the larger of operations / 197 TFLOP/s and bytes / 819 GB/s, from
shapes alone, as ``falcon_h1_gqa_attend_roofline_pct`` counts it for its
family: the causal half's two products of 32 query heads of 128, keys and
values read once a group of SIXTEEN query heads, no rotation, the last
layer at its read positions; compute wins), times the whole histories
each run of the chunk program was given. ``None``, never 0, for another
family's configuration, where no operation carries the scope, the
program keeps no job log or the device kind has no peak."""

from benchmark.rooflines.falcon_h1 import attend_layer_work
from benchmark.rooflines.nemotron_h import given_roofline_pct


def read(facts):
    return given_roofline_pct(facts, "gqa_attend", "attention", attend_layer_work)
