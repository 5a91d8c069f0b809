"""The time the v5e's roofline allows ``gqa_attend`` in a program of family
``falcon_h1`` over the device seconds the scope took in the traced window
(``benchmark/rooflines/falcon_h1.py``, as
``exaone_gqa_attend_roofline_pct`` for its family: per layer and history
the larger of operations / 197 TFLOP/s and bytes / 819 GB/s, from shapes
alone, with the head width the configuration states: the causal half's two
products of 20 query heads of 128, keys and values read once a group of
FIVE query heads, the last layer at its read positions; compute wins).
``None``, never 0, for another family's configuration, where no operation
carries the scope or the device kind has no peak."""

from benchmark.rooflines.falcon_h1 import attend_layer_work, roofline_pct


def read(facts):
    return roofline_pct(facts, "gqa_attend", attend_layer_work)
