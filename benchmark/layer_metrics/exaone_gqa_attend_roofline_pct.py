"""The time the v5e's roofline allows ``gqa_attend`` in a program of family
``exaone_moe`` over the device seconds the scope took in the traced window
(``benchmark/rooflines/exaone_moe.py``: per full-attention layer and
history the larger of operations / 197 TFLOP/s and bytes / 819 GB/s, from
shapes alone, with the head width the configuration states, which
``gqa_attend_roofline_pct`` does not read: its work is
``rooflines/lfm2_moe.py``'s, a head of ``hidden // heads``; the causal
half's two products of every query head, keys and values read once a
group, the last layer at its read positions; at the published widths
compute wins). ``None``, never 0, for another family's configuration,
where no operation carries the scope or the device kind has no peak."""

from benchmark.rooflines.exaone_moe import FULL, attend_roofline_pct


def read(facts):
    return attend_roofline_pct(facts, FULL)
