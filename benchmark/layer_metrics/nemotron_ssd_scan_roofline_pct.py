"""The time the v5e's roofline allows ``ssm_scan`` in a program of family
``nemotron_h`` over the device seconds the scope took in the traced
window (``benchmark/rooflines/nemotron_h.py``: per mixer layer and history
the larger of operations / 197 TFLOP/s and bytes / 819 GB/s of the
REQUIRED work, as ``ssd_scan_roofline_pct`` counts it for ``falcon_h1``,
at this model's shapes: heads of 64, a state of 128, 8 heads a group;
memory is the larger, by 1.6), times the whole histories each run of the
chunk program was given (its own rows, the padded tail's too, from the
window's job records). ``None``, never 0, for another family's
configuration, where no operation carries the scope, the program keeps no
job log or the device kind has no peak."""

from benchmark.rooflines.falcon_h1 import scan_layer_work
from benchmark.rooflines.nemotron_h import given_roofline_pct


def read(facts):
    return given_roofline_pct(facts, "ssm_scan", "mamba", scan_layer_work)
