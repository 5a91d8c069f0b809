"""The time the v5e's roofline allows ``ssm_scan`` (the selective
state-space scan of `mlops_tpu/ops/ssd.py` with ``dt``'s softplus and the
decays, in a program of family ``falcon_h1``) over the device seconds the
scope took in the traced window (``benchmark/rooflines/falcon_h1.py``: per
layer and history the larger of operations / 197 TFLOP/s and bytes / 819
GB/s of the REQUIRED work, whatever implements it: the recurrence's three
products a position and head; x, B, C, dt read once and y written once in
bfloat16; the last layer's answers at its read positions; at the
published widths compute is the larger, by 1.4), times the chunk
program's runs counted in the trace and the histories a run holds.
``None``, never 0, for another family's configuration, where no operation
carries the scope or the device kind has no peak."""

from benchmark.rooflines.falcon_h1 import roofline_pct, scan_layer_work


def read(facts):
    return roofline_pct(facts, "ssm_scan", scan_layer_work)
