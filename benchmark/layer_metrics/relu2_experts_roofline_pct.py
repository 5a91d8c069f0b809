"""The time the v5e's roofline allows the routed ReLU² experts' two
grouped products (scope ``relu2_experts``: `mlops_tpu/ops/expert_products.py`
``experts_up_fwd`` and ``experts_down_fwd``, or the compiler's own
``ragged-dot``, which carries no scope) over the device seconds they took
in the traced window (``benchmark/rooflines/nemotron_h.py``). Operations
and bytes come from the program's routing counter, which every traced job
carries: the assignments that really fell on held experts, and the (run,
expert) pairs in which a held expert had to be read. Per expert layer the
larger of operations / 197 TFLOP/s and bytes / 819 GB/s. ``None``, never
0, for another family's configuration, where no operation carries the
scope, no job carries a counter, or the device kind has no peak."""

from benchmark.rooflines.nemotron_h import experts_roofline_pct


def read(facts):
    return experts_roofline_pct(facts)
