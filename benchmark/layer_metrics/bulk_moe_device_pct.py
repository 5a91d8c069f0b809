"""Share of the device's busy time spent in operations whose scope holds
``router``, ``moe_dispatch``, ``experts``, ``moe_combine`` or
``shared_expert`` (`mlops_tpu/ops/moe_dispatch.py`,
`mlops_tpu/models/kimi_k2.py`: the sigmoid router and its top-k, the sort
and gather, the held experts' grouped products, the weighted scatter, the
shared expert). Against it: the expert layers' own parts are 18% of the
forward matrix-multiply operations (``benchmark/flops/kimi_k2.py``), the
routed part of them bound by memory. ``None`` where no operation carries
any of the scopes (a program without them, no profile, no device)."""

from benchmark import program_trace
from benchmark.rooflines.kimi_k2 import GROUPED_PRODUCT, MOE_SCOPES, kind_seconds, scope_seconds


def read(facts):
    program = program_trace.load(facts)
    if program is None or not program["busy_s"]:
        return None
    seconds = scope_seconds(program, MOE_SCOPES)
    if seconds:  # the grouped products carry no scope: found by their kind
        seconds += kind_seconds(facts, GROUPED_PRODUCT)
    return 100.0 * seconds / program["busy_s"] if seconds else None
