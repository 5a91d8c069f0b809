"""Share of the device's busy time spent in operations whose scope holds
``moe_layer`` (`mlops_tpu/models/nemotron_h.py`: a whole expert layer but
its norm: the sigmoid router and its top-k, the sort and gather, the
routed ReLU² experts' grouped products, the combine and the shared
expert), plus any grouped product of the compiler's own, which carries no
scope. Against it: the five expert layers are 47% of the forward
matrix-multiply operations of ``nemotron-labs-twotower-30b-a3b``
(``benchmark/flops/nemotron_h.py``). ``None`` where no operation carries
the scope (a program without it, no profile, no device)."""

from benchmark import program_trace
from benchmark.rooflines.kimi_k2 import GROUPED_PRODUCT, kind_seconds, scope_seconds


def read(facts):
    program = program_trace.load(facts)
    if program is None or not program["busy_s"]:
        return None
    seconds = scope_seconds(program, ("moe_layer",))
    if seconds:  # a grouped product of the compiler's own carries no scope
        seconds += kind_seconds(facts, GROUPED_PRODUCT)
    return 100.0 * seconds / program["busy_s"] if seconds else None
