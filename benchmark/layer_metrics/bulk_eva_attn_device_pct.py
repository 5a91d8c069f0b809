"""Share of the device's busy time spent in operations whose scope holds
``eva_prep_kv`` or ``eva_attend`` (`mlops_tpu/ops/eva_attention.py`: the
chunk summaries, and the joint softmax over the local window and the
remote summaries with its two products). Against it: the two scopes are
6% of the forward matrix-multiply operations (``benchmark/flops/``).
``None`` where no operation carries either scope (a program without
them, no profile, no device)."""

from benchmark import program_trace
from benchmark.rooflines.eva_attention import scope_seconds


def read(facts):
    program = program_trace.load(facts)
    if program is None or not program["busy_s"]:
        return None
    seconds = scope_seconds(program)
    return 100.0 * seconds / program["busy_s"] if seconds else None
