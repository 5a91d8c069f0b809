"""Share of the device's busy time spent in operations whose scope holds
``gqa_qkv``, ``gqa_attend`` or ``gqa_o`` (`mlops_tpu/models/lfm2_moe.py`:
the three projections with the head norms and `rope`, the causal
grouped-query attention of `mlops_tpu/ops/causal_attention.py`, and the
output projection). Against it: the three are 7% of the forward
matrix-multiply operations (``benchmark/flops/lfm2_moe.py``). ``None``
where no operation carries any of the scopes (a program without them, no
profile, no device)."""

from benchmark import program_trace
from benchmark.rooflines.kimi_k2 import scope_seconds
from benchmark.rooflines.lfm2_moe import GQA_SCOPES


def read(facts):
    program = program_trace.load(facts)
    if program is None or not program["busy_s"]:
        return None
    seconds = scope_seconds(program, GQA_SCOPES)
    return 100.0 * seconds / program["busy_s"] if seconds else None
