"""Share of the device's busy time spent in operations whose scope holds
``swa_qkv``, ``swa_attend`` or ``swa_o`` (`mlops_tpu/models/exaone_moe.py`:
a window layer's three projections with the head norms and `rope`, the
causal attention over a sliding window of
`mlops_tpu/ops/causal_attention.py`, and the output projection). Against
it: the four window layers' projections are 33% of the forward
matrix-multiply operations of the cell's five layers, their attention
0.6% (``benchmark/flops/exaone_moe.py``). ``None`` where no operation carries
any of the scopes (a program without them, no profile, no device)."""

from benchmark import program_trace
from benchmark.rooflines.exaone_moe import SWA_SCOPES
from benchmark.rooflines.kimi_k2 import scope_seconds


def read(facts):
    program = program_trace.load(facts)
    if program is None or not program["busy_s"]:
        return None
    seconds = scope_seconds(program, SWA_SCOPES)
    return 100.0 * seconds / program["busy_s"] if seconds else None
