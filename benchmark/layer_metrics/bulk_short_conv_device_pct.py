"""Share of the device's busy time spent in operations whose scope holds
``conv_in``, ``short_conv`` or ``conv_out`` (`mlops_tpu/models/lfm2_moe.py`,
`mlops_tpu/ops/short_conv.py`: the gated short convolution's input
projection, its gates and taps, its output projection). Against it: the
three are 20% of the forward matrix-multiply operations
(``benchmark/flops/lfm2_moe.py``), nearly all of them the two
projections'. ``None`` where no operation carries any of the scopes (a
program without them, no profile, no device)."""

from benchmark import program_trace
from benchmark.rooflines.kimi_k2 import scope_seconds
from benchmark.rooflines.lfm2_moe import CONV_SCOPES


def read(facts):
    program = program_trace.load(facts)
    if program is None or not program["busy_s"]:
        return None
    seconds = scope_seconds(program, CONV_SCOPES)
    return 100.0 * seconds / program["busy_s"] if seconds else None
