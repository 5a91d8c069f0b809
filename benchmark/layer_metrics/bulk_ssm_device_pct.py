"""Share of the device's busy time spent in operations whose scope holds
``ssm_in``, ``ssm_conv``, ``ssm_scan`` or ``ssm_out``
(`mlops_tpu/models/falcon_h1.py`: the state-space mixer's input projection
with the muP vector, the causal convolution of `ops/short_conv.py`, the
selective scan of `ops/ssd.py` with ``dt`` and the decays, and the gate,
the grouped norm and the output projection). Against it: the mixer's two
projections and the recurrence's products are 16% of the forward
matrix-multiply operations (``benchmark/flops/falcon_h1.py``). ``None``
where no operation carries any of the scopes (a program without them, no
profile, no device)."""

from benchmark import program_trace
from benchmark.rooflines.falcon_h1 import SSM_SCOPES
from benchmark.rooflines.kimi_k2 import scope_seconds


def read(facts):
    program = program_trace.load(facts)
    if program is None or not program["busy_s"]:
        return None
    seconds = scope_seconds(program, SSM_SCOPES)
    return 100.0 * seconds / program["busy_s"] if seconds else None
