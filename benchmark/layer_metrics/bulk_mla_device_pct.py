"""Share of the device's busy time spent in operations whose scope holds
``mla_q``, ``mla_kv``, ``mla_attend`` or ``mla_o``
(`mlops_tpu/models/kimi_k2.py`: the low-rank query path, the keys and
values expanded from the latent, the causal attention of
`mlops_tpu/ops/mla.py`, and the output projection). Against it: the four
are 47% of the forward matrix-multiply operations
(``benchmark/flops/kimi_k2.py``). ``None`` where no operation carries any
of the scopes (a program without them, no profile, no device)."""

from benchmark import program_trace
from benchmark.rooflines.kimi_k2 import MLA_SCOPES, scope_seconds


def read(facts):
    program = program_trace.load(facts)
    if program is None or not program["busy_s"]:
        return None
    seconds = scope_seconds(program, MLA_SCOPES)
    return 100.0 * seconds / program["busy_s"] if seconds else None
