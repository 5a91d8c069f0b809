"""Share of the device's busy time spent in operations whose scope holds
``mamba_mixer`` (`mlops_tpu/models/nemotron_h.py`: a whole mixer layer but
its norm, which is `models/mamba2.py`'s ``ssm_in``, ``ssm_conv``,
``ssm_scan`` and ``ssm_out``: the in-projection, the causal convolution,
the selective scan with ``dt`` and the decays, the gate, the grouped norm
and the out-projection). ``bulk_ssm_device_pct`` reads the same four
scopes in ``falcon_h1``, where the mixer shares its layer with attention.
Against it: the six mixer layers are 45.5% of the forward matrix-multiply
operations of ``nemotron-labs-twotower-30b-a3b``
(``benchmark/flops/nemotron_h.py``). ``None`` where no operation carries
the scope (a program without it, no profile, no device)."""

from benchmark import program_trace
from benchmark.rooflines.kimi_k2 import scope_seconds


def read(facts):
    program = program_trace.load(facts)
    if program is None or not program["busy_s"]:
        return None
    seconds = scope_seconds(program, ("mamba_mixer",))
    return 100.0 * seconds / program["busy_s"] if seconds else None
