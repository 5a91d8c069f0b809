"""Share of the device's busy time spent in operations whose scope holds
flax's ``MultiHeadSelfAttention`` module: the qkv and output projections,
``attend``, and the layout changes around them. The scope is XLA's
``op_name`` of the operation (``program_trace.SCOPE_STAT``); flax wrote the
module's name there before PR 25 too, so an executable served from an older
cache reads the same. Against it: attention is 34% of the forward matmul
FLOPs (``benchmark/flops/``). ``None`` where no operation carries the scope
(the profile was not found, or holds no device)."""

from benchmark import program_trace

SCOPE = "MultiHeadSelfAttention"


def read(facts):
    program = program_trace.load(facts)
    if program is None or not program["busy_s"]:
        return None
    seconds = sum(s for scope, s in program["device_by_scope"] if SCOPE in scope)
    return 100.0 * seconds / program["busy_s"] if seconds else None
