"""The time the v5e's roofline allows the routed experts' three grouped
products (scope ``experts``, and the compiler's own ``ragged-dot``
operations, which carry no scope) over the device seconds they took in the
traced window (``benchmark/rooflines/kimi_k2.py``). Operations and bytes
come from the program's routing counter, which every traced job carries
(``BulkScoreResult.routing``, kept by the driver with its job record):
the assignments that really fell on held experts, and the (run, expert)
pairs in which a held expert got a token and so had to be read. Per expert
layer the larger of operations / 197 TFLOP/s and bytes / 819 GB/s.
``None``, never 0, where no operation carries the scope, no job carries a
counter, or the device kind has no peak."""

from benchmark import program_trace
from benchmark.rooflines import kimi_k2


def read(facts):
    program, peaks = program_trace.load(facts), facts["peaks"]
    if program is None or peaks is None:
        return None
    seconds = kimi_k2.experts_scope_seconds(facts, program)
    allowed = kimi_k2.experts_seconds(
        facts["config"], getattr(facts["driver"], "jobs", []), peaks
    )
    if not seconds or not allowed:
        return None
    return 100.0 * allowed / seconds
