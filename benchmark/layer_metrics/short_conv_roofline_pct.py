"""The time the v5e's roofline allows ``short_conv`` over the device
seconds the scope took in the traced window
(``benchmark/rooflines/lfm2_moe.py``: per convolution layer and chunk run
one pass at 819 GB/s over the input projection's output ``[T, 3 d]`` and
the output projection's operand ``[T, d]`` in bfloat16; the operator is
elementwise, so memory bounds it). The work is what the chunk program was
GIVEN: every run of it that the device's trace shows in the window
(counted there, not reckoned from the job's size) holds
``score_chunk_rows`` rows of ``tokens_per_record`` tokens, padding
included. ``None``, never 0, where no operation carries the scope or no
run of the chunk program is in the trace."""

from benchmark import program_trace
from benchmark.rooflines import lfm2_moe
from benchmark.rooflines.kimi_k2 import chunk_runs, scope_seconds


def read(facts):
    program = program_trace.load(facts)
    if program is None or facts["peaks"] is None:
        return None
    seconds = scope_seconds(program, ("short_conv",))
    runs = chunk_runs(facts["trace"])
    if not seconds or not runs:
        return None
    spec = facts["config"]
    tokens = int(spec["deployment"]["score_chunk_rows"]) * int(spec["tokens_per_record"])
    return 100.0 * runs * lfm2_moe.short_conv_run_seconds(spec, tokens) / seconds
