"""The time the v5e's roofline allows ``gqa_attend`` over the device
seconds the scope took in the traced window
(``benchmark/rooflines/lfm2_moe.py``: per attention layer and history the
larger of operations / 197 TFLOP/s and bytes / 819 GB/s, from shapes
alone: the causal half's two products of every query head, keys and
values read once a group). The work is what the chunk program was GIVEN:
every run of it that the device's trace shows in the window (counted
there, not reckoned from the job's size) holds ``score_chunk_rows /
records_per_history`` whole histories at the full length, padding
included. ``None``, never 0, where no operation carries the scope or the
device kind has no peak."""

from benchmark import program_trace
from benchmark.rooflines import lfm2_moe
from benchmark.rooflines.kimi_k2 import chunk_runs, scope_seconds


def read(facts):
    program, peaks = program_trace.load(facts), facts["peaks"]
    if program is None or peaks is None:
        return None
    seconds = scope_seconds(program, ("gqa_attend",))
    runs = chunk_runs(facts["trace"])
    if not seconds or not runs:
        return None
    spec = facts["config"]
    per = int(spec["records_per_history"])
    histories = int(spec["deployment"]["score_chunk_rows"]) // per
    allowed = runs * histories * lfm2_moe.attend_history_seconds(spec, per, peaks)
    return 100.0 * allowed / seconds
