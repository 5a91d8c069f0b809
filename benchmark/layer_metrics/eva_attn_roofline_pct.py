"""The time the v5e's roofline allows ``eva_prep_kv`` + ``eva_attend``
over the device seconds the two scopes took in the traced window
(``benchmark/rooflines/eva_attention.py``: per layer and history the
larger of operations / 197 TFLOP/s and bytes / 819 GB/s, from shapes
alone). The work is what the chunk program was GIVEN: every run of it in
a traced job (the in-call warm-up chunk and the sweep's ceil(rows /
chunk) chunks) holds ``score_chunk_rows / records_per_history`` whole
histories at the full length, padding included, whatever the file's last
history holds. ``None``, never 0, where no operation carries the scopes
or the device kind has no peak."""

from benchmark import program_trace
from benchmark.rooflines import eva_attention


def read(facts):
    program, peaks = program_trace.load(facts), facts["peaks"]
    if program is None or peaks is None:
        return None
    seconds = eva_attention.scope_seconds(program)
    if not seconds:
        return None
    spec = facts["config"]
    per = int(spec["records_per_history"])
    chunk = int(spec["deployment"]["score_chunk_rows"])
    rows = int(facts["traffic"]["rows_per_file"])
    runs = len(program["jobs"]) * (1 + -(-rows // chunk))
    allowed = runs * (chunk // per) * eva_attention.history_seconds(spec, per, peaks)
    return 100.0 * allowed / seconds
