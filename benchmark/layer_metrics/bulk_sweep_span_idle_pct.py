"""Share of the traced window in which the device sat idle INSIDE a job's
pipelined sweep, by the program's own spans: the idle time whose innermost
span is ``mlops:bulk.sweep`` or one of the executor's ``mlops:pipe.<stage>``
spans (they run inside the sweep, on its threads), mean over the chips,
over the window. ``bulk_sweep_idle_pct`` asks the same of the gaps between
the runs of a job's chunk program, which it counts by the configuration's
one-chip chunk; this one counts nothing, so it also reads a job whose
chunk is the driver's (a mesh's). It is the larger of the two: the gaps
before the first run and after the last, and those an operation of another
program fills in part, are in it. Read from the traced run's profile
(``benchmark/program_trace.py``); ``None`` where the program writes no such
spans or no device is in the profile."""

from benchmark import program_trace


def read(facts):
    program = program_trace.load(facts)
    if program is None or not program["busy_s"]:
        return None
    idle = sum(
        seconds
        for span, seconds in program["idle_by_span"]
        if span == "bulk.sweep" or span.startswith("pipe.")
    )
    return 100.0 * idle / program["window_s"]
