"""Seconds of a bulk job's drift sample: the program's ``mlops:bulk.drift``
span (the sample's draw, ``drift_scores`` over it as eager operators, the
copy to the host), mean over the window's jobs. Read from the traced run's
profile (``benchmark/program_trace.py``); ``None`` where the program writes
no such span."""

from benchmark import program_trace


def read(facts):
    return program_trace.mean_per_job(program_trace.load(facts), ("drift",))
