"""Share of the traced window in which no operation ran on the device:
1 - union of the device operations' intervals over the window, from the
profiler's trace (``benchmark/trace_reduce.py``)."""


def read(facts):
    trace = facts["trace"]
    if trace is None:
        return None
    return 100.0 * (1.0 - trace["busy_s"] / trace["window_s"])
