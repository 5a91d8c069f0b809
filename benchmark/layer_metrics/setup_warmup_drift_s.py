"""Seconds of the drift sample in set-up's warm-up job: ``phases["drift"]``
of the job's record (``benchmark/job_log.py``), summed over set-up's jobs.
It is the sample's FIRST run in the process: its eager operators are
loaded or compiled one by one inside it, where a timed job's
(``bulk_drift_s``) finds them compiled. ``None`` where the program keeps
no job log."""

from benchmark import job_log


def read(facts):
    return job_log.setup_sum(facts, lambda record: float(record["phases"]["drift"]))
