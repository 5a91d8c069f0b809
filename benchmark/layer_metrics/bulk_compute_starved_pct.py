"""Share of a job's sweep in which the executor's dispatch thread
(``compute``) was blocked on its INPUT queue, with no chunk to dispatch:
the host starves the chip. ``stages["compute"]["wait_in_s"]`` over
``phases["sweep"]`` of each record of the window's jobs
(``benchmark/job_log.py``), mean over the jobs, in percent. Its
counterpart, ``wait_out_s``, is ``compute`` blocked on ``fetch``: the
device sets the pace, as it should. ``None`` where the program keeps no
job log."""

from benchmark import job_log


def read(facts):
    jobs = job_log.load(facts)
    if jobs is None or not jobs["window"]:
        return None
    shares = [
        100.0 * record["stages"]["compute"]["wait_in_s"] / record["phases"]["sweep"]
        for record in jobs["window"]
    ]
    return sum(shares) / len(shares)
