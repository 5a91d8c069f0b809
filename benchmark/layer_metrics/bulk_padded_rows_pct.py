"""Share of the rows the chunk program computed that were padding: 100 ×
(1 − Σ ``rows`` ÷ Σ ``rows_run``) over the records of the window's jobs
(``benchmark/job_log.py``). ``rows_run`` is every run's size, the last
one's included (``tail_chunk_rows`` of the record). A record without it
comes from a program that padded every run to ``chunk_rows``, and ran
``chunks × chunk_rows``. ``None`` where the program keeps no job log."""

from benchmark import job_log


def rows_run(record: dict) -> int:
    return record.get("rows_run", record["chunks"] * record["chunk_rows"])


def read(facts):
    jobs = job_log.load(facts)
    if jobs is None or not jobs["window"]:
        return None
    rows = sum(record["rows"] for record in jobs["window"])
    run = sum(rows_run(record) for record in jobs["window"])
    return 100.0 * (1.0 - rows / run)
