"""The four readers of the program's job log (PR 35) on hand-made ``facts``
and a hand-made log: set-up's jobs told from the window's, and nothing
read (``None``, never 0) on a CPU rehearsal and from a program that keeps
no log."""

import types
from pathlib import Path

import pytest

from benchmark import job_log, run

METRICS = Path(__file__).resolve().parents[1] / "layer_metrics"
NAMES = (
    "setup_warmup_compile_s", "setup_warmup_compiled_programs",
    "setup_warmup_drift_s", "bulk_compute_starved_pct",
)


def record(job, drift, sweep, wait_in, trace=0.0, lower=0.0, backend=0.0,
           requests=0, hits=0):
    return {
        "job": job, "rows": 100, "chunks": 4, "started": 10.0 * job, "wall_s": sweep + drift,
        "phases": {"build": 0.0, "warmup": 0.0, "sweep": sweep, "drift": drift},
        "compile_events": {
            "trace_s": trace, "lower_s": lower, "backend_compile_s": backend,
            "cache_retrieval_s": backend / 2, "cache_requests": requests,
            "cache_hits": hits, "cache_misses": 0, "programs": ["fused"],
        },
        "stages": {
            "transfer": {"wait_in_s": 0.0, "wait_out_s": sweep / 2,
                         "max_wait_in_s": 0.0, "max_wait_in_at": None,
                         "max_wait_out_s": sweep / 4, "max_wait_out_at": 1},
            "compute": {"wait_in_s": wait_in, "wait_out_s": sweep - wait_in,
                        "max_wait_in_s": wait_in, "max_wait_in_at": 0,
                        "max_wait_out_s": 0.1, "max_wait_out_at": 2},
        },
        "pauses": {"gc_s": 0.0, "gc_collections": 0, "gc_gen2_s": 0.0},
    }


# two jobs of set-up (say a warm-up of two shapes), then three of the window
LOG = [
    record(1, drift=4.0, sweep=1.0, wait_in=0.9, trace=1.0, lower=0.5, backend=2.0,
           requests=60, hits=20),
    record(2, drift=0.5, sweep=1.0, wait_in=0.1, trace=0.25, requests=3, hits=3),
    record(3, drift=0.3, sweep=2.0, wait_in=0.02),
    record(4, drift=0.3, sweep=2.0, wait_in=0.04),
    record(5, drift=0.3, sweep=4.0, wait_in=0.04),
]


def facts(window_jobs=3, trace=True):
    driver = types.SimpleNamespace(jobs=[{}] * window_jobs)
    return {"driver": driver, "trace": {"busy_s": 1.0} if trace else None}


@pytest.fixture
def program(monkeypatch):
    """The program with ``LOG`` as its job log."""
    from mlops_tpu.parallel import bulk

    monkeypatch.setattr(bulk, "job_log", lambda: list(LOG), raising=False)
    return bulk


def read(name, given):
    return run.load_module(METRICS / f"{name}.py").read(given)


@pytest.mark.parametrize("name,expected", [
    ("setup_warmup_compile_s", 1.0 + 0.5 + 2.0 + 0.25),
    ("setup_warmup_compiled_programs", (60 - 20) + (3 - 3)),
    ("setup_warmup_drift_s", 4.0 + 0.5),
    ("bulk_compute_starved_pct", 100 * (0.02 / 2 + 0.04 / 2 + 0.04 / 4) / 3),
])
def test_reader_tells_setups_jobs_from_the_windows(program, capsys, name, expected):
    assert read(name, facts()) == pytest.approx(expected)
    err = capsys.readouterr().err
    # set-up's records whole, the window's jobs' waits by stage, each once
    assert read(name, facts()) == pytest.approx(expected)
    assert capsys.readouterr().err == ""
    if err:  # the first reader of the process printed them
        assert err.count("set-up job record: ") == 2 and '"job": 1' in err
        assert err.count("queue waits by stage") == 3
        assert "compute in 0.0200 (max 0.0200 at 0) out 1.9800" in err


def test_split_counts_the_windows_jobs_from_the_end():
    assert job_log.split(LOG, 3) == {"setup": LOG[:2], "window": LOG[2:]}
    assert job_log.split(LOG, 0) == {"setup": LOG, "window": []}
    # a log that has lost set-up's records to its bound: nothing made up
    assert job_log.split(LOG[3:], 3) == {"setup": [], "window": LOG[3:]}


@pytest.mark.parametrize("name", NAMES)
def test_reader_returns_nothing_where_there_is_nothing_to_read(
    program, monkeypatch, name
):
    assert read(name, facts(trace=False)) is None, "a CPU rehearsal"
    setup_only = name != "bulk_compute_starved_pct"
    # every record is the window's: set-up left none
    assert (read(name, facts(window_jobs=5)) is None) == setup_only
    # a window of no job
    assert (read(name, facts(window_jobs=0)) is None) != setup_only
    monkeypatch.delattr(program, "job_log")
    assert read(name, facts()) is None, "a program of before PR 35"

