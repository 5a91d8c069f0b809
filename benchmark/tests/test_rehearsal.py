"""Every driver end to end at a tiny size on the CPU: the harness's look
for a chip is skipped, the rest of a run is driven as the driver's command
drives it. Then the same with the timed path broken underneath, once for
each fault a bulk cell can have, and with the control in the program's
place: ``correct`` has to come out false."""

import json

import numpy as np
import pytest
from conftest import CELLS

RESULT_KEYS = {"correct", "attempted", "failed", "metrics", "device"}


def drive(capsys, root, workload, trace=0, seed=3_000_000_017):
    from benchmark import run

    rc = run.main(
        ["--workload", workload, "--seed", str(seed), "--seconds", "0.3",
         "--trace", str(trace)],
        bench_root=root,
        require_chip=False,
    )
    captured = capsys.readouterr()
    assert rc == 0
    lines = captured.out.strip().splitlines()
    assert len(lines) == 1, "nothing but the result goes to standard output"
    return json.loads(lines[0]), captured.err


@pytest.mark.parametrize("workload", CELLS)
def test_untraced_run_prints_the_end_to_end_metrics(capsys, tiny_root, workload):
    result, err = drive(capsys, tiny_root, workload)
    assert RESULT_KEYS <= set(result)
    assert list(result)[-1] == "compared"
    assert result["correct"] is True and result["failed"] == 0
    assert result["attempted"] >= 1
    assert set(result["metrics"]) == {"bulk_rows_per_s", "setup_s"}
    for metric in result["metrics"].values():
        assert metric["value"] > 0 and metric["unit"]
    assert {"platform", "kind", "count", "memory_peak_bytes"} <= set(result["device"])
    last = err.strip().splitlines()[-len(result["compared"]):]
    assert all(line.startswith("compared ") and "limit=" in line for line in last)


@pytest.mark.parametrize("workload", CELLS)
def test_traced_run_prints_the_per_layer_metrics(capsys, tiny_root, workload):
    result, _ = drive(capsys, tiny_root, workload, trace=1)
    assert result["correct"] is True
    # on the CPU there is no device plane and no table of peaks: the readers
    # of the trace and of the peak return nothing and are left out, never 0
    assert set(result["metrics"]) == {
        "bulk_job_overhead_pct", "bulk_host_stage_busy_pct", "setup_compile_s",
    }
    assert result["attempted"] == 2  # the mix's traced_units


def test_a_run_without_a_chip_fails_and_prints_no_result(capsys, tiny_root):
    from benchmark import run

    rc = run.main(["--workload", CELLS[0], "--seconds", "0.1"], bench_root=tiny_root)
    assert rc == run.EXIT_NO_CHIP
    assert capsys.readouterr().out == ""


# ---------------------------------------------------------------- faults
def _break_scorer(monkeypatch, mangle):
    """Wrap the chunk scorer ``score_dataset`` builds: ``mangle(probs, flags,
    mask)`` alters what a chunk answers where it is produced."""
    from mlops_tpu.parallel import bulk

    make = bulk.make_chunk_scorer

    def broken(*args, **kwargs):
        scorer = make(*args, **kwargs)

        def score_chunk(cat, num, mask):
            probs, flags = scorer(cat, num, mask)
            return mangle(np.array(probs), np.array(flags), np.asarray(mask))

        return score_chunk

    monkeypatch.setattr(bulk, "make_chunk_scorer", broken)


def half_of_each_chunk_left_out(probs, flags, mask):
    half = probs.size // 2
    probs[half:] = probs[:half].mean()  # the rest answered with the mean
    return probs, flags


def one_answer_altered(probs, flags, mask):
    probs[0] = probs[0] + 0.25 if probs[0] < 0.5 else probs[0] - 0.25
    return probs, flags


def padded_rows_flagged(probs, flags, mask):
    return probs, np.where(mask, 1.0 - flags, flags).astype(np.float32)


def drift_over_the_wrong_rows(monkeypatch):
    from mlops_tpu.parallel import bulk

    real = bulk.drift_scores
    monkeypatch.setattr(
        bulk, "drift_scores",
        lambda monitor, cat, num, mask: real(monitor, cat[::2], num[::2], mask[::2]),
    )


def chunks_stored_out_of_order(monkeypatch):
    from mlops_tpu.data import pipeline_exec

    real = pipeline_exec.run_pipeline

    def wrong_place(source, stages, sink, **kwargs):
        spans = list(source)
        if len(spans) < 3:
            return real(spans, stages, sink, **kwargs)
        size = spans[0][1] - spans[0][0]

        def sink_swapped(item):
            start, stop, probs, flags = item
            if start == 0:
                start, stop = size, 2 * size
            elif start == size:
                start, stop = 0, size
            sink((start, stop, probs, flags))

        return real(spans, stages, sink_swapped, **kwargs)

    monkeypatch.setattr(pipeline_exec, "run_pipeline", wrong_place)


FAULTS = {
    "half_of_each_chunk_left_out": lambda mp: _break_scorer(mp, half_of_each_chunk_left_out),
    "one_answer_altered": lambda mp: _break_scorer(mp, one_answer_altered),
    "outlier_flags_inverted": lambda mp: _break_scorer(mp, padded_rows_flagged),
    "drift_over_the_wrong_rows": drift_over_the_wrong_rows,
    "chunks_stored_out_of_order": chunks_stored_out_of_order,
}


@pytest.mark.parametrize("fault", sorted(FAULTS))
@pytest.mark.parametrize("workload", CELLS)
def test_a_broken_timed_path_is_not_correct(capsys, monkeypatch, tiny_root, workload, fault):
    FAULTS[fault](monkeypatch)
    result, err = drive(capsys, tiny_root, workload)
    assert result["correct"] is False, fault
    assert "NOT CORRECT" in err


@pytest.mark.parametrize("workload", CELLS)
def test_the_control_is_not_correct(tiny_root, workload):
    """The reference one precision down, in the program's place, fails the
    fixture's limits; the reference itself, in the program's place, passes."""
    from benchmark import run

    loaded = run.load_cell(tiny_root, workload)
    for seed in (1, 2, 2**31 + 3):
        ctx = run.Context(seed, loaded["cell"], loaded["config"], loaded["traffic"])
        driver = run.load_module(loaded["driver_file"]).build(ctx)
        driver.setup()
        expected = driver.reference_outputs()
        limits = loaded["limits"]
        assert run.judge(driver.compare(expected, expected), limits)[1]
        compared, correct = run.judge(
            driver.compare(driver.control_outputs(), expected), limits
        )
        assert not correct
        failed = [k for k, c in compared.items() if c["value"] > c["limit"]]
        assert "pred_rms_gap" in failed and "drift_max_gap" in failed, compared


def test_readings_hold_program_control_and_altered_answer_to_the_limits(capsys, tiny_root):
    """``readings.py`` is how a chip run puts the control in the program's
    place: by the cell's own limits the program is correct, the control and
    one altered answer are not."""
    from benchmark import readings

    rc = readings.main(
        ["--workload", CELLS[0], "--seeds", "5,2147483653", "--seconds", "0.2"],
        bench_root=tiny_root, require_chip=False,
    )
    assert rc == 0
    lines = [json.loads(line) for line in capsys.readouterr().out.strip().splitlines()]
    assert [line["seed"] for line in lines] == [5, 2147483653]
    for line in lines:
        assert line["correct"] == {"program": True, "control": False, "altered_answer": False}
        assert line["fails"]["program"] == []
        assert "pred_rms_gap" in line["fails"]["control"]
        assert "pred_max_gap" in line["fails"]["altered_answer"]
