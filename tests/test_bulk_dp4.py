"""`score_dataset` under a four-device 'data' mesh, through the path the
benchmark's `bulk_files_mesh` driver takes (ISSUE 31: the cell
`bert-base.bulk-dp4`): the driver itself, loaded by path as
`benchmark/run.py` loads it, over the cell's own files cut to a
rehearsal's size, with FOUR of the test's CPU devices where the
benchmark's own rehearsal has one. The kept chunk program (PR 28,
`CHUNK_PROGRAMS`) is reused by the second job under the mesh too."""

import json
from pathlib import Path

import numpy as np
import pytest

ROOT = Path(__file__).resolve().parents[1]


@pytest.fixture(scope="module")
def driver():
    from benchmark import run

    # the benchmark's own way to a rehearsal's size (its conftest, by path:
    # this directory has a conftest of its own)
    cut = run.load_module(ROOT / "benchmark/tests/conftest.py").cut
    bench = json.loads((ROOT / "BENCHMARK.json").read_text())
    cell = next(w for w in bench["workloads"] if w["name"] == "bert-base.bulk-dp4")
    config = cut(json.loads((ROOT / "benchmark/configs/bert-base.json").read_text()))
    traffic = cut(json.loads((ROOT / f"benchmark/traffic/{cell['traffic']}.json").read_text()))
    traffic["mesh_chips"] = cell["chips"]  # the rehearsal's 1 -> the cell's 4
    ctx = run.Context(2**31 + 11, cell, config, traffic)
    built = run.load_module(ROOT / "benchmark/drivers/bulk_files_mesh.py").build(ctx)
    built.setup()
    return built


def test_the_job_is_sharded_over_four_devices_and_correct(driver):
    from mlops_tpu.parallel import bulk

    assert dict(driver.mesh.shape) == {"data": 4, "model": 1}
    assert driver.chunk == 4 * 256  # the configuration's chunk a chip
    bulk.CHUNK_PROGRAMS.clear()
    driver.warmup()
    window = driver.window(0.0, max_units=2)
    assert window["attempted"] == 2 and window["units"] == 2 * driver.rows
    limits = json.loads((ROOT / "benchmark/cells/bert-base.bulk-dp4.json").read_text())
    worst = driver.check()
    for name, value in worst.items():
        assert value <= limits["rehearsal_limits"][name], (name, value)
    assert set(worst) == set(limits["limits"])


def test_the_check_sample_holds_both_sides_of_every_shard_boundary(driver):
    """A chip's rows are 256 here (1,000 rows: three seams, none of them a
    boundary of the 1,024-row chunk): the rows on both sides of each seam
    are always compared, whatever the seed draws."""
    assert driver.chunk == 4 * 256 and driver.rows == 1000
    seams = np.arange(256, driver.rows, 256)
    assert set(seams - 1) | set(seams) | {0, driver.rows - 1} <= set(driver._sample)
    assert driver._sample.size == int(driver.traffic["check_rows"])


def test_the_kept_chunk_program_serves_the_second_job_under_the_mesh(driver):
    from mlops_tpu.data.encode import EncodedDataset

    first = driver._score(EncodedDataset(driver.cat, driver.num))
    again = driver._score(EncodedDataset(driver.cat, driver.num))
    assert again.compile_events["chunk_program_reused"] == 1
    assert "fused" not in again.compile_events["programs"]
    np.testing.assert_array_equal(first.predictions, again.predictions)
    one = driver._score.__func__  # the same job unsharded gives the same answers
    driver.mesh, mesh = None, driver.mesh
    try:
        plain = one(driver, EncodedDataset(driver.cat, driver.num))
    finally:
        driver.mesh = mesh
    np.testing.assert_allclose(first.predictions, plain.predictions, atol=2e-6)
    np.testing.assert_array_equal(first.outliers, plain.outliers)


def test_a_jobs_scorer_dies_with_the_job(driver, monkeypatch):
    """The scorer holds the weights a job replicated over the mesh (0.35 GB
    a chip for `bert-base`): it has to go by reference count when the job
    returns, not at some later collection (on the chip, a scorer that
    referred to itself kept seven jobs' copies on three chips: 2.1 GB)."""
    import gc
    import weakref

    from mlops_tpu.data.encode import EncodedDataset
    from mlops_tpu.parallel import bulk

    made = []
    real = bulk.make_chunk_scorer

    def watched(*args, **kwargs):
        scorer = real(*args, **kwargs)
        made.append(weakref.ref(scorer))
        return scorer

    monkeypatch.setattr(bulk, "make_chunk_scorer", watched)
    gc.collect()
    gc.disable()
    try:
        driver._score(EncodedDataset(driver.cat, driver.num))
        assert made and made[0]() is None
    finally:
        gc.enable()
