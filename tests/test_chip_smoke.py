"""chip_smoke.py rehearsed on the CPU at a tiny size.

The chip run itself is the builder's and the driver's (`python
chip_smoke.py` on the machine with the chip). Here every phase function
runs through the same code — real CLI children, real servers, real HTTP —
with a tiny model, Pallas kernels in interpret mode (chosen HERE, by
argument), four virtual CPU devices for the sharded trainer, and a compile
cache root of the test's own so both caches start cold together. And
`main()` itself must FAIL here: there is no TPU.
"""

import json
import subprocess
import sys
from pathlib import Path

import pytest

REPO = Path(__file__).resolve().parent.parent
sys.path.insert(0, str(REPO))

import chip_smoke  # noqa: E402

TINY_TRAIN = (
    "model.family=mlp",
    "model.hidden_dims=16,16",
    "model.embed_dim=4",
    "model.ensemble_size=2",
    "data.rows=3000",
    "train.steps=60",
    "train.eval_every=30",
    "train.batch_size=128",
    "train.warmup_steps=5",
    "train.distill_quant=true",
)
# Two buckets and no group grid: the fewest programs that still serve the
# 1-row and the 256-row body.
TINY_SERVE = (
    "serve.warmup_batch_sizes=1,256",
    "serve.batch_window_ms=0",
)
TINY_TP = (
    "model.family=ft_transformer",
    "model.tensor_parallel=2",
    "model.depth=1",
    "model.heads=2",
    "model.token_dim=16",
    "data.rows=600",
    "train.steps=3",
    "train.eval_every=1",
    "train.batch_size=32",
    "train.warmup_steps=1",
)


def test_main_exits_nonzero_with_ok_false_when_there_is_no_tpu():
    proc = subprocess.run(
        [sys.executable, str(REPO / "chip_smoke.py")],
        env=chip_smoke.child_env(JAX_PLATFORMS="cpu"),
        capture_output=True, text=True, timeout=300, cwd=REPO,
    )
    assert proc.returncode != 0
    last = json.loads(proc.stdout.strip().splitlines()[-1])
    assert last["ok"] is False
    assert last["device"]["platform"] == "cpu"
    assert "not a TPU" in last["error"]


def test_script_alone_without_the_program_fails(tmp_path):
    """In a directory that holds chip_smoke.py and nothing else of the
    repo the run fails (no result line says ok)."""
    (tmp_path / "chip_smoke.py").write_text(
        (REPO / "chip_smoke.py").read_text()
    )
    proc = subprocess.run(
        [sys.executable, "chip_smoke.py"],
        env={"PATH": "/usr/bin:/bin:/usr/local/bin", "HOME": str(tmp_path),
             "JAX_PLATFORMS": "cpu"},
        capture_output=True, text=True, timeout=300, cwd=tmp_path,
    )
    assert proc.returncode != 0
    assert '"ok": true' not in proc.stdout


def test_parent_side_of_the_script_stays_off_jax():
    """One process for each chip: the parent learns the device from a
    child, and nothing it imports or runs itself pulls jax in."""
    code = (
        "import sys, pathlib, tempfile, chip_smoke\n"
        "out = pathlib.Path(tempfile.mkdtemp())\n"
        "chip_smoke.phase_encoder(out)\n"
        "chip_smoke.request_bodies(out)\n"
        "chip_smoke.aot_dir()\n"
        "assert 'jax' not in sys.modules, 'parent imported jax'\n"
    )
    subprocess.run(
        [sys.executable, "-c", code], env=chip_smoke.child_env(),
        check=True, timeout=300, cwd=REPO,
    )


@pytest.mark.parametrize("target", ["directory", "latest"])
def test_serve_supervisor_stays_off_jax(tmp_path, target):
    """`serve --workers N` must reach `serve_multi_worker` — the process
    that forks the engine child, the one owner of the chip — with no
    backend initialized; given a bundle directory (the deployed form) it
    has not even imported jax. A registry lookup imports the module only."""
    registry = tmp_path / "registry"
    code = (
        "import sys\n"
        "import mlops_tpu.serve.frontend as frontend\n"
        "def probe(config, bundle_dir):\n"
        "    imported = 'jax' in sys.modules\n"
        "    if imported:\n"
        "        from jax._src import xla_bridge\n"
        "        assert not xla_bridge.backends_are_initialized()\n"
        "    print('JAX_IMPORTED', imported)\n"
        "    return 0\n"
        "frontend.serve_multi_worker = probe\n"
        "from mlops_tpu.cli import main\n"
        "raise SystemExit(main(sys.argv[1:]))\n"
    )
    model_directory = str(tmp_path) if target == "directory" else "latest"
    if target == "latest":
        # A registry with one registered version for "latest" to find.
        from mlops_tpu.bundle import ModelRegistry

        bundle = tmp_path / "bundle"
        bundle.mkdir()
        (bundle / "manifest.json").write_text("{}")
        ModelRegistry(registry).register("credit-default-uci-custom", bundle)
    proc = subprocess.run(
        [sys.executable, "-c", code, "serve", "--workers", "2",
         f"serve.model_directory={model_directory}",
         f"registry.root={registry}"],
        env=chip_smoke.child_env(), capture_output=True, text=True,
        timeout=300, cwd=REPO,
    )
    assert proc.returncode == 0, proc.stderr[-2000:]
    expected = "False" if target == "directory" else "True"
    assert f"JAX_IMPORTED {expected}" in proc.stdout


def test_encoder_phase_names_the_encoder(tmp_path):
    status = chip_smoke.phase_encoder(tmp_path)
    assert status["encoder"] == "c++" and status["gxx"] is True


# ------------------------------------------------------------ the rehearsal
@pytest.fixture(scope="module")
def rehearsal(tmp_path_factory):
    """train (tiny) -> bundle -> bodies -> CPU reference, with the compile
    cache root moved to a directory of this module's own."""
    out = tmp_path_factory.mktemp("chip-smoke")
    patch = pytest.MonkeyPatch()
    patch.setenv("JAX_COMPILATION_CACHE_DIR", str(out / "cache-root"))
    try:
        trained = chip_smoke.phase_train(out, overrides=TINY_TRAIN)
        bodies = chip_smoke.request_bodies(out)
        cpu = chip_smoke.CpuReference(trained["bundle"], bodies, out)
        try:
            reference = cpu.result()
        finally:
            cpu.stop()
        yield {"out": out, "trained": trained, "bodies": bodies,
               "reference": reference}
    finally:
        patch.undo()


def test_device_phase_reports_what_jax_reports():
    device = chip_smoke.phase_device()
    assert device == {"platform": "cpu", "kind": "cpu", "count": 8}


def test_train_phase_writes_a_bundle_and_the_loss_falls(rehearsal):
    trained = rehearsal["trained"]
    assert Path(trained["bundle"], "quant_params.npz").is_file()
    assert trained["losses"][-1] < trained["losses"][0]


def test_ring_plane_second_start_is_all_aot_hits_that_execute(rehearsal):
    """`serve --workers 2` twice against one cache root: the first start
    compiles and persists, the second is ALL hits — and a hit is executed
    at warmup before it counts (`compilecache/cache.py _runs`) — with no
    discard, nothing unrunnable, nothing unserializable at either."""
    r = rehearsal
    starts = [
        chip_smoke.phase_serve(
            r["trained"]["bundle"], r["bodies"], r["reference"], r["out"],
            workers=2, expect_all_hits=all_hits, tag=tag, overrides=TINY_SERVE,
        )
        for tag, all_hits in (("ring-1", False), ("ring-2", True))
    ]
    assert starts[0]["cache"]["misses"] > 0 and starts[0]["cache"]["hits"] == 0
    assert starts[1]["cache"]["hits"] == starts[0]["cache"]["misses"]
    # Same platform as the reference here, so the probabilities are equal.
    assert starts[1]["max_abs_prob_diff"]["bulk"] < 1e-6
    # Nothing of the caches was written outside the one root.
    root = r["out"] / "cache-root"
    assert (root / "aot-executables").is_dir()
    assert any(p.is_file() for p in root.iterdir())  # JAX's own entries


def test_single_process_plane_serves_and_matches_the_reference(rehearsal):
    r = rehearsal
    facts = chip_smoke.phase_serve(
        r["trained"]["bundle"], r["bodies"], r["reference"], r["out"],
        workers=1, expect_all_hits=False, tag="single", overrides=TINY_SERVE,
    )
    assert facts["max_abs_prob_diff"]["sample"] < 1e-6


def test_quant_phase_kernel_matches_composite_and_serves(rehearsal):
    r = rehearsal
    facts = chip_smoke.phase_quant(
        r["trained"]["bundle"], r["bodies"], r["out"], interpret=True,
        overrides=TINY_SERVE,
    )
    for worst in facts["kernel_vs_composite"].values():
        assert worst["probabilities"] < 1e-6  # interpret mode: an ulp


def test_kernels_phase_flash_matches_dense_in_interpret_mode():
    report = chip_smoke.phase_kernels(shapes=((1, 136, 2, 16),), interpret=True)
    assert report["flash"]["S136-float32"]["fwd_max_abs_diff"] < 1e-4
    assert set(report["flash"]) == {"S136-float32", "S136-bfloat16"}


def test_tp_phase_on_four_virtual_devices(tmp_path):
    """The `--chips 4` phase: the DP x TP CLI run on a (2, 2) mesh against
    one device, and every device holds parameter and batch bytes."""
    env = chip_smoke.child_env(
        XLA_FLAGS="--xla_force_host_platform_device_count=4",
        JAX_COMPILATION_CACHE_DIR=str(tmp_path / "cache-root"),
    )
    facts = chip_smoke.phase_tp(tmp_path, overrides=TINY_TP, env=env)
    assert facts["mesh"] == {"data": 2, "model": 2}
    assert len(facts["param_bytes_per_device"]) == 4
    assert facts["max_abs_loss_diff"] <= chip_smoke.TP_LOSS_ATOL
