"""bench.py's one-JSON-line contract under failure.

The driver parses the LAST stdout line of `python bench.py` as JSON
(`BENCH_r{N}.json`); round 1 lost its benchmark to a crash that printed
a traceback instead. The contract is now: ANY failure still emits one
parseable line with an ``error`` field and a nonzero exit code.
"""

import json
import subprocess
import sys
from pathlib import Path

REPO = Path(__file__).resolve().parent.parent


def test_forced_failure_still_emits_one_json_line():
    proc = subprocess.run(
        [sys.executable, str(REPO / "bench.py")],
        env={
            "PATH": "/usr/bin:/bin:/usr/local/bin",
            "HOME": "/tmp",
            "JAX_PLATFORMS": "bogus-backend",
        },
        capture_output=True,
        text=True,
        timeout=300,
        cwd=REPO,
    )
    assert proc.returncode != 0
    lines = [l for l in proc.stdout.strip().splitlines() if l.strip()]
    assert lines, f"no stdout at all; stderr:\n{proc.stderr[-500:]}"
    payload = json.loads(lines[-1])
    assert payload["metric"] == "inference_p50_latency_ms"
    assert payload["value"] is None
    assert payload["vs_baseline"] == 0.0
    assert "bogus-backend" in payload["error"]


def test_a_failed_stage_fails_the_run():
    """Any stage that lands in an ``*_error`` key makes the exit code
    non-zero (main() returns 1 when `_failed_stages` is non-empty)."""
    sys.path.insert(0, str(REPO))
    import bench

    assert bench._failed_stages({"value": 1.0, "p99_ms": 2.0}) == []
    assert bench._failed_stages(
        {"value": 1.0, "mfu_bulk_error": "X", "engine_respawn_error": "Y"}
    ) == ["engine_respawn_error", "mfu_bulk_error"]


def test_no_accelerator_without_an_explicit_cpu_request_fails():
    """With no chip and no explicit JAX_PLATFORMS=cpu the bench fails: it
    never prints CPU figures by accident."""
    proc = subprocess.run(
        [sys.executable, str(REPO / "bench.py")],
        env={"PATH": "/usr/bin:/bin:/usr/local/bin", "HOME": "/tmp"},
        capture_output=True,
        text=True,
        timeout=300,
        cwd=REPO,
    )
    assert proc.returncode != 0
    payload = json.loads(proc.stdout.strip().splitlines()[-1])
    assert payload["value"] is None and payload["error"]


def test_wall_watchdog_emits_json_on_midrun_stall():
    """A mid-run device stall (a hang AFTER a healthy init) must not
    hang the driver: the wall watchdog prints the error line and
    hard-exits. Simulated with a 1-second budget on the CPU backend."""
    proc = subprocess.run(
        [sys.executable, str(REPO / "bench.py")],
        env={
            "PATH": "/usr/bin:/bin:/usr/local/bin",
            "HOME": "/tmp",
            "JAX_PLATFORMS": "cpu",
            "BENCH_WALL_TIMEOUT_S": "1",
        },
        capture_output=True,
        text=True,
        timeout=120,
        cwd=REPO,
    )
    assert proc.returncode != 0
    lines = [l for l in proc.stdout.strip().splitlines() if l.strip()]
    assert lines, f"no stdout at all; stderr:\n{proc.stderr[-500:]}"
    payload = json.loads(lines[-1])
    assert payload["value"] is None
    assert "wall timeout" in payload["error"]
