"""Tests of the benchmark's own code. Run on the CPU, by name:

    JAX_PLATFORMS=cpu python -m pytest benchmark/tests -q

``tiny_root`` is the real ``BENCHMARK.json`` with every configuration and
traffic mix cut to a CPU rehearsal's size by the ``rehearsal`` overrides
each real file carries: made from the real files at each run, so it cannot
drift from them, and a later PR's cell is rehearsed without an edit here.
"""

import json
import os
import sys
from pathlib import Path

import pytest

os.environ.setdefault("JAX_PLATFORMS", "cpu")
ROOT = Path(__file__).resolve().parents[2]
if str(ROOT) not in sys.path:
    sys.path.insert(0, str(ROOT))

BENCH = json.loads((ROOT / "BENCHMARK.json").read_text())
CELLS = [w["name"] for w in BENCH["workloads"]]
CONFIGS = [c["name"] for c in BENCH["configs"]]


def cut(real: dict) -> dict:
    """A real data file with its own ``rehearsal`` overrides applied: tiny
    widths and sizes (and float32, so that the rehearsal's limits can be
    tight and the control and the faults stand out); never a cell."""
    out = {k: v for k, v in real.items() if k != "rehearsal"}
    for key, value in real["rehearsal"].items():
        if isinstance(value, dict):
            out[key] = {**out[key], **value}
        elif key != "what":
            out[key] = value
    return out


@pytest.fixture(scope="session")
def tiny_root(tmp_path_factory):
    root = tmp_path_factory.mktemp("tiny-bench")
    bench = json.loads(json.dumps(BENCH))
    bench["paths"] = ["tiny"]
    for sub in ("configs", "traffic", "cells"):
        (root / "tiny" / sub).mkdir(parents=True)
    for entry in bench["configs"]:
        spec = cut(json.loads((ROOT / entry["file"]).read_text()))
        entry["file"] = f"tiny/configs/{entry['name']}.json"
        (root / entry["file"]).write_text(json.dumps(spec))
    real = ROOT / "benchmark"
    for cell in bench["workloads"]:
        traffic = cut(json.loads((real / "traffic" / f"{cell['traffic']}.json").read_text()))
        (root / "tiny" / "traffic" / f"{cell['traffic']}.json").write_text(json.dumps(traffic))
        limits = json.loads((real / "cells" / f"{cell['name']}.json").read_text())
        (root / "tiny" / "cells" / f"{cell['name']}.json").write_text(
            json.dumps({"limits": limits["rehearsal_limits"]})
        )
    (root / "BENCHMARK.json").write_text(json.dumps(bench))
    return root


@pytest.fixture(scope="session")
def tiny_files(tiny_root):
    """name -> (configuration, one of its cells' traffic), at the tiny size."""
    bench = json.loads((tiny_root / "BENCHMARK.json").read_text())
    out = {}
    for entry in bench["configs"]:
        cell = next(w for w in bench["workloads"] if w["config"] == entry["name"])
        out[entry["name"]] = (
            json.loads((tiny_root / entry["file"]).read_text()),
            json.loads((tiny_root / "tiny" / "traffic" / f"{cell['traffic']}.json").read_text()),
        )
    return out
