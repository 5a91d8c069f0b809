"""The readings a cell's limits are set from, taken in one process:

    python3 benchmark/readings.py --workload <cell> --seeds 1,2,3 --seconds 5 --control 1

For each seed: the cell's set-up, a short window at the cell's own size,
then each compared number as the program reads (the lower reading), as
the control reads (the reference in the program's place, one precision
below what the configuration states: the upper reading), and as one
altered answer reads; and under ``correct`` what a run would say of each
of the three, held to the cell's own limits (``cells/<cell>.json``): the
program true, the control and the altered answer false, with the numbers
that fail them. One JSON object per seed on standard output. The
benchmark's own runs never call this.
"""

from __future__ import annotations

import argparse
import json
import sys
import time
from pathlib import Path

HERE = Path(__file__).resolve().parent
sys.path.insert(0, str(HERE.parent))


def main(argv=None, bench_root: Path = HERE.parent, require_chip: bool = True) -> int:
    from benchmark import run

    parser = argparse.ArgumentParser(description=__doc__.splitlines()[0])
    parser.add_argument("--workload", required=True)
    parser.add_argument("--seeds", required=True, help="comma-separated")
    parser.add_argument("--seconds", type=float, default=5.0)
    parser.add_argument("--control", type=int, choices=(0, 1), default=1)
    args = parser.parse_args(argv)

    loaded = run.load_cell(Path(bench_root), args.workload)
    import jax

    from mlops_tpu.compilecache.location import enable_persistent_cache

    enable_persistent_cache()
    if require_chip and jax.devices()[0].platform == "cpu":
        print("readings are taken on the chip", file=sys.stderr)
        return run.EXIT_NO_CHIP
    for seed in (int(s) for s in args.seeds.split(",")):
        ctx = run.Context(seed, loaded["cell"], loaded["config"], loaded["traffic"])
        driver = run.load_module(loaded["driver_file"]).build(ctx)
        driver.setup()
        driver.warmup()
        window = driver.window(args.seconds)
        driver.release()
        t0 = time.perf_counter()
        expected = driver.reference_outputs()
        line = {
            "workload": args.workload,
            "seed": seed,
            "units": window["attempted"],
            "metrics": window["metrics"],
            "program": driver.check(expected),
            "reference_s": time.perf_counter() - t0,
        }
        gaps = [job["predictions"] - expected["predictions"] for job in driver.jobs]
        line["diagnostics"] = {  # not compared: what the rms gap is made of
            "pred_mean_gap": float(gaps[0].mean()),
            "pred_centered_rms_gap": float((gaps[0] - gaps[0].mean()).std()),
            "prediction_std_over_rows": float(expected["predictions"].std()),
        }
        served = {"program": line["program"]}
        if args.control:
            served["control"] = line["control"] = driver.compare(
                driver.control_outputs(), expected
            )
            served["altered_answer"] = line["altered_answer"] = driver.compare(
                driver.altered(expected), expected
            )
        line["correct"], line["fails"] = {}, {}
        for who, numbers in served.items():
            compared, line["correct"][who] = run.judge(numbers, loaded["limits"])
            line["fails"][who] = [
                k for k, c in compared.items() if not c["value"] <= c["limit"]
            ]
        print(json.dumps(line), flush=True)
        del driver, expected
    return 0


if __name__ == "__main__":
    raise SystemExit(main())
