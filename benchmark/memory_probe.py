"""Is the scratch that ``memory_peak_bytes`` counts really held? Run by hand
on the chip (it is not part of a benchmark run):

    python3 benchmark/memory_probe.py --workload bert-base.bulk --gib 0,4,5,6,7

The TPU runtime books a program's temporaries under ``peak_bytes_reserved``
and not under ``peak_bytes_in_use``. If they are held while the program
runs, the program cannot run once other buffers leave less than that much
free. So for each size the probe puts that many GiB of ballast on the
device, runs the cell's own warm-up job (one ``score_dataset`` call over
one chunk, the timed path's program) and prints whether it ran, with the
runtime's readings. One JSON object per size on standard output.
"""

from __future__ import annotations

import argparse
import json
import sys
from pathlib import Path

HERE = Path(__file__).resolve().parent
sys.path.insert(0, str(HERE.parent))
KEYS = ("bytes_in_use", "peak_bytes_in_use", "peak_bytes_reserved", "bytes_limit")


def main(argv=None) -> int:
    from benchmark import run

    parser = argparse.ArgumentParser(description=__doc__.splitlines()[0])
    parser.add_argument("--workload", required=True)
    parser.add_argument("--gib", required=True, help="comma-separated ballast sizes")
    parser.add_argument("--seed", type=int, default=1)
    args = parser.parse_args(argv)

    loaded = run.load_cell(HERE.parent, args.workload)
    import jax
    import jax.numpy as jnp

    from mlops_tpu.compilecache.location import enable_persistent_cache

    enable_persistent_cache()
    device = jax.devices()[0]
    if device.platform == "cpu":
        print("the probe is for the chip", file=sys.stderr)
        return run.EXIT_NO_CHIP
    ctx = run.Context(args.seed, loaded["cell"], loaded["config"], loaded["traffic"])
    driver = run.load_module(loaded["driver_file"]).build(ctx)
    driver.setup()
    driver.warmup()  # compiles; the sized runs below find every program
    for gib in (float(g) for g in args.gib.split(",")):
        ballast = jnp.zeros((int(gib * 2**30),), jnp.uint8)
        ballast.block_until_ready()
        line = {"ballast_gib": gib, "before": {k: device.memory_stats().get(k) for k in KEYS}}
        try:
            driver.warmup()
            line["ran"] = True
        except Exception as exc:  # the runtime's refusal is the answer
            line["ran"], line["error"] = False, str(exc).splitlines()[0][:300]
        line["after"] = {k: device.memory_stats().get(k) for k in KEYS}
        print(json.dumps(line), flush=True)
        del ballast
    return 0


if __name__ == "__main__":
    raise SystemExit(main())
