"""`readings.py` with ONE fault planted under the program (the reference is
left sound), at the cell's own size on the chip:

    python3 benchmark/faulted_readings.py --family falcon_h1 \\
        --fault state_not_carried --workload <cell> --seeds 1,2 --control 0

``faults/<family>.py`` names the family's faults (``OWN_FAULTS``). Every
other argument is `readings.py`'s; under ``correct.program`` stands what a
run of the FAULTED program would say, held to the cell's own limits. The
benchmark's own runs never call this.
"""

from __future__ import annotations

import argparse
import importlib
import sys
from pathlib import Path

HERE = Path(__file__).resolve().parent
sys.path.insert(0, str(HERE.parent))


def main(argv=None, **readings_kwargs) -> int:
    import pytest

    from benchmark import readings

    parser = argparse.ArgumentParser(description=__doc__.splitlines()[0])
    parser.add_argument("--family", required=True)
    parser.add_argument("--fault", required=True)
    args, rest = parser.parse_known_args(argv)
    faults = importlib.import_module(f"benchmark.faults.{args.family}").OWN_FAULTS
    with pytest.MonkeyPatch.context() as patch:
        faults[args.fault](patch)
        return readings.main(rest, **readings_kwargs)


if __name__ == "__main__":
    raise SystemExit(main())
