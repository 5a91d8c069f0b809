"""Occupancy of the busiest HOST-WORK stage of the streaming executor
(slice, transfer, store) in ``BulkScoreResult.pipeline``, as a share of the
sweep, averaged over the window's jobs. Near 100 means host work sets the
sweep's pace. ``fetch`` is left out: its ``device_get`` waits for the
device, so it reads near 100 in every job whatever sets the pace (0.97 to
1.00 in both cells, chip runs of PR 24). All stages are printed."""

import sys

HOST_WORK_STAGES = ("slice", "transfer", "store")


def read(facts):
    jobs = facts["driver"].jobs
    if not jobs:
        return None
    shares = []
    for job in jobs:
        stages = job["stages"]
        print(
            "stage occupancy: "
            + ", ".join(f"{k}={v['occupancy']:.3f}" for k, v in stages.items()),
            file=sys.stderr,
        )
        shares.append(max(stages[s]["occupancy"] for s in HOST_WORK_STAGES if s in stages))
    return 100.0 * sum(shares) / len(shares)
