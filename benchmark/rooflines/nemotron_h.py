"""What the v5e's roofline allows three parts of
`mlops_tpu/models/nemotron_h.py`: the selective state-space scan of its
mixer layers (scope ``ssm_scan``, `mlops_tpu/ops/ssd.py`), the causal
grouped-query attention of its attention layers (scope ``gqa_attend``,
`mlops_tpu/ops/gqa_attention.py`) and the routed ReLU² experts' grouped
products (scope ``relu2_experts``, `mlops_tpu/ops/expert_products.py`'s
two kernels). Operations and bytes are REQUIRED work, from shapes and from
the program's routing counter, the same whatever implements the scopes.

The scan's and the attention's work is what the chunk program was GIVEN,
padding included: over the window's jobs (``benchmark/job_log.py``) each
run's own rows (``rows_run``: the full runs and the smaller padded tail),
``records_per_history`` rows a whole history at the full length.

``ssm_scan``, one mixer layer of one history: ``rooflines/falcon_h1.py
scan_layer_work`` (the recurrence's three products a position and head; x,
B, C, dt read once and y written once in bfloat16). At these shapes (S
3,072, H 64, P 64, N 128, G 8) a layer is 9.66 GFLOP and 63.3 MB: bound
by memory (0.077 ms at 819 GB/s against 0.049 ms at 197 TFLOP/s), where
Falcon-H1's (H 32, P 128, N 256, G 2) is bound by compute.

``gqa_attend``, one attention layer of one history: ``rooflines/falcon_h1.py
attend_layer_work`` (the causal half's two products of every query head,
q and o, k and v read or written once in bfloat16, the last layer at its
read positions); at 32 query heads over 2 of 128 and S 3,072 a full
layer is 77.3 GFLOP and 53.5 MB: bound by compute (0.393 ms against 0.065
ms), and the last layer, at its 64 read positions, 1.64 GFLOP.

``relu2_experts``, one expert layer over a window's jobs, a = the
assignments the routing counter says fell on held experts, r = the (run,
expert) pairs in which a held expert got a token (``expert_runs``):

- operations: 2 x 2 d f a (up and down of width f: no gate);
- bytes: an expert's two matrices (2 d f, bfloat16) once for each of the
  r, the gathered rows read and the result rows written once (2 a d,
  bfloat16). What is kept between the two products is not counted.

At a run of 8 histories (73,728 assignments on 64 held experts) a layer
is 1.47 TFLOP and 2.07 GB: bound by compute (7.5 ms against 2.5 ms).

The HBM bandwidth (819 GB/s) is ``rooflines/eva_attention.py``'s, with its
source; ``benchmark/peaks.py`` holds the bfloat16 peak (197 TFLOP/s).
"""

from benchmark import job_log, program_trace
from benchmark.flops.nemotron_h import kinds
from benchmark.rooflines.eva_attention import BF16, HBM_BYTES_PER_S
from benchmark.rooflines.kimi_k2 import GROUPED_PRODUCT, kind_seconds, scope_seconds

FAMILY = "nemotron_h"


def _least(work: tuple[int, int], peaks: dict) -> float:
    operations, moved = work
    return max(operations / peaks["bf16_flops_per_s"], moved / HBM_BYTES_PER_S)


def history_seconds(spec: dict, records: int, peaks: dict, kind: str, layer_work) -> float:
    """The least time over ``layer_work``'s scope for one history through
    every layer of ``kind``."""
    return sum(
        _least(layer_work(spec, records, layer), peaks)
        for layer, listed in enumerate(kinds(spec)) if listed == kind
    )


def histories_run(facts: dict) -> int | None:
    """Whole histories the chunk program was given over the window's jobs,
    each run counted at its own size (``rows_run``; a record without it
    ran ``chunks`` runs of ``chunk_rows``); ``None`` without a job log."""
    jobs = job_log.load(facts)
    if jobs is None or not jobs["window"]:
        return None
    per = int(facts["config"]["records_per_history"])
    return sum(
        record.get("rows_run", record["chunks"] * record["chunk_rows"]) // per
        for record in jobs["window"]
    )


def experts_layer_work(spec: dict, assignments: int, expert_runs: int) -> tuple[int, int]:
    """(operations, bytes) of ``relu2_experts`` in one layer over a window."""
    mc = spec["model_config"]
    d, f = mc["token_dim"], mc["moe_ffn_dim"]
    return 2 * 2 * d * f * assignments, BF16 * (2 * d * f * expert_runs + 2 * d * assignments)


def experts_seconds(spec: dict, jobs: list[dict], peaks: dict) -> float | None:
    """The least time over ``relu2_experts`` for the window's jobs, from each
    job's routing counter; ``None`` where no job carries one."""
    counted = [job["routing"] for job in jobs if job.get("routing")]
    if not counted:
        return None
    return sum(
        _least(experts_layer_work(spec, sum(row), sum(active)), peaks)
        for routing in counted
        for row, active in zip(routing["per_layer"], routing["expert_runs_per_layer"])
    )


def given_roofline_pct(facts: dict, scope: str, kind: str, layer_work):
    """The time the roofline allows ``scope`` in every layer of ``kind``
    over the device seconds the scope took in the traced window, the work
    `histories_run` whole histories. ``None``, never 0, where the
    configuration is not this family's, no operation carries the scope, the
    program keeps no job log or the device has no peak."""
    spec = facts["config"]
    if spec["model_config"].get("family") != FAMILY:
        return None
    program, peaks = program_trace.load(facts), facts["peaks"]
    if program is None or peaks is None:
        return None
    seconds = scope_seconds(program, (scope,))
    histories = histories_run(facts)
    if not seconds or not histories:
        return None
    per = int(spec["records_per_history"])
    return 100.0 * histories * history_seconds(spec, per, peaks, kind, layer_work) / seconds


def experts_roofline_pct(facts: dict):
    """The time the roofline allows ``relu2_experts`` (and any grouped
    product of the compiler's own, which carries no scope) over the device
    seconds they took in the traced window. ``None``, never 0, as
    `given_roofline_pct`, or where no job carries a routing counter."""
    spec = facts["config"]
    if spec["model_config"].get("family") != FAMILY:
        return None
    program, peaks = program_trace.load(facts), facts["peaks"]
    if program is None or peaks is None:
        return None
    seconds = scope_seconds(program, ("relu2_experts",)) + kind_seconds(facts, GROUPED_PRODUCT)
    allowed = experts_seconds(spec, getattr(facts["driver"], "jobs", []), peaks)
    if not seconds or not allowed:
        return None
    return 100.0 * allowed / seconds
