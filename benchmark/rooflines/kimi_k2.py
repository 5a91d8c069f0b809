"""What the v5e's roofline allows the two kernels-to-be of
`mlops_tpu/models/kimi_k2.py`: the causal attention (scope ``mla_attend``,
`mlops_tpu/ops/mla.py`) and the routed experts' grouped products (scope
``experts``, `mlops_tpu/ops/moe_dispatch.py`). Operations and bytes are
REQUIRED work, from shapes and from the program's routing counter, the
same whatever implements the scopes.

``mla_attend``, one layer of one history of S positions, H heads, query /
key width e = nope + rope, value width v:

- operations: two per multiply-accumulate of the two products (e + v a
  key, head and query) over the position + 1 keys a query may see;
- bytes: q and k (S H e each), v and o (S H v each) once in bfloat16.
  In the last layer only the read positions' queries are required (their
  o rows are the only ones written); k and v are whole.

``experts``, one expert layer over a window's jobs, a = the assignments
the routing counter says fell on held experts, r = the (run, expert) pairs
in which a held expert got a token (the counter's ``expert_runs``):

- operations: 2 x 3 d f a (gate, up, down of width f);
- bytes: an expert's three matrices (3 d f, bfloat16) once for each of the
  r, the gathered rows read and the result rows written once (2 a d,
  bfloat16). What is kept between the three products is not counted.

The HBM bandwidth (819 GB/s) is ``rooflines/eva_attention.py``'s, with its
source; ``benchmark/peaks.py`` holds the bfloat16 peak (197 TFLOP/s).
"""

import functools
import os
import tempfile
from pathlib import Path

from benchmark import program_trace
from benchmark.flops.kimi_k2 import attention_macs_per_key
from benchmark.rooflines.eva_attention import BF16, HBM_BYTES_PER_S

MLA_SCOPES = ("mla_q", "mla_kv", "mla_attend", "mla_o")
MOE_SCOPES = ("router", "moe_dispatch", "experts", "moe_combine", "shared_expert")
CHUNK_PROGRAM = "jit_fused"  # `parallel/bulk.py make_bulk_fused`'s programs
# `jax.lax.ragged_dot` on a TPU: the compiler makes it one Mosaic call and
# names it itself (``ragged-dot-none``), so the operation carries NO scope
# of the program's; it is found by its kind, and belongs to ``experts``
GROUPED_PRODUCT = "ragged-dot"


def scope_seconds(program: dict, scopes: tuple[str, ...]) -> float:
    """Device seconds of the operations under any of ``scopes``, from
    ``program_trace``'s reduction (innermost operations, so a ``while``
    and its body are counted once)."""
    return sum(
        seconds
        for scope, seconds in program["device_by_scope"]
        if any(name in scope.split("/") for name in scopes)
    )


@functools.lru_cache(maxsize=4)
def _kind_seconds(path: Path, prefix: str) -> float:
    """One profile's answer; kept, since two readers ask it of one run."""
    flat = program_trace.load_profile(path)
    window = [
        (start, start + dur)
        for plane in flat["planes"] if not program_trace.DEVICE_PLANE.match(plane["name"])
        for line in plane["lines"] for name, start, dur, _ in line["events"]
        if name == program_trace.HARNESS_PREFIX + program_trace.WINDOW
    ]
    lo, hi = window[0]
    devices = [
        sum(
            max(0, min(start + dur, hi) - max(start, lo))
            for line in plane["lines"] for kind, start, dur, _ in line["events"]
            if kind.startswith(prefix)
        )
        for plane in flat["planes"] if program_trace.DEVICE_PLANE.match(plane["name"])
    ]
    return sum(devices) / 1e9 / max(1, len(devices))


def kind_seconds(facts: dict, prefix: str) -> float:
    """Device seconds (the mean over devices) inside the traced window of
    the operations whose kind starts with ``prefix``: for an operation that
    carries no scope. The profile is found as ``program_trace.load`` finds
    it; 0 where there is none."""
    if facts["trace"] is None:
        return 0.0
    found = Path(tempfile.gettempdir()).glob("bench-trace-*/plugins/profile/*/*.xplane.pb")
    for path in sorted(found, key=lambda p: p.stat().st_mtime, reverse=True):
        if program_trace._reduced(path, os.getpid()) is not None:
            return _kind_seconds(path, prefix)
    return 0.0


def experts_scope_seconds(facts: dict, program: dict) -> float:
    """``experts``: what carries the scope (the gate's activation times the
    up product) and the grouped products themselves, which carry none."""
    return scope_seconds(program, ("experts",)) + kind_seconds(facts, GROUPED_PRODUCT)


def chunk_runs(trace: dict) -> int:
    """Runs of the bulk chunk program in the traced window, counted on the
    device's ``XLA Modules`` line (``trace_reduce``'s ``programs``), not
    reckoned from the job's size."""
    return sum(1 for name, _, _ in trace["programs"] if name.startswith(CHUNK_PROGRAM))


def attend_layer_work(spec: dict, records: int, last: bool) -> tuple[int, int]:
    """(operations, bytes) of ``mla_attend`` in one layer of one history."""
    mc = spec["model_config"]
    per = int(spec["tokens_per_record"])
    seq = records * per
    heads = mc["heads"]
    wide_qk = mc["qk_nope_head_dim"] + mc["qk_rope_head_dim"]
    if last:
        queries, keys = records, sum(r * per for r in range(1, records + 1))
    else:
        queries, keys = seq, seq * (seq + 1) // 2
    operations = 2 * attention_macs_per_key(mc) * keys
    moved = heads * BF16 * ((queries + seq) * wide_qk + (queries + seq) * mc["v_head_dim"])
    return operations, moved


def attend_history_seconds(spec: dict, records: int, peaks: dict) -> float:
    """The least time the chip could take over ``mla_attend`` for one
    history through every layer: per layer the larger of operations over
    the bfloat16 peak and bytes over the HBM bandwidth."""
    depth = spec["model_config"]["depth"]
    total = 0.0
    for layer in range(depth):
        operations, moved = attend_layer_work(spec, records, last=layer == depth - 1)
        total += max(operations / peaks["bf16_flops_per_s"], moved / HBM_BYTES_PER_S)
    return total


def experts_layer_work(spec: dict, assignments: int, expert_runs: int) -> tuple[int, int]:
    """(operations, bytes) of ``experts`` in one layer over a window."""
    mc = spec["model_config"]
    d, f = mc["token_dim"], mc["moe_ffn_dim"]
    operations = 2 * 3 * d * f * assignments
    moved = BF16 * (3 * d * f * expert_runs + 2 * d * assignments)
    return operations, moved


def experts_seconds(spec: dict, jobs: list[dict], peaks: dict) -> float | None:
    """The least time over ``experts`` for the window's jobs, from each
    job's routing counter (``per_layer``, ``expert_runs_per_layer``);
    ``None`` where no job carries one."""
    counted = [job["routing"] for job in jobs if job.get("routing")]
    if not counted:
        return None
    total = 0.0
    for routing in counted:
        for row, active in zip(routing["per_layer"], routing["expert_runs_per_layer"]):
            operations, moved = experts_layer_work(spec, sum(row), sum(active))
            total += max(operations / peaks["bf16_flops_per_s"], moved / HBM_BYTES_PER_S)
    return total
