"""What the v5e's roofline allows the two token mixers' cores of
`mlops_tpu/models/falcon_h1.py`: the selective state-space scan (scope
``ssm_scan``, `mlops_tpu/ops/ssd.py`) and the causal grouped-query
attention (scope ``gqa_attend``, `mlops_tpu/ops/gqa_attention.py`).
Operations and bytes are REQUIRED work from shapes alone, the same
whatever implements the scopes.

``ssm_scan``, one layer of one history of S positions, H heads of P
channels with a state of N, B and C shared by G groups:

- operations: two per multiply-accumulate of the RECURRENCE's three
  products a position and head, P N each (``dt x`` against ``B``, the
  decay on the state, the state against ``C``); the chunked form's
  products are more and count for nothing here;
- bytes: x (S H P), B and C (S G N each) and dt (S H) read once, y (S H P)
  written once, in bfloat16. In a last layer the state still moves at
  every position (two of the three products; x, B, dt read) and the third
  product, C and y are required at the read positions alone.

At the published widths (S 3,072, H 32, P 128, N 256, G 2) a layer is
19.33 GFLOP and 56.8 MB: bound by compute (0.098 ms at 197 TFLOP/s
against 0.069 ms at 819 GB/s), `binds` says so from the shapes.

``gqa_attend``: as ``rooflines/exaone_moe.py``'s full layer, the causal
half's two products of every query head (position + 1 keys a query), q
and o, k and v read or written once in bfloat16, the last layer at its
read positions; at 20 heads over 4 of 128 and S 3,072 a layer is 48.3
GFLOP and 37.7 MB: bound by compute.

The HBM bandwidth (819 GB/s) is ``rooflines/eva_attention.py``'s, with its
source; ``benchmark/peaks.py`` holds the bfloat16 peak (197 TFLOP/s).
"""

from benchmark import program_trace
from benchmark.flops.falcon_h1 import attention_macs_per_key, recurrence_macs
from benchmark.rooflines.eva_attention import BF16, HBM_BYTES_PER_S
from benchmark.rooflines.kimi_k2 import chunk_runs, scope_seconds

SSM_SCOPES = ("ssm_in", "ssm_conv", "ssm_scan", "ssm_out")
FAMILY = "falcon_h1"


def _asked(spec: dict, records: int, layer: int) -> tuple[int, range]:
    """(positions of a history, the positions ``layer`` answers at)."""
    per = int(spec["tokens_per_record"])
    seq = records * per
    last = layer == spec["model_config"]["depth"] - 1
    return seq, range(per - 1, seq, per) if last else range(seq)


def scan_layer_work(spec: dict, records: int, layer: int) -> tuple[int, int]:
    """(operations, bytes) of ``ssm_scan`` in ``layer`` over one history."""
    mc = spec["model_config"]
    seq, asked = _asked(spec, records, layer)
    operations = 2 * recurrence_macs(mc) * (2 * seq + len(asked))
    shared = mc["ssm_groups"] * mc["ssm_state"]
    moved = BF16 * (
        seq * (mc["ssm_dim"] + shared + mc["ssm_heads"])  # x, B, dt
        + len(asked) * (shared + mc["ssm_dim"])  # C, y
    )
    return operations, moved


def attend_layer_work(spec: dict, records: int, layer: int) -> tuple[int, int]:
    """(operations, bytes) of ``gqa_attend`` in ``layer`` over one history."""
    mc = spec["model_config"]
    seq, asked = _asked(spec, records, layer)
    operations = 2 * attention_macs_per_key(mc) * sum(p + 1 for p in asked)
    wide, narrow = mc["heads"] * mc["head_dim"], mc["kv_heads"] * mc["head_dim"]
    return operations, BF16 * (2 * len(asked) * wide + 2 * seq * narrow)


def binds(work: tuple[int, int], peaks: dict) -> str:
    """Which term of the roofline is the larger for (operations, bytes)."""
    operations, moved = work
    by_compute = operations / peaks["bf16_flops_per_s"] >= moved / HBM_BYTES_PER_S
    return "compute" if by_compute else "memory"


def history_seconds(spec: dict, records: int, peaks: dict, layer_work) -> float:
    """The least time over ``layer_work``'s scope for one history through
    every layer: per layer the larger of operations over the bfloat16 peak
    and bytes over the HBM bandwidth."""
    total = 0.0
    for layer in range(spec["model_config"]["depth"]):
        operations, moved = layer_work(spec, records, layer)
        total += max(operations / peaks["bf16_flops_per_s"], moved / HBM_BYTES_PER_S)
    return total


def roofline_pct(facts: dict, scope: str, layer_work):
    """The time the roofline allows ``scope`` over the device seconds it
    took in the traced window. The work is what the chunk program was
    GIVEN: every run of it that the device's trace shows in the window
    (counted there, not reckoned from the job's size) holds
    ``score_chunk_rows / records_per_history`` whole histories at the full
    length, padding included. ``None``, never 0, where the configuration
    is not this family's, no operation carries the scope, no run of the
    chunk program is in the trace or the device kind has no peak."""
    spec = facts["config"]
    if spec["model_config"].get("family") != FAMILY:
        return None
    program, peaks = program_trace.load(facts), facts["peaks"]
    if program is None or peaks is None:
        return None
    seconds = scope_seconds(program, (scope,))
    runs = chunk_runs(facts["trace"])
    if not seconds or not runs:
        return None
    per = int(spec["records_per_history"])
    histories = int(spec["deployment"]["score_chunk_rows"]) // per
    return 100.0 * runs * histories * history_seconds(spec, per, peaks, layer_work) / seconds
