"""What the v5e's roofline allows the two attentions of
`mlops_tpu/models/exaone_moe.py`, both `mlops_tpu/ops/causal_attention.py
causal_attend`: over a sliding window (scope ``swa_attend``) and over
every key so far (scope ``gqa_attend``). Operations and bytes are REQUIRED
work from shapes alone, the same whatever implements the scopes. The
expert layer's roofline is ``rooflines/kimi_k2.py``'s (the same scopes of
`mlops_tpu/ops/moe_dispatch.py`, the same routing counter).

One attention layer of one history of S positions, H query heads over G
key/value heads of width e (``head_dim``, the configuration's own):

- operations: two per multiply-accumulate of the two products (2 e a key,
  query head and query) over the keys a query may see: position + 1 in a
  full layer, min(position + 1, window) in a window layer;
- bytes: q and o (S H e each), k and v (S G e each: read once, a group's
  query heads share them, and a window re-reads nothing) in bfloat16. In
  a last layer only the read positions' queries are required.

At the published widths (S 3,072, H 64, G 8, e 128, window 128) a window
layer is 12.62 GFLOP and 113.2 MB: bound by memory (0.138 ms at 819 GB/s
against 0.064 ms at 197 TFLOP/s); a full layer is 154.7 GFLOP over the
same bytes: bound by compute (0.785 ms).

The HBM bandwidth (819 GB/s) is ``rooflines/eva_attention.py``'s, with its
source; ``benchmark/peaks.py`` holds the bfloat16 peak (197 TFLOP/s).
"""

from benchmark import program_trace
from benchmark.flops.exaone_moe import attention_macs_per_key, keys_seen
from benchmark.rooflines.eva_attention import BF16, HBM_BYTES_PER_S
from benchmark.rooflines.kimi_k2 import chunk_runs, scope_seconds

SWA_SCOPES = ("swa_qkv", "swa_attend", "swa_o")
SWA, FULL = "sliding_attention", "full_attention"
ATTEND_SCOPE = {SWA: "swa_attend", FULL: "gqa_attend"}


def kinds(spec: dict) -> list[str]:
    """The attention of each layer the configuration runs."""
    mc = spec["model_config"]
    return list(mc["layer_types"][: mc["depth"]])


def attend_layer_work(spec: dict, records: int, layer: int) -> tuple[int, int]:
    """(operations, bytes) of the attention of ``layer`` over one history."""
    mc = spec["model_config"]
    per = int(spec["tokens_per_record"])
    seq = records * per
    last = layer == mc["depth"] - 1
    asked = range(per - 1, seq, per) if last else range(seq)
    operations = 2 * attention_macs_per_key(mc) * keys_seen(mc, layer, asked)
    wide, narrow = mc["heads"] * mc["head_dim"], mc["kv_heads"] * mc["head_dim"]
    moved = BF16 * (2 * len(asked) * wide + 2 * seq * narrow)
    return operations, moved


def attend_history_seconds(spec: dict, records: int, peaks: dict, kind: str) -> float:
    """The least time over the attention of every layer of ``kind`` for one
    history: per layer the larger of operations over the bfloat16 peak and
    bytes over the HBM bandwidth."""
    total = 0.0
    for layer, listed in enumerate(kinds(spec)):
        if listed == kind:
            operations, moved = attend_layer_work(spec, records, layer)
            total += max(operations / peaks["bf16_flops_per_s"], moved / HBM_BYTES_PER_S)
    return total


def attend_roofline_pct(facts: dict, kind: str):
    """The time the roofline allows the attention of ``kind`` over the
    device seconds its scope took in the traced window. The work is what
    the chunk program was GIVEN: every run of it that the device's trace
    shows in the window (counted there, as ``gqa_attend_roofline_pct``
    counts them) holds ``score_chunk_rows / records_per_history`` whole
    histories at the full length, padding included. ``None``, never 0,
    where the configuration is not this family's, no operation carries the
    scope, or the device kind has no peak."""
    spec = facts["config"]
    if spec["model_config"].get("family") != "exaone_moe":
        return None
    program, peaks = program_trace.load(facts), facts["peaks"]
    if program is None or peaks is None:
        return None
    seconds = scope_seconds(program, (ATTEND_SCOPE[kind],))
    runs = chunk_runs(facts["trace"])
    if not seconds or not runs:
        return None
    per = int(spec["records_per_history"])
    histories = int(spec["deployment"]["score_chunk_rows"]) // per
    return 100.0 * runs * histories * attend_history_seconds(spec, per, peaks, kind) / seconds
