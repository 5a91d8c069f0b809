"""What the v5e's roofline allows ``eva_prep_kv`` + ``eva_attend``
(`mlops_tpu/ops/eva_attention.py`): operations and bytes from shapes
alone, the same whatever implements the two scopes.

One layer of one history of S positions, H heads of D (d = H D):

- operations: two per multiply-accumulate of the two attention products
  (q . k and weights . v, 2 D a key, head and query), over the keys a
  query REQUIRES: the causal half of its own window and the summaries of
  the whole windows before it (``benchmark/flops/evabyte.py
  attention_keys``). The summaries' own weighted sums (3 S d
  multiply-adds on the vector unit) are left out, so the share reads a
  little low, never high;
- bytes: q, k, v read and o written once in bfloat16 (4 S d x 2 bytes),
  the summaries written once and read once (2 x 2 (S / chunk) d x 2
  bytes).

In the last layer only the read positions' queries are required (their o
rows are the only ones written); k, v and the summaries are whole.

HBM_BYTES_PER_S: Google Cloud documentation, "TPU v5e" (system
architecture): 16 GB of HBM2e at 819 GB/s a chip; ``benchmark/peaks.py``
holds the bfloat16 peak (197 TFLOP/s) from the same page.
"""

from benchmark.flops.evabyte import attention_keys

HBM_BYTES_PER_S = 819e9
BF16 = 2
SCOPES = ("eva_prep_kv", "eva_attend")


def scope_seconds(program: dict) -> float:
    """Device seconds of the operations under either scope, from
    ``program_trace``'s reduction (innermost operations, so a ``while``
    and its body are counted once)."""
    return sum(
        seconds
        for scope, seconds in program["device_by_scope"]
        if any(name in scope.split("/") for name in SCOPES)
    )


def layer_work(spec: dict, records: int, last: bool) -> tuple[int, int]:
    """(operations, bytes) of the two scopes in one layer of one history."""
    mc = spec["model_config"]
    d, window, chunk = mc["token_dim"], mc["attn_window"], mc["attn_chunk"]
    seq = records * spec["record_bytes"]
    if last:
        queries = [r * spec["record_bytes"] - 1 for r in range(1, records + 1)]
    else:
        queries = range(seq)
    keys = sum(attention_keys(p, window, chunk) for p in queries)
    operations = 2 * 2 * d * keys
    summaries = 2 * 2 * (seq // chunk) * d * BF16
    moved = (2 * seq + 2 * len(queries)) * d * BF16 + summaries
    return operations, moved


def history_seconds(spec: dict, records: int, peaks: dict) -> float:
    """The least time the chip could take over the two scopes for one
    history through every layer: per layer the larger of operations over
    the bfloat16 peak and bytes over the HBM bandwidth."""
    depth = spec["model_config"]["depth"]
    total = 0.0
    for layer in range(depth):
        operations, moved = layer_work(spec, records, last=layer == depth - 1)
        total += max(operations / peaks["bf16_flops_per_s"], moved / HBM_BYTES_PER_S)
    return total
