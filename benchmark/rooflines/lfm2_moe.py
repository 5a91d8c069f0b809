"""What the v5e's roofline allows the two token mixers of
`mlops_tpu/models/lfm2_moe.py`: the gated short convolution (scope
``short_conv``, `mlops_tpu/ops/short_conv.py`) and the causal
grouped-query attention (scope ``gqa_attend``,
`mlops_tpu/ops/causal_attention.py`). Operations and bytes are REQUIRED
work from shapes alone, the same whatever implements the scopes. The
expert layer's roofline is ``rooflines/kimi_k2.py``'s (the same scopes of
`mlops_tpu/ops/moe_dispatch.py`, the same routing counter).

``short_conv``, one convolution layer of one chunk run of T tokens, hidden
size d: the operator is elementwise (two gates, ``conv_width`` taps), so
it is bound by memory; its least traffic is ONE pass, the input
projection's output ``[T, 3 d]`` read and the output projection's operand
``[T, d]`` written, at the products' dtype (bfloat16). The last layer is
counted whole, as the program computes it: it reads 64 positions a
history there, and the share is a little high, by under 1/12, never over
what one pass allows a layer that runs whole.

``gqa_attend``, one attention layer of one history of S positions, H
query heads over G key/value heads of width e:

- operations: two per multiply-accumulate of the two products (2 e a key,
  query head and query) over the position + 1 keys a query may see;
- bytes: q and o (S H e each), k and v (S G e each: read once, a group's
  query heads share them) in bfloat16. In a last layer only the read
  positions' queries are required.

The HBM bandwidth (819 GB/s) is ``rooflines/eva_attention.py``'s, with its
source; ``benchmark/peaks.py`` holds the bfloat16 peak (197 TFLOP/s).
"""

from benchmark.flops.lfm2_moe import attention_macs_per_key
from benchmark.rooflines.eva_attention import BF16, HBM_BYTES_PER_S

CONV_SCOPES = ("conv_in", "short_conv", "conv_out")
GQA_SCOPES = ("gqa_qkv", "gqa_attend", "gqa_o")


def mixers(spec: dict) -> list[str]:
    """The token mixer of each layer the configuration runs."""
    mc = spec["model_config"]
    return list(mc["layer_types"][: mc["depth"]])


def short_conv_run_seconds(spec: dict, tokens: int) -> float:
    """The least time over ``short_conv`` for one chunk run of ``tokens``
    tokens through every convolution layer."""
    d = spec["model_config"]["token_dim"]
    moved = tokens * (3 * d + d) * BF16
    return mixers(spec).count("conv") * moved / HBM_BYTES_PER_S


def attend_layer_work(spec: dict, records: int, last: bool) -> tuple[int, int]:
    """(operations, bytes) of ``gqa_attend`` in one layer of one history."""
    mc = spec["model_config"]
    per = int(spec["tokens_per_record"])
    seq = records * per
    d = mc["token_dim"]
    kv = mc["kv_heads"] * (d // mc["heads"])
    if last:
        queries, keys = records, sum(r * per for r in range(1, records + 1))
    else:
        queries, keys = seq, seq * (seq + 1) // 2
    operations = 2 * attention_macs_per_key(mc) * keys
    moved = BF16 * (2 * queries * d + 2 * seq * kv)
    return operations, moved


def attend_history_seconds(spec: dict, records: int, peaks: dict) -> float:
    """The least time over ``gqa_attend`` for one history through every
    attention layer: per layer the larger of operations over the bfloat16
    peak and bytes over the HBM bandwidth."""
    kinds = mixers(spec)
    total = 0.0
    for layer, kind in enumerate(kinds):
        if kind != "full_attention":
            continue
        operations, moved = attend_layer_work(spec, records, last=layer == len(kinds) - 1)
        total += max(operations / peaks["bf16_flops_per_s"], moved / HBM_BYTES_PER_S)
    return total
