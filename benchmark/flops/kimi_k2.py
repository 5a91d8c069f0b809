"""``models/kimi_k2.py KimiK2Scorer``: matrix-multiply operations a record
needs = those of a whole history of ``records_per_history`` records / that
many records. Only what the answer REQUIRES is counted, so that a program
that skips the rest reads no higher than one that does not:

- every layer but the last, every position: the five MLA projections
  (``mla_macs``), per query the two attention products over the position +
  1 keys it may see (nope + rope wide for the scores, v wide for the mix, a
  head), and the layer's FFN: the dense SwiGLU's three products in the
  leading dense layers; in an expert layer the shared expert's three, the
  router, and the routed experts' three at the EXPECTED load of an even
  router, ``experts_per_token * experts_held / num_experts`` assignments a
  token (0.5 at the published share; what a job really routed is the
  routing counter's, which the experts' roofline reads);
- the last layer: the key / value path (``kv_a``, ``kv_b``) at every
  position; the query path, the attention, the output projection, the FFN
  and the head at the read positions alone, one a record.
"""

DENSE_LAYERS = 1  # the source's first_k_dense_replace


def mla_macs(mc: dict) -> tuple[int, int]:
    """(key/value path, query path + output projection) a token."""
    d, heads = mc["token_dim"], mc["heads"]
    nope, rot, wide = mc["qk_nope_head_dim"], mc["qk_rope_head_dim"], mc["v_head_dim"]
    kv = d * (mc["kv_lora_rank"] + rot) + mc["kv_lora_rank"] * heads * (nope + wide)
    q = d * mc["q_lora_rank"] + mc["q_lora_rank"] * heads * (nope + rot)
    return kv, q + heads * wide * d


def attention_macs_per_key(mc: dict) -> int:
    return mc["heads"] * (mc["qk_nope_head_dim"] + mc["qk_rope_head_dim"] + mc["v_head_dim"])


def ffn_macs(mc: dict, layer: int) -> float:
    """A token's FFN in ``layer``; the routed part at an even router's load."""
    d = mc["token_dim"]
    if layer < DENSE_LAYERS:
        return 3 * d * mc["ffn_dim"]
    expert = 3 * d * mc["moe_ffn_dim"]
    held = mc["experts_held"] or mc["num_experts"]
    load = mc["experts_per_token"] * held / mc["num_experts"]
    return expert + d * mc["num_experts"] + load * expert


def history_macs(spec: dict, records: int) -> int:
    """Multiply-accumulates of one history of ``records`` records."""
    mc = spec["model_config"]
    per, depth = int(spec["tokens_per_record"]), mc["depth"]
    seq = records * per
    kv, rest = mla_macs(mc)
    key = attention_macs_per_key(mc)
    every = seq * (seq + 1) // 2  # sum of position + 1
    read = sum(r * per for r in range(1, records + 1))  # keys the read positions see
    total = 0.0
    for layer in range(depth - 1):
        total += seq * (kv + rest + ffn_macs(mc, layer)) + key * every
    total += seq * kv + records * (rest + ffn_macs(mc, depth - 1) + mc["token_dim"]) + key * read
    return int(total)


def forward_macs_per_row(spec: dict) -> int:
    records = int(spec["records_per_history"])
    return history_macs(spec, records) // records
