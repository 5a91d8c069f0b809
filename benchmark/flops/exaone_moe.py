"""``models/exaone_moe.py ExaoneMoeScorer``: matrix-multiply operations a
record needs = those of a whole history of ``records_per_history`` records
/ that many records. Only what the answer REQUIRES is counted, so that a
program that skips the rest reads no higher than one that does not, and
one that computes a window as a mask over every key reads lower:

- every layer but the last, every position: the attention's four
  projections (``head_dim`` wide heads: ``heads * head_dim`` need not be
  the hidden size) and, per query, its two products over the keys it may
  see, a head's width each, every query head: position + 1 keys in a
  ``full_attention`` layer, min(position + 1, ``attn_window``) in a
  ``sliding_attention`` one; and the layer's FFN: the dense SwiGLU's three
  products in the leading ``dense_layers`` layers; in a sparse layer the
  router (its published width), the routed experts' three at
  ``experts_per_token * experts_held / num_experts`` assignments a token
  (the share held here: 1 of a token's 8 with 16 of 128 held, exact
  whatever the router's balance) and the shared expert's three, whole;
- the last layer: keys and values whole; queries, the two products over
  the keys each read position may see, the output projection, the FFN and
  the head at the read positions alone, one a record.
"""


def attention_macs(mc: dict) -> tuple[int, int]:
    """(key and value projections, query and output projections) a token."""
    d, width = mc["token_dim"], mc["head_dim"]
    return 2 * d * mc["kv_heads"] * width, 2 * d * mc["heads"] * width


def attention_macs_per_key(mc: dict) -> int:
    """The two products of every query head against one key."""
    return 2 * mc["heads"] * mc["head_dim"]


def keys_seen(mc: dict, layer: int, positions) -> int:
    """(query, key) pairs of ``layer`` for queries at ``positions``."""
    if mc["layer_types"][layer] == "sliding_attention":
        return sum(min(p + 1, mc["attn_window"]) for p in positions)
    return sum(p + 1 for p in positions)


def ffn_macs(mc: dict, layer: int) -> float:
    """A token's FFN in ``layer``."""
    d = mc["token_dim"]
    if layer < mc["dense_layers"]:
        return 3 * d * mc["ffn_dim"]
    held = mc["experts_held"] or mc["num_experts"]
    load = mc["experts_per_token"] * held / mc["num_experts"]
    return d * mc["num_experts"] + (load + 1) * 3 * d * mc["moe_ffn_dim"]  # + the shared one


def history_macs(spec: dict, records: int) -> int:
    """Multiply-accumulates of one history of ``records`` records."""
    mc = spec["model_config"]
    per, depth = int(spec["tokens_per_record"]), mc["depth"]
    seq = records * per
    key = attention_macs_per_key(mc)
    keys_values, rest = attention_macs(mc)
    total = 0.0
    for layer in range(depth):
        last = layer == depth - 1
        asked = range(per - 1, seq, per) if last else range(seq)  # the queries' positions
        total += seq * keys_values + len(asked) * rest + key * keys_seen(mc, layer, asked)
        total += len(asked) * ffn_macs(mc, layer)
    return int(total + records * mc["token_dim"])  # the head


def forward_macs_per_row(spec: dict) -> int:
    records = int(spec["records_per_history"])
    return history_macs(spec, records) // records
