"""``models/lfm2_moe.py Lfm2MoeScorer``: matrix-multiply operations a record
needs = those of a whole history of ``records_per_history`` records / that
many records. Only what the answer REQUIRES is counted, so that a program
that skips the rest reads no higher than one that does not:

- every layer but the last, every position: the token mixer the layer's
  entry of ``layer_types`` names (the convolution's two projections,
  ``conv_macs``: the taps and the gates are no matrix products; or the
  attention's four projections and, per query, its two products over the
  position + 1 keys it may see, a head's width each, every query head),
  and the layer's FFN: the dense SwiGLU's three products in the leading
  ``dense_layers`` layers; in an expert layer the router and the routed
  experts' three at ``experts_per_token * experts_held / num_experts``
  assignments a token (4 with every expert held: exact, whatever the
  router's balance);
- the last layer: what its mixer reads at every position it has to (a
  convolution: the input projection at each read position and the
  ``conv_width - 1`` before it; an attention: keys and values whole); the
  rest of the mixer, the FFN and the head at the read positions alone,
  one a record.
"""


def conv_macs(mc: dict) -> tuple[int, int]:
    """(input projection, output projection) a token."""
    d = mc["token_dim"]
    return d * 3 * d, d * d


def attention_macs(mc: dict) -> tuple[int, int]:
    """(key and value projections, query and output projections) a token."""
    d = mc["token_dim"]
    width = d // mc["heads"]
    return 2 * d * mc["kv_heads"] * width, 2 * d * d


def attention_macs_per_key(mc: dict) -> int:
    """The two products of every query head against one key."""
    return 2 * mc["token_dim"]


def ffn_macs(mc: dict, layer: int) -> float:
    """A token's FFN in ``layer``."""
    d = mc["token_dim"]
    if layer < mc["dense_layers"]:
        return 3 * d * mc["ffn_dim"]
    held = mc["experts_held"] or mc["num_experts"]
    load = mc["experts_per_token"] * held / mc["num_experts"]
    return d * mc["num_experts"] + load * 3 * d * mc["moe_ffn_dim"]


def history_macs(spec: dict, records: int) -> int:
    """Multiply-accumulates of one history of ``records`` records."""
    mc = spec["model_config"]
    per, depth = int(spec["tokens_per_record"]), mc["depth"]
    seq = records * per
    key = attention_macs_per_key(mc)
    every = seq * (seq + 1) // 2  # sum of position + 1
    read = sum(r * per for r in range(1, records + 1))  # keys the read positions see
    total = 0.0
    for layer in range(depth):
        last = layer == depth - 1
        after = records if last else seq  # positions behind the mixer's inputs
        if mc["layer_types"][layer] == "conv":
            into, out = conv_macs(mc)
            taken = min(seq, records * mc["conv_width"]) if last else seq
            total += taken * into + after * out
        else:
            keys_values, rest = attention_macs(mc)
            total += seq * keys_values + after * rest + key * (read if last else every)
        total += after * ffn_macs(mc, layer)
    return int(total + records * mc["token_dim"])  # the head


def forward_macs_per_row(spec: dict) -> int:
    records = int(spec["records_per_history"])
    return history_macs(spec, records) // records
