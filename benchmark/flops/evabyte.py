"""``models/evabyte.py EvaByteScorer``: matrix-multiply operations a record
needs = those of a whole history of ``records_per_history`` records / that
many records. Only what the answer REQUIRES is counted, so that a program
that skips the rest reads no higher than one that does not:

- layers 1 .. depth-1, every position: the four attention projections
  (4 d^2), the gated FFN's three products (3 d f), and per query the two
  attention products (2 d) over its keys: the causal part of its own window
  plus one summary for every chunk of the whole windows before it
  (``attention_keys``);
- the last layer: k and v at every position (2 d^2), and the query and
  output projections, attention, the FFN and the head (d) at the read
  positions alone, one a record.

The summaries' weighted sums are no matrix products and are left out.
"""


def attention_keys(position: int, window: int, chunk: int) -> int:
    """Keys query ``position`` attends: local, causal, and the summaries of
    the windows wholly before its own."""
    return position % window + 1 + (position // window) * (window // chunk)


def history_macs(spec: dict, records: int) -> int:
    """Multiply-accumulates of one history of ``records`` records."""
    mc = spec["model_config"]
    d, f, depth = mc["token_dim"], mc["ffn_dim"], mc["depth"]
    window, chunk = mc["attn_window"], mc["attn_chunk"]
    seq = records * spec["record_bytes"]
    every = sum(attention_keys(p, window, chunk) for p in range(seq))
    read = sum(
        attention_keys(r * spec["record_bytes"] - 1, window, chunk)
        for r in range(1, records + 1)
    )
    full_layer = seq * (4 * d * d + 3 * d * f) + 2 * d * every
    last_layer = seq * 2 * d * d + records * (2 * d * d + 3 * d * f + d) + 2 * d * read
    return (depth - 1) * full_layer + last_layer


def forward_macs_per_row(spec: dict) -> int:
    records = int(spec["records_per_history"])
    return history_macs(spec, records) // records
