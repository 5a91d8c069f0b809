"""``models/bert.py BertEncoder``: per token and layer the four attention
projections (4 d^2), the two feed-forward products (2 d ffn) and the two
attention products over the sequence (2 S d); the pooler and the head read
one token per row."""


def forward_macs_per_row(spec: dict) -> int:
    mc = spec["model_config"]
    d, ffn, seq = mc["token_dim"], spec["intermediate_size"], spec["seq_len"]
    per_token_layer = 4 * d * d + 2 * d * ffn + 2 * seq * d
    return mc["depth"] * seq * per_token_layer + d * d + d
