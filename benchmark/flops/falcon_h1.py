"""``models/falcon_h1.py FalconH1Scorer``: matrix-multiply operations a
record needs = those of a whole history of ``records_per_history`` records
/ that many records. Only what the answer REQUIRES is counted, so that a
program that skips the rest reads no higher than one that does not, and
a scan that does more work than the recurrence reads no better:

- every layer but the last, every position: the state-space mixer's two
  projections (``in_proj`` to z | x | B | C | dt, ``out_proj``) and the
  RECURRENCE's three products a head, each a head's width times its state
  (``dt x`` against ``B``, the decay on the state, the state against
  ``C``: not the chunked form's products, which are more); the attention's
  four projections (``head_dim`` wide heads) and, per query, its two
  products over the position + 1 keys it may see, every query head; the
  SwiGLU's three products. The convolution's taps, the gate and the norms
  are no matrix products;
- the last layer: what later positions need at every position (keys and
  values; ``in_proj``'s columns of x, B and dt; the recurrence's two
  products that move the state), the rest at the read positions alone,
  one a record (queries, the two products over the keys each may see, the
  attention's output projection; ``in_proj``'s columns of z and C, the
  state against C, ``out_proj``; the SwiGLU; the head).
"""


def mixer_columns(mc: dict) -> tuple[int, int]:
    """``in_proj``'s columns: (those the state needs at every position: x,
    B, dt; those an answer needs at its own position: z, C)."""
    shared = mc["ssm_groups"] * mc["ssm_state"]
    return mc["ssm_dim"] + shared + mc["ssm_heads"], mc["ssm_dim"] + shared


def recurrence_macs(mc: dict) -> int:
    """ONE of the recurrence's three products, every head, a token."""
    return mc["ssm_dim"] * mc["ssm_state"]


def attention_macs(mc: dict) -> tuple[int, int]:
    """(key and value projections, query and output projections) a token."""
    d, width = mc["token_dim"], mc["head_dim"]
    return 2 * d * mc["kv_heads"] * width, 2 * d * mc["heads"] * width


def attention_macs_per_key(mc: dict) -> int:
    """The two products of every query head against one key."""
    return 2 * mc["heads"] * mc["head_dim"]


def history_macs(spec: dict, records: int) -> int:
    """Multiply-accumulates of one history of ``records`` records."""
    mc = spec["model_config"]
    per, depth, d = int(spec["tokens_per_record"]), mc["depth"], mc["token_dim"]
    seq = records * per
    carried, answered = mixer_columns(mc)
    keys_values, rest = attention_macs(mc)
    total = 0
    for layer in range(depth):
        asked = range(per - 1, seq, per) if layer == depth - 1 else range(seq)
        total += seq * (d * carried + 2 * recurrence_macs(mc) + keys_values)
        total += len(asked) * (
            d * answered + recurrence_macs(mc) + mc["ssm_dim"] * d  # C's product, out_proj
            + rest + 3 * d * mc["ffn_dim"]
        )
        total += attention_macs_per_key(mc) * sum(p + 1 for p in asked)
    return total + records * d  # the head


def forward_macs_per_row(spec: dict) -> int:
    records = int(spec["records_per_history"])
    return history_macs(spec, records) // records
