"""``models/nemotron_h.py NemotronHScorer``: matrix-multiply operations a
record needs = those of a whole history of ``records_per_history`` records
/ that many records. Only what the answer REQUIRES is counted, so that a
program that skips the rest reads no higher than one that does not. A
layer counts its ONE kind's products (``benchmark/flops/falcon_h1.py``'s
counts for the mixer, ``exaone_moe.py``'s for the router):

- a ``mamba`` layer: the mixer's two projections and the recurrence's
  three products a head (not the chunked form's, which are more); in a
  last layer the state's inputs and its two moving products at every
  position and the rest at the read positions;
- a ``moe`` layer: the router (its published width) and, a token, the
  routed experts' TWO products (up, down: no gate) at ``experts_per_token
  * experts_held / num_experts`` assignments (the share held here, exact
  whatever the router's balance) and the shared expert's two at its own
  width; in a last layer at the read positions alone;
- an ``attention`` layer: the four projections (``head_dim`` wide heads)
  and, per query, the two products over the position + 1 keys it may
  see, every query head; in a last layer keys and values at every
  position and the rest at the read positions;
- the head, one a record.
"""

from benchmark.flops.exaone_moe import attention_macs, attention_macs_per_key
from benchmark.flops.falcon_h1 import mixer_columns, recurrence_macs

KINDS = {"M": "mamba", "E": "moe", "*": "attention"}


def kinds(spec: dict) -> list[str]:
    """The kind of each layer the configuration runs, from the pattern."""
    depth = spec["model_config"]["depth"]
    return [KINDS[letter] for letter in spec["hybrid_override_pattern"][:depth]]


def experts_macs(mc: dict) -> float:
    """A token's expert layer: router, the held share of its routed
    experts, the shared expert."""
    d = mc["token_dim"]
    held = mc["experts_held"] or mc["num_experts"]
    load = mc["experts_per_token"] * held / mc["num_experts"]
    shared = mc.get("shared_ffn_dim") or mc["moe_ffn_dim"]
    return d * mc["num_experts"] + load * 2 * d * mc["moe_ffn_dim"] + 2 * d * shared


def layer_macs(spec: dict, kind: str, seq: int, asked) -> float:
    """One layer of ``kind`` over a history of ``seq`` positions that
    answers at ``asked``."""
    mc = spec["model_config"]
    d = mc["token_dim"]
    if kind == "mamba":
        carried, answered = mixer_columns(mc)
        return seq * (d * carried + 2 * recurrence_macs(mc)) + len(asked) * (
            d * answered + recurrence_macs(mc) + mc["ssm_dim"] * d
        )
    if kind == "moe":
        return len(asked) * experts_macs(mc)
    keys_values, rest = attention_macs(mc)
    return seq * keys_values + len(asked) * rest + attention_macs_per_key(mc) * sum(
        p + 1 for p in asked
    )


def history_macs(spec: dict, records: int) -> int:
    """Multiply-accumulates of one history of ``records`` records."""
    per = int(spec["tokens_per_record"])
    seq = records * per
    listed = kinds(spec)
    total = 0.0
    for layer, kind in enumerate(listed):
        asked = range(per - 1, seq, per) if layer == len(listed) - 1 else range(seq)
        total += layer_macs(spec, kind, seq, asked)
    return int(total + records * spec["model_config"]["token_dim"])  # the head


def forward_macs_per_row(spec: dict) -> int:
    records = int(spec["records_per_history"])
    return history_macs(spec, records) // records
