"""BERT-style tabular-as-text encoder (BASELINE.json config 5, the stretch).

The reference never goes near language models; this family exists because
the rebuild's baseline contract lists "BERT-base tabular-as-text fine-tune
(full TPU training loop, data-parallel on v5e-8)" as its stretch config.
Design is TPU-first rather than a port of any HF pipeline:

- **Tokenization is part of the jitted forward pass.** A record renders as
  the token sequence ``[CLS] name_1 value_1 ... name_23 value_23 [SEP]``
  (48 tokens, static shape). Categorical values map to per-feature vocab
  tokens by integer offset; numeric values (already standardized by the
  data pipeline) land in per-feature quantile-bin tokens via
  ``searchsorted`` over fixed standard-normal bin edges. No strings, no
  host-side tokenizer, no dynamic shapes — the "text" rendering is pure
  int32 arithmetic fused into the same XLA program as the encoder.
- **Same calling convention as every other family**
  (``apply(vars, cat_ids, numeric, train) -> logits[N]``), so the trainer,
  vmapped HPO, sharded train step, bundle format, and serving engine all
  work on BERT unchanged.
- Encoder blocks are the shared pre-LN ``TransformerBlock`` (GELU FFN at
  4x hidden, attention through ``ops.attention.attend`` which dispatches to
  the Pallas flash kernel at long sequence). Blocks are named ``block_i``
  and projections follow the zoo's naming, so the Megatron-style
  ``PARAM_RULES`` tensor-parallel layouts apply to BERT with zero new
  rules; DP x TP runs through ``parallel.make_sharded_train_step`` as-is.
- For sequence lengths beyond one record (multi-record documents), the
  sequence-parallel path is ``parallel.ring_attention`` — same online
  softmax, sharded over the 'seq' mesh axis.

``BERT_BASE`` is the true-scale preset (hidden 768, 12 layers, 12 heads,
FFN 3072, ~86M params + vocab). Tests and HPO use scaled-down instances.
"""

from __future__ import annotations

import dataclasses
from statistics import NormalDist
from typing import Sequence

import jax
import jax.numpy as jnp
import numpy as np
from flax import linen as nn

from mlops_tpu.models.ft_transformer import TransformerBlock

PAD_ID, CLS_ID, SEP_ID, MASK_ID = 0, 1, 2, 3
_SPECIAL = 4


@dataclasses.dataclass(frozen=True)
class TokenLayout:
    """Static vocabulary layout derived from the feature schema.

    Token id space: ``[PAD][CLS][SEP][MASK]`` | one name token per feature |
    per-categorical-feature value blocks (card each, OOV included) |
    per-numeric-feature bin blocks (num_bins each).
    """

    cards: tuple[int, ...]
    num_numeric: int
    num_bins: int

    @property
    def num_features(self) -> int:
        return len(self.cards) + self.num_numeric

    @property
    def name_offset(self) -> int:
        return _SPECIAL

    @property
    def cat_offsets(self) -> tuple[int, ...]:
        base = _SPECIAL + self.num_features
        offsets = []
        for card in self.cards:
            offsets.append(base)
            base += card
        return tuple(offsets)

    @property
    def bin_offsets(self) -> tuple[int, ...]:
        base = _SPECIAL + self.num_features + sum(self.cards)
        return tuple(
            base + j * self.num_bins for j in range(self.num_numeric)
        )

    @property
    def vocab_size(self) -> int:
        return (
            _SPECIAL
            + self.num_features
            + sum(self.cards)
            + self.num_numeric * self.num_bins
        )

    @property
    def seq_len(self) -> int:
        # [CLS] + (name, value) per feature + [SEP]
        return 2 + 2 * self.num_features

    def bin_edges(self) -> np.ndarray:
        """Interior standard-normal quantile edges (num_bins - 1 of them).

        Numeric features arrive standardized (mean 0 / std 1 under the
        train distribution), so fixed N(0,1) quantiles give near-uniform
        bin occupancy without any data-dependent state in the model.
        """
        nd = NormalDist()
        qs = [i / self.num_bins for i in range(1, self.num_bins)]
        return np.asarray([nd.inv_cdf(q) for q in qs], np.float32)


# `embed` and `head` are scopes of a device trace only (flax names what is
# a module; the tokenizer, the embedding front and the read-out as wholes are
# none): they name no parameter and change no number.
@jax.named_scope("embed")
def tokenize(
    cat_ids: jnp.ndarray, numeric: jnp.ndarray, layout: TokenLayout
) -> jnp.ndarray:
    """Render records as token ids: (int32[N,C], f32[N,M]) -> int32[N,S].

    Pure jnp integer math — traces into the encoder's XLA program.
    """
    n = cat_ids.shape[0]
    f = layout.num_features

    names = jnp.arange(
        layout.name_offset, layout.name_offset + f, dtype=jnp.int32
    )
    cat_tok = jnp.asarray(layout.cat_offsets, jnp.int32)[None, :] + cat_ids
    bins = jnp.searchsorted(
        jnp.asarray(layout.bin_edges()), numeric, side="right"
    ).astype(jnp.int32)
    num_tok = jnp.asarray(layout.bin_offsets, jnp.int32)[None, :] + bins

    values = jnp.concatenate([cat_tok, num_tok], axis=1)  # [N, F]
    pairs = jnp.stack(
        [jnp.broadcast_to(names[None, :], (n, f)), values], axis=2
    ).reshape(n, 2 * f)
    cls = jnp.full((n, 1), CLS_ID, jnp.int32)
    sep = jnp.full((n, 1), SEP_ID, jnp.int32)
    return jnp.concatenate([cls, pairs, sep], axis=1)


def tokenize_histories(
    cat_ids: jnp.ndarray, numeric: jnp.ndarray, layout: TokenLayout, records_per_history: int
) -> tuple[jnp.ndarray, np.ndarray]:
    """Rows as token-level HISTORIES, the history scorers' rule: every
    ``records_per_history`` consecutive rows (from row 0) are one history,
    fewer rows than one are one shorter history, and the last is padded
    with zero rows to a whole one. (int32[N,C], f32[N,M]) -> (int32
    [histories, records * S] token ids, the position of each record's last
    token ``[records]``, where a causal model reads that record's answer)."""
    n = cat_ids.shape[0]
    records = min(records_per_history, n)
    histories = -(-n // records)
    pad = histories * records - n
    tokens = tokenize(
        jnp.pad(cat_ids, ((0, pad), (0, 0))), jnp.pad(numeric, ((0, pad), (0, 0))), layout
    ).reshape(histories, records * layout.seq_len)
    return tokens, layout.seq_len * np.arange(1, records + 1) - 1


@jax.named_scope("embed")
def apply_embed_front(
    mod: nn.Module,
    tokens: jnp.ndarray,
    vocab_size: int,
    seq_len: int,
    hidden: int,
    dtype: jnp.dtype,
) -> jnp.ndarray:
    """The shared embedding front: tok_embed + pos_embed → ln_embed.

    Called from inside a ``@nn.compact`` ``__call__`` (``mod`` is the owning
    module); submodule/param names are fixed here ONCE so every consumer —
    ``BertEncoder``, ``BertMaskedLM``, ``BertDocEncoder``, and the
    pipeline-parallel split (`train/pipeline_parallel.py`) — produces
    byte-compatible param trees.
    """
    x = nn.Embed(vocab_size, hidden, dtype=dtype, name="tok_embed")(tokens)
    pos = mod.param(
        "pos_embed", nn.initializers.normal(0.02), (seq_len, hidden)
    )
    x = x + pos.astype(dtype)[None]
    return nn.LayerNorm(dtype=dtype, name="ln_embed")(x)


@jax.named_scope("head")
def apply_cls_head(
    mod: nn.Module, x: jnp.ndarray, hidden: int, dtype: jnp.dtype
) -> jnp.ndarray:
    """The shared read-out: ln_final on [CLS] → tanh pooler → head logit."""
    cls = nn.LayerNorm(dtype=dtype, name="ln_final")(x[:, 0])
    pooled = nn.tanh(nn.Dense(hidden, dtype=dtype, name="pooler")(cls))
    logit = nn.Dense(1, dtype=dtype, name="head")(pooled)
    return logit[:, 0].astype(jnp.float32)


class BertEncoder(nn.Module):
    """Pre-LN BERT-style encoder over the tabular token rendering.

    ``apply(vars, cat_ids, numeric, train) -> logits[f32 N]`` — the zoo
    convention (`mlops_tpu.models`), classifier head reading [CLS].
    """

    cards: Sequence[int]
    num_numeric: int
    hidden: int = 768
    depth: int = 12
    heads: int = 12
    dropout: float = 0.1
    num_bins: int = 32
    dtype: jnp.dtype = jnp.bfloat16

    @property
    def layout(self) -> TokenLayout:
        return TokenLayout(tuple(self.cards), self.num_numeric, self.num_bins)

    @nn.compact
    def __call__(
        self, cat_ids: jnp.ndarray, numeric: jnp.ndarray, *, train: bool = False
    ) -> jnp.ndarray:
        layout = self.layout
        tokens = tokenize(cat_ids, numeric, layout)  # [N, S]
        x = apply_embed_front(
            self, tokens, layout.vocab_size, layout.seq_len, self.hidden, self.dtype
        )
        x = nn.Dropout(self.dropout, deterministic=not train)(x)

        for i in range(self.depth):
            x = TransformerBlock(
                heads=self.heads,
                token_dim=self.hidden,
                dropout=self.dropout,
                dtype=self.dtype,
                name=f"block_{i}",
            )(x, train=train)

        return apply_cls_head(self, x, self.hidden, self.dtype)


class BertMaskedLM(nn.Module):
    """Masked-feature pretraining head over the same encoder trunk.

    Tabular analogue of BERT's MLM objective: mask a fraction of VALUE
    tokens (never names/CLS/SEP) and predict the original token id from
    context — self-supervised pretraining on unlabeled rows, no target
    column needed. The trunk modules carry the same names as
    ``BertEncoder`` (tok_embed, pos_embed, ln_embed, block_i, ln_final),
    so pretrained params transfer into the classifier via
    ``transfer_encoder_params`` and fine-tuning proceeds with the standard
    trainer.
    """

    cards: Sequence[int]
    num_numeric: int
    hidden: int = 768
    depth: int = 12
    heads: int = 12
    dropout: float = 0.1
    num_bins: int = 32
    dtype: jnp.dtype = jnp.bfloat16

    @property
    def layout(self) -> TokenLayout:
        return TokenLayout(tuple(self.cards), self.num_numeric, self.num_bins)

    def value_positions(self) -> np.ndarray:
        """Sequence indices holding value tokens (maskable positions):
        every second slot after CLS — [2, 4, ..., 2F]."""
        f = self.layout.num_features
        return np.arange(2, 2 * f + 1, 2)

    @nn.compact
    def __call__(
        self,
        cat_ids: jnp.ndarray,
        numeric: jnp.ndarray,
        mask: jnp.ndarray,
        *,
        train: bool = True,
    ) -> tuple[jnp.ndarray, jnp.ndarray]:
        """mask: bool [N, S], True = replace with [MASK] and predict.

        Returns (logits [N, S, vocab], original token ids [N, S]).
        """
        layout = self.layout
        targets = tokenize(cat_ids, numeric, layout)
        tokens = jnp.where(mask, MASK_ID, targets)
        x = apply_embed_front(
            self, tokens, layout.vocab_size, layout.seq_len, self.hidden, self.dtype
        )
        x = nn.Dropout(self.dropout, deterministic=not train)(x)
        for i in range(self.depth):
            x = TransformerBlock(
                heads=self.heads,
                token_dim=self.hidden,
                dropout=self.dropout,
                dtype=self.dtype,
                name=f"block_{i}",
            )(x, train=train)
        x = nn.LayerNorm(dtype=self.dtype, name="ln_final")(x)
        logits = nn.Dense(layout.vocab_size, dtype=self.dtype, name="mlm_head")(x)
        return logits.astype(jnp.float32), targets


def tokenize_documents(
    cat_ids: jnp.ndarray, numeric: jnp.ndarray, layout: TokenLayout
) -> jnp.ndarray:
    """Render record HISTORIES as one token sequence:
    (int32[N,R,C], f32[N,R,M]) -> int32[N, 2 + 2*F*R].

    Layout: ``[CLS] rec_1 pairs ... rec_R pairs [SEP]`` — each record
    contributes its (name, value) pairs from ``tokenize`` (per-record
    CLS/SEP stripped). Long-context consumer: `train/long_context.py`.
    """
    n, r, c = cat_ids.shape
    flat = tokenize(
        cat_ids.reshape(n * r, c), numeric.reshape(n * r, -1), layout
    )  # [N*R, 2 + 2F]
    pairs = flat[:, 1:-1].reshape(n, r * 2 * layout.num_features)
    cls = jnp.full((n, 1), CLS_ID, jnp.int32)
    sep = jnp.full((n, 1), SEP_ID, jnp.int32)
    return jnp.concatenate([cls, pairs, sep], axis=1)


class BertDocEncoder(nn.Module):
    """Long-context BERT over record histories (documents).

    The tabular-as-text rendering makes ONE record a 48-token sentence;
    this model reads ``doc_records`` consecutive records as one document
    (seq = 2 + 46R: R=11 -> 508 tokens) and predicts the default of the
    LAST record from the whole history. Calling convention is 3-D:
    ``apply(vars, cat[N,R,C], numeric[N,R,M], train) -> logits[N]``.

    This is the model the sequence-parallel training path runs
    (`train/long_context.py`): ``attend_fn`` injects the ppermute ring
    (`parallel.make_ring_attention`) so the sequence axis shards over the
    mesh's 'seq' axis; ``attend_fn=None`` is the dense single-chip
    reference the tests compare against. Trunk module names match
    ``BertEncoder`` (tok_embed, pos_embed, ln_embed, block_i, ln_final,
    pooler, head) so TP ``PARAM_RULES`` and pretrained-trunk grafting
    apply unchanged.
    """

    cards: Sequence[int]
    num_numeric: int
    doc_records: int
    hidden: int = 256
    depth: int = 4
    heads: int = 8
    dropout: float = 0.0  # attention-weight dropout needs materialized
    # scores, which the ring path never forms — keep 0 for SP training
    num_bins: int = 32
    dtype: jnp.dtype = jnp.bfloat16
    attend_fn: "object" = None  # Callable | None; static module attribute

    @property
    def layout(self) -> TokenLayout:
        return TokenLayout(tuple(self.cards), self.num_numeric, self.num_bins)

    @property
    def doc_seq_len(self) -> int:
        return 2 + 2 * self.layout.num_features * self.doc_records

    @nn.compact
    def __call__(
        self, cat_ids: jnp.ndarray, numeric: jnp.ndarray, *, train: bool = False
    ) -> jnp.ndarray:
        layout = self.layout
        tokens = tokenize_documents(cat_ids, numeric, layout)  # [N, S]
        x = apply_embed_front(
            self, tokens, layout.vocab_size, self.doc_seq_len, self.hidden, self.dtype
        )
        x = nn.Dropout(self.dropout, deterministic=not train)(x)
        for i in range(self.depth):
            x = TransformerBlock(
                heads=self.heads,
                token_dim=self.hidden,
                dropout=self.dropout,
                dtype=self.dtype,
                attend_fn=self.attend_fn,
                name=f"block_{i}",
            )(x, train=train)
        return apply_cls_head(self, x, self.hidden, self.dtype)


def transfer_encoder_params(pretrained: dict, target: dict) -> dict:
    """Graft pretrained trunk params into a freshly-initialized classifier
    param tree (same-named subtrees copy; heads keep their fresh init)."""
    merged = dict(target)
    for key, value in pretrained.items():
        if key in merged and key != "mlm_head":
            merged[key] = value
    return merged


def bert_base_config():
    """ModelConfig preset at true BERT-base scale (v5e-8 data-parallel)."""
    from mlops_tpu.config import ModelConfig

    return ModelConfig(family="bert", token_dim=768, depth=12, heads=12)
