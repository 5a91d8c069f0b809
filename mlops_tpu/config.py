"""Typed configuration tree.

The reference scatters configuration across four ad-hoc mechanisms (notebook
widgets, bundle variables, env vars, CI secrets/vars — SURVEY.md SS5.6). Here
a single dataclass tree covers model/train/serve/monitor, loadable from
TOML, overridable from environment (``MLOPS_TPU_<SECTION>_<FIELD>``) and CLI
flags (``--section.field=value``). Every knob constructed here must be READ
somewhere outside this module — tpulint's TPU503 dead-knob rule
(`analysis/contracts.py`) gates CI on it, keyed off the declaration below
(the PR 13 ``replica_affinity_slack`` lesson: a validated setting that
changes nothing is worse than no setting).
"""

from __future__ import annotations

import dataclasses
import os
import warnings

try:
    import tomllib
except ModuleNotFoundError:  # Python < 3.11: tomllib landed in 3.11
    import tomli as tomllib  # type: ignore[no-redef]
from pathlib import Path
from typing import Any

# Opts this module's *Config dataclasses into the TPU503 knob-liveness
# contract (read from source by the analyzer, never imported).
TPULINT_CONFIG_MODULE = True


@dataclasses.dataclass
class DataConfig:
    train_path: str = ""  # empty -> synthetic
    rows: int = 50_000  # synthetic row count
    seed: int = 0
    valid_fraction: float = 0.2  # parity: train_test_split 80/20,
    # random_state=2024 (`01-train-model.ipynb` cell 7)


# Causal families that take the zoo's 2-D rows, read every `doc_records`
# consecutive rows as one history and answer every record.
HISTORY_FAMILIES = (
    "evabyte", "kimi_k2", "lfm2_moe", "exaone_moe", "falcon_h1", "nemotron_h",
)
# LFM2-8B-A1B's published `layer_types`: which token mixer each of its 24
# layers runs (family lfm2_moe's default)
LFM2_LAYER_TYPES = (
    *("conv", "conv", "full_attention", "conv") * 4,
    *("conv", "conv", "full_attention") * 2,
    "conv",
    "conv",
)


@dataclasses.dataclass
class ModelConfig:
    family: str = "mlp"  # mlp | ft_transformer | moe | linear | bert |
    # evabyte | kimi_k2 | lfm2_moe | exaone_moe | falcon_h1 | nemotron_h |
    # gbm | rf
    hidden_dims: tuple[int, ...] = (256, 256, 128)
    embed_dim: int = 16
    dropout: float = 0.1
    precision: str = "bf16"  # compute dtype on MXU: bf16 | f32 (params stay f32)
    param_dtype: str = "f32"  # the dtype parameters are STORED in, on disk
    # and on the device: f32 | bf16. The token-level decoders (kimi_k2,
    # lfm2_moe, exaone_moe, falcon_h1, nemotron_h) alone take bf16 (what a chip holds of
    # them does not fit it at four bytes a parameter); nothing casts the
    # tree in the program, a product reads its leaf as stored
    ensemble_size: int = 1  # >1 wraps the Flax family in a vmapped deep
    # ensemble (models/ensemble.py) — the MXU-native answer to the
    # reference's RandomForest variance reduction; 1 = single model
    # FT-Transformer / MoE specifics
    depth: int = 3
    heads: int = 8
    token_dim: int = 64
    num_experts: int = 8  # moe family: experts per block; the stacked
    # expert axis shards over the mesh 'model' axis (expert parallelism)
    # CPU tree-baseline specifics (families gbm/rf — BASELINE config 1;
    # bounds mirror the reference's hyperopt space, `01-train-model.ipynb:342-353`)
    n_estimators: int = 300
    max_tree_depth: int = 8
    # `doc_records` consecutive records are read as ONE sequence. The
    # family decides what comes back (`reads_documents`, `history_rows`):
    # family bert reads a 3-D document (seq = 2 + 46R tokens) and predicts
    # the LAST record's default from the history (training path
    # `train/long_context.py`); family evabyte is causal, takes the zoo's
    # 2-D rows and answers EVERY record, conditioned on the records before
    # it in its history, as do the token-level decoders kimi_k2, lfm2_moe,
    # exaone_moe, falcon_h1 and nemotron_h. `seq_parallel` routes bert's attention through
    # the ppermute ring (`parallel.make_ring_attention`) over the mesh's
    # 'seq' axis.
    doc_records: int = 1
    seq_parallel: bool = False
    # Pipeline parallelism (families bert / ft_transformer): split the
    # `depth` encoder blocks
    # into `pipeline_stages` GPipe stages over the mesh's 'stage' axis
    # (`train/pipeline_parallel.py`); microbatches stream through the
    # ppermute ring (`parallel/pipeline.py`). 0 = off. Requires
    # depth % pipeline_stages == 0 and dropout == 0.
    pipeline_stages: int = 0
    # Tensor parallelism (Flax families): lay params out over a
    # ('data','model') mesh with 'model' axis = tensor_parallel, per the
    # Megatron column/row PARAM_RULES (`parallel/sharding.py`);
    # the train step is `parallel/steps.py make_sharded_train_step`, the
    # product loop `train/tensor_parallel.py`. 0 = off. The device count
    # must be a multiple of it.
    tensor_parallel: int = 0
    # Family evabyte (models/evabyte.py; EVA chunked linear attention,
    # `ops/eva_attention.py`): the gated FFN's width, the bytes a query
    # attends exactly (older ones only through chunk summaries), the bytes
    # a summary stands for, and the rotary base. Hidden size, heads and
    # depth are `token_dim`, `heads`, `depth`.
    ffn_dim: int = 11008
    attn_window: int = 2048
    attn_chunk: int = 16
    rope_theta: float = 100000.0
    # Family kimi_k2 (models/kimi_k2.py; latent attention `ops/mla.py`, the
    # sparse expert layer `ops/moe_dispatch.py`). Hidden size, heads, depth,
    # the dense layer's width, the rotary base and the routed experts'
    # count are `token_dim`, `heads`, `depth`, `ffn_dim`, `rope_theta`,
    # `num_experts`. The latent ranks and the three head widths (query/key
    # = nope + rope, value = v); the experts' width and how many a token
    # chooses; the experts THIS process holds, `experts_held` of them from
    # `first_expert` (0 held = all: the layer uncut), as one chip of an
    # expert-parallel layer does; the rows of the embedding held (a slice
    # of the vocabulary). The source's constants (norm eps, YaRN factor and
    # betas, routed scaling, one leading dense layer) are the module's.
    q_lora_rank: int = 1536
    kv_lora_rank: int = 512
    qk_nope_head_dim: int = 128
    qk_rope_head_dim: int = 64
    v_head_dim: int = 128
    moe_ffn_dim: int = 2048
    experts_per_token: int = 8
    first_expert: int = 0
    experts_held: int = 0
    vocab_rows: int = 20480
    # Family lfm2_moe (models/lfm2_moe.py; the gated short convolution
    # `ops/short_conv.py`, grouped-query attention over
    # `ops/causal_attention.py`, the expert layer above with no shared
    # expert). Beside `token_dim`, `heads`, `depth`, `ffn_dim`,
    # `rope_theta`, `num_experts` and the expert fields above: the
    # key/value heads the `heads` query heads are grouped over (0 = one a
    # query head: no grouping); each layer's token mixer, "conv" |
    # "full_attention", at least `depth` entries (layer i takes the i-th,
    # so the published list, the default, serves any cut of depth); the
    # leading layers whose FFN is dense; the convolution's taps.
    kv_heads: int = 0
    layer_types: tuple[str, ...] = LFM2_LAYER_TYPES
    dense_layers: int = 2
    conv_width: int = 3
    # Family exaone_moe (models/exaone_moe.py; the attention of lfm2_moe in
    # every layer, the expert layer of kimi_k2 with its shared expert).
    # `layer_types` names each layer "sliding_attention" (a query sees
    # itself and the `attn_window` - 1 keys before it, and turns by rotary
    # positions) or "full_attention" (every key so far, unturned), and has
    # to be given: the default is lfm2_moe's list; `dense_layers` is 1 in
    # the source. The one field of its own: a head's width, which its
    # source states apart from the hidden size (64 heads of 128 in a hidden
    # size of 6,144); 0 = `token_dim // heads`.
    head_dim: int = 0
    # Family falcon_h1 (models/falcon_h1.py; in EVERY layer a Mamba-2
    # state-space mixer, `ops/ssd.py` behind `ops/short_conv.py causal_conv`,
    # and the grouped-query attention above, unnormed and turned, read one
    # normed input and are summed; then a dense SwiGLU of `ffn_dim`). Beside
    # `token_dim`, `depth`, `heads`, `kv_heads`, `head_dim`, `ffn_dim`,
    # `conv_width`, `rope_theta`, `vocab_rows`: the mixer's inner width
    # (`ssm_heads` heads of `ssm_dim // ssm_heads`), a head's state, the
    # groups that share B and C, the scan's chunk; and the source's muP
    # multipliers, each a constant of the forward pass: on the embedding,
    # into and out of each mixer, on the keys, on the five parts of the
    # mixer's input projection (z, x, B, C, dt) and on the SwiGLU's gate
    # and output.
    ssm_dim: int = 4096
    ssm_heads: int = 32
    ssm_state: int = 256
    ssm_groups: int = 2
    ssm_chunk: int = 128
    embedding_multiplier: float = 1.0
    attention_in_multiplier: float = 1.0
    attention_out_multiplier: float = 1.0
    key_multiplier: float = 1.0
    ssm_in_multiplier: float = 1.0
    ssm_out_multiplier: float = 1.0
    ssm_multipliers: tuple[float, ...] = (1.0, 1.0, 1.0, 1.0, 1.0)
    mlp_multipliers: tuple[float, ...] = (1.0, 1.0)
    # Family nemotron_h (models/nemotron_h.py; every layer ONE of the
    # falcon_h1 Mamba-2 mixer, routed ReLU² experts beside a shared one, or
    # unturned grouped-query attention). `layer_types` names each layer
    # "mamba" | "moe" | "attention" (the published hybrid_override_pattern's
    # M, E and *, read off it) and has to be given; the mixer, attention
    # and expert fields above serve as named there. The one field of its
    # own: the shared expert's width, which its source states apart from
    # the routed experts' (0 = `moe_ffn_dim`).
    shared_ffn_dim: int = 0

    @property
    def reads_documents(self) -> bool:
        """True for the 3-D ``doc`` flavour: ``[D, R, C]`` record histories
        in, ONE answer a document out (bundle flavour ``doc``; refused by
        `score-batch` and the serving engine). False for every 2-D,
        answer-a-row family, the history scorers' (`HISTORY_FAMILIES`)
        included."""
        return self.family not in HISTORY_FAMILIES and self.doc_records > 1

    @property
    def history_rows(self) -> int:
        """Consecutive ROWS a 2-D model reads as one sequence (1 = rows
        are independent): what a bulk chunk must hold whole
        (`parallel/bulk.py mesh_chunk_rows`)."""
        return self.doc_records if self.family in HISTORY_FAMILIES else 1

    @property
    def uses_layout_trainer(self) -> bool:
        """True when this config needs a multi-device layout trainer
        (`train/pipeline.py run_layout_training`) instead of the dense
        ``run_training`` path — the ONE predicate both the CLI dispatch
        and run_training's guard share."""
        return bool(
            self.pipeline_stages
            or self.seq_parallel
            or self.reads_documents
            or self.tensor_parallel
        )


@dataclasses.dataclass
class TrainConfig:
    batch_size: int = 1024
    steps: int = 2000
    learning_rate: float = 3e-3
    weight_decay: float = 1e-4
    warmup_steps: int = 100
    seed: int = 0
    eval_every: int = 200
    checkpoint_every: int = 500
    pos_weight: float = 1.0  # class-imbalance weight on the positive class
    init_params: str = ""  # path to pretrained masked-LM params (`pretrain`
    # CLI output) to graft into the bert trunk before fine-tuning
    tensorboard_dir: str = ""  # also stream metrics.jsonl records as TF
    # scalar events here (utils/tboard.py); empty = jsonl only
    keep_best: bool = True  # package the eval window with the highest
    # validation ROC-AUC instead of the final step — the reference's
    # select-best-by-validation-metric semantics (cell 10), and the guard
    # against the measured overfitting cliff (2400 steps: AUC 0.8056 ->
    # 0.7537 on the synthetic task). False = always package final params.
    distill_bulk: bool = True  # ensembles (>1 member) also package a
    # distilled single-MLP "bulk student" (train/distill.py): CPU-backend
    # bulk sweeps route through it so they beat the sklearn GBM floor
    # instead of paying K× ensemble FLOPs; serving stays exact. The
    # student's fidelity record lands in the bundle manifest.
    distill_quant: bool = False  # also package the int8/bf16 QUANTIZED
    # student tier (train/distill.py distill_quant_student, served by
    # ops/quant_kernel.py): the raw-speed serving/bulk tier behind the
    # lifecycle AUC/ECE promotion gates. Opt-in — it costs a second
    # distillation fit at packaging time, and only deployments that set
    # serve.serve_tier (or bulk --tier quant) away from "exact" use it.
    pipeline_microbatches: int = 8  # GPipe microbatches per step on the
    # pipeline-parallel path (model.pipeline_stages > 0): bubble fraction
    # is (S-1)/(M+S-1), so raise M to amortize; batch_size must divide by
    # it (times the 'data' axis when composing DP x PP)
    pipeline_remat: bool = False  # jax.checkpoint around each stage:
    # recompute the stage's INTERNAL block activations (attention/MLP
    # intermediates x layers-per-stage, the dominant backward-memory term
    # at depth) from the stage-boundary input instead of storing them;
    # the boundary inputs themselves stay stored (the scan needs them)
    ema_decay: float = 0.0  # >0 serves bias-corrected Polyak-averaged
    # params (EMA folded into the compiled step; eval/packaging use the
    # debiased average, raw params keep training). 0 disables. Supported
    # by EVERY trainer: dense fit, the DP/TP sharded step, the vmapped
    # HPO sweep, the long-context/document loop, and pipeline parallel.


@dataclasses.dataclass
class HPOConfig:
    """Hyperparameter search (replaces hyperopt TPE ``fmin(max_evals=10)``,
    `01-train-model.ipynb:342-353`). Trials with identical architectures are
    vmapped; distinct architectures loop; everything shards across the mesh."""

    trials: int = 10
    seed: int = 2024
    objective: str = "roc_auc"  # selection metric, parity with
    # `mlflow.search_runs(order_by validation_roc_auc_score DESC)` (cell 10)
    steps: int = 1000
    strategy: str = "random"  # random | sha. "sha" = successive halving
    # (the ADAPTIVE analogue of the reference's TPE, `01-train-model.ipynb:349`):
    # train all `trials` candidates one rung in ONE vmapped program, keep
    # the top 1/eta by `objective`, continue the survivors — total step
    # budget stays <= trials*steps (equal-budget vs random search), but
    # most of it lands on the candidates that earn it.
    eta: int = 3  # sha survivor fraction per rung (keep top 1/eta)
    sha_rungs: int = 3  # sha rung count (last rung trains the finalists)
    # Continuous search space (both strategies sample from these — the
    # reference's TPE space is RandomForest-shaped; these are the neural
    # optimizer's knobs). log10 bounds for the log-uniform draws:
    lr_log10: tuple[float, float] = (-3.7, -2.0)
    wd_log10: tuple[float, float] = (-6.0, -3.0)
    pos_weight_range: tuple[float, float] = (1.0, 4.0)  # uniform
    architectures: tuple[str, ...] = ()  # structural sweep axis (the
    # reference's n_estimators/max_depth/criterion analogue,
    # `01-train-model.ipynb:342-353`): each spec is comma-separated
    # ModelConfig overrides, e.g. "family=mlp,hidden_dims=64x64,embed_dim=8"
    # (tuples use 'x'). Each spec is one vmapped group of `trials` trials;
    # groups loop in Python (shapes differ -> separate compiles), selection
    # crosses groups by the same objective ordering. Empty = single group
    # with the configured model.


@dataclasses.dataclass
class MonitorConfig:
    # (drift_p_val, the TabularDrift(p_val=.05) parity knob, was removed:
    # the fused monitor exports CONTINUOUS 1-p drift scores and the only
    # consumed threshold is lifecycle.drift_threshold on windowed means
    # — a p-value cutoff here was a validated no-op, TPU503.)
    outlier_quantile: float = 0.95  # parity: IForest(threshold=0.95)
    drift_ref_size: int = 2048  # per-feature reference sample for K-S


class ServeConfigError(ValueError):
    """An inconsistent serving geometry, named at startup.

    Raised by ``ServeConfig.validate()`` for ring/worker shapes that the
    server used to clamp silently into locals — a deployment that asked
    for ``max_inflight=8`` on a 4-thread pool now fails its rollout with
    the constraint spelled out instead of quietly serving with different
    numbers than its config says."""


@dataclasses.dataclass
class ServeConfig:
    host: str = "0.0.0.0"
    port: int = 5000  # parity: `app/Dockerfile:22-24`
    workers: int = 0  # HTTP front-end PROCESSES. 0/1 = the single-process
    # asyncio server (serve/server.py). >= 2 = the multi-worker plane
    # (serve/frontend.py): N processes each bind the same port via
    # SO_REUSEPORT (kernel load-balances accepts), parse/validate/encode
    # requests, and feed ONE engine process over the zero-copy
    # shared-memory ring (serve/ipc.py). Linux-only (SO_REUSEPORT + fork)
    ring_slots_small: int = 64  # per-front-end request slots whose slab
    # holds up to GROUP_ROW_BUCKET rows (the coalescable class — batch-1
    # traffic rides these). Slots bound admission: a front end with no
    # free slot sheds 503 + Retry-After instead of queueing unboundedly
    ring_slots_large: int = 4  # per-front-end slots sized at max_batch
    # rows (the solo class; small requests may overflow into them, large
    # requests never take a small slot)
    shed_retry_after_s: int = 1  # Retry-After header on shed 503s
    service_name: str = "credit-default-api"
    model_directory: str = "model"  # parity: MODEL_DIRECTORY (`app/main.py:27`)
    max_batch: int = 256  # request-size cap; must equal the largest warmed
    # bucket so steady-state serving never compiles a novel shape
    warmup_batch_sizes: tuple[int, ...] = (1, 8, 64, 256)
    batch_window_ms: float = 1.0  # micro-batching window: concurrent small
    # requests arriving within it coalesce into one vmapped dispatch
    # (serve/batcher.py); 0 disables coalescing. In continuous mode this
    # is the CAP on the measured admit deadline, not a fixed wave
    max_group: int = 64  # most requests one vmapped dispatch may carry;
    # clamped to the largest warmed slot bucket. Large groups are what
    # amortize the flat per-dispatch transport round trip into req/s
    batch_mode: str = "continuous"  # micro-batcher admission policy
    # (serve/batcher.py): "continuous" admits pending requests into the
    # next free in-flight dispatch slot at dispatch boundaries — while a
    # dispatch is in flight new arrivals accumulate for free, so the
    # admit wait only exists when the pipe is empty, where it is sized
    # from the MEASURED dispatch time (batch_admit_fraction x EWMA,
    # capped by batch_window_ms). "windowed" is the legacy fixed-wave
    # policy: hold every group open for the full window first. Responses
    # are bit-identical either way (group geometry never changes the
    # per-request math — tests/test_batcher.py pins it)
    batch_admit_fraction: float = 0.5  # continuous mode: fraction of the
    # EWMA dispatch-stage seconds an empty-pipe group waits for
    # co-travelers before dispatching. Higher coalesces more at idle,
    # lower trims batch-1 p50; irrelevant under load (in-flight
    # dispatches make the admit wait 0)
    serve_tier: str = "exact"  # which packed program family serves
    # (serve/engine.py): "exact" = the bundle's full model; "quant" =
    # the int8/bf16 distilled student tier (ops/quant_kernel.py —
    # Pallas-fused on TPU, ~2x bulk rows/s), REQUIRED to exist and to
    # have passed its packaging-time fidelity gates (refuses otherwise);
    # "auto" = quant when admissible, exact (logged) when not. Train
    # with train.distill_quant=true to package the tier
    max_inflight: int = 4  # overlapped grouped dispatches the micro-batcher
    # may have in flight at once. Sync constraint: must not exceed
    # max_workers, or dispatches just queue inside the executor and the
    # overlap is fiction (serve/batcher.py)
    max_workers: int = 8  # predict thread pool size; >= max_inflight so
    # every overlapped dispatch gets a thread, with headroom for the
    # batcher's solo fast-path and bulk scoring
    monitor_fetch_every_s: float = 2.0  # telemetry cadence for the
    # device-resident monitor aggregate (serve/engine.py
    # monitor_snapshot): the request path never fetches it; a background
    # task reads it at most this often when traffic is flowing. 0
    # disables the timer (the K-request trigger and /metrics scrapes
    # still fetch). Staleness bound: gauges lag live traffic by at most
    # max(monitor_fetch_every_s, monitor_fetch_every_requests requests)
    # — /metrics scrapes always read fresh (docs/operations.md)
    monitor_fetch_every_requests: int = 512  # also fetch after this many
    # predict requests since the last fetch; 0 disables the K-trigger
    request_timeout_s: float = 30.0  # per-request deadline on the predict
    # path: a stalled device answers the documented 504
    # fast instead of wedging every in-flight connection until the
    # client gives up. Clients can tighten it per request with the
    # x-request-deadline-ms header (serve/httpcore.py — the budget also
    # rides into the engine so expired work is shed, never dispatched).
    # 0 disables.
    drain_deadline_s: float = 30.0  # graceful-drain window: how long a
    # draining server (single-process) or front-end worker (multi-worker)
    # waits for busy exchanges and in-flight ring slots to finish before
    # force-closing connections. Tune DOWN for chaos scenarios that
    # should converge fast, UP for slow CI boxes; keep it under the pod's
    # terminationGracePeriodSeconds (the hard stop)
    zygote_join_deadline_s: float = 35.0  # supervisor shutdown: ONE
    # shared wall-clock budget for joining all front-end children after
    # the SIGTERM forward (they drain concurrently; stragglers past it
    # are SIGKILLed). Must cover drain_deadline_s plus respawn slack.
    # (Name kept from the PR 6 zygote model for config stability; the
    # supervisor absorbed the zygote's role in ISSUE 11.)
    engine_zygote_join_s: float = 50.0  # engine-child drain: how long
    # the supervisor waits for the engine process (SIGTERMed AFTER the
    # front ends joined — their in-flight slots need a live engine)
    # before escalating to SIGKILL. Must exceed zygote_join_deadline_s
    # + 5 so a cleanly-draining plane is never cut short end to end
    engine_respawn_eta_s: float = 5.0  # brownout contract (ISSUE 11): the
    # Retry-After a front end advertises on a 503 shed while the ENGINE
    # process is down and the parking partition is full — the estimated
    # detect -> fork -> cached-warmup -> replay wall time, minus however
    # long the engine has already been down. Tune to the measured warm
    # re-attach on the deployment box;
    # too low hammers retries into the still-full parking lot, too high
    # parks well-behaved clients longer than the outage
    engine_replicas: int = 1  # engine replica set (ISSUE 13,
    # mlops_tpu/replicaset/): E engine PROCESSES behind one shm ring on
    # the multi-worker plane — the front ends' ReplicaRouter fans
    # descriptors out least-loaded with small-class affinity, every
    # replica AOT-warms from the SAME compile cache (E deserializes, not
    # E compiles), and a kill -9 of one replica is a brownout of 1/E
    # capacity (its busy slots replay on the respawned incarnation while
    # the router routes around the hole). 1 (default) = the single
    # supervised engine child. Requires serve.workers >= 2 (the ring
    # plane); size E to the device budget, not the worker count
    # (docs/operations.md "Engine replica set")
    replica_affinity_slack: int = 4  # how many slots of extra live depth
    # the small-class sticky replica may carry before the router re-picks
    # least-loaded: low values spread faster (less coalescing company),
    # high values batch better (lumpier load) — see the runbook
    model_shards: int = 1  # partition-rule model sharding (ISSUE 13,
    # parallel/sharding.py match-style regex rules): >1 lays each
    # engine's params out over a ('model',) mesh of that many devices —
    # large families (moe experts, bert/ft_transformer projections)
    # SHARD instead of replicating, and the compile-cache key carries
    # the mesh shape so sharded and unsharded artifacts can never mix.
    # Requires at least that many visible jax devices in the engine
    # process
    tier_routing: bool = False  # per-request SLO tier routing (ISSUE 19,
    # serve/tierroute.py): the engine commits every OTHER gated tier
    # alongside the default one (exact-default keeps its gated quant
    # student; quant-default keeps its exact teacher) and each request
    # picks its tier by SLO class — x-slo-class: cheap|default|accurate,
    # defaulting by x-request-deadline-ms budget. Off (default) =
    # single-tier serving, bit-identical to pre-routing behavior
    slo_cheap_deadline_ms: float = 50.0  # requests with no explicit
    # x-slo-class whose x-request-deadline-ms budget is at or under this
    # route to the CHEAP class (tight budgets can't afford the accurate
    # tier's latency). <= 0 disables deadline-based classing: only the
    # explicit header routes
    brownout_demote_depth: float = 0.75  # brownout-over-shed (ISSUE 19):
    # when admission pressure (in-flight depth fraction) crosses this,
    # DEFAULT-class requests demote to the next-cheaper gated tier
    # instead of shedding 503 — degraded answers beat refused ones.
    # Explicit cheap/accurate classes are never reclassified
    brownout_restore_depth: float = 0.5  # pressure must fall back under
    # this before demotion stops (hysteresis: a gap below
    # brownout_demote_depth prevents flapping at the threshold)
    tenants_path: str = ""  # multi-tenant fleet declaration
    # (mlops_tpu/tenancy/): a tenants.toml naming N tenants (name,
    # bundle_dir, quota weight, default tenant) served from ONE engine
    # process on either plane — `mlops-tpu serve --tenants <file>` is the
    # flag sugar. Empty (default) = the single-tenant "default" fleet
    # serving serve.model_directory, bit-identical to pre-tenancy serving
    profile_dir: str = ""  # jax.profiler trace dir for the /debug/profile
    # endpoints (SURVEY.md SS5.1). Empty = DISABLED (default): the routes
    # are unauthenticated, so tracing is opt-in per deployment — enable
    # with serve.profile_dir=/tmp/profile when debugging a pod
    log_sample_rate: float = 1.0  # fraction of the two-event structured
    # request logs (InferenceData/ModelOutput) actually emitted. At 10x
    # overload the per-request json.dumps becomes measurable hot-path
    # CPU; sampling keeps a statistical picture while non-200 responses
    # (sheds, 504s, 500s) are ALWAYS logged regardless of the rate —
    # errors must never be sampled out of the evidence stream. 1.0
    # (default) = log everything, the pre-sampling behavior
    loop_lag_monitor: bool = False  # arm the LoopLagSanitizer
    # (analysis/loopcheck.py) on each serving event loop: every callback
    # is timed and the worst window lands in the
    # mlops_tpu_event_loop_lag_ms gauge. Off by default — the wrapper
    # adds one closure per scheduled callback to the hot path
    loop_lag_slow_ms: float = 100.0  # callbacks at or above this are
    # recorded with attribution (coroutine qualname) for the sanitizer's
    # slow-callback report; only meaningful with loop_lag_monitor=true

    def validate(self) -> "ServeConfig":
        """Reject inconsistent worker/ring geometries at startup.

        One named error per broken invariant (``ServeConfigError``)
        instead of the ad-hoc warn-and-clamp that used to live in server
        locals: a config that says one thing while the server runs
        another is exactly the silent degradation this gate exists to
        stop. Returns self so call sites can chain."""
        problems: list[str] = []
        if self.max_workers < 1:
            problems.append(f"serve.max_workers={self.max_workers} must be >= 1")
        if self.max_batch < 1:
            problems.append(f"serve.max_batch={self.max_batch} must be >= 1")
        inflight_cap = max(1, self.max_workers - 2)
        if not 1 <= self.max_inflight <= inflight_cap:
            problems.append(
                f"serve.max_inflight={self.max_inflight} outside "
                f"[1, max(1, serve.max_workers - 2) = {inflight_cap}]: the "
                "dispatch bound, the fetch ring, and one thread of headroom "
                "(solo fast path / monitor fetch) must fit the predict pool "
                "— raise serve.max_workers or lower serve.max_inflight"
            )
        if self.workers < 0:
            problems.append(f"serve.workers={self.workers} must be >= 0")
        if self.batch_window_ms < 0:
            problems.append(
                f"serve.batch_window_ms={self.batch_window_ms} must be "
                ">= 0 (0 disables coalescing; negative has no meaning)"
            )
        if self.max_group < 2:
            problems.append(
                f"serve.max_group={self.max_group} must be >= 2 (a group "
                "of one is the solo path; the batcher clamps the top end "
                "to the largest warmed slot bucket)"
            )
        if self.batch_mode not in ("continuous", "windowed"):
            problems.append(
                f"serve.batch_mode={self.batch_mode!r} must be "
                "'continuous' or 'windowed'"
            )
        if not 0.0 < self.batch_admit_fraction <= 1.0:
            problems.append(
                f"serve.batch_admit_fraction={self.batch_admit_fraction} "
                "must be in (0, 1] — it scales the measured dispatch time "
                "into the empty-pipe admit deadline; more than one whole "
                "dispatch of waiting buys nothing a deeper group wouldn't"
            )
        if self.serve_tier not in ("exact", "quant", "auto"):
            problems.append(
                f"serve.serve_tier={self.serve_tier!r} must be 'exact', "
                "'quant' or 'auto'"
            )
        if not 0.0 < self.brownout_demote_depth <= 1.0:
            problems.append(
                f"serve.brownout_demote_depth={self.brownout_demote_depth} "
                "must be in (0, 1] — it is a fraction of admission depth"
            )
        if not 0.0 <= self.brownout_restore_depth < self.brownout_demote_depth:
            problems.append(
                f"serve.brownout_restore_depth={self.brownout_restore_depth}"
                " must be in [0, serve.brownout_demote_depth ="
                f" {self.brownout_demote_depth}) — restoring at or above "
                "the demote threshold flaps the brownout on every sample"
            )
        if self.drain_deadline_s <= 0:
            problems.append(
                f"serve.drain_deadline_s={self.drain_deadline_s} must be "
                "> 0 (a zero drain window severs in-flight responses on "
                "every rollout)"
            )
        if self.zygote_join_deadline_s < self.drain_deadline_s:
            problems.append(
                f"serve.zygote_join_deadline_s={self.zygote_join_deadline_s}"
                f" must cover serve.drain_deadline_s={self.drain_deadline_s}"
                " (the zygote joins children that are themselves draining "
                "for the full drain window)"
            )
        if self.engine_zygote_join_s < self.zygote_join_deadline_s + 5:
            problems.append(
                f"serve.engine_zygote_join_s={self.engine_zygote_join_s} "
                "must exceed serve.zygote_join_deadline_s + 5 "
                f"(= {self.zygote_join_deadline_s + 5:g}: the zygote's "
                "child-join budget plus its SIGKILL grace — a shorter "
                "engine wait SIGKILLs a zygote that is still joining "
                "cleanly)"
            )
        if self.workers > 1:
            if self.ring_slots_small < 1 or self.ring_slots_large < 1:
                problems.append(
                    f"serve.ring_slots_small={self.ring_slots_small} / "
                    f"serve.ring_slots_large={self.ring_slots_large} must "
                    "each be >= 1 with serve.workers > 1 (every front end "
                    "needs at least one slot per bucket class, or whole "
                    "request classes would shed 100%)"
                )
            if self.shed_retry_after_s < 1:
                problems.append(
                    f"serve.shed_retry_after_s={self.shed_retry_after_s} "
                    "must be >= 1 (the shed 503 contract promises a "
                    "positive Retry-After)"
                )
            if self.engine_respawn_eta_s <= 0:
                problems.append(
                    f"serve.engine_respawn_eta_s={self.engine_respawn_eta_s}"
                    " must be > 0 (the brownout 503 contract promises a "
                    "positive respawn-ETA Retry-After)"
                )
        if self.engine_replicas < 1:
            problems.append(
                f"serve.engine_replicas={self.engine_replicas} must be "
                ">= 1"
            )
        if self.engine_replicas > 1 and self.workers < 2:
            problems.append(
                f"serve.engine_replicas={self.engine_replicas} needs the "
                "multi-worker ring plane (serve.workers >= 2): the "
                "single-process server has no descriptor ring to fan out"
            )
        if self.replica_affinity_slack < 0:
            problems.append(
                f"serve.replica_affinity_slack={self.replica_affinity_slack}"
                " must be >= 0"
            )
        if self.model_shards < 1:
            problems.append(
                f"serve.model_shards={self.model_shards} must be >= 1"
            )
        if not 0.0 < self.log_sample_rate <= 1.0:
            problems.append(
                f"serve.log_sample_rate={self.log_sample_rate} must be in "
                "(0, 1] (0 would silence even the always-logged errors' "
                "InferenceData events; sample DOWN, never off)"
            )
        if self.loop_lag_slow_ms <= 0:
            problems.append(
                f"serve.loop_lag_slow_ms={self.loop_lag_slow_ms} must be "
                "> 0 (0 would record every callback as slow, unbounded "
                "attribution overhead)"
            )
        if problems:
            raise ServeConfigError("; ".join(problems))
        return self


@dataclasses.dataclass
class RegistryConfig:
    root: str = "registry"
    model_name: str = "credit-default-uci-custom"  # parity:
    # `databricks/resources/train_register_model.yml` var model_name
    experiment_name: str = "credit-default-uci-train"  # parity: parent
    # MLflow run name (`01-train-model.ipynb` cell 8)
    run_root: str = "runs"  # per-run artifacts: metrics.jsonl, checkpoints
    run_name: str = ""  # stable run-directory name: a retried/preempted
    # job that passes the same name (e.g. the K8s ${JOB_NAME}) lands in
    # the same <run_root>/<run_name> and RESUMES from its checkpoints —
    # provided run_root is on storage that survives the pod. Empty = a
    # fresh timestamped directory per invocation.
    promote_version: str = ""  # `promote` CLI: version to move
    promote_stage: str = "staging"  # `promote` CLI: target stage
    gc_keep: int = 0  # `gc` CLI: also prune old unstaged versions beyond
    # the newest N (0 = remove crash orphans only)


@dataclasses.dataclass
class ScoreConfig:
    """Bulk scoring (BASELINE config 4: 1M rows over the data mesh)."""

    chunk_rows: int = 131_072  # rows per compiled chunk (rounded to mesh axis)
    drift_sample: int = 65_536  # bounded sample for dataset-level drift
    pipeline_depth: int = 2  # bounded-queue depth of the streaming
    # executor (data/pipeline_exec.py): read+parse, encode, device
    # transfer, compute, and result fetch/output each run on their own
    # stage, overlapped across chunks, with peak memory fixed at a few
    # chunks. 1 = strict serial (bit-identical outputs, the debugging
    # baseline); 2 = classic double buffering (the measured sweet spot —
    # deeper queues oversubscribe small CPU hosts without buying overlap)
    output_path: str = ""  # optional .npz with predictions/outliers
    streaming: bool = False  # out-of-core: stream CSV chunks through the
    # fused predict with one-chunk peak memory (data/stream.py); output
    # becomes an incrementally-written CSV instead of an .npz
    exact: bool = False  # True forces the serving-identical ensemble for
    # bulk scoring; False (default) auto-routes through the distilled
    # bulk student on CPU backends (parallel/bulk.py use_distilled_bulk —
    # the output JSON's "path" field records which ran)


class LifecycleConfigError(ValueError):
    """An inconsistent lifecycle geometry, named at startup (the
    ``ServeConfigError`` discipline applied to the controller knobs)."""


@dataclasses.dataclass
class LifecycleConfig:
    """The closed-loop controller (`mlops_tpu/lifecycle/`): drift-triggered
    retrain -> shadow serve -> gated hot promotion. Disabled by default —
    `serve` grows the loop only when ``lifecycle.enabled=true`` (or the
    one-shot offline pass runs via ``mlops-tpu lifecycle``)."""

    enabled: bool = False
    dir: str = "lifecycle"  # controller state root: the on-disk sample
    # reservoir, candidate bundles (candidates/gen-N), retrain checkpoints
    labeled_path: str = ""  # labeled window source (CSV/Parquet WITH the
    # target column) for retrain + the candidate-vs-incumbent gates.
    # Serving traffic is unlabeled; ground truth (the realized default)
    # arrives out of band — this file is that delivery point. Empty =
    # retrain triggers are observed but can never produce a candidate
    # ---------------------------------------------------------- triggers
    drift_threshold: float = 0.9  # fire when the WINDOWED per-feature mean
    # drift score (1 - p_val, monitor aggregates between controller ticks)
    # exceeds this on any feature
    outlier_threshold: float = 0.5  # ... or the windowed outlier rate does
    min_window_rows: int = 256  # a trigger window must carry at least this
    # many scored rows (a near-empty window's statistics are noise)
    hysteresis_windows: int = 2  # consecutive over-threshold windows
    # required before firing — one noisy window can never retrain-storm
    cooldown_s: float = 300.0  # dead time after any trigger/outcome during
    # which new spikes neither fire nor accumulate hysteresis
    tick_s: float = 1.0  # controller evaluation cadence (its own thread,
    # off the request path)
    # ----------------------------------------------------------- retrain
    reservoir_rows: int = 8192  # bounded on-disk sample reservoir fed from
    # the serve path (algorithm-R over every scored row)
    retrain_steps: int = 300  # incremental fine-tune budget from the
    # incumbent's params over the labeled window
    retrain_batch_size: int = 256
    min_labeled_rows: int = 512  # labeled window smaller than this skips
    # retrain (the gate evaluation would be statistically meaningless)
    refit_preprocessor: bool = False  # True re-fits normalization stats on
    # the labeled window via `fit_streaming` (single-process serving
    # only): the multi-worker plane's front ends encode with the
    # preprocessor loaded at fork, so the ring plane forces False — the
    # encode contract is part of the promotion contract there. False
    # (default) also makes the hot swap's one-generation guarantee cover
    # the encode stage unconditionally (the preprocessor is then
    # identical across generations); with a refit, a request already
    # past encode when a swap lands scores old-stats rows against the
    # new params for that instant (serve/engine.py swap_bundle)
    # ------------------------------------------------------------ shadow
    mirror_fraction: float = 0.1  # fraction of live traffic mirrored to
    # the shadow candidate (dispatch-only; responses discarded)
    shadow_min_mirrors: int = 32  # mirrored dispatches to accumulate
    # before the gates are evaluated
    shadow_max_s: float = 600.0  # evaluate anyway after this long in
    # shadow (a traffic lull must not wedge the loop mid-candidate)
    # ------------------------------------------------------------- gates
    max_auc_drop: float = 0.01  # candidate AUC may trail the incumbent's
    # by at most this (epsilon) on the labeled holdout
    max_ece: float = 0.1  # candidate expected-calibration-error bound
    max_p99_ratio: float = 2.0  # candidate p99 latency bound, relative to
    # the incumbent's on the same mirrored/holdout shapes
    auto_promote: bool = True  # False stops after the gate report (the
    # human-in-the-loop mode; promote later via the registry CLI)
    # ---------------------------------------------------- circuit breaker
    breaker_failures: int = 3  # consecutive retrain/shadow/evaluate
    # FAILURES (not gate rejections — those are the loop working) that
    # open the circuit breaker: while open, triggers neither fire nor
    # accumulate hysteresis, so a persistently broken retrain path
    # (corrupt labeled file, full disk, compile regression) cools down
    # instead of hot-looping retrain attempts against live serving
    breaker_cooldown_s: float = 1800.0  # how long the breaker stays open
    # before the loop re-arms (half-open: the next trigger is the probe)

    def validate(self) -> "LifecycleConfig":
        problems: list[str] = []
        if not 0.0 < self.drift_threshold <= 1.0:
            problems.append(
                f"lifecycle.drift_threshold={self.drift_threshold} must be "
                "in (0, 1] (drift scores are 1 - p_val)"
            )
        if not 0.0 < self.outlier_threshold <= 1.0:
            problems.append(
                f"lifecycle.outlier_threshold={self.outlier_threshold} "
                "must be in (0, 1] (a rate)"
            )
        if self.hysteresis_windows < 1:
            problems.append(
                f"lifecycle.hysteresis_windows={self.hysteresis_windows} "
                "must be >= 1 (0 would fire on no evidence at all)"
            )
        if not 0.0 <= self.mirror_fraction <= 1.0:
            problems.append(
                f"lifecycle.mirror_fraction={self.mirror_fraction} must be "
                "in [0, 1]"
            )
        if self.reservoir_rows < 1:
            problems.append(
                f"lifecycle.reservoir_rows={self.reservoir_rows} must be >= 1"
            )
        if self.retrain_steps < 1:
            problems.append(
                f"lifecycle.retrain_steps={self.retrain_steps} must be >= 1"
            )
        if self.max_p99_ratio <= 0:
            problems.append(
                f"lifecycle.max_p99_ratio={self.max_p99_ratio} must be > 0"
            )
        if self.tick_s <= 0:
            problems.append(
                f"lifecycle.tick_s={self.tick_s} must be > 0 (a zero tick "
                "turns the controller thread into a busy loop of "
                "fetch-and-reset device round trips contending the "
                "accumulator lock with live traffic)"
            )
        if self.cooldown_s < 0:
            problems.append(
                f"lifecycle.cooldown_s={self.cooldown_s} must be >= 0"
            )
        if self.min_window_rows < 1:
            problems.append(
                f"lifecycle.min_window_rows={self.min_window_rows} must "
                "be >= 1"
            )
        if self.min_labeled_rows < 2:
            problems.append(
                f"lifecycle.min_labeled_rows={self.min_labeled_rows} must "
                "be >= 2 (the holdout split needs both classes a chance "
                "to exist)"
            )
        if self.shadow_min_mirrors < 0:
            problems.append(
                f"lifecycle.shadow_min_mirrors={self.shadow_min_mirrors} "
                "must be >= 0"
            )
        if self.shadow_max_s <= 0:
            problems.append(
                f"lifecycle.shadow_max_s={self.shadow_max_s} must be > 0 "
                "(the shadow phase needs a bounded evaluation deadline)"
            )
        if self.breaker_failures < 1:
            problems.append(
                f"lifecycle.breaker_failures={self.breaker_failures} must "
                "be >= 1 (0 would open the breaker on no evidence)"
            )
        if self.breaker_cooldown_s < 0:
            problems.append(
                f"lifecycle.breaker_cooldown_s={self.breaker_cooldown_s} "
                "must be >= 0"
            )
        if problems:
            raise LifecycleConfigError("; ".join(problems))
        return self


class TraceConfigError(ValueError):
    """An inconsistent tracing geometry, named at startup (the
    ``ServeConfigError`` discipline applied to the tracewire knobs)."""


@dataclasses.dataclass
class TraceConfig:
    """tracewire (`mlops_tpu/trace/`): end-to-end request tracing +
    shape/goodput telemetry on both serving planes. Disabled by default —
    disarmed, the hot path pays one ``is None`` check per request."""

    enabled: bool = False
    dir: str = "traces"  # span JSONL root: the single-process server
    # writes spans.jsonl, each multi-worker front end spans-w{N}.jsonl;
    # `mlops-tpu trace-report trace.dir=<dir>` aggregates them
    ring_capacity: int = 4096  # bounded span buffer per process; a full
    # buffer DROPS (counted in mlops_tpu_trace_dropped_total) instead of
    # ever back-pressuring the request path
    flush_interval_s: float = 0.5  # background writer cadence; the drain
    # path flushes everything regardless, so this only bounds how long a
    # span sits in memory while the server runs
    tenant: str = ""  # `trace-report` filter (`--tenant` flag sugar):
    # only aggregate spans carrying this tenant label — multi-tenant
    # planes (mlops_tpu/tenancy/) stamp every span with its tenant;
    # pre-tenancy spans count as "default". Empty = all tenants
    replica: int = -1  # `trace-report` filter (`--replica` flag sugar):
    # only aggregate spans served by this engine replica (the ring
    # plane stitches the router's choice into every span; pre-replica
    # spans count as replica 0). -1 = all replicas
    ledger: bool = False  # `trace-report --ledger` flag sugar: report
    # the device-time cost ledger (slo.ledger_dir) ranked by
    # cost_ms_per_row instead of aggregating span files

    def validate(self) -> "TraceConfig":
        problems: list[str] = []
        if self.ring_capacity < 1:
            problems.append(
                f"trace.ring_capacity={self.ring_capacity} must be >= 1"
            )
        if self.flush_interval_s <= 0:
            problems.append(
                f"trace.flush_interval_s={self.flush_interval_s} must be "
                "> 0 (a zero interval busy-loops the writer thread)"
            )
        if self.enabled and not self.dir:
            problems.append(
                "trace.enabled=true requires trace.dir (the span JSONL "
                "root)"
            )
        if problems:
            raise TraceConfigError("; ".join(problems))
        return self


class SLOConfigError(ValueError):
    """An inconsistent sloscope geometry, named at startup (the
    ``ServeConfigError`` discipline applied to the SLO knobs)."""


@dataclasses.dataclass
class SLOConfig:
    """sloscope (`mlops_tpu/slo/`): SLO/error-budget accounting with
    multi-window multi-burn-rate alerts, the anomaly-triggered flight
    recorder, and the per-entry device-time cost ledger. Disabled by
    default — disarmed, every hot path pays one ``is None`` check."""

    enabled: bool = False
    # ------------------------------------------------------------- targets
    availability_target: float = 0.999  # fraction of /predict requests
    # answered without a server-side failure (5xx: 500s, shed 503s, and
    # deadline 504s all spend budget — a shed request is not goodput)
    latency_target: float = 0.99  # fraction of requests answered inside
    # the latency threshold below
    latency_threshold_ms: float = 50.0  # measured against the existing
    # latency histogram: the EFFECTIVE threshold is the smallest bucket
    # edge >= this value (ServingMetrics.LATENCY_BUCKETS)
    tick_s: float = 1.0  # evaluation cadence (the single-process plane's
    # timer task; the ring plane's lead-replica telemetry loop). The
    # alert contract is "flips within two ticks of the counters
    # crossing" — tune down for chaos drills, up for huge fleets
    # --------------------------------------------------------- burn alerts
    # The SRE-workbook multiwindow multi-burn-rate pairs: each alert
    # requires BOTH its windows over the threshold (long filters blips,
    # short ends the alert fast once the burn stops). Defaults are the
    # classic 30-day-budget numbers; chaos drills shrink the windows.
    fast_burn_threshold: float = 14.4  # page: budget gone in ~2 days
    slow_burn_threshold: float = 6.0  # ticket: budget gone in ~5 days
    fast_short_s: float = 300.0  # 5m
    fast_long_s: float = 3600.0  # 1h
    slow_short_s: float = 21600.0  # 6h
    slow_long_s: float = 259200.0  # 3d
    # ---------------------------------------------------- flight recorder
    flightrec_enabled: bool = True  # armed with slo.enabled: each serving
    # process keeps a bounded in-memory ring of recent request summaries
    # (+ spans when tracewire is armed) and dumps it atomically on
    # anomaly — burn alert, engine respawn, 5xx/504 spike, breaker open,
    # SIGTERM-with-evidence. A clean run writes NOTHING.
    flightrec_dir: str = "runs"  # dump directory (flightrec-*.json)
    flightrec_capacity: int = 2048  # events per process ring
    flightrec_cooldown_s: float = 30.0  # min seconds between triggered
    # dumps per process (a sustained burn produces a bounded stream)
    flightrec_keep: int = 8  # retention: newest N dumps kept in the dir
    flightrec_spike_errors: int = 8  # 5xx/504 spike trigger: this many
    # server-side failures inside the window below trips a dump even
    # when no burn alert is armed to notice
    flightrec_spike_window_s: float = 5.0
    # --------------------------------------------------------- cost ledger
    ledger_dir: str = ""  # per-entry device-time cost ledger root
    # (mlops_tpu/slo/ledger.py): empty = OFF. Set it and every packed
    # dispatch accounts (entry, rows, padded rows, device-path seconds)
    # into <dir>/ledger.json — persisted atomically, ACCUMULATED across
    # runs, keyed by entry + model fingerprint (a regrid/promotion never
    # cross-pollutes), exported as mlops_tpu_entry_* series and ranked
    # by `mlops-tpu trace-report --ledger`. Arms independently of
    # slo.enabled: the ledger is autotuner input, not alerting.
    ledger_flush_s: float = 30.0  # background flush cadence

    def validate(self) -> "SLOConfig":
        problems: list[str] = []
        for name, target in (
            ("availability_target", self.availability_target),
            ("latency_target", self.latency_target),
        ):
            if not 0.0 < target < 1.0:
                problems.append(
                    f"slo.{name}={target} must be in (0, 1) — a target of "
                    "1.0 leaves zero error budget and every burn rate "
                    "undefined"
                )
        if self.latency_threshold_ms <= 0:
            problems.append(
                f"slo.latency_threshold_ms={self.latency_threshold_ms} "
                "must be > 0"
            )
        else:
            # The SLO measures against the serving latency histogram;
            # a threshold past its largest FINITE edge would map to the
            # +Inf bucket and count EVERY request as good — a silently
            # dead latency alert, exactly what the always-emit contract
            # exists to prevent. (Lazy import: serve/metrics is jax-free
            # and never imports config back.)
            from mlops_tpu.serve.metrics import ServingMetrics

            max_edge = ServingMetrics.LATENCY_BUCKETS[-2]
            if self.latency_threshold_ms > max_edge:
                problems.append(
                    f"slo.latency_threshold_ms={self.latency_threshold_ms}"
                    f" exceeds the largest finite latency bucket "
                    f"({max_edge:g} ms) — every request would count as "
                    "good and the latency alerts could never fire"
                )
        if self.tick_s <= 0:
            problems.append(
                f"slo.tick_s={self.tick_s} must be > 0 (a zero tick "
                "busy-loops the evaluator)"
            )
        if self.fast_burn_threshold <= 0 or self.slow_burn_threshold <= 0:
            problems.append(
                "slo.fast_burn_threshold/slow_burn_threshold must be > 0"
            )
        if not (
            0 < self.fast_short_s < self.fast_long_s
            and 0 < self.slow_short_s < self.slow_long_s
        ):
            problems.append(
                "slo burn windows must satisfy 0 < fast_short_s < "
                "fast_long_s and 0 < slow_short_s < slow_long_s "
                f"(got {self.fast_short_s}/{self.fast_long_s} and "
                f"{self.slow_short_s}/{self.slow_long_s}): each alert "
                "pairs a short window with its long one"
            )
        else:
            # Burn gauges carry a window LABEL dimension ("5m"/"1h"):
            # two windows collapsing to one label (90 vs 90.5 s both →
            # "90s") would silently overwrite each other's burns and
            # drop a series — reject the collision by name instead.
            from mlops_tpu.slo.engine import window_label

            windows = (self.fast_short_s, self.fast_long_s,
                       self.slow_short_s, self.slow_long_s)
            labels = [window_label(w) for w in windows]
            if len(set(labels)) != len(labels):
                problems.append(
                    f"slo burn windows {windows} collapse to duplicate "
                    f"window labels {labels}: every window needs a "
                    "distinct whole-second label (the burn gauges' "
                    "window dimension)"
                )
        if self.flightrec_capacity < 1:
            problems.append(
                f"slo.flightrec_capacity={self.flightrec_capacity} must "
                "be >= 1"
            )
        if self.flightrec_cooldown_s < 0:
            problems.append(
                f"slo.flightrec_cooldown_s={self.flightrec_cooldown_s} "
                "must be >= 0"
            )
        if self.flightrec_keep < 1:
            problems.append(
                f"slo.flightrec_keep={self.flightrec_keep} must be >= 1"
            )
        if self.flightrec_spike_errors < 1:
            problems.append(
                f"slo.flightrec_spike_errors={self.flightrec_spike_errors}"
                " must be >= 1"
            )
        if self.flightrec_spike_window_s <= 0:
            problems.append(
                f"slo.flightrec_spike_window_s="
                f"{self.flightrec_spike_window_s} must be > 0"
            )
        if self.ledger_flush_s <= 0:
            problems.append(
                f"slo.ledger_flush_s={self.ledger_flush_s} must be > 0"
            )
        if problems:
            raise SLOConfigError("; ".join(problems))
        return self


class AutotuneConfigError(ValueError):
    """An inconsistent autotuner geometry, named at startup (the
    ``ServeConfigError`` discipline applied to the gridtuner knobs)."""


@dataclasses.dataclass
class AutotuneConfig:
    """gridtuner (`mlops_tpu/autotune/`): the traffic-shape autotuner —
    fit a measured per-entry cost model from the device-time ledger,
    search bucket grids against the observed shape histogram, and
    hot-apply the winner through the swap machinery. Disabled by
    default; the one-shot offline pass runs via ``mlops-tpu autotune``."""

    enabled: bool = False
    interval_s: float = 60.0  # periodic evaluation cadence (its own
    # thread, off the request path — the LifecycleController discipline)
    min_dispatches: int = 512  # observed dispatches required before a
    # plan is even considered: a near-empty shape histogram is noise,
    # and regridding on noise churns the compile cache for nothing
    max_entries: int = 16  # compile budget: the most solo-bucket entries
    # a plan may carry (each is one AOT compile at warm time; group
    # geometries stay the full fixed grid and don't count against this)
    min_gain_pct: float = 5.0  # predicted useful_rows_per_s gain below
    # which a plan is rejected (outcome="rejected"): swapping grids for
    # sub-noise gains invalidates warm telemetry for nothing
    apply: bool = True  # False = dry-run: plans are computed, exported,
    # and persisted, but never hot-applied (the human-in-the-loop mode —
    # read the plan, then `mlops-tpu serve autotune.apply=true`)
    plan_dir: str = "autotune"  # plan root: the controller (and the
    # offline CLI) writes plan.json here atomically; on the ring plane
    # sibling replicas ADOPT the lead's applied plan from this file,
    # warming through the shared compile cache instead of re-searching
    cooldown_s: float = 300.0  # dead time after any apply/rollback
    # before the next evaluation: measured-gain audit needs a full
    # observation window on the new grid before anyone moves again

    def validate(self) -> "AutotuneConfig":
        problems: list[str] = []
        if self.interval_s <= 0:
            problems.append(
                f"autotune.interval_s={self.interval_s} must be > 0 (a "
                "zero interval busy-loops the controller thread)"
            )
        if self.min_dispatches < 1:
            problems.append(
                f"autotune.min_dispatches={self.min_dispatches} must be "
                ">= 1 (0 would regrid on an empty histogram)"
            )
        if self.max_entries < 2:
            problems.append(
                f"autotune.max_entries={self.max_entries} must be >= 2 "
                "(every grid needs at least a batch-1 bucket and a tail "
                "bucket)"
            )
        if self.min_gain_pct < 0:
            problems.append(
                f"autotune.min_gain_pct={self.min_gain_pct} must be >= 0"
            )
        if self.cooldown_s < 0:
            problems.append(
                f"autotune.cooldown_s={self.cooldown_s} must be >= 0"
            )
        if self.enabled and not self.plan_dir:
            problems.append(
                "autotune.enabled=true requires autotune.plan_dir (the "
                "plan root sibling replicas adopt from)"
            )
        if problems:
            raise AutotuneConfigError("; ".join(problems))
        return self


@dataclasses.dataclass
class CacheConfig:
    """Persistent AOT executable cache (`mlops_tpu/compilecache/`)."""

    dir: str = ""  # cache directory; empty (default) = caching OFF. Set
    # (or export MLOPS_TPU_CACHE_DIR) and every hot program — the serve
    # engine's bucketed/grouped predicts, the dense train window, the TP
    # pjit step, the bulk chunk scorer — deserializes its compiled
    # executable from here instead of re-XLA-compiling per process; the
    # `warmup` CLI pre-populates it (e.g. at container build time)
    warmup_workers: int = 0  # parallel compile threads for warmup misses
    # (XLA compilation releases the GIL); 0 = auto: min(8, cpu count)


@dataclasses.dataclass
class Config:
    data: DataConfig = dataclasses.field(default_factory=DataConfig)
    model: ModelConfig = dataclasses.field(default_factory=ModelConfig)
    train: TrainConfig = dataclasses.field(default_factory=TrainConfig)
    hpo: HPOConfig = dataclasses.field(default_factory=HPOConfig)
    monitor: MonitorConfig = dataclasses.field(default_factory=MonitorConfig)
    serve: ServeConfig = dataclasses.field(default_factory=ServeConfig)
    registry: RegistryConfig = dataclasses.field(default_factory=RegistryConfig)
    score: ScoreConfig = dataclasses.field(default_factory=ScoreConfig)
    lifecycle: LifecycleConfig = dataclasses.field(
        default_factory=LifecycleConfig
    )
    trace: TraceConfig = dataclasses.field(default_factory=TraceConfig)
    slo: SLOConfig = dataclasses.field(default_factory=SLOConfig)
    autotune: AutotuneConfig = dataclasses.field(
        default_factory=AutotuneConfig
    )
    cache: CacheConfig = dataclasses.field(default_factory=CacheConfig)
    # (mesh: MeshConfig was removed — its data_axis/model_axis index knobs
    # were never read; the mesh axis layout is the hardcoded
    # parallel/mesh.py AXES, and sizing flows through make_mesh(n,
    # model_parallel=...) arguments. TPU503 dead-knob cleanup.)


def _tuple_element_type(owner: type, field: str) -> type:
    """Element type of a ``tuple[X, ...]`` dataclass field, read from the
    annotation — the one place the type is stated, instead of guessing
    from the (possibly empty) current value."""
    import typing

    args = typing.get_args(typing.get_type_hints(owner).get(field, tuple))
    return args[0] if args else str


def _coerce(current: Any, raw: str, inner: type = str) -> Any:
    if isinstance(current, bool):
        return raw.lower() in ("1", "true", "yes", "on")
    if isinstance(current, int):
        return int(raw)
    if isinstance(current, float):
        return float(raw)
    if isinstance(current, tuple):
        body = raw.strip("()[] ")
        if inner is str:
            # String tuples (hpo.architectures) hold comma-containing
            # specs ("hidden_dims=16,embed_dim=8"), so their CLI/env
            # items separate on ';':
            # hpo.architectures='hidden_dims=16;family=bert'.
            return tuple(x.strip() for x in body.split(";") if x.strip())
        return tuple(inner(x) for x in body.split(",") if x.strip())
    return raw


def _apply(config: Config, section: str, field: str, value: Any) -> None:
    sub = getattr(config, section, None)
    if sub is None or not hasattr(sub, field):
        raise KeyError(f"unknown config key {section}.{field}")
    current = getattr(sub, field)
    if isinstance(value, str) and not isinstance(current, str):
        inner = (
            _tuple_element_type(type(sub), field)
            if isinstance(current, tuple)
            else str
        )
        value = _coerce(current, value, inner)
    if isinstance(current, tuple) and isinstance(value, list):
        value = tuple(value)
    setattr(sub, field, value)


def load_config(
    toml_path: str | Path | None = None,
    overrides: list[str] | None = None,
    env: dict[str, str] | None = None,
) -> Config:
    """Build a Config: defaults <- TOML <- env <- CLI overrides."""
    config = Config()
    if toml_path:
        with open(toml_path, "rb") as f:
            doc = tomllib.load(f)
        for section, fields in doc.items():
            for field, value in fields.items():
                _apply(config, section, field, value)
    env = dict(os.environ if env is None else env)
    for key, raw in env.items():
        if not key.startswith("MLOPS_TPU_"):
            continue
        parts = key[len("MLOPS_TPU_") :].lower().split("_", 1)
        if len(parts) != 2:
            warnings.warn(f"ignoring malformed env override {key}", stacklevel=2)
            continue
        section, field = parts
        try:
            _apply(config, section, field, raw)
        except KeyError:
            warnings.warn(f"ignoring unknown env override {key}", stacklevel=2)
    for item in overrides or []:
        key, _, raw = item.partition("=")
        section, _, field = key.strip("-").partition(".")
        _apply(config, section, field, raw)
    return config
