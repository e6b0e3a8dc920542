"""Sequential recommendation engine template — self-attentive next-item
prediction with long-history sequence parallelism.

No counterpart exists in the reference (it predates sequence models; its
closest relative is the MarkovChain experimental engine, reference
e2/src/main/scala/io/prediction/e2/engine/MarkovChain.scala:201-260).
This template is the framework-native sequence family: "view"/"buy"/"rate"
events become per-user time-ordered item histories; a causal-attention
model (models/seq_attention.py) predicts the next item; histories longer
than one chip shard over a ``seq`` mesh axis via ring attention.

Four algorithms, one serving route (models/seq_serving.py: `pio deploy` ->
micro-batcher -> serving pipeline -> the retriever's fused top-k):

- ``seqrec``: the SASRec-style model above, learned positions, histories
  taken whole;
- ``looped``: a looped decoder (models/looped_lm.py, LoopLM / Ouro):
  ``num_hidden_layers`` layers whose weights are shared by
  ``total_ut_steps`` passes, RoPE, sandwich RMSNorms, SwiGLU, an exit
  gate, an untied head. Its params are the published ``config.json``'s
  keys, so the ``engine.json`` is the config a user copies:

      "algorithms": [{"name": "looped", "params": {
          "hidden_size": 2048, "intermediate_size": 5632,
          "num_hidden_layers": 48, "num_attention_heads": 16,
          "head_dim": 128, "total_ut_steps": 4,
          "early_exit_threshold": 1, "rms_norm_eps": 1e-06,
          "rope_theta": 1000000, "max_len": 512,
          "epochs": 10, "batch_size": 64, "lr": 0.001, "seed": 0}}]

  (the item table stands in for ``vocab_size``; ``max_len`` is the most
  events of a history that are kept; training at those widths does not
  fit one chip, serving does: PERF.md).
- ``latent_moe``: a latent-attention mixture-of-experts decoder
  (models/latent_moe_lm.py; the DeepSeek-V2/V3 family's block as A.X-K1
  publishes it): low-rank query and key/value bottlenecks, one rotary
  key shared by all heads, YaRN, a leading dense layer, then routed
  experts beside a shared one. Its params are the published config's
  keys plus the part of a deployment this process holds:
  ``first_layer`` and ``num_hidden_layers`` (the pipeline stage),
  ``first_expert`` and ``experts_held`` (the block of every routed
  layer's ``n_routed_experts`` that lives here; the router keeps its
  width and ``num_experts_per_tok``, and what the absent experts would
  add is left out). A serving step holds up to 8,192 packed tokens and
  ``max_len`` may be as long; ``exclude_seen`` is off by default and
  needs ``max_len`` <= 512 (the head's top-k keeps at most 528):

      "algorithms": [{"name": "latent_moe", "params": {
          "hidden_size": 7168, "intermediate_size": 18432,
          "moe_intermediate_size": 2048, "num_attention_heads": 64,
          "q_lora_rank": 1536, "kv_lora_rank": 512,
          "qk_nope_head_dim": 128, "qk_rope_head_dim": 64,
          "v_head_dim": 128, "n_routed_experts": 192,
          "n_shared_experts": 1, "num_experts_per_tok": 8,
          "first_k_dense_replace": 1, "routed_scaling_factor": 2.5,
          "first_layer": 0, "num_hidden_layers": 5,
          "first_expert": 0, "experts_held": 12, "max_len": 8192}}]

- ``hybrid_ssm``: a hybrid state-space decoder
  (models/hybrid_ssm_lm.py; granite-4.0-h-micro's block,
  ``model_type: granitemoehybrid``): Mamba-2 layers (a depthwise causal
  convolution and a selective scan whose state starts from zero at every
  history inside a packed step) with grouped-query attention layers
  between them in the order ``layer_types`` gives, no positional
  encoding, a shared SwiGLU MLP after every mixer, a tied head. Its
  params are the published config's keys; ``max_len`` up to the
  8,192-token step, ``exclude_seen`` as for ``latent_moe``:

      "algorithms": [{"name": "hybrid_ssm", "params": {
          "hidden_size": 2048, "num_hidden_layers": 40,
          "layer_types": ["mamba", "mamba", "mamba", "mamba", "mamba",
                          "attention", "mamba", "mamba", "mamba", "mamba",
                          "... the 40 of the published config ..."],
          "num_attention_heads": 32, "num_key_value_heads": 8,
          "attention_multiplier": 0.015625, "embedding_multiplier": 12,
          "residual_multiplier": 0.22, "logits_scaling": 8,
          "shared_intermediate_size": 8192, "mamba_n_heads": 64,
          "mamba_d_head": 64, "mamba_d_state": 128, "mamba_n_groups": 1,
          "mamba_d_conv": 4, "mamba_expand": 2, "mamba_chunk_size": 256,
          "rms_norm_eps": 1e-05, "position_embedding_type": "nope",
          "tie_word_embeddings": true, "max_len": 8192}}]

Query:  {"user": "u1", "num": 4}
Result: {"itemScores": [{"item": "i1", "score": 3.2}, ...]}
"""

from __future__ import annotations

import dataclasses
from dataclasses import dataclass

import numpy as np

from predictionio_tpu.controller import (
    Algorithm,
    DataSource,
    Engine,
    FirstServing,
    Params,
    Preparator,
    SanityCheck,
)
from predictionio_tpu.models.hybrid_ssm_lm import (
    HybridSSMConfig,
    HybridSSMModel,
    train_hybrid_ssm,
)
from predictionio_tpu.models.latent_moe_lm import (
    LatentMoEConfig,
    LatentMoEModel,
    train_latent_moe,
)
from predictionio_tpu.models.looped_lm import (
    LoopedLMConfig,
    LoopedLMModel,
    train_looped_lm,
)
from predictionio_tpu.models.seq_attention import (
    SeqRecConfig,
    SeqRecModel,
    build_sequences,
    train_seq_rec,
)


@dataclass(frozen=True)
class DataSourceParams(Params):
    app_name: str = "MyApp"
    event_names: tuple = ("view", "buy", "rate")


@dataclass(frozen=True)
class AlgorithmParams(Params):
    max_len: int = 64
    embed_dim: int = 48
    num_heads: int = 2
    num_blocks: int = 2
    epochs: int = 10
    batch_size: int = 256
    lr: float = 1e-3
    seq_parallel: bool = False
    seed: int = 0


@dataclass(frozen=True)
class LoopedParams(Params):
    """The published config's keys, then what training takes."""
    hidden_size: int = 2048
    intermediate_size: int = 5632
    num_hidden_layers: int = 48
    num_attention_heads: int = 16
    head_dim: int = 128
    total_ut_steps: int = 4
    early_exit_threshold: float = 1.0
    rms_norm_eps: float = 1e-6
    rope_theta: float = 1_000_000.0
    max_len: int = 512
    compute_dtype: str = "bfloat16"
    epochs: int = 10
    batch_size: int = 64
    lr: float = 1e-3
    seed: int = 0


@dataclass(frozen=True)
class LatentMoEParams(Params):
    """The published config's keys, the share held here, then serving
    and training: models/latent_moe_lm.py's ``LatentMoEConfig``, whose
    defaults are A.X-K1's."""
    hidden_size: int = 7168
    intermediate_size: int = 18432
    moe_intermediate_size: int = 2048
    num_attention_heads: int = 64
    q_lora_rank: int = 1536
    kv_lora_rank: int = 512
    qk_nope_head_dim: int = 128
    qk_rope_head_dim: int = 64
    v_head_dim: int = 128
    n_routed_experts: int = 192
    n_shared_experts: int = 1
    num_experts_per_tok: int = 8
    first_k_dense_replace: int = 1
    norm_topk_prob: bool = True
    routed_scaling_factor: float = 2.5
    scoring_func: str = "sigmoid"
    topk_method: str = "none"
    rms_norm_eps: float = 1e-6
    rope_theta: float = 10000.0
    rope_scaling: dict = dataclasses.field(default_factory=lambda: {
        "type": "yarn", "factor": 32,
        "original_max_position_embeddings": 4096, "beta_fast": 32,
        "beta_slow": 1, "mscale": 1, "mscale_all_dim": 1})
    first_layer: int = 0
    num_hidden_layers: int = 5
    first_expert: int = 0
    experts_held: int = 12
    max_len: int = 8192
    exclude_seen: bool = False
    compute_dtype: str = "bfloat16"
    epochs: int = 10
    batch_size: int = 16
    lr: float = 1e-3
    seed: int = 0


@dataclass(frozen=True)
class HybridSSMParams(HybridSSMConfig, Params):
    """models/hybrid_ssm_lm.py's ``HybridSSMConfig`` as it is: the
    published config's keys (defaults granite-4.0-h-micro's), then
    serving and training."""


@dataclass(frozen=True)
class Query:
    user: str
    num: int = 10


@dataclass(frozen=True)
class ItemScore:
    item: str
    score: float


@dataclass(frozen=True)
class PredictedResult:
    itemScores: tuple = ()


class TrainingData(SanityCheck):
    def __init__(self, users, items, times):
        self.users = users
        self.items = items
        self.times = times

    def sanity_check(self) -> None:
        if len(self.users) == 0:
            raise ValueError("No interaction events found; import data first.")


class SeqDataSource(DataSource):
    params_class = DataSourceParams

    def read_training(self, ctx) -> TrainingData:
        frame = ctx.event_store().find_frame(
            app_name=self.params.app_name,
            entity_type="user",
            event_names=tuple(self.params.event_names),
            target_entity_type="item",
        )
        has_target = np.asarray(
            [t is not None for t in frame.target_entity_id], dtype=bool
        )
        frame = frame.select(has_target)
        return TrainingData(frame.entity_id, frame.target_entity_id,
                            frame.event_time)


class SeqPreparator(Preparator):
    def prepare(self, ctx, td: TrainingData) -> TrainingData:
        return td


class SeqRecAlgorithm(Algorithm):
    params_class = AlgorithmParams
    query_class = Query

    def train(self, ctx, td: TrainingData) -> SeqRecModel:
        p = self.params
        cfg = SeqRecConfig(
            max_len=p.max_len, embed_dim=p.embed_dim, num_heads=p.num_heads,
            num_blocks=p.num_blocks, epochs=p.epochs, batch_size=p.batch_size,
            lr=p.lr, seq_parallel=p.seq_parallel, seed=p.seed,
        )
        seqs, uids, iids = build_sequences(
            td.users, td.items, td.times, max_len=cfg.max_len
        )
        return train_seq_rec(seqs, uids, iids, cfg, mesh=ctx.mesh)

    def predict(self, model, query: Query) -> PredictedResult:
        recs = model.recommend_products(query.user, query.num)
        return PredictedResult(
            itemScores=tuple(ItemScore(item=i, score=s) for i, s in recs)
        )

    def batch_predict(self, model, queries) -> list:
        """One device step for the whole micro-batch (the dispatcher in
        workflow/microbatch.py feeds this; per-query predict pays one
        step per request instead)."""
        recs = model.batch_recommend([q.user for _, q in queries],
                                     [q.num for _, q in queries])
        return [
            (i, PredictedResult(itemScores=tuple(
                ItemScore(item=t, score=s) for t, s in rec)))
            for (i, _q), rec in zip(queries, recs)
        ]

    def cost_budget(self, model) -> int:
        """Tokens a serving step holds: the micro-batcher's cut."""
        return model.serving_cost_budget

    def query_cost(self, model, query: Query) -> int:
        return model.serving_cost(query.user)


class LoopedAlgorithm(SeqRecAlgorithm):
    """The looped decoder behind the same queries and the same route."""

    params_class = LoopedParams

    def train(self, ctx, td: TrainingData) -> LoopedLMModel:
        cfg = LoopedLMConfig(**dataclasses.asdict(self.params))
        seqs, uids, iids = build_sequences(
            td.users, td.items, td.times, max_len=cfg.max_len
        )
        return train_looped_lm(seqs, uids, iids, cfg, mesh=ctx.mesh)


class LatentMoEAlgorithm(SeqRecAlgorithm):
    """The latent-attention mixture-of-experts decoder behind the same
    queries and the same route."""

    params_class = LatentMoEParams

    def train(self, ctx, td: TrainingData) -> LatentMoEModel:
        cfg = LatentMoEConfig(**dataclasses.asdict(self.params))
        seqs, uids, iids = build_sequences(
            td.users, td.items, td.times, max_len=cfg.max_len
        )
        return train_latent_moe(seqs, uids, iids, cfg, mesh=ctx.mesh)


class HybridSSMAlgorithm(SeqRecAlgorithm):
    """The hybrid state-space decoder behind the same queries and the
    same route."""

    params_class = HybridSSMParams

    def train(self, ctx, td: TrainingData) -> HybridSSMModel:
        cfg = HybridSSMConfig(**dataclasses.asdict(self.params))
        seqs, uids, iids = build_sequences(
            td.users, td.items, td.times, max_len=cfg.max_len
        )
        return train_hybrid_ssm(seqs, uids, iids, cfg, mesh=ctx.mesh)


def engine_factory() -> Engine:
    return Engine(
        data_source_classes=SeqDataSource,
        preparator_classes=SeqPreparator,
        algorithm_classes={"seqrec": SeqRecAlgorithm,
                           "looped": LoopedAlgorithm,
                           "latent_moe": LatentMoEAlgorithm,
                           "hybrid_ssm": HybridSSMAlgorithm},
        serving_classes=FirstServing,
    )
