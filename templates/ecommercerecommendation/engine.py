"""E-commerce recommendation template — ALS + real-time business filters.

Analog of the reference's scala-parallel-ecommercerecommendation
train-with-rate-event variant (reference: examples/scala-parallel-
ecommercerecommendation/train-with-rate-event/src/main/scala/
ALSAlgorithm.scala, 436 LoC): implicit ALS over view/buy events, and at
``predict()`` time the engine queries the LIVE event store for

- the user's recently seen items (ALSAlgorithm.scala:160-181),
- the latest ``$set`` of the ``constraint/unavailableItems`` entity
  (ALSAlgorithm.scala:194-216),

merges them with the query's blackList, and serves top-N from the
remaining candidates — so business rules take effect without retraining.
Unseen users fall back to scoring against their recent view events'
item factors (predictNewUser, :285).

TPU note (SURVEY §7 hard part (b)): dynamic filters never reshape device
arrays — they become boolean candidate masks over the fixed item axis.
"""

from __future__ import annotations

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
from predictionio_tpu.models.als import ALSConfig, ALSModel, train_als
from predictionio_tpu.storage.frame import Ratings


@dataclass(frozen=True)
class DataSourceParams(Params):
    app_name: str = "MyApp"


@dataclass(frozen=True)
class AlgorithmParams(Params):
    """(reference ECommAlgorithmParams: appName, unseenOnly, seenEvents,
    similarEvents, rank, numIterations, lambda, alpha, seed)"""

    app_name: str = "MyApp"
    unseen_only: bool = True
    seen_events: tuple = ("buy", "view")
    rank: int = 10
    num_iterations: int = 10
    lambda_: float = 0.01
    alpha: float = 1.0
    seed: int = 3
    #: TTL for the global constraint/unavailableItems lookup. The entity
    #: is catalog-global and changes rarely, but the reference re-reads
    #: it on EVERY query (ALSAlgorithm.scala:194-216) — under the
    #: micro-batcher those reads serialize inside the batch. Staleness is
    #: bounded by this many seconds; 0 restores per-query reads.
    constraint_ttl_seconds: float = 5.0


@dataclass(frozen=True)
class Query:
    user: str
    num: int = 10
    categories: tuple | None = None
    whiteList: tuple | None = None
    blackList: tuple | None = None


@dataclass(frozen=True)
class ItemScore:
    item: str
    score: float


@dataclass(frozen=True)
class PredictedResult:
    itemScores: tuple = ()


class TrainingData(SanityCheck):
    def __init__(self, ratings: Ratings, item_categories: dict[str, tuple]):
        self.ratings = ratings
        self.item_categories = item_categories

    def sanity_check(self) -> None:
        if len(self.ratings) == 0:
            raise ValueError("No view/buy events found; import data first.")


class ECommDataSource(DataSource):
    params_class = DataSourceParams

    def read_training(self, ctx) -> TrainingData:
        store = ctx.event_store()
        items = store.aggregate_properties(
            app_name=self.params.app_name, entity_type="item"
        )
        item_categories = {
            iid: tuple(pm.get_or_else("categories", []) or [])
            for iid, pm in items.items()
        }
        ratings = store.find_frame(
            app_name=self.params.app_name,
            entity_type="user", event_names=("view", "buy"),
            target_entity_type="item",
        ).to_ratings(
            # buy counts stronger than view (reference weights buy as rate-4)
            rating_of=lambda name, props: 2.0 if name == "buy" else 1.0,
            dedup_latest=False,
        )
        return TrainingData(ratings, item_categories)


class ECommPreparator(Preparator):
    def prepare(self, ctx, td: TrainingData) -> TrainingData:
        return td


class ECommModel:
    def __init__(self, als: ALSModel, item_categories: dict[str, tuple]):
        self.als = als
        self.item_categories = item_categories


class ECommAlgorithm(Algorithm):
    params_class = AlgorithmParams
    query_class = Query

    def __init__(self, params=None):
        super().__init__(params)
        self._store = None  # live event-store handle, bound lazily
        # constraint TTL cache: (expiry_monotonic, frozenset) — written
        # atomically (single assignment) so concurrent micro-batch
        # dispatch threads need no lock
        self._constraint_cache = (0.0, frozenset())

    def train(self, ctx, td: TrainingData) -> ECommModel:
        cfg = ALSConfig(
            rank=self.params.rank, iterations=self.params.num_iterations,
            lambda_=self.params.lambda_, alpha=self.params.alpha,
            implicit_prefs=True, seed=self.params.seed,
        )
        return ECommModel(train_als(td.ratings, cfg, mesh=ctx.mesh),
                          td.item_categories)

    # -- live lookups (the reference's LEventStore calls at predict time) --
    def _event_store(self):
        if self._store is None:
            from predictionio_tpu.store import EventStore

            self._store = EventStore(default_app_name=self.params.app_name)
        return self._store

    def _seen_items(self, user: str) -> set[str]:
        """(ALSAlgorithm.scala:160-181; limit mirrors its list size)"""
        return set(self._seen_weights(user))

    def _seen_weights(self, user: str) -> dict:
        """item -> summed training-style weight (buy=2, view=1, repeats
        accumulate) over the user's recent events — the same confidence
        inputs training derives from these events, so fold-in matches
        what training would have produced."""
        try:
            events = self._event_store().find(
                entity_type="user", entity_id=user,
                event_names=tuple(self.params.seen_events),
                target_entity_type="item", limit=100, latest=True,
            )
            weights: dict = {}
            for e in events:
                if e.target_entity_id:
                    w = 2.0 if e.event == "buy" else 1.0
                    weights[e.target_entity_id] = \
                        weights.get(e.target_entity_id, 0.0) + w
            return weights
        except Exception:
            return {}

    def _unavailable_items(self) -> set[str]:
        """Latest $set of the constraint/unavailableItems entity
        (ALSAlgorithm.scala:194-216), TTL-cached: the entity is global,
        so its staleness bound is ``constraint_ttl_seconds``, not
        one-store-read-per-query."""
        import time as _time

        ttl = getattr(self.params, "constraint_ttl_seconds", 0.0)
        expiry, cached = self._constraint_cache
        if ttl > 0 and _time.monotonic() < expiry:
            return set(cached)
        items = self._read_unavailable_items()
        if ttl > 0:
            self._constraint_cache = (_time.monotonic() + ttl,
                                      frozenset(items))
        return items

    def _read_unavailable_items(self) -> set[str]:
        try:
            pm = self._event_store().aggregate_properties(
                entity_type="constraint"
            ).get("unavailableItems")
            if pm is None:
                return set()
            return set(pm.get_or_else("items", []) or [])
        except Exception:
            return set()

    def _candidate_mask(self, model: ECommModel, query: Query,
                        seen: dict | None = None) -> np.ndarray:
        als = model.als
        ni = len(als.item_ids)
        mask = np.ones(ni, bool)
        if query.categories:
            cats = set(query.categories)
            for iid, row in als.item_ids.items():
                if not (cats & set(model.item_categories.get(iid, ()))):
                    mask[row] = False
        if query.whiteList:
            wl = np.zeros(ni, bool)
            for iid in query.whiteList:
                row = als.item_ids.get(iid)
                if row is not None:
                    wl[row] = True
            mask &= wl
        block = set(query.blackList or ())
        block |= self._unavailable_items()
        if self.params.unseen_only:
            block |= self._seen_items_cached(query.user, seen)
        for iid in block:
            row = als.item_ids.get(iid)
            if row is not None:
                mask[row] = False
        return mask

    def _seen_items_cached(self, user: str, seen: dict | None) -> set[str]:
        """Per-micro-batch memo of the seen-items lookup: a batch often
        repeats users, and each store read serializes inside the batch."""
        if seen is None:
            return self._seen_items(user)
        if user not in seen:
            seen[user] = self._seen_items(user)
        return seen[user]

    def predict(self, model: ECommModel, query: Query) -> PredictedResult:
        return self._predict_one(model, query, None)

    def batch_predict(self, model: ECommModel, queries):
        """One micro-batch: the seen-items lookups dedupe per user via a
        batch-scoped memo (the global constraint read is TTL-cached in
        _unavailable_items); the reference does two sequential store
        reads per query on this path."""
        seen: dict = {}
        return [(i, self._predict_one(model, q, seen)) for i, q in queries]

    def _predict_one(self, model: ECommModel, query: Query,
                     seen: dict | None) -> PredictedResult:
        als = model.als
        mask = self._candidate_mask(model, query, seen)
        scores = als.scores_for_user(query.user)
        if scores is None:
            scores = self._new_user_scores(model, query, seen)
            if scores is None:
                return PredictedResult()
        scores = np.where(mask, scores, -np.inf)
        num = min(query.num, len(scores))
        top = np.argpartition(-scores, num - 1)[:num]
        top = top[np.argsort(-scores[top])]
        inv = als.item_ids.inverse
        return PredictedResult(itemScores=tuple(
            ItemScore(item=inv[int(i)], score=float(scores[i]))
            for i in top if np.isfinite(scores[i])
        ))

    def _new_user_scores(self, model: ECommModel, query: Query,
                         seen: dict | None = None) -> np.ndarray | None:
        """Unseen user: exact WALS fold-in from their recent events —
        the factor vector training would have produced (beyond the
        reference's predictNewUser item-factor averaging,
        ALSAlgorithm.scala:285+; ALSModel.fold_in_user)."""
        als = model.als
        # weights, not just ids: a 5x buyer folds in with 5x the
        # confidence of a one-time viewer, exactly as training would
        weights = self._seen_weights(query.user)
        if seen is not None:
            seen.setdefault(query.user, set(weights))
        items = sorted(weights)
        u = als.fold_in_user(items, [weights[i] for i in items])
        if u is None:
            return None
        return als.item_factors @ u


def engine_factory() -> Engine:
    return Engine(
        data_source_classes=ECommDataSource,
        preparator_classes=ECommPreparator,
        algorithm_classes={"ecomm": ECommAlgorithm},
        serving_classes=FirstServing,
    )
