"""`pio tune`: mesh-packed hyperparameter sweeps (ISSUE 15).

The reference's fifth DASE letter — Evaluation — tunes by looping
EngineParams variants through full re-trains (EvaluationWorkflow.scala;
MLlib CrossValidation does the same serial loop). On TPU that loop is
exactly backwards: ALX (arXiv:2112.02194) shows the wins come from
keeping the chips saturated, and a rank/λ/α grid of dozens of SMALL
independent ALS trains is the ideal many-small-problems saturation
workload. This module packs the whole grid into one compiled program:

- ``TuneSupervisor`` takes an EngineParams grid (typically from an
  ``EngineParamsGenerator``), wraps the engine in ``FastEvalEngine`` so
  the data/prepare stages memoize ONCE across every trial, and — when
  every trial is a single ALS algorithm exposing the ``als_config()``
  hook over Ratings folds — trains all trials per fold via
  ``models/als.train_als_grid`` (per-rank vmapped λ/α lanes, one jitted
  dispatch per iteration, bitwise-equal to serial training) and seeds
  the resulting models into the FastEvalEngine cache, so each trial's
  ``eval`` scores straight from cache.
- Each trial's score-and-record body runs under a PR-8
  ``TrainSupervisor`` (classify/retry): a diverging or faulted trial
  becomes a FAILED leaderboard row — it never kills the grid. The
  ``tune.trial`` chaos site proves that isolation.
- ``run_tune`` drives the end-to-end pipeline: tune -> train the
  winner on the FULL training data (``run_train`` — supervised,
  persisted, heartbeated) -> stamp the leaderboard into the winner's
  ``EngineInstance.tuning`` and its eval result into
  ``evaluator_results`` -> emit the eval-gate decision against the
  incumbent instance (same promote-iff-no-regression semantics as the
  PR-10 streaming gate: candidate >= baseline - gate). ``pio tune
  --deploy`` deploys only on promote.

Per-trial convergence streams into ``ConvergenceTracker`` under
``source="tune:<trial>"``; the grid emits ``pio_tune_*`` metrics (see
docs/operations.md's catalog).
"""

from __future__ import annotations

import dataclasses
import json
import logging
import math
import time
from typing import Any, Sequence

from ..controller.engine import Engine
from ..controller.evaluation import MetricEvaluatorResult, MetricScores
from ..controller.fast_eval import FastEvalEngine
from ..controller.metric import Metric
from ..controller.params import EngineParams, params_to_json
from ..faults import FAULTS
from ..obs.metrics import METRICS
from ..obs.training import TRAINING
from ..storage import Storage
from ..storage.frame import Ratings
from ..storage.metadata import EngineInstance
from .context import Context
from .core_workflow import run_train, stamp_evaluator_results
from .supervisor import TrainSupervisor

log = logging.getLogger("predictionio_tpu.tuning")

__all__ = ["TrialResult", "TuneResult", "TuneSupervisor", "run_tune",
           "tune_gate_decision"]

_M_TRIALS = METRICS.counter(
    "pio_tune_trials_total",
    "tuning trials by terminal status (workflow/tuning.py; FAILED rows "
    "stay on the leaderboard — they never kill the grid)",
    labelnames=("status",))
for _s in ("COMPLETED", "FAILED"):
    _M_TRIALS.labels(status=_s).inc(0)
_M_GRID_S = METRICS.histogram(
    "pio_tune_grid_seconds",
    "wall clock of one packed grid train: every trial x every eval fold "
    "through train_als_grid (excludes scoring)")
_M_TRIAL_S = METRICS.histogram(
    "pio_tune_trial_seconds",
    "per-trial supervised score-and-record wall clock (cache-served "
    "model + metric calculation; includes retries)")
_M_BEST = METRICS.gauge(
    "pio_tune_best_score",
    "primary-metric score of the current tuning leaderboard winner")


@dataclasses.dataclass
class TrialResult:
    """One leaderboard row: a trial's params, terminal status and score.
    ``status`` is COMPLETED or FAILED — a FAILED trial keeps its row
    (with ``error``) so the operator sees WHICH config diverged."""

    index: int
    params: EngineParams
    status: str
    score: Any = None
    other_scores: tuple = ()
    error: str = ""
    attempts: int = 1
    seconds: float = 0.0
    convergence: list = dataclasses.field(default_factory=list)

    def to_row(self) -> dict:
        return {
            "trial": self.index,
            "status": self.status,
            "score": self.score,
            "otherScores": list(self.other_scores),
            "error": self.error,
            "attempts": self.attempts,
            "seconds": round(self.seconds, 3),
            "algorithmsParams":
                self.params.to_json_dict().get("algorithmsParams"),
            "convergence": self.convergence,
        }


@dataclasses.dataclass
class TuneResult:
    """A whole sweep's outcome: every trial's row plus the winner."""

    trials: list[TrialResult]
    best_idx: int  # winning TRIAL index (trials[i].index), -1 if none
    metric_header: str
    other_metric_headers: tuple[str, ...]
    lower_is_better: bool
    grid_mode: str  # "vmapped" (packed program) | "serial" (fallback)
    grid_seconds: float = 0.0

    @property
    def winner(self) -> TrialResult | None:
        for t in self.trials:
            if t.index == self.best_idx:
                return t
        return None

    def completed(self) -> list[TrialResult]:
        return [t for t in self.trials if t.status == "COMPLETED"]

    def to_metric_result(self) -> MetricEvaluatorResult:
        """The COMPLETED rows as a MetricEvaluatorResult — the shape
        ``stamp_evaluator_results`` / best.json already speak."""
        done = self.completed()
        if not done:
            raise ValueError("no completed trials to rank")
        scored = [(t.params, MetricScores(t.score, list(t.other_scores)))
                  for t in done]
        bi = next(i for i, t in enumerate(done) if t.index == self.best_idx)
        return MetricEvaluatorResult(
            best_score=scored[bi][1],
            best_engine_params=scored[bi][0],
            best_idx=bi,
            metric_header=self.metric_header,
            other_metric_headers=list(self.other_metric_headers),
            engine_params_scores=scored,
            lower_is_better=self.lower_is_better,
        )

    def leaderboard_json(self) -> str:
        """The ``EngineInstance.tuning`` document (also `/tune.json`)."""
        return json.dumps({
            "metricHeader": self.metric_header,
            "otherMetricHeaders": list(self.other_metric_headers),
            "lowerIsBetter": self.lower_is_better,
            "bestTrial": self.best_idx,
            "gridMode": self.grid_mode,
            "gridSeconds": round(self.grid_seconds, 3),
            "trials": [t.to_row() for t in self.trials],
        }, default=str)

    def pretty_print(self) -> str:
        lines = [f"Tuning leaderboard ({self.metric_header}, "
                 f"{self.grid_mode} grid):"]
        done = sorted(
            self.completed(),
            key=lambda t: t.score if t.score is not None else 0.0,
            reverse=not self.lower_is_better)
        for pos, t in enumerate(done):
            star = "  <== WINNER" if t.index == self.best_idx else ""
            lines.append(
                f"  {pos + 1:2d}. trial #{t.index} "
                f"[{self.metric_header}={t.score}] "
                f"({t.seconds:.2f}s, {t.attempts} attempt(s)){star}")
        for t in self.trials:
            if t.status != "COMPLETED":
                lines.append(f"   -. trial #{t.index} FAILED: {t.error}")
        return "\n".join(lines)


def _prefix_key(ep: EngineParams) -> str:
    """data-source + preparator identity of a variant (the shared-fold
    precondition of the packed grid)."""
    return (params_to_json(ep.data_source_params) + "|"
            + params_to_json(ep.preparator_params))


class TuneSupervisor:
    """Run an EngineParams grid as one mesh-packed program and rank it.

    ``run(ctx, engine_params_list)`` returns a ``TuneResult`` whose
    trials are 1:1 with the input grid, in order. Per-trial failures
    (divergence, injected ``tune.trial`` chaos, metric errors) are
    classified by the PR-8 supervisor — transient ones retry up to
    ``max_retries`` — and a trial that still fails becomes a FAILED row
    without affecting its neighbors.
    """

    def __init__(self, engine: Engine, metric: Metric,
                 other_metrics: Sequence[Metric] = (), *,
                 max_retries: int = 0, retry_backoff_s: float = 0.25,
                 backoff_cap_s: float = 5.0, rng=None):
        self.engine = engine
        self.metric = metric
        self.other_metrics = list(other_metrics)
        self.max_retries = max_retries
        self.retry_backoff_s = retry_backoff_s
        self.backoff_cap_s = backoff_cap_s
        self.rng = rng
        self.grid_mode = "serial"
        self.grid_seconds = 0.0

    # -- engine wrapping ---------------------------------------------------
    def _wrap(self, engine: Engine) -> Engine:
        try:
            return FastEvalEngine.wrap(engine)
        except ValueError as e:
            log.info("FastEvalEngine unavailable (%s); tuning without "
                     "prefix memoization", e)
            return engine

    # -- packed grid train -------------------------------------------------
    def _grid_configs(self, eng: Engine, eps: list[EngineParams]):
        """Per-trial ALSConfigs when EVERY trial is one ALS algorithm
        exposing the ``als_config()`` hook, else None (serial path)."""
        configs = []
        for ep in eps:
            if len(list(ep.algorithm_params_list)) != 1:
                return None
            _names, algos = eng.make_algorithms(ep)
            hook = getattr(algos[0], "als_config", None)
            if hook is None:
                return None
            configs.append(hook())
        return configs

    def _grid_train(self, ctx, eng: Engine, eps: list[EngineParams]) -> None:
        """Try the packed path: train every trial x every fold via
        ``train_als_grid`` and seed the FastEvalEngine model cache. Any
        incompatibility (multi-algo trials, non-ALS algorithms, mixed
        data-source params, non-Ratings prepared data, incompatible
        configs) falls back to the serial per-trial path — the sweep
        still completes, just without the packed speedup."""
        if not isinstance(eng, FastEvalEngine):
            return
        if len({_prefix_key(ep) for ep in eps}) != 1:
            log.info("grid trials differ in data/prepare params; "
                     "training serially")
            return
        configs = self._grid_configs(eng, eps)
        if configs is None:
            log.info("grid trials are not single-ALS (no als_config hook); "
                     "training serially")
            return
        try:
            prepared = eng._prepared(ctx, eps[0])
            if not prepared:
                return  # no eval folds — scoring will surface the error
            if not all(isinstance(pd, Ratings) for pd, _ei, _qa in prepared):
                log.info("prepared eval data is not Ratings; training "
                         "serially")
                return
            from ..models.als import train_als_grid

            iters = configs[0].iterations
            n_folds, n_trials = len(prepared), len(eps)
            for idx in range(n_trials):
                TRAINING.reset_source(f"tune:{idx}")
                TRAINING.begin(f"tune:{idx}",
                               total_iterations=iters * n_folds)
            t0 = time.perf_counter()
            fold_models = []
            for f, (pd, _ei, _qa) in enumerate(prepared):

                def observe(idx, it, loss, delta, step_s, _f=f):
                    # step_s covers the WHOLE grid dispatch — attribute
                    # an even per-trial share
                    TRAINING.observe(f"tune:{idx}", _f * iters + it,
                                     loss=loss, delta_norm=delta,
                                     step_seconds=step_s / max(1, n_trials))

                fold_models.append(
                    train_als_grid(pd, configs, mesh=ctx.mesh,
                                   observe=observe))
            self.grid_seconds = time.perf_counter() - t0
            _M_GRID_S.record(self.grid_seconds)
            for idx, ep in enumerate(eps):
                eng.seed_models(
                    ep, [[fold_models[f][idx]] for f in range(n_folds)])
            self.grid_mode = "vmapped"
            log.info("packed grid trained: %d trial(s) x %d fold(s) in "
                     "%.2fs", n_trials, n_folds, self.grid_seconds)
        except Exception as e:
            log.warning("packed grid train unavailable (%s: %s); trials "
                        "train serially", type(e).__name__, e)

    # -- per-trial supervised scoring --------------------------------------
    def _score_trial(self, ctx, eng: Engine, idx: int,
                     ep: EngineParams) -> TrialResult:
        src = f"tune:{idx}"
        sup = TrainSupervisor(
            max_retries=self.max_retries,
            retry_backoff_s=self.retry_backoff_s,
            backoff_cap_s=self.backoff_cap_s,
            rng=self.rng)

        def body():
            # chaos site: one trial's failure must become a FAILED
            # leaderboard row, never kill the grid (arm times=1)
            FAULTS.fire("tune.trial")
            folds = eng.eval(ctx, ep)
            if not folds:
                raise ValueError(
                    "data source produced no eval folds — set eval_k >= 2")
            fold_tuples = [(f.eval_info, f.qpa) for f in folds]
            score = self.metric.calculate(ctx, fold_tuples)
            if isinstance(score, float) and not math.isfinite(score):
                raise ValueError(
                    f"trial diverged: {self.metric.header()}={score}")
            others = [m.calculate(ctx, fold_tuples)
                      for m in self.other_metrics]
            return score, others

        t0 = time.perf_counter()
        try:
            score, others = sup.run(body)
            status, err = "COMPLETED", ""
        except Exception as e:
            score, others = None, []
            status, err = "FAILED", f"{type(e).__name__}: {e}"
            log.warning("tune trial %d FAILED after %d attempt(s): %s",
                        idx, sup.attempts, err)
        seconds = time.perf_counter() - t0
        _M_TRIAL_S.record(seconds)
        _M_TRIALS.labels(status=status).inc()
        conv: list = []
        if self.grid_mode == "vmapped":
            TRAINING.finish(src, status)
            conv = TRAINING.summaries(src)
        return TrialResult(index=idx, params=ep, status=status, score=score,
                           other_scores=tuple(others), error=err,
                           attempts=sup.attempts, seconds=seconds,
                           convergence=conv)

    def run(self, ctx, engine_params_list: Sequence[EngineParams]) -> TuneResult:
        eps = list(engine_params_list)
        if not eps:
            raise ValueError("empty EngineParams grid")
        eng = self._wrap(self.engine)
        self._grid_train(ctx, eng, eps)
        trials = [self._score_trial(ctx, eng, idx, ep)
                  for idx, ep in enumerate(eps)]
        done = [t for t in trials if t.status == "COMPLETED"
                and t.score is not None]
        best_idx = -1
        if done:
            best = max(done, key=lambda t: self.metric.compare_key(t.score))
            best_idx = best.index
            try:
                _M_BEST.set(float(best.score))
            except (TypeError, ValueError):
                pass
        result = TuneResult(
            trials=trials,
            best_idx=best_idx,
            metric_header=self.metric.header(),
            other_metric_headers=tuple(m.header()
                                       for m in self.other_metrics),
            lower_is_better=bool(self.metric.lower_is_better),
            grid_mode=self.grid_mode,
            grid_seconds=self.grid_seconds,
        )
        log.info("tuning done: %d/%d trial(s) completed, winner=%s",
                 len(done), len(trials),
                 best_idx if best_idx >= 0 else "none")
        return result


# -- eval-gated promotion ---------------------------------------------------
def _stamped_best_score(inst: EngineInstance | None) -> float | None:
    """The incumbent's primary-metric score, from its stamped eval result
    (or its tuning leaderboard's winner). None = nothing comparable."""
    if inst is None:
        return None
    try:
        doc = json.loads(inst.evaluator_results_json or "null")
        if doc and doc.get("bestScore"):
            return float(doc["bestScore"][0])
    except (ValueError, TypeError):
        pass
    try:
        doc = json.loads(inst.tuning or "null")
        if doc:
            for row in doc.get("trials", ()):
                if row.get("trial") == doc.get("bestTrial"):
                    return float(row["score"])
    except (ValueError, TypeError):
        pass
    return None


def tune_gate_decision(tune: TuneResult, baseline: float | None,
                       eval_gate: float | None) -> dict:
    """Promotion gate with the PR-10 streaming-gate semantics
    (workflow/streaming.py _gate_decision): promote iff the candidate
    does not regress past ``eval_gate`` vs the incumbent's stamped score
    (inequality flipped for lower-is-better metrics). ``eval_gate=None``
    -> ungated (always deploy); no incumbent -> promote."""
    winner = tune.winner
    cand = winner.score if winner is not None else None
    d = {"metric": tune.metric_header, "candidate": cand,
         "baseline": baseline, "threshold": eval_gate}
    if eval_gate is None:
        d["decision"] = "ungated"
    elif cand is None:
        d["decision"] = "hold"
    elif baseline is None:
        d["decision"] = "promote"
    elif tune.lower_is_better:
        d["decision"] = ("promote" if cand <= baseline + eval_gate
                         else "hold")
    else:
        d["decision"] = ("promote" if cand >= baseline - eval_gate
                         else "hold")
    return d


def run_tune(
    engine: Engine,
    engine_params_list: Sequence[EngineParams],
    metric: Metric,
    other_metrics: Sequence[Metric] = (),
    ctx: Context | None = None,
    *,
    engine_id: str = "default",
    engine_version: str = "1",
    engine_variant: str = "default",
    engine_factory: str = "",
    batch: str = "",
    evaluator_class: str = "",
    max_retries: int = 0,
    retry_backoff_s: float = 0.25,
    eval_gate: float | None = None,
    best_json_path: str | None = None,
    train_max_retries: int = 0,
    train_budget_s: float | None = None,
) -> tuple[str, TuneResult, dict]:
    """The whole pipeline: tune the grid, train the WINNER on the full
    training data (supervised + persisted ``run_train``), stamp the
    leaderboard + eval result onto the winner's EngineInstance, and
    return ``(engine_instance_id, TuneResult, gate)`` where ``gate`` is
    the promotion decision vs the incumbent (the instance that was
    latest-completed BEFORE this run). ``pio tune --deploy`` serves the
    new instance only when the gate says promote/ungated."""
    ctx = ctx or Context(mode="Evaluation", batch=batch)
    supervisor = TuneSupervisor(
        engine, metric, other_metrics,
        max_retries=max_retries, retry_backoff_s=retry_backoff_s)
    tune = supervisor.run(ctx, engine_params_list)
    winner = tune.winner
    if winner is None:
        raise RuntimeError(
            "tuning produced no completed trial — nothing to train "
            f"({sum(1 for t in tune.trials if t.status == 'FAILED')} "
            "FAILED)")
    result = tune.to_metric_result()
    if best_json_path:
        with open(best_json_path, "w") as f:
            json.dump(winner.params.to_json_dict(), f, indent=2, default=str)

    # the incumbent BEFORE the winner trains — the baseline the gate
    # compares against
    meta = Storage.get_metadata()
    incumbent = meta.engine_instance_get_latest_completed(
        engine_id, engine_version, engine_variant)
    baseline = _stamped_best_score(incumbent)

    iid = run_train(
        engine, winner.params, None,
        engine_id=engine_id, engine_version=engine_version,
        engine_variant=engine_variant, engine_factory=engine_factory,
        batch=batch, max_retries=train_max_retries,
        train_budget_s=train_budget_s)
    stamp_evaluator_results(iid, result, evaluator_class=evaluator_class,
                            tuning_json=tune.leaderboard_json())
    gate = tune_gate_decision(tune, baseline, eval_gate)
    log.info("tune winner trial #%d trained as instance %s; gate=%s",
             winner.index, iid, gate["decision"])
    return iid, tune, gate
