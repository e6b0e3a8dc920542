"""`pio tune`: mesh-packed hyperparameter sweeps (workflow/tuning.py +
models/als.py train_als_grid).

Pins the contracts ISSUE 15 promises: the packed grid's per-trial factors
are BITWISE-equal to individually-trained serial runs; an injected
``tune.trial`` fault becomes one FAILED leaderboard row while every other
trial completes and the winner still trains and promotes; the leaderboard
lands on the winner's ``EngineInstance.tuning`` where `pio status` and
`/tune.json` read it."""

import json

import numpy as np
import pytest

from predictionio_tpu.controller import AverageMetric, EngineParams
from predictionio_tpu.models.als import ALSConfig, train_als, train_als_grid
from predictionio_tpu.obs.metrics import METRICS
from predictionio_tpu.storage import Storage
from predictionio_tpu.storage.frame import Ratings
from predictionio_tpu.testing.sample_engine import (
    SampleAlgoParams,
    SampleDataSourceParams,
    make_sample_engine,
)
from predictionio_tpu.workflow import Context, run_tune
from predictionio_tpu.faults import FAULTS
from predictionio_tpu.workflow.tuning import (
    TrialResult,
    TuneResult,
    TuneSupervisor,
    tune_gate_decision,
)
from tests.test_templates import insert, load_template, setup_app

pytestmark = pytest.mark.tune


def _make_ratings(rng, nu=40, ni=30, n=500):
    seen = {}
    while len(seen) < n:
        u, i = int(rng.integers(nu)), int(rng.integers(ni))
        seen[(u, i)] = float(rng.normal() + 3.0)
    return Ratings.from_triples(
        [f"u{u}" for u, _ in seen],
        [f"i{i}" for _, i in seen],
        list(seen.values()),
    )


# ---------------------------------------------------------------------------
# train_als_grid: the packed program itself
# ---------------------------------------------------------------------------

class TestTrainAlsGrid:
    def test_bitwise_parity_with_serial(self, mesh8, rng):
        """The tentpole contract: every trial of the packed grid produces
        factors BITWISE-equal to a serial train_als of the same config —
        the grid is an execution strategy, never a numerics change."""
        ratings = _make_ratings(rng)
        configs = [
            ALSConfig(rank=rank, iterations=3, lambda_=lam, seed=7)
            for rank in (5, 10)
            for lam in (0.01, 0.1)
        ]
        grid = train_als_grid(ratings, configs, mesh=mesh8)
        assert len(grid) == len(configs)
        for cfg, packed in zip(configs, grid):
            serial = train_als(ratings, cfg, mesh=mesh8)
            assert packed.user_factors.shape == (40, cfg.rank)
            assert np.array_equal(packed.user_factors,
                                  serial.user_factors), cfg
            assert np.array_equal(packed.item_factors,
                                  serial.item_factors), cfg

    @pytest.mark.parametrize("piecewise", [False, True])
    def test_parity_with_serial_over_a_split_layout(self, mesh8, monkeypatch,
                                                    piecewise):
        """The grid vmaps `_solve_side` over its lanes; where the layout's
        buckets are split, each lane takes its own hot slices (piece by
        piece past the equation budget, the slice's start then a lane's
        own value): the same factors as the serial trainer's."""
        import predictionio_tpu.models.als as als_mod
        from predictionio_tpu.ops import neighbors
        from predictionio_tpu.storage.bimap import BiMap
        from predictionio_tpu.storage.frame import Ratings
        from tests.helpers import SMALL_HOT_SLICES, zipf_coo

        monkeypatch.setattr(neighbors, "GATHER_NS_BY_TABLE_ROWS",
                            SMALL_HOT_SLICES)
        monkeypatch.setattr(neighbors, "COLD_WIDTH_SIGMAS", 0.0)
        if piecewise:
            monkeypatch.setattr(als_mod, "SOLVE_EQ_BUDGET_BYTES", 1)
        users, items, vals = zipf_coo(np.random.default_rng(3), 300, 200,
                                      9_000)
        ratings = Ratings(
            user_indices=users.astype(np.int32),
            item_indices=items.astype(np.int32), ratings=vals,
            user_ids=BiMap({f"u{i}": i for i in range(300)}),
            item_ids=BiMap({f"i{j}": j for j in range(200)}))
        configs = [ALSConfig(rank=6, iterations=2, lambda_=lam, seed=7,
                             chunk_cap=128) for lam in (0.05, 0.2)]
        grid = train_als_grid(ratings, configs, mesh=mesh8)
        for cfg, packed in zip(configs, grid):
            serial = train_als(ratings, cfg, mesh=mesh8)
            # not bitwise: a lane's gather of its slice is another program
            # than the serial step's slice, and CG answers the rounding
            np.testing.assert_allclose(packed.user_factors,
                                       serial.user_factors, rtol=1e-4,
                                       atol=1e-4)
            np.testing.assert_allclose(packed.item_factors,
                                       serial.item_factors, rtol=1e-4,
                                       atol=1e-4)

    def test_mixed_alpha_implicit_parity(self, mesh8, rng):
        """α is the third sweepable axis (implicit confidence scale).

        Parity here is ulp-level, not bitwise: the serial path bakes α
        into the compiled program as a constant (XLA folds ``1 + α·r``),
        while the grid must trace it as a per-lane scalar — same math,
        slightly different fused rounding. The bitwise contract above is
        for the explicit ridge path, where λ enters linearly and the
        traced/constant programs compile identically."""
        ratings = _make_ratings(rng, n=300)
        configs = [
            ALSConfig(rank=4, iterations=2, lambda_=0.05, alpha=a,
                      implicit_prefs=True, seed=7)
            for a in (1.0, 10.0, 40.0)
        ]
        grid = train_als_grid(ratings, configs, mesh=mesh8)
        for cfg, packed in zip(configs, grid):
            serial = train_als(ratings, cfg, mesh=mesh8)
            np.testing.assert_allclose(
                packed.user_factors, serial.user_factors,
                rtol=1e-3, atol=1e-5)
            np.testing.assert_allclose(
                packed.item_factors, serial.item_factors,
                rtol=1e-3, atol=1e-5)

    def test_config_validation(self, mesh8, rng):
        ratings = _make_ratings(rng, n=100)
        with pytest.raises(ValueError, match="empty config grid"):
            train_als_grid(ratings, [], mesh=mesh8)
        with pytest.raises(ValueError, match="iterations"):
            train_als_grid(
                ratings,
                [ALSConfig(rank=4, iterations=2),
                 ALSConfig(rank=4, iterations=3)],
                mesh=mesh8)
        with pytest.raises(ValueError, match="model_sharded"):
            train_als_grid(
                ratings, [ALSConfig(rank=4, model_sharded=True)], mesh=mesh8)
        with pytest.raises(ValueError, match="iterations >= 1"):
            train_als_grid(
                ratings, [ALSConfig(rank=4, iterations=0)], mesh=mesh8)

    def test_observe_callback(self, mesh8, rng):
        """observe fires per trial per iteration with a finite probe loss
        (lanes share a step, so step_seconds is the whole dispatch)."""
        ratings = _make_ratings(rng, n=200)
        configs = [ALSConfig(rank=4, iterations=3, lambda_=lam, seed=7)
                   for lam in (0.01, 0.1)]
        calls = []
        train_als_grid(ratings, configs, mesh=mesh8,
                       observe=lambda *a: calls.append(a))
        assert len(calls) == 2 * 3
        for idx, it, loss, _delta, step_s in calls:
            assert idx in (0, 1) and 0 <= it < 3
            assert loss is not None and np.isfinite(loss)
            assert step_s > 0
        # grid-step histogram observed one record per iteration
        assert METRICS.get(
            "pio_tune_grid_step_seconds").snapshot()["count"] == 3


# ---------------------------------------------------------------------------
# TuneSupervisor over the recommendation template (the vmapped path)
# ---------------------------------------------------------------------------

def _seed_recommendation(rng, nu=16, ni=12):
    """Low-rank rate events so the grid has signal to rank."""
    mod = load_template("recommendation")
    app = setup_app()
    u = rng.normal(size=(nu, 3)) + 1
    v = rng.normal(size=(ni, 3)) + 1
    full = u @ v.T
    for uu in range(nu):
        for ii in range(ni):
            if rng.random() < 0.7:
                insert(app.id, event="rate", entity_type="user",
                       entity_id=f"u{uu}", target_entity_type="item",
                       target_entity_id=f"i{ii}",
                       props={"rating": float(full[uu, ii])})
    return mod


def _grid(mod, ranks=(3, 4), lams=(0.01, 0.1)):
    ds = mod.DataSourceParams(app_name="MyApp", eval_k=2, eval_top_k=5)
    return [
        EngineParams(
            data_source_params=("", ds),
            algorithm_params_list=(
                ("als", mod.AlgorithmParams(rank=r, num_iterations=2,
                                            lambda_=lam)),
            ),
        )
        for r in ranks
        for lam in lams
    ]


class TestTuneSupervisor:
    def test_vmapped_sweep(self, mesh8, rng):
        mod = _seed_recommendation(rng)
        eps = _grid(mod)
        sup = TuneSupervisor(mod.engine_factory(), mod.HitRateAtK(5))
        res = sup.run(Context(mode="Evaluation"), eps)

        assert res.grid_mode == "vmapped"
        assert res.grid_seconds > 0
        assert [t.status for t in res.trials] == ["COMPLETED"] * 4
        assert res.best_idx in range(4)
        assert res.winner is res.trials[res.best_idx]
        assert all(t.score is not None and np.isfinite(t.score)
                   for t in res.trials)
        # per-trial convergence series flowed through ConvergenceTracker
        # (2 iterations x 2 folds per trial)
        for t in res.trials:
            assert len(t.convergence) == 1
            assert t.convergence[0]["iterations"] == 4
        # telemetry
        assert METRICS.get("pio_tune_trials_total").value("COMPLETED") == 4
        assert METRICS.get("pio_tune_trials_total").value("FAILED") == 0
        assert METRICS.get("pio_tune_grid_seconds").snapshot()["count"] == 1
        assert METRICS.get("pio_tune_trial_seconds").snapshot()["count"] == 4
        assert (METRICS.get("pio_tune_best_score").value()
                == res.winner.score)
        # leaderboard document round-trips
        doc = json.loads(res.leaderboard_json())
        assert doc["gridMode"] == "vmapped"
        assert doc["bestTrial"] == res.best_idx
        assert len(doc["trials"]) == 4
        assert "WINNER" in res.pretty_print()
        # and converts to the standard evaluator result shape
        mer = res.to_metric_result()
        assert mer.best_engine_params is eps[res.best_idx]

    def test_grid_scores_match_serial_eval(self, mesh8, rng):
        """Scoring from grid-seeded models equals a plain (non-packed)
        engine.eval of the same params — the end-to-end parity the
        operator actually cares about."""
        mod = _seed_recommendation(rng)
        eps = _grid(mod, ranks=(3,), lams=(0.01, 0.1))
        metric = mod.HitRateAtK(5)
        sup = TuneSupervisor(mod.engine_factory(), metric)
        res = sup.run(Context(mode="Evaluation"), eps)
        assert res.grid_mode == "vmapped"
        for ep, trial in zip(eps, res.trials):
            folds = mod.engine_factory().eval(Context(mode="Evaluation"), ep)
            serial = metric.calculate(
                Context(), [(f.eval_info, f.qpa) for f in folds])
            assert trial.score == serial

    def test_serial_fallback_still_ranks(self, mesh8):
        """No als_config hook (sample engine) -> serial path, same
        leaderboard semantics."""

        class ValueMetric(AverageMetric):
            def calculate_qpa(self, q, p, a):
                return float(p.value)

        grid = [
            EngineParams(
                data_source_params=("",
                                    SampleDataSourceParams(id=1, n_folds=2)),
                algorithm_params_list=(
                    ("sample", SampleAlgoParams(id=1, multiplier=m)),),
            )
            for m in (1, 5, 3)
        ]
        sup = TuneSupervisor(make_sample_engine(), ValueMetric())
        res = sup.run(Context(), grid)
        assert res.grid_mode == "serial"
        assert [t.status for t in res.trials] == ["COMPLETED"] * 3
        assert res.best_idx == 1  # multiplier=5 maximizes mean value

    def test_no_eval_folds_fails_trials(self):
        """n_folds=0 -> every trial FAILED with an actionable error and
        no winner (run_tune would raise RuntimeError)."""

        class ValueMetric(AverageMetric):
            def calculate_qpa(self, q, p, a):
                return float(p.value)

        grid = [EngineParams(
            data_source_params=("", SampleDataSourceParams(id=1, n_folds=0)),
            algorithm_params_list=(
                ("sample", SampleAlgoParams(id=1)),),
        )]
        res = TuneSupervisor(make_sample_engine(), ValueMetric()).run(
            Context(), grid)
        assert res.trials[0].status == "FAILED"
        assert "eval_k" in res.trials[0].error
        assert res.best_idx == -1 and res.winner is None
        with pytest.raises(ValueError, match="no completed trials"):
            res.to_metric_result()

    def test_empty_grid_raises(self):
        from predictionio_tpu.controller.metric import ZeroMetric

        sup = TuneSupervisor(make_sample_engine(), ZeroMetric())
        with pytest.raises(ValueError, match="empty EngineParams grid"):
            sup.run(Context(), [])


# ---------------------------------------------------------------------------
# chaos: one trial's failure never kills the sweep
# ---------------------------------------------------------------------------

@pytest.mark.chaos
def test_chaos_trial_failure_isolated_and_winner_promotes(mesh8, rng):
    """Arm tune.trial with times=1: trial 0's scoring body faults and
    becomes a FAILED leaderboard row; trials 1..3 complete; run_tune
    still trains the winner, stamps the leaderboard (FAILED row included)
    onto the instance, and the gate promotes."""
    mod = _seed_recommendation(rng)
    eps = _grid(mod)
    FAULTS.inject("tune.trial", "error", times=1)

    iid, tune, gate = run_tune(
        mod.engine_factory(), eps, mod.HitRateAtK(5),
        evaluator_class="engine:RecommendationEvaluation",
        eval_gate=0.5)

    assert FAULTS.fired("tune.trial") == 1
    assert tune.trials[0].status == "FAILED"
    assert "FaultInjected" in tune.trials[0].error
    assert [t.status for t in tune.trials[1:]] == ["COMPLETED"] * 3
    assert tune.best_idx in (1, 2, 3)
    assert METRICS.get("pio_tune_trials_total").value("FAILED") == 1
    assert METRICS.get("pio_tune_trials_total").value("COMPLETED") == 3

    # the winner trained for real and carries the full leaderboard
    meta = Storage.get_metadata()
    inst = meta.engine_instance_get(iid)
    assert inst.status == "COMPLETED"
    doc = json.loads(inst.tuning)
    assert doc["bestTrial"] == tune.best_idx
    rows = {r["trial"]: r for r in doc["trials"]}
    assert rows[0]["status"] == "FAILED" and rows[0]["error"]
    assert inst.evaluator_results  # satellite: one-liner for pio status
    assert json.loads(inst.evaluator_results_json)["bestScore"]
    # no incumbent existed -> promote even with a gate armed
    assert gate["decision"] == "promote"
    assert gate["baseline"] is None
    assert gate["candidate"] == tune.winner.score
    # models persisted -> instance is deployable
    assert Storage.get_models().get(iid) is not None


@pytest.mark.chaos
def test_chaos_retry_recovers_trial(mesh8, rng):
    """FaultInjected classifies transient: with max_retries=1 the faulted
    trial retries and COMPLETES — the leaderboard shows attempts=2."""
    mod = _seed_recommendation(rng)
    eps = _grid(mod, ranks=(3,), lams=(0.01, 0.1))
    FAULTS.inject("tune.trial", "error", times=1)
    sup = TuneSupervisor(mod.engine_factory(), mod.HitRateAtK(5),
                         max_retries=1, retry_backoff_s=0.01)
    res = sup.run(Context(mode="Evaluation"), eps)
    assert [t.status for t in res.trials] == ["COMPLETED"] * 2
    assert res.trials[0].attempts == 2
    assert res.trials[1].attempts == 1


# ---------------------------------------------------------------------------
# eval-gated promotion
# ---------------------------------------------------------------------------

def _tune_result(score, lower=False):
    t = TrialResult(index=0, params=EngineParams(), status="COMPLETED",
                    score=score)
    return TuneResult(trials=[t], best_idx=0, metric_header="m",
                      other_metric_headers=(), lower_is_better=lower,
                      grid_mode="serial")


def test_tune_gate_decision_semantics():
    # ungated: always deploy
    assert tune_gate_decision(_tune_result(0.1), 0.9, None)["decision"] \
        == "ungated"
    # no incumbent -> promote
    assert tune_gate_decision(_tune_result(0.1), None, 0.0)["decision"] \
        == "promote"
    # higher-is-better: promote iff candidate >= baseline - gate
    assert tune_gate_decision(_tune_result(0.55), 0.6, 0.05)["decision"] \
        == "promote"
    assert tune_gate_decision(_tune_result(0.54), 0.6, 0.05)["decision"] \
        == "hold"
    # lower-is-better flips the inequality
    assert tune_gate_decision(
        _tune_result(0.64, lower=True), 0.6, 0.05)["decision"] == "promote"
    assert tune_gate_decision(
        _tune_result(0.66, lower=True), 0.6, 0.05)["decision"] == "hold"
    # no winner -> hold (never deploy an untrained candidate past a gate)
    none_result = _tune_result(0.5)
    none_result.best_idx = -1
    assert tune_gate_decision(none_result, 0.6, 0.05)["decision"] == "hold"


def test_gate_uses_prior_instance_baseline(mesh8, rng):
    """Second run_tune gates against the FIRST run's stamped score: a
    candidate that cannot beat an inflated baseline holds."""
    mod = _seed_recommendation(rng)
    eps = _grid(mod, ranks=(3,), lams=(0.01, 0.1))
    metric = mod.HitRateAtK(5)
    iid1, tune1, gate1 = run_tune(mod.engine_factory(), eps, metric)
    assert gate1["decision"] == "ungated"

    # inflate the incumbent's stamped score past any achievable hit rate
    import dataclasses as dc

    meta = Storage.get_metadata()
    inst = meta.engine_instance_get(iid1)
    doc = json.loads(inst.evaluator_results_json)
    doc["bestScore"][0] = 2.0
    meta.engine_instance_update(
        dc.replace(inst, evaluator_results_json=json.dumps(doc)))

    _iid2, tune2, gate2 = run_tune(mod.engine_factory(), eps, metric,
                                   eval_gate=0.25)
    assert gate2["baseline"] == 2.0
    assert gate2["decision"] == "hold"  # hit rate <= 1 < 2.0 - 0.25


# ---------------------------------------------------------------------------
# satellite: run_evaluation stamps results onto an EngineInstance
# ---------------------------------------------------------------------------

def test_run_evaluation_stamps_engine_instance():
    from predictionio_tpu.controller import Evaluation
    from predictionio_tpu.workflow import run_evaluation, run_train

    class ValueMetric(AverageMetric):
        def calculate_qpa(self, q, p, a):
            return float(p.value)

    engine = make_sample_engine()
    iid = run_train(engine, EngineParams(
        data_source_params=("", SampleDataSourceParams(id=1)),
        algorithm_params_list=(("sample", SampleAlgoParams(id=1)),)))

    class Eval(Evaluation):
        pass

    Eval.engine = engine
    Eval.metric = ValueMetric()
    grid = [EngineParams(
        data_source_params=("", SampleDataSourceParams(id=1, n_folds=2)),
        algorithm_params_list=(
            ("sample", SampleAlgoParams(id=1, multiplier=m)),),
    ) for m in (1, 2)]

    meta = Storage.get_metadata()
    assert meta.engine_instance_get(iid).evaluator_results == ""
    _eid, result = run_evaluation(Eval(), grid, engine_instance_id=iid)
    inst = meta.engine_instance_get(iid)
    assert inst.evaluator_results == result.to_one_liner()
    assert json.loads(inst.evaluator_results_json)["bestScore"]
    assert inst.tuning == ""  # eval-only stamp leaves the leaderboard alone
    assert inst.status == "COMPLETED"  # stamp never clobbers lifecycle

    # unknown instance: warn-and-skip, never abort the evaluation
    from predictionio_tpu.workflow import stamp_evaluator_results

    stamp_evaluator_results("nope", result)


# ---------------------------------------------------------------------------
# satellite: FastEvalEngine shares fold/prepare caches across algo-only
# differences and accepts grid-seeded models
# ---------------------------------------------------------------------------

def test_fast_eval_per_algo_cache_and_seeding():
    from predictionio_tpu.controller import FastEvalEngine

    base = make_sample_engine()
    eng = FastEvalEngine(
        data_source_classes=base.data_source_classes,
        preparator_classes=base.preparator_classes,
        algorithm_classes=base.algorithm_classes,
        serving_classes=base.serving_classes,
    )
    ds = SampleDataSourceParams(id=1, n_folds=2)

    def ep(*mults):
        return EngineParams(
            data_source_params=("", ds),
            algorithm_params_list=tuple(
                ("sample", SampleAlgoParams(id=1, multiplier=m))
                for m in mults))

    # two 2-algo variants overlapping in ONE algo config: the shared algo
    # trains once (per-pair cache), but neither variant is a whole-variant
    # hit, so the pinned coarse counter stays 0
    eng.eval(Context(), ep(1, 2))
    assert len(eng._algo_cache) == 2
    eng.eval(Context(), ep(2, 3))
    assert len(eng._algo_cache) == 3  # multiplier=2 reused, 3 trained
    assert eng.hit_counts["algorithms"] == 0
    assert eng.hit_counts["preparator"] == 1

    # full overlap IS a whole-variant hit
    eng.eval(Context(), ep(1, 2))
    assert eng.hit_counts["algorithms"] == 1

    # seed_models injects pre-trained models: a fresh params variant
    # evals without calling Algorithm.train at all
    sentinel_ep = ep(9)

    class Boom(Exception):
        pass

    import predictionio_tpu.testing.sample_engine as se

    orig = se.SampleAlgorithm.train
    se.SampleAlgorithm.train = lambda *a, **k: (_ for _ in ()).throw(Boom())
    try:
        eng.seed_models(sentinel_ep, [
            [se.SampleModel(ds_id=1, prep_id=1, algo_id=9, multiplier=9)]
            for _fold in range(2)])
        folds = eng.eval(Context(), sentinel_ep)
    finally:
        se.SampleAlgorithm.train = orig
    assert len(folds) == 2
    assert folds[0].qpa[1][1].value == 9  # query q=1 x multiplier 9


# ---------------------------------------------------------------------------
# CLI + dashboard: `pio tune` end to end
# ---------------------------------------------------------------------------

def _tune_engine_dir(tmp_path, rng):
    """An engine dir + app + evaluation module for CLI tune runs —
    the test_quickstart_e2e idiom."""
    import shutil

    from tests.test_quickstart_e2e import REPO, make_events_file
    from predictionio_tpu.tools.cli import main as pio

    d = tmp_path / "myrec"
    shutil.copytree(REPO / "templates" / "recommendation", d)
    variant = json.loads((d / "engine.json").read_text())
    variant["datasource"]["params"]["app_name"] = "qtest"
    (d / "engine.json").write_text(json.dumps(variant))

    assert pio(["app", "new", "qtest"]) == 0
    app = Storage.get_metadata().app_get_by_name("qtest")
    events_file = tmp_path / "events.jsonl"
    make_events_file(events_file, rng, nu=16, ni=12)
    assert pio(["import", "--appid", str(app.id),
                "--input", str(events_file)]) == 0

    (d / "evaluation.py").write_text('''
from predictionio_tpu.controller import (AverageMetric, EngineParams,
                                         Evaluation)
from engine import DataSourceParams, AlgorithmParams, engine_factory

class Hit(AverageMetric):
    def calculate_qpa(self, q, p, a):
        return 1.0 if any(s.item == a["item"] for s in p.itemScores) else 0.0

class TuneEval(Evaluation):
    engine = engine_factory()
    metric = Hit()
    engine_params_list = [
        EngineParams(
            data_source_params=("", DataSourceParams(app_name="qtest",
                                                     eval_k=2,
                                                     eval_top_k=5)),
            algorithm_params_list=(
                ("als", AlgorithmParams(rank=r, num_iterations=2,
                                        lambda_=lam)),),
        )
        for r in (3, 4)
        for lam in (0.01, 0.1)
    ]
''')
    return d


def test_pio_tune_cli_end_to_end(mesh8, rng, tmp_path, capsys):
    """`pio tune` -> leaderboard on stdout, best.json written, winner
    instance stamped; `pio status` prints the leaderboard; the dashboard
    serves it at /tune.json."""
    import requests

    from predictionio_tpu.tools.cli import main as pio
    from predictionio_tpu.tools.dashboard import create_dashboard_app
    from tests.helpers import ServerThread

    d = _tune_engine_dir(tmp_path, rng)
    assert pio(["tune", "--engine-dir", str(d),
                "evaluation:TuneEval"]) == 0
    out = capsys.readouterr().out
    assert "Tuning leaderboard" in out and "WINNER" in out
    assert "vmapped" in out
    assert "gate: ungated" in out
    assert (d / "best.json").exists()

    # the winner's instance carries the leaderboard under engine.json's
    # ids (so `pio deploy --engine-dir` finds it)
    meta = Storage.get_metadata()
    inst = meta.engine_instance_get_latest_completed(
        "default", "1", "default")
    assert inst is not None and inst.tuning

    # pio status surfaces it
    assert pio(["status"]) == 0
    out = capsys.readouterr().out
    assert "tuning: 4 trial(s), vmapped grid" in out
    assert "<== winner" in out
    assert "eval: " in out

    # dashboard /tune.json serves the same document from metadata
    st = ServerThread(lambda: create_dashboard_app())
    try:
        r = requests.get(st.url + "/tune.json")
        assert r.status_code == 200
        doc = r.json()
        assert doc["engineInstanceId"] == inst.id
        assert doc["tuning"]["gridMode"] == "vmapped"
        assert len(doc["tuning"]["trials"]) == 4
        # pinned instance + 404 contract
        r = requests.get(st.url + "/tune.json",
                         params={"instance": inst.id})
        assert r.status_code == 200
        r = requests.get(st.url + "/tune.json",
                         params={"instance": "nope"})
        assert r.status_code == 404
    finally:
        st.stop()


def test_pio_tune_deploy_gate_hold_exits_2(mesh8, rng, tmp_path, capsys):
    """`pio tune --deploy --eval-gate` with an unbeatable incumbent:
    tuning completes, the winner trains, but the gate HOLDS and the CLI
    exits 2 without binding a server."""
    import dataclasses as dc

    from predictionio_tpu.tools.cli import main as pio

    d = _tune_engine_dir(tmp_path, rng)
    assert pio(["tune", "--engine-dir", str(d),
                "evaluation:TuneEval"]) == 0
    capsys.readouterr()

    # inflate the incumbent's stamped score past any achievable hit rate
    meta = Storage.get_metadata()
    inst = meta.engine_instance_get_latest_completed(
        "default", "1", "default")
    doc = json.loads(inst.evaluator_results_json)
    doc["bestScore"][0] = 2.0
    meta.engine_instance_update(
        dc.replace(inst, evaluator_results_json=json.dumps(doc)))

    rc = pio(["tune", "--engine-dir", str(d), "evaluation:TuneEval",
              "--deploy", "--eval-gate", "0.25"])
    assert rc == 2
    out = capsys.readouterr().out
    assert "gate: hold" in out
    assert "HELD deployment" in out
