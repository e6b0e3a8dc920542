"""Template engines — trained against the in-memory event store,
predictions verified including the serving-time business filters (the
reference's judge-checked workloads, SURVEY §2.8)."""

import dataclasses
import importlib.util
import sys
from pathlib import Path

import numpy as np
import pytest

from predictionio_tpu.controller import EngineParams
from predictionio_tpu.storage import DataMap, Event, Storage
from predictionio_tpu.workflow import Context

REPO = Path(__file__).resolve().parents[1]


def load_template(name):
    spec = importlib.util.spec_from_file_location(
        f"tmpl_{name}", REPO / "templates" / name / "engine.py"
    )
    mod = importlib.util.module_from_spec(spec)
    sys.modules[f"tmpl_{name}"] = mod
    spec.loader.exec_module(mod)
    return mod


def setup_app(name="MyApp"):
    meta = Storage.get_metadata()
    app = meta.app_insert(name)
    Storage.get_events().init_app(app.id)
    return app


def insert(app_id, **kw):
    props = kw.pop("props", None)
    e = Event(properties=DataMap(props or {}), **kw)
    Storage.get_events().insert(e, app_id)


class TestClassification:
    def test_train_and_predict(self, rng, mesh8):
        mod = load_template("classification")
        app = setup_app()
        # two separable classes via attr profile
        for i in range(60):
            label = i % 2
            attrs = {
                "attr0": float(rng.poisson(5 if label else 1)),
                "attr1": float(rng.poisson(1 if label else 5)),
                "attr2": float(rng.poisson(2)),
                "plan": float(label),
            }
            insert(app.id, event="$set", entity_type="user",
                   entity_id=f"u{i}", props=attrs)
        engine = mod.engine_factory()
        ep = EngineParams(
            data_source_params=("", mod.DataSourceParams(app_name="MyApp")),
            algorithm_params_list=(
                ("naive", mod.NaiveBayesParams()),
                ("logreg", mod.LogRegParams(steps=150)),
                ("randomforest", mod.RandomForestParams(num_trees=5)),
            ),
        )
        result = engine.train(Context(), ep)
        assert len(result.models) == 3
        q1 = mod.Query(features=(6.0, 0.0, 2.0))  # class-1 profile
        q0 = mod.Query(features=(0.0, 6.0, 2.0))  # class-0 profile
        for algo, model in zip(result.algorithms, result.models):
            assert algo.predict(model, q1).label == 1.0, type(algo).__name__
            assert algo.predict(model, q0).label == 0.0, type(algo).__name__


class TestSimilarProduct:
    def _ingest(self, rng, app):
        # items with categories
        for i in range(12):
            insert(app.id, event="$set", entity_type="item", entity_id=f"i{i}",
                   props={"categories": ["even" if i % 2 == 0 else "odd"]})
        # two cohorts: users view even items or odd items
        for u in range(30):
            parity = u % 2
            for i in range(12):
                if i % 2 == parity and rng.random() < 0.8:
                    insert(app.id, event="view", entity_type="user",
                           entity_id=f"u{u}", target_entity_type="item",
                           target_entity_id=f"i{i}")
        # likes reinforce the same structure
        for u in range(0, 30, 3):
            parity = u % 2
            insert(app.id, event="like", entity_type="user", entity_id=f"u{u}",
                   target_entity_type="item", target_entity_id=f"i{parity}")

    def test_similar_items_with_filters(self, rng, mesh8):
        mod = load_template("similarproduct")
        app = setup_app()
        self._ingest(rng, app)
        engine = mod.engine_factory()
        ep = EngineParams(
            data_source_params=("", mod.DataSourceParams(app_name="MyApp")),
            algorithm_params_list=(
                ("als", mod.AlgorithmParams(rank=4, num_iterations=8, alpha=10.0)),
                ("likealgo", mod.AlgorithmParams(rank=4, num_iterations=8, alpha=10.0)),
            ),
        )
        result = engine.train(Context(), ep)
        assert len(result.models) == 2

        def serve(q):
            preds = [a.predict(m, q) for a, m in zip(result.algorithms, result.models)]
            return result.serving.serve(q, preds)

        out = serve(mod.Query(items=("i0",), num=4))
        assert 1 <= len(out.itemScores) <= 4
        assert "i0" not in [s.item for s in out.itemScores]
        # co-viewed parity should dominate similarity
        evens = [s for s in out.itemScores if int(s.item[1:]) % 2 == 0]
        assert len(evens) >= len(out.itemScores) / 2

        # category filter
        out = serve(mod.Query(items=("i0",), num=6, categories=("odd",)))
        assert all(int(s.item[1:]) % 2 == 1 for s in out.itemScores)
        # black list
        out = serve(mod.Query(items=("i0",), num=6, blackList=("i2", "i4")))
        assert not {"i2", "i4"} & {s.item for s in out.itemScores}
        # white list
        out = serve(mod.Query(items=("i0",), num=6, whiteList=("i6",)))
        assert [s.item for s in out.itemScores] == ["i6"]
        # unknown query item -> empty
        out = serve(mod.Query(items=("nope",), num=3))
        assert out.itemScores == ()


class TestECommerce:
    def _ingest(self, rng, app):
        for i in range(10):
            insert(app.id, event="$set", entity_type="item", entity_id=f"i{i}",
                   props={"categories": ["c1"]})
        for u in range(20):
            for i in range(10):
                if (u + i) % 3 == 0:
                    insert(app.id, event="view", entity_type="user",
                           entity_id=f"u{u}", target_entity_type="item",
                           target_entity_id=f"i{i}")
                if (u + i) % 5 == 0:
                    insert(app.id, event="buy", entity_type="user",
                           entity_id=f"u{u}", target_entity_type="item",
                           target_entity_id=f"i{i}")

    def test_realtime_filters(self, rng, mesh8):
        mod = load_template("ecommercerecommendation")
        app = setup_app()
        self._ingest(rng, app)
        engine = mod.engine_factory()
        ep = EngineParams(
            data_source_params=("", mod.DataSourceParams(app_name="MyApp")),
            algorithm_params_list=(
                ("ecomm", mod.AlgorithmParams(app_name="MyApp", rank=4,
                                              num_iterations=6, unseen_only=True)),
            ),
        )
        result = engine.train(Context(), ep)
        algo, model = result.algorithms[0], result.models[0]
        # immediate constraint visibility for this test (the TTL cache's
        # staleness bound is pinned separately below)
        algo.params = dataclasses.replace(algo.params,
                                          constraint_ttl_seconds=0.0)

        # unseen-only: u0's seen items (views+buys) are excluded
        out = algo.predict(model, mod.Query(user="u0", num=10))
        seen_u0 = {f"i{i}" for i in range(10) if i % 3 == 0 or i % 5 == 0}
        assert not seen_u0 & {s.item for s in out.itemScores}
        assert out.itemScores  # still recommends something

        # $set constraint/unavailableItems takes effect WITHOUT retraining
        insert(app.id, event="$set", entity_type="constraint",
               entity_id="unavailableItems", props={"items": ["i1", "i7"]})
        out = algo.predict(model, mod.Query(user="u0", num=10))
        assert not {"i1", "i7"} & {s.item for s in out.itemScores}

        # unseen user with recent views -> profile fallback
        insert(app.id, event="view", entity_type="user", entity_id="brandnew",
               target_entity_type="item", target_entity_id="i2")
        out = algo.predict(model, mod.Query(user="brandnew", num=3))
        assert out.itemScores
        # totally unknown user -> empty
        out = algo.predict(model, mod.Query(user="ghost", num=3))
        assert out.itemScores == ()

    def test_constraint_ttl_and_batch_dedupe(self, rng, mesh8, monkeypatch):
        """Serving-plane store traffic: the global
        unavailable-items read is TTL-cached (staleness bounded by
        constraint_ttl_seconds) and a micro-batch dedupes seen-items
        lookups per user."""
        mod = load_template("ecommercerecommendation")
        app = setup_app()
        self._ingest(rng, app)
        engine = mod.engine_factory()
        ep = EngineParams(
            data_source_params=("", mod.DataSourceParams(app_name="MyApp")),
            algorithm_params_list=(
                ("ecomm", mod.AlgorithmParams(
                    app_name="MyApp", rank=4, num_iterations=4,
                    unseen_only=True, constraint_ttl_seconds=30.0)),
            ),
        )
        result = engine.train(Context(), ep)
        algo, model = result.algorithms[0], result.models[0]

        reads = {"constraint": 0, "seen": 0}
        real_read = algo._read_unavailable_items
        real_seen = algo._seen_items

        def counting_read():
            reads["constraint"] += 1
            return real_read()

        def counting_seen(user):
            reads["seen"] += 1
            return real_seen(user)

        monkeypatch.setattr(algo, "_read_unavailable_items", counting_read)
        monkeypatch.setattr(algo, "_seen_items", counting_seen)

        # one micro-batch: 6 queries over 2 users -> 1 constraint read,
        # 2 seen-items reads
        queries = [(i, mod.Query(user=f"u{i % 2}", num=3))
                   for i in range(6)]
        out = dict(algo.batch_predict(model, queries))
        assert len(out) == 6 and all(out[i].itemScores for i in range(6))
        assert reads["constraint"] == 1
        assert reads["seen"] == 2

        # within the TTL, the next batch re-reads nothing global; a $set
        # lands only after the TTL expires (staleness bound)
        insert(app.id, event="$set", entity_type="constraint",
               entity_id="unavailableItems", props={"items": ["i2"]})
        out = dict(algo.batch_predict(
            model, [(0, mod.Query(user="u0", num=10))]))
        assert reads["constraint"] == 1  # cache hit — possibly stale
        # force expiry instead of sleeping
        algo._constraint_cache = (0.0, algo._constraint_cache[1])
        out = dict(algo.batch_predict(
            model, [(0, mod.Query(user="u0", num=10))]))
        assert reads["constraint"] == 2
        assert "i2" not in {s.item for s in out[0].itemScores}


class TestSeqRec:
    def test_next_item_prediction(self, mesh8):
        mod = load_template("seqrec")
        app = setup_app()
        # cyclic histories shorter than the catalog: user u views 4 of 6
        # items, so the cycle's next item is always unseen
        n_items = 6
        for u in range(48):
            for t in range(4):
                insert(app.id, event="view", entity_type="user",
                       entity_id=f"u{u}", target_entity_type="item",
                       target_entity_id=f"i{(u + t) % n_items}")
        engine = mod.engine_factory()
        ep = EngineParams(
            data_source_params=("", mod.DataSourceParams(app_name="MyApp")),
            algorithm_params_list=(
                ("seqrec", mod.AlgorithmParams(
                    max_len=4, embed_dim=32, num_heads=2, num_blocks=1,
                    epochs=40, batch_size=48, lr=3e-3)),
            ),
        )
        result = engine.train(Context(), ep)
        algo, model = result.algorithms[0], result.models[0]
        # u0 viewed i0..i3; the learned cycle continues with i4
        out = algo.predict(model, mod.Query(user="u0", num=2))
        assert out.itemScores
        assert out.itemScores[0].item == "i4"

        # batched serving: one forward for the whole micro-batch, same
        # answers as per-query predict, unknown users empty
        queries = [(0, mod.Query(user="u0", num=2)),
                   (1, mod.Query(user="u5", num=3)),
                   (2, mod.Query(user="nosuch", num=2))]
        got = dict(algo.batch_predict(model, queries))
        assert [s.item for s in got[0].itemScores] == \
            [s.item for s in out.itemScores]
        single_u5 = algo.predict(model, mod.Query(user="u5", num=3))
        assert [s.item for s in got[1].itemScores] == \
            [s.item for s in single_u5.itemScores]
        np.testing.assert_allclose(
            [s.score for s in got[1].itemScores],
            [s.score for s in single_u5.itemScores], rtol=1e-5, atol=1e-6)
        assert got[2].itemScores == ()
        # one device step a micro-batch, through the shared route
        seq = model._pipeline.stats()["sequence"]
        assert seq["rows"] == 1 + 2 + 1 and seq["steps"] == 3
        assert model.serving_cost("u0") == 4 == model.config.max_len
        assert model.serving_cost_budget == 4 * 128

    def test_looped_algorithm_takes_the_published_keys(self, mesh8):
        """`looped` beside `seqrec` in the one template: its params are
        the published config's keys, and it learns the same cycle."""
        mod = load_template("seqrec")
        app = setup_app()
        n_items = 6
        for u in range(48):
            for t in range(4):
                insert(app.id, event="view", entity_type="user",
                       entity_id=f"u{u}", target_entity_type="item",
                       target_entity_id=f"i{(u + t) % n_items}")
        engine = mod.engine_factory()
        ep = EngineParams(
            data_source_params=("", mod.DataSourceParams(app_name="MyApp")),
            algorithm_params_list=(
                ("looped", mod.LoopedParams(
                    hidden_size=32, intermediate_size=48,
                    num_hidden_layers=2, num_attention_heads=2, head_dim=16,
                    total_ut_steps=2, max_len=4, compute_dtype="float32",
                    epochs=60, batch_size=48, lr=3e-3)),
            ),
        )
        result = engine.train(Context(), ep)
        algo, model = result.algorithms[0], result.models[0]
        assert model.params["layers"]["wq"].shape == (2, 32, 32)
        out = algo.predict(model, mod.Query(user="u0", num=2))
        assert out.itemScores[0].item == "i4"
        got = dict(algo.batch_predict(model, [
            (0, mod.Query(user="u0", num=2)),
            (1, mod.Query(user="nosuch", num=2))]))
        assert [s.item for s in got[0].itemScores] == [
            s.item for s in out.itemScores]
        assert got[1].itemScores == ()


class TestRegression:
    def test_train_and_predict(self, rng, mesh8):
        mod = load_template("regression")
        app = setup_app()
        # y = 2*x0 - 3*x1 + 1 + noise
        w = np.array([2.0, -3.0])
        for i in range(80):
            x = rng.normal(size=2)
            y = float(x @ w + 1.0 + rng.normal(scale=0.01))
            insert(app.id, event="$set", entity_type="point",
                   entity_id=f"p{i}",
                   props={"x0": float(x[0]), "x1": float(x[1]), "y": y})
        engine = mod.engine_factory()
        ep = EngineParams(
            data_source_params=("", mod.DataSourceParams(app_name="MyApp")),
            algorithm_params_list=(("ridge", mod.RidgeParams()),),
        )
        result = engine.train(Context(), ep)
        algo, model = result.algorithms[0], result.models[0]
        pred = algo.predict(model, mod.Query(features=(1.0, 1.0))).prediction
        assert abs(pred - (2.0 - 3.0 + 1.0)) < 0.1
        assert np.allclose(model.weights, w, atol=0.05)

    def test_eval_folds(self, rng, mesh8):
        mod = load_template("regression")
        app = setup_app()
        for i in range(30):
            x = rng.normal(size=2)
            insert(app.id, event="$set", entity_type="point",
                   entity_id=f"p{i}",
                   props={"x0": float(x[0]), "x1": float(x[1]),
                          "y": float(x.sum())})
        ds = mod.RegressionDataSource(mod.DataSourceParams(app_name="MyApp", eval_k=3))
        folds = ds.read_eval(Context())
        assert len(folds) == 3
        td, _ei, qa = folds[0]
        assert len(td.y) + len(qa) == 30


class TestFriendRecommendation:
    def test_similarity_and_acceptance(self, mesh8):
        mod = load_template("friendrecommendation")
        app = setup_app()
        insert(app.id, event="$set", entity_type="user", entity_id="u1",
               props={"keywords": {"music": 0.9, "sports": 0.1}})
        insert(app.id, event="$set", entity_type="user", entity_id="u2",
               props={"keywords": {"cooking": 1.0}})
        insert(app.id, event="$set", entity_type="item", entity_id="i1",
               props={"keywords": {"music": 0.8}})
        insert(app.id, event="$set", entity_type="item", entity_id="i2",
               props={"keywords": {"sports": 0.5, "cooking": 0.5}})
        # invites teach the acceptance threshold
        insert(app.id, event="invite", entity_type="user", entity_id="u1",
               target_entity_type="item", target_entity_id="i1",
               props={"accepted": True})
        insert(app.id, event="invite", entity_type="user", entity_id="u2",
               target_entity_type="item", target_entity_id="i1",
               props={"accepted": False})
        engine = mod.engine_factory()
        ep = EngineParams(
            data_source_params=("", mod.DataSourceParams(app_name="MyApp")),
            algorithm_params_list=(("keywordsim", mod.KeywordSimParams()),),
        )
        result = engine.train(Context(), ep)
        algo, model = result.algorithms[0], result.models[0]
        strong = algo.predict(model, mod.Query(user="u1", item="i1"))
        weak = algo.predict(model, mod.Query(user="u2", item="i1"))
        assert strong.confidence == pytest.approx(0.9 * 0.8)
        assert weak.confidence == 0.0
        assert strong.confidence > weak.confidence
        # unseen entities -> zero-confidence fallback, not an error
        unseen = algo.predict(model, mod.Query(user="nobody", item="i1"))
        assert unseen.confidence == 0.0 and not unseen.acceptance


class TestMarkovChain:
    def test_next_item(self, mesh8):
        from datetime import datetime, timedelta, timezone

        mod = load_template("markovchain")
        app = setup_app()
        t0 = datetime(2026, 1, 1, tzinfo=timezone.utc)
        # deterministic cycle i0 -> i1 -> i2 (3 users repeat it)
        for u in range(3):
            for t in range(6):
                insert(app.id, event="view", entity_type="user",
                       entity_id=f"u{u}", target_entity_type="item",
                       target_entity_id=f"i{t % 3}",
                       event_time=t0 + timedelta(minutes=t))
        engine = mod.engine_factory()
        ep = EngineParams(
            data_source_params=("", mod.DataSourceParams(app_name="MyApp")),
            algorithm_params_list=(("markov", mod.MarkovParams(top_n=2)),),
        )
        result = engine.train(Context(), ep)
        algo, model = result.algorithms[0], result.models[0]
        out = algo.predict(model, mod.Query(item="i0", num=2))
        assert out.itemScores[0].item == "i1"
        assert out.itemScores[0].score == pytest.approx(1.0)
        # unseen item -> empty result
        assert algo.predict(model, mod.Query(item="zzz")).itemScores == ()


class TestStock:
    def _ingest_prices(self, app, t_days=80):
        from datetime import datetime, timedelta, timezone

        t0 = datetime(2026, 1, 1, tzinfo=timezone.utc)
        rng = np.random.default_rng(7)
        # UP trends steadily; DOWN decays; FLAT is noise
        paths = {
            "UP": 100 * np.exp(np.cumsum(0.01 + 0.001 * rng.standard_normal(t_days))),
            "DOWN": 100 * np.exp(np.cumsum(-0.01 + 0.001 * rng.standard_normal(t_days))),
            "FLAT": 100 * np.exp(np.cumsum(0.0005 * rng.standard_normal(t_days))),
        }
        for t in range(t_days):
            for tick, path in paths.items():
                insert(app.id, event="price", entity_type="ticker",
                       entity_id=tick, props={"close": float(path[t])},
                       event_time=t0 + timedelta(days=t))

    def test_strategy_ranks_momentum(self, mesh8):
        mod = load_template("stock")
        app = setup_app()
        self._ingest_prices(app)
        engine = mod.engine_factory()
        ep = EngineParams(
            data_source_params=("", mod.DataSourceParams(app_name="MyApp")),
            algorithm_params_list=(("regression", mod.StrategyParams()),),
        )
        result = engine.train(Context(), ep)
        algo, model = result.algorithms[0], result.models[0]
        out = algo.predict(model, mod.Query(dateIdx=-1, num=3))
        assert out.tickerScores[0].ticker == "UP"
        assert out.tickerScores[-1].ticker == "DOWN"
        assert out.tickerScores[0].score > out.tickerScores[-1].score

    def test_backtest_profits_on_trend(self, mesh8):
        mod = load_template("stock")
        app = setup_app()
        self._ingest_prices(app)
        engine = mod.engine_factory()
        ep = EngineParams(
            data_source_params=(
                "", mod.DataSourceParams(app_name="MyApp", eval_start=40)),
            algorithm_params_list=(("regression", mod.StrategyParams()),),
        )
        folds = engine.eval(Context(), ep)
        assert len(folds) == 1
        evaluator = mod.BacktestingEvaluator(mod.BacktestingParams(
            enter_threshold=0.002, exit_threshold=-0.002, max_positions=1))
        res = evaluator.evaluate(folds)
        assert res.days > 0
        # riding the UP trend must beat cash
        assert res.ret > 0
        assert "sharpe=" in res.to_one_liner()


class TestHelloWorld:
    def test_average_per_day(self, mesh8):
        mod = load_template("helloworld")
        app = setup_app()
        for day, temp in [("Mon", 70.0), ("Mon", 80.0), ("Tue", 60.0)]:
            insert(app.id, event="read", entity_type="sensor", entity_id="s1",
                   props={"day": day, "temperature": temp})
        engine = mod.engine_factory()
        ep = EngineParams(
            data_source_params=("", mod.DataSourceParams(app_name="MyApp")),
            algorithm_params_list=(("average", None),),
        )
        result = engine.train(Context(), ep)
        algo, model = result.algorithms[0], result.models[0]
        assert algo.predict(model, mod.Query(day="Mon")).temperature == 75.0
        assert algo.predict(model, mod.Query(day="Tue")).temperature == 60.0
        assert algo.predict(model, mod.Query(day="Sun")).temperature == 0.0


class TestCustomDataSource:
    def test_trains_from_file_without_event_store(self, mesh8):
        """The custom-datasource tutorial: DataSource reads the shipped
        ratings file; nothing touches the event store (the tutorial's
        point — only the D of DASE changed)."""
        mod = load_template("customdatasource")
        engine = mod.engine_factory()
        ep = EngineParams(
            data_source_params=("", mod.DataSourceParams()),
            algorithm_params_list=(
                ("als", mod.AlgorithmParams(rank=6, num_iterations=6)),),
        )
        result = engine.train(Context(), ep)
        algo, model = result.algorithms[0], result.models[0]
        out = algo.predict(model, mod.Query(user="u3", num=4))
        assert len(out.itemScores) == 4
        scores = [s.score for s in out.itemScores]
        assert scores == sorted(scores, reverse=True)
        # unknown user -> empty, not an error
        assert algo.predict(model, mod.Query(user="nope", num=4)).itemScores == ()

    def test_custom_separator(self, tmp_path, mesh8):
        mod = load_template("customdatasource")
        f = tmp_path / "r.tsv"
        f.write_text("a\tX\t5.0\na\tY\t1.0\nb\tX\t4.5\n")
        ds = mod.FileDataSource(mod.DataSourceParams(
            filepath=str(f), separator="\t"))
        td = ds.read_training(Context())
        assert len(td.ratings) == 3
        assert set(td.ratings.user_ids.keys()) == {"a", "b"}


class TestMovieLensEvaluation:
    def _seed(self, rng, n_users=40, n_items=25):
        app = setup_app("mlapp")
        u = rng.normal(size=(n_users, 3)) + 1
        v = rng.normal(size=(n_items, 3)) + 1
        full = np.clip(u @ v.T, 0.5, 5.0)
        for i in range(n_users):
            for j in range(n_items):
                if rng.random() < 0.5:
                    insert(app.id, event="rate", entity_type="user",
                           entity_id=f"u{i}", target_entity_type="item",
                           target_entity_id=f"i{j}",
                           props={"rating": float(full[i, j])})
        return app

    def test_eval_grid_leaderboard_and_best_json(self, rng, mesh8, tmp_path):
        """The worked tuning loop: grid -> 3-metric leaderboard ->
        best.json (the scala-local-movielens-evaluation teaching flow)."""
        import json

        from predictionio_tpu.workflow import run_evaluation

        mod = load_template("movielensevaluation")
        self._seed(rng)
        ev = mod.MovieLensEvaluation(app_name="mlapp", eval_k=2)
        assert len(ev.engine_params_list) == 4  # 2 ranks x 2 lambdas
        best_json = tmp_path / "best.json"
        _iid, res = run_evaluation(ev, ev.engine_params_list, Context(),
                                   best_json_path=str(best_json))
        # leaderboard ranks by hit rate, carries both context metrics
        assert res.metric_header == "HitRate@10"
        assert "MRR(hits)" in res.other_metric_headers
        assert "MSE(hits)" in res.other_metric_headers
        best = json.loads(best_json.read_text())
        assert best["algorithmsParams"][0]["params"]["rank"] in (4, 8)
        scores = [ms.score for _ep, ms in res.engine_params_scores]
        assert max(scores) > 0.05  # the grid finds signal, not noise


class TestFilterByCategory:
    def _ingest(self, rng, app):
        # 14 rated items: even-indexed are "drama", odd are "comedy";
        # items 12,13 also carry a second category "classic"
        for i in range(14):
            cats = ["drama" if i % 2 == 0 else "comedy"]
            if i >= 12:
                cats.append("classic")
            insert(app.id, event="$set", entity_type="item",
                   entity_id=f"i{i}", props={"categories": cats})
        # an unrated item's categories must be ignored (no factors)
        insert(app.id, event="$set", entity_type="item", entity_id="i99",
               props={"categories": ["drama"]})
        # a RATED item with $set properties but NO categories field must
        # not crash training (DataMap.get raises on absent fields)
        insert(app.id, event="$set", entity_type="item", entity_id="i50",
               props={"title": "uncategorized"})
        for u in range(25):
            insert(app.id, event="rate", entity_type="user",
                   entity_id=f"u{u}", target_entity_type="item",
                   target_entity_id="i50",
                   props={"rating": float(rng.integers(1, 6))})
        for u in range(25):
            for i in range(14):
                if rng.random() < 0.6:
                    insert(app.id, event="rate", entity_type="user",
                           entity_id=f"u{u}", target_entity_type="item",
                           target_entity_id=f"i{i}",
                           props={"rating": float(rng.integers(1, 6))})

    def test_category_filter(self, rng, mesh8):
        mod = load_template("filterbycategory")
        app = setup_app()
        self._ingest(rng, app)
        engine = mod.engine_factory()
        ep = EngineParams(
            data_source_params=("", mod.DataSourceParams(app_name="MyApp")),
            algorithm_params_list=(
                ("als", mod.AlgorithmParams(rank=6, num_iterations=5)),),
        )
        result = engine.train(Context(), ep)
        algo, model = result.algorithms[0], result.models[0]

        # unfiltered == plain ALS top-N
        full = algo.predict(model, mod.Query(user="u3", num=5))
        assert len(full.itemScores) == 5

        # filtered: only drama items, ranked by the same scores
        drama = algo.predict(
            model, mod.Query(user="u3", num=5, categories=("drama",)))
        items = [s.item for s in drama.itemScores]
        assert items and all(int(i[1:]) % 2 == 0 for i in items)
        assert "i99" not in items  # unrated: no factors, never recommended
        # scores agree with the unfiltered ranking where they overlap
        full_scores = {s.item: s.score for s in full.itemScores}
        for s in drama.itemScores:
            if s.item in full_scores:
                np.testing.assert_allclose(s.score, full_scores[s.item],
                                           rtol=1e-5)
        # filtered results are the drama-subset of a big unfiltered top-N
        # (i50 is rated but uncategorized: in the unfiltered list, never
        # in any category filter)
        big = algo.predict(model, mod.Query(user="u3", num=15))
        want = [s.item for s in big.itemScores
                if int(s.item[1:]) < 14 and int(s.item[1:]) % 2 == 0][:5]
        assert items == want

        # multi-category union covers everything EXCEPT the uncategorized
        both = algo.predict(
            model, mod.Query(user="u3", num=15,
                             categories=("drama", "comedy")))
        assert [s.item for s in both.itemScores] == \
            [s.item for s in big.itemScores if s.item != "i50"]
        assert "i50" in [s.item for s in big.itemScores]
        none = algo.predict(
            model, mod.Query(user="u3", num=5, categories=("nope",)))
        assert none.itemScores == ()

        # batch path: mixed filtered/unfiltered, order preserved
        queries = [(0, mod.Query(user="u3", num=5)),
                   (1, mod.Query(user="u3", num=5, categories=("drama",))),
                   (2, mod.Query(user="nosuch", num=3))]
        got = dict(algo.batch_predict(model, queries))
        assert [s.item for s in got[0].itemScores] == \
            [s.item for s in full.itemScores]
        assert [s.item for s in got[1].itemScores] == items
        assert got[2].itemScores == ()


class TestSimilarProductBatch:
    def test_batch_matches_single(self, rng, mesh8):
        """batch_predict == per-query predict, with and without the
        device similarity retriever, filtered and unfiltered."""
        mod = load_template("similarproduct")
        app = setup_app()
        TestSimilarProduct._ingest(TestSimilarProduct(), rng, app)
        engine = mod.engine_factory()
        ep = EngineParams(
            data_source_params=("", mod.DataSourceParams(app_name="MyApp")),
            algorithm_params_list=(
                ("als", mod.AlgorithmParams(rank=4, num_iterations=8,
                                            alpha=10.0)),),
        )
        result = engine.train(Context(), ep)
        algo, model = result.algorithms[0], result.models[0]
        queries = [
            mod.Query(items=("i0",), num=4),
            mod.Query(items=("i1", "i3"), num=6),
            mod.Query(items=("i0",), num=6, categories=("odd",)),  # masked
            mod.Query(items=("i0",), num=6, blackList=("i2",)),    # masked
            mod.Query(items=("nope",), num=3),                     # empty
        ]

        def check():
            batched = dict(algo.batch_predict(
                model, list(enumerate(queries))))
            for i, q in enumerate(queries):
                single = algo.predict(model, q)
                assert [s.item for s in batched[i].itemScores] == \
                    [s.item for s in single.itemScores], (i, q)
                np.testing.assert_allclose(
                    [s.score for s in batched[i].itemScores],
                    [s.score for s in single.itemScores],
                    rtol=1e-4, atol=1e-5)

        check()                                  # host path (no retriever)
        model.attach_retriever(interpret=True)   # fused kernel path
        check()
