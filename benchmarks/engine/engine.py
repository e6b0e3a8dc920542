"""The benchmark's engine: the stock recommendation template with a
synthetic DataSource in front and the benchmark's clock around it.

PredictionIO's documented extension point for data that does not come
from the event store is a custom DataSource (templates/customdatasource).
This one draws a data set's shape from a seed (benchmarks/lib/draw.py)
straight into the `Ratings` frame; Preparator, ALS algorithm, model,
serving and query classes are the stock template's own, imported from
templates/recommendation/engine.py. `TimedALS` adds nothing to training:
it takes the benchmark's own clock readings around the stock `train` and
at every iteration the trainer reports, and writes them, with the
device's peak memory, to the file `PIO_BENCH_SIDE` names.

A `pio deploy` of this engine serves exactly as the template does; at
exit the process writes its device's peak memory to the same file.
"""

from __future__ import annotations

import atexit
import importlib.util
import json
import os
import sys
import time
from dataclasses import dataclass, replace
from pathlib import Path

import numpy as np

import predictionio_tpu
from predictionio_tpu.controller import DataSource, Engine, FirstServing, Params
from predictionio_tpu.storage.bimap import BiMap
from predictionio_tpu.storage.frame import Ratings


def _load(path: Path, name: str):
    if name in sys.modules:
        return sys.modules[name]
    spec = importlib.util.spec_from_file_location(name, path)
    mod = importlib.util.module_from_spec(spec)
    sys.modules[name] = mod
    spec.loader.exec_module(mod)
    return mod


_REPO = Path(predictionio_tpu.__file__).resolve().parents[1]
_tpl = _load(_REPO / "templates" / "recommendation" / "engine.py",
             "pio_bench_stock_recommendation")
_draw = _load(_REPO / "benchmarks" / "lib" / "draw.py", "pio_bench_draw")

_SIDE = os.environ.get("PIO_BENCH_SIDE")
#: benchmarks/tests/test_broken_path.py alone sets this, to see `correct`
#: come out false when the timed path is broken where answers are produced
_BREAK = os.environ.get("PIO_BENCH_BREAK")


def _side_update(**facts) -> None:
    """Merge facts into the side file (one JSON object)."""
    if not _SIDE:
        return
    path = Path(_SIDE)
    cur = json.loads(path.read_text()) if path.exists() else {}
    cur.update(facts)
    tmp = path.with_suffix(".tmp")
    tmp.write_text(json.dumps(cur))
    tmp.replace(path)


def _device_facts() -> dict:
    import jax

    devices = jax.local_devices()
    stats = [d.memory_stats() or {} for d in devices]
    return {
        "device": {"platform": devices[0].platform,
                   "kind": devices[0].device_kind,
                   "count": len(jax.devices())},
        "memory_peak_bytes": max(
            (s.get("peak_bytes_in_use", 0) for s in stats), default=0),
        "memory_limit_bytes": max(
            (s.get("bytes_limit", 0) for s in stats), default=0),
    }


@atexit.register
def _at_exit() -> None:
    # registered after jax's own exit hook (the CLI imports jax before any
    # engine), so it runs before the backend is torn down
    if _SIDE and "jax" in sys.modules:
        try:
            _side_update(**{"exit_" + k: v
                            for k, v in _device_facts().items()})
        except Exception as e:  # noqa: BLE001 - exiting; say so in the file
            _side_update(exit_error=repr(e))


@dataclass(frozen=True)
class SyntheticParams(Params):
    n_users: int = 1000
    n_items: int = 500
    n_ratings: int = 20000
    user_sigma: float = 1.2
    item_exponent: float = 0.9
    item_top_share: float = 0.01
    rating_max: int = 100
    data_seed: int = 0
    #: seed of the data set's shape, the same for every run (draw.py)
    structure_seed: int = 0
    #: item rows whose ratings are set aside for the reference's check
    check_rows: int = 64
    #: 1 in a labelled rehearsal: the only way to train off the chip
    rehearse: int = 0


class SyntheticDataSource(DataSource):
    """Draws (user, item, rating) triples of a stated shape from a seed."""

    params_class = SyntheticParams

    def read_training(self, ctx):
        import jax

        p = self.params
        if jax.devices()[0].platform == "cpu" and not p.rehearse:
            raise RuntimeError(
                "benchmark engine: JAX found no accelerator; nothing is "
                "drawn or trained off the chip outside a rehearsal")
        t0 = time.perf_counter()
        users, items, vals = _draw.draw_ratings(
            p.data_seed, p.n_users, p.n_items, p.n_ratings,
            structure_seed=p.structure_seed, user_sigma=p.user_sigma, item_exponent=p.item_exponent,
            item_top_share=p.item_top_share, rating_max=p.rating_max)
        t_drawn = time.perf_counter()
        self._set_aside(users, items, vals)
        ratings = Ratings(
            user_indices=users, item_indices=items, ratings=vals,
            user_ids=BiMap({f"u{i}": i for i in range(p.n_users)}),
            item_ids=BiMap({f"i{i}": i for i in range(p.n_items)}))
        _side_update(data_draw_s=t_drawn - t0,
                     data_frame_s=time.perf_counter() - t_drawn)
        return _tpl.TrainingData(ratings)

    def _set_aside(self, users, items, vals) -> None:
        """The ratings of a seeded sample of item rows, half of them drawn
        from the most rated items, for the reference's normal equations."""
        p = self.params
        if not _SIDE or p.check_rows <= 0:
            return
        rng = np.random.default_rng([p.data_seed, 0xC4EC])
        degree = np.bincount(items, minlength=p.n_items)
        heavy = np.argsort(-degree)[:max(p.check_rows * 4, 8)]
        rows = np.unique(np.concatenate([
            rng.choice(heavy, p.check_rows // 2, replace=False),
            rng.choice(p.n_items, p.check_rows - p.check_rows // 2,
                       replace=False)]))
        lut = np.full(p.n_items, -1, np.int32)
        lut[rows] = np.arange(len(rows), dtype=np.int32)
        which = lut[items]
        sel = np.flatnonzero(which >= 0)
        order = np.argsort(which[sel], kind="stable")
        sel = sel[order]
        counts = np.bincount(which[sel], minlength=len(rows))
        np.savez(Path(_SIDE).with_suffix(".check.npz"), rows=rows,
                 counts=counts, users=users[sel], ratings=vals[sel])


class TimedALS(_tpl.ALSAlgorithm):
    """The stock ALS algorithm under the benchmark's clock."""

    def als_config(self):
        # only the builder's control runs set this: the program's own
        # lower-precision path, which the comparison has to refuse
        control = os.environ.get("PIO_BENCH_CONTROL_DTYPE")
        cfg = super().als_config()
        return replace(cfg, compute_dtype=control) if control else cfg

    def train(self, ctx, ratings):
        import jax
        from predictionio_tpu.obs.training import TRAINING

        marks: list[dict] = []
        compiles: list[float] = []
        stock_observe = TRAINING.observe

        def observe(source, iteration, **kw):
            # the annotation puts the same instant on the profiler's clock
            with jax.profiler.TraceAnnotation("bench_iteration_end"):
                stock_observe(source, iteration, **kw)
            if source == "train":
                # taken when the trainer's own probe has returned: the
                # interval between two marks is one whole iteration
                marks.append({"iteration": int(iteration),
                              "t": time.perf_counter(),
                              "step_seconds": kw.get("step_seconds")})

        def on_duration(event, seconds, **_kw):
            # a compile, or a read of the persistent cache in its place
            if "backend_compile" in event or "cache_retrieval" in event:
                compiles.append(time.perf_counter())

        jax.monitoring.register_event_duration_secs_listener(on_duration)
        TRAINING.observe = observe
        t0 = time.perf_counter()
        try:
            model = super().train(ctx, ratings)
        finally:
            TRAINING.observe = stock_observe
        t1 = time.perf_counter()
        if _BREAK == "train":
            # every item's factor handed to its neighbour
            model.item_factors = np.roll(model.item_factors, 1, axis=0)
        _side_update(train_als_t0=t0, train_als_t1=t1, marks=marks, compile_times=compiles, **_device_facts())
        return model


    def batch_predict(self, model, queries):
        out = super().batch_predict(model, queries)
        if _BREAK != "answers":
            return out
        broken = []
        for i, p in out:  # the two best items change places, scores stay
            s = list(p.itemScores)
            if len(s) >= 2:
                s[0], s[1] = (replace(s[0], item=s[1].item),
                              replace(s[1], item=s[0].item))
            broken.append((i, replace(p, itemScores=tuple(s))))
        return broken


def engine_factory() -> Engine:
    return Engine(
        data_source_classes=SyntheticDataSource,
        preparator_classes=_tpl.RecommendationPreparator,
        algorithm_classes={"als": TimedALS},
        serving_classes=FirstServing,
    )
