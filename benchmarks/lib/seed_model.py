"""Child of a serving run: makes the served model from the seed and
persists it the way `pio train` would, so that `pio deploy` loads it
through the program's own serialization and metadata.

Never touches the chip (the harness starts it with JAX held to the host;
the program's serializer imports jax to pull device arrays, of which
there are none here).

    python benchmarks/lib/seed_model.py --engine-dir D --seed N \
        --users U --items I --rank R
"""

from __future__ import annotations

import argparse
import json
import sys
import time
from concurrent.futures import ThreadPoolExecutor
from pathlib import Path

sys.path.insert(0, str(Path(__file__).resolve().parents[1]))

from lib import draw  # noqa: E402


def main(argv=None) -> int:
    p = argparse.ArgumentParser()
    p.add_argument("--engine-dir", required=True)
    p.add_argument("--seed", type=int, required=True)
    p.add_argument("--users", type=int, required=True)
    p.add_argument("--items", type=int, required=True)
    p.add_argument("--rank", type=int, required=True)
    a = p.parse_args(argv)

    from predictionio_tpu.models.als import ALSConfig, ALSModel
    from predictionio_tpu.storage import EngineInstance, Model, Storage
    from predictionio_tpu.storage.bimap import BiMap
    from predictionio_tpu.workflow.serialization import serialize_models

    t0 = time.perf_counter()
    variant = json.loads((Path(a.engine_dir) / "engine.json").read_text())
    algo = variant["algorithms"][0]
    # the factor draw releases the interpreter lock; the id maps, which
    # take longer, are built meanwhile
    with ThreadPoolExecutor(1) as pool:
        drawn = pool.submit(lambda: (
            draw.factors(a.seed, draw.USER_SIDE, a.users, a.rank),
            draw.factors(a.seed, draw.ITEM_SIDE, a.items, a.rank)))
        user_ids = BiMap({f"u{i}": i for i in range(a.users)})
        item_ids = BiMap({f"i{i}": i for i in range(a.items)})
        user_factors, item_factors = drawn.result()
    model = ALSModel(
        user_factors=user_factors, item_factors=item_factors,
        user_ids=user_ids, item_ids=item_ids,
        config=ALSConfig(rank=a.rank,
                         iterations=algo["params"]["num_iterations"],
                         lambda_=algo["params"]["lambda_"],
                         seed=algo["params"]["seed"]))
    t_drawn = time.perf_counter()
    blob = serialize_models([model])
    t_ser = time.perf_counter()
    meta = Storage.get_metadata()
    iid = meta.engine_instance_insert(EngineInstance(
        status="COMPLETED",
        engine_id=variant["id"],
        engine_version=str(variant.get("version", "1")),
        engine_variant=str(variant.get("variantId", "default")),
        engine_factory=variant["engineFactory"],
        data_source_params=json.dumps(
            {"name": "", "params": variant["datasource"]["params"]}),
        preparator_params=json.dumps({"name": "", "params": {}}),
        algorithms_params=json.dumps(variant["algorithms"]),
        serving_params=json.dumps({"name": "", "params": {}}),
        backend_conf={"seeded_by": "benchmarks/lib/seed_model.py"}))
    Storage.get_models().insert(Model(
        id=iid, models=blob, checksum=Model.compute_checksum(blob)))
    t_end = time.perf_counter()
    print("SEEDED " + json.dumps({
        "engine_instance": iid, "blob_bytes": len(blob),
        "draw_s": t_drawn - t0, "serialize_s": t_ser - t_drawn,
        "persist_s": t_end - t_ser}))
    return 0


if __name__ == "__main__":
    raise SystemExit(main())
