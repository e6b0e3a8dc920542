"""Child of a `granite-4.0-h-micro-seqrec` run: makes the hybrid
state-space decoder (all of it) and every user's history from the seed
and persists the model the way `pio train` would, so that `pio deploy`
loads it through the program's own serialization and metadata.

Never touches the chip (the harness starts it with JAX held to the
host). The program's model class is imported FIRST, before anything is
drawn: a program that has no such decoder ends the run here, in
seconds. The histories go into the blob as int32 (ids up to 100,351 do
not fit 16 bits), and beside it as the file the check child reads.

    python benchmarks/lib/hybrid_ssm_seed_model.py --engine-dir D \
        --seed N --cell-json FILE --seconds S --histories-out FILE
"""

from __future__ import annotations

import argparse
import json
import sys
import time
from concurrent.futures import ThreadPoolExecutor
from pathlib import Path

sys.path.insert(0, str(Path(__file__).resolve().parents[1]))


def main(argv=None) -> int:
    p = argparse.ArgumentParser()
    p.add_argument("--engine-dir", required=True)
    p.add_argument("--seed", type=int, required=True)
    p.add_argument("--cell-json", required=True,
                   help="{model, sizes, traffic} of this run")
    p.add_argument("--seconds", type=float, required=True)
    p.add_argument("--histories-out", required=True)
    a = p.parse_args(argv)

    from predictionio_tpu.models.hybrid_ssm_lm import (HybridSSMConfig,
                                                       HybridSSMModel)

    import numpy as np
    from predictionio_tpu.storage import EngineInstance, Model, Storage
    from predictionio_tpu.storage.bimap import BiMap
    from predictionio_tpu.workflow.serialization import serialize_models

    from lib import hybrid_ssm_draw as draw, seq_draw

    t0 = time.perf_counter()
    cell = json.loads(Path(a.cell_json).read_text())
    model, sizes, traffic = cell["model"], cell["sizes"], cell["traffic"]
    variant = json.loads((Path(a.engine_dir) / "engine.json").read_text())
    config = HybridSSMConfig(**variant["algorithms"][0]["params"])
    hidden = model["hidden_size"]

    def histories():
        hist = draw.histories(traffic, a.seed, sizes["users"],
                              sizes["items"], sizes["max_len"], a.seconds)
        np.save(a.histories_out, hist)
        return hist

    # the draws release the interpreter lock: the histories and the tied
    # table are made while the layers are
    with ThreadPoolExecutor(2) as pool:
        hist_job = pool.submit(histories)
        embed_job = pool.submit(seq_draw.table, a.seed, seq_draw.EMBED,
                                sizes["items"] + 1, hidden)
        layers = draw.stacked_layers(a.seed, model)
        t_drawn = time.perf_counter()
        hist = hist_job.result()
        params = {"embed": embed_job.result(),
                  "norm_f": np.ones(hidden, np.float32), **layers}
    served = HybridSSMModel(
        params=params, seqs=hist,
        user_ids=BiMap({f"u{i}": i for i in range(sizes["users"])}),
        item_ids=BiMap({f"i{i}": i for i in range(sizes["items"])}),
        config=config)
    t_made = time.perf_counter()
    blob = serialize_models([served])
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
        backend_conf={"seeded_by": "benchmarks/lib/hybrid_ssm_seed_model.py"}))
    Storage.get_models().insert(Model(
        id=iid, models=blob, checksum=Model.compute_checksum(blob)))
    t_end = time.perf_counter()
    lengths = (hist > 0).sum(axis=1)
    print("SEEDED " + json.dumps({
        "engine_instance": iid, "blob_bytes": len(blob),
        "draw_s": t_drawn - t0, "histories_wait_s": t_made - t_drawn,
        "serialize_s": t_ser - t_made, "persist_s": t_end - t_ser,
        "history_tokens": int(lengths.sum()),
        "history_mean": float(lengths.mean())}))
    return 0


if __name__ == "__main__":
    raise SystemExit(main())
