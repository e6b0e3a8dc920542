"""Child of an `axk1-seqrec` run, started once `pio deploy` has gone and
the chip is free: the plain reference (lib/latent_moe_reference.py,
float32 under `highest`, one history at a time) over a sample of the
window's own answers, at the sizes that were served.

The weights are made again from the seed layer by layer
(lib/latent_moe_draw.py) and stay on the device in the bfloat16 they were drawn in,
widened (exactly) to float32 inside each layer's computation: the share's
3.2 B parameters are 12.8 GB in float32, more than the chip has beside
the scores of a history of 8,192 events. A history is padded on the
RIGHT to one of two lengths so that four layer programs compile instead
of two a length: under causal attention, and with experts that take a
token at a time, the positions before the padding compute what they
compute without it. The reference's own blocks (lib/latent_moe_reference
`HEAD_BLOCK`) keep 8 heads' scores alive at once.

    python benchmarks/lib/latent_moe_check.py --seed N --cell-json FILE \
        --answers FILE --histories FILE --out FILE \
        [--control]

`--control` also answers the sampled queries from the reference computed
with its bfloat16-stated matrices rounded to float8 (e5m2, through
`lax.reduce_precision`, which the compiler may not fold away), a
precision below the one the configuration states, and compares those
answers the same way: the reading the limits have to refuse.
"""

from __future__ import annotations

import argparse
import json
import sys
import time
from concurrent.futures import ThreadPoolExecutor
from pathlib import Path

sys.path.insert(0, str(Path(__file__).resolve().parents[1]))

import numpy as np  # noqa: E402

from lib import latent_moe_draw as draw  # noqa: E402
from lib import latent_moe_reference as ref  # noqa: E402
from lib import seq_draw  # noqa: E402
from lib.seq_reference import compare_answer  # noqa: E402

#: right-padded lengths a history is computed at (the smaller that holds
#: it; toy sizes fall into the first). Two and not three: a layer program
#: of a further length costs 10 s of a cold run's compiles (two layer
#: kinds a length), a short history computed at 4,096 0.5 s more
BUCKETS = (4096, 8192)
LOWERED = (ref.ATTENTION_MATRICES + ref.DENSE_MATRICES + ref.MOE_MATRICES)


def main(argv=None) -> int:
    p = argparse.ArgumentParser()
    p.add_argument("--seed", type=int, required=True)
    p.add_argument("--cell-json", required=True)
    p.add_argument("--answers", required=True)
    p.add_argument("--histories", required=True)
    p.add_argument("--out", required=True)
    p.add_argument("--control", action="store_true")
    a = p.parse_args(argv)

    import jax
    import jax.numpy as jnp

    t0 = time.perf_counter()
    cell = json.loads(Path(a.cell_json).read_text())
    cfg, sizes = cell["model"], cell["sizes"]
    answers = json.loads(Path(a.answers).read_text())
    hist = np.load(a.histories, mmap_mode="r")
    vocab = sizes["items"] + 1
    f32 = jnp.float32
    embed = seq_draw.table(a.seed, seq_draw.EMBED, vocab, cfg["hidden_size"])
    head = jnp.asarray(seq_draw.table(a.seed, seq_draw.HEAD, vocab,
                                      cfg["hidden_size"])[1:], f32)
    norm_f = jnp.ones(cfg["hidden_size"], f32)
    n_layers = cfg["num_hidden_layers"]

    def widened(w, lowered: bool):
        out = {}
        for k, v in w.items():
            v = v.astype(f32)
            if lowered and k in LOWERED:
                v = jax.lax.reduce_precision(v, exponent_bits=5,
                                             mantissa_bits=2)
            out[k] = v
        return out

    first_routed = cfg["first_k_dense_replace"] - cfg["first_layer"]

    def layer_program(dense: bool, lowered: bool):
        def run(x, w):
            with jax.default_matmul_precision("highest"):
                return ref.layer_forward(x, widened(w, lowered), cfg,
                                         0 if dense else first_routed)[0]

        return jax.jit(run)

    def width_of(n: int) -> int:
        return next((b for b in BUCKETS if b >= n), n)

    # a layer's row blocks are drawn in the pool's threads; the layer
    # before it goes up meanwhile
    with ThreadPoolExecutor(seq_draw.THREADS) as pool:
        layers = [{k: jnp.asarray(v) for k, v in
                   draw.layer_weights(a.seed, cfg, i, pool).items()}
                  for i in range(n_layers)]
    jax.block_until_ready(layers)
    t_weights = time.perf_counter()
    programs = {(dense, lowered): layer_program(dense, lowered)
                for dense in (True, False) for lowered in (False, True)}

    def logits_of(tokens, lowered: bool):
        n = len(tokens)
        width = width_of(n)
        padded = np.zeros(width, np.int64)
        padded[:n] = tokens
        x = jnp.asarray(np.asarray(embed[padded], np.float32))
        for i in range(n_layers):
            x = programs[ref.is_dense(cfg, i), lowered](x, layers[i])
        with jax.default_matmul_precision("highest"):
            last = ref.rms_norm(x[n - 1], norm_f, cfg["rms_norm_eps"])
        return np.asarray(ref.scores(last, head))

    num = int(cell["traffic"]["num"])
    unseen = np.zeros(0, np.int64)   # exclude_seen is false in this cell
    rows, ctl_rows = [], []
    for ans in answers:
        row = hist[ans["row"]]
        tokens = np.asarray(row[row > 0], np.int64)
        t_answer = time.perf_counter()
        logits = logits_of(tokens, False)
        seconds = time.perf_counter() - t_answer
        served = [(int(it["item"][1:]), float(it["score"]))
                  for it in ans["served"]]
        rows.append({"length": int(len(tokens)), "seconds": seconds,
                     **compare_answer(served, logits, unseen, num)})
        if a.control:
            low = logits_of(tokens, True)
            best = np.argsort(-low, kind="stable")[:num]
            ctl_rows.append(compare_answer(
                [(int(i), float(low[i])) for i in best], logits, unseen, num))

    def summary(per_answer):
        errs = [r["score_err"] for r in per_answer]
        return {"score_err": float(max(errs)),
                "score_err_median": float(np.median(errs)),
                "rank_slack": float(max(r["rank_slack"] for r in per_answer)),
                "short": int(sum(r["short"] for r in per_answer))}

    out = {"answers": len(answers), **summary(rows), "per_answer": rows,
           "weights_s": t_weights - t0,
           "forward_s": time.perf_counter() - t_weights,
           "device": jax.devices()[0].platform}
    if a.control:
        out["control"] = summary(ctl_rows)
    Path(a.out).write_text(json.dumps(out))
    return 0


if __name__ == "__main__":
    raise SystemExit(main())
