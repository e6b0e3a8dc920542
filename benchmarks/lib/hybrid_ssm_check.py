"""Child of a `granite-4.0-h-micro-seqrec` run, started once `pio
deploy` has gone and the chip is free: the plain reference
(lib/hybrid_ssm_reference.py, float32 under `highest`, one history at a
time, the scan in its QUADRATIC form, every pair written out in blocks
of rows: 8,192 sequential steps a layer would not fit this child's
clock, and the program's tests hold the two forms to each other) over a
sample of the window's own answers, at the sizes that were served.

The weights are made again from the seed layer by layer
(lib/hybrid_ssm_draw.py) and stay on the device in the bfloat16 they
were drawn in, widened (exactly) to float32 inside each layer's
computation: 3.2 B parameters are 12.8 GB in float32, more than the chip
has beside the scores of a history of 8,192 events. A history is padded
on the RIGHT to one of three lengths so that six layer programs compile
(two kinds a length): under a causal mixer, attention or scan, the
positions before the padding compute what they compute without it.
The layer programs compile in threads while the weights are drawn.

    python benchmarks/lib/hybrid_ssm_check.py --seed N --cell-json FILE \
        --answers FILE --histories FILE --out FILE [--control]

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

from lib import hybrid_ssm_draw as draw  # noqa: E402
from lib import hybrid_ssm_reference as ref  # noqa: E402
from lib import seq_draw  # noqa: E402
from lib.seq_reference import compare_answer  # noqa: E402

#: right-padded lengths a history is computed at (the smallest that
#: holds it; toy sizes fall into the first). A layer program of a further
#: length costs its compile twice (two kinds); a short history computed
#: at 1,024 costs 0.1 s
BUCKETS = (1024, 4096, 8192)


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
    f32 = jnp.float32
    embed = seq_draw.table(a.seed, seq_draw.EMBED, sizes["items"] + 1,
                           cfg["hidden_size"])
    item_rows = jnp.asarray(embed[1:], f32)      # the tied head, pad row out
    norm_f = jnp.ones(cfg["hidden_size"], f32)
    kinds = cfg["layer_types"]

    def widened(w, lowered: bool):
        out = {}
        for k, v in w.items():
            v = v.astype(f32)
            if lowered and k in draw.MATRICES:
                v = jax.lax.reduce_precision(v, exponent_bits=5,
                                             mantissa_bits=2)
            out[k] = v
        return out

    def width_of(n: int) -> int:
        return next((b for b in BUCKETS if b >= n), n)

    def layer_program(kind: str, lowered: bool, width: int):
        def run(x, w):
            with jax.default_matmul_precision("highest"):
                return ref.layer_forward(x, widened(w, lowered), cfg, kind,
                                         form="quadratic")

        weights = {
            k: jax.ShapeDtypeStruct(
                shape, jnp.bfloat16 if k in draw.MATRICES else f32)
            for k, shape in ref.layer_shapes(cfg, kind).items()}
        return jax.jit(run).lower(
            jax.ShapeDtypeStruct((width, cfg["hidden_size"]), f32),
            weights).compile()

    histories = [np.asarray(hist[ans["row"]]) for ans in answers]
    histories = [row[row > 0].astype(np.int64) for row in histories]
    # the layer programs compile in threads of their own while the
    # weights are drawn: two kinds a width (and as many again under
    # --control), each some seconds of a cold run
    wanted = [(kind, lowered, width)
              for width in sorted({width_of(len(h)) for h in histories})
              for kind in ("mamba", "attention")
              for lowered in ((False, True) if a.control else (False,))]
    with ThreadPoolExecutor(len(wanted)) as compilers:
        programs = {key: compilers.submit(layer_program, *key)
                    for key in wanted}
        # a layer's row blocks are drawn in the pool's threads; the layer
        # before it goes up meanwhile
        with ThreadPoolExecutor(seq_draw.THREADS) as pool:
            layers = [{k: jnp.asarray(v) for k, v in
                       draw.layer_weights(a.seed, cfg, l, pool).items()}
                      for l in range(len(kinds))]
        jax.block_until_ready(layers)
        t_weights = time.perf_counter()
        programs = {key: job.result() for key, job in programs.items()}
    t_compiled = time.perf_counter()

    def logits_of(tokens, lowered: bool):
        n = len(tokens)
        width = width_of(n)
        padded = np.zeros(width, np.int64)
        padded[:n] = tokens
        x = (jnp.asarray(np.asarray(embed[padded], np.float32))
             * cfg["embedding_multiplier"])
        for l, kind in enumerate(kinds):
            x = programs[kind, lowered, width](x, layers[l])
        last = ref.rms_norm(x[n - 1], norm_f, cfg["rms_norm_eps"])
        return np.asarray(ref.scores(last, item_rows, cfg))

    num = int(cell["traffic"]["num"])
    unseen = np.zeros(0, np.int64)   # exclude_seen is false in this cell
    rows, ctl_rows = [], []
    for ans, tokens in zip(answers, histories):
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
           "compile_wait_s": t_compiled - t_weights,
           "forward_s": time.perf_counter() - t_compiled,
           "device": jax.devices()[0].platform}
    if a.control:
        out["control"] = summary(ctl_rows)
    Path(a.out).write_text(json.dumps(out))
    return 0


if __name__ == "__main__":
    raise SystemExit(main())
