"""Child of a sequence-serving run, started once `pio deploy` has gone
and the chip is free: the plain reference (lib/seq_reference.py, float32
under `highest`, one sequence at a time) over a sample of the window's
own answers, at the sizes that were served.

The weights are made again from the seed layer by layer
(lib/seq_draw.py) and stay on the device as float32 while the answers go
through; the histories are the file the seeding child wrote. A sequence
is padded on the RIGHT to a multiple of 128 events so that four shapes
compile instead of one a length: under causal attention the positions
before the padding compute what they compute without it.

    python benchmarks/lib/seq_check.py --seed N --cell-json FILE \
        --answers FILE --histories FILE --out FILE [--control]

`--control` also answers the sampled queries from the reference computed
with its matrices rounded to float8 (e5m2: bfloat16's exponent range to
the nearest byte below, 2 bits of mantissa for its 7), a precision below
the bfloat16 the configuration states, and compares those answers the
same way: the reading the limits have to refuse. The rounding is
`lax.reduce_precision`, which the compiler may not fold away (a
convert to float8 and back it does, on the TPU).
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

from lib import seq_draw, seq_reference as ref  # noqa: E402

BUCKET = 128


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
    model, sizes = cell["model"], cell["sizes"]
    cfg = {k: model[k] for k in ref.CONFIG_KEYS}
    answers = json.loads(Path(a.answers).read_text())
    hist = np.load(a.histories, mmap_mode="r")
    vocab = sizes["items"] + 1
    f32 = jnp.float32
    embed = seq_draw.table(a.seed, seq_draw.EMBED, vocab, cfg["hidden_size"])
    head = jnp.asarray(seq_draw.table(a.seed, seq_draw.HEAD, vocab,
                                      cfg["hidden_size"])[1:], f32)
    top = {k: jnp.asarray(v) for k, v in
           seq_draw.top_weights(a.seed, model).items()}
    # the next layers are drawn while this one goes up
    with ThreadPoolExecutor(2) as pool:
        layers = [{k: jnp.asarray(v, f32) for k, v in drawn.items()}
                  for drawn in pool.map(
                      lambda layer: seq_draw.layer_weights(a.seed, model,
                                                           layer),
                      range(cfg["num_hidden_layers"]))]
    jax.block_until_ready(layers)
    t_weights = time.perf_counter()

    # one compile a shape; `forward` hands the layer its config, which the
    # jitted functions already hold
    sound_jit = jax.jit(lambda h, w: ref.layer_forward(h, w, cfg))

    def sound_layer(h, w, _cfg):
        return sound_jit(h, w)

    def to_f8(w):
        return {k: (jax.lax.reduce_precision(v, exponent_bits=5,
                                             mantissa_bits=2)
                    if k in ref.MATRICES else v) for k, v in w.items()}

    control_jit = jax.jit(lambda h, w: ref.layer_forward(h, to_f8(w), cfg))

    def control_layer(h, w, _cfg):
        return control_jit(h, w)

    def logits_of(tokens, layer):
        n = len(tokens)
        padded = np.zeros(-(-n // BUCKET) * BUCKET, np.int64)
        padded[:n] = tokens
        emb = np.asarray(embed[padded], np.float32)
        h_exit, exit_step, _half, _p = ref.forward(
            emb, lambda _t, l: layers[l], top, cfg, layer=layer)  # noqa: E741
        return (np.asarray(ref.scores(h_exit[n - 1], head)),
                int(exit_step[n - 1]))

    num = int(cell["traffic"]["num"])
    worst = {"score_err": 0.0, "rank_slack": 0.0, "short": 0}
    ctl = {"score_err": 0.0, "rank_slack": 0.0, "short": 0}
    exit_steps = []
    for ans in answers:
        row = hist[ans["row"]]
        tokens = np.asarray(row[row > 0], np.int64)
        seen = np.unique(tokens) - 1
        logits, exit_step = logits_of(tokens, sound_layer)
        exit_steps.append(exit_step)
        served = [(int(it["item"][1:]), float(it["score"]))
                  for it in ans["served"]]
        got = ref.compare_answer(served, logits, seen, num)
        for k in worst:
            worst[k] = (worst[k] + got[k] if k == "short"
                        else max(worst[k], got[k]))
        if a.control:
            low, _step = logits_of(tokens, control_layer)
            masked = low.copy()
            masked[seen] = -np.inf
            best = np.argsort(-masked, kind="stable")[:num]
            got = ref.compare_answer([(int(i), float(low[i])) for i in best],
                                     logits, seen, num)
            for k in ctl:
                ctl[k] = (ctl[k] + got[k] if k == "short"
                          else max(ctl[k], got[k]))
    out = {"answers": len(answers), **worst, "exit_steps": exit_steps,
           "weights_s": t_weights - t0,
           "forward_s": time.perf_counter() - t_weights,
           "device": jax.devices()[0].platform}
    if a.control:
        out["control"] = ctl
    Path(a.out).write_text(json.dumps(out))
    return 0


if __name__ == "__main__":
    raise SystemExit(main())
