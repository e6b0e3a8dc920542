"""Plain reference of the looped decoder (models/looped_lm.py): the
published forward in straightforward ``jax.numpy``, float32, under
``jax.default_matmul_precision("highest")``, ONE sequence at a time, a
Python loop over the passes and the layers; no scan, no cache, no
batching, no kernel, nothing of the program imported.

Source: https://huggingface.co/ByteDance/Ouro-2.6B/blob/main/config.json
(``model_type: ouro``) and the LoopLM report, arXiv:2510.25741. What the
config gives: every size, ``hidden_act: silu``, ``rms_norm_eps``,
``rope_theta``, untied embedding and head, ``total_ut_steps``,
``early_exit_threshold``, full attention in every layer. Assumed from the
report and the modelling code beside the config (no network here; each is
listed under ``assumed`` in benchmarks/configs/ouro-2.6b-seqrec.json):

- a second RMSNorm after each sub-layer (sandwich norms), before the
  residual add;
- the final RMSNorm after EVERY pass, its output being both the next
  pass's input and the state the gate and the head read;
- the gate: ``lam_t = sigmoid(w_e . h_t + b_e)``, ``p_t = lam_t *
  prod_{j<t}(1 - lam_j)`` for t < T and the remaining mass at T; a
  position exits at the first pass whose cumulated p reaches the
  threshold;
- no biases; RoPE in the rotate-half convention over the whole head.

Departures from the published model: the vocabulary is an item table
whose row 0 is a pad id that no history holds; positions count a
history's own events from 0. A query is one full forward over its
history: there is no cache and no decode step to compare.

This file is copied, byte for byte, to benchmarks/lib/seq_reference.py
(the harness takes nothing from the program); a test holds the two equal.
"""

from __future__ import annotations

import numpy as np

#: the published config's keys the forward reads
CONFIG_KEYS = ("hidden_size", "intermediate_size", "num_hidden_layers",
               "num_attention_heads", "head_dim", "total_ut_steps",
               "early_exit_threshold", "rms_norm_eps", "rope_theta")
MATRICES = ("wq", "wk", "wv", "wo", "wg", "wu", "wd")


def rms_norm(x, gain, eps):
    import jax.numpy as jnp

    return x / jnp.sqrt(jnp.mean(x * x, axis=-1, keepdims=True) + eps) * gain


def rope(x, theta):
    """x [n, heads, head_dim], position = row index."""
    import jax.numpy as jnp

    n, _h, hd = x.shape
    half = hd // 2
    inv = theta ** (-jnp.arange(half, dtype=jnp.float32) / half)
    ang = jnp.arange(n, dtype=jnp.float32)[:, None] * inv[None, :]
    cos = jnp.concatenate([jnp.cos(ang), jnp.cos(ang)], axis=-1)[:, None, :]
    sin = jnp.concatenate([jnp.sin(ang), jnp.sin(ang)], axis=-1)[:, None, :]
    rotated = jnp.concatenate([-x[..., half:], x[..., :half]], axis=-1)
    return x * cos + rotated * sin


def layer_forward(h, w, cfg: dict):
    """One decoder layer on one sequence: h [n, hidden] float32, w the
    layer's float32 weights."""
    import jax
    import jax.numpy as jnp

    n = h.shape[0]
    heads, hd, eps = cfg["num_attention_heads"], cfg["head_dim"], cfg[
        "rms_norm_eps"]
    a = rms_norm(h, w["norm1"], eps)
    q = rope((a @ w["wq"]).reshape(n, heads, hd), cfg["rope_theta"])
    k = rope((a @ w["wk"]).reshape(n, heads, hd), cfg["rope_theta"])
    v = (a @ w["wv"]).reshape(n, heads, hd)
    s = jnp.einsum("qhd,khd->hqk", q, k) / np.sqrt(hd)
    causal = jnp.arange(n)[None, :] <= jnp.arange(n)[:, None]
    s = jnp.where(causal[None], s, -jnp.inf)
    o = jnp.einsum("hqk,khd->qhd", jax.nn.softmax(s, axis=-1), v)
    h = h + rms_norm(o.reshape(n, heads * hd) @ w["wo"], w["norm2"], eps)
    m = rms_norm(h, w["norm3"], eps)
    mlp = (jax.nn.silu(m @ w["wg"]) * (m @ w["wu"])) @ w["wd"]
    return h + rms_norm(mlp, w["norm4"], eps)


def forward(embedded, layer_of, top: dict, cfg: dict, *, passes=None,
            layer=layer_forward):
    """The whole forward of one sequence. ``embedded`` [n, hidden]: the
    embedding rows of its tokens; ``layer_of(t, l)``: the float32
    weights of layer l in pass t (the model's are the same for every t:
    that is the loop); ``top``: norm_f, gate_w, gate_b. ``passes`` runs
    fewer passes than the config says (a broken path, for the tests).
    Returns h_exit [n, hidden], exit_step [n] (1-based), half_step [n]
    (first pass with cumulated p >= 1/2), p [passes, n]."""
    import jax
    import jax.numpy as jnp

    total = cfg["total_ut_steps"] if passes is None else passes
    with jax.default_matmul_precision("highest"):
        h = jnp.asarray(embedded, jnp.float32)
        n = h.shape[0]
        rest = jnp.ones(n, jnp.float32)
        cum = jnp.zeros(n, jnp.float32)
        h_exit = jnp.zeros_like(h)
        exit_step = jnp.zeros(n, jnp.int32)
        half_step = jnp.zeros(n, jnp.int32)
        probs = []
        for t in range(total):
            for l in range(cfg["num_hidden_layers"]):  # noqa: E741
                h = layer(h, layer_of(t, l), cfg)
            h = rms_norm(h, top["norm_f"], cfg["rms_norm_eps"])
            lam = jax.nn.sigmoid(h @ top["gate_w"] + top["gate_b"])
            p = rest if t == total - 1 else lam * rest
            new_cum = jnp.ones(n, jnp.float32) if t == total - 1 else cum + p
            exits = (exit_step == 0) & (new_cum >= cfg["early_exit_threshold"])
            h_exit = jnp.where(exits[:, None], h, h_exit)
            exit_step = jnp.where(exits, t + 1, exit_step)
            half_step = jnp.where((half_step == 0) & (new_cum >= 0.5), t + 1,
                                  half_step)
            rest, cum = rest - p, new_cum
            probs.append(p)
        return h_exit, exit_step, half_step, jnp.stack(probs)


def scores(h_last, head):
    """Logits of one state against the output head [rows, hidden]."""
    import jax
    import jax.numpy as jnp

    with jax.default_matmul_precision("highest"):
        return jnp.asarray(head, jnp.float32) @ jnp.asarray(h_last,
                                                            jnp.float32)


def stacked_layer_of(layers: dict):
    """``layer_of`` for a parameter tree whose layer weights are stacked
    [L, ...] (the program's): the same layer l in every pass."""
    import jax.numpy as jnp

    def layer_of(_t, l):  # noqa: E741
        return {k: jnp.asarray(v[l], jnp.float32) for k, v in layers.items()}

    return layer_of


def next_item_scores(params: dict, cfg: dict, history) -> np.ndarray:
    """Float32 logits [rows of the head] for the item after ``history``
    (ids as stored: item i is i + 1), from a whole parameter tree."""
    history = np.asarray(history, np.int64)
    emb = np.asarray(params["embed"])[history].astype(np.float32)
    top = {k: np.asarray(params[k], np.float32)
           for k in ("norm_f", "gate_w", "gate_b")}
    h_exit, _e, _h, _p = forward(emb, stacked_layer_of(params["layers"]),
                                 top, cfg)
    return np.asarray(scores(h_exit[-1], np.asarray(params["head"])))


def compare_answer(served: list[tuple[int, float]], logits: np.ndarray,
                   seen: np.ndarray, num: int) -> dict:
    """One served answer, [(item row, score)] best first, against the
    reference's logits over the item rows (the pad row left out):
    ``score_err`` - the worst gap between a served score and the
    reference's logit of the same item, over the row's logit spread
    (max - min); ``rank_slack`` - how far the worst served item's
    reference logit lies under the reference's ``num``-th best unseen
    logit, over the same spread (ids are not compared: random weights
    flip near-ties on rounding); ``short`` - 1 if the answer has not
    ``num`` items, holds a seen item, or repeats one."""
    spread = float(logits.max() - logits.min())
    unseen = np.ones(len(logits), bool)
    unseen[np.asarray(seen, np.int64)] = False
    want = min(num, int(unseen.sum()))
    ids = np.asarray([i for i, _s in served], np.int64)
    got = np.asarray([s for _i, s in served], np.float64)
    ok = (len(ids) == want and len(set(ids.tolist())) == len(ids)
          and bool(np.all((ids >= 0) & (ids < len(logits))))
          and bool(unseen[ids].all()))
    if not ok or want == 0:
        return {"score_err": 0.0, "rank_slack": 0.0, "short": int(not ok)}
    ref = logits[ids].astype(np.float64)
    kth = np.sort(logits[unseen])[-want]
    return {"score_err": float(np.abs(got - ref).max() / spread),
            "rank_slack": float(max(0.0, kth - ref.min()) / spread),
            "short": 0}
