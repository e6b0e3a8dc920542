"""Plain reference of the hybrid state-space decoder
(predictionio_tpu/models/hybrid_ssm_lm.py): the published forward in
straightforward ``jax.numpy``, float32, under
``jax.default_matmul_precision("highest")``, ONE history at a time, a
Python loop over the layers; no packing, no chunks, no kernel, nothing
of the program imported. The selective scan stands here in TWO forms:

- ``recurrence``: the state update of the published description token
  by token (a ``lax.scan`` over the history's events);
- ``quadratic``: the same sum with every (i, j <= i) pair written out,
  ``y_i = sum_j (C_i . B_j) exp(A sum_{k=j+1..i} D_k) D_j x_j``, in
  blocks of rows so that the [rows, n] matrices of a head fit (the form
  the check child takes at 8,192 events, where 8,192 sequential steps a
  layer would not fit its clock).

Neither shares the program's chunk decomposition (there is no chunk
here, and no state handed from one to the next). The program's tier-1
tests hold the two to each other and the program to both; the
benchmark's check child loads this one file.

Source: https://huggingface.co/ibm-granite/granite-4.0-h-micro/blob/main/config.json
(``model_type: granitemoehybrid``; HF ``modeling_granitemoehybrid.py``
is the published description). What the config gives: every size,
``layer_types``, the four multipliers (``embedding_multiplier`` on the
embedding's rows, ``residual_multiplier`` on every block's output
before it is added, ``attention_multiplier`` as the score scale in
place of head_dim^-0.5, ``logits_scaling`` dividing the logits),
``position_embedding_type: nope`` (no rotary, no position term at all),
``tie_word_embeddings``, ``num_local_experts: 0`` (the feed-forward is
the shared MLP alone), ``mamba_*``, no biases but the convolution's.
Readings the config leaves open (each under ``assumed`` in
benchmarks/configs/granite-4.0-h-micro-seqrec.json): no clamp on the
time step (HF's ``time_step_limit`` is (0, inf)); the gate multiplies
BEFORE the mixer's RMSNorm, which is over all of d_inner (one group).

Departures from the published model: the vocabulary is an item table
whose row 0 is a pad id that no history holds; a query is one full
forward over its history (no state cache, no decode step).

``variant`` breaks the forward on purpose, for the tests that show each
break to fail: ``sqrt_scale`` (attention scaled by head_dim^-0.5 and not
by ``attention_multiplier``), ``norm_before_gate`` (the mixer's norm
before the gate).
"""

from __future__ import annotations

import numpy as np

#: the published config's keys the forward reads
CONFIG_KEYS = (
    "hidden_size", "num_hidden_layers", "layer_types", "num_attention_heads",
    "num_key_value_heads", "attention_multiplier", "embedding_multiplier",
    "residual_multiplier", "logits_scaling", "shared_intermediate_size",
    "mamba_n_heads", "mamba_d_head", "mamba_d_state", "mamba_n_groups",
    "mamba_d_conv", "mamba_expand", "mamba_chunk_size", "mamba_conv_bias",
    "mamba_proj_bias", "rms_norm_eps", "position_embedding_type",
    "tie_word_embeddings", "num_local_experts")
MAMBA_MATRICES = ("in_proj", "out_proj")
ATTENTION_MATRICES = ("wq", "wk", "wv", "wo")
MLP_MATRICES = ("w_in", "w_out")
#: what is no matrix (float32 in every tree)
VECTORS = ("input_norm", "post_norm", "norm", "conv_w", "conv_b", "dt_bias",
           "A_log", "D")
#: rows of the quadratic form alive at once: 64 heads x 128 x 8,192 x 4 B
#: = 0.27 GB a matrix
ROW_BLOCK = 128
#: query heads whose [n, n] scores are alive at once (4 x 8,192^2 x 4 B =
#: 1.1 GB, and the softmax's copy of it)
HEAD_BLOCK = 4


def d_inner(cfg: dict) -> int:
    return cfg["mamba_n_heads"] * cfg["mamba_d_head"]


def conv_dim(cfg: dict) -> int:
    return d_inner(cfg) + 2 * cfg["mamba_n_groups"] * cfg["mamba_d_state"]


def layer_shapes(cfg: dict, kind: str) -> dict:
    """Shapes of ONE layer's weights (mixer and the MLP behind it),
    matrices as [in, out]."""
    D, F = cfg["hidden_size"], cfg["shared_intermediate_size"]
    out = {"input_norm": (D,), "post_norm": (D,), "w_in": (D, 2 * F),
           "w_out": (F, D)}
    if kind == "attention":
        H, KV = cfg["num_attention_heads"], cfg["num_key_value_heads"]
        hd = D // H
        out.update(wq=(D, H * hd), wk=(D, KV * hd), wv=(D, KV * hd),
                   wo=(H * hd, D))
        return out
    di, cv, Hm = d_inner(cfg), conv_dim(cfg), cfg["mamba_n_heads"]
    out.update(in_proj=(D, di + cv + Hm), conv_w=(cfg["mamba_d_conv"], cv),
               conv_b=(cv,), dt_bias=(Hm,), A_log=(Hm,), D=(Hm,), norm=(di,),
               out_proj=(di, D))
    return out


def rms_norm(x, gain, eps):
    import jax.numpy as jnp

    return x / jnp.sqrt(jnp.mean(x * x, axis=-1, keepdims=True) + eps) * gain


def silu(x):
    import jax.numpy as jnp

    return x / (1.0 + jnp.exp(-x))


def softplus(x):
    import jax.numpy as jnp

    return jnp.logaddexp(x, 0.0)


def conv(u, w, b):
    """u [n, C], w [K, C], b [C]: out_t = b + sum_k w_k u_{t-K+1+k}, the
    taps before the first event zero."""
    import jax.numpy as jnp

    K, n = w.shape[0], u.shape[0]
    padded = jnp.concatenate([jnp.zeros((K - 1, u.shape[1]), u.dtype), u])
    return b + sum(w[k] * padded[k:k + n] for k in range(K))


def scan_recurrence(x, dt, A, B, C):
    """x [n, H, P], dt [n, H], A [H], B, C [n, N] -> y [n, H, P] (no skip
    term): S_t = exp(dt_t A) S_{t-1} + dt_t x_t outer B_t; y_t = S_t C_t,
    one event at a time from S = 0."""
    import jax
    import jax.numpy as jnp

    def step(S, t):
        x_t, dt_t, B_t, C_t = t
        S = (jnp.exp(dt_t * A)[:, None, None] * S
             + (dt_t[:, None] * x_t)[:, :, None] * B_t[None, None, :])
        return S, jnp.einsum("hpn,n->hp", S, C_t)

    S0 = jnp.zeros((x.shape[1], x.shape[2], B.shape[1]), jnp.float32)
    return jax.lax.scan(step, S0, (x, dt, B, C))[1]


def scan_quadratic(x, dt, A, B, C, row_block: int = ROW_BLOCK):
    """The same sum with every pair written out:
    y_i = sum_{j <= i} (C_i . B_j) exp(A (cum_i - cum_j)) dt_j x_j, cum
    the inclusive cumulative sum of dt; ``row_block`` rows i at a time."""
    import jax
    import jax.numpy as jnp

    n = x.shape[0]
    R = min(row_block, n)
    pad = -n % R
    cum = jnp.cumsum(dt, axis=0) * A                                # [n, H]
    xdt = x * dt[..., None]
    C_p = jnp.pad(C, ((0, pad), (0, 0)))
    cum_p = jnp.pad(cum, ((0, pad), (0, 0)))
    cols = jnp.arange(n)

    def rows(lo):
        c = jax.lax.dynamic_slice_in_dim(C_p, lo, R)                # [R, N]
        own = jax.lax.dynamic_slice_in_dim(cum_p, lo, R)            # [R, H]
        pair = (cols[None, :] <= lo + jnp.arange(R)[:, None])       # [R, n]
        decay = jnp.exp(jnp.where(
            pair[None], own.T[:, :, None] - cum.T[:, None, :], -jnp.inf))
        return jnp.einsum("hrj,jhp->rhp", (c @ B.T)[None] * decay, xdt)

    y = jax.lax.map(rows, jnp.arange(0, n + pad, R))
    return y.reshape(n + pad, *x.shape[1:])[:n]


def mamba_mixer(h, w, cfg: dict, form: str = "recurrence",
                variant: str | None = None):
    """The Mamba-2 mixer on the normed states h [n, hidden] of one
    history."""
    import jax.numpy as jnp

    n = h.shape[0]
    di, N = d_inner(cfg), cfg["mamba_d_state"]
    Hm, P = cfg["mamba_n_heads"], cfg["mamba_d_head"]
    zudt = h @ w["in_proj"]
    z, u, dt = zudt[:, :di], zudt[:, di:di + conv_dim(cfg)], zudt[:, -Hm:]
    u = silu(conv(u, w["conv_w"], w["conv_b"]))
    x, B, C = u[:, :di].reshape(n, Hm, P), u[:, di:di + N], u[:, di + N:]
    dt = softplus(dt + w["dt_bias"])
    A = -jnp.exp(w["A_log"])
    scan = {"recurrence": scan_recurrence, "quadratic": scan_quadratic}[form]
    y = (scan(x, dt, A, B, C) + w["D"][:, None] * x).reshape(n, di)
    eps = cfg["rms_norm_eps"]
    if variant == "norm_before_gate":
        y = rms_norm(y, w["norm"], eps) * silu(z)
    else:
        y = rms_norm(y * silu(z), w["norm"], eps)
    return y @ w["out_proj"]


def attention_mixer(h, w, cfg: dict, variant: str | None = None):
    """Grouped-query attention on the normed states of one history: the
    key/value heads REPEATED, each group's times, then plain causal
    attention a head (``HEAD_BLOCK`` heads at a time, so that the [n, n]
    scores fit); no position enters."""
    import jax
    import jax.numpy as jnp

    n = h.shape[0]
    H, KV = cfg["num_attention_heads"], cfg["num_key_value_heads"]
    hd = cfg["hidden_size"] // H
    q = (h @ w["wq"]).reshape(n, H, hd)
    k = jnp.repeat((h @ w["wk"]).reshape(n, KV, hd), H // KV, axis=1)
    v = jnp.repeat((h @ w["wv"]).reshape(n, KV, hd), H // KV, axis=1)
    scale = (hd ** -0.5 if variant == "sqrt_scale"
             else cfg["attention_multiplier"])
    causal = jnp.tril(jnp.ones((n, n), bool))

    def some_heads(qkv):
        q_b, k_b, v_b = qkv                                   # [b, n, hd]
        s = jnp.einsum("hid,hjd->hij", q_b, k_b) * scale
        s = jnp.where(causal[None], s, -jnp.inf)
        return jnp.einsum("hij,hjd->hid", jax.nn.softmax(s, axis=-1), v_b)

    b = min(HEAD_BLOCK, H)
    blocks = [x.transpose(1, 0, 2).reshape(H // b, b, n, hd)
              for x in (q, k, v)]
    o = jax.lax.map(some_heads, tuple(blocks))                # [H/b, b, n, hd]
    return o.reshape(H, n, hd).transpose(1, 0, 2).reshape(n, H * hd) @ w["wo"]


def layer_forward(x, w, cfg: dict, kind: str, form: str = "recurrence",
                  variant: str | None = None):
    """One layer on one history, x [n, hidden] float32."""
    eps, res = cfg["rms_norm_eps"], cfg["residual_multiplier"]
    h = rms_norm(x, w["input_norm"], eps)
    mixed = (attention_mixer(h, w, cfg, variant) if kind == "attention"
             else mamba_mixer(h, w, cfg, form, variant))
    x = x + res * mixed
    F = cfg["shared_intermediate_size"]
    ab = rms_norm(x, w["post_norm"], eps) @ w["w_in"]
    return x + res * ((silu(ab[:, :F]) * ab[:, F:]) @ w["w_out"])


def forward(embedded, layer_of, norm_f, cfg: dict, *,
            form: str = "recurrence", variant: str | None = None):
    """The whole forward of one history. ``embedded`` [n, hidden]: the
    embedding's rows of its events, NOT yet multiplied;
    ``layer_of(l)``: layer l's float32 weights. Returns the states
    [n, hidden] after the final norm."""
    import jax
    import jax.numpy as jnp

    with jax.default_matmul_precision("highest"):
        x = jnp.asarray(embedded, jnp.float32) * cfg["embedding_multiplier"]
        for l, kind in enumerate(cfg["layer_types"]):
            x = layer_forward(x, layer_of(l), cfg, kind, form, variant)
        return rms_norm(x, norm_f, cfg["rms_norm_eps"])


def scores(h_last, item_rows, cfg: dict):
    """Logits of one state against the tied embedding's rows
    [rows, hidden]."""
    import jax
    import jax.numpy as jnp

    with jax.default_matmul_precision("highest"):
        return (jnp.asarray(item_rows, jnp.float32)
                @ jnp.asarray(h_last, jnp.float32)) / cfg["logits_scaling"]


def stacked_layer_of(params: dict, cfg: dict):
    """``layer_of`` over the program's PUBLIC tree, whose layers are
    stacked by kind in their published order (host arrays or traced)."""
    import jax.numpy as jnp

    place, seen = [], {"mamba": 0, "attention": 0}
    for kind in cfg["layer_types"]:
        place.append((kind, seen[kind]))
        seen[kind] += 1

    def layer_of(l):
        kind, i = place[l]
        w = {**{k: v[i] for k, v in params[kind].items()},
             **{k: v[l] for k, v in params["mlp"].items()}}
        return {k: jnp.asarray(v, jnp.float32) for k, v in w.items()}

    return layer_of


def next_item_scores(params: dict, cfg: dict, history, *,
                     form: str = "recurrence",
                     variant: str | None = None) -> np.ndarray:
    """Float32 logits [rows of the table] for the item after ``history``
    (ids as stored: item i is i + 1), from a whole public parameter
    tree."""
    import jax.numpy as jnp

    history = np.asarray(history, np.int64)
    table = np.asarray(params["embed"]).astype(np.float32)
    h = forward(table[history], stacked_layer_of(params, cfg),
                jnp.asarray(params["norm_f"], jnp.float32), cfg, form=form,
                variant=variant)
    return np.asarray(scores(h[-1], table, cfg))


def loss(params: dict, cfg: dict, rows) -> "jax.Array":
    """Mean cross-entropy of the next item over the real events of
    left-padded histories (a Python loop over the rows, each alone):
    what the program's trainer minimises. Differentiable in ``params``
    (the public tree, float32 jax arrays)."""
    import jax
    import jax.numpy as jnp

    total, count = 0.0, 0
    layer_of = stacked_layer_of(params, cfg)
    for row in np.asarray(rows):
        events = row[row > 0]
        if len(events) < 2:
            continue
        h = forward(params["embed"][events[:-1]], layer_of,
                    params["norm_f"], cfg)
        with jax.default_matmul_precision("highest"):
            logits = h @ params["embed"].T / cfg["logits_scaling"]
        logp = logits - jax.scipy.special.logsumexp(logits, -1, keepdims=True)
        total = total - jnp.sum(logp[jnp.arange(len(events) - 1), events[1:]])
        count += len(events) - 1
    return total / max(count, 1)


def expected_counts(step_lengths, cfg: dict) -> dict:
    """What the device's counters must read for steps that packed
    histories of these lengths, in order (a list of lists): a chunk of
    ``mamba_chunk_size`` a Mamba layer wherever a real token lies, a
    reset wherever a history starts off a chunk's first token, a causal
    triangle a history an attention layer."""
    Q = cfg["mamba_chunk_size"]
    n_mamba = sum(1 for t in cfg["layer_types"] if t == "mamba")
    n_attention = len(cfg["layer_types"]) - n_mamba
    chunks = resets = pairs = 0
    for lengths in step_lengths:
        lengths = np.asarray(lengths, np.int64)
        starts = np.cumsum(lengths) - lengths
        chunks += -(-int(lengths.sum()) // Q)
        resets += int((starts % Q != 0).sum())
        pairs += int((lengths * (lengths + 1) // 2).sum())
    return {"ssmChunks": chunks * n_mamba,
            "ssmResetsInChunk": resets * n_mamba,
            "pairsCausal": pairs * n_attention}
