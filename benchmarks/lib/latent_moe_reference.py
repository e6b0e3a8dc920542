"""Plain reference of the latent-attention mixture-of-experts decoder
(predictionio_tpu/models/latent_moe_lm.py): the published forward in
straightforward ``jax.numpy``, float32, under
``jax.default_matmul_precision("highest")``, ONE history at a time, a
Python loop over the layers, a plain loop (``lax.scan``, so that one body
compiles and not twelve) over the heads' blocks and over the held
experts, every token through every held expert; no kernel, no packing,
no sort, no grouped matmul, nothing of the program imported.
The program's tier-1 tests and the benchmark's check child load this one
file.

Source: https://huggingface.co/skt/A.X-K1/blob/main/config.json
(``model_type: axk1``; the DeepSeek-V2/V3 family's block). What the
config gives: every size, ``hidden_act: silu``, ``rms_norm_eps``,
``rope_theta`` and the YaRN block, ``first_k_dense_replace``,
``n_routed_experts``, ``n_shared_experts``, ``num_experts_per_tok``,
``norm_topk_prob``, ``routed_scaling_factor``, ``scoring_func: sigmoid``,
``topk_method: none``, untied embedding and head. Readings the config
leaves open (each under ``assumed`` in
benchmarks/configs/axk1-seqrec.json):

- ``topk_method: "none"`` is plain top-k over all experts: ``n_group`` /
  ``topk_group`` unused, no correction bias;
- the softmax scale is ``d_qk^-0.5 * m^2`` with ``m = 0.1 *
  mscale_all_dim * ln(factor) + 1``, and the rotary tables' own factor is
  ``yarn_mscale(factor, mscale) / yarn_mscale(factor, mscale_all_dim)``
  (1 here): the DeepSeek-V2/V3 modelling code's convention;
- RoPE in the rotate-half convention over the 64 rotary dimensions;
- no biases anywhere.

The SHARE. ``cfg["first_expert"]`` / ``cfg["experts_held"]`` name the
block of each routed layer's experts whose weights ``w`` holds; every
token is routed over all ``n_routed_experts`` and only the held experts'
part of the result is added, beside the shared expert (which every share
computes alike). With ``experts_held`` = ``n_routed_experts`` this is the
uncut model.

Departures from the published model: the vocabulary is an item table
whose row 0 is a pad id that no history holds; positions count a
history's own events from 0; a query is one full forward over its
history (no cache, no decode step).

``variant`` breaks the forward on purpose, for the tests that show each
break to fail: ``no_shared`` (the shared expert left out), ``top7``
(one expert a token fewer), ``no_yarn`` (plain RoPE and no mscale).
"""

from __future__ import annotations

import math

import numpy as np

#: the published config's keys the forward reads, and the share's
CONFIG_KEYS = (
    "hidden_size", "intermediate_size", "moe_intermediate_size",
    "num_attention_heads", "q_lora_rank", "kv_lora_rank",
    "qk_nope_head_dim", "qk_rope_head_dim", "v_head_dim",
    "n_routed_experts", "n_shared_experts", "num_experts_per_tok",
    "first_k_dense_replace", "norm_topk_prob", "routed_scaling_factor",
    "rms_norm_eps", "rope_theta", "rope_scaling",
    "first_layer", "num_hidden_layers", "first_expert", "experts_held")
ATTENTION_MATRICES = ("wq_a", "wq_b", "wkv_a", "wkv_b", "wo")
DENSE_MATRICES = ("w_gate", "w_up", "w_down")
MOE_MATRICES = ("experts_gate", "experts_up", "experts_down",
                "shared_gate", "shared_up", "shared_down")
NORMS = ("input_norm", "q_norm", "kv_norm", "post_norm")
#: heads whose [n, n] scores are alive at once (8 x 8,192^2 x 4 B = 2.1 GB)
HEAD_BLOCK = 8


def is_dense(cfg: dict, i: int) -> bool:
    return cfg["first_layer"] + i < cfg["first_k_dense_replace"]


def layer_shapes(cfg: dict, i: int) -> dict:
    """Shapes of held layer ``i``'s weights, matrices as [in, out]."""
    D, H = cfg["hidden_size"], cfg["num_attention_heads"]
    dn, dr, dv = (cfg["qk_nope_head_dim"], cfg["qk_rope_head_dim"],
                  cfg["v_head_dim"])
    out = {"input_norm": (D,), "q_norm": (cfg["q_lora_rank"],),
           "kv_norm": (cfg["kv_lora_rank"],), "post_norm": (D,),
           "wq_a": (D, cfg["q_lora_rank"]),
           "wq_b": (cfg["q_lora_rank"], H * (dn + dr)),
           "wkv_a": (D, cfg["kv_lora_rank"] + dr),
           "wkv_b": (cfg["kv_lora_rank"], H * (dn + dv)),
           "wo": (H * dv, D)}
    if is_dense(cfg, i):
        F = cfg["intermediate_size"]
        out.update(w_gate=(D, F), w_up=(D, F), w_down=(F, D))
        return out
    F, E = cfg["moe_intermediate_size"], cfg["experts_held"]
    S = F * cfg["n_shared_experts"]
    out.update(router=(D, cfg["n_routed_experts"]),
               experts_gate=(E, D, F), experts_up=(E, D, F),
               experts_down=(E, F, D),
               shared_gate=(D, S), shared_up=(D, S), shared_down=(S, D))
    return out


def rms_norm(x, gain, eps):
    import jax.numpy as jnp

    return x / jnp.sqrt(jnp.mean(x * x, axis=-1, keepdims=True) + eps) * gain


def yarn_mscale(factor, mscale):
    return 1.0 if factor <= 1 else 0.1 * mscale * math.log(factor) + 1.0


def yarn_inv_freq(dim: int, theta: float, scaling) -> np.ndarray:
    """Rotary frequencies [dim / 2]; YaRN's blend of the plain and the
    interpolated ones where ``scaling`` is given (DeepSeek-V2's
    ``yarn_find_correction_range`` and ``yarn_linear_ramp_mask``)."""
    plain = 1.0 / theta ** (np.arange(0, dim, 2, dtype=np.float64) / dim)
    if not scaling:
        return plain
    factor = scaling["factor"]
    orig = scaling["original_max_position_embeddings"]

    def correction_dim(rotations):
        return (dim * math.log(orig / (rotations * 2 * math.pi))
                / (2 * math.log(theta)))

    low = max(math.floor(correction_dim(scaling["beta_fast"])), 0)
    high = min(math.ceil(correction_dim(scaling["beta_slow"])), dim - 1)
    if low == high:
        high += 0.001
    ramp = np.clip((np.arange(dim // 2, dtype=np.float64) - low)
                   / (high - low), 0, 1)
    mask = 1.0 - ramp
    return plain / factor * (1 - mask) + plain * mask


def rope(x, inv_freq, own_scale):
    """x [n, ..., d], position = row index; rotate-half."""
    import jax.numpy as jnp

    n, half = x.shape[0], x.shape[-1] // 2
    ang = (np.arange(n, dtype=np.float64)[:, None]
           * np.asarray(inv_freq, np.float64)[None, :])
    cos = jnp.asarray(np.concatenate([np.cos(ang), np.cos(ang)], -1)
                      * own_scale, jnp.float32)
    sin = jnp.asarray(np.concatenate([np.sin(ang), np.sin(ang)], -1)
                      * own_scale, jnp.float32)
    while cos.ndim < x.ndim:
        cos, sin = cos[:, None], sin[:, None]
    rotated = jnp.concatenate([-x[..., half:], x[..., :half]], axis=-1)
    return x * cos + rotated * sin


def swiglu(x, gate, up, down):
    import jax

    return (jax.nn.silu(x @ gate) * (x @ up)) @ down


def attention(x, w, cfg: dict, variant: str | None = None):
    """x + the latent attention of one history, x [n, hidden]."""
    import jax
    import jax.numpy as jnp

    n = x.shape[0]
    H = cfg["num_attention_heads"]
    dn, dr, dv = (cfg["qk_nope_head_dim"], cfg["qk_rope_head_dim"],
                  cfg["v_head_dim"])
    eps = cfg["rms_norm_eps"]
    scaling = None if variant == "no_yarn" else cfg["rope_scaling"]
    inv = yarn_inv_freq(dr, cfg["rope_theta"], scaling)
    own, scale = 1.0, (dn + dr) ** -0.5
    if scaling:
        m_all = yarn_mscale(scaling["factor"], scaling["mscale_all_dim"])
        own = yarn_mscale(scaling["factor"], scaling["mscale"]) / m_all
        scale *= m_all * m_all
    h = rms_norm(x, w["input_norm"], eps)
    c_q = rms_norm(h @ w["wq_a"], w["q_norm"], eps)
    q = (c_q @ w["wq_b"]).reshape(n, H, dn + dr)
    q_nope, q_rope = q[..., :dn], rope(q[..., dn:], inv, own)
    kv = h @ w["wkv_a"]
    c_kv = rms_norm(kv[:, :cfg["kv_lora_rank"]], w["kv_norm"], eps)
    k_rope = rope(kv[:, cfg["kv_lora_rank"]:], inv, own)       # one head
    kv_up = (c_kv @ w["wkv_b"]).reshape(n, H, dn + dv)
    k_nope, v = kv_up[..., :dn], kv_up[..., dn:]
    causal = jnp.arange(n)[None, :] <= jnp.arange(n)[:, None]

    def heads_block(_, block):      # a few heads' [n, n] scores at a time
        qn, qr, kn, vb = block      # [n, HEAD_BLOCK, .]
        s = (jnp.einsum("qhd,khd->hqk", qn, kn)
             + jnp.einsum("qhd,kd->hqk", qr, k_rope)) * scale
        p = jax.nn.softmax(jnp.where(causal[None], s, -jnp.inf), axis=-1)
        return None, jnp.einsum("hqk,khd->qhd", p, vb)

    def blocks(t):                  # [n, H, d] -> [H / block, n, block, d]
        return t.reshape(n, -1, min(HEAD_BLOCK, H), t.shape[-1]
                         ).transpose(1, 0, 2, 3)

    # a loop over the blocks of heads (`lax.scan`: one body to compile,
    # not one a block)
    _, outs = jax.lax.scan(heads_block, None, (
        blocks(q_nope), blocks(q_rope), blocks(k_nope), blocks(v)))
    attended = outs.transpose(1, 0, 2, 3).reshape(n, H * dv)
    return x + attended @ w["wo"]


def route(h, w, cfg: dict, variant: str | None = None):
    """(experts chosen [n, k], their weights [n, k]) over ALL experts."""
    import jax
    import jax.numpy as jnp

    k = cfg["num_experts_per_tok"] - (1 if variant == "top7" else 0)
    gate = jax.nn.sigmoid(h @ w["router"])
    top, chosen = jax.lax.top_k(gate, k)
    weight = top * cfg["routed_scaling_factor"]
    if cfg["norm_topk_prob"]:
        weight = weight / jnp.sum(top, axis=-1, keepdims=True)
    return chosen, weight


def held_experts_part(h, w, cfg: dict, chosen, weight):
    """What the held experts add, expert by expert: every token times
    every held expert, weighted by the token's weight for it (0 where it
    did not choose it)."""
    import jax
    import jax.numpy as jnp

    def add_expert(out, expert):
        e, gate, up, down = expert
        mine = jnp.sum(jnp.where(chosen == e, weight, 0.0), axis=-1)
        return out + mine[:, None] * swiglu(h, gate, up, down), None

    # a loop over the held experts (`lax.scan`: one body to compile)
    held = cfg["first_expert"] + jnp.arange(cfg["experts_held"])
    out, _ = jax.lax.scan(add_expert, jnp.zeros_like(h), (
        held, w["experts_gate"], w["experts_up"], w["experts_down"]))
    return out


def layer_forward(x, w, cfg: dict, i: int, variant: str | None = None):
    """(held layer ``i`` on one history, x [n, hidden] float32; the
    (token, choice) pairs that fell on held experts, by held expert)."""
    import jax.numpy as jnp

    x = attention(x, w, cfg, variant)
    h = rms_norm(x, w["post_norm"], cfg["rms_norm_eps"])
    if is_dense(cfg, i):
        return x + swiglu(h, w["w_gate"], w["w_up"], w["w_down"]), None
    chosen, weight = route(h, w, cfg, variant)
    x = x + held_experts_part(h, w, cfg, chosen, weight)
    if variant != "no_shared":
        x = x + swiglu(h, w["shared_gate"], w["shared_up"], w["shared_down"])
    local = chosen - cfg["first_expert"]
    load = jnp.sum((local[..., None] == jnp.arange(cfg["experts_held"])),
                   axis=(0, 1))
    return x, load


def forward(embedded, layer_of, norm_f, cfg: dict, *, layer=layer_forward,
            variant: str | None = None):
    """The whole forward of one history. ``embedded`` [n, hidden]: the
    embedding rows of its events; ``layer_of(i)``: held layer i's float32
    weights. Returns (states [n, hidden] after the final norm, load
    int [routed layers, experts_held])."""
    import jax
    import jax.numpy as jnp

    with jax.default_matmul_precision("highest"):
        x = jnp.asarray(embedded, jnp.float32)
        loads = []
        for i in range(cfg["num_hidden_layers"]):
            x, load = layer(x, layer_of(i), cfg, i, variant)
            if load is not None:
                loads.append(load)
        return rms_norm(x, norm_f, cfg["rms_norm_eps"]), loads


def scores(h_last, head):
    """Logits of one state against the output head [rows, hidden]."""
    import jax
    import jax.numpy as jnp

    with jax.default_matmul_precision("highest"):
        return jnp.asarray(head, jnp.float32) @ jnp.asarray(h_last,
                                                            jnp.float32)


def next_item_scores(params: dict, cfg: dict, history,
                     variant: str | None = None) -> np.ndarray:
    """Float32 logits [rows of the head] for the item after ``history``
    (ids as stored: item i is i + 1), from a whole public parameter
    tree."""
    import jax.numpy as jnp

    history = np.asarray(history, np.int64)
    emb = np.asarray(params["embed"])[history].astype(np.float32)

    def layer_of(i):
        return {k: jnp.asarray(np.asarray(v), jnp.float32)
                for k, v in params["layers"][str(i)].items()}

    h, _loads = forward(emb, layer_of,
                        jnp.asarray(params["norm_f"], jnp.float32), cfg,
                        variant=variant)
    return np.asarray(scores(h[-1], np.asarray(params["head"])))


def expected_counts(lengths, cfg: dict) -> dict:
    """What the device's exact counters must read for histories of these
    lengths: 8 router assignments a real token a routed layer, and a
    causal triangle a history a layer."""
    lengths = np.asarray(lengths, np.int64)
    routed = sum(1 for i in range(cfg["num_hidden_layers"])
                 if not is_dense(cfg, i))
    return {"routerAssignments": int(lengths.sum()) * routed
            * cfg["num_experts_per_tok"],
            "pairsCausal": int((lengths * (lengths + 1) // 2).sum())
            * cfg["num_hidden_layers"]}
