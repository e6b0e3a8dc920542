"""Latent-attention mixture-of-experts decoder over item ids (the
DeepSeek-V2/V3 family's block, as A.X-K1 publishes it): multi-head
LATENT attention (queries and keys/values through low-rank bottlenecks,
one rotary key shared by every head, YaRN-scaled RoPE), a leading dense
SwiGLU layer, then layers of ROUTED experts beside a shared one.

Configuration keys are the published ``config.json``'s own, so a user's
``engine.json`` is the config they copy, plus what says which part of a
deployment THIS process holds:

- ``first_layer`` / ``num_hidden_layers``: the published index of the
  first layer held here and how many follow it (a pipeline stage; layer
  ``i`` of the model is dense while ``i < first_k_dense_replace``);
- ``first_expert`` / ``experts_held``: the contiguous block of each
  routed layer's ``n_routed_experts`` that lives here (expert
  parallelism). The router keeps its published width and its
  ``num_experts_per_tok``: every token is routed over ALL experts, and
  this process computes the part of the result that ITS experts give,
  for the tokens routed to them, whatever the load (dropless: no
  capacity factor, no token dropped). What the absent experts would have
  added is left out and the partial result goes on to the next layer;
  nothing stands in for the absent chips or for their exchange.

One layer (pre-norm; ``h = RMSNorm(x)``):

    c_q = RMSNorm(h W_qa)                       [q_lora_rank]
    q_nope | q_rope = c_q W_qb                  heads of 128 + 64
    c_kv | k_r = h W_kva;  c_kv = RMSNorm(c_kv) [kv_lora_rank] + 64
    k_nope | v = c_kv W_kvb                     heads of 128 + 128
    q_rope, k_r = RoPE_YaRN(.; position among the history's own events)
    s = (q_nope . k_nope + q_rope . k_r) * d_qk^-0.5 * m^2,
        m = 0.1 mscale_all_dim ln(factor) + 1   (causal, inside a history)
    x += concat_h(softmax(s) v) W_o
    dense layer:  x += SwiGLU(RMSNorm(x))
    routed layer: g = sigmoid(h W_r) over n_routed_experts, float32; the
        num_experts_per_tok largest; w = g_sel / sum(g_sel) *
        routed_scaling_factor; x += sum_e w_e SwiGLU_e(h) + SwiGLU_shared(h)

then the final RMSNorm and the scores of the last event's state against
the untied head. Weights and matmul inputs are ``compute_dtype``
(bfloat16 as published); the residual stream, accumulation, norms,
softmax, router and scores float32.

The forward takes ONE packed token stream [T] with segment ids and
positions (histories one after another; padding is segment 0 and is
never routed). Attention is ``segment_flash_attention``
(parallel/ring_attention.py: d_qk 192, d_v 128, only the block pairs
whose histories meet). The routed experts sort the (token, choice)
pairs that fall on the held experts by expert and run grouped matmuls
over them, ``EXPERT_CHUNK_ROWS`` sorted rows at a time, as many chunks
as the load needs, each row then added, weighted, to its token's state. The device program counts what it did (router
assignments, those that fell here, the fullest expert's, the causal
pairs the attention kernel's mask let through) and hands the counts
out through the serving pipeline's encoder seam.
"""

from __future__ import annotations

import dataclasses
import functools
import math
from typing import Any

import numpy as np

from ..storage.bimap import BiMap
from .seq_common import rms_norm as _rms, rows_to_stream
from .seq_serving import SequenceServingMixin

__all__ = [
    "COUNTERS",
    "EXPERT_CHUNK_ROWS",
    "LatentMoEConfig",
    "LatentMoEEncoder",
    "LatentMoEModel",
    "STEP_TOKEN_BUDGET",
    "device_tree",
    "forward_hidden",
    "init_params",
    "param_shapes",
    "train_latent_moe",
    "yarn_inv_freq",
    "yarn_mscale",
]

#: Most tokens one serving step holds. A step reads every held weight
#: once (6.4 GB at the published widths: 8 ms at the v5e's 819 GB/s) and
#: a token costs about 2.8 GFLOP, so the step is MXU-bound from a few
#: hundred tokens on; what the budget buys is rows for the experts: of a
#: full step a held expert sees about budget x 8 / 192 rows, 341 at
#: 8,192, and a grouped matmul under 256 rows is bound by its weights'
#: bytes. It is also the longest history a query may bring (``max_len``
#: at most this).
STEP_TOKEN_BUDGET = 8192

#: Sorted (token, choice) rows one pass of the routed experts takes. A
#: full step sends about tokens / 2 rows here when routing is balanced
#: (8 choices over 192 experts, 12 held), so one pass is the rule; a
#: skewed router makes more passes, never a dropped token.
EXPERT_CHUNK_ROWS = 8192

#: Rows, contraction and columns of one grouped-matmul tile on the TPU.
_GMM_TILING = (256, 1024, 1024)

#: What the device program counts, in the order it hands them out.
COUNTERS = ("routerAssignments", "expertAssignmentsHere",
            "expertAssignmentsFullest", "pairsCausal")

_YARN_DEFAULT = {"type": "yarn", "factor": 32,
                 "original_max_position_embeddings": 4096, "beta_fast": 32,
                 "beta_slow": 1, "mscale": 1, "mscale_all_dim": 1}


@dataclasses.dataclass(frozen=True)
class LatentMoEConfig:
    # the published keys (A.X-K1's values)
    hidden_size: int = 7168
    intermediate_size: int = 18432
    moe_intermediate_size: int = 2048
    num_attention_heads: int = 64
    q_lora_rank: int = 1536
    kv_lora_rank: int = 512
    qk_nope_head_dim: int = 128
    qk_rope_head_dim: int = 64
    v_head_dim: int = 128
    n_routed_experts: int = 192
    n_shared_experts: int = 1
    num_experts_per_tok: int = 8
    first_k_dense_replace: int = 1
    norm_topk_prob: bool = True
    routed_scaling_factor: float = 2.5
    scoring_func: str = "sigmoid"
    topk_method: str = "none"
    rms_norm_eps: float = 1e-6
    rope_theta: float = 10000.0
    rope_scaling: Any = dataclasses.field(
        default_factory=lambda: dict(_YARN_DEFAULT))
    # which part of the deployment this process holds
    first_layer: int = 0
    num_hidden_layers: int = 5
    first_expert: int = 0
    experts_held: int = 12
    # serving
    max_len: int = 8192
    exclude_seen: bool = False
    compute_dtype: str = "bfloat16"
    # training (test sizes; the published widths are served, not trained)
    epochs: int = 10
    batch_size: int = 16
    lr: float = 1e-3
    seed: int = 0

    def __post_init__(self):
        if self.scoring_func != "sigmoid" or self.topk_method != "none":
            raise ValueError(
                "latent_moe routes by sigmoid scores with no group limit "
                "(scoring_func 'sigmoid', topk_method 'none'); got "
                f"{self.scoring_func!r}, {self.topk_method!r}")
        if not (0 <= self.first_expert and self.experts_held >= 1
                and self.first_expert + self.experts_held
                <= self.n_routed_experts):
            raise ValueError(
                f"experts {self.first_expert}..+{self.experts_held} are not "
                f"a block of the router's {self.n_routed_experts}")
        if self.num_experts_per_tok > self.n_routed_experts:
            raise ValueError("num_experts_per_tok over n_routed_experts")
        if self.max_len > STEP_TOKEN_BUDGET:
            raise ValueError(f"max_len {self.max_len} is over a serving "
                             f"step's {STEP_TOKEN_BUDGET} tokens")
        if self.exclude_seen and self.max_len > 512:
            raise ValueError(
                "exclude_seen over-fetches num + the history's distinct "
                "items from the head's top-k, which keeps at most 528: "
                "set max_len <= 512 or exclude_seen false")

    @property
    def qk_head_dim(self) -> int:
        return self.qk_nope_head_dim + self.qk_rope_head_dim

    def is_dense(self, i: int) -> bool:
        """Whether held layer ``i`` (published index ``first_layer + i``)
        is one of the model's leading dense layers."""
        return self.first_layer + i < self.first_k_dense_replace

    @property
    def softmax_scale(self) -> float:
        scale = self.qk_head_dim ** -0.5
        rs = self.rope_scaling
        if rs:
            scale *= yarn_mscale(rs["factor"], rs.get("mscale_all_dim", 0)) ** 2
        return scale


# -- parameters ---------------------------------------------------------------

_NORMS = ("input_norm", "q_norm", "kv_norm", "post_norm")


def layer_shapes(cfg: LatentMoEConfig, i: int) -> dict:
    """The public shapes of held layer ``i``: matrices as [in, out]."""
    D, H = cfg.hidden_size, cfg.num_attention_heads
    out = {
        "input_norm": (D,), "q_norm": (cfg.q_lora_rank,),
        "kv_norm": (cfg.kv_lora_rank,), "post_norm": (D,),
        "wq_a": (D, cfg.q_lora_rank),
        "wq_b": (cfg.q_lora_rank, H * cfg.qk_head_dim),
        "wkv_a": (D, cfg.kv_lora_rank + cfg.qk_rope_head_dim),
        "wkv_b": (cfg.kv_lora_rank,
                  H * (cfg.qk_nope_head_dim + cfg.v_head_dim)),
        "wo": (H * cfg.v_head_dim, D),
    }
    if cfg.is_dense(i):
        F = cfg.intermediate_size
        out.update(w_gate=(D, F), w_up=(D, F), w_down=(F, D))
        return out
    F, E = cfg.moe_intermediate_size, cfg.experts_held
    S = cfg.moe_intermediate_size * cfg.n_shared_experts
    out.update(router=(D, cfg.n_routed_experts),
               experts_gate=(E, D, F), experts_up=(E, D, F),
               experts_down=(E, F, D),
               shared_gate=(D, S), shared_up=(D, S), shared_down=(S, D))
    return out


def param_shapes(cfg: LatentMoEConfig, vocab: int) -> dict:
    D = cfg.hidden_size
    return {"embed": (vocab, D), "head": (vocab, D), "norm_f": (D,),
            "layers": {str(i): layer_shapes(cfg, i)
                       for i in range(cfg.num_hidden_layers)}}


def _is_float32_leaf(name: str) -> bool:
    """Gains and the router stay float32 in every tree."""
    return name in _NORMS or name in ("norm_f", "router")


def init_params(cfg: LatentMoEConfig, vocab: int, seed: int = 0) -> dict:
    """Matrices iid normal at 0.02, gains 1; float32 numpy on the host."""
    rng = np.random.default_rng([seed, 0x1A7E])

    def leaf(name, shape):
        if name in _NORMS or name == "norm_f":
            return np.ones(shape, np.float32)
        return rng.standard_normal(shape, dtype=np.float32) * np.float32(0.02)

    shapes = param_shapes(cfg, vocab)
    return {"embed": leaf("embed", shapes["embed"]),
            "head": leaf("head", shapes["head"]),
            "norm_f": leaf("norm_f", shapes["norm_f"]),
            "layers": {i: {k: leaf(k, s) for k, s in layer.items()}
                       for i, layer in shapes["layers"].items()}}


def _stored(params: dict, cd) -> dict:
    """The tree with its matrices and tables in ``compute_dtype`` (no
    copy where they are already), gains and routers float32."""
    def leaf(name, x):
        want = np.float32 if _is_float32_leaf(name) else cd
        return np.asarray(x).astype(want, copy=False)

    out = {k: leaf(k, v) for k, v in params.items() if k != "layers"}
    out["layers"] = {i: {k: leaf(k, v) for k, v in layer.items()}
                     for i, layer in params["layers"].items()}
    return out


def device_layer(layer: dict, cfg: LatentMoEConfig) -> dict:
    """One layer's tree as ``forward_hidden`` reads it: the attention's
    up-projections and ``wo`` head-major and split where the kernel
    takes them apart ([H, head, in]: each is sliced inside the matmul
    that reads it, and the kernel's [H, T, head] operands come out of
    the contraction as they are: PERF.md, PR 35), the rest as it is.
    numpy or jax arrays, under a trace or not."""
    H, dn, dr, dv = (cfg.num_attention_heads, cfg.qk_nope_head_dim,
                     cfg.qk_rope_head_dim, cfg.v_head_dim)
    out = {k: v for k, v in layer.items()
           if k not in ("wq_b", "wkv_b", "wo")}
    wq = layer["wq_b"].reshape(cfg.q_lora_rank, H, dn + dr)
    out["wq_nope"] = wq[:, :, :dn].transpose(1, 2, 0)
    out["wq_rope"] = wq[:, :, dn:].transpose(1, 2, 0)
    wkv = layer["wkv_b"].reshape(cfg.kv_lora_rank, H, dn + dv)
    out["wk_nope"] = wkv[:, :, :dn].transpose(1, 2, 0)
    out["wv"] = wkv[:, :, dn:].transpose(1, 2, 0)
    out["wo"] = layer["wo"].reshape(H, dv, cfg.hidden_size)
    return out


def device_tree(params: dict, cfg: LatentMoEConfig) -> dict:
    """The whole tree in ``device_layer``'s layout (the head left out:
    it is the retriever's catalog)."""
    out = {k: v for k, v in params.items() if k not in ("layers", "head")}
    out["layers"] = {i: device_layer(layer, cfg)
                     for i, layer in params["layers"].items()}
    return out


# -- YaRN ---------------------------------------------------------------------

def yarn_mscale(factor: float, mscale: float) -> float:
    return 1.0 if factor <= 1 else 0.1 * mscale * math.log(factor) + 1.0


def yarn_inv_freq(dim: int, theta: float, scaling: dict | None) -> np.ndarray:
    """The rotary frequencies [dim / 2], float32. With YaRN: the
    interpolated frequencies (over ``factor``) where a dimension turns
    fewer than ``beta_slow`` times over the original context, the plain
    ones where it turns more than ``beta_fast`` times, a linear ramp
    between."""
    exponent = np.arange(0, dim, 2, dtype=np.float64) / dim
    plain = 1.0 / theta ** exponent
    if not scaling:
        return plain.astype(np.float32)
    factor = float(scaling["factor"])
    orig = float(scaling["original_max_position_embeddings"])

    def turns_dim(turns):
        return dim * math.log(orig / (turns * 2 * math.pi)) / (
            2 * math.log(theta))

    low = max(math.floor(turns_dim(float(scaling["beta_fast"]))), 0)
    high = min(math.ceil(turns_dim(float(scaling["beta_slow"]))), dim - 1)
    ramp = np.clip((np.arange(dim // 2, dtype=np.float64) - low)
                   / max(high - low, 1e-3), 0.0, 1.0)
    keep = 1.0 - ramp                      # 1: plain, 0: interpolated
    return (plain / factor * (1 - keep) + plain * keep).astype(np.float32)


def _rope_tables(cfg: LatentMoEConfig, pos):
    """(cos, sin) [T, qk_rope_head_dim], the rotate-half convention; with
    ``mscale`` = ``mscale_all_dim`` the tables' own factor is 1."""
    import jax.numpy as jnp

    rs = cfg.rope_scaling
    inv = jnp.asarray(yarn_inv_freq(cfg.qk_rope_head_dim, cfg.rope_theta, rs))
    own = 1.0
    if rs:
        own = (yarn_mscale(rs["factor"], rs.get("mscale", 1))
               / yarn_mscale(rs["factor"], rs.get("mscale_all_dim", 0)))
    ang = pos.astype(jnp.float32)[:, None] * inv[None, :]
    cos = jnp.concatenate([jnp.cos(ang), jnp.cos(ang)], -1) * own
    sin = jnp.concatenate([jnp.sin(ang), jnp.sin(ang)], -1) * own
    return cos, sin


def _rotate(x, cos, sin):
    import jax.numpy as jnp

    half = x.shape[-1] // 2
    rot = jnp.concatenate([-x[..., half:], x[..., :half]], -1)
    return x * cos + rot * sin


# -- the forward --------------------------------------------------------------

def _grouped_matmul(x, w, sizes, cd):
    """x [rows, in] sorted by group, w [groups, in, out], sizes int32
    [groups] -> float32 [rows, out]: rows of group g times w[g]. On the
    TPU the megablox kernel (only the tiles that hold rows are grid
    steps; rows past the groups' sum are left as they were allocated:
    the caller masks them); elsewhere XLA's ragged dot."""
    import jax
    import jax.numpy as jnp

    if jax.default_backend() == "tpu":
        from jax.experimental.pallas.ops.tpu.megablox import gmm

        tm, tk, tn = _GMM_TILING
        rows, k = x.shape
        tiling = (min(tm, rows), min(tk, k), min(tn, w.shape[2]))
        return gmm(x.astype(cd), w.astype(cd), sizes,
                   preferred_element_type=jnp.float32, tiling=tiling)
    prec = (jax.lax.Precision.HIGHEST if jnp.dtype(cd) == jnp.float32
            else None)
    return jax.lax.ragged_dot(x.astype(cd), w.astype(cd), sizes,
                              precision=prec,
                              preferred_element_type=jnp.float32)


def _routed_experts(h, real, w, cfg: LatentMoEConfig, cd, chunk_rows: int):
    """(what the HELD experts add [T, D] float32, counters int32[3]:
    router assignments, those on held experts, the fullest held
    expert's) for the normed states ``h`` [T, D]; ``real`` [T] marks the
    tokens that are no padding (padding is never routed)."""
    import jax
    import jax.numpy as jnp

    f32 = jnp.float32
    T, D = h.shape
    K, E = cfg.num_experts_per_tok, cfg.experts_held
    with jax.named_scope("pio.seq.router"):
        gate = jax.nn.sigmoid(jnp.dot(
            h.astype(f32), w["router"].astype(f32),
            precision=jax.lax.Precision.HIGHEST))
        top, chosen = jax.lax.top_k(gate, K)                       # [T, K]
        weight = top * cfg.routed_scaling_factor
        if cfg.norm_topk_prob:
            weight = weight / jnp.sum(top, -1, keepdims=True)
        local = chosen - cfg.first_expert
        here = (local >= 0) & (local < E) & real[:, None]
        key = jnp.where(here, local, E).reshape(T * K).astype(jnp.int32)
        sizes = jnp.sum(key[:, None] == jnp.arange(E, dtype=jnp.int32),
                        axis=0, dtype=jnp.int32)                   # [E]
        n_here = jnp.sum(sizes)
        # (token, choice) pairs sorted by held expert, the absent last
        order = jnp.argsort(key, stable=True).astype(jnp.int32)
        token_of = order // K
        weight_of = jnp.where(here, weight, 0.0).reshape(T * K)[order]
        ends = jnp.cumsum(sizes)
        counts = jnp.stack([jnp.sum(real) * K, n_here, jnp.max(sizes)]
                           ).astype(jnp.int32)

    C = min(int(chunk_rows), T * K)
    whole = (0, -(T * K) % C)                                # whole passes
    token_of, weight_of = jnp.pad(token_of, whole), jnp.pad(weight_of, whole)
    x = h.astype(cd)

    def one_pass(c, out):
        start = c * C
        tok = jax.lax.dynamic_slice(token_of, (start,), (C,))
        rows = x[tok]                                              # [C, D]
        upto = jnp.clip(ends - start, 0, C)
        part = jnp.diff(upto, prepend=0).astype(jnp.int32)
        with jax.named_scope("pio.seq.experts.matmul"):
            a = _grouped_matmul(rows, w["experts_gate"], part, cd)
            b = _grouped_matmul(rows, w["experts_up"], part, cd)
            y = _grouped_matmul(jax.nn.silu(a) * b, w["experts_down"],
                                part, cd)
        # each live row, weighted, is added to its token's state (the
        # rows past the held assignments are as they were allocated)
        live = jnp.arange(C, dtype=jnp.int32) < n_here - start
        scale = jax.lax.dynamic_slice(weight_of, (start,), (C,))
        y = jnp.where(live[:, None], y * scale[:, None], 0.0)
        return out.at[jnp.where(live, tok, T)].add(y, mode="drop")

    with jax.named_scope("pio.seq.experts"):
        zero = jnp.zeros((T, D), f32)
        if C >= T * K:      # one pass holds any load: no loop (training)
            out = one_pass(0, zero)
        else:
            out = jax.lax.fori_loop(0, (n_here + C - 1) // C, one_pass, zero)
    return out, counts


def forward_hidden(params: dict, cfg: LatentMoEConfig, tokens, seg, pos, *,
                   expert_chunk_rows: int = EXPERT_CHUNK_ROWS,
                   differentiable: bool = False):
    """(states [T, D] float32 after the final norm, counters int32[4] in
    ``COUNTERS``' order) of one packed token stream. tokens, seg, pos:
    int32 [T]; the events of one history share a segment id (1..; 0 is
    padding) and count 0, 1, ... within it. ``params`` is
    ``device_tree``'s layout. ``differentiable`` (training, at test
    sizes) takes the plain attention, which has a gradient and builds
    the [H, T, T] scores; serving never passes it."""
    import jax
    import jax.numpy as jnp

    from ..parallel.ring_attention import (segment_attention,
                                           segment_flash_attention)

    f32 = jnp.float32
    cd = jnp.dtype(cfg.compute_dtype)
    prec = jax.lax.Precision.HIGHEST if cd == f32 else None
    eps = cfg.rms_norm_eps
    real = seg > 0

    def mm(x, w):
        return jnp.dot(x.astype(cd), w.astype(cd), precision=prec,
                       preferred_element_type=f32)

    def heads(spec, x, w):
        return jnp.einsum(spec, x.astype(cd), w.astype(cd), precision=prec,
                          preferred_element_type=f32)

    def swiglu(x, gate, up, down):
        return mm(jax.nn.silu(mm(x, gate)) * mm(x, up), down)

    cos, sin = _rope_tables(cfg, pos)
    n_pad = jnp.sum(~real)

    def plain_attention(q_nope, q_rope, k_nope, k_rope, v):
        q = jnp.concatenate([q_nope, q_rope], -1)           # [H, T, d_qk]
        k = jnp.concatenate(
            [k_nope, jnp.broadcast_to(k_rope[None], q_rope.shape)], -1)
        # segment_attention scales by d_qk^-0.5; the rest goes into q
        q = q * (cfg.softmax_scale * cfg.qk_head_dim ** 0.5)
        o = segment_attention(
            q.astype(cd).transpose(1, 0, 2)[None],
            k.astype(cd).transpose(1, 0, 2)[None],
            v.astype(cd).transpose(1, 0, 2)[None], seg[None], causal=True)
        pairs = jnp.sum(jnp.where(real, pos + 1, 0)) + n_pad * (n_pad + 1) // 2
        return o[0].transpose(1, 0, 2), pairs.astype(jnp.int32)

    def attention(x, w):
        with jax.named_scope("pio.seq.latent_proj"):
            h = _rms(x, w["input_norm"], eps)
            c_q = _rms(mm(h, w["wq_a"]), w["q_norm"], eps)
            q_nope = heads("tc,hkc->htk", c_q, w["wq_nope"])
            q_rope = _rotate(heads("tc,hkc->htk", c_q, w["wq_rope"]),
                             cos[None], sin[None])
            kv = mm(h, w["wkv_a"])
            c_kv = _rms(kv[:, :cfg.kv_lora_rank], w["kv_norm"], eps)
            k_rope = _rotate(kv[:, cfg.kv_lora_rank:], cos, sin)
            k_nope = heads("tc,hkc->htk", c_kv, w["wk_nope"])
            v = heads("tc,hkc->htk", c_kv, w["wv"])
        with jax.named_scope("pio.seq.latent_attn"):
            if differentiable:
                o, pairs = plain_attention(q_nope, q_rope, k_nope, k_rope, v)
            else:
                o, pairs = segment_flash_attention(
                    (q_nope.astype(cd)[None], q_rope.astype(cd)[None]),
                    (k_nope.astype(cd)[None], k_rope.astype(cd)[None, None]),
                    v.astype(cd)[None], seg[None], scale=cfg.softmax_scale,
                    causal=True)
                o = o[0]
        with jax.named_scope("pio.seq.latent_proj"):
            return x + heads("htk,hkd->td", o, w["wo"]), pairs

    with jax.named_scope("pio.seq.embed"):
        x = params["embed"][tokens].astype(f32)
    counts = jnp.zeros(3, jnp.int32)
    pairs_total = jnp.int32(0)
    for i in range(cfg.num_hidden_layers):
        w = params["layers"][str(i)]
        x, pairs = attention(x, w)
        pairs_total = pairs_total + pairs
        h = _rms(x, w["post_norm"], eps)
        if cfg.is_dense(i):
            with jax.named_scope("pio.seq.dense_mlp"):
                x = x + swiglu(h, w["w_gate"], w["w_up"], w["w_down"])
            continue
        routed, c = _routed_experts(h, real, w, cfg, cd, expert_chunk_rows)
        counts = counts + c
        with jax.named_scope("pio.seq.shared_expert"):
            x = x + routed + swiglu(h, w["shared_gate"], w["shared_up"],
                                    w["shared_down"])
    # the padding's own triangle is no history's: the mask let it
    # through, the count leaves it out
    pairs_total = pairs_total - cfg.num_hidden_layers * (
        n_pad * (n_pad + 1) // 2)
    out = _rms(x, params["norm_f"], eps)
    return out, jnp.concatenate([counts, pairs_total.reshape(1)])


def encoder_program(cfg: LatentMoEConfig):
    """stream int32 [3, t_pad] (tokens, segments, positions), params ->
    (states [t_pad, D] float32, None, None, counters int32[4]): the
    function a serving step's encoder executable is compiled from."""

    def fn(stream, params):
        h, counters = forward_hidden(params, cfg, stream[0], stream[1],
                                     stream[2])
        return h, None, None, counters

    return fn


class LatentMoEEncoder:
    """The serving pipeline's encoder (ops/pipeline.py): histories PACKED
    into one stream of a lattice length. The lattice is half, three
    quarters and the whole of the budget: a pool of waiting callers
    fills a step to 6,490 of 8,192 tokens on average when cut in arrival
    order (histories log-normal around 2,048), a tenth of the steps fit
    the half and a quarter the three quarters, and the dense two thirds
    of a token's work are linear in the padded length; every point is
    one more program of unlike layers to compile at deploy (25 s each),
    so no finer."""

    dense = False
    aux_name = None
    passes = 0
    counter_names = COUNTERS

    def __init__(self, params: dict, cfg: LatentMoEConfig):
        import jax
        import jax.numpy as jnp

        self.cfg = cfg
        self.dim = cfg.hidden_size
        self.max_len = cfg.max_len
        self.budget = STEP_TOKEN_BUDGET
        self.lattice = tuple(self.budget * j // 4 for j in (2, 3, 4))
        cd = jnp.dtype(cfg.compute_dtype)
        tree = _stored({k: v for k, v in params.items() if k != "head"}, cd)
        # a layer at a time, waited for: the public copy of a layer's
        # attention matrices and their reshapes are freed before the next
        # layer goes up (looped_lm.LoopedEncoder has the reading)
        tree["layers"] = {
            i: jax.block_until_ready(
                device_layer(jax.device_put(layer), cfg))
            for i, layer in tree["layers"].items()}
        self.params = jax.block_until_ready(jax.device_put(tree))
        self.param_bytes = int(sum(
            x.nbytes for x in jax.tree_util.tree_leaves(self.params)))

    def program(self, t_pad: int):
        return encoder_program(self.cfg)


@dataclasses.dataclass
class LatentMoEModel(SequenceServingMixin):
    params: Any
    seqs: np.ndarray  # [NU, max_len] left-padded histories, 0 = pad
    user_ids: BiMap
    item_ids: BiMap
    config: LatentMoEConfig

    @property
    def catalog(self) -> np.ndarray:
        """The output head's item rows (row 0, the pad id, left out) as
        the float32 catalog the retriever scans."""
        return np.asarray(self.params["head"])[1:].astype(np.float32)

    @property
    def serving_ks(self) -> tuple[int, ...]:
        """The k's the head is compiled for: without ``exclude_seen``
        nothing is over-fetched, and ``num`` up to 16 is one program."""
        from .seq_serving import k_lattice

        return k_lattice(self.config.max_len) if self.config.exclude_seen \
            else (16,)

    def make_encoder(self) -> LatentMoEEncoder:
        return LatentMoEEncoder(self.params, self.config)

    def batch_recommend(self, users, nums, *, exclude_seen=None):
        if exclude_seen is None:
            exclude_seen = self.config.exclude_seen
        return super().batch_recommend(users, nums,
                                       exclude_seen=exclude_seen)

    def recommend_products(self, user_id, num, *, exclude_seen=None):
        return self.batch_recommend([user_id], [num],
                                    exclude_seen=exclude_seen)[0]


def train_latent_moe(seqs: np.ndarray, user_ids: BiMap, item_ids: BiMap,
                     cfg: LatentMoEConfig, mesh=None) -> LatentMoEModel:
    """Next-item prediction over left-padded histories packed into one
    stream a batch; Adam on float32 parameters, stored in
    ``compute_dtype``. The held experts are the model: a test-size job
    holds them all (``experts_held`` = ``n_routed_experts``) or trains
    the share it holds."""
    import jax
    import jax.numpy as jnp
    import optax

    del mesh  # one device: the published widths are served, not trained
    vocab = len(item_ids) + 1
    params = jax.tree_util.tree_map(
        jnp.asarray, init_params(cfg, vocab, cfg.seed))
    opt = optax.adam(cfg.lr)
    state = opt.init(params)
    width = -(-seqs.shape[1] // 128) * 128  # the kernel's blocks

    def loss_fn(p, batch):
        inp, tgt = batch[:, :-1], batch[:, 1:]
        B, L = inp.shape
        inp = jnp.pad(inp, ((0, 0), (width - L, 0)))
        tgt = jnp.pad(tgt, ((0, 0), (width - L, 0)))
        toks, seg, pos = rows_to_stream(inp)
        h, _counts = forward_hidden(
            device_tree(p, cfg), cfg, toks, seg, pos, differentiable=True,
            expert_chunk_rows=toks.shape[0] * cfg.num_experts_per_tok)
        logits = jnp.einsum("td,vd->tv", h, p["head"].astype(jnp.float32))
        flat = tgt.reshape(-1)
        mask = (flat > 0).astype(jnp.float32)
        ce = optax.softmax_cross_entropy_with_integer_labels(logits, flat)
        return (ce * mask).sum() / jnp.maximum(mask.sum(), 1.0)

    @functools.partial(jax.jit, donate_argnums=(0, 1))
    def step(p, s, batch):
        loss, g = jax.value_and_grad(loss_fn)(p, batch)
        updates, s = opt.update(g, s)
        return optax.apply_updates(p, updates), s, loss

    active = np.flatnonzero((seqs > 0).sum(axis=1) > 1)
    bs = max(1, min(cfg.batch_size, len(active)))
    rng = np.random.default_rng([cfg.seed, 0x7A11])
    for _ep in range(cfg.epochs if len(active) else 0):
        order = rng.permutation(len(active))
        for start in range(0, len(order), bs):
            idx = order[np.arange(start, start + bs) % len(order)]
            params, state, _loss = step(
                params, state, jnp.asarray(seqs[active[idx]], jnp.int32))
    host = _stored(jax.tree_util.tree_map(np.asarray, params),
                   jnp.dtype(cfg.compute_dtype))
    return LatentMoEModel(params=host, seqs=seqs, user_ids=user_ids,
                          item_ids=item_ids, config=cfg)
