"""Looped decoder over item ids (LoopLM / Ouro-style): a stack of
``num_hidden_layers`` pre-and-post-normed decoder layers whose weights
are SHARED by ``total_ut_steps`` passes, an exit gate after every pass,
and an untied output head over the item table.

Configuration keys are the published ``config.json``'s own
(``hidden_size``, ``intermediate_size``, ``num_hidden_layers``,
``num_attention_heads``, ``head_dim``, ``total_ut_steps``,
``early_exit_threshold``, ``rms_norm_eps``, ``rope_theta``), so a user's
``engine.json`` is the ``config.json`` they copy. The item table stands
in for the vocabulary: row 0 is the pad id (models/seq_attention.py's
convention), item ``i`` is row ``i + 1``.

    h0 = E[x]
    layer l:  a = RMSNorm_1(h); q, k, v = a Wq, a Wk, a Wv   (no bias)
              q, k = RoPE(q, k; position = index among the real events)
              o = softmax(q k^T / sqrt(head_dim) + causal) v
              h = h + RMSNorm_2(o Wo)
              m = RMSNorm_3(h)
              h = h + RMSNorm_4((silu(m Wg) * (m Wu)) Wd)
    pass t:   h = layer_L(... layer_1(h)); h_t = RMSNorm_f(h); h = h_t
              lam_t = sigmoid(w_e . h_t + b_e)
    p_t = lam_t * prod_{j<t}(1 - lam_j) for t < T, p_T the rest; a
    position exits at the first t whose cumulated p reaches
    ``early_exit_threshold`` (the last pass at the published threshold 1).
    scores = h_exit[last position] . W_head^T               (float32)

The parameter tree holds ``num_hidden_layers`` layers, stacked
``[L, ...]``; the program runs them ``total_ut_steps`` times in ONE
``lax.scan`` of L x T iterations (one layer body compiled once, one
``while`` on the device, the pass's end under a ``cond``). Weights and
matmul inputs are ``compute_dtype`` (bfloat16 as published); the
residual stream, accumulation, norms, softmax, gate and scores are
float32.

The PUBLIC tree (``param_shapes``, ``init_params``, a model's ``params``,
the persisted blob) holds the attention projections as ``[L, D, A]`` and
``[L, A, D]``. The forward reads them head-major, ``[L, H, hd, D]``
(``head_major``): in that layout every matrix of a layer is sliced from
its stack inside the matmul that reads it, where ``[D, H x hd]`` made
the TPU stage and transpose 8 MB a projection on every layer
application (PERF.md, PR 35).

The forward takes a token STREAM ``[R, S]`` with segment ids and
positions: histories packed one after another in a row (serving: R = 1,
padding waste is what the last lattice point leaves) or one history a
row (training). Attention never crosses a segment.
"""

from __future__ import annotations

import dataclasses
import functools
from typing import Any

import numpy as np

from ..storage.bimap import BiMap
from .seq_common import rms_norm as _rms, rows_as_streams
from .seq_serving import SequenceServingMixin

__all__ = [
    "LoopedLMConfig",
    "LoopedLMModel",
    "LoopedEncoder",
    "STEP_TOKEN_BUDGET",
    "forward_hidden",
    "head_major",
    "init_params",
    "param_count",
    "train_looped_lm",
]

#: Most tokens one serving step holds. A step streams every layer's
#: weights once a pass whatever it holds (4.93 GB x 4 passes = 24 ms at
#: the v5e's 819 GB/s for the published widths), and a token costs 19.7
#: GFLOP, so below about 240 tokens a step is byte-bound and above it
#: MXU-bound: 1,024 is four times that ridge, and at the rate the chip
#: reaches a step of 1,024 tokens stays near 0.2 s, the grain at which
#: a closed loop's answers are counted (PERF.md, PR 31).
STEP_TOKEN_BUDGET = 1024

#: Smallest stream a step is padded to: at the ridge, so that a lone
#: short query costs the byte floor and nothing more.
STEP_TOKEN_MIN = 256


@dataclasses.dataclass(frozen=True)
class LoopedLMConfig:
    hidden_size: int = 2048
    intermediate_size: int = 5632
    num_hidden_layers: int = 48
    num_attention_heads: int = 16
    head_dim: int = 128
    total_ut_steps: int = 4
    early_exit_threshold: float = 1.0
    rms_norm_eps: float = 1e-6
    rope_theta: float = 1_000_000.0
    max_len: int = 512
    compute_dtype: str = "bfloat16"
    # training (test sizes; the published widths are served, not trained)
    epochs: int = 10
    batch_size: int = 64
    lr: float = 1e-3
    seed: int = 0

    @property
    def attn_dim(self) -> int:
        return self.num_attention_heads * self.head_dim


_LAYER_SHAPES = {
    "wq": ("hidden_size", "attn_dim"), "wk": ("hidden_size", "attn_dim"),
    "wv": ("hidden_size", "attn_dim"), "wo": ("attn_dim", "hidden_size"),
    "wg": ("hidden_size", "intermediate_size"),
    "wu": ("hidden_size", "intermediate_size"),
    "wd": ("intermediate_size", "hidden_size"),
}
_LAYER_NORMS = ("norm1", "norm2", "norm3", "norm4")


def param_shapes(cfg: LoopedLMConfig, vocab: int) -> dict:
    """The parameter tree's shapes: ``num_hidden_layers`` layers stacked
    on a leading axis, never ``total_ut_steps`` times that."""
    L, D = cfg.num_hidden_layers, cfg.hidden_size
    layers = {k: (L, getattr(cfg, a), getattr(cfg, b))
              for k, (a, b) in _LAYER_SHAPES.items()}
    layers.update({k: (L, D) for k in _LAYER_NORMS})
    return {"embed": (vocab, D), "head": (vocab, D), "layers": layers,
            "norm_f": (D,), "gate_w": (D,), "gate_b": ()}


def param_count(cfg: LoopedLMConfig, vocab: int) -> int:
    import jax

    return int(sum(int(np.prod(s)) for s in jax.tree_util.tree_leaves(
        param_shapes(cfg, vocab), is_leaf=lambda x: isinstance(x, tuple))))


def init_params(cfg: LoopedLMConfig, vocab: int, seed: int = 0) -> dict:
    """Matrices iid normal at 0.02 (the family's initialiser), norm gains
    1, the gate's bias 0; float32 numpy on the host."""
    rng = np.random.default_rng([seed, 0x100B])

    def normal(shape):
        return rng.standard_normal(shape, dtype=np.float32) * np.float32(0.02)

    shapes = param_shapes(cfg, vocab)
    layers = {k: (normal(s) if k in _LAYER_SHAPES else np.ones(s, np.float32))
              for k, s in shapes["layers"].items()}
    return {"embed": normal(shapes["embed"]), "head": normal(shapes["head"]),
            "layers": layers, "norm_f": np.ones(shapes["norm_f"], np.float32),
            "gate_w": normal(shapes["gate_w"]),
            "gate_b": np.zeros((), np.float32)}


def _stored(params: dict, cd) -> dict:
    """The tree with its matrices and tables in ``compute_dtype`` (no copy
    where they are already), gains and the gate float32."""
    out = dict(params)
    for name in ("embed", "head"):
        if name in out:
            out[name] = np.asarray(out[name]).astype(cd, copy=False)
    out["layers"] = {
        k: (np.asarray(v).astype(cd, copy=False) if k in _LAYER_SHAPES else v)
        for k, v in params["layers"].items()}
    return out


def head_major(layers: dict, cfg: LoopedLMConfig) -> dict:
    """The layer stacks ``forward_hidden`` reads, from the public ones
    (all of a tree's, or some): ``wq``, ``wk``, ``wv`` ``[L, D, A]`` ->
    ``[L, H, hd, D]``, a transpose; ``wo`` ``[L, A, D]`` -> ``[L, H, hd,
    D]``, a view; the rest as they are. numpy or jax arrays, under a
    trace or not."""
    H, hd = cfg.num_attention_heads, cfg.head_dim

    def one(name, w):
        if name in ("wq", "wk", "wv"):
            L, D, _A = w.shape
            return w.reshape(L, D, H, hd).transpose(0, 2, 3, 1)
        if name == "wo":
            L, _A, D = w.shape
            return w.reshape(L, H, hd, D)
        return w

    return {name: one(name, w) for name, w in layers.items()}


def _rope(x, pos, theta: float):
    """Rotary embedding, the rotate-half convention: x [R, S, H, hd]
    float32, pos [R, S]."""
    import jax.numpy as jnp

    half = x.shape[-1] // 2
    inv = theta ** (-jnp.arange(half, dtype=jnp.float32) / half)
    ang = pos.astype(jnp.float32)[..., None] * inv          # [R, S, half]
    cos = jnp.concatenate([jnp.cos(ang), jnp.cos(ang)], -1)[:, :, None, :]
    sin = jnp.concatenate([jnp.sin(ang), jnp.sin(ang)], -1)[:, :, None, :]
    rot = jnp.concatenate([-x[..., half:], x[..., :half]], -1)
    return x * cos + rot * sin


def forward_hidden(params: dict, cfg: LoopedLMConfig, tokens, seg, pos):
    """(h_exit [R, S, D] float32, half_step [R, S] int32, passes int32)
    of a token stream: the state each position exits with, the first
    pass at which its cumulated exit probability reached one half (what a
    lower threshold would do on this traffic; decides nothing), and the
    passes the loop ran, counted on the device where a pass ends.

    tokens, seg, pos: int32 [R, S]; positions of one history share a
    segment id and count 0, 1, ... within it. ``params["layers"]`` is
    ``head_major``'s tree, not the public one."""
    import jax
    import jax.numpy as jnp

    from ..parallel.ring_attention import flash_attention

    f32 = jnp.float32
    cd = jnp.dtype(cfg.compute_dtype)
    prec = jax.lax.Precision.HIGHEST if cd == f32 else None
    L, T = cfg.num_hidden_layers, cfg.total_ut_steps
    eps = cfg.rms_norm_eps
    R, S = tokens.shape

    def mm(x, w):
        return jnp.dot(x.astype(cd), w.astype(cd), precision=prec,
                       preferred_element_type=f32)

    def heads(spec, x, w):
        return jnp.einsum(spec, x.astype(cd), w.astype(cd), precision=prec,
                          preferred_element_type=f32)

    def layer(h, w):
        a = _rms(h, w["norm1"], eps)
        q = _rope(heads("rsd,hkd->rshk", a, w["wq"]), pos, cfg.rope_theta)
        k = _rope(heads("rsd,hkd->rshk", a, w["wk"]), pos, cfg.rope_theta)
        v = heads("rsd,hkd->rshk", a, w["wv"])
        o = flash_attention(q.astype(cd), k.astype(cd), v.astype(cd),
                            causal=True, segment_ids=seg)
        h = h + _rms(heads("rshk,hkd->rsd", o, w["wo"]), w["norm2"], eps)
        m = _rms(h, w["norm3"], eps)
        act = jax.nn.silu(mm(m, w["wg"])) * mm(m, w["wu"])
        return h + _rms(mm(act, w["wd"]), w["norm4"], eps)

    def end_of_pass(t, h, h_exit, cum, rest, half, ran):
        with jax.named_scope("pio.seq.gate"):
            h = _rms(h, params["norm_f"], eps)
            lam = jax.nn.sigmoid(
                jnp.sum(h * params["gate_w"].astype(f32), -1)
                + params["gate_b"].astype(f32))
            p = jnp.where(t == T - 1, rest, lam * rest)
            new_cum = jnp.where(t == T - 1, 1.0, cum + p)
            exits = (cum < cfg.early_exit_threshold) & (
                new_cum >= cfg.early_exit_threshold)
            h_exit = jnp.where(exits[..., None], h, h_exit)
            half = jnp.where((cum < 0.5) & (new_cum >= 0.5), t + 1, half)
        return h, h_exit, new_cum, rest - p, half, ran + 1

    def body(carry, i):
        h, *ends = carry
        w = jax.tree_util.tree_map(
            lambda x: jax.lax.dynamic_index_in_dim(x, i % L, 0, False),
            params["layers"])
        h = layer(h, w)
        carry = jax.lax.cond(
            i % L == L - 1,
            lambda c: end_of_pass(i // L, *c), lambda c: c, (h, *ends))
        return carry, None

    with jax.named_scope("pio.seq.embed"):
        h0 = params["embed"][tokens].astype(f32)
    zeros = jnp.zeros((R, S), f32)
    with jax.named_scope("pio.seq.loop"):
        (_h, h_exit, _cum, _rest, half, ran), _ = jax.lax.scan(
            body, (h0, jnp.zeros_like(h0), zeros, zeros + 1.0,
                   jnp.zeros((R, S), jnp.int32), jnp.int32(0)),
            jnp.arange(L * T, dtype=jnp.int32))
    return h_exit, half, ran


def encoder_program(cfg: LoopedLMConfig):
    """stream int32 [3, t_pad] (tokens, segments, positions), params ->
    (states [t_pad, D] float32, half_step int32 [t_pad], passes int32):
    the function a serving step's encoder executable is compiled from."""

    def fn(stream, params):
        h, half, ran = forward_hidden(params, cfg, stream[0][None],
                                      stream[1][None], stream[2][None])
        return h[0], half[0], ran

    return fn


class LoopedEncoder:
    """The serving pipeline's encoder (ops/pipeline.py) for a looped
    decoder: histories PACKED into one stream of a lattice length, so a
    step's cost is its tokens and its padding what the lattice leaves."""

    dense = False
    aux_name = "exitStepHistogram"

    def __init__(self, params: dict, cfg: LoopedLMConfig):
        import jax
        import jax.numpy as jnp

        self.cfg = cfg
        self.dim = cfg.hidden_size
        self.max_len = cfg.max_len
        self.budget = max(STEP_TOKEN_BUDGET, cfg.max_len)
        self.passes = cfg.total_ut_steps
        t, lattice = min(STEP_TOKEN_MIN, self.budget), []
        while t < self.budget:
            lattice.append(t)
            t *= 2
        self.lattice = tuple(lattice) + (self.budget,)
        # the head is the retriever's catalog, not the encoder's
        tree = _stored({k: v for k, v in params.items() if k != "head"},
                       jnp.dtype(cfg.compute_dtype))
        # head-major once, here, on the device, one stack at a time: the
        # wait is what frees a stack's public copy and its reshape (0.8
        # GB at the published widths) before the next stack goes up;
        # dispatched without it, three stacks' were live at once and the
        # deploy's peak read 8.4 GB for 5.6 (PERF.md, PR 35)
        tree["layers"] = {
            k: jax.block_until_ready(
                head_major({k: jax.device_put(v)}, cfg)[k])
            for k, v in tree["layers"].items()}
        self.params = jax.block_until_ready(jax.device_put(tree))
        self.param_bytes = int(sum(
            x.nbytes for x in jax.tree_util.tree_leaves(self.params)))

    def program(self, t_pad: int):
        return encoder_program(self.cfg)


@dataclasses.dataclass
class LoopedLMModel(SequenceServingMixin):
    params: Any
    seqs: np.ndarray  # [NU, max_len] left-padded histories, 0 = pad
    user_ids: BiMap
    item_ids: BiMap
    config: LoopedLMConfig

    @property
    def catalog(self) -> np.ndarray:
        """The output head's item rows (row 0, the pad id, left out) as
        the float32 catalog the retriever scans."""
        return np.asarray(self.params["head"])[1:].astype(np.float32)

    def make_encoder(self) -> LoopedEncoder:
        return LoopedEncoder(self.params, self.config)


def train_looped_lm(seqs: np.ndarray, user_ids: BiMap, item_ids: BiMap,
                    cfg: LoopedLMConfig, mesh=None) -> LoopedLMModel:
    """Next-item prediction over left-padded histories, one history a
    stream row; Adam on float32 parameters, stored in ``compute_dtype``."""
    import jax
    import jax.numpy as jnp
    import optax

    del mesh  # one device: the published widths are served, not trained
    vocab = len(item_ids) + 1
    params = jax.tree_util.tree_map(
        jnp.asarray, init_params(cfg, vocab, cfg.seed))
    opt = optax.adam(cfg.lr)
    state = opt.init(params)

    def loss_fn(p, batch):
        inp, tgt = batch[:, :-1], batch[:, 1:]
        h, _half, _ran = forward_hidden(
            {**p, "layers": head_major(p["layers"], cfg)}, cfg,
            *rows_as_streams(inp))
        logits = jnp.einsum("bld,vd->blv", h, p["head"].astype(jnp.float32))
        mask = (tgt > 0).astype(jnp.float32)
        ce = optax.softmax_cross_entropy_with_integer_labels(logits, tgt)
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
            params, state, _loss = step(params, state,
                                        jnp.asarray(seqs[active[idx]]))
    host = _stored(jax.tree_util.tree_map(np.asarray, params),
                   jnp.dtype(cfg.compute_dtype))
    return LoopedLMModel(params=host, seqs=seqs, user_ids=user_ids,
                         item_ids=item_ids, config=cfg)
