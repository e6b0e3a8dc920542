"""Self-attentive sequential recommender (SASRec-style) with
sequence-parallel long-history support.

The reference has no sequence models (SURVEY.md §5); its closest analog is
the MarkovChain engine (reference e2/src/main/scala/io/prediction/e2/
engine/MarkovChain.scala:201-260), which predicts the next item from only
the *current* state. This model family is the TPU-native generalization:
causal self-attention over the user's full event history predicts the next
item, and histories longer than one chip's HBM are sharded over a ``seq``
mesh axis using ring attention (parallel/ring_attention.py) so the [L, L]
score matrix never materializes on a single device.

Layout: histories are LEFT-padded (pad id 0, real items 1..n_items) so the
last position always holds the newest interaction; serving scores the last
hidden state against the tied item-embedding table through the shared
route of the sequence models (models/seq_serving.py: the serving
pipeline's encoder seam and the retriever's fused top-k).
"""

from __future__ import annotations

import dataclasses
import functools
from typing import Any

import numpy as np

from ..storage.bimap import BiMap
from .seq_serving import SequenceServingMixin

__all__ = [
    "SeqRecConfig",
    "SeqRecModel",
    "build_sequences",
    "train_seq_rec",
]


@dataclasses.dataclass(frozen=True)
class SeqRecConfig:
    max_len: int = 64
    embed_dim: int = 48
    num_heads: int = 2
    num_blocks: int = 2
    batch_size: int = 256
    epochs: int = 10
    lr: float = 1e-3
    dropout: float = 0.0  # deterministic by default; serving is always det
    seq_parallel: bool = False  # ring attention over the mesh's "seq" axis
    seed: int = 0


def build_sequences(
    users: np.ndarray,
    items: np.ndarray,
    times: np.ndarray,
    *,
    max_len: int,
    user_ids: BiMap | None = None,
    item_ids: BiMap | None = None,
) -> tuple[np.ndarray, BiMap, BiMap]:
    """Per-user, time-ordered, left-padded item sequences.

    users/items: raw string ids [n]; times: float epoch seconds [n].
    Returns (seqs [NU, max_len] int32 with 0 = pad and item index i stored
    as i+1, user BiMap, item BiMap).
    """
    if user_ids is None:
        user_ids, uidx = BiMap.from_array(np.asarray(users, dtype=object))
    else:
        uidx = user_ids.map_array(list(users))
    if item_ids is None:
        item_ids, iidx = BiMap.from_array(np.asarray(items, dtype=object))
    else:
        iidx = item_ids.map_array(list(items))
    valid = (uidx >= 0) & (iidx >= 0)
    uidx, iidx, times = uidx[valid], iidx[valid], np.asarray(times)[valid]

    nu = len(user_ids)
    seqs = np.zeros((nu, max_len), dtype=np.int32)
    order = np.lexsort((times, uidx))
    uo, io = uidx[order], iidx[order]
    starts = np.searchsorted(uo, np.arange(nu))
    ends = np.searchsorted(uo, np.arange(nu), side="right")
    for u in range(nu):
        hist = io[starts[u] : ends[u]][-max_len:] + 1  # +1: 0 is pad
        if len(hist):
            seqs[u, max_len - len(hist) :] = hist
    return seqs, user_ids, item_ids


def _make_model(n_items: int, cfg: SeqRecConfig, mesh=None):
    import flax.linen as nn
    import jax.numpy as jnp

    from ..parallel.ring_attention import flash_attention, ring_self_attention

    vocab = n_items + 1  # 0 = pad
    use_ring = (
        cfg.seq_parallel
        and mesh is not None
        and "seq" in mesh.shape
        and mesh.shape["seq"] > 1
    )

    def attn(q, k, v):
        if use_ring:
            return ring_self_attention(mesh, q, k, v, causal=True)
        # Pallas flash kernel on TPU, blockwise XLA elsewhere
        return flash_attention(q, k, v, causal=True)

    class Block(nn.Module):
        @nn.compact
        def __call__(self, h):
            B, L, D = h.shape
            x = nn.LayerNorm()(h)
            qkv = nn.Dense(3 * D, dtype=jnp.bfloat16)(x)
            q, k, v = jnp.split(qkv.astype(jnp.float32), 3, axis=-1)
            hd = D // cfg.num_heads
            q = q.reshape(B, L, cfg.num_heads, hd)
            k = k.reshape(B, L, cfg.num_heads, hd)
            v = v.reshape(B, L, cfg.num_heads, hd)
            o = attn(q, k, v).reshape(B, L, D)
            h = h + nn.Dense(D, dtype=jnp.bfloat16)(o).astype(jnp.float32)
            x = nn.LayerNorm()(h)
            x = nn.Dense(2 * D, dtype=jnp.bfloat16)(x)
            x = nn.relu(x)
            h = h + nn.Dense(D, dtype=jnp.bfloat16)(x).astype(jnp.float32)
            return h

    class SeqRec(nn.Module):
        @nn.compact
        def __call__(self, seqs, hidden_only=False):  # [B, L] int32
            B, L = seqs.shape
            emb = nn.Embed(vocab, cfg.embed_dim,
                           embedding_init=nn.initializers.normal(0.02),
                           name="item_embed")
            h = emb(seqs)
            h = h + self.param(
                "pos", nn.initializers.normal(0.02), (cfg.max_len, cfg.embed_dim)
            )[None, -L:, :]
            for _ in range(cfg.num_blocks):
                h = Block()(h)
            h = nn.LayerNorm()(h)
            if hidden_only:  # serving: the retriever scores the table
                return h
            # tied weights: logits against the embedding table
            return h @ emb.embedding.T  # [B, L, vocab]

    return SeqRec()


class SeqRecEncoder:
    """The serving pipeline's encoder (ops/pipeline.py) for this model:
    every history taken whole, left pads included, as the model was
    trained (its positions are learned and counted from the right end),
    so a row costs ``max_len`` tokens and a step's stream is
    ``[rows, max_len]`` row by row."""

    dense = True
    aux_name = None
    passes = 1
    #: most rows one step takes
    STEP_ROWS = 128

    def __init__(self, params, n_items: int, cfg: SeqRecConfig):
        import jax

        self.cfg = cfg
        self.dim = cfg.embed_dim
        self.max_len = cfg.max_len
        self.budget = cfg.max_len * self.STEP_ROWS
        self.lattice = tuple(cfg.max_len * b for b in (8, 16, 32, 64, 128))
        self._model = _make_model(n_items, cfg)
        self.params = jax.block_until_ready(jax.device_put(params))
        self.param_bytes = int(sum(
            x.nbytes for x in jax.tree_util.tree_leaves(self.params)))

    def program(self, t_pad: int):
        model, L = self._model, self.max_len

        def fn(stream, params):
            h = model.apply(params, stream[0].reshape(t_pad // L, L),
                            hidden_only=True)
            return h.reshape(t_pad, -1), None, None

        return fn


@dataclasses.dataclass
class SeqRecModel(SequenceServingMixin):
    params: Any
    seqs: np.ndarray  # [NU, L] training-time histories for serving
    user_ids: BiMap
    item_ids: BiMap
    config: SeqRecConfig

    @property
    def catalog(self) -> np.ndarray:
        """The tied item-embedding table's item rows (row 0, the pad id,
        left out): what the retriever scans."""
        table = self.params["params"]["item_embed"]["embedding"]
        return np.asarray(table, np.float32)[1:]

    def make_encoder(self) -> SeqRecEncoder:
        return SeqRecEncoder(self.params, len(self.item_ids), self.config)


def train_seq_rec(
    seqs: np.ndarray,
    user_ids: BiMap,
    item_ids: BiMap,
    cfg: SeqRecConfig,
    mesh=None,
) -> SeqRecModel:
    """Next-item prediction over left-padded histories. Data parallel over
    the mesh's ``data`` axis; optionally sequence-parallel (ring attention)
    over a ``seq`` axis for histories too long for one chip."""
    import jax
    import jax.numpy as jnp
    import optax
    from jax.sharding import NamedSharding, PartitionSpec as P

    if mesh is None:
        from ..parallel.mesh import make_mesh

        mesh = make_mesh()

    n_items = len(item_ids)
    model = _make_model(n_items, cfg, mesh)
    key = jax.random.PRNGKey(cfg.seed)
    kinit, kshuf = jax.random.split(key)
    params = model.init(kinit, jnp.zeros((2, cfg.max_len), jnp.int32))
    opt = optax.adam(cfg.lr)
    opt_state = opt.init(params)

    data_sh = NamedSharding(mesh, P("data")) if "data" in mesh.shape else None

    def loss_fn(p, batch):
        inp, tgt = batch[:, :-1], batch[:, 1:]
        logits = model.apply(p, inp)  # [B, L-1, vocab]
        mask = (tgt > 0).astype(jnp.float32)
        ce = optax.softmax_cross_entropy_with_integer_labels(logits, tgt)
        return (ce * mask).sum() / jnp.maximum(mask.sum(), 1.0)

    @jax.jit
    def train_step(p, state, batch):
        loss, g = jax.value_and_grad(loss_fn)(p, batch)
        updates, state = opt.update(g, state)
        return optax.apply_updates(p, updates), state, loss

    # One device dispatch per EPOCH: shuffled batches stage as
    # [n_batches, bs, L] and a jitted lax.scan chains the train steps
    # on-device with donated state, so the host pays one dispatch per
    # epoch instead of one per step (the difference is not measured on
    # the chip).
    @functools.partial(jax.jit, donate_argnums=(0, 1))
    def epoch_scan(p, state, batches):
        def body(carry, batch):
            p, state = carry
            p, state, loss = train_step(p, state, batch)
            return (p, state), loss

        (p, state), losses = jax.lax.scan(body, (p, state), batches)
        return p, state, losses[-1]

    ep_sh = (NamedSharding(mesh, P(None, "data"))
             if "data" in mesh.shape else None)

    # drop empty histories from the training set
    active = np.nonzero((seqs > 0).any(axis=1))[0]
    n = len(active)
    per = mesh.shape.get("data", 1)
    bs = min(cfg.batch_size, max(per, n))
    bs = max(per, (bs // per) * per)
    n_batches = -(-n // bs)  # tail batch wraps so no user is dropped
    ep_key = kshuf
    for _ep in range(cfg.epochs):
        ep_key, sub = jax.random.split(ep_key)  # reshuffle every epoch
        order = np.asarray(jax.random.permutation(sub, n))
        idx = order[np.arange(n_batches * bs) % n]
        batches = seqs[active[idx]].reshape(n_batches, bs, -1)
        if ep_sh is not None:
            batches = jax.device_put(batches, ep_sh)
        params, opt_state, _loss = epoch_scan(params, opt_state, batches)

    return SeqRecModel(
        params=jax.tree_util.tree_map(np.asarray, params),
        seqs=seqs,
        user_ids=user_ids,
        item_ids=item_ids,
        config=cfg,
    )
