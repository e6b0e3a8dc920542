"""Two-tower neural retrieval (user/item encoders) on TPU.

BASELINE.json config 5: "Two-tower neural retrieval (JAX user/item
encoders) as drop-in PAlgorithm". No counterpart exists in the reference
(it predates neural recommenders); this is the framework's native neural
model family. Design:

- Embedding + MLP towers (flax.linen), L2-normalized outputs, temperature-
  scaled in-batch sampled-softmax loss (the standard retrieval recipe).
- Data parallel over the mesh's ``data`` axis: batches are sharded, the
  loss's in-batch negatives stay within the global batch via one logits
  matmul (user_emb @ item_emb.T) — XLA all-gathers item embeddings across
  shards automatically from the sharding annotations.
- bfloat16 matmuls in the towers; float32 logits/loss.
- Serving: item embeddings precomputed once; a query is one user-tower
  forward + one [1, D] x [D, N] matmul + top-k.
"""

from __future__ import annotations

import dataclasses
import functools
from typing import Any

import numpy as np

from ..ops.retrieval import RetrievalServingMixin
from ..storage.bimap import BiMap
from ..storage.frame import Ratings

__all__ = ["TwoTowerConfig", "TwoTowerModel", "train_two_tower"]


@dataclasses.dataclass(frozen=True)
class TwoTowerConfig:
    embed_dim: int = 64
    hidden_dim: int = 128
    out_dim: int = 32
    batch_size: int = 1024
    epochs: int = 5
    lr: float = 1e-3
    temperature: float = 0.1
    #: shard the embedding TABLES' vocab rows over the mesh's ``model``
    #: axis (tensor parallel — tables too big for one chip's HBM). Same
    #: math as replicated (pinned by tests); silently replicated when the
    #: mesh has no model axis. The MLP weights stay replicated (tiny).
    model_sharded: bool = False
    seed: int = 0


def _make_towers(n_users: int, n_items: int, cfg: TwoTowerConfig):
    import flax.linen as nn
    import jax
    import jax.numpy as jnp

    class Tower(nn.Module):
        vocab: int

        @nn.compact
        def __call__(self, ids):
            x = nn.Embed(self.vocab, cfg.embed_dim,
                         embedding_init=nn.initializers.normal(0.02))(ids)
            x = x.astype(jnp.bfloat16)
            x = nn.Dense(cfg.hidden_dim, dtype=jnp.bfloat16)(x)
            x = nn.relu(x)
            x = nn.Dense(cfg.out_dim, dtype=jnp.bfloat16)(x)
            x = x.astype(jnp.float32)
            # L2 normalize with the epsilon INSIDE the rsqrt: the naive
            # x / (||x|| + eps) has a NaN gradient at x = 0 (d||x||/dx is
            # 0/0), and an all-dead-ReLU row really produces x = 0 at
            # small widths — one such row NaNs the whole batch's step
            return x * jax.lax.rsqrt(
                jnp.sum(x * x, axis=-1, keepdims=True) + 1e-12)

    return Tower(n_users), Tower(n_items)


@dataclasses.dataclass
class TwoTowerModel(RetrievalServingMixin):
    _retrieval_attr = "item_embeddings"
    _query_attr = "user_embeddings"
    user_params: Any
    item_params: Any
    user_embeddings: np.ndarray  # [NU, D] precomputed
    item_embeddings: np.ndarray  # [NI, D]
    user_ids: BiMap
    item_ids: BiMap
    config: TwoTowerConfig

    def recommend_products(self, user_id: str, num: int) -> list[tuple[str, float]]:
        row = self.user_ids.get(user_id)
        if row is None:
            return []
        return self.top_n_from_catalog(self.user_embeddings[row], num)


@dataclasses.dataclass
class TwoTowerTrainState:
    """The data-parallel training unit of ``train_two_tower``.
    ``epoch_scan(params, opt_state, u_batches, i_batches) -> (params,
    opt_state, last_loss)`` chains the train steps of one staged
    [n_batches, bs] epoch on-device in a single dispatch, so the host
    pays one dispatch per epoch instead of one per step (the
    difference is not measured on the chip)."""

    towers: tuple  # (user_tower, item_tower)
    params: Any
    opt_state: Any
    train_step: Any  # jitted (p, state, u_ids, i_ids) -> (p, state, loss)
    epoch_scan: Any  # jitted, donates (params, opt_state)
    batch_sharding: Any  # [n_batches, bs] sharding for staged epochs
    shuffle_key: Any  # the data loop's PRNG key (derived with the init keys)


def make_train_state(n_users: int, n_items: int, cfg: TwoTowerConfig,
                     mesh) -> TwoTowerTrainState:
    import jax
    import jax.numpy as jnp
    import optax
    from jax.sharding import NamedSharding, PartitionSpec as P

    model_sharded = bool(cfg.model_sharded)
    m_ax = mesh.shape.get("model", 1)
    if model_sharded and m_ax <= 1:
        import logging

        logging.getLogger("predictionio_tpu.two_tower").warning(
            "model_sharded requested but mesh %s has no 'model' axis; "
            "training with replicated tables", dict(mesh.shape))
        model_sharded = False
    # vocab rows pad up to the model axis so arbitrary catalog sizes
    # shard evenly (the padded rows are never looked up — ids stay in
    # the real range — and only real rows are read back for serving)
    pad = (lambda n: -(-n // m_ax) * m_ax) if model_sharded else (lambda n: n)
    user_tower, item_tower = _make_towers(pad(n_users), pad(n_items), cfg)
    key = jax.random.PRNGKey(cfg.seed)
    ku, ki, kshuf = jax.random.split(key, 3)
    u_params = user_tower.init(ku, jnp.zeros((2,), jnp.int32))
    i_params = item_tower.init(ki, jnp.zeros((2,), jnp.int32))
    params = {"user": u_params, "item": i_params}
    if model_sharded:
        # tensor-parallel tables: the Embed kernels' vocab rows shard
        # over `model`; everything else (tiny MLP weights) replicates.
        # Committed input shardings propagate through jit, and adam's
        # moment tensors follow their params' shardings.
        emb = NamedSharding(mesh, P("model", None))
        rep = NamedSharding(mesh, P())

        def place(path, leaf):
            is_table = any(getattr(p, "key", None) == "embedding"
                           for p in path)
            return jax.device_put(leaf, emb if is_table else rep)

        params = jax.tree_util.tree_map_with_path(place, params)
    opt = optax.adam(cfg.lr)
    opt_state = opt.init(params)

    def loss_fn(p, u_ids, i_ids):
        ue = user_tower.apply(p["user"], u_ids)  # [B, D]
        ie = item_tower.apply(p["item"], i_ids)  # [B, D]
        logits = (ue @ ie.T) / cfg.temperature  # [B, B] in-batch negatives
        labels = jnp.arange(logits.shape[0])
        # mask duplicate positives (same item appearing twice in batch)
        dup = i_ids[None, :] == i_ids[:, None]
        neg_mask = dup & (labels[None, :] != labels[:, None])
        logits = jnp.where(neg_mask, -1e9, logits)
        return optax.softmax_cross_entropy_with_integer_labels(logits, labels).mean()

    @jax.jit
    def train_step(p, state, u_ids, i_ids):
        loss, g = jax.value_and_grad(loss_fn)(p, u_ids, i_ids)
        updates, state = opt.update(g, state)
        return optax.apply_updates(p, updates), state, loss

    # donate the chained state: epoch N+1 consumes epoch N's outputs, so
    # aliasing avoids copying the full table+optimizer tree every epoch.
    # (One extra compile still happens at epoch 2 — the chained call's
    # input layouts are the first call's OUTPUT layouts; stable after.)
    @functools.partial(jax.jit, donate_argnums=(0, 1))
    def epoch_scan(p, state, u_batches, i_batches):
        def body(carry, batch):
            p, state = carry
            u_ids, i_ids = batch
            p, state, loss = train_step(p, state, u_ids, i_ids)
            return (p, state), loss

        (p, state), losses = jax.lax.scan(body, (p, state),
                                          (u_batches, i_batches))
        return p, state, losses[-1]

    return TwoTowerTrainState(
        towers=(user_tower, item_tower), params=params, opt_state=opt_state,
        train_step=train_step, epoch_scan=epoch_scan,
        batch_sharding=NamedSharding(mesh, P(None, "data")),
        shuffle_key=kshuf)


def train_two_tower(ratings: Ratings, cfg: TwoTowerConfig, mesh=None) -> TwoTowerModel:
    import jax
    import jax.numpy as jnp

    if mesh is None:
        from ..parallel.mesh import make_mesh

        mesh = make_mesh()

    nu, ni = ratings.num_users, ratings.num_items
    if nu == 0 or ni == 0:
        raise ValueError("empty ratings")
    ts = make_train_state(nu, ni, cfg, mesh)
    user_tower, item_tower = ts.towers
    params, opt_state = ts.params, ts.opt_state

    n = len(ratings)
    per = mesh.shape.get("data", 1)
    batch_sh = ts.batch_sharding
    if n < per:
        # fewer interactions than data shards: one replicated tiny batch
        from jax.sharding import NamedSharding, PartitionSpec as P

        bs = n
        batch_sh = NamedSharding(mesh, P())
    else:
        # align batch to the data axis so shards stay equal, and never
        # exceed n (a too-large bs would make the epoch reshape fail)
        bs = min(cfg.batch_size, n)
        bs = max(per, (bs // per) * per)

    n_batches = max(1, n // bs)
    losses = []
    ep_key = ts.shuffle_key
    for _ep in range(cfg.epochs):
        ep_key, k = jax.random.split(ep_key)
        order = np.asarray(jax.random.permutation(k, n))[: n_batches * bs]
        u_ep = jax.device_put(
            ratings.user_indices[order].reshape(n_batches, bs), batch_sh)
        i_ep = jax.device_put(
            ratings.item_indices[order].reshape(n_batches, bs), batch_sh)
        params, opt_state, loss = ts.epoch_scan(params, opt_state, u_ep, i_ep)
        losses.append(float(loss))

    # precompute embeddings for serving
    u_emb = np.asarray(user_tower.apply(params["user"], jnp.arange(nu)))
    i_emb = np.asarray(item_tower.apply(params["item"], jnp.arange(ni)))
    return TwoTowerModel(
        user_params=jax.tree_util.tree_map(np.asarray, params["user"]),
        item_params=jax.tree_util.tree_map(np.asarray, params["item"]),
        user_embeddings=u_emb,
        item_embeddings=i_emb,
        user_ids=ratings.user_ids,
        item_ids=ratings.item_ids,
        config=cfg,
    )
