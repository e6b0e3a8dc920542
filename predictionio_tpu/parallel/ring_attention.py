"""Sequence/context parallelism: ring attention + Ulysses all-to-all.

Long-sequence scaling is first-class in this framework even though the
reference has no sequence models at all (SURVEY.md §5 "long-context":
its longest sequence is an event iterator folded into a PropertyMap,
reference data/.../storage/LEventAggregator.scala:68-110). The TPU-native
sequence path shards user event histories over a ``seq`` mesh axis so
attention over arbitrarily long histories never materializes the full
[L, L] score matrix on one chip:

- **Ring attention** (`ring_attention`): K/V blocks rotate around the ring
  via ``ppermute`` while each device keeps its Q block; softmax is
  accumulated flash-style (running max + denominator), so memory per chip
  is O(L_local^2) and the K/V transfer overlaps with the block matmul.
  Communication = (n-1) ppermute hops of the local K/V block over ICI.
- **Ulysses** (`ulysses_attention`): ``all_to_all`` reshards seq->heads,
  runs exact local attention per head group over the *full* sequence, and
  reshards back. Communication = 2 all_to_alls; best when heads >= axis.

Both are exact (not approximations) and match single-device attention to
float tolerance; see tests/test_parallel_seq.py.
"""

from __future__ import annotations

from functools import partial

__all__ = [
    "blockwise_attention",
    "flash_attention",
    "ring_attention",
    "segment_attention",
    "ring_self_attention",
    "ulysses_attention",
]

_NEG = -1e30


def _merge_carry(m, acc, l, bm, pv, bl):  # noqa: E741 - l is the flash sum
    """Fold one block's (bm, pv, bl) into the running flash-softmax carry
    (m, acc, l): rescale both sides to the new running max, guarding
    never-touched rows (m = _NEG) against exp(_NEG - _NEG) = 1. Shared by
    the ring and blockwise loops so their numerics cannot diverge."""
    import jax.numpy as jnp

    m_new = jnp.maximum(m, bm)
    alpha = jnp.exp(jnp.where(m > _NEG / 2, m - m_new, 0.0))
    beta = jnp.exp(jnp.where(bm > _NEG / 2, bm - m_new, 0.0))
    acc = acc * alpha[..., None] + pv * beta[..., None]
    return m_new, acc, l * alpha + bl * beta


def _block_attn_bhld(qt, k_blk, v_blk, scale, mask, mm_dtype):
    """One [Lq, Lk] score block in [B, H, L, D] layout -> (scores_max,
    exp-weights @ v, exp-sum): m [B, H, Lq], pv [B, H, Lq, D] f32,
    l [B, H, Lq] f32. Matmuls stay in ``mm_dtype`` with f32 accumulation
    (``preferred_element_type``); the softmax pieces are f32 — the
    formulation shared with ``blockwise_attention``."""
    import jax.numpy as jnp

    f32 = jnp.float32
    s = jnp.einsum("bhld,bhsd->bhls", qt, k_blk,
                   preferred_element_type=f32) * scale
    if mask is not None:
        s = jnp.where(mask[None, None], s, _NEG)
    m = s.max(-1)  # [B, H, Lq]
    # fully-masked rows: keep them at exp(_NEG) ≈ 0, not exp(0)
    p = jnp.exp(s - jnp.where(m > _NEG / 2, m, 0.0)[..., None])
    l = p.sum(-1)  # noqa: E741
    pv = jnp.einsum("bhls,bhsd->bhld", p.astype(mm_dtype), v_blk,
                    preferred_element_type=f32)
    return m, pv, l


def ring_attention(q, k, v, axis_name: str = "seq", *, causal: bool = False):
    """Exact attention with Q resident and K/V ring-rotating over
    ``axis_name``. Must run inside shard_map (or pmap) with the sequence
    dimension sharded over ``axis_name``.

    q, k, v: [B, L_local, H, D] per-device blocks of a global [B, L, H, D].
    Causal masking uses *global* positions: device p's Q block covers
    positions [p*L_local, (p+1)*L_local). Internally runs in [B, H, L, D]
    layout with input-dtype matmuls and f32 carries (the tuned
    formulation of ``blockwise_attention``); returns q.dtype.
    """
    import jax
    import jax.numpy as jnp

    B, Lq, H, D = q.shape
    Lk = k.shape[1]
    f32 = jnp.float32
    scale = 1.0 / (D**0.5)
    n = jax.lax.psum(1, axis_name)
    p_idx = jax.lax.axis_index(axis_name)
    perm = [(i, (i + 1) % n) for i in range(n)]

    mm_dtype = q.dtype if q.dtype == jnp.bfloat16 else f32
    qt = jnp.transpose(q, (0, 2, 1, 3)).astype(mm_dtype)
    kt = jnp.transpose(k, (0, 2, 1, 3)).astype(mm_dtype)
    vt = jnp.transpose(v, (0, 2, 1, 3)).astype(mm_dtype)
    q_pos = p_idx * Lq + jnp.arange(Lq)  # global positions of our queries

    def body(i, carry):
        k_blk, v_blk, m, acc, l = carry  # noqa: E741
        # the block we hold at step i originated on device (p_idx - i) mod n
        src = (p_idx - i) % n
        if causal:
            k_pos = src * Lk + jnp.arange(Lk)
            mask = k_pos[None, :] <= q_pos[:, None]
        else:
            mask = None
        bm, bpv, bl = _block_attn_bhld(qt, k_blk, v_blk, scale, mask,
                                       mm_dtype)
        m_new, acc, l = _merge_carry(m, acc, l, bm, bpv, bl)  # noqa: E741
        k_blk = jax.lax.ppermute(k_blk, axis_name, perm)
        v_blk = jax.lax.ppermute(v_blk, axis_name, perm)
        return k_blk, v_blk, m_new, acc, l

    m0 = jnp.full((B, H, Lq), _NEG, f32)
    acc0 = jnp.zeros((B, H, Lq, D), f32)
    l0 = jnp.zeros((B, H, Lq), f32)
    _, _, _, acc, l = jax.lax.fori_loop(  # noqa: E741
        0, n, body, (kt, vt, m0, acc0, l0)
    )
    out = acc / jnp.maximum(l, 1e-20)[..., None]
    return jnp.transpose(out, (0, 2, 1, 3)).astype(q.dtype)


def blockwise_attention(q, k, v, *, causal: bool = False, block_size: int = 1024):
    """Single-device flash-style blockwise attention over K/V chunks —
    the n=1 degenerate case of the ring, used when no ``seq`` axis exists.
    q, k, v: [B, L, H, D]; returns q.dtype.

    Internally runs in [B, H, L, D] layout so each block's two einsums are
    pure batched matmuls with no relayout inside the loop, matmuls stay in
    the input dtype with f32 accumulation (``preferred_element_type``),
    and the softmax carries (max / denominator / accumulator) are f32.
    Against the stock Pallas flash kernel, which ``flash_attention``
    prefers where its shape test admits: not measured on the chip."""
    import jax
    import jax.numpy as jnp

    B, L, H, D = q.shape
    f32 = jnp.float32
    scale = 1.0 / (D**0.5)
    bs = min(block_size, L)
    nblk = (L + bs - 1) // bs
    L_pad = nblk * bs
    mm_dtype = q.dtype if q.dtype == jnp.bfloat16 else f32
    qt = jnp.transpose(q, (0, 2, 1, 3)).astype(mm_dtype)
    kt = jnp.transpose(k, (0, 2, 1, 3)).astype(mm_dtype)
    vt = jnp.transpose(v, (0, 2, 1, 3)).astype(mm_dtype)
    if L_pad != L:
        # pad K/V to whole blocks; padded keys are masked out below
        pad = [(0, 0), (0, 0), (0, L_pad - L), (0, 0)]
        kt = jnp.pad(kt, pad)
        vt = jnp.pad(vt, pad)
    q_pos = jnp.arange(L)
    kr = kt.reshape(B, H, nblk, bs, D)
    vr = vt.reshape(B, H, nblk, bs, D)

    def body(i, carry):
        m, acc, l = carry  # noqa: E741
        k_blk = jax.lax.dynamic_index_in_dim(kr, i, 2, keepdims=False)
        v_blk = jax.lax.dynamic_index_in_dim(vr, i, 2, keepdims=False)
        k_pos = i * bs + jnp.arange(bs)
        mask = None
        if L_pad != L:
            mask = jnp.broadcast_to((k_pos < L)[None, :], (L, bs))
        if causal:
            cm = k_pos[None, :] <= q_pos[:, None]
            mask = cm if mask is None else mask & cm
        bm, pv, bl = _block_attn_bhld(qt, k_blk, v_blk, scale, mask,
                                      mm_dtype)
        return _merge_carry(m, acc, l, bm, pv, bl)

    m0 = jnp.full((B, H, L), _NEG, f32)
    acc0 = jnp.zeros((B, H, L, D), f32)
    l0 = jnp.zeros((B, H, L), f32)
    _, acc, l = jax.lax.fori_loop(0, nblk, body, (m0, acc0, l0))  # noqa: E741
    out = acc / jnp.maximum(l, 1e-20)[..., None]
    return jnp.transpose(out, (0, 2, 1, 3)).astype(q.dtype)


def segment_attention(q, k, v, segment_ids, *, causal: bool = False):
    """Plain XLA attention for [B, L, H, D] in which a position sees only
    positions of its own segment (``segment_ids`` int [B, L]): the
    [L, L] scores are materialized, so this is for the sizes that run
    off the TPU (the flash kernel takes the same ids there)."""
    import jax
    import jax.numpy as jnp

    B, L, H, D = q.shape
    f32 = jnp.float32
    mm_dtype = q.dtype if q.dtype == jnp.bfloat16 else f32
    prec = None if mm_dtype == jnp.bfloat16 else jax.lax.Precision.HIGHEST
    s = jnp.einsum("blhd,bshd->bhls", q.astype(mm_dtype), k.astype(mm_dtype),
                   preferred_element_type=f32, precision=prec) / (D ** 0.5)
    mask = segment_ids[:, :, None] == segment_ids[:, None, :]
    if causal:
        pos = jnp.arange(L)
        mask = mask & (pos[None, :] <= pos[:, None])[None]
    s = jnp.where(mask[:, None], s, _NEG)
    p = jax.nn.softmax(s, axis=-1)
    out = jnp.einsum("bhls,bshd->blhd", p.astype(mm_dtype),
                     v.astype(mm_dtype), preferred_element_type=f32,
                     precision=prec)
    return out.astype(q.dtype)


def flash_attention(q, k, v, *, causal: bool = False, block_size: int = 1024,
                    segment_ids=None):
    """Best-available single-device attention for [B, L, H, D]: the stock
    Pallas TPU flash kernel (jax.experimental.pallas.ops.tpu) when on TPU
    and the shape fits its tiling, else ``blockwise_attention``. The
    Pallas kernel fuses the whole softmax-accumulate into one Mosaic
    program (against blockwise: not measured on the chip); NOTE its
    ``sm_scale`` defaults to 1.0, so the 1/sqrt(D) scale must be passed
    explicitly. ``segment_ids`` (int [B, L]) keeps attention inside a
    segment: histories packed one after another in a row; off the TPU
    that is ``segment_attention``."""
    import jax

    B, L, H, D = q.shape
    if jax.default_backend() == "tpu" and L % 128 == 0 and D in (64, 128):
        return _stock_flash(q, k, v, causal, segment_ids)
    if segment_ids is not None:
        return segment_attention(q, k, v, segment_ids, causal=causal)
    return blockwise_attention(q, k, v, causal=causal, block_size=block_size)


#: Rows and columns of one grid step of the stock kernel where the stream
#: is long enough (its default, 128, makes 256 steps a head at 2,048).
_FLASH_BLOCK = 512


def _stock_flash(q, k, v, causal: bool, segment_ids=None):
    """The stock Pallas kernel on [B, L, H, D], whatever the backend (so
    that tests/test_tpu_compile.py compiles what a TPU runs): the shape
    test in ``flash_attention`` decides which kernel runs; a compile or
    run-time error of the chosen kernel is an error, not a reason to
    change kernels behind the caller's back."""
    import jax.numpy as jnp
    from jax.experimental.pallas.ops.tpu.flash_attention import (
        BlockSizes, SegmentIds, flash_attention as _pallas_flash)

    L, D = q.shape[1], q.shape[3]
    sizes = None
    if L % _FLASH_BLOCK == 0:
        sizes = BlockSizes(block_q=_FLASH_BLOCK, block_k_major=_FLASH_BLOCK,
                           block_k=_FLASH_BLOCK, block_b=1)
    seg = (None if segment_ids is None
           else SegmentIds(q=segment_ids, kv=segment_ids))
    qt = jnp.transpose(q, (0, 2, 1, 3))
    kt = jnp.transpose(k, (0, 2, 1, 3))
    vt = jnp.transpose(v, (0, 2, 1, 3))
    out = _pallas_flash(qt, kt, vt, segment_ids=seg, causal=causal,
                        sm_scale=1.0 / (D**0.5), block_sizes=sizes)
    return jnp.transpose(out, (0, 2, 1, 3)).astype(q.dtype)


def ring_self_attention(mesh, q, k, v, *, causal: bool = False,
                        seq_axis: str = "seq", batch_axis: str | None = "data"):
    """Top-level entry: shard [B, L, H, D] arrays over (batch, seq) mesh
    axes and run ring attention. Returns the output with the same
    sharding. Falls back to blockwise single-device attention when the
    mesh lacks ``seq_axis``."""
    import jax
    from jax.sharding import NamedSharding, PartitionSpec as P

    if seq_axis not in mesh.shape or mesh.shape[seq_axis] == 1:
        # no sequence axis: the tuned single-device path (Pallas on TPU)
        return flash_attention(q, k, v, causal=causal)
    b_ax = batch_axis if (batch_axis and batch_axis in mesh.shape) else None
    spec = P(b_ax, seq_axis, None, None)
    fn = jax.shard_map(
        partial(ring_attention, axis_name=seq_axis, causal=causal),
        mesh=mesh, in_specs=(spec, spec, spec), out_specs=spec,
        check_vma=False,
    )
    # with_sharding_constraint works both eagerly and under jit traces,
    # so the same code path serves the deploy server and compiled train steps
    sh = NamedSharding(mesh, spec)
    q, k, v = (jax.lax.with_sharding_constraint(x, sh) for x in (q, k, v))
    return fn(q, k, v)


def ulysses_attention(q, k, v, axis_name: str = "seq", *, causal: bool = False):
    """All-to-all sequence parallelism (DeepSpeed-Ulysses style): reshard
    seq-sharded [B, L_local, H, D] into head-sharded [B, L, H/n, D], run
    exact attention on the full sequence locally, reshard back. Must run
    inside shard_map with seq dim sharded over ``axis_name``; H must be
    divisible by the axis size."""
    import jax

    n = jax.lax.psum(1, axis_name)
    H = q.shape[2]
    if H % n:
        raise ValueError(
            f"ulysses_attention needs heads ({H}) divisible by the "
            f"'{axis_name}' axis size ({n})"
        )

    def seq_to_heads(x):
        # [B, Ll, H, D] -> [B, Ll*n, H/n, D]: split heads across devices,
        # gather sequence. all_to_all(split_axis=heads, concat_axis=seq).
        return jax.lax.all_to_all(x, axis_name, split_axis=2, concat_axis=1,
                                  tiled=True)

    def heads_to_seq(x):
        return jax.lax.all_to_all(x, axis_name, split_axis=1, concat_axis=2,
                                  tiled=True)

    qh, kh, vh = seq_to_heads(q), seq_to_heads(k), seq_to_heads(v)
    # local attention over the FULL sequence via the tuned flash-style
    # path — the naive [B, H/n, L, L] logits tensor this replaces is
    # exactly the long-context memory wall sequence parallelism exists
    # to break (L=16k f32 would be ~8.6 GB per 8 local heads)
    out = blockwise_attention(qh, kh, vh, causal=causal)
    return heads_to_seq(out.astype(q.dtype))
