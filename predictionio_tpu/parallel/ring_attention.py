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
    "segment_flash_attention",
    "attention_kernel_for",
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


#: Longest stream the plain path (``segment_attention``, which builds the
#: whole [B, H, L, L] scores) may take ON the TPU for a shape no kernel
#: there admits (an unaligned L): 64 heads at this length are 0.27 GB of
#: float32 scores. Off the TPU it takes any length (tests, CPU drives).
PLAIN_MAX_L = 1024


def attention_kernel_for(L: int, d_qk: int, d_v: int, *, backend: str,
                         segmented: bool, grouped: bool = False) -> str:
    """Which kernel ``flash_attention`` runs for a shape, by name:
    ``stock`` (the stock Pallas kernel: on the TPU, L a multiple of 128,
    q, k and v sharing a head size of 64 or 128), ``segment_flash``
    (this module's kernel: on the TPU, L a multiple of 128, any other
    head sizes, d_qk != d_v among them), ``plain`` (``segment_attention``
    / ``blockwise_attention``: off the TPU, or on it up to
    ``PLAIN_MAX_L``). A shape none admits on the TPU raises: the plain
    path at a long L would build gigabytes of scores behind the caller's
    back.

    ``grouped`` (fewer key/value heads than query heads, each shared by
    a group of them) is ``segment_flash`` whatever the head size: the
    stock kernel takes one key/value head a query head, so the shared
    heads would be written out once a query head first (4 x the keys and
    values of every layer at 32 over 8), where ``segment_flash``'s index
    map reads query head ``h``'s keys from head ``h // group`` in place;
    it also takes the caller's ``scale`` (the stock path fixes
    ``1 / sqrt(D)``) and counts the pairs its mask let through."""
    if backend != "tpu":
        return "plain"
    if L % 128 == 0:
        if grouped:
            return "segment_flash"
        return "stock" if d_qk == d_v and d_v in (64, 128) else "segment_flash"
    if L <= PLAIN_MAX_L and (segmented or d_qk == d_v):
        return "plain"
    raise ValueError(
        f"no attention kernel on the TPU for L={L}, d_qk={d_qk}, d_v={d_v}: "
        f"pad the stream to a multiple of 128 (the plain path builds "
        f"[B, H, L, L] and is held to L <= {PLAIN_MAX_L})")


def flash_attention(q, k, v, *, causal: bool = False, block_size: int = 1024,
                    segment_ids=None):
    """Best-available single-device attention for q, k [B, L, H, D_qk]
    and v [B, L, H, D_v]; ``attention_kernel_for`` names the choice and
    is the one place it is made. The stock Pallas kernel fuses the whole
    softmax-accumulate into one Mosaic program (against blockwise: not
    measured on the chip); NOTE its ``sm_scale`` defaults to 1.0, so the
    1/sqrt(D) scale must be passed explicitly. ``segment_ids`` (int
    [B, L]) keeps attention inside a segment: histories packed one after
    another in a row. Head sizes the stock kernel does not take (not 64
    or 128, or d_qk != d_v) run ``segment_flash_attention`` on the TPU."""
    import jax
    import jax.numpy as jnp

    B, L, H, D = q.shape
    Dv = v.shape[-1]
    which = attention_kernel_for(L, D, Dv, backend=jax.default_backend(),
                                 segmented=segment_ids is not None)
    if which == "stock":
        return _stock_flash(q, k, v, causal, segment_ids)
    if which == "segment_flash":
        seg = (jnp.ones((B, L), jnp.int32) if segment_ids is None
               else segment_ids)
        t = lambda x: jnp.transpose(x, (0, 2, 1, 3))  # noqa: E731
        out, _pairs = segment_flash_attention(
            _lane_parts(t(q)), _lane_parts(t(k)), t(v), seg,
            scale=1.0 / (D ** 0.5), causal=causal)
        return t(out).astype(q.dtype)
    if segment_ids is not None or D != Dv:
        seg = (jnp.ones((B, L), jnp.int32) if segment_ids is None
               else segment_ids)
        return segment_attention(q, k, v, seg, causal=causal)
    return blockwise_attention(q, k, v, causal=causal, block_size=block_size)


def _lane_parts(x):
    """[B, H, L, D] as the parts a kernel contracts one by one: whole
    128-lane groups, then what is left (192 -> 128 + 64)."""
    D = x.shape[-1]
    whole = D // 128 * 128
    if whole in (0, D):
        return (x,)
    return (x[..., :whole], x[..., whole:])


#: Rows and columns of one grid step of ``segment_flash_attention``.
SEGMENT_FLASH_BLOCK = 512


def _block_pairs(segment_ids, block: int, causal: bool):
    """The (query block, key block) pairs whose segments can meet, in
    query-major order, as the tables the kernel's index maps read:
    (q_of, k_of, first, last int32 [B, S], count int32 [B]); S is the
    static most (every pair of the triangle). Two blocks can meet where
    their ranges of segment ids overlap (and, causal, the key block is
    not after the query block): exact for ids that never decrease along
    the stream (packed histories, the padding's 0 last or first), only
    a superset otherwise, which the mask inside the kernel settles."""
    import jax.numpy as jnp

    B, L = segment_ids.shape
    n = L // block
    blocks = segment_ids.reshape(B, n, block)
    lo, hi = blocks.min(-1), blocks.max(-1)                       # [B, n]
    meet = (hi[:, None, :] >= lo[:, :, None]) & (lo[:, None, :]
                                                 <= hi[:, :, None])
    if causal:
        meet &= jnp.tril(jnp.ones((n, n), bool))[None]
    size = n * (n + 1) // 2 if causal else n * n
    flat = meet.reshape(B, n * n)
    count = flat.sum(-1).astype(jnp.int32)
    # the positions of the pairs that run, ascending: stable sort of
    # "does not run" keeps the running ones first, in order
    order = jnp.argsort(~flat, axis=-1, stable=True)[:, :size].astype(
        jnp.int32)
    valid = jnp.arange(size)[None, :] < count[:, None]
    last_valid = jnp.take_along_axis(
        order, jnp.maximum(count - 1, 0)[:, None], axis=-1)
    order = jnp.where(valid, order, last_valid)  # no new block to fetch
    q_of, k_of = order // n, order % n
    prev_q = jnp.concatenate([jnp.full((B, 1), -1, jnp.int32),
                              q_of[:, :-1]], -1)
    next_q = jnp.concatenate([q_of[:, 1:],
                              jnp.full((B, 1), -1, jnp.int32)], -1)
    step = jnp.arange(size)[None, :]
    first = valid & (q_of != prev_q)
    last = valid & ((q_of != next_q) | (step == count[:, None] - 1))
    return (q_of, k_of, first.astype(jnp.int32), last.astype(jnp.int32),
            valid.astype(jnp.int32), count)


def _segment_flash_kernel(q_of, k_of, first, last, valid, *refs, n_parts,
                          scale, causal, block):
    """One (query block, key block) pair of one head: the flash-softmax
    carry (m, l, acc in VMEM scratch) over the pairs of a query block,
    which the tables list one after another. refs: q parts, k parts, v,
    the query block's segment ids [block, 1], the key block's [1, block];
    then o, the count of unmasked pairs (SMEM, head 0 alone counts: the
    mask is every head's); then the scratch."""
    import jax
    import jax.numpy as jnp
    from jax.experimental import pallas as pl

    q_refs, k_refs = refs[:n_parts], refs[n_parts:2 * n_parts]
    v_ref, qseg_ref, kseg_ref, o_ref, cnt_ref, m_s, l_s, acc_s = refs[
        2 * n_parts:]
    b, h, s = pl.program_id(0), pl.program_id(1), pl.program_id(2)
    f32 = jnp.float32

    @pl.when((b == 0) & (h == 0) & (s == 0))
    def _zero_count():
        cnt_ref[0] = 0

    @pl.when(first[b, s] == 1)
    def _start():
        m_s[...] = jnp.full(m_s.shape, _NEG, f32)
        l_s[...] = jnp.zeros(l_s.shape, f32)
        acc_s[...] = jnp.zeros(acc_s.shape, f32)

    @pl.when(valid[b, s] == 1)
    def _pair():
        sc = None
        for q_ref, k_ref in zip(q_refs, k_refs):
            part = jax.lax.dot_general(
                q_ref[0, 0], k_ref[0, 0], (((1,), (1,)), ((), ())),
                preferred_element_type=f32)
            sc = part if sc is None else sc + part
        sc = sc * scale
        mask = qseg_ref[0] == kseg_ref[0]                 # [block, block]
        if causal:
            rows = q_of[b, s] * block + jax.lax.broadcasted_iota(
                jnp.int32, (block, block), 0)
            cols = k_of[b, s] * block + jax.lax.broadcasted_iota(
                jnp.int32, (block, block), 1)
            mask &= cols <= rows
        sc = jnp.where(mask, sc, _NEG)
        m_prev = m_s[...]
        m_new = jnp.maximum(m_prev, sc.max(axis=1, keepdims=True))
        alpha = jnp.exp(m_prev - m_new)
        # a row with nothing unmasked yet keeps m = _NEG and gathers
        # exp(0) here; its own diagonal, which every row has and which
        # comes last, multiplies that away (alpha = exp(_NEG - m) = 0)
        p = jnp.exp(sc - m_new)
        l_s[...] = alpha * l_s[...] + p.sum(axis=1, keepdims=True)
        acc_s[...] = acc_s[...] * alpha + jax.lax.dot_general(
            p.astype(v_ref.dtype), v_ref[0, 0], (((1,), (0,)), ((), ())),
            preferred_element_type=f32)
        m_s[...] = m_new

        @pl.when(h == 0)
        def _count():
            cnt_ref[0] += jnp.sum(mask.astype(jnp.int32))

    @pl.when(last[b, s] == 1)
    def _store():
        o_ref[0, 0] = (acc_s[...] / l_s[...]).astype(o_ref.dtype)


def segment_flash_attention(q_parts, k_parts, v, segment_ids, *, scale,
                            causal: bool = True,
                            block: int = SEGMENT_FLASH_BLOCK,
                            interpret: bool | None = None):
    """Flash attention in which the scores are a SUM of contractions and
    the value's head size is its own: q_parts[i] [B, H, L, d_i] against
    k_parts[i] [B, H or 1, L, d_i] (a part with ONE key head is shared
    by every query head: latent attention's rotary key; a part, and v,
    with G heads where G divides H is grouped-query attention: query head
    h reads key/value head h // (H / G)), v [B, H or G, L, d_v],
    ``segment_ids`` int32 [B, L]. A position sees the positions of
    its own segment (causal: those not after it). Only the block pairs
    whose segments can meet are grid steps at all (``_block_pairs``): a
    stream of packed histories costs its histories' triangles, not the
    stream's. Returns (out [B, H, L, d_v] in v's dtype, the count of
    unmasked (query, key) pairs, int32: what the kernel's own mask let
    through, counted once, not once a head).

    L must be a multiple of 128; the block is the largest of ``block``,
    256, 128 that divides it. ``interpret`` defaults to "not on a TPU"."""
    import functools

    import jax
    import jax.numpy as jnp
    from jax.experimental import pallas as pl
    from jax.experimental.pallas import tpu as pltpu

    B, _, L, d_v = v.shape
    H = q_parts[0].shape[1]
    if L % 128:
        raise ValueError(f"segment_flash_attention needs L % 128 == 0, "
                         f"got {L}")
    for x in (*k_parts, v):
        if H % x.shape[1]:
            raise ValueError(f"{x.shape[1]} key/value heads do not divide "
                             f"the {H} query heads")
    block = next(b for b in (block, 256, 128) if b <= block and L % b == 0)
    if interpret is None:
        interpret = jax.default_backend() != "tpu"
    seg = segment_ids.astype(jnp.int32)
    q_of, k_of, first, last, valid, count = _block_pairs(seg, block, causal)
    steps = count.max()
    n_parts = len(q_parts)

    def q_map(b, h, s, q_of, k_of, *_):
        return b, h, q_of[b, s], 0

    def k_map(heads):
        def index(b, h, s, q_of, k_of, *_):
            if heads in (1, H):
                return b, (0 if heads == 1 else h), k_of[b, s], 0
            return b, h // (H // heads), k_of[b, s], 0
        return index

    in_specs = [pl.BlockSpec((1, 1, block, x.shape[-1]), q_map)
                for x in q_parts]
    in_specs += [pl.BlockSpec((1, 1, block, x.shape[-1]),
                              k_map(x.shape[1])) for x in k_parts]
    in_specs += [
        pl.BlockSpec((1, 1, block, d_v), k_map(v.shape[1])),
        pl.BlockSpec((1, block, 1),
                     lambda b, h, s, q_of, k_of, *_: (b, q_of[b, s], 0)),
        pl.BlockSpec((1, 1, block),
                     lambda b, h, s, q_of, k_of, *_: (b, 0, k_of[b, s])),
    ]
    kernel = functools.partial(_segment_flash_kernel, n_parts=n_parts,
                               scale=float(scale), causal=causal,
                               block=block)
    out, pairs = pl.pallas_call(
        kernel,
        grid_spec=pltpu.PrefetchScalarGridSpec(
            num_scalar_prefetch=5,
            in_specs=in_specs,
            out_specs=[
                pl.BlockSpec((1, 1, block, d_v), q_map),
                pl.BlockSpec(memory_space=pltpu.SMEM),
            ],
            grid=(B, H, steps),
            scratch_shapes=[pltpu.VMEM((block, 1), jnp.float32),
                            pltpu.VMEM((block, 1), jnp.float32),
                            pltpu.VMEM((block, d_v), jnp.float32)],
        ),
        out_shape=[jax.ShapeDtypeStruct((B, H, L, d_v), v.dtype),
                   jax.ShapeDtypeStruct((1,), jnp.int32)],
        compiler_params=pltpu.CompilerParams(
            dimension_semantics=("arbitrary", "arbitrary", "arbitrary")),
        interpret=interpret,
        name="segment_flash_attention",
    )(q_of, k_of, first, last, valid, *q_parts, *k_parts, v,
      seg[:, :, None], seg[:, None, :])
    return out, pairs[0]


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
