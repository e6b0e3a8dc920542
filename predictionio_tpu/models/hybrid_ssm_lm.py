"""Hybrid state-space decoder over item ids (granite-4.0-h-micro's
block, ``model_type: granitemoehybrid``): Mamba-2 layers with a few
grouped-query attention layers between them in the published order
(``layer_types``), a shared SwiGLU MLP after every mixer, no positional
encoding, a tied head.

Configuration keys are the published ``config.json``'s own, so a user's
``engine.json`` is the config they copy. D = ``hidden_size``; the item
table stands in for the vocabulary (row 0 the pad id, item ``i`` row
``i + 1``):

    x = embedding_multiplier * E[token]
    layer l:  x = x + residual_multiplier * Mixer_l(RMSNorm(x))
              h = RMSNorm(x); [a | b] = h W_in
              x = x + residual_multiplier * (silu(a) * b) W_out
    scores = RMSNorm(x_last) E^T / logits_scaling

    Mixer = attention: q = h W_q (num_attention_heads heads), k, v = h W_k,
        h W_v (num_key_value_heads heads; query head i reads head i //
        group); s = q . k * attention_multiplier, causal, inside the
        history; NO rotary, no position term; out = concat(softmax(s) v) W_o
    Mixer = mamba (d_inner = mamba_n_heads x mamba_d_head, N =
        mamba_d_state, one group):
        [z | u | dt] = h W_in              d_inner | d_inner + 2 N | heads
        u_t = silu(sum_k w_k * u_{t-K+1+k} + b)   depthwise, K =
              mamba_d_conv; taps before the history's first event are zero
        [x | B | C] = u;  D_t = softplus(dt_t + dt_bias);  A = -exp(A_log)
        S_t = exp(D_t A) S_{t-1} + D_t * (x_t outer B_t)   per head
              [d_head, N]; S = 0 before the history's first event
        y_t = S_t C_t + Dskip * x_t
        out = RMSNorm(y * silu(z)) W_out           (the gate before the norm)

The forward takes ONE packed token stream [T] with segment ids (histories
one after another; padding is segment 0), as the two other decoders do.
What is new here is a state that must not cross a history's boundary
inside the stream:

- the scan is CHUNKED (the state-space duality of Mamba-2,
  arXiv:2405.21060): inside a chunk of ``mamba_chunk_size`` tokens the
  sum in its quadratic form, as matmuls; across chunks a scan over one
  state a chunk. A history's start inside a chunk zeroes every (i, j)
  pair that straddles it, every token's part of the chunk's outgoing
  state that lies before the last start, and the carried state for every
  token behind a start; the convolution reads no tap from the history
  before. The boundary is wherever the segment id CHANGES (``runs``), so
  a left-padded or right-padded layout (the trainer's, ``pio eval``'s)
  goes through the same function as the packed one. Two forms, one
  result: ``ssd_scan`` (XLA's: the trainer's, the CPU's, odd shapes)
  and ``ssd_scan_kernel`` (one Pallas TPU kernel: a chunk's decay tiles
  built in VMEM and fed to the MXU from there, the states carried
  between chunks never leaving the chip; serving on the TPU at the
  published widths); ``scan_kernel_for`` names the choice from the
  backend, the shapes and whether a gradient is asked, and is the one
  place it is made;
- the weights are stacked by kind (``mamba`` [36, ...], ``attention``
  [4, ...], ``mlp`` [40, ...]) and every run of Mamba layers in the
  published order is ONE ``lax.scan`` (five at the published order: 5, 9,
  9, 9 and 4 layers), the few attention layers between them written out,
  so a lattice point compiles five Mamba bodies and four attention
  bodies, not 40 layers, and the attention kernel stands at the
  program's top level as in the two other decoders.

Precision: weights and matmul inputs ``compute_dtype`` (bfloat16 as
published); the residual stream, norms, softmax, the scan's decays
(cumulative sums, their differences, the exponentials) and states,
``A_log``, ``dt_bias``, ``D``, the convolution, accumulation and scores
float32. The head is the TIED embedding: the retriever's catalog is its
item rows widened to float32 (exact) and divided by ``logits_scaling``
there (a power of two: exact), so the scores the kernel gives are the
published logits.

The device program counts what it did and hands the counts out through
the serving pipeline's encoder seam (``COUNTERS``).
"""

from __future__ import annotations

import dataclasses
import functools
import itertools
from typing import Any

import numpy as np

from ..storage.bimap import BiMap
from .seq_common import rms_norm as _rms, rows_to_stream
from .seq_serving import SequenceServingMixin

__all__ = [
    "COUNTERS",
    "GRANITE_LAYER_TYPES",
    "HybridSSMConfig",
    "HybridSSMEncoder",
    "HybridSSMModel",
    "STEP_TOKEN_BUDGET",
    "causal_conv",
    "forward_hidden",
    "init_params",
    "param_count",
    "param_shapes",
    "scan_kernel_for",
    "segment_runs",
    "ssd_scan",
    "ssd_scan_kernel",
    "train_hybrid_ssm",
]

#: Most tokens one serving step holds, and so the longest history a query
#: may bring: the least that holds the longest history whole. A step
#: reads every weight once (6.4 GB at the published widths: 8 ms at the
#: v5e's 819 GB/s) and a token costs 6 GFLOP, so a step is MXU-bound from
#: some 250 tokens on and a larger step buys no rate, only latency and
#: temporaries. Swept on the chip (PERF.md section 7: 32 callers, histories
#: of 16 to 8,192 events in one queue): 12,288 and 16,384 answered 8% and
#: 19% FEWER queries a second, the pool too small to keep two such steps
#: full (81% and 76% of a step's tokens real for 89%).
STEP_TOKEN_BUDGET = 8192

#: Smallest stream a step is padded to: four scan chunks at the
#: published chunk size, past the ridge above, so that a lone short
#: query costs about the weights' bytes and no more.
STEP_TOKEN_MIN = 1024

#: What the device program counts, in the order it hands them out:
#: chunks of ``mamba_chunk_size`` that held a real token, the Mamba
#: layers together; history starts that fell INSIDE such a chunk (not on
#: its first token: the cut is then made by the chunk's masks and not by
#: dropping the carried state), layers together; the attention layers'
#: unmasked (query, key) pairs; the live chunks, layers together, whose
#: scan ran as ``ssd_scan_kernel``: all of them where ``scan_kernel_for``
#: names the kernel (serving on the TPU at aligned widths), none where
#: ``ssd_scan`` ran (the CPU, training, odd shapes).
COUNTERS = ("ssmChunks", "ssmResetsInChunk", "pairsCausal", "ssmKernelChunks")

#: granite-4.0-h-micro's published order: attention at 5, 15, 25, 35.
GRANITE_LAYER_TYPES = tuple(
    "attention" if i % 10 == 5 else "mamba" for i in range(40))


@dataclasses.dataclass(frozen=True)
class HybridSSMConfig:
    # the published keys (granite-4.0-h-micro's values)
    hidden_size: int = 2048
    num_hidden_layers: int = 40
    layer_types: tuple = GRANITE_LAYER_TYPES
    num_attention_heads: int = 32
    num_key_value_heads: int = 8
    attention_multiplier: float = 0.015625
    embedding_multiplier: float = 12.0
    residual_multiplier: float = 0.22
    logits_scaling: float = 8.0
    shared_intermediate_size: int = 8192
    mamba_n_heads: int = 64
    mamba_d_head: int = 64
    mamba_d_state: int = 128
    mamba_n_groups: int = 1
    mamba_d_conv: int = 4
    mamba_expand: int = 2
    mamba_chunk_size: int = 256
    mamba_conv_bias: bool = True
    mamba_proj_bias: bool = False
    rms_norm_eps: float = 1e-5
    position_embedding_type: str = "nope"
    tie_word_embeddings: bool = True
    num_local_experts: int = 0
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
        object.__setattr__(self, "layer_types", tuple(self.layer_types))
        if (len(self.layer_types) != self.num_hidden_layers
                or set(self.layer_types) - {"mamba", "attention"}):
            raise ValueError(
                "layer_types names `mamba` or `attention` once a layer: got "
                f"{len(self.layer_types)} for {self.num_hidden_layers} "
                "layers")
        if (self.position_embedding_type != "nope" or self.num_local_experts
                or not self.tie_word_embeddings or self.mamba_proj_bias
                or not self.mamba_conv_bias or self.mamba_n_groups != 1):
            raise ValueError(
                "hybrid_ssm runs the block granite-4.0-h-micro publishes: "
                "position_embedding_type 'nope', num_local_experts 0, a "
                "tied head, one B/C group, a convolution bias and no "
                "projection bias")
        if self.mamba_expand * self.hidden_size != self.d_inner:
            raise ValueError(
                f"mamba_n_heads x mamba_d_head = {self.d_inner} is not "
                f"mamba_expand x hidden_size")
        if (self.hidden_size % self.num_attention_heads
                or self.num_attention_heads % self.num_key_value_heads):
            raise ValueError("num_key_value_heads must divide "
                             "num_attention_heads, and that hidden_size")
        if self.max_len > STEP_TOKEN_BUDGET:
            raise ValueError(f"max_len {self.max_len} is over a serving "
                             f"step's {STEP_TOKEN_BUDGET} tokens")
        if self.exclude_seen and self.max_len > 512:
            raise ValueError(
                "exclude_seen over-fetches num + the history's distinct "
                "items from the head's top-k, which keeps at most 528: "
                "set max_len <= 512 or exclude_seen false")

    @property
    def d_inner(self) -> int:
        return self.mamba_n_heads * self.mamba_d_head

    @property
    def conv_dim(self) -> int:
        return self.d_inner + 2 * self.mamba_n_groups * self.mamba_d_state

    @property
    def head_dim(self) -> int:
        return self.hidden_size // self.num_attention_heads

    def count(self, kind: str) -> int:
        return sum(1 for t in self.layer_types if t == kind)


# -- parameters ---------------------------------------------------------------

#: leaves that are no matrix and stay float32 in every tree
_FLOAT32 = frozenset({"input_norm", "post_norm", "norm", "norm_f", "conv_w",
                      "conv_b", "dt_bias", "A_log", "D"})


def param_shapes(cfg: HybridSSMConfig, vocab: int) -> dict:
    """The PUBLIC tree's shapes: matrices as [in, out], the layers of a
    kind stacked on a leading axis in their published order (the MLP
    follows every mixer: ``num_hidden_layers`` of them)."""
    D, F = cfg.hidden_size, cfg.shared_intermediate_size
    Lm, La = cfg.count("mamba"), cfg.count("attention")
    L = len(cfg.layer_types)
    H, KV, hd = cfg.num_attention_heads, cfg.num_key_value_heads, cfg.head_dim
    di, cv, Hm = cfg.d_inner, cfg.conv_dim, cfg.mamba_n_heads
    return {
        "embed": (vocab, D), "norm_f": (D,),
        "mamba": {"input_norm": (Lm, D), "in_proj": (Lm, D, di + cv + Hm),
                  "conv_w": (Lm, cfg.mamba_d_conv, cv), "conv_b": (Lm, cv),
                  "dt_bias": (Lm, Hm), "A_log": (Lm, Hm), "D": (Lm, Hm),
                  "norm": (Lm, di), "out_proj": (Lm, di, D)},
        "attention": {"input_norm": (La, D), "wq": (La, D, H * hd),
                      "wk": (La, D, KV * hd), "wv": (La, D, KV * hd),
                      "wo": (La, H * hd, D)},
        "mlp": {"post_norm": (L, D), "w_in": (L, D, 2 * F),
                "w_out": (L, F, D)},
    }


def _leaves(shapes: dict):
    for group, value in shapes.items():
        if isinstance(value, dict):
            for name, shape in value.items():
                yield group, name, shape
        else:
            yield None, group, value


def param_count(cfg: HybridSSMConfig, vocab: int) -> int:
    return int(sum(int(np.prod(s)) for _g, _n, s
                   in _leaves(param_shapes(cfg, vocab))))


def init_params(cfg: HybridSSMConfig, vocab: int, seed: int = 0) -> dict:
    """Matrices iid normal at 0.02; what is no matrix as Mamba-2
    initialises it: ``A_log`` the log of uniform 1..16, ``dt_bias`` the
    inverse softplus of a time step log-uniform in 0.001..0.1, ``D`` 1,
    the convolution uniform in +-mamba_d_conv^-0.5 (PyTorch's Conv1d
    default), gains 1. float32 numpy on the host."""
    rng = np.random.default_rng([seed, 0x55D])

    def leaf(name, shape):
        if name in ("input_norm", "post_norm", "norm", "norm_f", "D"):
            return np.ones(shape, np.float32)
        if name == "A_log":
            return np.log(rng.uniform(1.0, 16.0, shape)).astype(np.float32)
        if name == "dt_bias":
            dt = np.exp(rng.uniform(np.log(1e-3), np.log(1e-1), shape))
            return (dt + np.log(-np.expm1(-dt))).astype(np.float32)
        if name in ("conv_w", "conv_b"):
            bound = cfg.mamba_d_conv ** -0.5
            return rng.uniform(-bound, bound, shape).astype(np.float32)
        return rng.standard_normal(shape, dtype=np.float32) * np.float32(0.02)

    out: dict = {}
    for group, name, shape in _leaves(param_shapes(cfg, vocab)):
        (out if group is None else out.setdefault(group, {}))[name] = leaf(
            name, shape)
    return out


def _stored(params: dict, cd) -> dict:
    """The tree with its matrices and the table in ``compute_dtype`` (no
    copy where they are already), the rest float32."""
    def leaf(name, x):
        want = np.float32 if name in _FLOAT32 else cd
        return np.asarray(x).astype(want, copy=False)

    return {k: ({n: leaf(n, x) for n, x in v.items()} if isinstance(v, dict)
                else leaf(k, v)) for k, v in params.items()}


# -- the state-space mixer's parts --------------------------------------------

def segment_runs(seg):
    """int32 [T]: 0, 0, 1, 1, 1, 2, ... rising by one wherever the
    segment id changes: two tokens belong to one history exactly where
    they share a run (and are no padding). Equal to ``seg`` up to
    renaming for a packed stream; in a padded layout it keeps one row's
    pads apart from the next row's."""
    import jax.numpy as jnp

    change = jnp.concatenate([jnp.zeros(1, bool), seg[1:] != seg[:-1]])
    return jnp.cumsum(change.astype(jnp.int32))


def causal_conv(u, w, b, runs):
    """Depthwise causal convolution along the stream: u [T, C] float32,
    w [K, C] (``w[k]`` multiplies ``u[t - K + 1 + k]``), b [C], runs [T]
    -> [T, C] float32. A tap that lies in another run (before the
    history's first event) is zero."""
    import jax.numpy as jnp

    K = w.shape[0]
    out = u * w[K - 1] + b
    for back in range(1, K):
        tap = jnp.pad(u, ((back, 0), (0, 0)))[:-back]
        same = jnp.pad(runs, (back, 0), constant_values=-1)[:-back] == runs
        out = out + jnp.where(same[:, None], tap, 0.0) * w[K - 1 - back]
    return out


def _chunk_decays(dt, A, runs):
    """What a chunk's tokens carry of the decay, all float32, from dt
    [n, Q, H], A [H] and runs [n, Q] (chunk by position): ``cs`` [n, Q,
    H], the inclusive cumulative sum of dt * A inside the chunk; ``tail``
    [n, Q, H], exp(cs_end - cs_j) for the tokens of the chunk's LAST run
    (their part of the outgoing state), else 0; ``keep`` [n, H],
    exp(cs_end) where the whole chunk is the run the incoming state came
    from, else 0; ``reach`` [n, Q, H], exp(cs_i) for the tokens of that
    run (what they read of the incoming state), else 0."""
    import jax.numpy as jnp

    cs = jnp.cumsum(dt * A, axis=1)
    last_run = runs[:, -1]
    tail = jnp.where((runs == last_run[:, None])[..., None],
                     jnp.exp(cs[:, -1:, :] - cs), 0.0)
    came_from = jnp.concatenate([jnp.full((1,), -2, runs.dtype),
                                 last_run[:-1]])                # [n]
    whole = (runs[:, 0] == came_from) & (last_run == came_from)
    keep = jnp.where(whole[:, None], jnp.exp(cs[:, -1, :]), 0.0)
    reach = jnp.where((runs == came_from[:, None])[..., None],
                      jnp.exp(cs), 0.0)
    return cs, tail, keep, reach


def ssd_scan(x, dt, A, B, C, runs, chunk: int, cd):
    """The selective scan of the module's head, chunked: x [T, H, P], dt
    [T, H] (after the softplus), A [H] (negative), B, C [T, N], all
    float32; runs [T] int32 (``segment_runs``) -> y [T, H, P] float32
    WITHOUT the skip term. XLA's form: the trainer's (it has a
    gradient), the CPU's, and what ``ssd_scan_kernel`` is held to.

    With a = dt * A and cs its inclusive cumulative sum inside a chunk:

    - inside a chunk, for j <= i of one run: (C_i . B_j) exp(cs_i -
      cs_j) dt_j x_j, a [Q, Q] matrix a head against [Q, P];
    - a chunk's outgoing state: sum over the tokens j of the chunk's
      LAST run of exp(cs_end - cs_j) dt_j x_j outer B_j, plus the
      incoming state decayed by exp(cs_end) where the whole chunk is the
      run the state came from;
    - a token of the run that the incoming state belongs to adds
      exp(cs_i) C_i . S_in.

    Decays, their sums and exponentials, the states and every
    accumulation float32; the matmuls' inputs ``cd``."""
    import jax
    import jax.numpy as jnp

    f32 = jnp.float32
    prec = jax.lax.Precision.HIGHEST if jnp.dtype(cd) == f32 else None
    T, H, P = x.shape
    N = B.shape[-1]
    Q = min(int(chunk), T)
    pad = -T % Q
    if pad:  # a time step of 0 adds nothing and decays nothing
        x, B, C = (jnp.pad(v, ((0, pad),) + ((0, 0),) * (v.ndim - 1))
                   for v in (x, B, C))
        dt = jnp.pad(dt, ((0, pad), (0, 0)))
        runs = jnp.pad(runs, (0, pad), constant_values=-1)
    n = (T + pad) // Q
    x, dt, B, C = (v.reshape(n, Q, *v.shape[1:]) for v in (x, dt, B, C))
    runs = runs.reshape(n, Q)

    def mm(spec, a, b):
        return jnp.einsum(spec, a.astype(cd), b.astype(cd), precision=prec,
                          preferred_element_type=f32)

    cs, tail, keep, reach = _chunk_decays(dt, A, runs)
    xdt = x * dt[..., None]                                     # [n, Q, H, P]
    # -- inside the chunks
    i, j = jnp.arange(Q)[:, None], jnp.arange(Q)[None, :]
    meet = (j <= i)[None] & (runs[:, :, None] == runs[:, None, :])
    decay = jnp.exp(jnp.where(
        meet[:, None], cs.transpose(0, 2, 1)[:, :, :, None]
        - cs.transpose(0, 2, 1)[:, :, None, :], -jnp.inf))      # [n, H, Q, Q]
    y = mm("chij,cjhp->cihp", mm("cin,cjn->cij", C, B)[:, None] * decay, xdt)
    # -- the chunks' own states, then the states carried between them
    own = mm("cjhp,cjn->chpn", xdt * tail[..., None], B)        # [n, H, P, N]

    def carry(state, c):
        own_c, keep_c = c
        return state * keep_c[:, None, None] + own_c, state

    _end, incoming = jax.lax.scan(carry, jnp.zeros((H, P, N), f32),
                                  (own, keep))                  # [n, H, P, N]
    y = y + mm("cin,chpn->cihp", C, incoming) * reach[..., None]
    return y.reshape(n * Q, H, P)[:T]


#: Most heads a grid step of ``ssd_scan_kernel`` (at the published head
#: size of 64 a block of x^T and of y^T is [1024, 256] float32, 1 MB).
#: Probed on the chip at the published widths, 8,192 tokens, the kernel
#: alone (PERF.md section 5): 0.725 / 0.605 / 0.537 ms at 4 / 8 / 16; a
#: grid step's fixed part (the chunk's pairs, the pipeline's hand-over) is
#: some 250 of its 1,820 bundles at 8.
SCAN_HEADS_BLOCK = 16

#: Rows and columns of the blocks a chunk's decay tile is built in: a
#: block with j > i throughout is all masked and is never built.
_SCAN_SUB = 128

#: What ``ssd_scan_kernel`` may hold in VMEM: the v5e's scoped limit of
#: 16 MB a kernel, less a tenth for what the body keeps on its stack.
_SCAN_VMEM_BUDGET = int(0.9 * 16 * 2 ** 20)


def _scan_heads_block(H: int) -> int:
    """Heads a grid step: the most, up to ``SCAN_HEADS_BLOCK``, that
    divide H."""
    return max(hb for hb in range(1, SCAN_HEADS_BLOCK + 1) if H % hb == 0)


def _scan_vmem_bytes(Q: int, H: int, P: int, N: int) -> int:
    """What ``ssd_scan_kernel`` holds in VMEM, counted for float32
    operands (the larger): the blocks of x^T, y^T, B^T, C^T, the
    per-token rows and columns (a column pads to 128 lanes) twice, for
    the pipeline; once the chunk's pairs, B, the block's tails and EVERY
    head's state (at the published widths 8.9 MB, 2 of them the states;
    a chunk of 1,024 tokens, a state of 512 or a head of 256 would not
    fit, and Mosaic refuses them)."""
    hb = _scan_heads_block(H)
    blocks = (2 * hb * P * Q + 2 * N * Q + 4 * max(hb, 8) * Q + 2 * Q * 128
              + 8 * Q)
    scratch = Q * Q + Q * N + hb * P * Q + H * P * N
    return 4 * (2 * blocks + scratch)


def scan_kernel_for(T: int, Q: int, H: int, P: int, N: int, *, backend: str,
                    differentiable: bool) -> str:
    """Which form of the selective scan ``forward_hidden`` runs for a
    shape, by name, and the one place the choice is made: ``kernel``
    (``ssd_scan_kernel``) on the TPU where the stream is whole chunks, a
    chunk whole blocks of 128 tokens (the lanes), the state size a
    multiple of 128 (B^T is transposed back in VMEM), B^T and C^T start
    on a multiple of their height under x^T, a head is whole sublane
    tiles of the compute type (16 rows), and the kernel's blocks with
    every head's state fit its VMEM; ``xla`` (``ssd_scan``) everywhere
    else: off the TPU, in training (the kernel has no gradient), at odd
    or outsize shapes."""
    aligned = (T % Q == 0 and Q % _SCAN_SUB == 0 and N % 128 == 0
               and (H * P) % N == 0 and P % 16 == 0)
    return ("kernel" if backend == "tpu" and not differentiable and aligned
            and _scan_vmem_bytes(Q, H, P, N) <= _SCAN_VMEM_BUDGET else "xla")


def _ssd_scan_body(keep_ref, skip_ref, x_ref, b_ref, c_ref, rows_ref,
                   cs_col_ref, runs_col_ref, runs_row_ref, y_ref,
                   cb_s, bt_s, tail_s, state_s, *, H, hb, P, Q, sub, cd, prec):
    """One grid step (chunk c, head block h), TOKENS ON THE LANES: x_ref,
    y_ref [hb P, Q]; b_ref, c_ref [N, Q]; rows_ref [4, 1, hb, Q] (cs, dt,
    tail, reach, a row a head); cs_col_ref [1, Q, hb] (cs again, a column
    a head); runs as a column and as a row; in SMEM keep_ref [chunks x
    H] and skip_ref [H]. Scratch: ``cb_s`` [Q, Q] the chunk's (B_j . C_i),
    zero where j and i do not meet, and ``bt_s`` [Q, N] B with the tokens
    on the sublanes, both made at the chunk's first head block and read
    by all; ``tail_s`` [hb P, Q] what the block's tokens add to the state;
    ``state_s`` [H / hb, hb P, N] every head's state as the chunk before
    left it."""
    import jax
    import jax.numpy as jnp
    from jax.experimental import pallas as pl

    f32 = jnp.float32
    c, h = pl.program_id(0), pl.program_id(1)

    def dot(a, b):
        return jnp.dot(a, b, precision=prec, preferred_element_type=f32)

    @pl.when(c == 0)
    def _no_state_before_the_stream():
        state_s[h] = jnp.zeros(state_s.shape[1:], f32)

    @pl.when(h == 0)
    def _the_chunks_pairs():
        bt_s[...] = b_ref[...].T.astype(cd)
        j = jax.lax.broadcasted_iota(jnp.int32, (Q, Q), 0)
        i = jax.lax.broadcasted_iota(jnp.int32, (Q, Q), 1)
        meet = (j <= i) & (runs_col_ref[...] == runs_row_ref[...])
        cb_s[...] = jnp.where(meet, dot(bt_s[...], c_ref[...].astype(cd)),
                              0.0)

    c_cd = c_ref[...].astype(cd)
    # what every token of the run the state came from reads of it, the
    # block's heads in one matmul; a head's `reach` multiplies it below
    y_ref[...] = dot(state_s[h].astype(cd), c_cd)
    for k in range(hb):
        head = slice(k * P, (k + 1) * P)
        dt, tail = rows_ref[1, 0, k:k + 1, :], rows_ref[2, 0, k:k + 1, :]
        cs_col = cs_col_ref[0, :, k:k + 1]                      # [Q, 1]
        xdt = x_ref[head, :] * dt                               # [P, Q]
        xdt_cd = xdt.astype(cd)
        tail_s[head, :] = (xdt * tail).astype(cd)
        skip = skip_ref[h * hb + k]
        for r in range(Q // sub):
            at, upto = slice(r * sub, (r + 1) * sub), (r + 1) * sub
            cs, reach = rows_ref[0, 0, k:k + 1, at], rows_ref[3, 0, k:k + 1, at]
            # j <= i of one run: cs_i - cs_j <= 0; elsewhere `cb_s` is 0
            # and the minimum keeps the exponential finite beside it
            decay = jnp.exp(jnp.minimum(cs - cs_col[:upto], 0.0))
            inside = dot(xdt_cd[:, :upto],
                         (cb_s[:upto, at] * decay).astype(cd))  # [P, sub]
            y_ref[head, at] = (inside + y_ref[head, at] * reach
                               + x_ref[head, at] * skip)
    own = dot(tail_s[...], bt_s[...])                           # [hb P, N]
    for k in range(hb):
        head = slice(k * P, (k + 1) * P)
        state_s[h, head, :] = (state_s[h, head, :]
                               * keep_ref[c * H + h * hb + k] + own[head])


def ssd_scan_kernel(uT, dt, A, runs, *, d_state: int, chunk: int, cd, skip,
                    heads_block: int | None = None,
                    interpret: bool | None = None):
    """``ssd_scan`` as ONE Pallas TPU kernel, TOKENS ON THE LANES: uT [H
    P + 2 N, T] float32, the TRANSPOSE of the convolution's [x | B | C];
    dt [T, H], A [H], runs [T], ``skip`` [H] (the mixer's D) -> y^T [H P,
    T] float32 WITH the skip term, the transpose of ``ssd_scan(...) + x *
    D``.

    Why transposes: XLA lays the input projection's [T, 8,512] out
    tokens-minor (8,512 is no multiple of 128 lanes, 8,192 is), the
    convolution and the gate follow it, so u^T and y^T in row-major
    order ARE those arrays as they lie and no copy stands before or
    after the kernel (row-major [T, H P] cost one of 143 MB before and
    one of 134 MB behind, a layer); every per-token factor is then a row
    that broadcasts along sublanes, and every elementwise operation
    fills its lanes at a head size of 64.

    Grid (chunk, head block), both in order. x^T, B^T and C^T are block
    views of u^T ([hb P, Q] and [N, Q] at row offsets that are multiples
    of their heights) and y^T is written as [hb P, Q] blocks: no [chunk,
    position, head, d_head] layout on either side. A chunk's (B_j .
    C_i), masked to the pairs j <= i of one run, are made once in VMEM
    and shared by the heads; a head's decay tile exp(cs_i - cs_j) is
    built there in float32 from the DIFFERENCE of the cumulative sums,
    block by block of 128 (the blocks with j > i not at all),
    multiplied in, cast to ``cd`` and fed to the MXU; every head's state
    stays in VMEM from chunk to chunk: a chunk first reads the incoming
    state out (for the tokens of the run it came from), then replaces
    it (decayed only where the whole chunk is that run) plus its own:
    ``ssd_scan``'s three rules. What is one number a token a head (the
    cumulative sums and the three factors of ``_chunk_decays``) stays
    XLA's. Precision as ``ssd_scan``'s, cast for cast.

    T must be whole chunks; ``scan_kernel_for`` names the shapes the
    compiled kernel takes (the interpreter takes any). ``interpret``
    defaults to "not on a TPU"."""
    import functools

    import jax
    import jax.numpy as jnp
    from jax.experimental import pallas as pl
    from jax.experimental.pallas import tpu as pltpu

    f32 = jnp.float32
    cd = jnp.dtype(cd)
    (T, H), N, Q = dt.shape, int(d_state), int(chunk)
    di = uT.shape[0] - 2 * N
    P = di // H
    hb = heads_block or _scan_heads_block(H)
    if T % Q or di % N or H % hb:
        raise ValueError(
            f"ssd_scan_kernel takes whole chunks ({T} tokens, chunk {Q}), "
            f"B^T and C^T a multiple of their height under x^T ({di} rows, "
            f"d_state {N}) and whole head blocks ({H} heads, {hb} a block)")
    if interpret is None:
        interpret = jax.default_backend() != "tpu"
    n, sub = T // Q, (_SCAN_SUB if Q % _SCAN_SUB == 0 else Q)
    cs, tail, keep, reach = _chunk_decays(
        dt.reshape(n, Q, H), A, runs.reshape(n, Q))
    cs = cs.reshape(T, H)
    rows = jnp.stack([cs, dt, tail.reshape(T, H), reach.reshape(T, H)]
                     ).transpose(0, 2, 1).reshape(4, H // hb, hb, T)
    in_specs = [
        pl.BlockSpec((hb * P, Q), lambda c, h, *_: (h, c)),          # x^T
        pl.BlockSpec((N, Q), lambda c, h, *_: (di // N, c)),         # B^T
        pl.BlockSpec((N, Q), lambda c, h, *_: (di // N + 1, c)),     # C^T
        pl.BlockSpec((4, 1, hb, Q), lambda c, h, *_: (0, h, 0, c)),
        pl.BlockSpec((1, Q, hb), lambda c, h, *_: (h, c, 0)),        # cs
        pl.BlockSpec((Q, 1), lambda c, h, *_: (c, 0)),               # runs
        pl.BlockSpec((1, Q), lambda c, h, *_: (0, c)),
    ]
    return pl.pallas_call(
        functools.partial(
            _ssd_scan_body, H=H, hb=hb, P=P, Q=Q, sub=sub, cd=cd,
            prec=jax.lax.Precision.HIGHEST if cd == f32 else None),
        grid_spec=pltpu.PrefetchScalarGridSpec(
            num_scalar_prefetch=2,
            in_specs=in_specs,
            out_specs=pl.BlockSpec((hb * P, Q), lambda c, h, *_: (h, c)),
            grid=(n, H // hb),
            scratch_shapes=[pltpu.VMEM((Q, Q), f32),
                            pltpu.VMEM((Q, N), cd),
                            pltpu.VMEM((hb * P, Q), cd),
                            pltpu.VMEM((H // hb, hb * P, N), f32)],
        ),
        out_shape=jax.ShapeDtypeStruct((di, T), f32),
        compiler_params=pltpu.CompilerParams(
            dimension_semantics=("arbitrary", "arbitrary")),
        interpret=interpret,
        name="ssd_scan_kernel",
    )(keep.reshape(n * H), skip.astype(f32), uT, uT, uT, rows,
      cs.reshape(T, H // hb, hb).transpose(1, 0, 2), runs[:, None],
      runs[None, :])


# -- the forward --------------------------------------------------------------

def forward_hidden(params: dict, cfg: HybridSSMConfig, tokens, seg, pos, *,
                   differentiable: bool = False):
    """(states [T, D] float32 after the final norm, counters int32[4] in
    ``COUNTERS``' order) of one token stream. tokens, seg, pos: int32
    [T]; the events of one history share a segment id (1..; 0 is
    padding) and lie one after another; ``pos`` counts them from 0 and is
    read by nothing but the count of causal pairs (the model has no
    positional encoding). ``params`` is the public tree (four attention
    layers: a head-major copy of their projections would save four
    transposes of [T, 2048] a step and cost a second layout).
    ``differentiable`` (training, at test sizes) takes the plain
    attention, which has a gradient and builds the [H, T, T] scores;
    serving never passes it."""
    import jax
    import jax.numpy as jnp

    from ..parallel.ring_attention import (attention_kernel_for,
                                           segment_attention,
                                           segment_flash_attention)

    f32 = jnp.float32
    cd = jnp.dtype(cfg.compute_dtype)
    prec = jax.lax.Precision.HIGHEST if cd == f32 else None
    eps, res = cfg.rms_norm_eps, cfg.residual_multiplier
    T = tokens.shape[0]
    H, KV, hd = cfg.num_attention_heads, cfg.num_key_value_heads, cfg.head_dim
    Hm, P, N, di = (cfg.mamba_n_heads, cfg.mamba_d_head, cfg.mamba_d_state,
                    cfg.d_inner)
    F = cfg.shared_intermediate_size
    real = seg > 0
    runs = segment_runs(seg)
    n_pad = jnp.sum(~real)
    kernel = "plain" if differentiable else attention_kernel_for(
        T, hd, hd, backend=jax.default_backend(), segmented=True,
        grouped=KV != H)
    Q = min(cfg.mamba_chunk_size, T)
    scan = scan_kernel_for(T, Q, Hm, P, N, backend=jax.default_backend(),
                           differentiable=differentiable)

    def mm(x, w):
        return jnp.dot(x.astype(cd), w.astype(cd), precision=prec,
                       preferred_element_type=f32)

    def layer_of(stack, i):
        return jax.tree_util.tree_map(
            lambda w: jax.lax.dynamic_index_in_dim(w, i, 0, False), stack)

    def mamba(x, i):
        w = layer_of(params["mamba"], i)
        with jax.named_scope("pio.seq.ssm_in_proj"):
            zudt = mm(_rms(x, w["input_norm"], eps), w["in_proj"])
        # every operation between the two projections stands under a
        # scope, the slices of `zudt` too: device time is read by scope,
        # and a slice of [T, 4352] float32 is a pass over memory
        with jax.named_scope("pio.seq.ssm_conv"):
            u = jax.nn.silu(causal_conv(zudt[:, di:di + cfg.conv_dim],
                                        w["conv_w"], w["conv_b"], runs))
            if scan == "kernel":    # as XLA lays it: no data moves
                u = u.T
        with jax.named_scope("pio.seq.ssm_scan"):
            dt = jax.nn.softplus(zudt[:, -Hm:] + w["dt_bias"])
            A = -jnp.exp(w["A_log"])
            if scan == "kernel":
                y = ssd_scan_kernel(u, dt, A, runs, d_state=N, chunk=Q,
                                    cd=cd, skip=w["D"]).T
            else:
                xs = u[:, :di].reshape(T, Hm, P)
                y = ssd_scan(xs, dt, A, u[:, di:di + N], u[:, di + N:], runs,
                             Q, cd)
                y = (y + xs * w["D"][:, None]).reshape(T, di)
        with jax.named_scope("pio.seq.ssm_gate_out"):
            y = _rms(y * jax.nn.silu(zudt[:, :di]), w["norm"], eps)
            return x + res * mm(y, w["out_proj"])

    def attention(x, i):
        w = layer_of(params["attention"], i)
        with jax.named_scope("pio.seq.attn_proj"):
            h = _rms(x, w["input_norm"], eps)
            q = mm(h, w["wq"]).reshape(T, H, hd)
            k = mm(h, w["wk"]).reshape(T, KV, hd)
            v = mm(h, w["wv"]).reshape(T, KV, hd)
        with jax.named_scope("pio.seq.gqa_attn"):
            if kernel == "segment_flash":
                o, pairs = segment_flash_attention(
                    (q.astype(cd).transpose(1, 0, 2)[None],),
                    (k.astype(cd).transpose(1, 0, 2)[None],),
                    v.astype(cd).transpose(1, 0, 2)[None], seg[None],
                    scale=cfg.attention_multiplier, causal=True)
                o = o[0].transpose(1, 0, 2)
            else:
                # segment_attention scales by hd^-0.5; the rest goes into
                # q (granite's 1/64 over 64^-0.5 is 1/8, exact)
                o = segment_attention(
                    (q * (cfg.attention_multiplier * hd ** 0.5)
                     ).astype(cd)[None],
                    jnp.repeat(k, H // KV, axis=1).astype(cd)[None],
                    jnp.repeat(v, H // KV, axis=1).astype(cd)[None],
                    seg[None], causal=True)[0]
                pairs = (jnp.sum(jnp.where(real, pos + 1, 0))
                         + n_pad * (n_pad + 1) // 2).astype(jnp.int32)
        with jax.named_scope("pio.seq.attn_proj"):
            return x + res * mm(o.reshape(T, H * hd), w["wo"]), pairs

    def mlp(x, l):
        w = layer_of(params["mlp"], l)
        with jax.named_scope("pio.seq.mlp"):
            ab = mm(_rms(x, w["post_norm"], eps), w["w_in"])
            return x + res * mm(jax.nn.silu(ab[:, :F]) * ab[:, F:], w["w_out"])

    def mamba_layer(x, step):
        l, i = step
        return mlp(mamba(x, i), l), None

    with jax.named_scope("pio.seq.embed"):
        x = params["embed"][tokens].astype(f32) * cfg.embedding_multiplier
    pairs = jnp.int32(0)
    seen = {"mamba": 0, "attention": 0}
    with jax.named_scope("pio.seq.layers"):
        for kind, run in itertools.groupby(enumerate(cfg.layer_types),
                                           key=lambda t: t[1]):
            layers = [l for l, _kind in run]
            first = seen[kind]
            seen[kind] += len(layers)
            if kind == "mamba":     # a run of Mamba layers: one scan
                # What the compiler makes inside the run's body and names
                # after no part of it reads `pio.seq.ssm_run`: today the
                # convolution's fusion (its root a convert that XLA
                # hoists above the slices into x, B and C) and the
                # cumulative sums' expansions, both the mixer's middle.
                with jax.named_scope("pio.seq.ssm_run"):
                    x, _ = jax.lax.scan(mamba_layer, x, (
                        jnp.asarray(layers, jnp.int32),
                        jnp.arange(first, seen[kind], dtype=jnp.int32)))
                continue
            for i, l in enumerate(layers, first):
                x, p = attention(x, i)
                pairs = pairs + p
                x = mlp(x, l)
    # the padding's own triangle is no history's: the mask let it
    # through, the count leaves it out
    pairs = pairs - cfg.count("attention") * (n_pad * (n_pad + 1) // 2)
    at = jnp.arange(T)
    starts = real & jnp.concatenate([jnp.ones(1, bool),
                                     runs[1:] != runs[:-1]])
    live_chunks = jnp.sum(jax.ops.segment_sum(
        real.astype(jnp.int32), at // Q, num_segments=-(-T // Q)) > 0)
    counters = jnp.stack([
        live_chunks * cfg.count("mamba"),
        jnp.sum(starts & (at % Q != 0)) * cfg.count("mamba"),
        pairs,
        live_chunks * (cfg.count("mamba") if scan == "kernel" else 0),
    ]).astype(jnp.int32)
    return _rms(x, params["norm_f"], eps), counters


def encoder_program(cfg: HybridSSMConfig):
    """stream int32 [3, t_pad] (tokens, segments, positions), params ->
    (states [t_pad, D] float32, None, None, counters int32[4]): the
    function a serving step's encoder executable is compiled from."""

    def fn(stream, params):
        h, counters = forward_hidden(params, cfg, stream[0], stream[1],
                                     stream[2])
        return h, None, None, counters

    return fn


class HybridSSMEncoder:
    """The serving pipeline's encoder (ops/pipeline.py): histories PACKED
    into one stream of a lattice length, doubling from ``STEP_TOKEN_MIN``
    to the budget (every point a multiple of the scan's chunk and of the
    attention kernel's 128). A pool of callers whose histories span 16 to
    8,192 events keeps the steps at the budget; the lower points are what
    a lone query pays at low load. Each point is one more program to
    compile at deploy, but of a scan a run of Mamba layers and the few
    attention layers, not of 40 layers (8 s a point on the compile-only
    client; the points compile side by side)."""

    dense = False
    aux_name = None
    passes = 0
    counter_names = COUNTERS

    def __init__(self, params: dict, cfg: HybridSSMConfig):
        import jax
        import jax.numpy as jnp

        self.cfg = cfg
        self.dim = cfg.hidden_size
        self.max_len = cfg.max_len
        self.budget = STEP_TOKEN_BUDGET
        t, lattice = min(STEP_TOKEN_MIN, self.budget), []
        while t < self.budget:
            lattice.append(t)
            t *= 2
        self.lattice = tuple(lattice) + (self.budget,)
        tree = _stored(params, jnp.dtype(cfg.compute_dtype))
        self.params = jax.block_until_ready(jax.device_put(tree))
        self.param_bytes = int(sum(
            x.nbytes for x in jax.tree_util.tree_leaves(self.params)))

    def program(self, t_pad: int):
        return encoder_program(self.cfg)


@dataclasses.dataclass
class HybridSSMModel(SequenceServingMixin):
    params: Any
    seqs: np.ndarray  # [NU, max_len] left-padded histories, 0 = pad
    user_ids: BiMap
    item_ids: BiMap
    config: HybridSSMConfig

    @property
    def catalog(self) -> np.ndarray:
        """The TIED embedding's item rows (row 0, the pad id, left out)
        as the float32 catalog the retriever scans, divided by
        ``logits_scaling`` here, once, so that the kernel's scores are
        the published logits (exact for the published 8)."""
        rows = np.asarray(self.params["embed"])[1:].astype(np.float32)
        rows /= np.float32(self.config.logits_scaling)
        return rows

    @property
    def serving_ks(self) -> tuple[int, ...]:
        """The k's the head is compiled for: without ``exclude_seen``
        nothing is over-fetched, and ``num`` up to 16 is one program."""
        from .seq_serving import k_lattice

        return k_lattice(self.config.max_len) if self.config.exclude_seen \
            else (16,)

    def make_encoder(self) -> HybridSSMEncoder:
        return HybridSSMEncoder(self.params, self.config)

    def batch_recommend(self, users, nums, *, exclude_seen=None):
        if exclude_seen is None:
            exclude_seen = self.config.exclude_seen
        return super().batch_recommend(users, nums,
                                       exclude_seen=exclude_seen)

    def recommend_products(self, user_id, num, *, exclude_seen=None):
        return self.batch_recommend([user_id], [num],
                                    exclude_seen=exclude_seen)[0]


def next_item_loss(params: dict, cfg: HybridSSMConfig, batch):
    """Mean cross-entropy of the next item over the real events of
    left-padded histories [B, L], packed into one stream; ``params`` is
    the PUBLIC tree."""
    import jax.numpy as jnp
    import optax

    toks, seg, pos = rows_to_stream(batch[:, :-1])
    h, _counts = forward_hidden(params, cfg, toks, seg, pos,
                                differentiable=True)
    logits = jnp.einsum("td,vd->tv", h, params["embed"].astype(jnp.float32)
                        ) / cfg.logits_scaling
    flat = batch[:, 1:].reshape(-1)
    mask = ((flat > 0) & (toks > 0)).astype(jnp.float32)
    ce = optax.softmax_cross_entropy_with_integer_labels(logits, flat)
    return (ce * mask).sum() / jnp.maximum(mask.sum(), 1.0)


def train_hybrid_ssm(seqs: np.ndarray, user_ids: BiMap, item_ids: BiMap,
                     cfg: HybridSSMConfig, mesh=None) -> HybridSSMModel:
    """Next-item prediction over left-padded histories packed into one
    stream a batch; Adam on float32 parameters, stored in
    ``compute_dtype``."""
    import jax
    import jax.numpy as jnp
    import optax

    del mesh  # one device: the published widths are served, not trained
    vocab = len(item_ids) + 1
    params = jax.tree_util.tree_map(
        jnp.asarray, init_params(cfg, vocab, cfg.seed))
    opt = optax.adam(cfg.lr)
    state = opt.init(params)

    @functools.partial(jax.jit, donate_argnums=(0, 1))
    def step(p, s, batch):
        loss, g = jax.value_and_grad(next_item_loss)(p, cfg, batch)
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
    return HybridSSMModel(params=host, seqs=seqs, user_ids=user_ids,
                          item_ids=item_ids, config=cfg)
