"""The hybrid state-space decoder (models/hybrid_ssm_lm.py) against its
plain reference (benchmarks/lib/hybrid_ssm_reference.py: the one file,
which the benchmark's check child loads too), at small sizes on the CPU:
the chunked scan against the recurrence and the quadratic form, history
boundaries inside a chunk, the packed and the padded layouts,
grouped-query attention at the published scale, the trainer's gradients,
the serving route."""

import asyncio
import dataclasses
import importlib.util
import json
import shutil
import subprocess
import sys
from pathlib import Path

import jax
import jax.numpy as jnp
import numpy as np
import pytest

from predictionio_tpu.models import hybrid_ssm_lm as lm
from predictionio_tpu.models.seq_common import rows_to_stream
from predictionio_tpu.parallel.ring_attention import (
    attention_kernel_for, segment_attention, segment_flash_attention)
from predictionio_tpu.storage.bimap import BiMap

REPO = Path(__file__).resolve().parents[1]


def _load_reference():
    name = "pio_hybrid_ssm_reference"
    if name in sys.modules:
        return sys.modules[name]
    spec = importlib.util.spec_from_file_location(
        name, REPO / "benchmarks" / "lib" / "hybrid_ssm_reference.py")
    mod = importlib.util.module_from_spec(spec)
    sys.modules[name] = mod
    spec.loader.exec_module(mod)
    return mod


ref = _load_reference()

N_ITEMS, N_USERS = 97, 24
CHUNK = 16
#: one period's kinds at test widths: Mamba, attention (4 heads over 2
#: key/value heads), Mamba, Mamba; a scan chunk of 16
CFG = lm.HybridSSMConfig(
    hidden_size=64, num_hidden_layers=4,
    layer_types=("mamba", "attention", "mamba", "mamba"),
    num_attention_heads=4, num_key_value_heads=2, attention_multiplier=0.05,
    shared_intermediate_size=96, mamba_n_heads=8, mamba_d_head=16,
    mamba_d_state=8, mamba_chunk_size=CHUNK, max_len=96,
    compute_dtype="float32")


@pytest.fixture(autouse=True)
def small_steps(monkeypatch):
    """A serving step of 512 tokens (lattice 128, 256, 512): the CPU
    compiles three small programs and not four of up to 8,192 tokens."""
    monkeypatch.setattr(lm, "STEP_TOKEN_BUDGET", 512)
    monkeypatch.setattr(lm, "STEP_TOKEN_MIN", 128)


def ref_cfg(cfg=CFG) -> dict:
    return {k: getattr(cfg, k) for k in ref.CONFIG_KEYS}


def make_model(cfg=CFG, seed=1) -> lm.HybridSSMModel:
    """Mixed history lengths, 0 (no event) to max_len, as int32 (ids at
    the published vocabulary do not fit 16 bits)."""
    params = lm.init_params(cfg, N_ITEMS + 1, seed=seed)
    rng = np.random.default_rng(seed)
    seqs = np.zeros((N_USERS, cfg.max_len), np.int32)
    for u in range(N_USERS):
        n = int(rng.integers(0, cfg.max_len + 1))
        if n:
            seqs[u, -n:] = rng.integers(1, N_ITEMS + 1, n)
    return lm.HybridSSMModel(
        params, seqs, BiMap({f"u{i}": i for i in range(N_USERS)}),
        BiMap({f"i{i}": i for i in range(N_ITEMS)}), cfg)


def history(model, user):
    row = model.seqs[model.user_ids.get(user)]
    return row[row > 0].astype(np.int64)


def draw_histories(lengths, seed=0):
    rng = np.random.default_rng(seed)
    return [rng.integers(1, N_ITEMS + 1, n).astype(np.int64)
            for n in lengths]


def pack(histories, t_pad):
    toks, seg, pos = (np.zeros(t_pad, np.int32) for _ in range(3))
    at = 0
    for j, h in enumerate(histories):
        n = len(h)
        toks[at:at + n], seg[at:at + n] = h, j + 1
        pos[at:at + n] = np.arange(n)
        at += n
    return toks, seg, pos


_FWD = {}


def forward(params, cfg, stream):
    """(states, counters) of the program over one stream, the program
    compiled once a config and stream length."""
    key = (cfg, len(stream[0]))
    if key not in _FWD:
        _FWD[key] = jax.jit(
            lambda p, a, b, c: lm.forward_hidden(p, cfg, a, b, c))
    return _FWD[key](jax.tree_util.tree_map(jnp.asarray, params), *stream)


_REF_JIT = {}


def reference_scores(params, cfg_dict, hist, width=128):
    """`ref.next_item_scores` with the history padded on the RIGHT to one
    width (a causal mixer, attention or scan, leaves the positions before
    the padding as they are), so the reference compiles once and not
    once a length."""
    key = (json.dumps(cfg_dict, sort_keys=True), width)
    if key not in _REF_JIT:
        def run(table, tree, last, tokens):
            h = ref.forward(table[tokens], ref.stacked_layer_of(tree, cfg_dict),
                            tree["norm_f"], cfg_dict)
            return ref.scores(h[last], table, cfg_dict)
        _REF_JIT[key] = jax.jit(run)
    padded = np.zeros(width, np.int32)
    padded[:len(hist)] = hist
    tree = jax.tree_util.tree_map(
        lambda x: jnp.asarray(np.asarray(x), jnp.float32), params)
    return np.asarray(_REF_JIT[key](tree["embed"], tree, len(hist) - 1,
                                    padded))


def test_the_padded_reference_is_the_reference():
    params = lm.init_params(CFG, N_ITEMS + 1, seed=2)
    hist = draw_histories([37], seed=6)[0]
    np.testing.assert_allclose(
        reference_scores(params, ref_cfg(), hist),
        ref.next_item_scores(params, ref_cfg(), hist), rtol=1e-5, atol=1e-6)


def program_logits(params, cfg, states_row):
    table = np.asarray(params["embed"], np.float32)
    return table @ np.asarray(states_row) / cfg.logits_scaling


def rel_err(got, want):
    return float(np.abs(got - want).max() / (want.max() - want.min()))


# -- the reference's two forms, and the program against both -----------------

@pytest.mark.parametrize("n", [1, 5, CHUNK, CHUNK + 1, 3 * CHUNK + 7])
def test_the_recurrence_is_the_quadratic_form(n):
    """The reference's token-by-token scan and its every-pair sum (in
    row blocks of 8, so that blocks and their padding are at work) give
    one result, whole model, on logits."""
    params = lm.init_params(CFG, N_ITEMS + 1, seed=3)
    hist = draw_histories([n], seed=n)[0]
    a = ref.next_item_scores(params, ref_cfg(), hist, form="recurrence")
    old = ref.ROW_BLOCK
    ref.ROW_BLOCK = 8
    try:
        b = ref.next_item_scores(params, ref_cfg(), hist, form="quadratic")
    finally:
        ref.ROW_BLOCK = old
    assert rel_err(b, a) < 2e-5


def test_the_quadratic_form_in_blocks_is_the_scan_itself():
    rng = np.random.default_rng(0)
    n, H, P, N = 37, 3, 4, 5
    x = jnp.asarray(rng.standard_normal((n, H, P)), jnp.float32)
    dt = jnp.asarray(rng.uniform(0.01, 0.5, (n, H)), jnp.float32)
    A = -jnp.asarray(rng.uniform(1, 16, H), jnp.float32)
    B, C = (jnp.asarray(rng.standard_normal((n, N)), jnp.float32)
            for _ in range(2))
    with jax.default_matmul_precision("highest"):
        want = ref.scan_recurrence(x, dt, A, B, C)
        for block in (4, 37, 128):
            got = ref.scan_quadratic(x, dt, A, B, C, row_block=block)
            np.testing.assert_allclose(np.asarray(got), np.asarray(want),
                                       rtol=2e-5, atol=2e-6)


@pytest.mark.parametrize("n", [3, CHUNK - 1, CHUNK, CHUNK + 1, 2 * CHUNK,
                               5 * CHUNK + 3])
@pytest.mark.parametrize("form", ["recurrence", "quadratic"])
def test_the_packed_forward_matches_the_reference(n, form):
    """One history in a stream (shorter than a chunk, exactly one, one
    more, several) against both forms of the reference, on logits."""
    params = lm.init_params(CFG, N_ITEMS + 1, seed=5)
    hist = draw_histories([n], seed=100 + n)[0]
    states, _c = forward(params, CFG, pack([hist], 128))
    want = ref.next_item_scores(params, ref_cfg(), hist, form=form)
    assert rel_err(program_logits(params, CFG, states[n - 1]), want) < 2e-5


def test_history_boundaries_inside_one_chunk():
    """Several histories whose starts fall inside one chunk (and one
    that runs over three), each position's state against the history
    alone through the reference; the counters say what the chunks held."""
    lengths = [3, 2, 7, 1, 40, 5, 9]            # starts at 3, 5, 12, 13, 53
    params = lm.init_params(CFG, N_ITEMS + 1, seed=7)
    hists = draw_histories(lengths, seed=11)
    states, counters = forward(params, CFG, pack(hists, 128))
    at = 0
    for h in hists:
        want = ref.next_item_scores(params, ref_cfg(), h)
        got = program_logits(params, CFG, states[at + len(h) - 1])
        assert rel_err(got, want) < 2e-5
        at += len(h)
    need = ref.expected_counts([lengths], ref_cfg())
    # the fourth counter: no chunk goes through the kernel off the TPU
    assert dict(zip(lm.COUNTERS, np.asarray(counters).tolist())) == {
        **need, "ssmKernelChunks": 0}
    assert need["ssmChunks"] == 3 * -(-sum(lengths) // CHUNK)
    assert need["ssmResetsInChunk"] == 3 * 6    # all but the stream's first


def test_a_historys_logits_do_not_change_with_what_is_packed_before_it():
    """The state and the convolution leak nothing: the same history
    behind three different neighbours (none, a short one ending inside
    the chunk, a long one ending on a chunk's last token) gives the same
    states."""
    params = lm.init_params(CFG, N_ITEMS + 1, seed=9)
    mine = draw_histories([29], seed=1)[0]
    alone, _ = forward(params, CFG, pack([mine], 128))
    want = np.asarray(alone[:29])
    for before in ([5], [2 * CHUNK], [7, 1, CHUNK + 3]):
        others = draw_histories(before, seed=sum(before))
        packed, _ = forward(params, CFG, pack(others + [mine], 128))
        at = sum(before)
        np.testing.assert_allclose(np.asarray(packed[at:at + 29]), want,
                                   rtol=2e-5, atol=2e-6)
    # and a neighbour BEHIND it changes nothing either (causal)
    packed, _ = forward(params, CFG,
                        pack([mine] + draw_histories([40], seed=4), 128))
    np.testing.assert_allclose(np.asarray(packed[:29]), want, rtol=2e-5,
                               atol=2e-6)


@pytest.mark.parametrize("side", ["left", "right"])
def test_a_padded_layout_goes_through_the_same_function(side):
    """Rows padded on the left (the trainer's, `pio eval`'s) or on the
    right, one row a segment, flattened into one stream: the pads of one
    row stand between two histories and carry nothing across."""
    params = lm.init_params(CFG, N_ITEMS + 1, seed=13)
    hists = draw_histories([5, 32, 17, 1], seed=21)
    L = 32
    rows = np.zeros((len(hists), L), np.int32)
    for r, h in enumerate(hists):
        if side == "left":
            rows[r, L - len(h):] = h
        else:
            rows[r, :len(h)] = h
    stream = tuple(np.asarray(a) for a in rows_to_stream(jnp.asarray(rows)))
    states, _c = forward(params, CFG, stream)
    for r, h in enumerate(hists):
        last = r * L + (L - 1 if side == "left" else len(h) - 1)
        want = ref.next_item_scores(params, ref_cfg(), h)
        assert rel_err(program_logits(params, CFG, states[last]), want) < 2e-5


# -- the mixer's parts alone --------------------------------------------------

@pytest.mark.parametrize("chunk", [4, 16, 64, 256])
def test_the_chunked_scan_against_the_recurrence_a_history(chunk):
    """`ssd_scan` over a stream of histories and padding at every chunk
    size (smaller than a history, larger than the stream) against the
    reference's recurrence over each history alone."""
    rng = np.random.default_rng(chunk)
    lengths, T, H, P, N = [9, 1, 30, 16, 4], 70, 3, 4, 5
    seg = np.zeros(T, np.int32)
    at = 0
    for j, n in enumerate(lengths):
        seg[at:at + n] = j + 1
        at += n
    x = jnp.asarray(rng.standard_normal((T, H, P)), jnp.float32)
    dt = jnp.asarray(rng.uniform(0.001, 0.5, (T, H)), jnp.float32)
    A = -jnp.asarray(rng.uniform(1, 16, H), jnp.float32)
    B, C = (jnp.asarray(rng.standard_normal((T, N)), jnp.float32)
            for _ in range(2))
    got = lm.ssd_scan(x, dt, A, B, C, lm.segment_runs(jnp.asarray(seg)),
                      chunk, jnp.float32)
    at = 0
    for n in lengths:
        s = slice(at, at + n)
        want = ref.scan_recurrence(x[s], dt[s], A, B[s], C[s])
        np.testing.assert_allclose(np.asarray(got[s]), np.asarray(want),
                                   rtol=2e-5, atol=2e-6)
        at += n


def _segments(lengths, T):
    return pack(draw_histories(lengths), T)[1]


def _padded_rows(side):
    """Four rows of 192 (a chunk and a half), flattened: the trainer's
    layout, one row's pads between two histories."""
    rows = np.zeros((4, 192), np.int32)
    for r, n in enumerate([5, 192, 130, 1]):
        if side == "left":
            rows[r, 192 - n:] = 1
        else:
            rows[r, :n] = 1
    return np.asarray(rows_to_stream(jnp.asarray(rows))[1])


#: segment ids of a stream of three chunks of 256, or of one (two blocks
#: of 128 a chunk, so that the block above the diagonal is skipped)
SCAN_LAYOUTS = {
    "a start inside a chunk": _segments([300, 200], 768),
    "a start on a chunk's first token": _segments([256, 300], 768),
    "several starts in one chunk": _segments(
        [10, 50, 3, 1, 100, 60, 30, 2, 200], 768),
    "one history over many chunks": _segments([768], 768),
    "chunks of padding alone": _segments([100], 768),
    "a stream of one chunk": _segments([100, 120], 256),
    "padded on the left": _padded_rows("left"),
    "padded on the right": _padded_rows("right"),
}


@pytest.mark.parametrize("dtype,limit", [("float32", 1e-5),
                                         ("bfloat16", 2e-2)])
@pytest.mark.parametrize("layout", sorted(SCAN_LAYOUTS))
def test_the_scan_kernel_is_the_chunked_scan_and_the_recurrence(
        layout, dtype, limit):
    """`ssd_scan_kernel` (interpreted here) at the published head size,
    state size and chunk, two heads a block: against `ssd_scan` with the
    skip term, to rounding in either dtype (the casts stand where
    `ssd_scan`'s do, so bfloat16 inputs round alike), and against the
    reference's recurrence over every history alone (`limit` of the
    spread: float32 to rounding, bfloat16 matmul inputs to theirs)."""
    seg = SCAN_LAYOUTS[layout]
    T, H, P, N, Q = len(seg), 4, 64, 128, 256
    rng = np.random.default_rng(len(layout))
    x = jnp.asarray(rng.standard_normal((T, H, P)), jnp.float32)
    dt = jnp.asarray(rng.uniform(0.001, 0.1, (T, H)), jnp.float32)
    A = -jnp.asarray(rng.uniform(1, 16, H), jnp.float32)
    B, C = (jnp.asarray(rng.standard_normal((T, N)), jnp.float32)
            for _ in range(2))
    D = jnp.asarray(rng.standard_normal(H), jnp.float32)
    runs = lm.segment_runs(jnp.asarray(seg))
    cd = jnp.dtype(dtype)
    got = np.asarray(lm.ssd_scan_kernel(
        jnp.concatenate([x.reshape(T, H * P), B, C], axis=1).T, dt, A, runs,
        d_state=N, chunk=Q, cd=cd, skip=D, heads_block=2, interpret=True)
    ).T.reshape(T, H, P)
    skip = np.asarray(x * D[:, None])
    want = np.asarray(lm.ssd_scan(x, dt, A, B, C, runs, Q, cd)) + skip
    spread = float(want.max() - want.min())
    assert np.abs(got - want).max() < 1e-5 * spread
    np_runs = np.asarray(runs)
    for run in np.unique(np_runs[seg > 0]):
        s = np.flatnonzero(np_runs == run)
        s = slice(s[0], s[-1] + 1)
        alone = np.asarray(ref.scan_recurrence(x[s], dt[s], A, B[s], C[s]))
        assert np.abs(got[s] - alone - skip[s]).max() < limit * spread


@pytest.mark.parametrize("T,Q,H,P,N,backend,differentiable,want", [
    (8192, 256, 64, 64, 128, "tpu", False, "kernel"),   # the published
    (1024, 256, 64, 64, 128, "tpu", False, "kernel"),   # widths, served
    (256, 128, 2, 64, 128, "tpu", False, "kernel"),     # two heads a block
    (8192, 256, 64, 64, 128, "cpu", False, "xla"),
    (8192, 256, 64, 64, 128, "tpu", True, "xla"),       # no gradient
    (8000, 256, 64, 64, 128, "tpu", False, "xla"),      # a part chunk
    (8192, 64, 64, 64, 128, "tpu", False, "xla"),       # chunk under 128
    (8192, 256, 64, 64, 16, "tpu", False, "xla"),       # a state under 128
    (8192, 256, 3, 64, 128, "tpu", False, "xla"),       # B^T 192 rows down
    (8192, 256, 64, 8, 128, "tpu", False, "xla"),       # a head under a tile
    (8192, 1024, 64, 64, 128, "tpu", False, "xla"),     # blocks over VMEM
    (8192, 256, 64, 64, 512, "tpu", False, "xla"),      # states over VMEM
    (512, 256, 224, 64, 128, "tpu", False, "kernel"),   # 14 MB: the most
    (128, 16, 8, 16, 8, "tpu", False, "xla"),           # these tests' widths
])
def test_the_scan_kernel_is_chosen_from_backend_shape_and_gradient(
        T, Q, H, P, N, backend, differentiable, want):
    assert lm.scan_kernel_for(T, Q, H, P, N, backend=backend,
                              differentiable=differentiable) == want


def test_the_forward_runs_the_scan_kernel_where_the_tpu_would(monkeypatch):
    """With the backend said to be the TPU and widths the kernel takes
    (heads of 64, a state of 128, chunks of 128) the forward runs every
    Mamba layer's scan as `ssd_scan_kernel` (interpreted here, like the
    attention kernel beside it) on the convolution's output as it lies:
    the hidden states of the CPU path, its three counters, and the
    fourth saying that every live chunk went through the kernel."""
    cfg = dataclasses.replace(CFG, mamba_n_heads=2, mamba_d_head=64,
                              mamba_d_state=128, mamba_chunk_size=128)
    params = lm.init_params(cfg, N_ITEMS + 1, seed=25)
    stream = pack(draw_histories([130, 3, 61, 40], seed=9), 384)
    plain, c_plain = forward(params, cfg, stream)
    tree = jax.tree_util.tree_map(jnp.asarray, params)
    real_scan, real_attention = lm.ssd_scan_kernel, segment_flash_attention
    taken = []

    def interpreted_scan(uT, *a, **kw):
        taken.append(uT.shape)
        return real_scan(uT, *a, **kw, interpret=True)

    from predictionio_tpu.parallel import ring_attention
    monkeypatch.setattr(lm, "ssd_scan_kernel", interpreted_scan)
    monkeypatch.setattr(
        ring_attention, "segment_flash_attention",
        lambda *a, **kw: real_attention(*a, **kw, interpret=True))
    monkeypatch.setattr(jax, "default_backend", lambda: "tpu")
    kernel, c_kernel = lm.forward_hidden(tree, cfg, *stream)
    # on [x | B | C] as the convolution leaves it (its transpose: the
    # tokens on the lanes), nothing sliced out
    assert taken and set(taken) == {(cfg.conv_dim, 384)}
    np.testing.assert_allclose(np.asarray(kernel[:234]),
                               np.asarray(plain[:234]), rtol=1e-4, atol=1e-5)
    counts = dict(zip(lm.COUNTERS, np.asarray(c_kernel).tolist()))
    assert counts["ssmKernelChunks"] == counts["ssmChunks"] == 3 * 2
    assert counts == {**dict(zip(lm.COUNTERS, np.asarray(c_plain).tolist())),
                      "ssmKernelChunks": 6}


def test_the_convolution_reads_no_tap_across_a_boundary():
    rng = np.random.default_rng(2)
    T, Cw, K = 20, 6, 4
    seg = jnp.asarray([1] * 2 + [2] * 7 + [3] * 1 + [4] * 6 + [0] * 4)
    u = jnp.asarray(rng.standard_normal((T, Cw)), jnp.float32)
    w = jnp.asarray(rng.standard_normal((K, Cw)), jnp.float32)
    b = jnp.asarray(rng.standard_normal(Cw), jnp.float32)
    got = np.asarray(lm.causal_conv(u, w, b, lm.segment_runs(seg)))
    at = 0
    for n in (2, 7, 1, 6):
        want = np.asarray(ref.conv(u[at:at + n], w, b))
        np.testing.assert_allclose(got[at:at + n], want, rtol=1e-6, atol=1e-6)
        at += n


def test_runs_rise_wherever_the_segment_changes():
    seg = jnp.asarray([0, 0, 1, 1, 0, 2, 2, 2, 0, 0, 3])
    assert np.asarray(lm.segment_runs(seg)).tolist() == [
        0, 0, 1, 1, 2, 3, 3, 3, 4, 4, 5]


# -- grouped-query attention at the published scale ---------------------------

@pytest.mark.parametrize("kv_heads", [1, 2, 8])
def test_segment_flash_with_shared_key_value_heads(kv_heads):
    """The kernel (interpret mode here) at heads of 64, 8 query heads
    over 1, 2 or 8 key/value heads, a caller's scale, packed segments
    and padding, against the plain path with the key/value heads
    REPEATED; and its count of unmasked pairs."""
    rng = np.random.default_rng(kv_heads)
    H, L, hd, lens = 8, 256, 64, [100, 37, 90]
    seg = np.zeros(L, np.int32)
    at = 0
    for j, n in enumerate(lens):
        seg[at:at + n] = j + 1
        at += n
    q = rng.standard_normal((1, H, L, hd)).astype(np.float32)
    k, v = (rng.standard_normal((1, kv_heads, L, hd)).astype(np.float32)
            for _ in range(2))
    scale = 1.0 / 64
    out, pairs = segment_flash_attention(
        (jnp.asarray(q),), (jnp.asarray(k),), jnp.asarray(v),
        jnp.asarray(seg)[None], scale=scale, block=128)
    rep = H // kv_heads
    want = segment_attention(
        jnp.asarray((q * scale * np.sqrt(hd)).transpose(0, 2, 1, 3)),
        jnp.asarray(np.repeat(k, rep, axis=1).transpose(0, 2, 1, 3)),
        jnp.asarray(np.repeat(v, rep, axis=1).transpose(0, 2, 1, 3)),
        jnp.asarray(seg)[None], causal=True)
    np.testing.assert_allclose(np.asarray(out).transpose(0, 2, 1, 3),
                               np.asarray(want), rtol=1e-4, atol=1e-5)
    pad = L - sum(lens)
    assert int(pairs) == sum(n * (n + 1) // 2 for n in lens) + pad * (
        pad + 1) // 2


def test_key_value_heads_that_do_not_divide_the_query_heads_are_refused():
    q = jnp.zeros((1, 8, 128, 64))
    kv = jnp.zeros((1, 3, 128, 64))
    with pytest.raises(ValueError, match="do not divide"):
        segment_flash_attention((q,), (kv,), kv, jnp.ones((1, 128), jnp.int32),
                                scale=1.0)


@pytest.mark.parametrize("L,backend,grouped,want", [
    (8192, "tpu", True, "segment_flash"),    # shared heads: never the stock
    (8192, "tpu", False, "stock"),           # heads of 64, one a query head
    (1000, "tpu", True, "plain"),
    (8192, "cpu", True, "plain"),
])
def test_the_kernel_for_shared_key_value_heads_is_named(L, backend, grouped,
                                                        want):
    assert attention_kernel_for(L, 64, 64, backend=backend, segmented=True,
                                grouped=grouped) == want


def test_the_forward_runs_the_kernel_where_the_tpu_would(monkeypatch):
    """With the backend said to be the TPU the forward takes the segment
    kernel (interpreted here) with the grouped heads and the published
    scale, and agrees with the plain path it takes on the CPU; the
    kernel's own count of pairs is the plain path's."""
    params = lm.init_params(CFG, N_ITEMS + 1, seed=15)
    stream = pack(draw_histories([40, 3, 61], seed=5), 128)
    plain, c_plain = forward(params, CFG, stream)
    tree = jax.tree_util.tree_map(jnp.asarray, params)
    real_kernel = segment_flash_attention
    taken = []

    def interpreted(*a, **kw):
        taken.append(kw["scale"])
        return real_kernel(*a, **kw, interpret=True)

    from predictionio_tpu.parallel import ring_attention
    monkeypatch.setattr(ring_attention, "segment_flash_attention",
                        interpreted)
    monkeypatch.setattr(jax, "default_backend", lambda: "tpu")
    kernel, c_kernel = lm.forward_hidden(tree, CFG, *stream)
    assert taken == [CFG.attention_multiplier]
    np.testing.assert_allclose(np.asarray(kernel[:104]),
                               np.asarray(plain[:104]), rtol=1e-4, atol=1e-5)
    assert np.asarray(c_kernel).tolist() == np.asarray(c_plain).tolist()


def test_the_attention_scale_is_the_multiplier_and_not_the_square_root():
    """At the published 1/64 over heads of 64 the scores are an eighth
    of what head_dim^-0.5 gives: the program agrees with the reference
    and not with the reference at the usual scale."""
    cfg = dataclasses.replace(CFG, attention_multiplier=1.0 / 64,
                              num_attention_heads=1, num_key_value_heads=1)
    assert cfg.head_dim == 64
    params = lm.init_params(cfg, N_ITEMS + 1, seed=17)
    # sharper scores than iid rows at 0.02 give, so that the scale shows
    params["attention"]["wq"] *= 40.0
    params["attention"]["wk"] *= 10.0
    hist = draw_histories([50], seed=3)[0]
    states, _ = forward(params, cfg, pack([hist], 128))
    got = program_logits(params, cfg, states[49])
    sound = ref.next_item_scores(params, ref_cfg(cfg), hist)
    usual = ref.next_item_scores(params, ref_cfg(cfg), hist,
                                 variant="sqrt_scale")
    assert rel_err(got, sound) < 2e-5
    assert rel_err(usual, sound) > 1e-3


def test_the_gate_multiplies_before_the_mixers_norm():
    params = lm.init_params(CFG, N_ITEMS + 1, seed=19)
    hist = draw_histories([33], seed=8)[0]
    states, _ = forward(params, CFG, pack([hist], 128))
    got = program_logits(params, CFG, states[32])
    sound = ref.next_item_scores(params, ref_cfg(), hist)
    assert rel_err(got, sound) < 2e-5
    assert rel_err(ref.next_item_scores(
        params, ref_cfg(), hist, variant="norm_before_gate"), sound) > 1e-3


# -- the trainer --------------------------------------------------------------

def test_the_trainers_loss_and_gradients_are_the_references():
    """The packed, chunked, left-padded loss of the trainer against the
    reference's loop over the rows one by one: the value and the
    gradient of every leaf."""
    cfg = dataclasses.replace(CFG, max_len=20)
    params = jax.tree_util.tree_map(
        jnp.asarray, lm.init_params(cfg, N_ITEMS + 1, seed=23))
    rows = np.zeros((4, 20), np.int32)      # a row over a chunk, one event
    for r, h in enumerate(draw_histories([20, 3, 1, 9], seed=2)):
        rows[r, 20 - len(h):] = h
    got, g_got = jax.value_and_grad(lm.next_item_loss)(
        params, cfg, jnp.asarray(rows))
    want, g_want = jax.jit(jax.value_and_grad(
        lambda p: ref.loss(p, ref_cfg(cfg), rows)))(params)
    assert abs(float(got) - float(want)) < 1e-5 * abs(float(want))
    flat_got = jax.tree_util.tree_leaves_with_path(g_got)
    flat_want = dict(jax.tree_util.tree_leaves_with_path(g_want))
    assert len(flat_got) == len(flat_want) == 19
    for path, g in flat_got:
        w = np.asarray(flat_want[path])
        scale = max(float(np.abs(w).max()), 1e-8)
        assert float(np.abs(np.asarray(g) - w).max()) < 2e-4 * scale, path


def test_training_lowers_the_loss():
    cfg = dataclasses.replace(CFG, max_len=16, epochs=20, batch_size=8,
                              lr=1e-2)
    rng = np.random.default_rng(0)
    # every user walks the same cycle of items: learnable
    seqs = np.zeros((16, 16), np.int32)
    for u in range(16):
        start = int(rng.integers(0, 10))
        seqs[u] = (start + np.arange(16)) % 10 + 1
    users = BiMap({f"u{i}": i for i in range(16)})
    items = BiMap({f"i{i}": i for i in range(N_ITEMS)})
    before = float(lm.next_item_loss(
        jax.tree_util.tree_map(jnp.asarray,
                               lm.init_params(cfg, N_ITEMS + 1, cfg.seed)),
        cfg, jnp.asarray(seqs)))
    model = lm.train_hybrid_ssm(seqs, users, items, cfg)
    after = float(lm.next_item_loss(
        jax.tree_util.tree_map(jnp.asarray, model.params), cfg,
        jnp.asarray(seqs)))
    assert after < 0.8 * before
    assert type(model).__name__ == "HybridSSMModel"


# -- the serving route --------------------------------------------------------

@pytest.mark.parametrize("dtype,limit", [("float32", 2e-5),
                                         ("bfloat16", 0.05)])
def test_served_path_matches_the_reference(dtype, limit):
    """Through the retriever, the pipeline's encoder seam and the fused
    top-k over the TIED table divided by logits_scaling: float32 to
    rounding; bfloat16 matmul inputs against the float32 reference
    within 0.05 of the row's logit spread (four random-weight layers at
    hidden 64 read under 0.02 here)."""
    model = make_model(dataclasses.replace(CFG, compute_dtype=dtype))
    if dtype == "bfloat16":
        model.params = lm._stored(model.params, jnp.bfloat16)
    users = [f"u{i}" for i in range(N_USERS)] + ["nobody"]
    answers = model.batch_recommend(users, [5] * len(users))
    assert answers[-1] == []
    worst, checked = 0.0, 0
    for user, answer in zip(users[:-1], answers):
        hist = history(model, user)
        if len(hist) == 0:
            assert answer == []
            continue
        want = reference_scores(model.params, ref_cfg(), hist)[1:]
        got = np.asarray([s for _i, s in answer])
        ids = np.asarray([int(i[1:]) for i, _s in answer])
        assert len(ids) == 5 == len(set(ids.tolist()))
        spread = want.max() - want.min()
        worst = max(worst, float(np.abs(got - want[ids]).max() / spread))
        assert want[ids].min() >= np.sort(want)[-5] - limit * spread
        checked += 1
    assert checked >= 20 and worst <= limit
    seq = model._serving_pipeline().stats()["sequence"]
    lengths = [len(history(model, u)) for u in users[:-1]]
    assert seq["tokensReal"] == sum(lengths)
    assert seq["tokenLattice"] == [128, 256, 512]
    # the device program's own counts, through the encoder seam: exact
    # for the pairs; the chunks between what the tokens need and one
    # part-filled chunk a step more
    assert seq["pairsCausal"] == sum(n * (n + 1) // 2 for n in lengths)
    low = 3 * -(-sum(lengths) // CHUNK)
    assert low <= seq["ssmChunks"] <= low + 3 * seq["steps"]
    assert 0 < seq["ssmResetsInChunk"] <= 3 * seq["rows"]


def test_the_catalog_is_the_tied_table_over_the_logits_scaling():
    model = make_model()
    want = np.asarray(model.params["embed"], np.float32)[1:] / 8.0
    np.testing.assert_array_equal(model.catalog, want)
    assert "head" not in model.params


def test_the_published_widths_count_what_the_model_card_says(monkeypatch):
    """3,191.4 M parameters: 36 Mamba layers of 76.18 M, 4 attention
    layers of 60.82 M (each with its MLP), the tied table 205.5 M."""
    monkeypatch.setattr(lm, "STEP_TOKEN_BUDGET", 8192)
    cfg = lm.HybridSSMConfig()
    assert cfg.layer_types == lm.GRANITE_LAYER_TYPES
    assert [i for i, t in enumerate(cfg.layer_types) if t == "attention"
            ] == [5, 15, 25, 35]
    assert cfg.d_inner == 4096 and cfg.conv_dim == 4352 and cfg.head_dim == 64
    total = lm.param_count(cfg, 100352)
    assert round(total / 1e6, 1) == 3191.4
    shapes = lm.param_shapes(cfg, 100352)
    assert shapes["mamba"]["in_proj"] == (36, 2048, 8512)
    assert shapes["attention"]["wk"] == (4, 2048, 512)
    assert shapes["mlp"]["w_in"] == (40, 2048, 16384)


def test_config_says_what_it_cannot_hold(monkeypatch):
    with pytest.raises(ValueError, match="layer_types"):
        dataclasses.replace(CFG, num_hidden_layers=5)
    with pytest.raises(ValueError, match="granite-4.0-h-micro publishes"):
        dataclasses.replace(CFG, position_embedding_type="rope")
    with pytest.raises(ValueError, match="granite-4.0-h-micro publishes"):
        dataclasses.replace(CFG, mamba_n_groups=2)
    with pytest.raises(ValueError, match="mamba_expand"):
        dataclasses.replace(CFG, mamba_n_heads=4)
    with pytest.raises(ValueError, match="num_key_value_heads"):
        dataclasses.replace(CFG, num_key_value_heads=3)
    with pytest.raises(ValueError, match="over a serving step"):
        dataclasses.replace(CFG, max_len=513)
    monkeypatch.setattr(lm, "STEP_TOKEN_BUDGET", 8192)
    with pytest.raises(ValueError, match="exclude_seen"):
        lm.HybridSSMConfig(exclude_seen=True)
    # a list from engine.json is the tuple
    assert dataclasses.replace(
        CFG, layer_types=list(CFG.layer_types)).layer_types == CFG.layer_types


def test_the_bfloat16_tree_survives_serialization():
    """The persisted blob keeps bfloat16 matrices bfloat16 and the
    float32 vectors float32, and the model answers the same after."""
    from predictionio_tpu.workflow.serialization import (deserialize_models,
                                                         serialize_models)

    model = make_model(dataclasses.replace(CFG, compute_dtype="bfloat16"))
    model.params = lm._stored(model.params, jnp.bfloat16)
    before = model.batch_recommend(["u1", "u2"], [4, 4])
    back = deserialize_models(serialize_models([model]))[0]
    assert back.config == model.config
    assert back.params["mamba"]["in_proj"].dtype == jnp.bfloat16
    assert back.params["embed"].dtype == jnp.bfloat16
    for name in ("A_log", "dt_bias", "D", "conv_w", "norm"):
        assert back.params["mamba"][name].dtype == np.float32
    np.testing.assert_array_equal(
        np.asarray(back.params["mlp"]["w_out"], np.float32),
        np.asarray(model.params["mlp"]["w_out"], np.float32))
    assert back.seqs.dtype == np.int32
    assert back.batch_recommend(["u1", "u2"], [4, 4]) == before


def test_exclude_seen_within_the_heads_reach():
    cfg = dataclasses.replace(CFG, exclude_seen=True)
    model = make_model(cfg)
    user = next(f"u{i}" for i in range(N_USERS)
                if len(history(model, f"u{i}")) > 10)
    answer = model.recommend_products(user, 5)
    seen = {f"i{int(t) - 1}" for t in history(model, user)}
    assert len(answer) == 5 and not seen & {i for i, _s in answer}
    assert model.serving_ks != (16,)
    assert make_model().serving_ks == (16,)


@pytest.mark.parametrize("case, expected", [
    ("one reader", "pio.seq.ssm_scan"),
    ("through a nameless bitcast", "pio.seq.ssm_scan"),
    ("two readers that disagree", None),
    ("a reader outside every scope", None),
    ("read by a container alone", None),
])
def test_a_nameless_operation_takes_the_scope_of_what_reads_it(case,
                                                               expected):
    """obs/trace.DeviceScopes: the copy the compiler puts before a
    reshape that is no bitcast carries no `op_name`; the Mamba mixer has
    two such relayouts of [T, 4096] a layer, which `ssm_scan_share`
    has to contain. It takes the scope of the
    operations that read it where they agree, and stays nameless
    otherwise."""
    from predictionio_tpu.obs.trace import DeviceScopes

    scan = ('metadata={op_name="jit(fn)/pio.seq.layers/while/body/'
            'closed_call/pio.seq.ssm_scan/reshape"}')
    mlp = 'metadata={op_name="jit(fn)/pio.seq.layers/pio.seq.mlp/dot"}'
    copy = "  %copy.9 = f32[512,8,32,256]{3,2,1,0:T(8,128)} copy(%bitcast.1)"
    readers = {
        "one reader": [
            f"  %bitcast.2 = f32[32,256,64,64]{{1,0,3,2}} bitcast(%copy.9), "
            f"{scan}"],
        "through a nameless bitcast": [
            "  %bitcast.2 = f32[32,256,64,64]{1,0,3,2} bitcast(%copy.9)",
            f"  %fusion.3 = f32[8]{{0}} fusion(%p.0, /*index=1*/%bitcast.2), "
            f"kind=kLoop, calls=%fused.1, {scan}"],
        "two readers that disagree": [
            f"  %fusion.3 = f32[8]{{0}} fusion(%copy.9), kind=kLoop, {scan}",
            f"  %fusion.4 = f32[8]{{0}} fusion(%copy.9), kind=kLoop, {mlp}"],
        "a reader outside every scope": [
            f"  %fusion.3 = f32[8]{{0}} fusion(%copy.9), kind=kLoop, {scan}",
            '  %add.4 = f32[8]{0} add(%copy.9, %copy.9), '
            'metadata={op_name="jit(fn)/add"}'],
        "read by a container alone": [
            "  %while.3 = (f32[8]{0}) while(%copy.9), condition=%c, body=%b, "
            + scan],
    }[case]
    scopes = DeviceScopes()
    scopes.record("\n".join([copy] + readers))
    assert scopes.snapshot().get(
        "%copy.9 = f32[512,8,32,256]{3,2,1,0:T(8,128)} copy") == expected


@pytest.mark.parametrize("engine", ["templates/recommendation/engine.py",
                                    "benchmarks/engine/engine.py"])
def test_the_als_engines_load_nothing_of_the_hybrid_decoder(engine):
    """The ALS cells' processes (`pio train` / `pio deploy` of
    benchmarks/engine, which is templates/recommendation behind a drawn
    DataSource) never load models/hybrid_ssm_lm.py or the template that
    imports it, so no change to the decoder can reach those cells."""
    code = (
        "import importlib.util, sys\n"
        "import predictionio_tpu.tools.cli\n"
        "import predictionio_tpu.workflow.create_server\n"
        f"spec = importlib.util.spec_from_file_location('e', {str(REPO / engine)!r})\n"
        "mod = importlib.util.module_from_spec(spec); sys.modules['e'] = mod\n"
        "spec.loader.exec_module(mod)\n"
        "print([m for m in sys.modules if 'hybrid_ssm' in m or 'seqrec' in m])\n")
    out = subprocess.run([sys.executable, "-c", code], cwd=REPO, text=True,
                         capture_output=True, timeout=120)
    assert out.returncode == 0, out.stderr[-2000:]
    assert out.stdout.strip().splitlines()[-1] == "[]"


def test_pio_train_then_deploy_of_hybrid_ssm_answers_through_the_batcher(
        tmp_path, rng):
    """`pio train` -> the deploy-time attach (retriever, encoder,
    pipeline, prewarm over the token lattice) -> queries through the
    micro-batcher, cut by tokens: /stats.json shows the pipeline, the
    kernel and the `sequence` counters the device program counted. No
    sleep; the asks share one timeout."""
    from predictionio_tpu.storage import Storage
    from predictionio_tpu.tools.cli import main as pio
    from predictionio_tpu.workflow import resolve_engine_factory
    from predictionio_tpu.workflow.create_server import EngineServer
    from tests.test_quickstart_e2e import make_events_file

    engine_dir = tmp_path / "myseq"
    shutil.copytree(REPO / "templates" / "seqrec", engine_dir)
    variant = json.loads((engine_dir / "engine.json").read_text())
    variant["datasource"]["params"]["app_name"] = "hybridtest"
    published = {k: getattr(CFG, k) for k in ref.CONFIG_KEYS}
    published["layer_types"] = list(CFG.layer_types)   # as JSON has it
    variant["algorithms"] = [{"name": "hybrid_ssm", "params": {
        **published, "max_len": 16, "compute_dtype": "float32", "epochs": 1,
        "batch_size": 16}}]
    (engine_dir / "engine.json").write_text(json.dumps(variant))
    assert pio(["app", "new", "hybridtest"]) == 0
    app = Storage.get_metadata().app_get_by_name("hybridtest")
    events = tmp_path / "events.jsonl"
    make_events_file(events, rng)
    assert pio(["import", "--appid", str(app.id), "--input",
                str(events)]) == 0
    assert pio(["train", "--engine-dir", str(engine_dir)]) == 0
    inst = Storage.get_metadata().engine_instance_get_completed(
        "default", "1", "default")[0]
    engine = resolve_engine_factory("engine:engine_factory",
                                    engine_dir=engine_dir)
    server = EngineServer(engine, inst)
    model = server.deployed.result.models[0]
    assert type(model).__name__ == "HybridSSMModel"
    assert model.params["mamba"]["in_proj"].shape[0] == 3
    user = next(u for u in model.user_ids if len(history(model, u)) > 1)
    cost_of, budget = server._costing()
    assert budget == model.serving_cost_budget == 512
    assert cost_of({"user": user, "num": 4}) == len(history(model, user))
    assert cost_of({"user": "nobody"}) == 0

    async def ask():
        try:
            return await asyncio.wait_for(asyncio.gather(*[
                server.batcher.submit({"user": user, "num": 4})
                for _ in range(3)]), timeout=120)
        finally:
            await server.batcher.close()

    answers = asyncio.run(ask())
    assert all(len(a["itemScores"]) == 4 for a in answers)
    cfg16 = {**ref_cfg(), "max_len": 16}
    want = ref.next_item_scores(model.params, cfg16, history(model, user))[1:]
    for s in answers[0]["itemScores"]:
        assert abs(s["score"] - want[model.item_ids.get(s["item"])]) <= (
            1e-4 * (want.max() - want.min()))
    stats = server.serving_stats()
    assert stats["pipeline"]["mode"] == "fused"
    seq = stats["sequence"]
    n = len(history(model, user))
    assert seq["steps"] >= 1 and seq["tokenBudget"] == 512
    assert seq["pairsCausal"] == 3 * n * (n + 1) // 2
    assert seq["ssmChunks"] >= 3 and "ssmResetsInChunk" in seq
    assert stats["batching"]["batchedQueries"] == 3
    phases = [name for name, *_ in stats["startup"]["phases"]]
    assert "pio.deploy.attach_encoder" in phases
    assert "pio.deploy.prewarm" in phases
