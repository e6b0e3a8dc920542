"""Compile for TPU v5e without a chip.

libtpu ships a compile-only client: ``get_topology_desc(platform="tpu",
topology_name="v5e:2x2")`` gives four ``TPU v5 lite`` devices that can be
lowered and compiled against — the real XLA:TPU and Mosaic compilers —
but never run on. Every program the chip-holding verbs put on the device
(the Pallas top-k kernel alone and fused into the serving pipeline's
program, the ALS train step on one and on four devices, the sharded
retriever, the stock flash-attention kernel) is compiled here, so a
kernel change Mosaic refuses turns tier-1 red before it costs chip time.

What this cannot show is anything a run shows: results, HBM at run time,
time. `python chip_smoke.py` on the chip does that.
"""

import functools
import re

import jax
import jax.numpy as jnp
import numpy as np
import pytest
from jax.sharding import Mesh, NamedSharding, PartitionSpec as P
from jax.sharding import SingleDeviceSharding

from predictionio_tpu.models.als import _layout_shardings, make_train_step
from predictionio_tpu.ops.neighbors import build_bilinear_layout
from predictionio_tpu.ops.pipeline import _capacity, _fused_fn
from predictionio_tpu.ops.retrieval import (ShardedDeviceRetriever, _lanes,
                                            _padded_shape, _query_shapes,
                                            _raw_call, _tile_rows)

#: the smoke's catalog (ML-20M's items at rank 64) and query table
N_ITEMS, N_USERS, RANK = 26_744, 138_493, 64

#: the serving cells' catalog and query table (benchmarks/configs/
#: als-amazon18.json): 3.89 GB on the chip, so shapes only
CELL_ITEMS, CELL_USERS = 15_200_000, 1_000_000


@pytest.fixture(scope="module")
def v5e():
    """The four compile-only v5e devices, or a skip that says why."""
    from jax.experimental import topologies

    try:
        topo = topologies.get_topology_desc(platform="tpu",
                                            topology_name="v5e:2x2")
    except Exception as e:  # noqa: BLE001 — any failure here means: skip
        pytest.skip(f"no compile-only TPU client in this installation: "
                    f"{type(e).__name__}: {e}")
    assert topo.devices[0].device_kind == "TPU v5 lite"
    return topo.devices


def _compile_kernel(dev, n_items, b_pad, k_pad, rank=RANK):
    d_pad, n_pad = _padded_shape(n_items, rank)
    one = SingleDeviceSharding(dev)
    return jax.jit(_raw_call(b_pad, d_pad, n_pad, n_items, k_pad,
                             False)).lower(
        jax.ShapeDtypeStruct((b_pad, _lanes(d_pad)), jnp.float32,
                             sharding=one),
        jax.ShapeDtypeStruct((d_pad, n_pad), jnp.float32, sharding=one),
    ).compile()


def _compile_fused(dev, n_items, n_users, b_pad, k_pad):
    d_pad, n_pad = _padded_shape(n_items, RANK)
    cap = _capacity(n_users)
    one = SingleDeviceSharding(dev)
    raw = _raw_call(b_pad, d_pad, n_pad, n_items, k_pad, False)
    # as ServingPipeline._exec_fused builds it on a TPU: donating
    return jax.jit(_fused_fn(raw, True), donate_argnums=(0,)).lower(
        jax.ShapeDtypeStruct((b_pad,), jnp.int32, sharding=one),
        jax.ShapeDtypeStruct((cap, _lanes(d_pad)), jnp.float32,
                             sharding=one),
        jax.ShapeDtypeStruct((d_pad, n_pad), jnp.float32, sharding=one),
    ).compile()


def _assert_one_kernel_and_the_packed_result(exe, b_pad, k_pad):
    hlo = exe.as_text()
    # exactly one Mosaic kernel a dispatch: the benchmark's kernel time
    # and rooflines count `custom-call` operations per call
    assert hlo.count("custom_call_target=\"tpu_custom_call\"") == 1
    # values, rows and the kernel's three counters in one buffer
    assert (exe.memory_analysis().output_size_in_bytes
            >= b_pad * (2 * k_pad + 3) * 4)


def test_fused_pipeline_program_over_the_prewarm_lattice(v5e):
    """`pio deploy` prewarms b_pad 8..128 at the k_pad of num=10; each is
    gather + the native Pallas kernel + packing in ONE program."""
    for b in (1, 8, 16, 32, 64, 128):
        b_pad, k_pad = _query_shapes(b, 10, N_ITEMS)
        exe = _compile_fused(v5e[0], N_ITEMS, N_USERS, b_pad, k_pad)
        _assert_one_kernel_and_the_packed_result(exe, b_pad, k_pad)


@pytest.mark.parametrize("b_pad", [8, 32, 128])
def test_topk_kernel_at_the_serving_cells_shape(v5e, b_pad):
    """15.2 M items along the lanes at rank 64, k_pad 16: the kernel
    alone and inside the fused program, shapes only. What Mosaic refuses
    at this size (a dynamic loop, an unaligned store, a tile over the
    VMEM budget) fails here, not on the chip."""
    d_pad, n_pad = _padded_shape(CELL_ITEMS, RANK)
    assert d_pad == RANK  # lane-dense: no byte of the scan is a pad's
    tile, chunk = _tile_rows(b_pad, d_pad, 16, n_pad)
    assert 2048 <= tile <= 16384 and n_pad % tile == 0 and tile % chunk == 0
    _compile_kernel(v5e[0], CELL_ITEMS, b_pad, 16)
    exe = _compile_fused(v5e[0], CELL_ITEMS, CELL_USERS, b_pad, 16)
    _assert_one_kernel_and_the_packed_result(exe, b_pad, 16)
    # the dense catalog (3.89 GB), the user table (gathered by row and
    # never scanned: whole 128-lane rows, 0.58 GB) and little else
    assert 4.4e9 < exe.memory_analysis().argument_size_in_bytes < 4.6e9


def test_topk_kernel_at_a_large_k(v5e):
    """A client may ask for hundreds: num=500 pads to k_pad 504, and the
    kept lists (four lane groups wide) take room beside the tile."""
    for b_pad, k_pad in ((8, 504), (128, 504)):
        _compile_kernel(v5e[0], N_ITEMS, b_pad, k_pad)


@pytest.mark.parametrize("rank", [10, 32, 100, 128])
def test_topk_kernel_at_the_ranks_a_template_may_train(v5e, rank):
    """The layout adapts to the rank alone: 10 (the recommendation
    template's default) takes 16 sublanes, 100 takes 104, and the tile
    shrinks as the rank grows so that two buffers of it fit VMEM."""
    d_pad, n_pad = _padded_shape(CELL_ITEMS, rank)
    assert d_pad == -(-rank // 8) * 8
    tile, _ = _tile_rows(8, d_pad, 16, n_pad)
    assert tile == (16384 if d_pad <= 64 else 8192)
    for b_pad, k_pad in ((8, 16), (128, 504)):
        _compile_kernel(v5e[0], CELL_ITEMS, b_pad, k_pad, rank=rank)


def _compile_train_step(v5e, n_dev, u_lay, i_lay):
    mesh = Mesh(np.asarray(v5e[:n_dev]), ("data",))
    blk, rep = _layout_shardings(mesh)

    def spec(lay):
        out = []
        for b, m in zip(lay.buckets, lay.metas):
            e = {name: jax.ShapeDtypeStruct(a.shape, a.dtype, sharding=blk)
                 for name, a in (("ids", b.ids), ("vals", b.vals),
                                 ("hot_ids", b.hot_ids),
                                 ("hot_vals", b.hot_vals)) if a is not None}
            if m.seg is not None:
                e["seg"] = jax.ShapeDtypeStruct(m.seg.shape, m.seg.dtype,
                                                sharding=rep)
            out.append(e)
        return out

    fac = NamedSharding(mesh, P(None, None))
    return make_train_step(mesh, u_lay, i_lay, rank=RANK, lambda_=0.01).lower(
        spec(u_lay), spec(i_lay),
        jax.ShapeDtypeStruct((u_lay.slots, RANK), jnp.float32, sharding=fac),
        jax.ShapeDtypeStruct((i_lay.slots, RANK), jnp.float32, sharding=fac),
    ).compile()


def _factor_row_gathers(hlo):
    """(rows of the gathered table, rows gathered, table kept in VMEM?,
    the gather's `integer_config`) of every factor-row gather inside a
    `lax.map` body of a compiled program. A fused gather's operand is a
    parameter of its computation, declared there with its memory space
    (`S(1)`: VMEM); the configuration is on the fusion's call."""
    import re

    comps = {}
    for comp in re.split(r"\n\n", hlo):
        head = re.match(r"%?(\S+) \(", comp.lstrip("\n"))
        if head:
            comps[head.group(1)] = comp
    out = []
    for call in re.finditer(r"fusion\([^\n]*calls=%?([\w.]+)[^\n]*", hlo):
        comp = comps.get(call.group(1), "")
        m = re.search(r"= f32\[(\d+),(\d+),\d+\]\S* gather\(%?([\w.-]+), "
                      r"[^\n]*while/body", comp)
        if not m:
            continue
        table = re.search(r"%?" + re.escape(m.group(3))
                          + r" = f32\[(\d+),\d+\]\{([^}]*)\}", comp)
        config = re.search(r'"integer_config":\{"integer":"(\d+)"',
                           call.group(0))
        out.append((int(table.group(1)), int(m.group(1)) * int(m.group(2)),
                    "S(1)" in table.group(2), int(config.group(1))))
    return out


@pytest.mark.parametrize("nb,b,d_cold,d_hot,table_rows,chunked", [
    (52, 960, 1016, 1032, 1_001_568, True),   # als-kdd11's item side
    (6, 1584, 560, 584, 1_001_568, False),
    (6, 8104, 136, 88, 1_001_568, False),
    (7, 960, 488, 1560, 625_392, True),       # and its user side
    (9, 6752, 88, 200, 625_392, False),
    (15, 8176, 16, 16, 625_392, False),
])
def test_hot_slice_is_gathered_from_vmem(v5e, nb, b, d_cold, d_hot,
                                         table_rows, chunked):
    """What makes a hot row cheap, read off the compiled `_gram_blocks` at
    the train cell's own bucket shapes (the probe's times are the chip's;
    the compiler's choices behind them show here): the 147,456-row slice
    is kept in VMEM across the `lax.map` (the hot part must come first in
    the body for that), the whole table is not, and a cold block whose
    count of ids is no multiple of 1,024 gets the wider of the two
    HBM-gather configurations."""
    from predictionio_tpu.models.als import _gram_blocks
    from predictionio_tpu.ops.neighbors import GATHER_NS_BY_TABLE_ROWS

    slice_rows = GATHER_NS_BY_TABLE_ROWS[-1][0]
    assert (b * d_cold) % 1024

    def run(table, ids, vals, hot_ids, hot_vals):
        hot = jax.lax.slice_in_dim(table, 1000, 1000 + slice_rows)
        kw = {} if chunked else dict(out_dtype=jnp.float32, with_diag=True)
        return _gram_blocks(ids, vals, table, implicit=False, alpha=1.0,
                            rank=RANK, hot=(hot, hot_ids, hot_vals), **kw)

    one = SingleDeviceSharding(v5e[0])
    sds = functools.partial(jax.ShapeDtypeStruct, sharding=one)
    hlo = jax.jit(run).lower(
        sds((table_rows, RANK), jnp.float32),
        sds((nb, b, d_cold), jnp.int32), sds((nb, b, d_cold), jnp.float32),
        sds((nb, b, d_hot), jnp.int32), sds((nb, b, d_hot), jnp.float32),
    ).compile().as_text()
    assert sorted(_factor_row_gathers(hlo)) == [
        (slice_rows, b * d_hot, True, 0), (table_rows, b * d_cold, False, 256)]


@pytest.mark.parametrize("n_dev", [1, 4])
def test_als_train_step(v5e, n_dev):
    """`make_train_step` over a small layout on the 1x and the 4x1 data
    mesh `pio train` builds by default (`make_mesh()` takes every device).
    Uniform popularity: nothing is split, one gather a tier."""
    rng = np.random.default_rng(0)
    nu, ni, n = 512, 256, 8_000
    u_lay, i_lay = build_bilinear_layout(
        rng.integers(0, nu, n), rng.integers(0, ni, n),
        (np.round(rng.random(n) * 9 + 1) / 2).astype(np.float32), nu, ni)
    hlo = _compile_train_step(v5e, n_dev, u_lay, i_lay).as_text()
    tables = [g[0] for g in _factor_row_gathers(hlo)]
    assert sorted(tables) == sorted(
        [i_lay.slots] * len(u_lay.buckets) + [u_lay.slots] * len(i_lay.buckets))


@pytest.mark.parametrize("n_dev", [1, 4])
def test_als_train_step_with_hot_slices(v5e, n_dev, monkeypatch):
    """A Zipf data set whose tables are sliced: every split tier compiles
    to two gathers, one of them from an operand of the slice's rows (the
    slice is materialised once a half-step, not fused back into a gather
    from the whole table, whose size is what makes a row slow)."""
    from predictionio_tpu.ops import neighbors
    from tests.helpers import SMALL_HOT_SLICES, zipf_coo

    monkeypatch.setattr(neighbors, "GATHER_NS_BY_TABLE_ROWS",
                        SMALL_HOT_SLICES)
    users, items, vals = zipf_coo(np.random.default_rng(0))
    u_lay, i_lay = build_bilinear_layout(users, items, vals, 600, 400,
                                         chunk_cap=256)
    assert 100 < u_lay.hot_rows <= 128 and 100 < i_lay.hot_rows <= 128
    gathers = _factor_row_gathers(
        _compile_train_step(v5e, n_dev, u_lay, i_lay).as_text())
    for lay in (u_lay, i_lay):
        assert sum(b.hot_ids is not None for b in lay.buckets) >= 3
    tables = sorted(g[0] for g in gathers)
    assert tables == sorted(
        [i_lay.slots] * len(u_lay.buckets) + [u_lay.slots] * len(i_lay.buckets)
        + [lay.hot_rows for lay in (u_lay, i_lay) for b in lay.buckets
           if b.hot_ids is not None])


def test_sharded_retriever_four_ways(v5e):
    """`pio deploy --retriever-mesh 4`: per-shard score + top-k, one
    all-gather, merge — compiled for the 2x2 host."""
    mesh = Mesh(np.asarray(v5e), ("model",))
    ret = object.__new__(ShardedDeviceRetriever)  # no device to put items on
    ret._mesh, ret._axis, ret._nshards = mesh, "model", 4
    ret.n_total, ret.dim = N_ITEMS, RANK
    n_pad = -(-N_ITEMS // (128 * 4)) * (128 * 4)
    ret._shard_rows = n_pad // 4
    ret._items = jax.ShapeDtypeStruct((n_pad, 128), jnp.float32)
    for b in (1, 128):
        b_pad, k_pad = _query_shapes(b, 10, N_ITEMS)
        hlo = ret._build(b_pad, min(k_pad, ret._shard_rows), k_pad).as_text()
        assert hlo.count(" all-gather(") + hlo.count(" all-gather-start(") == 1


def test_stock_flash_kernel_at_the_shapes_flash_attention_admits(
        v5e, monkeypatch):
    """`flash_attention` takes the stock Pallas kernel on a TPU when
    L % 128 == 0 and D in (64, 128); both head widths, causal and not."""
    from predictionio_tpu.parallel.ring_attention import flash_attention

    monkeypatch.setattr(jax, "default_backend", lambda: "tpu")
    one = SingleDeviceSharding(v5e[0])
    for d, causal in ((64, True), (128, False)):
        x = jax.ShapeDtypeStruct((2, 512, 4, d), jnp.bfloat16, sharding=one)
        exe = jax.jit(functools.partial(flash_attention, causal=causal)
                      ).lower(x, x, x).compile()
        assert "tpu_custom_call" in exe.as_text()


# ---------------------------------------------------------------------------
# the sequence cell (benchmarks/configs/ouro-2.6b-seqrec.json): the head
# at rank 2048 and the looped decoder's serving step, shapes only

SEQ_ITEMS, SEQ_RANK = 49_151, 2048


@pytest.mark.parametrize("b_pad", [8, 32, 128])
def test_topk_kernel_at_the_looped_decoders_head(v5e, b_pad):
    """[B, 2048] x [2048, 49,152] + top-k: two buffers of a 512-item tile
    are 8 MiB of the 12 MiB budget, the query is 16 lane groups; at the
    k's of the sequence models' lattice (16 to max_len + 16), alone and in
    the fused program over a step's query table."""
    from predictionio_tpu.models.looped_lm import STEP_TOKEN_BUDGET
    from predictionio_tpu.models.seq_serving import k_lattice

    d_pad, n_pad = _padded_shape(SEQ_ITEMS, SEQ_RANK)
    assert (d_pad, n_pad) == (2048, 49_152)
    one = SingleDeviceSharding(v5e[0])
    for k_pad in k_lattice(512):
        tile, chunk = _tile_rows(b_pad, d_pad, k_pad, n_pad)
        assert n_pad % tile == 0 and tile % chunk == 0
        _compile_kernel(v5e[0], SEQ_ITEMS, b_pad, k_pad, rank=SEQ_RANK)
    raw = _raw_call(b_pad, d_pad, n_pad, SEQ_ITEMS, 528, False)
    exe = jax.jit(_fused_fn(raw, True), donate_argnums=(0,)).lower(
        jax.ShapeDtypeStruct((b_pad,), jnp.int32, sharding=one),
        jax.ShapeDtypeStruct((STEP_TOKEN_BUDGET + 8, 2048), jnp.float32,
                             sharding=one),
        jax.ShapeDtypeStruct((d_pad, n_pad), jnp.float32, sharding=one),
    ).compile()
    _assert_one_kernel_and_the_packed_result(exe, b_pad, 528)


def _looped_params(one):
    """The tree `LoopedEncoder` puts on the device at Ouro-2.6B's
    published widths, as shapes: the public ones through `head_major`."""
    from predictionio_tpu.models import looped_lm as lm

    cfg = lm.LoopedLMConfig()
    shapes = lm.param_shapes(cfg, SEQ_ITEMS + 1)

    def arg(shape, matrix):
        return jax.ShapeDtypeStruct(
            shape, jnp.bfloat16 if matrix else jnp.float32, sharding=one)

    layers = jax.eval_shape(
        lambda t: lm.head_major(t, cfg),
        {k: arg(v, k in lm._LAYER_SHAPES)
         for k, v in shapes["layers"].items()})
    return {"embed": arg(shapes["embed"], True),
            "norm_f": arg(shapes["norm_f"], False),
            "gate_w": arg(shapes["gate_w"], False),
            "gate_b": arg((), False),
            "layers": {k: arg(v.shape, k in lm._LAYER_SHAPES)
                       for k, v in layers.items()}}


@pytest.fixture(scope="module")
def looped_step(v5e):
    """t_pad -> the compiled encoder executable of `pio deploy` at
    Ouro-2.6B's published widths, from the tree `LoopedEncoder` puts on
    the device (the public shapes through `head_major`); each compiled
    once for the tests below."""
    from predictionio_tpu.models import looped_lm as lm
    from predictionio_tpu.ops.pipeline import _encoder_fn

    one = SingleDeviceSharding(v5e[0])
    cfg = lm.LoopedLMConfig()
    params = _looped_params(one)
    cap = lm.STEP_TOKEN_BUDGET + 8

    @functools.lru_cache(maxsize=None)
    def compiled(t_pad):
        with pytest.MonkeyPatch.context() as mp:
            mp.setattr(jax, "default_backend", lambda: "tpu")
            return jax.jit(
                _encoder_fn(lm.encoder_program(cfg), cap, 2048)).lower(
                jax.ShapeDtypeStruct((3, t_pad), jnp.int32, sharding=one),
                params).compile()

    return compiled


@pytest.mark.parametrize("t_pad", [256, 512, 1024])
def test_looped_serving_step_at_its_token_lattice(looped_step, t_pad):
    """ONE `while` (48 layers x 4 passes in one scan, so the benchmark's
    `loop_share` counts no time twice), the stock flash kernel with
    segment ids inside it, the weights as arguments (4.93 GB of layers
    and the 0.2 GB embedding, bfloat16: the head-major layout moves no
    byte of them) and a step's query table out."""
    import re

    from predictionio_tpu.models.looped_lm import STEP_TOKEN_BUDGET

    exe = looped_step(t_pad)
    hlo = exe.as_text()
    # by its name, as benchmarks/layers/loop_share.json matches it (the
    # reads of its results carry the name too; they are no operations)
    assert len(re.findall(r"^\s*(?:ROOT )?%while[.\d]* = .* while\(", hlo,
                          re.M)) == 1
    assert hlo.count(" while(") == 1
    assert hlo.count("custom_call_target=\"tpu_custom_call\"") == 1
    mem = exe.memory_analysis()
    assert 5.1e9 < mem.argument_size_in_bytes < 5.2e9
    assert mem.output_size_in_bytes >= (STEP_TOKEN_BUDGET + 8) * 2048 * 4
    assert mem.temp_size_in_bytes < 1e9


def _moved_whole(body: str) -> list:
    """The instructions of a computation that only MOVE a whole matrix:
    a `copy`, a `dynamic-slice` or a fusion named for either, whose
    result is a bfloat16 array of 2,048 x 2,048 elements or more; and
    any `copy` of the Mosaic kernel's result or of a bitcast of it."""
    import re

    found = []
    kernel = {re.search(r"(%[\w.\-]+) = \S+ custom-call\(.*tpu_custom_call",
                        body).group(1)}
    for name, dtype, dims, op, operands in re.findall(
            r"^\s*(?:ROOT )?(%[\w.\-]+) = (\w+)\[([\d,]*)\]\S* "
            r"([\w\-]+)\(([^)]*)\)", body, re.M):
        moves = op in ("copy", "dynamic-slice") or (
            op == "fusion" and ("dynamic-slice" in name or "copy" in name))
        size = int(np.prod([int(d) for d in dims.split(",") if d]))
        if moves and dtype == "bf16" and size >= 2048 * 2048:
            found.append(name)
        if kernel & set(operands.split(", ")):
            if op == "bitcast":
                kernel.add(name)
            elif op == "copy":
                found.append(name)
    return found


@pytest.mark.parametrize("t_pad", [256, 512, 1024])
def test_looped_layer_slices_its_weights_inside_the_matmuls(looped_step,
                                                             t_pad):
    """No layer application stages, copies or transposes a matrix before
    its matmul: the `while` body has no `copy` and no `dynamic-slice`
    fusion of a bfloat16 array the size of a layer's matrix (the
    `[L, D, H x hd]` store cost three stagings and three transposes of 8
    MB an application, 9% of a 1,024-token step: PERF.md, PR 35), and
    the flash kernel's output goes to `wo` as it lies."""
    import re

    hlo = looped_step(t_pad).as_text()
    name = re.search(r" while\(.*?body=(%[\w.\-]+)", hlo).group(1)
    start = hlo.index("\n" + name + " ")
    body = hlo[start:hlo.index("\n}\n", start)]
    assert "custom_call_target=\"tpu_custom_call\"" in body
    assert _moved_whole(body) == []


def test_the_detector_finds_what_the_flat_store_compiled_to():
    """The `while` body of the `[L, D, H x hd]` store (v5e compile of
    PR 31's step, `t_pad` 1,024): one staging and one transpose a
    projection, and the kernel's output turned for `wo`."""
    tile = "T(8,128)(2,1)S(1)}"
    assert _moved_whole(
        f"  %constant_dynamic-slice_fusion.16 = bf16[1,2048,2048]{{2,1,0:{tile}"
        " fusion(%bitcast.165, %select_n.117), kind=kLoop, calls=%fused.16\n"
        f"  %copy.20 = bf16[1,2048,2048]{{1,2,0:{tile}"
        " copy(%constant_dynamic-slice_fusion.16)\n"
        f"  %flash_attention.6 = bf16[1,16,1024,128]{{3,2,1,0:{tile}"
        " custom-call(%fusion.83, %fusion.84),"
        " custom_call_target=\"tpu_custom_call\"\n"
        f"  %bitcast.168 = bf16[1,1024,16,128]{{3,1,2,0:{tile}"
        " bitcast(%flash_attention.6)\n"
        f"  %copy.23 = bf16[1,1024,16,128]{{1,3,2,0:{tile}"
        " copy(%bitcast.168), metadata={op_name=\"jit(fn)/transpose\"}\n"
        "  %fusion.86 = bf16[1024,5632]{1,0:T(8,128)(2,1)S(1)}"
        " fusion(%fusion.85), kind=kLoop, calls=%fused.86\n") == [
            "%constant_dynamic-slice_fusion.16", "%copy.20", "%copy.23"]


# ---------------------------------------------------------------------------
# the latent-attention mixture-of-experts cell
# (benchmarks/configs/axk1-seqrec.json): the attention kernel at d_qk 192 /
# d_v 128, the head at rank 7168, and the serving step, shapes only

AXK1_ITEMS, AXK1_RANK = 20_479, 7168


def test_segment_flash_kernel_at_the_latent_heads(v5e):
    """64 heads of 128 + 64 (the rotary key ONE head, shared) against
    values of 128 over a stream of 8,192 packed tokens: one Mosaic
    kernel whose grid is the block pairs that meet, sized on the device
    (a dynamic grid), and the count of unmasked pairs beside the
    output."""
    from predictionio_tpu.parallel.ring_attention import (
        segment_flash_attention)

    one = SingleDeviceSharding(v5e[0])
    H, L = 64, 8192

    def arg(*shape, dtype=jnp.bfloat16):
        return jax.ShapeDtypeStruct(shape, dtype, sharding=one)

    def fn(q_nope, q_rope, k_nope, k_rope, v, seg):
        return segment_flash_attention((q_nope, q_rope), (k_nope, k_rope), v,
                                       seg, scale=0.13, interpret=False)

    exe = jax.jit(fn).lower(
        arg(1, H, L, 128), arg(1, H, L, 64), arg(1, H, L, 128),
        arg(1, 1, L, 64), arg(1, H, L, 128), arg(1, L, dtype=jnp.int32)
    ).compile()
    assert exe.as_text().count("custom_call_target=\"tpu_custom_call\"") == 1
    assert exe.memory_analysis().output_size_in_bytes >= H * L * 128 * 2


def test_flash_attention_runs_the_new_kernel_where_the_stock_one_cannot(
        v5e, monkeypatch):
    """The explicit choice: on a TPU, q and k of 192 against v of 128 (or
    any head size the stock kernel refuses) compile to the segment flash
    kernel, never to the path that builds [B, H, L, L]."""
    from predictionio_tpu.parallel.ring_attention import flash_attention

    monkeypatch.setattr(jax, "default_backend", lambda: "tpu")
    one = SingleDeviceSharding(v5e[0])
    qk = jax.ShapeDtypeStruct((1, 1024, 4, 192), jnp.bfloat16, sharding=one)
    v = jax.ShapeDtypeStruct((1, 1024, 4, 128), jnp.bfloat16, sharding=one)
    seg = jax.ShapeDtypeStruct((1, 1024), jnp.int32, sharding=one)

    def fn(q, k, v, seg):
        # the kernel's default is interpret off a TPU: here the backend is
        # only SAID to be one, which is what the program would see on it
        return flash_attention(q, k, v, causal=True, segment_ids=seg)

    hlo = jax.jit(fn).lower(qk, qk, v, seg).compile().as_text()
    assert hlo.count("custom_call_target=\"tpu_custom_call\"") == 1
    assert "f32[1,4,1024,1024]" not in hlo  # no [B, H, L, L] scores


@pytest.mark.parametrize("b_pad", [8, 128])
def test_topk_kernel_at_the_latent_decoders_head(v5e, b_pad):
    """[B, 7168] x [7168, 20,480] + top-k at k 16: two buffers of a
    256-item tile would be 14.7 MB, over the 12 MiB budget, so the tile
    is 128 items (7.3 MB); alone and in the fused program over a step's
    query table of 8,192 + 8 rows."""
    from predictionio_tpu.models.latent_moe_lm import STEP_TOKEN_BUDGET

    d_pad, n_pad = _padded_shape(AXK1_ITEMS, AXK1_RANK)
    assert (d_pad, n_pad) == (7168, 20_480)
    tile, chunk = _tile_rows(b_pad, d_pad, 16, n_pad)
    assert tile == 128 and chunk == 128
    _compile_kernel(v5e[0], AXK1_ITEMS, b_pad, 16, rank=AXK1_RANK)
    one = SingleDeviceSharding(v5e[0])
    raw = _raw_call(b_pad, d_pad, n_pad, AXK1_ITEMS, 16, False)
    exe = jax.jit(_fused_fn(raw, True), donate_argnums=(0,)).lower(
        jax.ShapeDtypeStruct((b_pad,), jnp.int32, sharding=one),
        jax.ShapeDtypeStruct((STEP_TOKEN_BUDGET + 8, 7168), jnp.float32,
                             sharding=one),
        jax.ShapeDtypeStruct((d_pad, n_pad), jnp.float32, sharding=one),
    ).compile()
    _assert_one_kernel_and_the_packed_result(exe, b_pad, 16)


def _latent_params(one):
    """The tree `LatentMoEEncoder` puts on the device at A.X-K1's
    published widths, as shapes."""
    from predictionio_tpu.models import latent_moe_lm as lm

    cfg = lm.LatentMoEConfig()
    shapes = lm.param_shapes(cfg, AXK1_ITEMS + 1)

    def arg(name, shape):
        return jax.ShapeDtypeStruct(
            shape, jnp.float32 if lm._is_float32_leaf(name) else jnp.bfloat16,
            sharding=one)

    public = {"embed": arg("embed", shapes["embed"]),
              "norm_f": arg("norm_f", shapes["norm_f"]),
              "layers": {i: {k: arg(k, s) for k, s in layer.items()}
                         for i, layer in shapes["layers"].items()}}
    return jax.tree_util.tree_map(
        lambda x: jax.ShapeDtypeStruct(x.shape, x.dtype, sharding=one),
        jax.eval_shape(lambda p: lm.device_tree(p, cfg), public))


def test_latent_moe_serving_step_at_its_budget(v5e):
    """The encoder executable of `pio deploy` at A.X-K1's published
    widths for a full step of 8,192 tokens, from the tree
    `LatentMoEEncoder` puts on the device: five attention kernels (one a
    layer), three grouped matmuls a routed layer inside ONE `while` a
    routed layer (the passes over the sorted rows), the share's 6.4 GB
    of bfloat16 weights and the embedding as arguments, a step's query
    table and the four counters out, and temporaries that leave the chip
    room (weights 6.7 + head 0.6 + these under 16 GiB)."""
    import re

    from predictionio_tpu.models import latent_moe_lm as lm
    from predictionio_tpu.obs.trace import DeviceScopes
    from predictionio_tpu.ops.pipeline import _encoder_fn

    one = SingleDeviceSharding(v5e[0])
    cfg = lm.LatentMoEConfig()
    params = _latent_params(one)
    t_pad = lm.STEP_TOKEN_BUDGET
    with pytest.MonkeyPatch.context() as mp:
        mp.setattr(jax, "default_backend", lambda: "tpu")
        exe = jax.jit(_encoder_fn(lm.encoder_program(cfg), t_pad + 8, 7168)
                      ).lower(
            jax.ShapeDtypeStruct((3, t_pad), jnp.int32, sharding=one),
            params).compile()
    hlo = exe.as_text()
    kernels = re.findall(
        r"^\s*(%[\w.\-]+) = .* custom-call\(.*tpu_custom_call", hlo, re.M)
    assert sum(k.startswith("%segment_flash_attention") for k in kernels) == 5
    assert sum(k.startswith("%gmm") for k in kernels) == 3 * 4
    mem = exe.memory_analysis()
    assert 6.6e9 < mem.argument_size_in_bytes < 6.8e9
    assert mem.output_size_in_bytes >= (t_pad + 8) * 7168 * 4
    assert mem.temp_size_in_bytes < 4e9
    # the capture's operation-to-scope map names the step's operations
    scopes = DeviceScopes()
    assert scopes.record(hlo) > 500
    named = set(scopes.snapshot().values())
    assert {"pio.seq.embed", "pio.seq.latent_proj", "pio.seq.latent_attn",
            "pio.seq.router", "pio.seq.experts", "pio.seq.experts.matmul",
            "pio.seq.shared_expert", "pio.seq.dense_mlp"} <= named
    # a container's time is its body's: none is named
    assert not any(k.endswith((" while", " conditional", " call"))
                   for k in scopes.snapshot())


# ---------------------------------------------------------------------------
# the hybrid state-space cell
# (benchmarks/configs/granite-4.0-h-micro-seqrec.json): the attention
# kernel at 32 query heads over 8 key/value heads of 64, the head at rank
# 2048 over 100,351 rows, and the serving step over its token lattice,
# shapes only; and what the two other decoders lower to, pinned

GRANITE_ITEMS = 100_351


def test_segment_flash_kernel_at_the_grouped_heads(v5e):
    """32 query heads of 64 over 8 key/value heads, a stream of 8,192
    packed tokens, the published scale 1/64: one Mosaic kernel whose
    index map reads query head h's keys and values from head h // 4 in
    place (nothing repeated in memory), and the count of unmasked pairs
    beside the output."""
    from predictionio_tpu.parallel.ring_attention import (
        segment_flash_attention)

    one = SingleDeviceSharding(v5e[0])
    H, KV, L = 32, 8, 8192

    def arg(*shape, dtype=jnp.bfloat16):
        return jax.ShapeDtypeStruct(shape, dtype, sharding=one)

    def fn(q, k, v, seg):
        return segment_flash_attention((q,), (k,), v, seg, scale=1.0 / 64,
                                       interpret=False)

    exe = jax.jit(fn).lower(arg(1, H, L, 64), arg(1, KV, L, 64),
                            arg(1, KV, L, 64), arg(1, L, dtype=jnp.int32)
                            ).compile()
    hlo = exe.as_text()
    assert hlo.count("custom_call_target=\"tpu_custom_call\"") == 1
    assert exe.memory_analysis().output_size_in_bytes >= H * L * 64 * 2
    assert "bf16[1,32,8192,64]" in hlo       # the queries' and the output's
    # no key or value array at the query heads' count was made
    assert not re.search(r"= bf16\[1,32,8192,64\][^ ]* (broadcast|concatenate"
                         r"|gather)\(", hlo)


@pytest.mark.parametrize("b_pad", [8, 128])
def test_topk_kernel_at_the_hybrid_decoders_head(v5e, b_pad):
    """[B, 2048] x [2048, 100,352] + top-k at k 16 over the TIED table:
    the looped cell's program at twice its rows."""
    from predictionio_tpu.models.hybrid_ssm_lm import STEP_TOKEN_BUDGET

    one = SingleDeviceSharding(v5e[0])
    d_pad, n_pad = _padded_shape(GRANITE_ITEMS, 2048)
    assert d_pad == 2048
    raw = _raw_call(b_pad, d_pad, n_pad, GRANITE_ITEMS, 16, False)
    exe = jax.jit(_fused_fn(raw, True), donate_argnums=(0,)).lower(
        jax.ShapeDtypeStruct((b_pad,), jnp.int32, sharding=one),
        jax.ShapeDtypeStruct((STEP_TOKEN_BUDGET + 8, 2048), jnp.float32,
                             sharding=one),
        jax.ShapeDtypeStruct((d_pad, n_pad), jnp.float32, sharding=one),
    ).compile()
    _assert_one_kernel_and_the_packed_result(exe, b_pad, 16)


def _hybrid_params(one):
    """The tree `HybridSSMEncoder` puts on the device at
    granite-4.0-h-micro's published widths, as shapes: the public ones."""
    from predictionio_tpu.models import hybrid_ssm_lm as lm

    shapes = lm.param_shapes(lm.HybridSSMConfig(), GRANITE_ITEMS + 1)

    def arg(name, shape):
        return jax.ShapeDtypeStruct(
            shape, jnp.float32 if name in lm._FLOAT32 else jnp.bfloat16,
            sharding=one)

    return {k: ({n: arg(n, s) for n, s in v.items()}
                if isinstance(v, dict) else arg(k, v))
            for k, v in shapes.items()}


@pytest.fixture(scope="module")
def hybrid_step(v5e):
    """t_pad -> the compiled encoder executable of `pio deploy` at
    granite-4.0-h-micro's published widths, from the tree
    `HybridSSMEncoder` puts on the device (the public shapes)."""
    from predictionio_tpu.models import hybrid_ssm_lm as lm
    from predictionio_tpu.ops.pipeline import _encoder_fn

    one = SingleDeviceSharding(v5e[0])
    cfg = lm.HybridSSMConfig()
    params = _hybrid_params(one)

    @functools.lru_cache(maxsize=None)
    def compiled(t_pad):
        with pytest.MonkeyPatch.context() as mp:
            mp.setattr(jax, "default_backend", lambda: "tpu")
            return jax.jit(_encoder_fn(
                lm.encoder_program(cfg), lm.STEP_TOKEN_BUDGET + 8, 2048)
            ).lower(jax.ShapeDtypeStruct((3, t_pad), jnp.int32,
                                         sharding=one), params).compile()

    return compiled


@pytest.mark.parametrize("t_pad", [1024, 2048, 4096, 8192])
def test_hybrid_serving_step_at_its_token_lattice(hybrid_step, t_pad):
    """Every lattice point of `HybridSSMEncoder`: one `while` a run of
    Mamba layers in the published order (5, 9, 9, 9, 4: five), no
    conditional, the FOUR attention kernels at the program's top level,
    6.4 GB of bfloat16 weights as arguments and no copy of a Mamba or MLP
    stack (each layer is sliced from its stack where it lies), a step's
    query table and the four counters out, and temporaries that
    leave the chip room.

    Re-pinned by PR 46, whose `ssd_scan_kernel` runs every Mamba layer's
    scan: `tpu_custom_call` 4 -> 9 (one more a run's body, carrying the
    scope `pio.seq.ssm_scan`); ` while(` 10 -> 5 (the chunk states'
    carry is the kernel's VMEM scratch, no inner loop); no copy of the
    convolution's [t_pad, 4352] or of a [t_pad, 4096] before or behind
    the kernel, in either orientation (it reads and writes the
    transposes, which is how XLA lays these arrays: a row-major kernel
    got both copies, 277 MB a layer at 8,192 tokens); temporaries 0.88 ->
    0.61 GB at 8,192 tokens (the decay matrices, the two relayouts and
    the chunk states went), held under 0.6 GB a full step + 0.1."""
    from predictionio_tpu.obs.trace import DeviceScopes

    exe = hybrid_step(t_pad)
    hlo = exe.as_text()
    assert hlo.count(" while(") == 5 and hlo.count(" conditional(") == 0
    assert hlo.count("custom_call_target=\"tpu_custom_call\"") == 9
    assert "segment_flash_attention" in hlo
    kernels = [line for line in hlo.splitlines()
               if " custom-call(" in line and "ssd_scan_kernel" in line]
    assert len(kernels) == 5
    assert all("pio.seq.ssm_scan/ssd_scan_kernel" in line
               and f"= f32[4096,{t_pad}]" in line for line in kernels)
    assert not re.search(r"= bf16\[(36|40),\d+,\d+\][^ ]* copy\(", hlo)
    assert not re.search(
        rf"= f32\[({t_pad},(4096|4352)|(4096|4352),{t_pad})\][^ ]* "
        r"(copy|transpose)\(", hlo)
    mem = exe.memory_analysis()
    assert 6.35e9 < mem.argument_size_in_bytes < 6.45e9
    assert mem.output_size_in_bytes >= (8192 + 8) * 2048 * 4
    assert mem.temp_size_in_bytes < 0.6e9 * t_pad / 8192 + 0.1e9
    scopes = DeviceScopes()
    assert scopes.record(hlo) > 100
    assert {"pio.seq.embed", "pio.seq.ssm_in_proj", "pio.seq.ssm_conv",
            "pio.seq.ssm_scan", "pio.seq.ssm_gate_out", "pio.seq.attn_proj",
            "pio.seq.gqa_attn", "pio.seq.mlp"} <= set(
                scopes.snapshot().values())
    assert not any(k.endswith((" while", " conditional", " call"))
                   for k in scopes.snapshot())
    # The map CONTAINS the mixer: of what the operations in the bodies
    # of the five Mamba runs write, under 1% stands under no scope or
    # under `pio.seq.layers` alone. The convolution's fusion, whose root
    # is now the transpose that hands it to the kernel (a bitcast: the
    # fusion writes [4352, t_pad] as it wrote [t_pad, 4352] tokens-minor),
    # reads `pio.seq.ssm_conv` beside the slice that feeds it; what
    # reads `pio.seq.ssm_run` alone is crumbs of the cumulative sums;
    # `pio.seq.ssm_scan` is the kernel's y^T and the per-token factors.
    written = _bytes_written_by_scope(hlo, scopes.snapshot())
    assert sum(written.values()) > 1.3e9 * t_pad / 8192
    stray = written.get("", 0) + written.get("pio.seq.layers", 0)
    assert stray < 0.01 * sum(written.values()), written
    assert {"pio.seq.ssm_in_proj", "pio.seq.ssm_conv", "pio.seq.ssm_scan",
            "pio.seq.ssm_run", "pio.seq.ssm_gate_out", "pio.seq.mlp"
            } >= set(written) - {"", "pio.seq.layers"}
    conv = 2 * t_pad * 4352 * 4         # the slice of zudt, then u^T
    assert conv <= written["pio.seq.ssm_conv"] < 1.05 * conv
    assert written["pio.seq.ssm_run"] < 0.1 * conv
    y = t_pad * 4096 * 4
    assert y <= written["pio.seq.ssm_scan"] < 1.3 * y


@pytest.mark.parametrize("T,Q,H,P,N,dtype", [
    (256, 128, 2, 64, 128, "bfloat16"),     # two heads, all in one block
    (256, 128, 8, 16, 128, "bfloat16"),     # a head of one bfloat16 tile
    (512, 256, 6, 64, 128, "bfloat16"),     # blocks of 6 heads
    (512, 256, 224, 64, 128, "float32"),    # 14 MB of VMEM: the most
    (768, 384, 64, 64, 128, "float32"),     # chunks of three blocks of 128
])
def test_scan_kernel_lowers_at_the_edges_of_what_the_choice_admits(
        v5e, T, Q, H, P, N, dtype):
    """`scan_kernel_for` promises the serving path a kernel that Mosaic
    takes: the smallest head block and head, a head count that 16 does
    not divide, and the largest VMEM need it admits, in the wider compute
    type."""
    from predictionio_tpu.models import hybrid_ssm_lm as lm

    assert lm.scan_kernel_for(T, Q, H, P, N, backend="tpu",
                              differentiable=False) == "kernel"
    one = SingleDeviceSharding(v5e[0])

    def arg(*shape, dtype=jnp.float32):
        return jax.ShapeDtypeStruct(shape, dtype, sharding=one)

    def fn(uT, dt, A, runs, D):
        return lm.ssd_scan_kernel(uT, dt, A, runs, d_state=N, chunk=Q,
                                  cd=jnp.dtype(dtype), skip=D,
                                  interpret=False)

    exe = jax.jit(fn).lower(arg(H * P + 2 * N, T), arg(T, H), arg(H),
                            arg(T, dtype=jnp.int32), arg(H)).compile()
    assert exe.as_text().count("custom_call_target=\"tpu_custom_call\"") == 1


_ARRAY = re.compile(r"(pred|s8|u8|bf16|f16|s32|u32|f32)\[([\d,]*)\]")
_WIDTH = {"pred": 1, "s8": 1, "u8": 1, "bf16": 2, "f16": 2, "s32": 4,
          "u32": 4, "f32": 4}


def _bytes_written_by_scope(hlo: str, scope_of: dict) -> dict:
    """{scope: bytes of the results} over the operations of ONE body of a
    run of Mamba layers (a `while` body that holds the scan kernel's
    call), by the capture's operation-to-scope map; what moves no data
    of its own is left out."""
    from predictionio_tpu.obs.trace import operation_key

    bodies, at = {}, None
    for line in hlo.splitlines():
        head = re.match(r"^(?:ENTRY )?(%[\w.\-]+) \(.*\) -> .* \{$", line)
        if head:
            at = bodies.setdefault(head.group(1), [])
        elif line.startswith("}"):
            at = None
        elif at is not None:
            at.append(line)
    runs = [bodies[b] for b in re.findall(r" while\(.*?body=(%[\w.\-]+)", hlo)
            if any("ssd_scan_kernel" in line for line in bodies[b])]
    assert len(runs) == 5
    out: dict = {}
    for line in runs[0]:
        m = re.match(r"^\s*(?:ROOT )?%[\w.\-]+ = (.+?) ([\w\-]+)\(", line)
        # an asynchronous pair's result is its `-done`; the `-start`
        # carries the operand along in its tuple
        if m is None or m.group(2).endswith("-start") or m.group(2) in (
                "parameter", "get-tuple-element", "tuple", "constant",
                "bitcast", "while"):
            continue
        size = sum(_WIDTH[t] * int(np.prod([int(d) for d in dims.split(",")
                                            if d] or [1]))
                   for t, dims in _ARRAY.findall(m.group(1)))
        scope = scope_of.get(operation_key(line), "")
        out[scope] = out.get(scope, 0) + size
    return out


def _without_kernel_bodies(lowered_text: str) -> str:
    """A lowered program's text with every Mosaic kernel's serialized
    body taken out: the body holds the checkout's path and the source
    lines of every frame, which differ between two checkouts of one
    program."""
    return re.sub(r'\\22body\\22: \\22[^\\]*\\22', "BODY", lowered_text)


def _digest(text: str) -> str:
    import hashlib

    return hashlib.sha256(text.encode()).hexdigest()[:16]


def test_the_looped_encoders_lowered_program_is_what_it_was(v5e):
    """PR 44 moved `_rms` and the stream layouts into models/seq_common.py
    and gave `attention_kernel_for` a `grouped` argument: the looped
    decoder's serving step at 1,024 tokens lowers to the text it lowered
    to at the parent commit (kernel bodies apart, which hold source
    lines). A PR that changes this program on purpose brings the new
    digest and says so."""
    from predictionio_tpu.models import looped_lm as lm
    from predictionio_tpu.ops.pipeline import _encoder_fn

    one = SingleDeviceSharding(v5e[0])
    cfg = lm.LoopedLMConfig()
    params = _looped_params(one)
    with pytest.MonkeyPatch.context() as mp:
        mp.setattr(jax, "default_backend", lambda: "tpu")
        text = jax.jit(_encoder_fn(
            lm.encoder_program(cfg), lm.STEP_TOKEN_BUDGET + 8, 2048)).lower(
            jax.ShapeDtypeStruct((3, 1024), jnp.int32, sharding=one),
            params).as_text()
    assert _digest(_without_kernel_bodies(text)) == "fbc17a4fe9535752"


def test_the_latent_encoders_lowered_program_is_what_it_was(v5e):
    """The latent decoder's serving step at 8,192 tokens, as above; and
    the segment kernel itself at the latent heads, as the jaxpr its
    `pallas_call` holds (source lines apart): the grouped heads added a
    branch to an index map that the shared and the per-head layouts do
    not take."""
    from predictionio_tpu.models import latent_moe_lm as lm
    from predictionio_tpu.ops.pipeline import _encoder_fn
    from predictionio_tpu.parallel.ring_attention import (
        segment_flash_attention)

    one = SingleDeviceSharding(v5e[0])
    cfg = lm.LatentMoEConfig()
    params = _latent_params(one)
    with pytest.MonkeyPatch.context() as mp:
        mp.setattr(jax, "default_backend", lambda: "tpu")
        text = jax.jit(_encoder_fn(lm.encoder_program(cfg), 8192 + 8, 7168)
                       ).lower(jax.ShapeDtypeStruct((3, 8192), jnp.int32,
                                                    sharding=one),
                               params).as_text()
    assert _digest(_without_kernel_bodies(text)) == "0ce4dbb19d2cdfd4"

    H, L = 64, 8192

    def shaped(*shape, dtype=jnp.bfloat16):
        return jax.ShapeDtypeStruct(shape, dtype)

    def kernel(q_nope, q_rope, k_nope, k_rope, v, seg):
        return segment_flash_attention((q_nope, q_rope), (k_nope, k_rope), v,
                                       seg, scale=0.13, interpret=False)

    jaxpr = str(jax.make_jaxpr(kernel)(
        shaped(1, H, L, 128), shaped(1, H, L, 64), shaped(1, H, L, 128),
        shaped(1, 1, L, 64), shaped(1, H, L, 128),
        shaped(1, L, dtype=jnp.int32)))
    assert _digest(re.sub(r" at [^\s]+:\d+", "", jaxpr)) == "3ca78feab50cd58c"
