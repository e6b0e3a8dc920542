"""Collective wrapper semantics on the virtual 8-device mesh — the
communication backend's unit tests (analog of nothing in the reference:
Spark's shuffle is implicit; here communication is explicit and testable).
"""

import jax
import numpy as np
import pytest
from jax.sharding import Mesh, NamedSharding, PartitionSpec as P

from predictionio_tpu.parallel import collectives as C

shard_map = jax.shard_map


@pytest.fixture(scope="module")
def mesh1d():
    devices = jax.devices()
    if len(devices) < 8:
        pytest.skip("needs 8 virtual devices")
    return Mesh(np.asarray(devices), ("data",))


def test_outside_spmd_is_identity():
    x = np.arange(4.0)
    np.testing.assert_array_equal(C.allreduce_sum(x), x)
    np.testing.assert_array_equal(C.ring_shift(x, "data"), x)
    assert C.axis_size("data") == 1 and C.axis_index("data") == 0


def test_allreduce_and_axis_info(mesh1d):
    x = np.ones((8, 3), np.float32)

    def f(blk):
        return (
            C.allreduce_sum(blk.sum(), "data"),
            C.allreduce_mean(blk.sum(), "data"),
            C.axis_size("data") + 0.0 * blk.sum(),
        )

    total, mean, size = shard_map(
        f, mesh=mesh1d, in_specs=(P("data"),),
        out_specs=(P(), P(), P()), check_vma=False,
    )(x)
    assert float(total) == 24.0
    assert float(mean) == 3.0
    assert float(size) == 8.0


def test_ring_shift_rotates(mesh1d):
    x = np.arange(8, dtype=np.float32).reshape(8, 1)

    def f(blk):
        return C.ring_shift(blk, "data")

    out = shard_map(f, mesh=mesh1d, in_specs=(P("data"),),
                    out_specs=P("data"), check_vma=False)(x)
    # device i's block moved to device i+1: global result is a roll
    np.testing.assert_array_equal(np.asarray(out)[:, 0], np.roll(np.arange(8), 1))


def test_allgather_tiled(mesh1d):
    x = np.arange(16, dtype=np.float32).reshape(16, 1)

    def f(blk):
        return C.allgather(blk, "data", axis=0)

    out = shard_map(f, mesh=mesh1d, in_specs=(P("data"),),
                    out_specs=P(None), check_vma=False)(x)
    np.testing.assert_array_equal(np.asarray(out)[:, 0], np.arange(16))


def test_reduce_scatter_matches_psum_shard(mesh1d):
    rng = np.random.default_rng(0)
    x = rng.standard_normal((8, 8)).astype(np.float32)

    def f(blk):
        # every device contributes its [1, 8] row; reduce_scatter leaves
        # each device the psum of its own column slice
        return C.reduce_scatter(blk[0], "data")

    out = shard_map(f, mesh=mesh1d, in_specs=(P("data", None),),
                    out_specs=P("data"), check_vma=False)(x)
    np.testing.assert_allclose(np.asarray(out), x.sum(axis=0), rtol=1e-5)


def test_all_to_all_roundtrip(mesh1d):
    rng = np.random.default_rng(1)
    x = rng.standard_normal((8, 8, 4)).astype(np.float32)

    def f(blk):
        y = C.all_to_all(blk, "data", split_axis=1, concat_axis=0)
        return C.all_to_all(y, "data", split_axis=0, concat_axis=1)

    out = shard_map(f, mesh=mesh1d, in_specs=(P("data"),),
                    out_specs=P("data"), check_vma=False)(x)
    np.testing.assert_allclose(np.asarray(out), x, rtol=1e-6)
