"""XLA collective wrappers — the framework's communication backend.

Where the reference's distributed substrate is Spark 1.3's netty shuffle +
akka control plane (implicit in every RDD op; see reference build.sbt:41
sparkVersion and HBase RPC at
data/src/main/scala/io/prediction/data/storage/hbase/HBPEvents.scala:99),
this framework communicates exclusively through XLA collectives compiled
into pjit/shard_map programs. Collectives ride ICI within a slice and DCN
across hosts; there is no NCCL/MPI and no user-visible message passing.

These wrappers exist so algorithm code names *semantic* operations
(``allreduce_sum``, ``ring_shift``) rather than raw lax primitives, and so
non-SPMD callers (no mesh / 1 device) degrade to no-ops without branching
at every call site.
"""

from __future__ import annotations

from typing import Any, Callable, Sequence

__all__ = [
    "allreduce_sum",
    "allreduce_mean",
    "allreduce_max",
    "allgather",
    "reduce_scatter",
    "all_to_all",
    "ring_shift",
    "axis_size",
    "axis_index",
    "sharded",
]


def _has_axis(axis_name: str) -> bool:
    import jax

    try:
        jax.lax.axis_index(axis_name)
        return True
    except NameError:
        return False


def allreduce_sum(x, axis_name: str = "data"):
    """psum over a mesh axis; identity if the axis is not in scope."""
    import jax

    if not _has_axis(axis_name):
        return x
    return jax.lax.psum(x, axis_name)


def allreduce_mean(x, axis_name: str = "data"):
    import jax

    if not _has_axis(axis_name):
        return x
    return jax.lax.pmean(x, axis_name)


def allreduce_max(x, axis_name: str = "data"):
    import jax

    if not _has_axis(axis_name):
        return x
    return jax.lax.pmax(x, axis_name)


def allgather(x, axis_name: str = "model", *, axis: int = 0, tiled: bool = True):
    """Gather shards along ``axis`` from every device on the mesh axis.
    ``tiled=True`` concatenates (shard-size*n along ``axis``); ``tiled=False``
    stacks a new leading device dimension."""
    import jax

    if not _has_axis(axis_name):
        return x
    return jax.lax.all_gather(x, axis_name, axis=axis, tiled=tiled)


def reduce_scatter(x, axis_name: str = "data", *, scatter_axis: int = 0):
    """psum then keep only this device's shard — the bandwidth-optimal way
    to combine gradients that will immediately be re-sharded."""
    import jax

    if not _has_axis(axis_name):
        return x
    return jax.lax.psum_scatter(x, axis_name, scatter_dimension=scatter_axis, tiled=True)


def all_to_all(x, axis_name: str, *, split_axis: int, concat_axis: int):
    """Transpose which array dimension is sharded over ``axis_name`` —
    the primitive behind Ulysses-style sequence<->head resharding."""
    import jax

    if not _has_axis(axis_name):
        return x
    return jax.lax.all_to_all(
        x, axis_name, split_axis=split_axis, concat_axis=concat_axis, tiled=True
    )


def ring_shift(x, axis_name: str, *, reverse: bool = False):
    """Send this device's block to the next device on the axis (a ring
    ppermute) — the building block of ring attention and blocked ALS."""
    import jax

    if not _has_axis(axis_name):
        return x
    n = jax.lax.psum(1, axis_name)
    if reverse:
        perm = [(i, (i - 1) % n) for i in range(n)]
    else:
        perm = [(i, (i + 1) % n) for i in range(n)]
    return jax.lax.ppermute(x, axis_name, perm)


def axis_size(axis_name: str) -> int:
    import jax

    if not _has_axis(axis_name):
        return 1
    return jax.lax.psum(1, axis_name)


def axis_index(axis_name: str):
    import jax

    if not _has_axis(axis_name):
        return 0
    return jax.lax.axis_index(axis_name)


def sharded(
    mesh,
    fn: Callable[..., Any],
    in_specs: Sequence[Any],
    out_specs: Any,
    *,
    check_vma: bool = False,
):
    """shard_map wrapper: run ``fn`` SPMD over ``mesh`` with explicit
    per-argument PartitionSpecs. The per-device view inside ``fn`` sees
    local shards and may call the collectives above by axis name."""
    import jax

    return jax.shard_map(
        fn, mesh=mesh, in_specs=tuple(in_specs), out_specs=out_specs,
        check_vma=check_vma,
    )
