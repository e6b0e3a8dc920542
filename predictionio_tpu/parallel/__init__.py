"""Parallelism subsystem: mesh construction, XLA collectives, and
sequence/context parallelism (ring attention, Ulysses).

This package is the TPU-native replacement for the reference's entire
distributed substrate (Spark shuffle + akka control plane + HBase RPC,
SURVEY.md §2.9): arrays are sharded over a ``jax.sharding.Mesh`` and all
communication is XLA collectives compiled into the program, riding ICI
within a slice and DCN across hosts.
"""

from .mesh import (  # noqa: F401
    make_mesh,
    data_sharding,
    replicated,
    shard_batch,
    init_distributed,
    local_device_count,
)
from . import ring_attention  # noqa: F401
