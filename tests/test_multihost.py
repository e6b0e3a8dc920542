"""Two-process jax.distributed smoke test — the DCN control plane.

The reference's driver<->executor control plane is Spark's akka RPC
(reference: tools/src/main/scala/io/prediction/tools/Runner.scala:36-110
spawning executors via spark-submit; CreateServer.scala actor system).
Here the equivalent is the jax.distributed runtime: N processes join a
coordinator, jax.devices() spans all of them, and collectives ride the
global mesh. Round 1 wrapped this in ``parallel/mesh.py:init_distributed``
but never exercised it end to end; this test spawns a real coordinator +
worker process pair on the CPU backend and checks:

- both processes see the union of devices (2 local x 2 procs = 4 global);
- a jitted global-sum over a data-sharded global array (XLA inserts the
  cross-process psum) gives the true total on BOTH processes;
- ``find_frame(host_shard=(process_index, process_count))`` over a shared
  sqlite event store hands each process a disjoint, complete entity slice
  (the multi-host data-loading contract, storage/partition.py).
"""

import json
import os
import socket
import subprocess
import sys
from pathlib import Path

import numpy as np
import pytest

REPO = Path(__file__).resolve().parents[1]

WORKER_SRC = r'''
import json, os, sys

pid = int(sys.argv[1])
nproc = int(sys.argv[2])
addr = sys.argv[3]
db_path = sys.argv[4]

os.environ["XLA_FLAGS"] = "--xla_force_host_platform_device_count=2"
os.environ["JAX_PLATFORMS"] = "cpu"
sys.path.insert(0, %(repo)r)

import jax
jax.config.update("jax_platforms", "cpu")
import numpy as np
from jax.sharding import NamedSharding, PartitionSpec as P

from predictionio_tpu.parallel.mesh import init_distributed, make_mesh

init_distributed(coordinator_address=addr, num_processes=nproc, process_id=pid)
assert jax.process_index() == pid
assert jax.process_count() == nproc
n_global = len(jax.devices())
assert n_global == 2 * nproc, jax.devices()

# --- global-mesh collective: data-sharded sum (psum over DCN) ----------
mesh = make_mesh((n_global,), ("data",))
sh = NamedSharding(mesh, P("data"))
rows = 2 * n_global
full = np.arange(rows, dtype=np.float32)
local = full[pid * (rows // nproc):(pid + 1) * (rows // nproc)]
arr = jax.make_array_from_process_local_data(sh, local)
total = jax.jit(lambda x: x.sum(), out_shardings=NamedSharding(mesh, P()))(arr)
total_host = float(np.asarray(total))

# --- multi-host event slice over the SHARED store ----------------------
from predictionio_tpu.storage import Storage
Storage.reset()
Storage.configure("METADATA", "sqlite", path=db_path + ".meta")
Storage.configure("EVENTDATA", "sqlite", path=db_path)
from predictionio_tpu.store.event_store import EventStore
store = EventStore()
frame = store.find_frame(app_name="mh", host_shard=(pid, nproc))
entities = sorted(set(frame.entity_id))

print("RESULT " + json.dumps({
    "pid": pid, "process_count": jax.process_count(),
    "global_devices": n_global, "total": total_host,
    "entities": entities,
}), flush=True)
'''


def _free_port() -> int:
    with socket.socket() as s:
        s.bind(("127.0.0.1", 0))
        return s.getsockname()[1]



def _run_workers(worker_path, args_for_pid, timeout, fail_label):
    """Spawn one worker per pid, collect RESULT lines, kill-all on
    timeout — the shared boilerplate of every multihost test here."""
    env = dict(os.environ)
    env.pop("PYTHONSTARTUP", None)
    procs = [
        subprocess.Popen(
            [sys.executable, str(worker_path), *args_for_pid(pid)],
            stdout=subprocess.PIPE, stderr=subprocess.PIPE, text=True,
            env=env, cwd=str(worker_path.parent),
        )
        for pid in range(2)
    ]
    results = {}
    for p in procs:
        try:
            out, err = p.communicate(timeout=timeout)
        except subprocess.TimeoutExpired:
            for q in procs:
                q.kill()
            pytest.fail(f"{fail_label} worker timed out")
        assert p.returncode == 0, err[-3000:]
        for line in out.splitlines():
            if line.startswith("RESULT "):
                r = json.loads(line[7:])
                results[r["pid"]] = r
    assert set(results) == {0, 1}
    return results


@pytest.mark.multihost
def test_two_process_distributed_psum_and_host_sharded_load(tmp_path):
    # seed a shared sqlite event store with 40 entities of events
    from predictionio_tpu.storage import Storage
    from predictionio_tpu.storage.event import Event
    from predictionio_tpu.storage.sqlite import SQLiteEvents
    from datetime import datetime, timezone

    db_path = str(tmp_path / "events.db")
    # metadata must be shared too: workers resolve app_name -> app_id
    Storage.configure("METADATA", "sqlite", path=db_path + ".meta")
    app_id = Storage.get_metadata().app_insert("mh").id
    be = SQLiteEvents({"path": db_path})
    be.init_app(app_id)
    t = datetime(2020, 1, 1, tzinfo=timezone.utc)
    for i in range(40):
        be.insert(Event(event="rate", entity_type="user",
                        entity_id=f"u{i}", event_time=t,
                        properties={"rating": 4.0}), app_id)
    be.close()

    worker = tmp_path / "worker.py"
    worker.write_text(WORKER_SRC % {"repo": str(REPO)})
    addr = f"127.0.0.1:{_free_port()}"
    results = _run_workers(worker, lambda pid: [str(pid), "2", addr, db_path],
                           240, "multihost")

    rows = 2 * results[0]["global_devices"]
    expected_total = sum(range(rows))
    for r in results.values():
        assert r["process_count"] == 2
        assert r["global_devices"] == 4
        assert r["total"] == expected_total  # psum crossed the processes

    e0 = set(results[0]["entities"])
    e1 = set(results[1]["entities"])
    assert e0 and e1, "both hosts must get a non-empty slice"
    assert not (e0 & e1), "host shards must be disjoint"
    assert e0 | e1 == {f"u{i}" for i in range(40)}, "shards must cover all"


ALS_WORKER_SRC = r'''
import json, os, sys

pid = int(sys.argv[1])
nproc = int(sys.argv[2])
addr = sys.argv[3]
db_path = sys.argv[4]

os.environ["XLA_FLAGS"] = "--xla_force_host_platform_device_count=2"
os.environ["JAX_PLATFORMS"] = "cpu"
sys.path.insert(0, %(repo)r)

import jax
jax.config.update("jax_platforms", "cpu")
import numpy as np
from jax.experimental import multihost_utils
from jax.sharding import NamedSharding, PartitionSpec as P

from predictionio_tpu.parallel.mesh import init_distributed, make_mesh
from predictionio_tpu.models.als import make_train_step, put_layout
from predictionio_tpu.ops.neighbors import build_bilinear_layout

init_distributed(coordinator_address=addr, num_processes=nproc, process_id=pid)
n_global = len(jax.devices())
mesh = make_mesh((n_global,), ("data",))

# 1. each process loads ONLY its host_shard slice of the rating events
from predictionio_tpu.storage import Storage
Storage.reset()
Storage.configure("METADATA", "sqlite", path=db_path + ".meta")
Storage.configure("EVENTDATA", "sqlite", path=db_path)
from predictionio_tpu.store.event_store import EventStore
frame = EventStore().find_frame(app_name="mhals", host_shard=(pid, nproc))
# test corpus uses dense integer ids baked into the entity names, so the
# global index space needs no BiMap exchange (production would allgather
# the id maps the same way the triples travel below)
local = np.array(
    [(int(e[1:]), int(t[1:]), p["rating"])
     for e, t, p in zip(frame.entity_id, frame.target_entity_id,
                        frame.properties)], dtype=np.float32)

# 2. the layout must be identical on every process: allgather the local
#    triples (the one shuffle this design needs — MLlib reshuffles factor
#    blocks every iteration, reference ALSModel.scala:172-179)
pad = np.full((%(max_local)d - len(local), 3), -1, np.float32)
mine = np.concatenate([local, pad]) if len(pad) else local
gathered = multihost_utils.process_allgather(mine)  # [nproc, max_local, 3]
trip = gathered.reshape(-1, 3)
trip = trip[trip[:, 0] >= 0]
users = trip[:, 0].astype(np.int64)
items = trip[:, 1].astype(np.int64)
vals = trip[:, 2].astype(np.float32)
nu, ni = %(nu)d, %(ni)d

if %(split)r:
    # gather costs that slice tables of test size (tests/helpers.py), so
    # that the layout's hot parts travel too
    from predictionio_tpu.ops import neighbors
    neighbors.GATHER_NS_BY_TABLE_ROWS = ((8, 4.0),)
    neighbors.COLD_WIDTH_SIGMAS = 0.0
u_lay, i_lay = build_bilinear_layout(users, items, vals, nu, ni, seed=11)
split_buckets = sum(b.hot_ids is not None
                    for lay in (u_lay, i_lay) for b in lay.buckets)

# 3. global block arrays assembled from per-process local slices
u_bk = put_layout(u_lay, mesh)
i_bk = put_layout(i_lay, mesh)
# u0/v0 init mirrors train_als (same PRNG stream for the parity check;
# u0 only seeds the CG warm start and is inert under cholesky)
import jax.numpy as jnp
k_u, k_v = jax.random.split(jax.random.PRNGKey(11))
v_host = np.zeros((i_lay.slots, 4), np.float32)
v_host[i_lay.pos] = (np.abs(np.asarray(
    jax.random.normal(k_v, (ni, 4), dtype=jnp.float32))) / np.sqrt(4))
v = jax.make_array_from_process_local_data(NamedSharding(mesh, P()), v_host)
u_host = np.zeros((u_lay.slots, 4), np.float32)
u_host[u_lay.pos] = (np.abs(np.asarray(
    jax.random.normal(k_u, (nu, 4), dtype=jnp.float32))) / np.sqrt(4))
u = jax.make_array_from_process_local_data(NamedSharding(mesh, P()), u_host)

# 4. the SHARED train step, unchanged, over the multi-process mesh
step = make_train_step(mesh, u_lay, i_lay, rank=4, lambda_=0.05,
                       solver="cholesky")
for _ in range(3):
    u, v = step(u_bk, i_bk, u, v)
uf = np.asarray(u)[u_lay.pos]
vf = np.asarray(v)[i_lay.pos]
print("RESULT " + json.dumps({
    "pid": pid, "u": uf.tolist(), "v": vf.tolist(),
    "split_buckets": split_buckets,
    "shipped": sum("hot_ids" in e for e in u_bk + i_bk)}), flush=True)
'''


@pytest.mark.multihost
@pytest.mark.parametrize("split", [False, True])
def test_two_process_als_training_parity(tmp_path, split):
    """The Spark-executor replacement, end to end: two
    processes each load only their host_shard event slice, assemble the
    global blocked layout via jax.make_array_from_process_local_data, run
    the SHARED make_train_step over the cross-process mesh, and produce
    factors matching single-process training. ``split``: the tables are
    sliced, so `put_layout` ships each split bucket's hot part as it
    ships ids and vals, each process its own rows of them."""
    import numpy as np

    from predictionio_tpu.models.als import ALSConfig, train_als
    from predictionio_tpu.storage import Storage
    from predictionio_tpu.storage.bimap import BiMap
    from predictionio_tpu.storage.event import Event
    from predictionio_tpu.storage.frame import Ratings
    from predictionio_tpu.storage.sqlite import SQLiteEvents
    from datetime import datetime, timezone

    nu, ni = 24, 16
    rng = np.random.default_rng(3)
    density = np.full((nu, ni), 0.6)
    if split:  # eight items that nearly everyone meets, and a long tail
        ni = 48
        density = np.full((nu, ni), 0.2)
        density[:, :8] = 0.95
    u_true = rng.normal(size=(nu, 3)) + 1
    v_true = rng.normal(size=(ni, 3)) + 1
    full = u_true @ v_true.T
    mask = rng.random((nu, ni)) < density
    rows, cols = np.nonzero(mask)
    vals = np.round(full[rows, cols] * 2) / 2  # half-star: exact in f32

    db_path = str(tmp_path / "als_events.db")
    Storage.reset()
    Storage.configure("METADATA", "sqlite", path=db_path + ".meta")
    app_id = Storage.get_metadata().app_insert("mhals").id
    be = SQLiteEvents({"path": db_path})
    be.init_app(app_id)
    t = datetime(2020, 1, 1, tzinfo=timezone.utc)
    for r, c, x in zip(rows, cols, vals):
        be.insert(Event(event="rate", entity_type="user", entity_id=f"u{r}",
                        target_entity_type="item", target_entity_id=f"i{c}",
                        event_time=t, properties={"rating": float(x)}),
                  app_id)
    be.close()
    Storage.reset()

    worker = tmp_path / "als_worker.py"
    worker.write_text(ALS_WORKER_SRC % {
        "repo": str(REPO), "max_local": len(rows), "nu": nu, "ni": ni,
        "split": split})
    addr = f"127.0.0.1:{_free_port()}"
    results = _run_workers(worker, lambda pid: [str(pid), "2", addr, db_path],
                           300, "ALS multihost")

    for r in results.values():
        assert (r["split_buckets"] > 0) == split
        assert r["shipped"] == r["split_buckets"]
    # both processes computed the same global model...
    u0 = np.asarray(results[0]["u"])
    u1 = np.asarray(results[1]["u"])
    np.testing.assert_allclose(u0, u1, rtol=1e-5, atol=1e-6)

    # ...and it matches single-process training on the union of the data
    # (cholesky = exact per-row solve, so factors are independent of the
    # entry order the allgather produced, up to f32 summation noise)
    ratings = Ratings(
        user_indices=rows.astype(np.int64), item_indices=cols.astype(np.int64),
        ratings=vals.astype(np.float32),
        user_ids=BiMap({f"u{i}": i for i in range(nu)}),
        item_ids=BiMap({f"i{j}": j for j in range(ni)}),
    )
    ref = train_als(ratings, ALSConfig(rank=4, iterations=3, lambda_=0.05,
                                       solver="cholesky", seed=11))
    np.testing.assert_allclose(u0, ref.user_factors, rtol=2e-3, atol=2e-4)
    np.testing.assert_allclose(np.asarray(results[0]["v"]),
                               ref.item_factors, rtol=2e-3, atol=2e-4)


SERVE_WORKER_SRC = r'''
import json, os, sys

pid = int(sys.argv[1])
nproc = int(sys.argv[2])
addr = sys.argv[3]

os.environ["XLA_FLAGS"] = "--xla_force_host_platform_device_count=2"
os.environ["JAX_PLATFORMS"] = "cpu"
sys.path.insert(0, %(repo)r)

import jax
jax.config.update("jax_platforms", "cpu")
import numpy as np

from predictionio_tpu.ops.retrieval import ShardedDeviceRetriever
from predictionio_tpu.parallel.mesh import init_distributed, make_mesh

init_distributed(coordinator_address=addr, num_processes=nproc, process_id=pid)
n_global = len(jax.devices())
assert n_global == 2 * nproc

# identical catalog + queries on every host (SPMD: all processes run the
# same serving program; each holds only its 1/P catalog shard in "HBM")
rng = np.random.default_rng(7)
items = rng.standard_normal((1000, 16)).astype(np.float32)
q = rng.standard_normal((3, 16)).astype(np.float32)

mesh = make_mesh((n_global,), ("model",))
ret = ShardedDeviceRetriever(items, mesh)
n_local = sum(s.data.shape[0] for s in ret._items.addressable_shards)
vals, idx = ret.topk(q, 7)

print("RESULT " + json.dumps({
    "pid": pid,
    "rows_local": int(n_local),
    "rows_global": int(ret._items.shape[0]),
    "vals": np.asarray(vals).tolist(),
    "idx": np.asarray(idx).tolist(),
}), flush=True)
'''


@pytest.mark.multihost
def test_two_process_sharded_serving_parity(tmp_path):
    """Serving-plane counterpart of the ALS multihost test: the catalog
    shards over a mesh spanning two processes, each host materializes
    only its addressable shards, and the sharded top-k matches exact
    host scoring on both processes."""
    import numpy as np

    worker = tmp_path / "serve_worker.py"
    worker.write_text(SERVE_WORKER_SRC % {"repo": str(REPO)})
    addr = f"127.0.0.1:{_free_port()}"
    results = _run_workers(worker, lambda pid: [str(pid), "2", addr],
                           300, "sharded-serving multihost")

    # each process holds exactly HALF the (padded) catalog locally
    for r in results.values():
        assert r["rows_local"] * 2 == r["rows_global"]

    # both processes agree, and match exact host scoring
    rng = np.random.default_rng(7)
    items = rng.standard_normal((1000, 16)).astype(np.float32)
    q = rng.standard_normal((3, 16)).astype(np.float32)
    want = np.sort(q @ items.T, axis=1)[:, ::-1][:, :7]
    for r in results.values():
        np.testing.assert_allclose(np.asarray(r["vals"]), want,
                                   rtol=1e-5, atol=1e-5)
        idx = np.asarray(r["idx"])
        got = np.take_along_axis(q @ items.T, idx, axis=1)
        np.testing.assert_allclose(got, want, rtol=1e-5, atol=1e-5)
    assert results[0]["idx"] == results[1]["idx"]


# ---------------------------------------------------------------------------
# elastic multi-host training (ISSUE 8): sharded checkpoints, N→M resume,
# host-loss tolerance.
#
# This container's CPU jaxlib cannot run multi-process XLA collectives
# ("Multiprocess computations aren't implemented on the CPU backend"), so
# the elastic workers below do NOT call jax.distributed.initialize — each
# "host" is its own single-process JAX, and the ONLY coordination between
# them is the surface under test: the sharded-manifest checkpoint protocol
# (per-process shards, FileBarrier rendezvous, process-0 manifest commit).


def test_init_distributed_fails_loud_on_partial_config(monkeypatch):
    """ISSUE 8 satellite: a half-configured host must never silently join
    (or silently skip) a distributed run."""
    from predictionio_tpu.parallel.mesh import init_distributed

    for var in ("JAX_COORDINATOR_ADDRESS", "JAX_NUM_PROCESSES",
                "JAX_PROCESS_ID"):
        monkeypatch.delenv(var, raising=False)
    # no coordinator anywhere → single-host no-op
    assert init_distributed() is None
    with pytest.raises(ValueError, match="num_processes and process_id"):
        init_distributed(coordinator_address="host0:1234")
    with pytest.raises(ValueError, match="process_id"):
        init_distributed(coordinator_address="host0:1234", num_processes=2)
    with pytest.raises(ValueError, match="out of range"):
        init_distributed(coordinator_address="host0:1234",
                         num_processes=2, process_id=5)


def test_init_distributed_fails_loud_on_partial_env(monkeypatch):
    from predictionio_tpu.parallel.mesh import init_distributed

    monkeypatch.setenv("JAX_COORDINATOR_ADDRESS", "host0:1234")
    monkeypatch.delenv("JAX_NUM_PROCESSES", raising=False)
    monkeypatch.delenv("JAX_PROCESS_ID", raising=False)
    with pytest.raises(ValueError, match="JAX_NUM_PROCESSES"):
        init_distributed()
    monkeypatch.setenv("JAX_NUM_PROCESSES", "two")
    with pytest.raises(ValueError, match="not an integer"):
        init_distributed()


def test_sharded_manifest_resumes_across_topologies(tmp_path):
    """Loader-level N→M bit parity, no subprocesses: a state saved by N
    writers reassembles identically for any reader topology M."""
    import threading

    from predictionio_tpu.workflow.checkpoint import (
        ShardedTrainCheckpointer, reshard_state)

    rng = np.random.default_rng(4)
    state = {"u": rng.standard_normal((13, 6)).astype(np.float32),
             "v": rng.standard_normal((9, 6)).astype(np.float32),
             "it": np.int64(2), "fp": np.uint64(99)}

    # 1-writer save → 2-process reader slices (1→2)
    d1 = tmp_path / "n1"
    ShardedTrainCheckpointer(d1).save(2, state)
    _, global_state = ShardedTrainCheckpointer(d1).restore()
    slices = [reshard_state(global_state, process_id=p, num_processes=2)
              for p in range(2)]
    np.testing.assert_array_equal(
        np.concatenate([s["u"] for s in slices]), state["u"])
    np.testing.assert_array_equal(
        np.concatenate([s["v"] for s in slices]), state["v"])

    # 2-writer save (threads stand in for the hosts) → 1-process reader
    # reassembles the global matrices bitwise (2→1)
    d2 = tmp_path / "n2"
    cks = [ShardedTrainCheckpointer(d2, process_id=p, num_processes=2,
                                    barrier_timeout_s=30.0)
           for p in range(2)]
    threads = [threading.Thread(target=ck.save, args=(2, state))
               for ck in cks]
    for t in threads:
        t.start()
    for t in threads:
        t.join(60)
    step, got = ShardedTrainCheckpointer(d2).restore()
    assert step == 2
    np.testing.assert_array_equal(got["u"], state["u"])
    np.testing.assert_array_equal(got["v"], state["v"])
    assert int(got["it"]) == 2 and int(got["fp"]) == 99


def _elastic_ratings():
    """The deterministic corpus every elastic worker regenerates —
    np.default_rng is stable across processes, so no storage is needed."""
    rng = np.random.default_rng(0)
    nu, ni, n = 40, 30, 600
    from predictionio_tpu.storage.bimap import BiMap
    from predictionio_tpu.storage.frame import Ratings

    return Ratings(
        user_indices=rng.integers(0, nu, n).astype(np.int64),
        item_indices=rng.integers(0, ni, n).astype(np.int64),
        ratings=(rng.random(n).astype(np.float32) * 4 + 1),
        user_ids=BiMap({f"u{i}": i for i in range(nu)}),
        item_ids=BiMap({f"i{i}": i for i in range(ni)}),
    )


_ELASTIC_PRELUDE = r'''
import json, os, sys

pid = int(sys.argv[1])
nproc = int(sys.argv[2])
ckpt_dir = sys.argv[3]

# 8 virtual devices to MATCH the parent suite's mesh: the parity check
# compares factors across the kill/resume boundary, and the CG inner
# solver amplifies device-count-dependent reduction-order noise
os.environ["XLA_FLAGS"] = "--xla_force_host_platform_device_count=8"
os.environ["JAX_PLATFORMS"] = "cpu"
sys.path.insert(0, %(repo)r)

import jax
jax.config.update("jax_platforms", "cpu")
import numpy as np

from predictionio_tpu.models.als import ALSConfig, train_als
from predictionio_tpu.storage.bimap import BiMap
from predictionio_tpu.storage.frame import Ratings
from predictionio_tpu.workflow.checkpoint import ShardedTrainCheckpointer
from predictionio_tpu.faults import FAULTS, FaultInjected
from predictionio_tpu.workflow.supervisor import classify_error


def _elastic_ratings():
    rng = np.random.default_rng(0)
    nu, ni, n = 40, 30, 600
    return Ratings(
        user_indices=rng.integers(0, nu, n).astype(np.int64),
        item_indices=rng.integers(0, ni, n).astype(np.int64),
        ratings=(rng.random(n).astype(np.float32) * 4 + 1),
        user_ids=BiMap({f"u{i}": i for i in range(nu)}),
        item_ids=BiMap({f"i{i}": i for i in range(ni)}),
    )

# cholesky: the exact per-row solver — resume parity is bit-level, free
# of the CG depth schedule (train_als uses cold depth below 3 iterations)
cfg = ALSConfig(rank=8, iterations=4, lambda_=0.1, seed=5, solver="cholesky")
'''

CHAOS_WORKER_SRC = _ELASTIC_PRELUDE + r'''
ck = ShardedTrainCheckpointer(ckpt_dir, process_id=pid, num_processes=nproc,
                              barrier_timeout_s=10.0)
if pid == 1:
    # host 1 dies at its SECOND shard write: step 1 commits first, then
    # the host is gone mid-step-2 (the instrumented chaos site IS the
    # death point — no cleanup, no barrier mark)
    FAULTS.inject("checkpoint.shard_write", "error", after=1)
try:
    train_als(_elastic_ratings(), cfg, checkpointer=ck, checkpoint_every=1)
    result = {"pid": pid, "outcome": "completed"}
except FaultInjected:
    print("RESULT " + json.dumps({"pid": pid, "outcome": "died"}), flush=True)
    os._exit(0)
except Exception as e:
    result = {"pid": pid, "outcome": "aborted",
              "classification": classify_error(e),
              "error": type(e).__name__,
              "complete": ck.steps(), "partial": ck.partial_steps()}
print("RESULT " + json.dumps(result), flush=True)
'''


@pytest.mark.multihost
def test_host_loss_mid_run_then_elastic_resume_2_to_1(tmp_path):
    """ISSUE 8 acceptance: 2-process elastic training, one worker killed
    mid-step at the `checkpoint.shard_write` chaos site. The survivor
    classifies the loss transient (barrier timeout) and reports the last
    complete step; a relaunch at M=1 resumes from the 2-shard step-1
    manifest, discards the torn step, and converges to parity with an
    uninterrupted run."""
    from predictionio_tpu.models.als import ALSConfig, train_als
    from predictionio_tpu.workflow.checkpoint import ShardedTrainCheckpointer
    from predictionio_tpu.faults import FAULTS

    ckpt = tmp_path / "ck"
    worker = tmp_path / "chaos_worker.py"
    worker.write_text(CHAOS_WORKER_SRC % {"repo": str(REPO)})
    results = _run_workers(worker,
                           lambda pid: [str(pid), "2", str(ckpt)],
                           240, "host-loss chaos")

    assert results[1]["outcome"] == "died"
    surv = results[0]
    assert surv["outcome"] == "aborted"
    assert surv["error"] == "BarrierTimeoutError"
    assert surv["classification"] == "transient"  # → supervisor retries
    assert surv["complete"] == [1]  # step 2 never got a manifest
    assert surv["partial"] == [2]   # the survivor's lone step-2 shard

    # relaunch at M=1 (2→1): resume from the last complete manifest
    cfg = ALSConfig(rank=8, iterations=4, lambda_=0.1, seed=5,
                    solver="cholesky")
    baseline = train_als(_elastic_ratings(), cfg)
    ck = ShardedTrainCheckpointer(ckpt)
    FAULTS.inject("train.step", "slow", delay_s=0.0)  # firing counter only
    try:
        resumed = train_als(_elastic_ratings(), cfg,
                            checkpointer=ck, checkpoint_every=1)
        # resumed from step 1, not restarted: iterations 2-4 ran
        assert FAULTS.fired("train.step") == 3
    finally:
        FAULTS.clear()
    # the torn step was discarded and recorded for `pio status`
    assert [e["step"] for e in ck.discarded()] == [2]
    assert 2 not in ck.steps()
    np.testing.assert_allclose(resumed.item_factors, baseline.item_factors,
                               rtol=1e-5, atol=1e-5)
    np.testing.assert_allclose(resumed.user_factors, baseline.user_factors,
                               rtol=1e-5, atol=1e-5)


RESUME_WORKER_SRC = _ELASTIC_PRELUDE + r'''
ck = ShardedTrainCheckpointer(ckpt_dir, process_id=pid, num_processes=nproc,
                              barrier_timeout_s=60.0)
FAULTS.inject("train.step", "slow", delay_s=0.0)  # firing counter only
model = train_als(_elastic_ratings(), cfg, checkpointer=ck, checkpoint_every=1)
print("RESULT " + json.dumps({
    "pid": pid,
    "steps_run": FAULTS.fired("train.step"),
    "complete": ck.steps(),
    "u": model.user_factors.tolist(),
    "v": model.item_factors.tolist(),
}), flush=True)
'''


@pytest.mark.multihost
def test_elastic_resume_1_to_2_bit_level_restore(tmp_path):
    """The other direction (1→2): a single-process run checkpoints 2 of 4
    iterations, then TWO elastic workers resume from its 1-shard manifest.
    Both must RESUME (2 device steps each, not 4), agree with each other,
    and match the uninterrupted single-process run."""
    from predictionio_tpu.models.als import ALSConfig, train_als
    from predictionio_tpu.workflow.checkpoint import ShardedTrainCheckpointer

    ckpt = tmp_path / "ck"
    cfg2 = ALSConfig(rank=8, iterations=2, lambda_=0.1, seed=5,
                     solver="cholesky")
    train_als(_elastic_ratings(), cfg2,
              checkpointer=ShardedTrainCheckpointer(ckpt),
              checkpoint_every=1)
    assert ShardedTrainCheckpointer(ckpt).latest_step() == 2

    worker = tmp_path / "resume_worker.py"
    worker.write_text(RESUME_WORKER_SRC % {"repo": str(REPO)})
    results = _run_workers(worker,
                           lambda pid: [str(pid), "2", str(ckpt)],
                           240, "elastic 1→2 resume")

    for r in results.values():
        assert r["steps_run"] == 2  # resumed at step 2, ran 3 and 4 only
        assert r["complete"] == [3, 4]  # keep=2 window advanced
    # the two hosts computed the same model from the resharded state...
    np.testing.assert_allclose(np.asarray(results[0]["u"]),
                               np.asarray(results[1]["u"]),
                               rtol=1e-6, atol=1e-7)
    # ...and it matches the uninterrupted 4-iteration run
    cfg4 = ALSConfig(rank=8, iterations=4, lambda_=0.1, seed=5,
                     solver="cholesky")
    baseline = train_als(_elastic_ratings(), cfg4)
    np.testing.assert_allclose(np.asarray(results[0]["u"]),
                               baseline.user_factors, rtol=1e-5, atol=1e-5)
    np.testing.assert_allclose(np.asarray(results[0]["v"]),
                               baseline.item_factors, rtol=1e-5, atol=1e-5)
