"""The path `chip_smoke.py` drives, pinned on the CPU.

What only a chip shows is checked by `python chip_smoke.py` there. What a
CPU can show is here: the smoke's own control flow (its rehearsal passes,
it refuses to run without an accelerator, its parent stays off JAX),
where the compile cache goes, that nothing on the deploy path trades a
failed device step for host scoring, that the stock flash kernel's
errors come out, that a run names its device, and that a native library
of another source is never loaded.
"""

import json
import os
import shutil
import socket
import subprocess
import sys
from pathlib import Path

import jax
import numpy as np
import pytest

from predictionio_tpu.tools import cli
from tests.test_capture_replay import _train_quickstart

REPO = Path(__file__).resolve().parents[1]
CHECKOUT_CACHE = REPO / ".xla_cache"


def _child_env(**extra):
    env = dict(os.environ)
    env["PYTHONPATH"] = os.pathsep.join(
        p for p in (str(REPO), env.get("PYTHONPATH")) if p)
    env.pop("JAX_COMPILATION_CACHE_DIR", None)
    env.update(extra)
    return env


def _listing(d: Path) -> set:
    return {p.name for p in d.iterdir()} if d.is_dir() else set()


# ---------------------------------------------------------------------------
# chip_smoke.py itself


def test_smoke_rehearsal_passes_with_the_parent_off_jax(tmp_path):
    """The whole sequence (app new, import, train, deploy, 321 requests,
    stats, stop, reference check) at the rehearsal's tiny size. The
    parent runs under a JAX platform that does not exist, so its first
    touch of a JAX backend would end it; the children get `cpu`. With
    JAX_COMPILATION_CACHE_DIR set, train and deploy write their cache
    there and the checkout's own directory gains nothing. The checkout
    is a copy of its own under tmp_path: other test files, run beside
    this one, write to the real checkout's `.xla_cache` meanwhile."""
    cache = tmp_path / "placed-cache"
    checkout = tmp_path / "checkout"
    for name in ("predictionio_tpu", "templates"):
        shutil.copytree(REPO / name, checkout / name,
                        ignore=shutil.ignore_patterns("__pycache__"))
    shutil.copy(REPO / "chip_smoke.py", checkout)
    own_cache = checkout / ".xla_cache"
    driver = (
        "import os, sys\n"
        f"sys.path.insert(0, {str(checkout)!r})\n"
        "import chip_smoke\n"
        "class ChildrenOnCpu(chip_smoke.Smoke):\n"
        "    def __init__(self, args):\n"
        "        super().__init__(args)\n"
        "        self.env['JAX_PLATFORMS'] = 'cpu'\n"
        "chip_smoke.Smoke = ChildrenOnCpu\n"
        "raise SystemExit(chip_smoke.main(['--rehearse']))\n")
    p = subprocess.run(
        [sys.executable, "-c", driver], capture_output=True, text=True,
        timeout=600, cwd=tmp_path,
        env=_child_env(JAX_PLATFORMS="no-such-platform",
                       JAX_COMPILATION_CACHE_DIR=str(cache),
                       PYTHONPATH=str(checkout)))
    assert p.returncode == 0, p.stderr[-4000:]
    result, verdict = map(json.loads, p.stdout.splitlines()[-2:])
    assert "rehearsal" in result  # labelled: never read as a chip run
    # the last line is the verdict alone, with exactly these keys
    assert verdict == {"ok": True, "device": result["device"]}
    assert verdict["device"] == {"platform": "cpu", "kind": "cpu",
                                 "count": verdict["device"]["count"]}
    assert isinstance(verdict["device"]["count"], int)
    assert result["serving"]["compiles_after_prewarm"] == 0
    assert result["serving"]["answers_checked"] == 320
    assert result["cache"]["dir"] == str(cache)
    assert 1 <= result["cache"]["entries_after_train"] \
        < result["cache"]["entries_after_deploy"]
    assert _listing(own_cache) == set()


def test_smoke_without_a_chip_fails_and_prints_no_result(tmp_path):
    """No accelerator and no `--rehearse`: non-zero exit, the reason on
    stderr, nothing on stdout that could be read as a result."""
    p = subprocess.run(
        [sys.executable, str(REPO / "chip_smoke.py")], capture_output=True,
        text=True, timeout=300, cwd=tmp_path, env=_child_env())
    assert p.returncode != 0
    assert "no accelerator" in p.stderr
    assert p.stdout.strip() == ""


def test_importing_the_console_leaves_jax_alone():
    """Routers, supervisors, `pio import`, the event server and the
    smoke's parent all import the console: a chip-holding child could not
    start beside them if that import took JAX along."""
    code = ("import sys; import predictionio_tpu.tools.cli, "
            "predictionio_tpu.storage, predictionio_tpu.native, "
            "predictionio_tpu.workflow.serialization, "
            "predictionio_tpu.models.als, predictionio_tpu.api, "
            "predictionio_tpu.tools.import_export, "
            "predictionio_tpu.tools.dashboard, predictionio_tpu.tools.admin, "
            "predictionio_tpu.workflow.fleet, "
            "predictionio_tpu.workflow.supervise; "
            "sys.exit('jax' in sys.modules)")
    p = subprocess.run([sys.executable, "-c", code], env=_child_env(),
                       capture_output=True, text=True, timeout=120)
    assert p.returncode == 0, p.stderr[-2000:]


# ---------------------------------------------------------------------------
# one compile-cache function, placeable from outside


@pytest.fixture
def cache_config():
    """Put JAX's cache configuration back after a test moved it."""
    names = ("jax_compilation_cache_dir",
             "jax_persistent_cache_min_compile_time_secs")
    old = {n: getattr(jax.config, n) for n in names}
    yield
    for n, v in old.items():
        jax.config.update(n, v)


def test_cache_is_the_checkouts_whatever_pio_home_and_cwd(
        tmp_path, monkeypatch, cache_config):
    monkeypatch.delenv("JAX_COMPILATION_CACHE_DIR", raising=False)
    seen = []
    for name in ("a", "b"):
        home, cwd = tmp_path / f"home-{name}", tmp_path / f"cwd-{name}"
        home.mkdir()
        cwd.mkdir()
        monkeypatch.setenv("PIO_HOME", str(home))
        monkeypatch.chdir(cwd)
        jax.config.update("jax_compilation_cache_dir", None)
        cli._enable_compile_cache()
        seen.append(jax.config.jax_compilation_cache_dir)
        assert cli.compile_cache_dir() == seen[-1]
        assert _listing(home) == set() and _listing(cwd) == set()
    assert seen == [str(CHECKOUT_CACHE)] * 2


def test_cache_dir_from_the_environment_is_left_alone(
        tmp_path, monkeypatch, cache_config):
    """JAX reads JAX_COMPILATION_CACHE_DIR itself; the code sets no
    directory over it (the sentinel stands for what JAX read)."""
    monkeypatch.setenv("JAX_COMPILATION_CACHE_DIR", str(tmp_path / "placed"))
    jax.config.update("jax_compilation_cache_dir", "what-jax-read")
    cli._enable_compile_cache()
    assert jax.config.jax_compilation_cache_dir == "what-jax-read"
    assert cli.compile_cache_dir() == str(tmp_path / "placed")


def test_one_helper_knows_the_cache():
    hits = [p.relative_to(REPO).as_posix()
            for p in (REPO / "predictionio_tpu").rglob("*.py")
            if "xla_cache" in p.read_text() or "PIO_XLA_CACHE" in p.read_text()]
    assert hits == ["predictionio_tpu/tools/cli.py"]


# ---------------------------------------------------------------------------
# no silent host fallback on deploy and /reload


def _free_port() -> int:
    with socket.socket() as s:
        s.bind(("127.0.0.1", 0))
        return s.getsockname()[1]


@pytest.mark.parametrize("broken", [
    "predictionio_tpu.models.als.ALSModel.attach_retriever",
    "predictionio_tpu.models.als.ALSModel.attach_pipeline",
    "predictionio_tpu.ops.retrieval.DeviceRetriever.prewarm",
    "predictionio_tpu.ops.pipeline.ServingPipeline.prewarm",
])
def test_failed_attach_or_prewarm_fails_deploy_and_reload(
        tmp_path, rng, monkeypatch, broken):
    """A retriever attach, pipeline attach or prewarm that raises ends
    `pio deploy` with the error and nothing bound; on /reload it fails
    the reload and the bundle that was serving keeps answering."""
    from predictionio_tpu.workflow.create_server import EngineServer

    engine, inst = _train_quickstart(tmp_path, rng, "failtest")
    server = EngineServer(engine, inst)
    bundle = server.deployed
    query = {"user": "u1", "num": 4}
    answer = server.serve_query(query)
    assert answer["itemScores"]

    def boom(*a, **kw):
        raise RuntimeError("device step refused")

    monkeypatch.setattr(broken, boom)
    with pytest.raises(RuntimeError, match="device step refused"):
        server.reload_latest()
    assert server.deployed is bundle
    assert server.serve_query(query) == answer

    port = _free_port()
    with pytest.raises(RuntimeError, match="device step refused"):
        cli.main(["deploy", "--engine-dir", str(tmp_path / "myrec"),
                  "--ip", "127.0.0.1", "--port", str(port)])
    with socket.socket() as s:
        assert s.connect_ex(("127.0.0.1", port)) != 0  # nothing bound


def test_failed_deferred_prewarm_never_reports_ready(
        tmp_path, rng, monkeypatch):
    """`--prewarm-async` binds first, so there a failed prewarm cannot
    mean nothing bound: it means the server never says ready."""
    from predictionio_tpu.workflow.create_server import EngineServer

    engine, inst = _train_quickstart(tmp_path, rng, "defertest")
    server = EngineServer(engine, inst, defer_prewarm=True)
    monkeypatch.setattr(
        "predictionio_tpu.ops.pipeline.ServingPipeline.prewarm",
        lambda *a, **kw: (_ for _ in ()).throw(RuntimeError("no compile")))
    with pytest.raises(RuntimeError, match="no compile"):
        server.complete_prewarm()
    assert server.health()["ready"] is False


def test_flash_attention_reraises_what_the_stock_kernel_raises(monkeypatch):
    """On the TPU branch the shape test picks the kernel; an error of
    the kernel it picked is the caller's to see. (Here the backend is
    only said to be a TPU, so the Mosaic kernel cannot lower: before,
    that was swallowed and the blockwise path answered instead.)"""
    from predictionio_tpu.parallel.ring_attention import flash_attention

    x = jax.numpy.ones((1, 128, 2, 64), jax.numpy.float32)
    np.testing.assert_allclose(  # the CPU branch still answers
        np.asarray(flash_attention(x, x, x)), 1.0, rtol=1e-5)
    monkeypatch.setattr(jax, "default_backend", lambda: "tpu")
    with pytest.raises(Exception):  # noqa: B017 — whatever Mosaic raises
        jax.block_until_ready(flash_attention(x, x, x))


# ---------------------------------------------------------------------------
# a run names its device


def test_train_stamps_its_backend_and_stats_name_the_device(tmp_path, rng):
    from predictionio_tpu.workflow.create_server import EngineServer

    engine, inst = _train_quickstart(tmp_path, rng, "nametest")
    devices = jax.devices()
    assert inst.backend_conf == {
        "platform": "cpu", "device_kind": devices[0].device_kind,
        "device_count": len(devices), "mesh": {"data": len(devices)},
        "native": True}
    (attempt,) = json.loads(inst.convergence)
    assert attempt["layoutSeconds"] > 0 and attempt["uploadSeconds"] > 0
    assert attempt["firstStepSeconds"] >= attempt["laterStepSeconds"] > 0
    assert len(attempt["deviceBytesInUse"]) == len(devices)

    stats = EngineServer(engine, inst).serving_stats()
    assert {k: stats["device"][k]
            for k in ("platform", "device_kind", "device_count")} == {
        "platform": "cpu", "device_kind": devices[0].device_kind,
        "device_count": len(devices)}
    assert "components" in stats["device"]  # the HBM ledger is still there
    # off a TPU the compiled XLA program serves; `native` is the Pallas
    # kernel and `interpret` the parity tool
    assert stats["retrieval"]["kernel"] == "xla"
    assert stats["retrieval"]["mode"] == "exact"
    assert stats["pipeline"]["mode"] == "fused"


def test_retriever_kernel_modes(rng):
    from predictionio_tpu.ops.ann import AnnRetriever
    from predictionio_tpu.ops.retrieval import (DeviceRetriever,
                                                ShardedDeviceRetriever)
    from predictionio_tpu.parallel.mesh import make_mesh

    items = rng.standard_normal((300, 16)).astype(np.float32)
    assert DeviceRetriever(items).kernel == "xla"
    assert DeviceRetriever(items, interpret=True).kernel == "interpret"
    assert DeviceRetriever(items, interpret=False).kernel == "native"
    assert ShardedDeviceRetriever(
        items, make_mesh((2,), ("model",))).kernel == "xla"
    small = AnnRetriever(items)  # below min_items: exact fallback
    assert small.stats()["kernel"] == "xla"


# ---------------------------------------------------------------------------
# the native library is the one built from this source


def test_native_library_of_another_source_is_rebuilt(tmp_path):
    """The library's file name carries its source's hash. A `_build/`
    copied along from another source (any mtime) is not loaded; the
    library of this source is built beside it and the other one goes."""
    pkg = tmp_path / "predictionio_tpu" / "native"
    pkg.mkdir(parents=True)
    (tmp_path / "predictionio_tpu" / "__init__.py").write_text("")
    src = REPO / "predictionio_tpu" / "native"
    shutil.copy(src / "__init__.py", pkg / "__init__.py")
    (pkg / "pio_native.cpp").write_text(
        (src / "pio_native.cpp").read_text() + "\n// another source\n")
    (pkg / "_build").mkdir()
    from predictionio_tpu import native

    assert native.available()
    foreign = pkg / "_build" / native.lib_path().name
    shutil.copy(native.lib_path(), foreign)  # this repo's build, newer mtime
    code = ("from predictionio_tpu import native; import json; "
            "print(json.dumps([native.available(), str(native.lib_path())]))")
    p = subprocess.run([sys.executable, "-c", code], capture_output=True,
                       text=True, timeout=600, cwd=tmp_path,
                       env={**os.environ, "PYTHONPATH": str(tmp_path)})
    assert p.returncode == 0, p.stderr[-2000:]
    ok, path = json.loads(p.stdout.splitlines()[-1])
    assert ok and Path(path).is_file()
    assert Path(path).name != foreign.name
    assert not foreign.exists()
    assert _listing(pkg / "_build") == {Path(path).name}
