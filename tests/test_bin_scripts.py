"""bin/ ops-plane scripts — the pio-start-all/pio-stop-all daemon pair
(reference bin/pio-start-all brings up ES + HBase + event server; here it
starts the event server, dashboard, and admin API with pidfiles) and the
`bin/pio` dispatcher. These were the only untested executables."""

from __future__ import annotations

import os
import subprocess
import time
from pathlib import Path

import pytest
import requests

REPO = Path(__file__).resolve().parents[1]


def _free_port() -> int:
    import socket

    with socket.socket() as s:
        s.bind(("127.0.0.1", 0))
        return s.getsockname()[1]


def test_start_all_stop_all(tmp_path):
    env = dict(
        os.environ,
        PIO_HOME=str(tmp_path),
        PIO_EVENTSERVER_PORT=str(_free_port()),
        PIO_DASHBOARD_PORT=str(_free_port()),
        PIO_ADMINSERVER_PORT=str(_free_port()),
        JAX_PLATFORMS="cpu",
    )
    out = subprocess.run([str(REPO / "bin" / "pio-start-all")],
                         capture_output=True, text=True, env=env, timeout=60)
    assert out.returncode == 0, out.stderr
    assert "eventserver started" in out.stdout
    try:
        # pidfiles written and processes alive
        for name in ("eventserver", "dashboard", "adminserver"):
            pidfile = tmp_path / "run" / f"{name}.pid"
            assert pidfile.exists(), f"{name} pidfile missing"
            os.kill(int(pidfile.read_text()), 0)  # raises if dead

        # the event server actually serves
        url = f"http://127.0.0.1:{env['PIO_EVENTSERVER_PORT']}"
        for _ in range(60):
            try:
                r = requests.get(url + "/", timeout=2)
                break
            except requests.ConnectionError:
                time.sleep(0.5)
        else:
            log = (tmp_path / "log" / "eventserver.log").read_text()
            pytest.fail(f"event server never came up; log: {log[-800:]}")
        assert r.json()["status"] == "alive"

        # idempotent restart: already-running services are left alone
        out2 = subprocess.run([str(REPO / "bin" / "pio-start-all")],
                              capture_output=True, text=True, env=env,
                              timeout=60)
        assert "already running" in out2.stdout
    finally:
        out3 = subprocess.run([str(REPO / "bin" / "pio-stop-all")],
                              capture_output=True, text=True, env=env,
                              timeout=60)
    assert out3.returncode == 0
    assert "eventserver stopped" in out3.stdout
    # pids really gone
    time.sleep(0.5)
    for name in ("eventserver", "dashboard", "adminserver"):
        assert not (tmp_path / "run" / f"{name}.pid").exists()

    # stop-all on an already-stopped home is a clean no-op
    out4 = subprocess.run([str(REPO / "bin" / "pio-stop-all")],
                          capture_output=True, text=True, env=env, timeout=60)
    assert out4.returncode == 0
    assert "not running" in out4.stdout


def test_pio_dispatcher_version(tmp_path):
    env = dict(os.environ, PIO_HOME=str(tmp_path), JAX_PLATFORMS="cpu")
    out = subprocess.run([str(REPO / "bin" / "pio"), "version"],
                         capture_output=True, text=True, env=env, timeout=60)
    assert out.returncode == 0
    from predictionio_tpu import __version__

    assert __version__ in out.stdout


def test_pio_eventserver_help_documents_journal_flags(tmp_path):
    """The durability knobs are part of the operator surface: `pio
    eventserver --help` must advertise the journal flags and every fsync
    policy choice, so the docs/operations.md runbook stays honest."""
    env = dict(os.environ, PIO_HOME=str(tmp_path), JAX_PLATFORMS="cpu")
    out = subprocess.run([str(REPO / "bin" / "pio"), "eventserver", "--help"],
                         capture_output=True, text=True, env=env, timeout=60)
    assert out.returncode == 0
    for flag in ("--journal-dir", "--journal-fsync", "--journal-max-mb",
                 "--journal-partitions"):
        assert flag in out.stdout, f"{flag} missing from eventserver --help"
    for policy in ("always", "batch", "never"):
        assert policy in out.stdout


def test_pio_eventserver_help_documents_admission_flags(tmp_path):
    """The overload-control knobs (ISSUE 6) are operator surface too:
    ingestion admission + per-key rate limiting."""
    env = dict(os.environ, PIO_HOME=str(tmp_path), JAX_PLATFORMS="cpu")
    out = subprocess.run([str(REPO / "bin" / "pio"), "eventserver", "--help"],
                         capture_output=True, text=True, env=env, timeout=60)
    assert out.returncode == 0
    for flag in ("--admission", "--rate-limit-qps", "--rate-limit-burst"):
        assert flag in out.stdout, f"{flag} missing from eventserver --help"


def test_pio_deploy_help_documents_overload_flags(tmp_path):
    """`pio deploy --help` must advertise the admission / rate-limit /
    brownout knobs the Overload-control runbook documents."""
    env = dict(os.environ, PIO_HOME=str(tmp_path), JAX_PLATFORMS="cpu")
    out = subprocess.run([str(REPO / "bin" / "pio"), "deploy", "--help"],
                         capture_output=True, text=True, env=env, timeout=60)
    assert out.returncode == 0
    for flag in ("--admission", "--admission-queue-high",
                 "--admission-wait-budget-ms", "--rate-limit-qps",
                 "--rate-limit-burst", "--brownout-topk"):
        assert flag in out.stdout, f"{flag} missing from deploy --help"


@pytest.mark.parametrize("argv, code", [
    (["deploy", "--serving-pipeline", "legacy"], 2),
    (["deploy", "--no-instrumentation"], 2),
    (["bench", "serve"], 2),
    (["bench", "backup", "--help"], 0),
], ids=["deploy-serving-pipeline", "deploy-no-instrumentation",
        "bench-serve", "bench-backup-help"])
def test_pio_has_one_serving_path_and_one_bench(argv, code):
    """`pio deploy` has one serving path and no switch for its
    waterfall, and `pio bench` measures backups only (serving is
    measured by benchmarks/run.py): argparse refuses what went."""
    from predictionio_tpu.tools.cli import build_parser

    with pytest.raises(SystemExit) as exc:
        build_parser().parse_args(argv)
    assert exc.value.code == code


def test_pio_deploy_help_documents_retrieval_flags(tmp_path):
    """`pio deploy --help`: the ANN mode override and the auto mesh."""
    env = dict(os.environ, PIO_HOME=str(tmp_path), JAX_PLATFORMS="cpu")
    out = subprocess.run([str(REPO / "bin" / "pio"), "deploy", "--help"],
                         capture_output=True, text=True, env=env, timeout=60)
    assert out.returncode == 0
    assert "--retrieval-mode" in out.stdout
    assert "--retriever-mesh" in out.stdout
    assert "auto" in out.stdout


def test_pio_train_help_documents_supervision_flags(tmp_path):
    """The preemption-tolerance knobs are operator surface: `pio train
    --help` must advertise the supervised-retry / budget flags the
    Training-robustness runbook documents."""
    env = dict(os.environ, PIO_HOME=str(tmp_path), JAX_PLATFORMS="cpu")
    out = subprocess.run([str(REPO / "bin" / "pio"), "train", "--help"],
                         capture_output=True, text=True, env=env, timeout=60)
    assert out.returncode == 0
    for flag in ("--max-retries", "--retry-backoff-s", "--train-budget-s"):
        assert flag in out.stdout, f"{flag} missing from train --help"


def test_pio_train_help_documents_distributed_flags(tmp_path):
    """Elastic multi-host launch surface: `pio train --help` must
    advertise the distributed-topology flags the Elastic multi-host
    training runbook documents."""
    env = dict(os.environ, PIO_HOME=str(tmp_path), JAX_PLATFORMS="cpu")
    out = subprocess.run([str(REPO / "bin" / "pio"), "train", "--help"],
                         capture_output=True, text=True, env=env, timeout=60)
    assert out.returncode == 0
    for flag in ("--coordinator", "--num-processes", "--process-id"):
        assert flag in out.stdout, f"{flag} missing from train --help"


def test_pio_tune_help_documents_sweep_flags(tmp_path):
    """ISSUE 15: `pio tune --help` must advertise the sweep surface —
    per-trial retries, the winner's training knobs, and the eval-gated
    --deploy the Hyperparameter tuning runbook documents."""
    env = dict(os.environ, PIO_HOME=str(tmp_path), JAX_PLATFORMS="cpu")
    out = subprocess.run([str(REPO / "bin" / "pio"), "tune", "--help"],
                         capture_output=True, text=True, env=env, timeout=60)
    assert out.returncode == 0
    for flag in ("--max-retries", "--train-max-retries",
                 "--train-budget-s", "--eval-gate", "--deploy"):
        assert flag in out.stdout, f"{flag} missing from tune --help"


def test_pio_admin_reap_help_documents_flags(tmp_path):
    env = dict(os.environ, PIO_HOME=str(tmp_path), JAX_PLATFORMS="cpu")
    out = subprocess.run([str(REPO / "bin" / "pio"), "admin", "reap",
                          "--help"],
                         capture_output=True, text=True, env=env, timeout=60)
    assert out.returncode == 0
    for flag in ("--stale-after-s", "--dry-run"):
        assert flag in out.stdout, f"{flag} missing from admin reap --help"


def test_pio_deploy_help_documents_variant_flags(tmp_path):
    """ISSUE 14: `pio deploy --help` must advertise the co-hosting
    flags — join an existing server as a variant at a traffic weight."""
    env = dict(os.environ, PIO_HOME=str(tmp_path), JAX_PLATFORMS="cpu")
    out = subprocess.run([str(REPO / "bin" / "pio"), "deploy", "--help"],
                         capture_output=True, text=True, env=env, timeout=60)
    assert out.returncode == 0
    for flag in ("--variant-of", "--weight", "--variant-id"):
        assert flag in out.stdout, f"{flag} missing from deploy --help"


def test_pio_variant_help_documents_subcommands(tmp_path):
    """ISSUE 14: the variant lifecycle is operator surface — `pio
    variant --help` must list every lifecycle subcommand the
    Multi-variant serving runbook documents."""
    env = dict(os.environ, PIO_HOME=str(tmp_path), JAX_PLATFORMS="cpu")
    out = subprocess.run([str(REPO / "bin" / "pio"), "variant", "--help"],
                         capture_output=True, text=True, env=env, timeout=60)
    assert out.returncode == 0
    for sub in ("list", "weight", "promote", "retire"):
        assert sub in out.stdout, f"{sub} missing from variant --help"


def test_pio_stream_help_documents_variant_flag(tmp_path):
    """ISSUE 14 satellite: the streaming updater stamps its target
    variant; the flag must be on the CLI surface."""
    env = dict(os.environ, PIO_HOME=str(tmp_path), JAX_PLATFORMS="cpu")
    out = subprocess.run([str(REPO / "bin" / "pio"), "stream", "--help"],
                         capture_output=True, text=True, env=env, timeout=60)
    assert out.returncode == 0
    assert "--variant" in out.stdout


def test_pio_stream_help_documents_updater_flags(tmp_path):
    """ISSUE 10: the streaming updater's operator surface — `pio stream
    --help` must advertise the journal-tailing, gating and publish
    knobs the docs/operations.md runbook names."""
    env = dict(os.environ, PIO_HOME=str(tmp_path), JAX_PLATFORMS="cpu")
    out = subprocess.run([str(REPO / "bin" / "pio"), "stream", "--help"],
                         capture_output=True, text=True, env=env, timeout=60)
    assert out.returncode == 0
    for flag in ("--journal-dir", "--engine-url", "--batch-window-ms",
                 "--eval-gate", "--eval-k", "--journal-partitions",
                 "--follow-name", "--max-records", "--fold-in-solver",
                 "--breaker-threshold", "--breaker-reset-s"):
        assert flag in out.stdout, f"{flag} missing from stream --help"


def test_pio_fleet_help_documents_subcommands(tmp_path):
    """ISSUE 17: the serving fleet is operator surface — `pio fleet
    --help` must list the lifecycle subcommands the Serving fleet
    runbook documents."""
    env = dict(os.environ, PIO_HOME=str(tmp_path), JAX_PLATFORMS="cpu")
    out = subprocess.run([str(REPO / "bin" / "pio"), "fleet", "--help"],
                         capture_output=True, text=True, env=env, timeout=60)
    assert out.returncode == 0
    for sub in ("start", "status", "drain", "restart"):
        assert sub in out.stdout, f"{sub} missing from fleet --help"


def test_pio_fleet_start_help_documents_router_flags(tmp_path):
    """ISSUE 17: every routing-tier policy knob — replica topology,
    probe/breaker cadence, hedging, delta journal, SLO drain and the
    reload canary gate — must be on `pio fleet start --help`."""
    env = dict(os.environ, PIO_HOME=str(tmp_path), JAX_PLATFORMS="cpu")
    out = subprocess.run(
        [str(REPO / "bin" / "pio"), "fleet", "start", "--help"],
        capture_output=True, text=True, env=env, timeout=60)
    assert out.returncode == 0
    for flag in ("--replicas", "--base-port", "--replica-urls",
                 "--probe-interval-s", "--breaker-reset-s", "--deadline-ms",
                 "--max-hedges", "--spillover-inflight", "--journal-max",
                 "--slo-drain-burn", "--canary-sample",
                 "--canary-max-mismatch",
                 # ISSUE 18: the self-healing knobs
                 "--supervise", "--max-respawns", "--crash-window-s",
                 "--quarantine-s", "--state-dir"):
        assert flag in out.stdout, f"{flag} missing from fleet start --help"


def test_pio_fleet_restart_help_documents_wave_flags(tmp_path):
    """ISSUE 18: the rolling, canary-gated restart wave is operator
    surface — its knobs must be on `pio fleet restart --help`."""
    env = dict(os.environ, PIO_HOME=str(tmp_path), JAX_PLATFORMS="cpu")
    out = subprocess.run(
        [str(REPO / "bin" / "pio"), "fleet", "restart", "--help"],
        capture_output=True, text=True, env=env, timeout=60)
    assert out.returncode == 0
    for flag in ("--router-url", "--canary-sample", "--timeout-s"):
        assert flag in out.stdout, f"{flag} missing from fleet restart --help"


def test_pio_fleet_status_and_drain_help(tmp_path):
    env = dict(os.environ, PIO_HOME=str(tmp_path), JAX_PLATFORMS="cpu")
    out = subprocess.run(
        [str(REPO / "bin" / "pio"), "fleet", "status", "--help"],
        capture_output=True, text=True, env=env, timeout=60)
    assert out.returncode == 0 and "--router-url" in out.stdout
    out = subprocess.run(
        [str(REPO / "bin" / "pio"), "fleet", "drain", "--help"],
        capture_output=True, text=True, env=env, timeout=60)
    assert out.returncode == 0
    for flag in ("--router-url", "--replica", "--stop"):
        assert flag in out.stdout, f"{flag} missing from fleet drain --help"


def test_pio_deploy_help_documents_prewarm_async(tmp_path):
    """ISSUE 17 satellite: fleet replicas bind first and prewarm in the
    background (live-but-not-ready); the flag must be on the surface."""
    env = dict(os.environ, PIO_HOME=str(tmp_path), JAX_PLATFORMS="cpu")
    out = subprocess.run([str(REPO / "bin" / "pio"), "deploy", "--help"],
                         capture_output=True, text=True, env=env, timeout=60)
    assert out.returncode == 0
    assert "--prewarm-async" in out.stdout


def test_pio_backup_restore_help_documents_dr_flags(tmp_path):
    """ISSUE 19: the disaster-recovery surface — `pio backup --help` and
    `pio restore --help` must advertise every knob the Disaster recovery
    runbook documents."""
    env = dict(os.environ, PIO_HOME=str(tmp_path), JAX_PLATFORMS="cpu")
    out = subprocess.run([str(REPO / "bin" / "pio"), "backup", "--help"],
                         capture_output=True, text=True, env=env, timeout=60)
    assert out.returncode == 0
    for flag in ("--backup-dir", "--keep", "--full"):
        assert flag in out.stdout, f"{flag} missing from backup --help"
    out = subprocess.run([str(REPO / "bin" / "pio"), "restore", "--help"],
                         capture_output=True, text=True, env=env, timeout=60)
    assert out.returncode == 0
    for flag in ("--backup-dir", "--backup-id", "--force", "--until",
                 "--target"):
        assert flag in out.stdout, f"{flag} missing from restore --help"


def test_pio_admin_fsck_and_gc_help(tmp_path):
    """ISSUE 19: `pio admin fsck --help` / `pio admin gc --help`."""
    env = dict(os.environ, PIO_HOME=str(tmp_path), JAX_PLATFORMS="cpu")
    out = subprocess.run([str(REPO / "bin" / "pio"), "admin", "fsck",
                          "--help"],
                         capture_output=True, text=True, env=env, timeout=60)
    assert out.returncode == 0
    assert "--repair" in out.stdout
    out = subprocess.run([str(REPO / "bin" / "pio"), "admin", "gc",
                          "--help"],
                         capture_output=True, text=True, env=env, timeout=60)
    assert out.returncode == 0
    for flag in ("--blobs", "--dry-run"):
        assert flag in out.stdout, f"{flag} missing from admin gc --help"


def test_pio_fleet_start_help_documents_observability_flags(tmp_path):
    """ISSUE 20: the fleet observability plane's knobs — collection
    on/off, staleness window, outlier band, incident-bundle directory —
    must be on `pio fleet start --help`."""
    env = dict(os.environ, PIO_HOME=str(tmp_path), JAX_PLATFORMS="cpu")
    out = subprocess.run(
        [str(REPO / "bin" / "pio"), "fleet", "start", "--help"],
        capture_output=True, text=True, env=env, timeout=60)
    assert out.returncode == 0
    for flag in ("--no-collect-metrics", "--metrics-stale-after-s",
                 "--outlier-band", "--incident-dir"):
        assert flag in out.stdout, f"{flag} missing from fleet start --help"


def test_pio_fleet_status_help_mentions_outlier_columns(tmp_path):
    """ISSUE 20: `pio fleet status` grew windowed p99/qps columns and
    outlier flags; the help text must say so."""
    env = dict(os.environ, PIO_HOME=str(tmp_path), JAX_PLATFORMS="cpu")
    out = subprocess.run(
        [str(REPO / "bin" / "pio"), "fleet", "status", "--help"],
        capture_output=True, text=True, env=env, timeout=60)
    assert out.returncode == 0
    assert "outlier" in out.stdout.lower()
    assert "p99" in out.stdout


def test_pio_trace_help_documents_join_sources(tmp_path):
    """ISSUE 20: `pio trace <rid>` joins router hops, replica flight
    records and ingest WAL entries — every source flag on the help."""
    env = dict(os.environ, PIO_HOME=str(tmp_path), JAX_PLATFORMS="cpu")
    out = subprocess.run([str(REPO / "bin" / "pio"), "trace", "--help"],
                         capture_output=True, text=True, env=env, timeout=60)
    assert out.returncode == 0
    for flag in ("request_id", "--router-url", "--url", "--wal-dir"):
        assert flag in out.stdout, f"{flag} missing from trace --help"


def test_pio_top_help_documents_fleet_flag(tmp_path):
    env = dict(os.environ, PIO_HOME=str(tmp_path), JAX_PLATFORMS="cpu")
    out = subprocess.run([str(REPO / "bin" / "pio"), "top", "--help"],
                         capture_output=True, text=True, env=env, timeout=60)
    assert out.returncode == 0
    assert "--fleet" in out.stdout


def test_pio_admin_metrics_help_documents_url_flag(tmp_path):
    """ISSUE 20 bugfix pin: `pio admin metrics` can be pointed at a live
    server; against a fleet router it prints the MERGED snapshot."""
    env = dict(os.environ, PIO_HOME=str(tmp_path), JAX_PLATFORMS="cpu")
    out = subprocess.run(
        [str(REPO / "bin" / "pio"), "admin", "metrics", "--help"],
        capture_output=True, text=True, env=env, timeout=60)
    assert out.returncode == 0
    assert "--url" in out.stdout and "--json" in out.stdout
    assert "fleet" in out.stdout.lower()


def test_pio_restore_refuses_nonempty_home_exit_2(tmp_path):
    """ISSUE 19 bugfix pin: `pio restore` onto a non-empty $PIO_HOME
    without --force must exit 2 (distinct from generic failure 1) and
    leave the home untouched — the refusal precedes backup selection, so
    even a bogus --backup-dir still reports the refusal."""
    home = tmp_path / "home"
    home.mkdir()
    (home / "precious.txt").write_text("keep me")
    env = dict(os.environ, PIO_HOME=str(home), JAX_PLATFORMS="cpu")
    out = subprocess.run(
        [str(REPO / "bin" / "pio"), "restore",
         "--backup-dir", str(tmp_path / "nope")],
        capture_output=True, text=True, env=env, timeout=60)
    assert out.returncode == 2, out.stderr
    assert "not empty" in out.stderr
    assert (home / "precious.txt").read_text() == "keep me"
