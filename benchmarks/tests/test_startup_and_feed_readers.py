"""The readers of the start-up record and of the pipeline's feed
counters, on hand-made evidence; and every per-layer metric of
BENCHMARK.json reads nothing, without raising, from a program that keeps
none of it."""

import pytest

from lib import spec

PHASES = [
    ["pio.process.to_device", 12.5, 1_000],
    ["pio.deploy.blob_read", 3.0, 5_000],
    ["pio.deploy.checksum", 2.0, 5_100],
    ["pio.deploy.deserialize", 20.0, 30_000],
    ["pio.deploy.blob_read", 1.5, 34_000],          # the second read
    ["pio.deploy.attach_retriever.catalog_pad", 4.0, 36_500],
    ["pio.deploy.attach_retriever.catalog_upload", 2.0, 33_000],
    ["pio.deploy.attach_retriever", 6.5, 33_000],
    ["pio.deploy.attach_pipeline", 0.5, 33_100],
    ["pio.deploy.prewarm.program", 0.4, None],
    ["pio.deploy.prewarm", 4.5, 33_200],
    ["pio.serve.id_map_inverse", 9.0, 35_000],
]


def evidence(startup=True, feed=True):
    before = {"pipeline": {"mode": "fused"}}
    after = {"pipeline": {"mode": "fused"}}
    if startup:
        before["startup"] = {"phases": PHASES, "readySeconds": 60.0}
    if feed:
        before["pipeline"].update(deviceIdleSeconds=1.0, inDeviceSeconds=10.0,
                                  clockSeconds=20.0)
        after["pipeline"].update(deviceIdleSeconds=4.0, inDeviceSeconds=250.0,
                                 clockSeconds=50.0)
    return {"stats_before": before, "stats_after": after, "harness": {},
            "convergence": None, "trace": None}


@pytest.mark.parametrize("metric,want", [
    ("deploy_blob_s", 3.0 + 2.0 + 20.0 + 1.5),
    ("deploy_catalog_s", 6.5 + 0.5),        # the parents, not their children
    ("deploy_prewarm_s", 4.5),
    ("id_map_inverse_s", 9.0),
    ("deploy_host_peak_bytes", 36_500),
    ("device_starved_share", 100.0 * 3.0 / 30.0),
    ("inflight_depth", 240.0 / 30.0),
])
def test_reads_hand_made_evidence(metric, want):
    assert spec.read_layer_metric(metric, evidence()) == pytest.approx(want)


def test_new_metrics_read_nothing_from_a_program_without_them():
    bare = evidence(startup=False, feed=False)
    for name in ("deploy_blob_s", "deploy_catalog_s", "deploy_prewarm_s",
                 "id_map_inverse_s", "deploy_host_peak_bytes",
                 "device_starved_share", "inflight_depth"):
        assert spec.read_layer_metric(name, bare) is None
    old_record = {"harness": {}, "convergence": {"layoutSeconds": 1.0}}
    for name in ("process_to_device_s", "als_init_s", "als_final_pull_s"):
        assert spec.read_layer_metric(name, old_record) is None
    new_record = {"convergence": {"processToDeviceSeconds": 12.0,
                                  "initSeconds": 4.0,
                                  "finalPullSeconds": 5.0}}
    assert spec.read_layer_metric("process_to_device_s", new_record) == 12.0
    assert spec.read_layer_metric("als_init_s", new_record) == 4.0
    assert spec.read_layer_metric("als_final_pull_s", new_record) == 5.0


def test_a_phase_named_in_no_metric_or_an_idle_window_reads_none():
    ev = evidence()
    ev["stats_before"]["startup"]["phases"] = [["pio.other", 1.0, None]]
    assert spec.read_layer_metric("deploy_prewarm_s", ev) is None
    assert spec.read_layer_metric("deploy_host_peak_bytes", ev) is None
    ev["stats_after"]["pipeline"]["clockSeconds"] = 20.0   # no time passed
    assert spec.read_layer_metric("inflight_depth", ev) is None
