"""The sequence-serving cell (`ouro-2.6b-seqrec.session-closed`): its
files are found by name, its counts match a hand count, what a seed may
change holds, its readers read and return nothing where the program
keeps no such counter, and the comparison that decides `correct` has
been shown to fail: on the control (the reference with float8 matrices,
in the program's place) and on broken paths (3 passes for 4; a layer's
weights not shared between the passes)."""

import json
import os
import subprocess
import sys

import numpy as np
import pytest

from lib import loadgen, seq_counts, seq_draw, seq_reference as ref
from lib import seq_serve, spec

CELL = "ouro-2.6b-seqrec.session-closed"


def test_cell_files_are_found_by_name():
    cell = spec.load_cell(CELL)
    assert cell["traffic"]["kind"] == "seq-closed-loop"
    assert cell["traffic"]["callers"] == 64 and cell["chips"] == 1
    assert cell["config"]["reduced"] == []
    model, sizes = seq_serve.sizes_of(cell, rehearse=False)
    assert (model["num_hidden_layers"], model["total_ut_steps"]) == (48, 4)
    assert sizes == {"users": 1_000_000, "items": 49_151, "max_len": 512}
    assert cell["config"]["vocab_size"] == sizes["items"] + 1
    assert hasattr(spec.kind_module("seq-closed-loop"), "run")
    assert [m["name"] for m in spec.metrics_of(cell, "end_to_end")] == [
        "served_qps", "setup_s"]
    per_layer = [m["name"] for m in spec.metrics_of(cell, "per_layer")]
    assert per_layer == [
        "seq_step_ms", "seq_step_mfu", "loop_share", "seq_token_fill",
        "head_topk_ms", "head_topk_roofline", "batch_rows.seq",
        "device_starved_share.seq", "seed_model_s.seq",
        "deploy_ready_s.seq", "deploy_prewarm_s.seq", "deploy_blob_s.seq",
        "deploy_catalog_s.seq", "first_query_s.seq",
        "deploy_host_peak_bytes.seq", "cut_held_share.seq",
        "topk_merge_share.seq", "topk_rounds_per_merge.seq",
        "seq_tokens_per_s"]
    for name in per_layer:   # every one has its file and its reader
        layer = spec.load_json(spec.BENCH / "layers" / f"{name}.json")
        assert (spec.BENCH / "layers" / "readers"
                / f"{layer['reader']}.py").is_file()


def test_configuration_holds_every_published_key():
    """The catalog row's `config`, key by key (copied here from the
    guide's architectures.jsonl, line 9: the test has no network and no
    guide either)."""
    published = {
        "head_dim": 128, "hidden_act": "silu", "hidden_size": 2048,
        "intermediate_size": 5632, "max_position_embeddings": 65536,
        "max_window_layers": 48, "model_type": "ouro",
        "num_attention_heads": 16, "num_hidden_layers": 48,
        "num_key_value_heads": 16, "rms_norm_eps": 1e-06,
        "rope_scaling": None, "rope_theta": 1000000, "sliding_window": None,
        "tie_word_embeddings": False, "total_ut_steps": 4,
        "early_exit_threshold": 1, "use_sliding_window": False,
        "vocab_size": 49152}
    cfg = spec.load_cell(CELL)["config"]
    for key, value in published.items():
        assert cfg[key] == value, key
    assert cfg["layer_types"] == ["full_attention"] * 48


def test_looped_lm_counts_against_a_hand_count():
    model = {k: spec.load_cell(CELL)["config"][k]
             for k in seq_serve.MODEL_KEYS}
    assert seq_counts.layer_params(model) == 4 * 2048 ** 2 + 3 * 2048 * 5632
    assert seq_counts.layer_params(model) == 51_380_224
    got = seq_counts.looped_lm_counts(model, tokens=1000, attention_pairs=0,
                                      rows=0, n_items=49_151)
    # 2 x 51.38 M x 192 applications = 19.73 GFLOP a token, + the gate
    assert got["dense"] == 2 * 51_380_224 * 192 * 1000
    assert got["flops"] == got["dense"] + 2 * 2048 * 4 * 1000
    # one history of 88 events: 88 x 89 / 2 pairs, q.k and p.v
    pairs = 88 * 89 // 2
    got = seq_counts.looped_lm_counts(model, 88, pairs, 1, 49_151)
    assert got["attention"] == 4 * 2048 * pairs * 192
    assert got["head"] == 2 * 2048 * 49_151
    assert 0.003 < got["attention"] / got["dense"] < 0.005
    # the layers' weights once a pass: 4.93 GB x 4, and the 0.4 GB head
    assert got["bytes"] == 2 * 51_380_224 * 192 + 4 * 2048 * 49_151


def test_histories_are_the_mix_s_draw_and_the_plan_offers_every_seed_the_same_work():
    """The plan is laid out as `loadgen.make_plan` lays a closed loop's
    out, in the mix's own order: whatever the seed, request i asks for a
    history of the same length (a row costs its length here), and another
    user holds it. Nothing ties a length to the plan: the lengths are the
    issue's log-normal (median 64, sigma 0.8, 8-512) and the histories
    are left-padded with their stated lengths."""
    traffic = spec.load_cell(CELL)["traffic"]
    users, seconds = 200_000, 30.0
    sent, plans = {}, {}
    for seed in (5, 3_000_000_019):
        plan = plans[seed] = seq_draw.closed_plan(traffic, seed, users,
                                                  seconds)
        like = loadgen.make_plan(traffic, seed, users, seconds,
                                 rate_qps=None)
        assert len(plan.users) == len(like.users) and plan.due is None
        assert (plan.warmup_s, plan.seconds, plan.num) == (
            like.warmup_s, like.seconds, like.num)
        assert json.loads(plan.body(0)) == {"user": plan.users[0],
                                            "num": 10}
        asked, rows = seq_draw.rank_rows(traffic, seed, users, seconds)
        known = plan.rows >= 0
        assert set(plan.rows[known].tolist()) <= set(rows.tolist())
        assert 0.015 < (~known).mean() < 0.025
        assert all(u == f"u{r}" if r >= 0 else u.startswith("nobody")
                   for u, r in zip(plan.users[:500], plan.rows[:500]))
        lengths = seq_draw.history_lengths(traffic, seed, users, seconds)
        assert lengths.min() == 8 and lengths.max() == 512
        assert 62 <= np.median(lengths) <= 66 and 85 < lengths.mean() < 91
        assert 0.75 < np.log(lengths[(lengths > 8) & (lengths < 512)]
                             ).std() < 0.8     # sigma 0.8 less the clip
        sent[seed] = np.where(known, lengths[plan.rows], 0)
    assert (sent[5] == sent[3_000_000_019]).all()
    assert ((plans[5].rows >= 0) == (plans[3_000_000_019].rows >= 0)).all()
    assert (plans[5].rows != plans[3_000_000_019].rows)[
        plans[5].rows >= 0].mean() > 0.99
    # the head a window answers is a fair draw of the mix, not a levelled
    # one: its lengths deviate as the mix's do
    head = sent[5][:2500][sent[5][:2500] > 0]
    assert 80 < head.mean() < 91 and 0.8 < head.std() / head.mean() < 1.0
    hist = seq_draw.histories(traffic, 5, 20_000, 211, 64, 4.0)
    lengths = seq_draw.history_lengths(traffic, 5, 20_000, 4.0)
    assert ((hist > 0).sum(axis=1) == np.minimum(lengths, 64)).all()
    assert (hist[:, -1] > 0).all() and hist.max() <= 211
    assert (np.diff((hist > 0).astype(int), axis=1) >= 0).all()


def test_seq_readers_read_and_fall_silent_without_counters():
    cell = spec.load_cell(CELL)
    model = {k: cell["config"][k] for k in seq_serve.MODEL_KEYS}
    seq0 = {"steps": 0, "rows": 0, "tokensReal": 0, "tokensComputed": 0,
            "attentionPairs": 0}
    seq1 = {"steps": 100, "rows": 1100, "tokensReal": 94_000,
            "tokensComputed": 102_400, "attentionPairs": 6_000_000}
    evidence = {
        "device_kind": "TPU v5 lite",
        "harness": {"first_query_s": 0.11},
        "stats_before": {
            "sequence": seq0, "pipeline": {"clockSeconds": 100.0},
            "batching": {"batches": 10, "cutsHeld": 4},
            "retrieval": {"tilesScanned": 0, "tilesMerged": 0,
                          "mergeRounds": 0},
            "startup": {"phases": [
                ["pio.deploy.blob_read", 6.5, 9e9],
                ["pio.deploy.checksum", 4.0, 9e9],
                ["pio.deploy.deserialize", 8.0, 21e9],
                ["pio.deploy.attach_encoder", 3.0, 15e9],
                ["pio.deploy.attach_pipeline", 3.5, 15e9],
                ["pio.deploy.attach_retriever", 1.5, 14e9]]}},
        "stats_after": {
            "sequence": seq1, "pipeline": {"clockSeconds": 130.0},
            "batching": {"batches": 110, "cutsHeld": 103},
            "retrieval": {"tilesScanned": 2400, "tilesMerged": 600,
                          "mergeRounds": 9000}},
        "shapes": {"n_items": 49_151, "dim": 2048, "k": 10, "model": model},
        "trace": {"busy_s": 4.0, "window_s": 5.0, "ops": [
            ["%while.2 = (s32[], f32[1,1024,2048]) while(%tuple)", 20, 3.9],
            ["%pio.seq.head_topk.1 = (f32[16,528]) custom-call(%fusion, %items)",
             20, 0.08],
            ["%flash_attention.3 = bf16[1,16,1024,128] custom-call(%a)",
             3840, 0.3]]},
    }
    assert spec.read_layer_metric("seq_step_ms", evidence) == 200.0
    assert spec.read_layer_metric("loop_share", evidence) == 97.5
    assert spec.read_layer_metric("head_topk_ms", evidence) == 4.0
    assert spec.read_layer_metric("seq_token_fill", evidence) == (
        pytest.approx(100 * 94_000 / 102_400))
    need = seq_counts.looped_lm_counts(model, 940, 60_000, 11, 49_151)
    assert spec.read_layer_metric("seq_step_mfu", evidence) == pytest.approx(
        100 * need["flops"] * 20 / (197e12 * 4.0))
    assert 40 < spec.read_layer_metric("seq_step_mfu", evidence) < 60
    # the server's own record and the counters the ALS cells read, here
    assert spec.read_layer_metric("seq_tokens_per_s", evidence) == (
        pytest.approx(94_000 / 30.0))
    assert spec.read_layer_metric("deploy_blob_s.seq", evidence) == 18.5
    assert spec.read_layer_metric("deploy_catalog_s.seq", evidence) == 5.0
    assert spec.read_layer_metric("first_query_s.seq", evidence) == 0.11
    assert spec.read_layer_metric("deploy_host_peak_bytes.seq",
                                  evidence) == 21e9
    assert spec.read_layer_metric("cut_held_share.seq", evidence) == 99.0
    assert spec.read_layer_metric("topk_merge_share.seq", evidence) == 25.0
    assert spec.read_layer_metric("topk_rounds_per_merge.seq",
                                  evidence) == 15.0
    # a program from before this cell: no `sequence` block, no loop
    bare = {**evidence, "stats_before": {}, "stats_after": {},
            "trace": {"busy_s": 4.0, "window_s": 5.0, "ops": [
                ["%fusion.1 = f32[8]", 5, 1.0]]}}
    for name in ("seq_step_ms", "seq_step_mfu", "loop_share",
                 "seq_token_fill", "head_topk_ms", "batch_rows.seq",
                 "seq_tokens_per_s", "cut_held_share.seq",
                 "topk_merge_share.seq", "deploy_blob_s.seq"):
        assert spec.read_layer_metric(name, bare) is None


# -- the comparison that decides `correct` ---------------------------------

# 6 layers x 4 passes: the depth at which a rounding is carried far enough
# for the float8 control to stand three times over the limit (at 3 layers
# it reads 0.16; on the chip, at 48, 0.77-0.79)
SMALL = {"hidden_size": 64, "intermediate_size": 96, "num_hidden_layers": 6,
         "num_attention_heads": 2, "head_dim": 32, "total_ut_steps": 4,
         "early_exit_threshold": 1, "rms_norm_eps": 1e-6,
         "rope_theta": 1_000_000}
N_ITEMS, NUM = 211, 10


def answered_by(variant: str):
    """The check's verdict over 8 histories answered by the reference
    itself: as it is, with float8 matrices, 3 passes for 4, or other
    weights in the later passes."""
    import jax
    import jax.numpy as jnp

    seed = 17
    f32 = jnp.float32
    layers = [{k: jnp.asarray(v, f32) for k, v in
               seq_draw.layer_weights(seed, SMALL, layer).items()}
              for layer in range(SMALL["num_hidden_layers"])]
    other = [{k: jnp.asarray(v, f32) for k, v in
              seq_draw.layer_weights(seed + 1, SMALL, layer).items()}
             for layer in range(SMALL["num_hidden_layers"])]
    top = {k: jnp.asarray(v) for k, v in
           seq_draw.top_weights(seed, SMALL).items()}
    embed = np.asarray(seq_draw.table(seed, seq_draw.EMBED, N_ITEMS + 1, 64),
                       np.float32)
    head = np.asarray(seq_draw.table(seed, seq_draw.HEAD, N_ITEMS + 1, 64),
                      np.float32)[1:]

    def f8(w):
        return {k: (jax.lax.reduce_precision(v, exponent_bits=5,
                                             mantissa_bits=2)
                    if k in ref.MATRICES else v) for k, v in w.items()}

    layer_of = {
        "sound": lambda t, l: layers[l],
        "float8": lambda t, l: f8(layers[l]),
        "three_passes": lambda t, l: layers[l],
        "unshared": lambda t, l: (layers if t == 0 else other)[l],
    }[variant]
    rng = np.random.default_rng(3)
    worst = {"score_err": 0.0, "rank_slack": 0.0, "short": 0,
             "exit_steps": []}
    for _ in range(8):
        hist = rng.integers(1, N_ITEMS + 1, int(rng.integers(4, 40)))
        seen = np.unique(hist) - 1
        sound, exit_step, _h, _p = ref.forward(
            embed[hist], lambda t, l: layers[l], top, SMALL)
        logits = np.asarray(ref.scores(sound[-1], head))
        h, step, _h, _p = ref.forward(
            embed[hist], layer_of, top, SMALL,
            passes=3 if variant == "three_passes" else None)
        low = np.asarray(ref.scores(h[-1], head))
        masked = low.copy()
        masked[seen] = -np.inf
        best = np.argsort(-masked, kind="stable")[:NUM]
        got = ref.compare_answer([(int(i), float(low[i])) for i in best],
                                 logits, seen, NUM)
        for k in ("score_err", "rank_slack"):
            worst[k] = max(worst[k], got[k])
        worst["short"] += got["short"]
        worst["exit_steps"].append(int(exit_step[-1]))
    passes = 3 if variant == "three_passes" else 4
    numbers = seq_serve.verdict_of(worst, 4, steps=10,
                                   loop_passes=passes * 10)
    return all(v <= lim for _n, v, lim in numbers), dict(
        (n, v) for n, v, _lim in numbers)


def test_the_reference_passes_itself():
    ok, numbers = answered_by("sound")
    assert ok and numbers["score_err"] < 1e-6


@pytest.mark.parametrize("variant", ["float8", "three_passes", "unshared"])
def test_control_and_broken_paths_come_out_not_correct(variant):
    ok, numbers = answered_by(variant)
    assert not ok
    assert numbers["score_err"] > 2 * seq_serve.SCORE_ERR_LIMIT
    if variant == "three_passes":   # the counters alone give it away too
        assert numbers["exit_step_mismatch"] > 0


def rehearse(broken):
    env = dict(os.environ, JAX_PLATFORMS="cpu")
    env.pop("PIO_BENCH_BREAK", None)
    if broken:
        env["PIO_BENCH_BREAK"] = broken
    proc = subprocess.run(
        [sys.executable, str(spec.BENCH / "run.py"), "--rehearse",
         "--workload", CELL, "--seed", "3000000077", "--seconds", "3",
         "--trace", "0"], env=env, capture_output=True, text=True,
        timeout=300)
    assert proc.returncode == 0, proc.stdout + proc.stderr
    assert proc.stdout.splitlines()[-1].startswith("REHEARSAL")
    out = json.loads((spec.BENCH / "out"
                      / f"{CELL}.seed3000000077.trace0.json").read_text())
    return out["result"]


def test_rehearsal_is_correct_and_a_pass_short_is_not():
    """The whole cell at toy sizes on the host: seeded, deployed, driven
    by 64 callers, stopped, checked. With the served model one pass
    short, `correct` is false and the answers count as failed."""
    sound = rehearse(None)
    assert sound["correct"] is True and sound["failed"] == 0
    assert sound["attempted"] > 100
    broken = rehearse("passes")
    assert broken["correct"] is False and broken["failed"] > 0
