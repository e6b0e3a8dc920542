"""The `granite-4.0-h-micro-seqrec.mixed-closed` cell's own pieces at toy
sizes on the host: a rehearsal of the whole cell; the limits of `correct`
refuse the control and each broken path; the counts against a
hand-reckoned small case; the draw's lengths against the mix's stated
shares; the sample holds long and short histories; the readers read what
the program counts and return None where it keeps no such counter (the
parent commit); the configuration against the catalog's row."""

import json
import os
import subprocess
import sys
from pathlib import Path

import numpy as np
import pytest

from lib import (hybrid_ssm_counts as counts, hybrid_ssm_draw as draw,
                 hybrid_ssm_reference as ref, hybrid_ssm_serve as cell_lib,
                 seq_draw, spec)
from lib.seq_reference import compare_answer

CELL = "granite-4.0-h-micro-seqrec.mixed-closed"
CFG = spec.load_json(spec.BENCH / "configs"
                     / "granite-4.0-h-micro-seqrec.json")
TOY = CFG["rehearsal"]["model"]
N_ITEMS = 211


def toy_params(seed, cfg=TOY, scale=6.0):
    """The program's public tree at toy widths from the cell's own
    draws, as float32."""
    stacks = draw.stacked_layers(seed, cfg)
    tree = {group: {k: np.asarray(v, np.float32)
                    * (scale if k in draw.MATRICES else 1.0)
                    for k, v in stack.items()}
            for group, stack in stacks.items()}
    tree["embed"] = np.asarray(seq_draw.table(
        seed, seq_draw.EMBED, N_ITEMS + 1, cfg["hidden_size"]), np.float32)
    tree["norm_f"] = np.ones(cfg["hidden_size"], np.float32)
    return tree


def verdict(served_logits, sound_logits, num=10):
    """The cell's limits over 'answers' made from `served_logits`."""
    rows = []
    for got, want in zip(served_logits, sound_logits):
        best = np.argsort(-got[1:], kind="stable")[:num]
        rows.append(compare_answer(
            [(int(i), float(got[1:][i])) for i in best], want[1:],
            np.zeros(0, np.int64), num))
    errs = [r["score_err"] for r in rows]
    numbers = [(float(np.median(errs)), cell_lib.SCORE_ERR_MEDIAN_LIMIT),
               (max(errs), cell_lib.SCORE_ERR_LIMIT),
               (max(r["rank_slack"] for r in rows),
                cell_lib.RANK_SLACK_LIMIT)]
    return all(v <= lim for v, lim in numbers), numbers


@pytest.fixture(scope="module")
def sound():
    params = toy_params(11)
    rng = np.random.default_rng(11)
    hists = [rng.integers(1, N_ITEMS + 1, n) for n in (40, 90, 130, 7)]
    logits = [ref.next_item_scores(params, TOY, h) for h in hists]
    return params, hists, logits


def test_the_sound_reference_passes_its_own_limits(sound):
    _params, _hists, logits = sound
    ok, numbers = verdict(logits, logits)
    assert ok and all(v == 0 for v, _lim in numbers)


def test_the_quadratic_form_the_check_takes_passes_too(sound):
    params, hists, logits = sound
    other = [ref.next_item_scores(params, TOY, h, form="quadratic")
             for h in hists]
    ok, numbers = verdict(other, logits)
    assert ok and numbers[1][0] < 1e-4


@pytest.mark.parametrize("variant", ["sqrt_scale", "norm_before_gate"])
def test_a_broken_path_is_refused(sound, variant):
    """Attention at head_dim^-0.5 for the published multiplier; the
    mixer's norm before its gate: each is refused by one of the cell's
    limits (the attention's matrices sharpened, as trained ones are, so
    that a scale shows at four toy layers)."""
    params, hists, _logits = sound
    sharp = {**params, "attention": {
        k: v * (4.0 if k == "wq" else 1.0)
        for k, v in params["attention"].items()}}
    logits = [ref.next_item_scores(sharp, TOY, h) for h in hists]
    broken = [ref.next_item_scores(sharp, TOY, h, variant=variant)
              for h in hists]
    ok, numbers = verdict(broken, logits)
    assert not ok, numbers


def test_a_state_that_crosses_a_boundary_is_refused(sound):
    """A history answered with its neighbour's events still in the
    scan's state and the convolution's taps (the two packed as one):
    what a missing reset would serve."""
    params, hists, logits = sound
    leaked = [ref.next_item_scores(
        params, TOY, np.concatenate([hists[(j + 1) % len(hists)], h]))
        for j, h in enumerate(hists)]
    ok, numbers = verdict(leaked, logits)
    assert not ok, numbers


def test_the_control_is_refused(sound):
    """The reference with its matrices rounded to float8 (e5m2), the
    nearest precision below the bfloat16 the configuration states."""
    import jax
    import jax.numpy as jnp

    params, hists, logits = sound
    low = {group: ({k: (np.asarray(jax.lax.reduce_precision(
        jnp.asarray(v), exponent_bits=5, mantissa_bits=2))
        if k in draw.MATRICES else v) for k, v in stack.items()}
        if isinstance(stack, dict) else stack)
        for group, stack in params.items()}
    broken = [ref.next_item_scores(low, TOY, h) for h in hists]
    ok, numbers = verdict(broken, logits)
    assert not ok, numbers


def test_the_counts_of_a_hand_reckoned_case():
    """Two steps at the toy chunk of 32 over 3 Mamba layers and one
    attention layer: [40, 5, 30] packs 75 tokens into 3 chunks with
    starts at 40 and 45, both inside a chunk; [32, 32] packs 2 chunks
    with its second start ON a chunk's first token."""
    need = ref.expected_counts([[40, 5, 30], [32, 32]], TOY)
    assert need == {"ssmChunks": (3 + 2) * 3, "ssmResetsInChunk": 2 * 3,
                    "pairsCausal": 820 + 15 + 465 + 2 * 528}
    model = dict(TOY)
    gained = {"steps": 2, "rows": 5, "tokensReal": 139,
              "attentionPairs": need["pairsCausal"], **need}
    assert [v for _n, v in cell_lib.count_mismatches(model, gained)] == [
        0, 0, 0]
    gained["ssmChunks"] -= 3                  # a layer's chunk dropped
    gained["pairsCausal"] -= 1
    assert [v for _n, v in cell_lib.count_mismatches(model, gained)] == [
        1, 3, 0]
    # the published widths: the issue's arithmetic a token
    full, _sizes = cell_lib.sizes_of({"config": CFG}, False)
    assert counts.kinds(full) == (36, 4)
    assert 2 * counts.matrix_params(full) == pytest.approx(5.97e9, rel=2e-3)
    a_token = counts.scan_counts(full, 36)["flops"] / 256
    assert a_token == pytest.approx(0.096e9, rel=0.02)
    assert counts.scan_counts(full, 1)["bytes"] == 4 * 256 * (4352 + 64
                                                              + 4096)
    assert counts.attention_counts(full, 1, 0)["flops"] == 4 * 32 * 64


def test_the_draws_lengths_hold_the_mixs_stated_shares():
    """Log-normal around 512 at sigma 1.2, clipped to 16-8,192: mean 998,
    token-weighted 2,883, 28% under 256, 12% over 2,048, 1% at 8,192 (as
    the mix's `what` and BENCHMARK.json's `why` say), over 200,000 draws."""
    h = spec.load_cell(CELL)["traffic"]["history"]
    assert (h["median"], h["sigma"], h["min"], h["max"]) == (512, 1.2, 16,
                                                             8192)
    n = seq_draw.lognormal_lengths(np.random.default_rng(5), 200_000, h
                                   ).astype(np.int64)
    assert n.min() == 16 and n.max() == 8192
    assert n.mean() == pytest.approx(998, rel=0.02)
    assert (n * n).sum() / n.sum() == pytest.approx(2883, rel=0.03)
    assert (n < 256).mean() == pytest.approx(0.28, abs=0.01)
    assert (n > 2048).mean() == pytest.approx(0.12, abs=0.01)
    assert (n == 8192).mean() == pytest.approx(0.0105, abs=0.003)


def test_the_draws_are_the_seeds_and_the_histories_are_int32():
    """A layer drawn alone is the layer in its stack; another seed is
    another; matrices bfloat16, vectors float32 and as Mamba-2 starts
    them; the int32 histories hold the lengths the plan was laid out
    with, left-padded, ids past 16 bits."""
    stacks = draw.stacked_layers(3, TOY)
    one, other = draw.layer_weights(3, TOY, 2), draw.layer_weights(4, TOY, 2)
    assert sorted(one) == sorted(ref.layer_shapes(TOY, "mamba"))
    for name in ref.layer_shapes(TOY, "mamba"):
        group, i = (("mlp", 2) if name in ("post_norm", "w_in", "w_out")
                    else ("mamba", 1))
        np.testing.assert_array_equal(
            np.asarray(one[name], np.float32),
            np.asarray(stacks[group][name][i], np.float32))
    attention = draw.layer_weights(3, TOY, 1)
    np.testing.assert_array_equal(
        np.asarray(attention["wk"], np.float32),
        np.asarray(stacks["attention"]["wk"][0], np.float32))
    assert str(one["in_proj"].dtype) == "bfloat16"
    assert one["A_log"].dtype == one["conv_w"].dtype == np.float32
    assert not np.array_equal(np.asarray(one["in_proj"], np.float32),
                              np.asarray(other["in_proj"], np.float32))
    assert not np.array_equal(one["dt_bias"], other["dt_bias"])
    assert (np.exp(one["A_log"]) >= 1).all() and (
        np.exp(one["A_log"]) <= 16).all()
    step = np.log1p(np.exp(one["dt_bias"]))       # softplus gives dt back
    assert (step > 0.9e-3).all() and (step < 0.11).all()
    assert np.abs(one["conv_w"]).max() <= 0.5 and (one["D"] == 1).all()
    traffic = dict(spec.load_cell(CELL)["traffic"])
    traffic["history"] = traffic["rehearsal_history"]
    hist = draw.histories(traffic, 9, 3000, 100_351, 256, 4.0)
    assert hist.dtype == np.int32 and hist.shape == (3000, 256)
    lengths = np.minimum(seq_draw.history_lengths(traffic, 9, 3000, 4.0),
                         256)
    assert ((hist > 0).sum(axis=1) == lengths).all()
    row = int(np.argmax(lengths < 256))
    assert (hist[row, :256 - lengths[row]] == 0).all()
    assert (1 << 16) < hist.max() <= 100_351
    np.testing.assert_array_equal(
        hist, draw.histories(traffic, 9, 3000, 100_351, 256, 4.0))


def test_the_sample_holds_long_and_short_histories():
    traffic = spec.load_cell(CELL)["traffic"]
    rng = np.random.default_rng(1)
    lengths = seq_draw.lognormal_lengths(rng, 400, traffic["history"]
                                         ).tolist()
    candidates = list(range(1000, 1400))
    picked = cell_lib.pick_sample(77, traffic, candidates, lengths)
    got = [lengths[i - 1000] for i in picked]
    assert len(picked) == len(set(picked)) == 32
    assert sum(n > 2048 for n in got) == 12
    assert sum(n <= 256 for n in got) == 8
    assert picked == cell_lib.pick_sample(77, traffic, candidates, lengths)
    assert picked != cell_lib.pick_sample(78, traffic, candidates, lengths)
    # no long history among the candidates: the others fill the sample
    short_only = [min(n, 2048) for n in lengths]
    assert len(cell_lib.pick_sample(77, traffic, candidates, short_only)
               ) == 32
    assert len(cell_lib.pick_sample(77, traffic, candidates[:5],
                                    lengths[:5])) == 5


def evidence(with_counters=True):
    model, _sizes = cell_lib.sizes_of({"config": CFG}, False)
    seq0 = {"steps": 10, "rows": 80, "tokensReal": 80_000,
            "tokensComputed": 81_920, "attentionPairs": 10**8}
    seq1 = {"steps": 70, "rows": 560, "tokensReal": 560_000,
            "tokensComputed": 573_440, "attentionPairs": 14 * 10**8}
    if with_counters:
        seq0.update(ssmChunks=0, ssmResetsInChunk=0, pairsCausal=0)
        seq1.update(ssmChunks=60 * 32 * 36, ssmResetsInChunk=400 * 36,
                    pairsCausal=4 * 13 * 10**8)
    head = ("%pio.seq.head_topk.1 = f32[8,35]{1,0} custom-call(s32[8] %a)")
    trace = {"busy_s": 4.9, "window_s": 5.0,
             "ops": [[head, 10, 0.02], ["%fusion.1 = f32[8]{0} fusion()", 10,
                                        1.0]]}
    return {"trace": trace, "device_kind": "TPU v5 lite",
            "scopes": {"pio.seq.ssm_scan": [360, 0.7],
                       "pio.seq.ssm_conv": [360, 0.1],
                       # what the compiler made inside a run of Mamba
                       # layers and named after no part of it
                       "pio.seq.ssm_run": [360, 0.2],
                       "pio.seq.ssm_in_proj": [360, 0.5],
                       "pio.seq.gqa_attn": [40, 0.08],
                       "pio.seq.mlp": [400, 2.0]},
            "stats_before": {"sequence": seq0, "batching": {
                "batchedQueries": 0, "batches": 0}},
            "stats_after": {"sequence": seq1, "batching": {
                "batchedQueries": 480, "batches": 60}},
            "shapes": {"n_items": 100_351, "dim": 2048, "k": 10,
                       "model": model}}


def test_the_new_per_layer_metrics_read_and_stay_under_100():
    ev = evidence()
    got = {name: spec.read_layer_metric(name, ev) for name in (
        "seq_step_ms.granite", "seq_step_mfu.granite", "ssm_scan_share",
        "ssm_scan_roofline", "gqa_attn_share", "gqa_attn_roofline",
        "ssm_resets_per_chunk", "seq_token_fill.granite",
        "head_topk_ms.granite", "head_topk_roofline.granite")}
    assert all(v is not None for v in got.values()), got
    assert got["seq_step_ms.granite"] == pytest.approx(4.9 * 1e3 / 10)
    assert got["ssm_scan_share"] == pytest.approx(100 * 1.0 / 4.9)
    assert got["gqa_attn_share"] == pytest.approx(100 * 0.08 / 4.9)
    assert got["ssm_resets_per_chunk"] == pytest.approx(400 / (60 * 32))
    # 10 traced steps of 8,000 real tokens at 6 GFLOP a token over 4.9 s
    assert got["seq_step_mfu.granite"] == pytest.approx(
        100 * 10 * 8000 * 6.08e9 / (197e12 * 4.9), rel=0.02)
    # 10 steps x 32 chunks x 36 layers, byte-bound: 34 KB a token
    least = 10 * 32 * 36 * 256 * 4 * (4352 + 64 + 4096) / 819e9
    assert got["ssm_scan_roofline"] == pytest.approx(100 * least / 1.0)
    for name in ("seq_step_mfu.granite", "ssm_scan_roofline",
                 "gqa_attn_roofline", "head_topk_roofline.granite"):
        assert 0 < got[name] < 100, (name, got[name])


def test_a_program_without_the_counters_reads_nothing_and_raises_nothing():
    """The parent commit under a traced run: no `sequence` counters of
    the device's, no scope map."""
    ev = evidence(with_counters=False)
    ev["scopes"] = None
    for name in ("seq_step_mfu.granite", "ssm_scan_share",
                 "ssm_scan_roofline", "gqa_attn_share", "gqa_attn_roofline",
                 "ssm_resets_per_chunk"):
        assert spec.read_layer_metric(name, ev) is None, name
    ev["stats_before"] = ev["stats_after"] = {}
    assert spec.read_layer_metric("seq_step_mfu.granite", ev) is None
    # another decoder's model block: no such layer to count
    ev = evidence()
    ev["shapes"]["model"] = {"hidden_size": 7168, "experts_held": 12}
    assert spec.read_layer_metric("ssm_scan_roofline", ev) is None


def test_the_configuration_holds_the_catalogs_row():
    """Every published key under its own name, unchanged; nothing
    reduced; the cell as the issue words it."""
    catalog = Path("/opt/skills/guides/model-configs/architectures.jsonl")
    if not catalog.is_file():
        pytest.skip("no catalog beside the model-configs guide here")
    row = next(entry for entry in map(json.loads,
                                      catalog.read_text().splitlines())
               if entry["name"] == "granite-4.0-h-micro")
    assert CFG["source"] == row["source_url"] and CFG["reduced"] == []
    for key, value in row["config"].items():
        assert CFG[key] == value, key
    assert set(ref.CONFIG_KEYS) <= set(row["config"])
    assert CFG["serve"] == {"users": 32768, "items": 100351, "max_len": 8192}
    assert CFG["serve"]["items"] + 1 == CFG["vocab_size"]
    for reason in CFG["assumed"].values():
        assert len(reason) > 20
    cell = spec.load_cell(CELL)
    traffic = cell["traffic"]
    assert traffic["kind"] == "mixed-closed-loop" and cell["chips"] == 1
    assert (traffic["callers"], traffic["num"], traffic["warmup_s"],
            traffic["unknown_share"], traffic["zipf_exponent"]) == (
        32, 10, 3.0, 0.02, 1.0)
    assert (traffic["check_answers"], traffic["check_long_answers"],
            traffic["check_short_answers"]) == (32, 12, 8)
    assert {m["name"] for m in spec.metrics_of(cell, "end_to_end")} == {
        "served_qps", "setup_s"}
    per_layer = {m["name"] for m in spec.metrics_of(cell, "per_layer")}
    assert len(per_layer) == 23
    # what the cell's `why` claims it runs (the cut by cost, the top-k
    # merge, the catalog attach, the first query) it also reports
    assert {"cut_held_share.granite", "topk_merge_share.granite",
            "topk_rounds_per_merge.granite", "first_query_s.granite",
            "deploy_catalog_s.granite"} <= per_layer


def test_rehearsal_of_the_whole_cell_is_correct():
    """The whole cell at toy sizes on the host: seeded, deployed, driven
    by 32 callers, stopped, checked (long and short histories in the
    sample)."""
    env = dict(os.environ, JAX_PLATFORMS="cpu")
    proc = subprocess.run(
        [sys.executable, str(spec.BENCH / "run.py"), "--rehearse",
         "--workload", CELL, "--seed", "4400000077", "--seconds", "3",
         "--trace", "0"], env=env, capture_output=True, text=True,
        timeout=300)
    assert proc.returncode == 0, proc.stdout + proc.stderr
    assert proc.stdout.splitlines()[-1].startswith("REHEARSAL")
    out = json.loads((spec.BENCH / "out"
                      / f"{CELL}.seed4400000077.trace0.json").read_text())
    result = out["result"]
    assert result["correct"] is True and result["failed"] == 0
    assert result["attempted"] > 100
    assert any("12 from histories over" in line and "8 from histories of at"
               in line for line in out["lines"])
