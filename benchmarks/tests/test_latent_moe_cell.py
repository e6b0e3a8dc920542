"""The `axk1-seqrec.lifelong-closed` cell's own pieces at toy sizes on
the host: the limits of `correct` refuse the control and each broken
path the issue names; the exact counts refuse a router that picks 7 for
8; the readers read what the program counts and return None where it
keeps no such counter (the parent commit); the scope arithmetic; the
configuration against the catalog's row."""

import json
from pathlib import Path

import numpy as np
import pytest

from lib import (latent_moe_counts as counts, latent_moe_draw as draw,
                 latent_moe_reference as ref, latent_moe_serve as cell_lib,
                 scope_time, seq_draw, spec)
from lib.seq_reference import compare_answer

CELL = "axk1-seqrec.lifelong-closed"
CFG = spec.load_json(spec.BENCH / "configs" / "axk1-seqrec.json")
TOY = CFG["rehearsal"]["model"]
N_ITEMS = 211


def toy_params(seed, cfg=TOY, scale=6.0):
    """A public tree at toy widths from the cell's own draws, its
    matrices scaled up so that five-layer effects (attention's scale,
    the shared expert) are as large against the logit spread as at the
    published widths."""
    layers = {}
    for i in range(cfg["num_hidden_layers"]):
        w = draw.layer_weights(seed, cfg, i)
        layers[str(i)] = {k: (np.asarray(v, np.float32) if k in ref.NORMS
                              else np.asarray(v, np.float32) * scale)
                          for k, v in w.items()}
    vocab = N_ITEMS + 1
    return {"embed": np.asarray(seq_draw.table(
                seed, seq_draw.EMBED, vocab, cfg["hidden_size"]),
                np.float32) * scale,
            "head": np.asarray(seq_draw.table(
                seed, seq_draw.HEAD, vocab, cfg["hidden_size"]), np.float32),
            "norm_f": np.ones(cfg["hidden_size"], np.float32),
            "layers": layers}


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
    hists = [rng.integers(1, N_ITEMS + 1, n) for n in (40, 90, 130, 64)]
    logits = [ref.next_item_scores(params, TOY, h) for h in hists]
    return params, hists, logits


def test_the_sound_reference_passes_its_own_limits(sound):
    _params, _hists, logits = sound
    ok, numbers = verdict(logits, logits)
    assert ok and all(v == 0 for v, _lim in numbers)


@pytest.mark.parametrize("variant", ["no_shared", "top7", "no_yarn"])
def test_a_broken_path_is_refused(sound, variant):
    """No shared expert; 7 experts a token for 8; plain RoPE and no
    mscale where the config says YaRN: each is refused by one of the
    cell's limits."""
    params, hists, logits = sound
    broken = [ref.next_item_scores(params, TOY, h, variant=variant)
              for h in hists]
    ok, numbers = verdict(broken, logits)
    assert not ok, numbers


def test_the_absent_experts_part_added_is_refused(sound):
    """The uncut layer (all 16 experts' parts) in the share's place."""
    params, hists, logits = sound
    whole_cfg = dict(TOY, first_expert=0,
                     experts_held=TOY["n_routed_experts"])
    whole = {**params, "layers": {}}
    for i, layer in params["layers"].items():
        if "router" not in layer:
            whole["layers"][i] = layer
            continue
        full = draw.layer_weights(11, whole_cfg, int(i))
        grown = dict(layer)
        for k in ("experts_gate", "experts_up", "experts_down"):
            grown[k] = np.asarray(full[k], np.float32) * 6.0
            lo = TOY["first_expert"]
            grown[k][lo:lo + TOY["experts_held"]] = layer[k]
        whole["layers"][i] = grown
    broken = [ref.next_item_scores(whole, whole_cfg, h) for h in hists]
    ok, numbers = verdict(broken, logits)
    assert not ok, numbers


def test_the_control_is_refused(sound):
    """The reference with its matrices rounded to float8 (e5m2), the
    nearest precision below the bfloat16 the configuration states."""
    import jax
    import jax.numpy as jnp

    params, hists, logits = sound
    low = {**params, "layers": {
        i: {k: (np.asarray(jax.lax.reduce_precision(
            jnp.asarray(v), exponent_bits=5, mantissa_bits=2))
            if k not in ref.NORMS and k != "router" else v)
            for k, v in layer.items()}
        for i, layer in params["layers"].items()}}
    broken = [ref.next_item_scores(low, TOY, h) for h in hists]
    ok, numbers = verdict(broken, logits)
    assert not ok, numbers


def test_seven_experts_for_eight_is_a_count_mismatch():
    model = dict(TOY)
    tokens = 1000
    routed = counts.routed_layers(model)
    assert routed == 2
    gained = {"tokensReal": tokens, "attentionPairs": 5000,
              "routerAssignments": tokens * routed * 4,
              "pairsCausal": 5000 * 3}
    assert [v for _n, v in cell_lib.count_mismatches(model, gained)] == [0, 0]
    gained["routerAssignments"] = tokens * routed * 3
    gained["pairsCausal"] -= 1
    assert [v for _n, v in cell_lib.count_mismatches(model, gained)] == [
        tokens * routed, 1]
    need = ref.expected_counts([10, 20], model)
    assert need["routerAssignments"] == 30 * routed * 4
    assert need["pairsCausal"] == (55 + 210) * 3


def test_the_draws_are_the_seeds_and_the_histories_fit_16_bits():
    """A layer drawn twice, alone or with the others, is the same
    layer; another seed is another; the router is float32, the
    matrices bfloat16; the uint16 histories hold the lengths the plan
    was laid out with, left-padded."""
    one = draw.layer_weights(3, TOY, 1)
    together = draw.all_layers(3, TOY)["1"]
    other = draw.layer_weights(4, TOY, 1)
    assert sorted(one) == sorted(ref.layer_shapes(TOY, 1))
    for name, shape in ref.layer_shapes(TOY, 1).items():
        assert one[name].shape == tuple(shape)
        np.testing.assert_array_equal(np.asarray(one[name], np.float32),
                                      np.asarray(together[name], np.float32))
    assert one["router"].dtype == np.float32
    assert str(one["experts_up"].dtype) == "bfloat16"
    assert not np.array_equal(np.asarray(one["wq_a"], np.float32),
                              np.asarray(other["wq_a"], np.float32))
    assert abs(float(np.asarray(one["experts_up"], np.float32).std())
               - 0.02) < 2e-3
    traffic = dict(spec.load_cell(CELL)["traffic"])
    traffic["history"] = traffic["rehearsal_history"]
    hist = draw.histories(traffic, 9, 3000, N_ITEMS, 256, 4.0)
    assert hist.dtype == np.uint16 and hist.shape == (3000, 256)
    lengths = np.minimum(seq_draw.history_lengths(traffic, 9, 3000, 4.0),
                         256)
    assert ((hist > 0).sum(axis=1) == lengths).all()
    row = int(np.argmax(lengths < 256))
    assert (hist[row, :256 - lengths[row]] == 0).all()
    assert hist.max() <= N_ITEMS
    np.testing.assert_array_equal(
        hist, draw.histories(traffic, 9, 3000, N_ITEMS, 256, 4.0))
    with pytest.raises(ValueError, match="16 bits"):
        draw.histories(traffic, 9, 10, 1 << 16, 256, 4.0)


def test_scope_arithmetic():
    ops = [["%fusion.7 = bf16[8192,64]{1,0:T(8,128)(2,1)} fusion(bf16[8192,"
            "7168]{1,0} %p), kind=kLoop", 10, 0.5],
           ["%gmm.3 = f32[8192,2048]{1,0:T(8,128)} custom-call(s32[] %a)", 4,
            0.25],
           ["%while.2 = (s32[]{:T(128)}, f32[8192,7168]{1,0}) while((s32[], "
            "f32[8192,7168]) %t)", 4, 0.9],
           ["%copy.1 = f32[8]{0} copy(f32[8]{0} %x)", 1, 0.01]]
    scopes = {"%fusion.7 = bf16[8192,64]{1,0:T(8,128)(2,1)} fusion":
              "pio.seq.experts",
              "%gmm.3 = f32[8192,2048]{1,0:T(8,128)} custom-call":
              "pio.seq.experts.matmul"}
    assert scope_time.operation_key(ops[0][0]) in scopes
    scoped = scope_time.by_scope(ops, scopes)
    assert scoped["pio.seq.experts"] == [10, 0.5]
    assert scoped["pio.seq.experts.matmul"] == [4, 0.25]
    assert scoped[""] == [1, 0.01]            # the while is its body's time
    assert scope_time.seconds_of(scoped, r"^pio\.seq\.experts") == 0.75
    assert scope_time.seconds_of(scoped, r"^pio\.seq\.experts$") == 0.5
    assert scope_time.load_scopes(Path("/nonexistent")) == {}


def evidence(with_counters=True):
    model, _sizes = cell_lib.sizes_of({"config": CFG}, False)
    seq0 = {"steps": 10, "rows": 20, "tokensReal": 50_000,
            "tokensComputed": 60_000, "attentionPairs": 10**8}
    seq1 = {"steps": 110, "rows": 270, "tokensReal": 700_000,
            "tokensComputed": 800_000, "attentionPairs": 3 * 10**9}
    if with_counters:
        seq0.update(routerAssignments=0, expertAssignmentsHere=0,
                    expertAssignmentsFullest=0, pairsCausal=0)
        seq1.update(routerAssignments=650_000 * 32,
                    expertAssignmentsHere=1_300_000,
                    expertAssignmentsFullest=250_000,
                    pairsCausal=5 * (3 * 10**9 - 10**8))
    head = ("%pio.seq.head_topk.1 = f32[8,35]{1,0} custom-call(s32[8] %a)")
    trace = {"busy_s": 4.9, "window_s": 5.0,
             "ops": [[head, 20, 0.02], ["%fusion.1 = f32[8]{0} fusion()", 20,
                                        1.0]]}
    return {"trace": trace, "device_kind": "TPU v5 lite",
            "scopes": {"pio.seq.latent_attn": [100, 1.5],
                       "pio.seq.experts": [400, 0.1],
                       "pio.seq.experts.matmul": [240, 0.6],
                       "pio.seq.router": [80, 0.05],
                       "pio.seq.shared_expert": [80, 0.3]},
            "stats_before": {"sequence": seq0, "batching": {
                "batchedQueries": 0, "batches": 0}},
            "stats_after": {"sequence": seq1, "batching": {
                "batchedQueries": 250, "batches": 100}},
            "shapes": {"n_items": 20479, "dim": 7168, "k": 10,
                       "model": model}}


def test_the_new_per_layer_metrics_read_and_stay_under_100():
    ev = evidence()
    got = {name: spec.read_layer_metric(name, ev) for name in (
        "seq_step_ms.axk1", "seq_step_mfu.axk1", "latent_attn_share",
        "moe_share", "latent_attn_roofline", "expert_matmul_roofline",
        "expert_load_max_over_mean", "seq_token_fill.axk1",
        "head_topk_ms.axk1")}
    assert all(v is not None for v in got.values()), got
    assert got["seq_step_ms.axk1"] == pytest.approx(4.9 * 1e3 / 20)
    assert got["latent_attn_share"] == pytest.approx(100 * 1.5 / 4.9)
    assert got["moe_share"] == pytest.approx(100 * 1.05 / 4.9)
    assert got["expert_load_max_over_mean"] == pytest.approx(
        250_000 * 12 / 1_300_000)
    for name in ("seq_step_mfu.axk1", "latent_attn_roofline",
                 "expert_matmul_roofline"):
        assert 0 < got[name] < 100, (name, got[name])
    # the step's FLOPs are the issue's arithmetic: 2.78 G a token at the
    # mix's attention, of it the latent projections 5 x 202 M
    model = ev["shapes"]["model"]
    assert counts.latent_projection_params(model) == pytest.approx(
        101.12e6, rel=1e-3)
    assert counts.expert_params(model) == 3 * 7168 * 2048
    per_token = counts.step_counts(model, 1, 4300 / 2 * 5, 4 * 8 / 16, 0, 0)
    assert per_token["flops"] == pytest.approx(2.78e9, rel=0.03)


def test_a_program_without_the_counters_reads_nothing_and_raises_nothing():
    """The parent commit under a traced run: no `sequence` counters of
    the device's, no scope map."""
    ev = evidence(with_counters=False)
    ev["scopes"] = None
    for name in ("seq_step_mfu.axk1", "latent_attn_share", "moe_share",
                 "latent_attn_roofline", "expert_matmul_roofline",
                 "expert_load_max_over_mean"):
        assert spec.read_layer_metric(name, ev) is None, name
    ev["stats_before"] = ev["stats_after"] = {}
    assert spec.read_layer_metric("seq_step_mfu.axk1", ev) is None


def test_the_configuration_holds_the_catalogs_row():
    """Every published key under its own name, unchanged, but the three
    in `reduced`; no width among them."""
    row = None
    catalog = Path("/opt/skills/guides/model-configs/architectures.jsonl")
    if not catalog.is_file():
        pytest.skip("no catalog beside the model-configs guide here")
    for line in catalog.read_text().splitlines():
        entry = json.loads(line)
        if entry["name"] == "A.X-K1":
            row = entry
    assert row is not None and CFG["source"] == row["source_url"]
    assert CFG["reduced"] == ["num_hidden_layers", "n_routed_experts",
                              "vocab_size"]
    for key, value in row["config"].items():
        if key in CFG["reduced"]:
            assert CFG["published"][key] == value
            assert CFG[key] < value
        else:
            assert CFG[key] == value, key
    assert CFG["vocab_size"] * 8 >= row["config"]["vocab_size"]
    assert CFG["n_routed_experts"] >= 8 and CFG["num_hidden_layers"] >= 5
    model, sizes = cell_lib.sizes_of({"config": CFG}, False)
    assert model["n_routed_experts"] == 192 and model["experts_held"] == 12
    assert sizes["items"] + 1 == CFG["vocab_size"]
    cell = spec.load_cell(CELL)
    assert cell["traffic"]["kind"] == "lifelong-closed-loop"
    assert {m["name"] for m in spec.metrics_of(cell, "end_to_end")} == {
        "served_qps", "setup_s"}
    assert len(spec.metrics_of(cell, "per_layer")) == 18
