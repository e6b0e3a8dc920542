"""The looped decoder (models/looped_lm.py) at a small size on the CPU:
the served path (deploy-time attach, serving pipeline with its encoder
seam, the retriever's top-k) against the plain reference
(testing/looped_lm_reference.py), and what makes the model a LOOP: the
parameter tree holds the layers once, the program runs them
`total_ut_steps` times.

Sizes: hidden 64, 2 heads of 32, 3 layers, 4 passes, 211 items, float32
compute (the chip runs bfloat16; benchmarks/ holds it to the same
reference there).
"""

import asyncio
import dataclasses
import json
import pickle
import shutil
from pathlib import Path

import jax
import jax.numpy as jnp
import numpy as np
import pytest

from predictionio_tpu.models import looped_lm as lm
from predictionio_tpu.models.seq_common import rows_as_streams
from predictionio_tpu.models.seq_serving import k_lattice
from predictionio_tpu.storage.bimap import BiMap
from predictionio_tpu.testing import looped_lm_reference as ref
from predictionio_tpu.workflow.serialization import (deserialize_models,
                                                     serialize_models)

REPO = Path(__file__).resolve().parents[1]
N_ITEMS, N_USERS = 211, 40
CFG = lm.LoopedLMConfig(
    hidden_size=64, intermediate_size=96, num_hidden_layers=3,
    num_attention_heads=2, head_dim=32, total_ut_steps=4, max_len=24,
    compute_dtype="float32")


def ref_cfg(cfg=CFG) -> dict:
    return {k: getattr(cfg, k) for k in ref.CONFIG_KEYS}


def make_model(cfg=CFG, seed=1, gate_b=0.3) -> lm.LoopedLMModel:
    """Mixed history lengths, 0 (no event) to max_len."""
    params = lm.init_params(cfg, N_ITEMS + 1, seed=seed)
    params["gate_b"] = np.float32(gate_b)
    rng = np.random.default_rng(seed)
    seqs = np.zeros((N_USERS, cfg.max_len), np.int32)
    for u in range(N_USERS):
        n = int(rng.integers(0, cfg.max_len + 1))
        if n:
            seqs[u, -n:] = rng.integers(1, N_ITEMS + 1, n)
    return lm.LoopedLMModel(
        params, seqs, BiMap({f"u{i}": i for i in range(N_USERS)}),
        BiMap({f"i{i}": i for i in range(N_ITEMS)}), cfg)


def device_tree(model):
    """The tree `forward_hidden` reads, as `LoopedEncoder` makes it from
    the model's public one."""
    params = jax.tree_util.tree_map(jnp.asarray, model.params)
    return {**params,
            "layers": lm.head_major(params["layers"], model.config)}


def history(model, user):
    row = model.seqs[model.user_ids.get(user)]
    return row[row > 0]


def held_to_reference(model, users, answers, num, exclude_seen=True):
    """Worst score_err / rank_slack of served answers over the users."""
    worst = 0.0
    for user, answer in zip(users, answers):
        row = model.user_ids.get(user)
        if row is None or not len(history(model, user)):
            assert answer == []
            continue
        hist = history(model, user)
        logits = ref.next_item_scores(model.params, ref_cfg(model.config),
                                      hist)[1:]
        seen = np.unique(hist) - 1 if exclude_seen else np.zeros(0, int)
        got = ref.compare_answer([(int(i[1:]), s) for i, s in answer],
                                 logits, seen, num)
        assert got["short"] == 0, (user, answer)
        worst = max(worst, got["score_err"], got["rank_slack"])
    return worst


USERS = [f"u{i}" for i in range(30)] + ["nobody"]


@pytest.mark.parametrize("exclude_seen", [True, False])
@pytest.mark.parametrize("interpret", [None, True],
                         ids=["xla-twin", "kernel-interpret"])
def test_served_path_matches_the_reference_for_mixed_lengths(
        exclude_seen, interpret):
    """One batch of histories of 0 to 24 events, packed into one stream:
    every answer's scores are the reference's logits, no seen item is
    served (or every item may be), an unknown user and a user without
    events get []."""
    model = make_model()
    model.attach_retriever(interpret=interpret)
    model.attach_pipeline()
    got = model.batch_recommend(USERS, [5] * len(USERS),
                                exclude_seen=exclude_seen)
    assert held_to_reference(model, USERS, got, 5, exclude_seen) < 1e-5
    stats = model._pipeline.stats()
    assert stats["mode"] == "fused"
    seq = stats["sequence"]
    assert seq["steps"] == 1 and seq["loopPasses"] == CFG.total_ut_steps
    real = sum(len(history(model, u)) for u in USERS[:-1])
    assert seq["tokensReal"] == real <= seq["tokensComputed"]
    assert seq["tokensComputed"] in seq["tokenLattice"]
    assert sum(seq["exitStepHistogram"]) == seq["rows"]


def test_left_padded_rows_and_the_packed_stream_agree():
    """The training layout (one left-padded history a row) and the
    serving layout (histories packed in one row) give every real
    position the same state, the reference's."""
    model = make_model()
    params = device_tree(model)
    rows = model.seqs[[3, 5, 8, 13]]
    h_rows, _half, _ran = lm.forward_hidden(params, CFG,
                                      *rows_as_streams(jnp.asarray(rows)))
    lens = (rows > 0).sum(axis=1)
    tokens = np.concatenate([r[r > 0] for r in rows])
    seg = np.repeat(np.arange(1, 5), lens)
    pos = np.concatenate([np.arange(n) for n in lens])
    h_pack, _half, _ran = lm.forward_hidden(
        params, CFG, jnp.asarray(tokens)[None], jnp.asarray(seg)[None],
        jnp.asarray(pos)[None])
    at = 0
    for r, n in zip(range(4), lens.tolist()):
        np.testing.assert_allclose(h_rows[r, CFG.max_len - n:],
                                   h_pack[0, at:at + n], atol=2e-5)
        emb = np.asarray(model.params["embed"])[rows[r][rows[r] > 0]]
        want, *_ = ref.forward(
            emb, ref.stacked_layer_of(model.params["layers"]),
            {k: model.params[k] for k in ("norm_f", "gate_w", "gate_b")},
            ref_cfg())
        np.testing.assert_allclose(h_pack[0, at:at + n], want, atol=2e-5)
        at += n


@pytest.mark.parametrize("dtype", ["float32", "bfloat16"])
def test_head_major_is_a_transpose_and_a_view(dtype):
    """`wq`, `wk`, `wv` `[L, D, A]` -> `[L, H, hd, D]` and `wo` `[L, A, D]`
    -> `[L, H, hd, D]` move no value: turned back they are the public
    stacks bit for bit, `wo` and the stacks it leaves alone are the
    public tree's own memory, and head h's slice is the h-th block of
    `head_dim` columns (rows, for `wo`)."""
    cfg = dataclasses.replace(CFG, compute_dtype=dtype)
    public = lm._stored(lm.init_params(cfg, N_ITEMS + 1, seed=5),
                        jnp.dtype(dtype))["layers"]
    got = lm.head_major(public, cfg)
    L, H, hd, D = 3, 2, 32, 64
    assert set(got) == set(public)
    for k in ("wq", "wk", "wv"):
        assert got[k].shape == (L, H, hd, D) and got[k].dtype == public[k].dtype
        back = got[k].transpose(0, 3, 1, 2).reshape(L, D, H * hd)
        assert back.tobytes() == public[k].tobytes()
        np.testing.assert_array_equal(
            got[k][1, 1].astype(np.float32),
            public[k][1, :, hd:2 * hd].T.astype(np.float32))
    assert got["wo"].shape == (L, H, hd, D)
    assert np.shares_memory(got["wo"], public["wo"])
    assert got["wo"].reshape(L, H * hd, D).tobytes() == public["wo"].tobytes()
    for k in set(public) - {"wq", "wk", "wv", "wo"}:
        assert got[k] is public[k]
    # some of a tree's stacks (the encoder converts stack by stack), and
    # under a trace, as the trainer's step does
    np.testing.assert_array_equal(
        lm.head_major({"wq": public["wq"]}, cfg)["wq"], got["wq"])
    traced = jax.jit(lambda t: lm.head_major(t, cfg))(public)
    for k in public:
        assert np.asarray(traced[k]).tobytes() == np.ascontiguousarray(
            got[k]).tobytes()


def test_a_blob_of_the_public_tree_deploys_head_major():
    """What `workflow/serialization.py` persists is the public tree
    (`[L, D, A]`: a blob written before the head-major layout deploys as
    it is); the encoder holds the converted stacks on the device, the
    model keeps its own, and the answers are the reference's."""
    written = make_model()
    (model,) = deserialize_models(serialize_models([written]))
    shapes = lm.param_shapes(CFG, N_ITEMS + 1)
    for k, shape in shapes["layers"].items():
        assert model.params["layers"][k].shape == shape
        np.testing.assert_array_equal(model.params["layers"][k],
                                      written.params["layers"][k])
    model.attach_retriever()
    model.attach_pipeline()
    enc = model._pipeline._encoder
    assert "head" not in enc.params
    for k in ("wq", "wk", "wv", "wo"):
        assert enc.params["layers"][k].shape == (3, 2, 32, 64)
        assert model.params["layers"][k].shape == shapes["layers"][k]
    assert enc.params["layers"]["wg"].shape == shapes["layers"]["wg"]
    assert enc.param_bytes == sum(
        np.asarray(v).nbytes for v in jax.tree_util.tree_leaves(
            {k: v for k, v in model.params.items() if k != "head"}))
    got = model.batch_recommend(USERS, [5] * len(USERS))
    assert held_to_reference(model, USERS, got, 5) < 1e-5


def test_the_tree_and_the_blob_hold_the_layers_once():
    """3 layers in the parameter tree and in the persisted blob, not
    3 x 4 passes; bfloat16 weights go through serialization as they are."""
    cfg = dataclasses.replace(CFG, compute_dtype="bfloat16")
    model = lm.train_looped_lm(
        make_model().seqs, BiMap({f"u{i}": i for i in range(N_USERS)}),
        BiMap({f"i{i}": i for i in range(N_ITEMS)}),
        dataclasses.replace(cfg, epochs=1))
    for name, shape in lm.param_shapes(cfg, N_ITEMS + 1)["layers"].items():
        assert model.params["layers"][name].shape == shape
        assert shape[0] == cfg.num_hidden_layers == 3
    assert lm.param_count(cfg, N_ITEMS + 1) == sum(
        int(np.prod(x.shape)) for x in
        jax.tree_util.tree_leaves(model.params))
    blob = serialize_models([model])
    (back,) = deserialize_models(blob)
    assert back.params["layers"]["wq"].dtype == jnp.bfloat16
    assert back.params["layers"]["wq"].shape == (3, 64, 64)
    np.testing.assert_array_equal(
        back.params["layers"]["wd"].astype(np.float32),
        model.params["layers"]["wd"].astype(np.float32))
    assert not hasattr(back, "_pipeline")       # device state stays out
    matrices = sum(v.nbytes for k, v in back.params["layers"].items())
    assert len(blob) < matrices * 2             # once, not once a pass
    assert pickle.loads(pickle.dumps(cfg)) == cfg


@pytest.mark.parametrize("passes", [1, 4])
def test_passes_are_the_plain_stack_applied_again(passes):
    """1 pass is the 3-layer stack and the final norm; 4 passes are that
    applied 4 times, the final norm between, with the SAME weights."""
    cfg = dataclasses.replace(CFG, total_ut_steps=passes)
    model = make_model(cfg)
    hist = history(model, "u7")
    h = jnp.asarray(np.asarray(model.params["embed"])[hist])
    with jax.default_matmul_precision("highest"):
        for _t in range(passes):
            for layer in range(cfg.num_hidden_layers):
                w = {k: jnp.asarray(v[layer])
                     for k, v in model.params["layers"].items()}
                h = ref.layer_forward(h, w, ref_cfg(cfg))
            h = ref.rms_norm(h, model.params["norm_f"], cfg.rms_norm_eps)
    params = device_tree(model)
    got, _half, _ran = lm.forward_hidden(
        params, cfg, jnp.asarray(hist)[None],
        jnp.ones((1, len(hist)), jnp.int32),
        jnp.arange(len(hist), dtype=jnp.int32)[None])
    np.testing.assert_allclose(got[0], h, atol=2e-5)
    assert int(_ran) == passes      # counted where a pass ends


def test_unshared_layers_or_a_pass_short_are_another_model():
    """What the loop means, held against its negations: the reference
    with other weights in the later passes, or one pass fewer, moves the
    logits far beyond anything rounding does."""
    model = make_model()
    hist = history(model, "u7")
    emb = np.asarray(model.params["embed"])[hist]
    top = {k: model.params[k] for k in ("norm_f", "gate_w", "gate_b")}
    shared = ref.stacked_layer_of(model.params["layers"])
    other = ref.stacked_layer_of(
        lm.init_params(CFG, N_ITEMS + 1, seed=99)["layers"])
    sound, *_ = ref.forward(emb, shared, top, ref_cfg())
    unshared, *_ = ref.forward(
        emb, lambda t, l: (shared if t == 0 else other)(t, l), top,
        ref_cfg())
    short, *_ = ref.forward(emb, shared, top, ref_cfg(), passes=3)
    spread = float(np.ptp(np.asarray(sound[-1])))
    assert float(np.abs(unshared[-1] - sound[-1]).max()) > 0.05 * spread
    assert float(np.abs(short[-1] - sound[-1]).max()) > 0.05 * spread


@pytest.mark.parametrize("threshold,last", [(1.0, True), (0.6, False)])
def test_exit_distribution_and_threshold(threshold, last):
    """The exit probabilities of a position sum to 1; the published
    threshold 1 exits at the last pass, a lower one earlier, and the
    program's exit state is the reference's either way."""
    cfg = dataclasses.replace(CFG, early_exit_threshold=threshold)
    model = make_model(cfg, gate_b=0.8)
    hist = history(model, "u9")
    emb = np.asarray(model.params["embed"])[hist]
    top = {k: model.params[k] for k in ("norm_f", "gate_w", "gate_b")}
    h_exit, exit_step, half_step, p = ref.forward(
        emb, ref.stacked_layer_of(model.params["layers"]), top,
        ref_cfg(cfg))
    np.testing.assert_allclose(np.asarray(p).sum(axis=0), 1.0, atol=1e-6)
    assert (np.asarray(exit_step) == cfg.total_ut_steps).all() == last
    assert np.asarray(exit_step).min() >= 1
    assert (np.asarray(half_step) <= np.asarray(exit_step)).all()
    params = device_tree(model)
    got, half, ran = lm.forward_hidden(
        params, cfg, jnp.asarray(hist)[None],
        jnp.ones((1, len(hist)), jnp.int32),
        jnp.arange(len(hist), dtype=jnp.int32)[None])
    np.testing.assert_allclose(got[0], h_exit, atol=2e-5)
    np.testing.assert_array_equal(half[0], half_step)
    assert int(ran) == cfg.total_ut_steps


def test_a_fold_larger_than_the_budget_goes_in_several_steps(monkeypatch):
    """A caller that cuts nothing (an evaluation fold) is split into
    steps of at most the token budget, in order, with the same answers."""
    monkeypatch.setattr(lm, "STEP_TOKEN_BUDGET", 64)
    monkeypatch.setattr(lm, "STEP_TOKEN_MIN", 32)
    model = make_model()
    users = [f"u{i}" for i in range(N_USERS)]
    got = model.batch_recommend(users, [3] * len(users))
    seq = model._pipeline.stats()["sequence"]
    assert seq["tokenLattice"] == [32, 64] and seq["steps"] > 4
    assert seq["tokensComputed"] <= 64 * seq["steps"]
    assert held_to_reference(model, users, got, 3) < 1e-5
    one = model.recommend_products("u3", 3)
    assert [i for i, _s in one] == [i for i, _s in got[3]]


def test_k_lattice_and_row_cost():
    assert k_lattice(512) == (16, 64, 256, 528)
    assert k_lattice(24) == (16, 40)
    model = make_model()
    assert model.serving_cost_budget == max(lm.STEP_TOKEN_BUDGET, CFG.max_len)
    assert model.serving_cost("u7") == len(history(model, "u7"))
    assert model.serving_cost("nobody") == 0


def test_the_harness_reference_is_this_reference():
    """benchmarks/lib/seq_reference.py is a copy, byte for byte: the
    harness takes nothing from the program."""
    assert (REPO / "benchmarks" / "lib" / "seq_reference.py").read_bytes() == (
        REPO / "predictionio_tpu" / "testing"
        / "looped_lm_reference.py").read_bytes()


def test_pio_train_then_deploy_of_looped_answers_a_query(tmp_path, rng):
    """`pio train` -> the deploy-time attach (retriever, encoder,
    pipeline, prewarm over the token lattice) -> a query through the
    micro-batcher, cut by tokens: /stats.json shows the pipeline and the
    kernel as for ALS, and the `sequence` counters."""
    from predictionio_tpu.storage import Storage
    from predictionio_tpu.tools.cli import main as pio
    from predictionio_tpu.workflow import resolve_engine_factory
    from predictionio_tpu.workflow.create_server import EngineServer
    from tests.test_quickstart_e2e import make_events_file

    engine_dir = tmp_path / "myseq"
    shutil.copytree(REPO / "templates" / "seqrec", engine_dir)
    variant = json.loads((engine_dir / "engine.json").read_text())
    variant["datasource"]["params"]["app_name"] = "seqtest"
    variant["algorithms"] = [{"name": "looped", "params": {
        **ref_cfg(), "max_len": 16, "compute_dtype": "float32",
        "epochs": 1, "batch_size": 16}}]
    (engine_dir / "engine.json").write_text(json.dumps(variant))
    assert pio(["app", "new", "seqtest"]) == 0
    app = Storage.get_metadata().app_get_by_name("seqtest")
    events = tmp_path / "events.jsonl"
    make_events_file(events, rng)
    assert pio(["import", "--appid", str(app.id), "--input",
                str(events)]) == 0
    assert pio(["train", "--engine-dir", str(engine_dir)]) == 0
    inst = Storage.get_metadata().engine_instance_get_completed(
        "default", "1", "default")[0]
    engine = resolve_engine_factory("engine:engine_factory",
                                    engine_dir=engine_dir)
    server = EngineServer(engine, inst)
    model = server.deployed.result.models[0]
    assert type(model).__name__ == "LoopedLMModel"
    assert model.params["layers"]["wq"].shape[0] == 3
    user = next(iter(model.user_ids))
    # the batcher's cut asks the ALGORITHM, of the parsed query
    cost_of, budget = server._costing()
    algo = server.deployed.result.algorithms[0]
    assert budget == algo.cost_budget(model) == model.serving_cost_budget
    assert cost_of({"user": user, "num": 4}) == len(history(model, user))
    assert cost_of({"user": "nobody"}) == 0 == cost_of({"no": "user"})
    assert server.batcher.costing == server._costing

    async def ask():
        try:
            return await asyncio.gather(*[
                server.batcher.submit({"user": user, "num": 4})
                for _ in range(3)])
        finally:
            await server.batcher.close()

    answers = asyncio.run(ask())
    assert all(len(a["itemScores"]) == 4 for a in answers)
    seen = {model.item_ids.inverse[int(t) - 1] for t in history(model, user)}
    assert not seen & {s["item"] for s in answers[0]["itemScores"]}
    stats = server.serving_stats()
    assert stats["pipeline"]["mode"] == "fused"
    assert stats["retrieval"]["kernel"] == "xla"
    assert stats["sequence"]["steps"] >= 1
    assert stats["sequence"]["loopPasses"] == 4 * stats["sequence"]["steps"]
    assert stats["batching"]["batchedQueries"] == 3
    phases = [name for name, *_ in stats["startup"]["phases"]]
    assert "pio.deploy.attach_encoder" in phases
    assert "pio.deploy.prewarm" in phases
