"""The latent-attention mixture-of-experts decoder (models/latent_moe_lm.py)
against its plain reference (benchmarks/lib/latent_moe_reference.py: the
one file, which the benchmark's check child loads too), at small sizes
on the CPU: the packed stream, the share of the experts, YaRN beyond the
original context, the attention kernel at d_qk != d_v, the serving
route."""

import asyncio
import dataclasses
import importlib.util
import json
import shutil
import sys
from pathlib import Path

import jax
import jax.numpy as jnp
import numpy as np
import pytest

from predictionio_tpu.models import latent_moe_lm as lm
from predictionio_tpu.parallel.ring_attention import (
    PLAIN_MAX_L, attention_kernel_for, flash_attention, segment_attention,
    segment_flash_attention)
from predictionio_tpu.storage.bimap import BiMap

REPO = Path(__file__).resolve().parents[1]


def _load_reference():
    name = "pio_latent_moe_reference"
    if name in sys.modules:
        return sys.modules[name]
    spec = importlib.util.spec_from_file_location(
        name, REPO / "benchmarks" / "lib" / "latent_moe_reference.py")
    mod = importlib.util.module_from_spec(spec)
    sys.modules[name] = mod
    spec.loader.exec_module(mod)
    return mod


ref = _load_reference()

N_ITEMS, N_USERS = 97, 24
YARN = {"type": "yarn", "factor": 32, "original_max_position_embeddings": 16,
        "beta_fast": 32, "beta_slow": 1, "mscale": 1, "mscale_all_dim": 1}
CFG = lm.LatentMoEConfig(
    hidden_size=64, intermediate_size=160, moe_intermediate_size=32,
    num_attention_heads=4, q_lora_rank=48, kv_lora_rank=32,
    qk_nope_head_dim=16, qk_rope_head_dim=8, v_head_dim=16,
    n_routed_experts=16, num_experts_per_tok=4, num_hidden_layers=3,
    first_expert=4, experts_held=4, rope_scaling=YARN, max_len=96,
    compute_dtype="float32")


@pytest.fixture(autouse=True)
def small_steps(monkeypatch):
    """A serving step of 512 tokens (lattice 256, 384, 512): the CPU
    compiles three small programs and not three of 8,192 tokens."""
    monkeypatch.setattr(lm, "STEP_TOKEN_BUDGET", 512)


def ref_cfg(cfg=CFG) -> dict:
    return {k: getattr(cfg, k) for k in ref.CONFIG_KEYS}


def make_model(cfg=CFG, seed=1) -> lm.LatentMoEModel:
    """Mixed history lengths, 0 (no event) to max_len, as uint16 (what
    the benchmark's seeding persists)."""
    params = lm.init_params(cfg, N_ITEMS + 1, seed=seed)
    rng = np.random.default_rng(seed)
    seqs = np.zeros((N_USERS, cfg.max_len), np.uint16)
    for u in range(N_USERS):
        n = int(rng.integers(0, cfg.max_len + 1))
        if n:
            seqs[u, -n:] = rng.integers(1, N_ITEMS + 1, n)
    return lm.LatentMoEModel(
        params, seqs, BiMap({f"u{i}": i for i in range(N_USERS)}),
        BiMap({f"i{i}": i for i in range(N_ITEMS)}), cfg)


def history(model, user):
    row = model.seqs[model.user_ids.get(user)]
    return row[row > 0].astype(np.int64)


def pack(histories, t_pad):
    toks, seg, pos = (np.zeros(t_pad, np.int32) for _ in range(3))
    at = 0
    for j, h in enumerate(histories):
        n = len(h)
        toks[at:at + n], seg[at:at + n] = h, j + 1
        pos[at:at + n] = np.arange(n)
        at += n
    return toks, seg, pos


def forward(model, histories, t_pad, **kw):
    cfg = model.config
    tree = jax.tree_util.tree_map(jnp.asarray,
                                  lm.device_tree(model.params, cfg))
    return jax.jit(lambda p, a, b, c: lm.forward_hidden(p, cfg, a, b, c, **kw)
                   )(tree, *pack(histories, t_pad))


_REF_JIT = {}


def reference_scores(params, cfg_dict, hist, width=128):
    """`ref.next_item_scores` with the history padded on the RIGHT to one
    width (causal attention and per-token experts leave the positions
    before the padding as they are), so the reference compiles once and
    not once a length."""
    key = (json.dumps(cfg_dict, sort_keys=True), width)
    if key not in _REF_JIT:
        def run(emb, layers, norm_f, head, last):
            h, _loads = ref.forward(emb, lambda i: layers[str(i)], norm_f,
                                    cfg_dict)
            return ref.scores(h[last], head)
        _REF_JIT[key] = jax.jit(run)
    padded = np.zeros(width, np.int64)
    padded[:len(hist)] = hist
    f32 = lambda t: jax.tree_util.tree_map(  # noqa: E731
        lambda x: jnp.asarray(np.asarray(x), jnp.float32), t)
    emb = np.asarray(params["embed"]).astype(np.float32)[padded]
    return np.asarray(_REF_JIT[key](
        emb, f32(params["layers"]), f32(params["norm_f"]),
        f32(params["head"]), len(hist) - 1))


def test_the_padded_reference_is_the_reference():
    model = make_model()
    hist = history(model, "u3")
    np.testing.assert_allclose(
        reference_scores(model.params, ref_cfg(), hist),
        ref.next_item_scores(model.params, ref_cfg(), hist),
        rtol=1e-5, atol=1e-6)


def rel_err(got, want):
    return float(np.abs(got - want).max() / (want.max() - want.min()))


@pytest.mark.parametrize("dtype,limit", [("float32", 1e-5),
                                         ("bfloat16", 0.05)])
def test_served_path_matches_the_reference(dtype, limit):
    """Through the retriever, the pipeline's encoder seam and the fused
    top-k: float32 to rounding (1e-5 of the row's logit spread);
    bfloat16 matmul inputs against the float32 reference within 0.05 of
    it (three random-weight layers at hidden 64 read 0.004-0.02 here;
    a flipped expert on a near-tie is inside that at these sizes)."""
    model = make_model(dataclasses.replace(CFG, compute_dtype=dtype))
    if dtype == "bfloat16":
        model.params = lm._stored(model.params, jnp.bfloat16)
    users = [f"u{i}" for i in range(N_USERS)] + ["nobody"]
    answers = model.batch_recommend(users, [5] * len(users))
    assert answers[-1] == []
    worst, checked = 0.0, 0
    for user, answer in zip(users[:-1], answers):
        hist = history(model, user)
        if len(hist) == 0:
            assert answer == []
            continue
        want = reference_scores(model.params, ref_cfg(), hist)[1:]
        got = np.asarray([s for _i, s in answer])
        ids = np.asarray([int(i[1:]) for i, _s in answer])
        assert len(ids) == 5 == len(set(ids.tolist()))
        worst = max(worst, float(np.abs(got - want[ids]).max()
                                 / (want.max() - want.min())))
        # a served item is one of the reference's best, to the tolerance
        kth = np.sort(want)[-5]
        assert want[ids].min() >= kth - limit * (want.max() - want.min())
        checked += 1
    assert checked >= 20 and worst <= limit
    seq = model._serving_pipeline().stats()["sequence"]
    lengths = [len(history(model, u)) for u in users[:-1]]
    need = ref.expected_counts(lengths, ref_cfg())
    # exact counts from the device program, through the encoder seam
    assert seq["routerAssignments"] == need["routerAssignments"]
    assert seq["pairsCausal"] == need["pairsCausal"]
    assert 0 < seq["expertAssignmentsHere"] < seq["routerAssignments"]
    assert (seq["expertAssignmentsFullest"] * CFG.experts_held
            >= seq["expertAssignmentsHere"])
    assert seq["tokensReal"] == sum(lengths)
    assert seq["tokenLattice"] == [256, 384, 512]


def test_a_packed_step_equals_its_histories_one_by_one():
    """One stream of several histories, padding at its end, against each
    history alone in a stream of its own: the same states, bit for
    nearly bit, and counters that add up."""
    model = make_model()
    hists = [history(model, f"u{i}") for i in range(N_USERS)]
    hists = [h for h in hists if len(h)][:5]
    packed, counts = forward(model, hists, 384)
    at, total = 0, np.zeros(4, np.int64)
    for h in hists:
        alone, c = forward(model, [h], 128)
        np.testing.assert_allclose(np.asarray(packed[at:at + len(h)]),
                                   np.asarray(alone[:len(h)]),
                                   rtol=2e-5, atol=2e-6)
        total += np.asarray(c)
        at += len(h)
    # the fullest expert of a step is not the sum of its histories'
    assert np.asarray(counts)[[0, 1, 3]].tolist() == total[[0, 1, 3]].tolist()


def test_more_passes_than_one_drop_no_token():
    """Dropless: with 16 sorted rows a pass, a step's held assignments go
    through in as many passes as they need and give what one pass of
    everything gives."""
    model = make_model()
    hists = [h for h in (history(model, f"u{i}") for i in range(N_USERS))
             if len(h)][:4]
    one, c1 = forward(model, hists, 256, expert_chunk_rows=256 * 4)
    many, c2 = forward(model, hists, 256, expert_chunk_rows=16)
    assert int(c1[1]) > 64  # more than four passes' worth
    np.testing.assert_allclose(np.asarray(one), np.asarray(many),
                               rtol=1e-5, atol=1e-6)
    assert np.asarray(c1).tolist() == np.asarray(c2).tolist()


def test_the_shares_add_up_to_the_uncut_layer():
    """The share test: the routed parts of the 4 shares of 16 experts,
    with the shared expert counted once, add up to the uncut
    reference's layer, for the program and for the reference."""
    cfg = dataclasses.replace(CFG, num_hidden_layers=1, first_layer=1,
                              first_expert=0, experts_held=16)
    whole = lm.init_params(cfg, N_ITEMS + 1, seed=5)
    layer = {k: jnp.asarray(v) for k, v in whole["layers"]["0"].items()}
    rng = np.random.default_rng(5)
    x = jnp.asarray(rng.standard_normal((128, cfg.hidden_size)), jnp.float32)
    with jax.default_matmul_precision("highest"):
        uncut, _load = ref.layer_forward(x, layer, ref_cfg(cfg), 0)
        after_attention = ref.attention(x, layer, ref_cfg(cfg))
        h = ref.rms_norm(after_attention, layer["post_norm"],
                         cfg.rms_norm_eps)
        shared = ref.swiglu(h, layer["shared_gate"], layer["shared_up"],
                            layer["shared_down"])
    routed_ref = jnp.zeros_like(x)
    routed_program = jnp.zeros_like(x)
    here_total = 0
    for first in range(0, 16, 4):
        share = dataclasses.replace(cfg, first_expert=first, experts_held=4)
        w = dict(layer)
        for k in ("experts_gate", "experts_up", "experts_down"):
            w[k] = layer[k][first:first + 4]
        with jax.default_matmul_precision("highest"):
            chosen, weight = ref.route(h, w, ref_cfg(share))
            routed_ref += ref.held_experts_part(h, w, ref_cfg(share), chosen,
                                                weight)
        part, counts = lm._routed_experts(
            h, jnp.ones(128, bool), w, share, jnp.float32, 64)
        routed_program += part
        here_total += int(counts[1])
    want = uncut - after_attention - shared
    np.testing.assert_allclose(np.asarray(routed_ref), np.asarray(want),
                               rtol=1e-4, atol=1e-6)
    np.testing.assert_allclose(np.asarray(routed_program), np.asarray(want),
                               rtol=1e-4, atol=1e-6)
    # every assignment fell on exactly one share
    assert here_total == 128 * cfg.num_experts_per_tok


def test_yarn_beyond_the_original_context():
    """Positions past original_max_position_embeddings (16 here; the
    histories reach 96): the program's tables are the reference's, the
    blend is at work (neither the plain nor the interpolated
    frequencies), and without it the scores differ."""
    got = lm.yarn_inv_freq(8, 10000.0, YARN)
    np.testing.assert_allclose(got, ref.yarn_inv_freq(8, 10000.0, YARN),
                               rtol=1e-6)
    plain = lm.yarn_inv_freq(8, 10000.0, None)
    assert not np.allclose(got, plain) and not np.allclose(got, plain / 32)
    full = lm.yarn_inv_freq(64, 10000.0, dict(YARN, **{
        "original_max_position_embeddings": 4096}))
    plain64 = lm.yarn_inv_freq(64, 10000.0, None)
    # A.X-K1's: the fast dimensions plain, the slow ones over the factor
    assert np.isclose(full[0], plain64[0]) and np.isclose(
        full[-1], plain64[-1] / 32)
    assert np.isclose(lm.LatentMoEConfig(max_len=512).softmax_scale,
                      192 ** -0.5 * (0.1 * np.log(32) + 1) ** 2)
    model = make_model()
    hist = next(h for h in (history(model, f"u{i}") for i in range(N_USERS))
                if len(h) > 64)
    states, _c = forward(model, [hist], 128)
    got_scores = np.asarray(model.params["head"]) @ np.asarray(
        states[len(hist) - 1])
    sound = ref.next_item_scores(model.params, ref_cfg(), hist)
    broken = ref.next_item_scores(model.params, ref_cfg(), hist,
                                  variant="no_yarn")
    assert rel_err(got_scores, sound) < 1e-5
    assert rel_err(broken, sound) > 1e-3


@pytest.mark.parametrize("block", [128, 256])
def test_segment_flash_against_segment_attention_at_192_and_128(block):
    """The new kernel (interpret mode here) at d_qk 128 + 64 with one
    shared rotary key head, d_v 128, packed segments and padding,
    against the plain path; and its count of unmasked pairs."""
    rng = np.random.default_rng(block)
    H, L, lens = 3, 512, [100, 37, 200, 90]
    seg = np.zeros(L, np.int32)
    at = 0
    for j, n in enumerate(lens):
        seg[at:at + n] = j + 1
        at += n
    qn, kn, v = (rng.standard_normal((1, H, L, 128)).astype(np.float32)
                 for _ in range(3))
    qr = rng.standard_normal((1, H, L, 64)).astype(np.float32)
    kr = rng.standard_normal((1, 1, L, 64)).astype(np.float32)
    scale = 0.07
    out, pairs = segment_flash_attention(
        (qn, qr), (kn, kr), v, jnp.asarray(seg)[None], scale=scale,
        block=block)
    q = np.concatenate([qn, qr], -1).transpose(0, 2, 1, 3)
    k = np.concatenate([kn, np.broadcast_to(kr, (1, H, L, 64))], -1
                       ).transpose(0, 2, 1, 3)
    want = segment_attention(
        jnp.asarray(q * scale * np.sqrt(192.0)), jnp.asarray(k),
        jnp.asarray(v.transpose(0, 2, 1, 3)), jnp.asarray(seg)[None],
        causal=True)
    np.testing.assert_allclose(np.asarray(out).transpose(0, 2, 1, 3),
                               np.asarray(want), rtol=1e-4, atol=1e-5)
    pad = L - sum(lens)
    assert int(pairs) == sum(n * (n + 1) // 2 for n in lens) + pad * (
        pad + 1) // 2


@pytest.mark.parametrize("L,d_qk,d_v,backend,segmented,want", [
    (1024, 128, 128, "tpu", True, "stock"),
    (1024, 64, 64, "tpu", False, "stock"),
    (8192, 192, 128, "tpu", True, "segment_flash"),
    (1024, 96, 96, "tpu", False, "segment_flash"),
    (1000, 192, 128, "tpu", True, "plain"),
    (1000, 96, 96, "tpu", False, "plain"),
    (8192, 192, 128, "cpu", True, "plain"),
    (8200, 192, 128, "tpu", True, None),
    (PLAIN_MAX_L + 8, 128, 128, "tpu", False, None),
])
def test_the_choice_of_attention_kernel_is_explicit(L, d_qk, d_v, backend,
                                                    segmented, want):
    """No shape falls silently to the path that builds [B, H, L, L]: on
    the TPU a head size the stock kernel does not take runs the new
    kernel, an unaligned stream the plain path only up to PLAIN_MAX_L,
    and anything else raises."""
    if want is None:
        with pytest.raises(ValueError, match="no attention kernel"):
            attention_kernel_for(L, d_qk, d_v, backend=backend,
                                 segmented=segmented)
    else:
        assert attention_kernel_for(L, d_qk, d_v, backend=backend,
                                    segmented=segmented) == want


def test_flash_attention_takes_unequal_head_sizes_off_the_tpu():
    rng = np.random.default_rng(2)
    q, k = (jnp.asarray(rng.standard_normal((1, 64, 2, 24)), jnp.float32)
            for _ in range(2))
    v = jnp.asarray(rng.standard_normal((1, 64, 2, 16)), jnp.float32)
    out = flash_attention(q, k, v, causal=True)
    assert out.shape == (1, 64, 2, 16)
    want = segment_attention(q, k, v, jnp.ones((1, 64), jnp.int32),
                             causal=True)
    np.testing.assert_allclose(np.asarray(out), np.asarray(want), rtol=1e-6)


def test_config_says_what_it_cannot_hold(monkeypatch):
    monkeypatch.setattr(lm, "STEP_TOKEN_BUDGET", 8192)
    with pytest.raises(ValueError, match="not a block"):
        dataclasses.replace(CFG, first_expert=14, experts_held=4)
    with pytest.raises(ValueError, match="exclude_seen"):
        dataclasses.replace(CFG, exclude_seen=True, max_len=513)
    with pytest.raises(ValueError, match="group limit"):
        dataclasses.replace(CFG, topk_method="noaux_tc")
    with pytest.raises(ValueError, match="over a serving step"):
        dataclasses.replace(CFG, max_len=8193)
    assert CFG.is_dense(0) and not CFG.is_dense(1)
    assert not dataclasses.replace(CFG, first_layer=1).is_dense(0)


def test_device_layout_is_the_public_tree_turned():
    """`device_layer` splits and turns the attention's up-projections
    head-major; the contraction it feeds gives what the public matrices
    give."""
    params = lm.init_params(CFG, N_ITEMS + 1, seed=2)
    layer = params["layers"]["1"]
    dev = lm.device_layer(layer, CFG)
    H, dn, dr, dv = 4, 16, 8, 16
    assert dev["wq_nope"].shape == (H, dn, CFG.q_lora_rank)
    assert dev["wq_rope"].shape == (H, dr, CFG.q_lora_rank)
    assert dev["wk_nope"].shape == dev["wv"].shape == (H, dn, CFG.kv_lora_rank)
    assert dev["wo"].shape == (H, dv, CFG.hidden_size)
    assert "wq_b" not in dev and "wkv_b" not in dev
    c = np.random.default_rng(0).standard_normal(
        (5, CFG.q_lora_rank)).astype(np.float32)
    public = (c @ layer["wq_b"]).reshape(5, H, dn + dr)
    np.testing.assert_allclose(
        np.einsum("tc,hkc->htk", c, dev["wq_nope"]),
        public[..., :dn].transpose(1, 0, 2), rtol=1e-5, atol=1e-6)
    np.testing.assert_allclose(
        np.einsum("tc,hkc->htk", c, dev["wq_rope"]),
        public[..., dn:].transpose(1, 0, 2), rtol=1e-5, atol=1e-6)


def test_exclude_seen_within_the_heads_reach():
    cfg = dataclasses.replace(CFG, exclude_seen=True)
    model = make_model(cfg)
    user = next(f"u{i}" for i in range(N_USERS)
                if len(history(model, f"u{i}")) > 10)
    answer = model.recommend_products(user, 5)
    seen = {f"i{int(t) - 1}" for t in history(model, user)}
    assert len(answer) == 5 and not seen & {i for i, _s in answer}
    assert model.serving_ks != (16,)
    assert make_model().serving_ks == (16,)


def test_pio_train_then_deploy_of_latent_moe_answers_through_the_batcher(
        tmp_path, rng):
    """`pio train` -> the deploy-time attach (retriever, encoder,
    pipeline, prewarm over the token lattice) -> queries through the
    micro-batcher, cut by tokens: /stats.json shows the pipeline, the
    kernel and the `sequence` counters the device program counted. No
    sleep; the asks share one timeout."""
    from predictionio_tpu.storage import Storage
    from predictionio_tpu.tools.cli import main as pio
    from predictionio_tpu.workflow import resolve_engine_factory
    from predictionio_tpu.workflow.create_server import EngineServer
    from tests.test_quickstart_e2e import make_events_file

    engine_dir = tmp_path / "myseq"
    shutil.copytree(REPO / "templates" / "seqrec", engine_dir)
    variant = json.loads((engine_dir / "engine.json").read_text())
    variant["datasource"]["params"]["app_name"] = "latenttest"
    held = {k: getattr(CFG, k) for k in (
        "hidden_size", "intermediate_size", "moe_intermediate_size",
        "num_attention_heads", "q_lora_rank", "kv_lora_rank",
        "qk_nope_head_dim", "qk_rope_head_dim", "v_head_dim",
        "n_routed_experts", "num_experts_per_tok", "num_hidden_layers",
        "first_expert", "experts_held", "rope_scaling")}
    variant["algorithms"] = [{"name": "latent_moe", "params": {
        **held, "max_len": 16, "compute_dtype": "float32", "epochs": 1,
        "batch_size": 16}}]
    (engine_dir / "engine.json").write_text(json.dumps(variant))
    assert pio(["app", "new", "latenttest"]) == 0
    app = Storage.get_metadata().app_get_by_name("latenttest")
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
    assert type(model).__name__ == "LatentMoEModel"
    assert model.params["layers"]["1"]["experts_gate"].shape[0] == 4
    user = next(u for u in model.user_ids if len(history(model, u)) > 1)
    cost_of, budget = server._costing()
    assert budget == model.serving_cost_budget == 512
    assert cost_of({"user": user, "num": 4}) == len(history(model, user))
    assert cost_of({"user": "nobody"}) == 0

    async def ask():
        try:
            return await asyncio.wait_for(asyncio.gather(*[
                server.batcher.submit({"user": user, "num": 4})
                for _ in range(3)]), timeout=120)
        finally:
            await server.batcher.close()

    answers = asyncio.run(ask())
    assert all(len(a["itemScores"]) == 4 for a in answers)
    want = ref.next_item_scores(model.params, {
        **ref_cfg(), "first_layer": 0}, history(model, user))[1:]
    for s in answers[0]["itemScores"]:
        assert abs(s["score"] - want[model.item_ids.get(s["item"])]) <= (
            1e-4 * (want.max() - want.min()))
    stats = server.serving_stats()
    assert stats["pipeline"]["mode"] == "fused"
    seq = stats["sequence"]
    n = len(history(model, user))
    assert seq["steps"] >= 1 and seq["tokenBudget"] == 512
    assert seq["routerAssignments"] == 3 * n * 2 * CFG.num_experts_per_tok
    assert seq["pairsCausal"] == 3 * 3 * n * (n + 1) // 2
    assert stats["batching"]["batchedQueries"] == 3
    phases = [name for name, *_ in stats["startup"]["phases"]]
    assert "pio.deploy.attach_encoder" in phases
    assert "pio.deploy.prewarm" in phases


def test_device_scopes_map_operations_to_their_innermost_scope(tmp_path):
    """obs/trace.DeviceScopes: the compiled text's `%name = type opcode`
    -> the innermost `pio.*` scope; containers left out; the map written
    beside a capture; the key of a profiler event's name (operands
    typed) is the key of the program's line (operands bare)."""
    from predictionio_tpu.obs.trace import DeviceScopes, operation_key

    hlo = """
  %fusion.7 = bf16[64,8192,128]{2,1,0:T(8,128)(2,1)} fusion(%p.1, %p.2), kind=kOutput, calls=%fused.1, metadata={op_name="jit(fn)/pio.seq.latent_proj/dot_general" stack_frame_id=4}
  %gmm.2 = f32[8192,2048]{1,0:T(8,128)} custom-call(%a, %b), custom_call_target="tpu_custom_call", metadata={op_name="jit(fn)/pio.seq.experts/while/body/pio.seq.experts.matmul/gmm" stack_frame_id=9}
  %while.3 = (s32[]{:T(128)}, f32[8192,7168]{1,0:T(8,128)}) while(%tuple.1), condition=%c, body=%b, metadata={op_name="jit(fn)/pio.seq.experts/while" stack_frame_id=9}
  %copy.4 = f32[8]{0} copy(%x), metadata={op_name="jit(fn)/mul"}
"""
    scopes = DeviceScopes()
    assert scopes.record(hlo) == 2
    got = scopes.snapshot()
    assert got == {
        "%fusion.7 = bf16[64,8192,128]{2,1,0:T(8,128)(2,1)} fusion":
        "pio.seq.latent_proj",
        "%gmm.2 = f32[8192,2048]{1,0:T(8,128)} custom-call":
        "pio.seq.experts.matmul"}
    event = ("%gmm.2 = f32[8192,2048]{1,0:T(8,128)} custom-call(s32[]{:T(128)} "
             "%a, bf16[8192,7168]{1,0:T(8,128)(2,1)} %b), custom_call_target=")
    assert operation_key(event) in got
    scopes.dump(str(tmp_path / "trace"))
    assert json.loads((tmp_path / "trace" / "pio_scopes.json").read_text()
                      ) == got
    DeviceScopes().dump(str(tmp_path / "none"))   # nothing recorded: no file
    assert not (tmp_path / "none").exists()


def test_the_encoder_seam_still_takes_three_values(monkeypatch):
    """A program without counters (the looped decoder's, the attention
    recommender's) hands out (states, aux, passes) and the seam reads it
    as before."""
    from predictionio_tpu.ops.pipeline import _encoder_fn

    def program(stream, params):
        del params
        return (jnp.ones((stream.shape[1], 4)), stream[0], jnp.int32(3))

    table, aux = _encoder_fn(program, 16, 8)(
        jnp.arange(24, dtype=jnp.int32).reshape(3, 8), {})
    assert table.shape == (16, 8) and float(table[:8, :4].min()) == 1.0
    assert np.asarray(aux).tolist() == list(range(8)) + [3]

    def counted(stream, params):
        return (*program(stream, params)[:2], None,
                jnp.asarray([5, 6], jnp.int32))

    _t, aux = _encoder_fn(counted, 16, 8)(
        jnp.arange(24, dtype=jnp.int32).reshape(3, 8), {})
    assert np.asarray(aux).tolist() == list(range(8)) + [0, 5, 6]
