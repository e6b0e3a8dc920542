"""The ``pio`` console: command-line surface of the framework.

Analog of reference ``Console`` (tools/src/main/scala/io/prediction/tools/
console/Console.scala:128-1245). Same verb set, no JVM/spark-submit spawning
— train/eval/deploy run in-process (the compiled XLA programs are the
"cluster"):

  pio app new|list|show|delete|data-delete|channel-new|channel-delete
  pio accesskey new|list|delete
  pio build | unregister
  pio train [--engine-json engine.json] [...]
  pio eval <Evaluation> [<EngineParamsGenerator>]
  pio deploy [--port 8000] [--feedback] [--event-server-url ...]
  pio batchpredict --input queries.jsonl --output predictions.jsonl
  pio bench backup [--files N] [--size-kb N] [--rounds N] [--json]
  pio undeploy [--port 8000]
  pio eventserver [--port 7070] [--stats] [--journal-dir D]
                  [--journal-fsync always|batch|never] [--journal-max-mb N]
                  [--journal-partitions N]
  pio adminserver [--port 7071]
  pio dashboard [--port 9000]
  pio import|export [events] --appid N --input|--output FILE
  pio template list|get
  pio status | version
  pio backup [--backup-dir D] [--keep N] [--full]
  pio restore [--backup-dir D] [--backup-id N] [--force] [--until TS|SEQ]
  pio admin reap [--stale-after-s N] [--dry-run]
  pio admin metrics [--json] [--url U]
  pio trace RID [--router-url U | --url U] [--wal-dir D]
  pio admin fsck [--repair] [--json]
  pio admin gc --blobs [--dry-run]
  pio capture start|stop [--url U] | export DIR --output F
  pio replay CAPTURE_DIR [--target URL | --engine-instance-id ID]

Engine directory convention (replacing the reference's sbt build + jar
manifest): an engine dir holds ``engine.json`` whose ``engineFactory``
names a Python attribute importable with the engine dir on sys.path.
"""

from __future__ import annotations

import argparse
import json
import logging
import os
import sys
import time
from pathlib import Path

from .. import __version__

log = logging.getLogger("predictionio_tpu.cli")


# ---------------------------------------------------------------------------
# helpers
# ---------------------------------------------------------------------------

def _storage():
    from ..storage import Storage

    return Storage


def _load_variant(engine_dir: Path, engine_json: str) -> dict:
    path = engine_dir / engine_json
    if not path.exists():
        _die(f"{path} not found. Run from an engine directory (or --engine-dir).")
    with open(path) as f:
        return json.load(f)


def _engine_from_variant(engine_dir: Path, variant: dict):
    from ..workflow import resolve_engine_factory

    factory = variant.get("engineFactory")
    if not factory:
        _die("engine.json has no engineFactory field")
    # dir-scoped import: each engine's `engine` module gets a unique
    # module name, so training/deploying several engines in one process
    # never cross-wires their code (workflow/core_workflow.py)
    return resolve_engine_factory(factory, engine_dir=engine_dir)


def _verify_template_min_version(engine_dir: Path) -> None:
    """Warn when the engine's ``template.json`` declares a minimum
    framework version newer than this one (reference
    Template.verifyTemplateMinVersion, console/Template.scala:417-425,
    called by train/deploy, Console.scala:808,831). template.json shape:
    ``{"pio": {"version": {"min": "X.Y.Z"}}}``. Missing or unparseable
    metadata is not an error — in-repo templates rarely carry it, but
    ``pio template get`` copies engines out where they can drift."""
    path = engine_dir / "template.json"
    if not path.exists():
        return
    try:
        with open(path) as f:
            meta = json.load(f)
        min_v = meta["pio"]["version"]["min"]
    except (json.JSONDecodeError, KeyError, TypeError, OSError,
            UnicodeDecodeError):
        print(f"[WARN] {path} cannot be parsed. Template metadata will "
              f"not be available.", file=sys.stderr)
        return

    def parse_v(s):
        """Leading numeric segments of a version string ("v2.1-rc" ->
        [2, 1]); [] when nothing numeric leads."""
        parts = []
        for seg in str(s).strip().lstrip("vV").split("."):
            digits = ""
            for ch in seg:
                if not ch.isdigit():
                    break
                digits += ch
            if not digits:
                break
            parts.append(int(digits))
        return parts

    cur, need = parse_v(__version__), parse_v(min_v)
    if not need:
        print(f"[WARN] {path} declares an unparseable minimum version "
              f"{min_v!r}; skipping the version check.", file=sys.stderr)
        return
    width = max(len(cur), len(need))
    pad = lambda p: p + [0] * (width - len(p))  # noqa: E731
    if pad(cur) < pad(need):
        print(f"[WARN] This engine template requires at least "
              f"predictionio_tpu {min_v}. The template may not work with "
              f"predictionio_tpu {__version__}.", file=sys.stderr)


def _engine_ids(engine_dir: Path, variant: dict) -> tuple[str, str, str]:
    engine_id = variant.get("id") or engine_dir.resolve().name
    version = str(variant.get("version", "1"))
    # ISSUE 14: the variant id is its OWN field — it used to read
    # variant.get("id"), which made the variant id track the engine id
    # and two variants of one engine indistinguishable in metadata
    variant_id = str(variant.get("variantId", "default"))
    return engine_id, version, variant_id


def _die(msg: str, code: int = 1):
    print(f"[ERROR] {msg}", file=sys.stderr)
    raise SystemExit(code)


def _ok(msg: str):
    print(msg)


# ---------------------------------------------------------------------------
# app / accesskey (console/App.scala:1-499, AccessKey.scala)
# ---------------------------------------------------------------------------

def cmd_app(args) -> int:
    meta = _storage().get_metadata()
    events = _storage().get_events()
    sub = args.app_command
    if sub == "new":
        app = meta.app_insert(args.name, args.description)
        if app is None:
            _die(f"App {args.name!r} already exists.")
        events.init_app(app.id)
        ak = meta.access_key_insert(app.id, key=args.access_key)
        if ak is None:
            _die(f"Access key already exists.")
        _ok(f"App created: id={app.id} name={app.name}")
        _ok(f"Access key: {ak.key}")
    elif sub == "list":
        for app in meta.app_get_all():
            keys = meta.access_key_get_by_appid(app.id)
            _ok(f"  id={app.id:4d}  name={app.name}  accessKeys={len(keys)}")
    elif sub == "show":
        app = meta.app_get_by_name(args.name)
        if app is None:
            _die(f"App {args.name!r} not found.")
        _ok(f"App: id={app.id} name={app.name} description={app.description}")
        for ak in meta.access_key_get_by_appid(app.id):
            _ok(f"  access key: {ak.key} (events: {list(ak.events) or 'all'})")
        for ch in meta.channel_get_by_appid(app.id):
            _ok(f"  channel: id={ch.id} name={ch.name}")
    elif sub == "delete":
        app = meta.app_get_by_name(args.name)
        if app is None:
            _die(f"App {args.name!r} not found.")
        for ch in meta.channel_get_by_appid(app.id):
            events.remove_app(app.id, ch.id)
            meta.channel_delete(ch.id)
        for ak in meta.access_key_get_by_appid(app.id):
            meta.access_key_delete(ak.key)
        events.remove_app(app.id)
        meta.app_delete(app.id)
        _ok(f"App {args.name!r} deleted.")
    elif sub == "data-delete":
        app = meta.app_get_by_name(args.name)
        if app is None:
            _die(f"App {args.name!r} not found.")
        channel_id = None
        if args.channel:
            chans = {c.name: c for c in meta.channel_get_by_appid(app.id)}
            if args.channel not in chans:
                _die(f"Channel {args.channel!r} not found.")
            channel_id = chans[args.channel].id
        if args.before is not None:
            from ..storage.event import _dt_from_wire
            from ..storage.events_base import StorageError

            try:
                cutoff = _dt_from_wire(args.before)
            except Exception:
                _die(f"--before: not an ISO-8601 instant: {args.before!r}")
            try:
                n = events.remove_before(app.id, cutoff, channel_id)
            except StorageError as e:
                _die(str(e))
            _ok(f"Trimmed {n} event(s) of app {args.name!r} before "
                f"{cutoff.isoformat()}.")
        else:
            events.remove_app(app.id, channel_id)
            events.init_app(app.id, channel_id)
            _ok(f"Data of app {args.name!r} deleted.")
    elif sub == "channel-new":
        app = meta.app_get_by_name(args.name)
        if app is None:
            _die(f"App {args.name!r} not found.")
        ch = meta.channel_insert(app.id, args.channel)
        if ch is None:
            _die(f"Invalid or duplicate channel name {args.channel!r} "
                 "(must match [a-zA-Z0-9-]{1,16}).")
        events.init_app(app.id, ch.id)
        _ok(f"Channel created: id={ch.id} name={ch.name}")
    elif sub == "channel-delete":
        app = meta.app_get_by_name(args.name)
        if app is None:
            _die(f"App {args.name!r} not found.")
        chans = {c.name: c for c in meta.channel_get_by_appid(app.id)}
        if args.channel not in chans:
            _die(f"Channel {args.channel!r} not found.")
        ch = chans[args.channel]
        events.remove_app(app.id, ch.id)
        meta.channel_delete(ch.id)
        _ok(f"Channel {args.channel!r} deleted.")
    return 0


def cmd_accesskey(args) -> int:
    meta = _storage().get_metadata()
    sub = args.ak_command
    if sub == "new":
        app = meta.app_get_by_name(args.app_name)
        if app is None:
            _die(f"App {args.app_name!r} not found.")
        ak = meta.access_key_insert(app.id, events=tuple(args.event or ()))
        _ok(f"Access key: {ak.key}")
    elif sub == "list":
        keys = meta.access_key_get_all()
        if args.app_name:
            app = meta.app_get_by_name(args.app_name)
            if app is None:
                _die(f"App {args.app_name!r} not found.")
            keys = [k for k in keys if k.appid == app.id]
        for k in keys:
            _ok(f"  {k.key}  appid={k.appid}  events={list(k.events) or 'all'}")
    elif sub == "delete":
        if meta.access_key_delete(args.key):
            _ok("Access key deleted.")
        else:
            _die("Access key not found.")
    return 0


# ---------------------------------------------------------------------------
# build / train / eval / deploy (Console.scala:772-869)
# ---------------------------------------------------------------------------

def cmd_build(args) -> int:
    """Register the engine manifest (no compilation needed — the 'build'
    is XLA tracing at train time). Reference: build = sbt package +
    RegisterEngine (Console.scala:772-805)."""
    from ..storage import EngineManifest

    engine_dir = Path(args.engine_dir)
    variant = _load_variant(engine_dir, args.engine_json)
    _engine_from_variant(engine_dir, variant)  # import check = the "build"
    engine_id, version, _ = _engine_ids(engine_dir, variant)
    manifest = EngineManifest(
        id=engine_id,
        version=version,
        name=engine_dir.resolve().name,
        description=variant.get("description"),
        files=(str(engine_dir.resolve()),),
        engine_factory=variant.get("engineFactory", ""),
    )
    _storage().get_metadata().engine_manifest_insert(manifest)
    _ok(f"Engine {engine_id}:{version} registered (factory import OK).")
    return 0


def cmd_unregister(args) -> int:
    engine_dir = Path(args.engine_dir)
    variant = _load_variant(engine_dir, args.engine_json)
    engine_id, version, _ = _engine_ids(engine_dir, variant)
    if _storage().get_metadata().engine_manifest_delete(engine_id, version):
        _ok(f"Engine {engine_id}:{version} unregistered.")
    else:
        _die("Engine manifest not found.")
    return 0


def compile_cache_dir() -> str:
    """Where XLA's persistent compilation cache lives:
    ``JAX_COMPILATION_CACHE_DIR`` when the operator set it, else
    ``<checkout>/.xla_cache``. The directory is part of the cache key's
    lookup, so it is computed from the package's location only — never
    from ``PIO_HOME``, the cwd, a pid or the time — and every process of
    a train -> deploy sequence finds what the previous one compiled."""
    return os.environ.get("JAX_COMPILATION_CACHE_DIR") or str(
        Path(__file__).resolve().parents[2] / ".xla_cache")


def _enable_compile_cache() -> None:
    """Turn on the persistent compilation cache for a device-holding
    verb. With ``JAX_COMPILATION_CACHE_DIR`` set JAX has already taken
    the directory from the environment and none is set in code."""
    import jax

    if not os.environ.get("JAX_COMPILATION_CACHE_DIR"):
        jax.config.update("jax_compilation_cache_dir", compile_cache_dir())
    # JAX's default persists only compiles of 1 s or more. On the v5e
    # the ten programs `pio deploy` prewarms build in 0.24-0.65 s each
    # (4.5 s together), so at the default none of them would be kept;
    # kept, they read back in 2.1 s together, and the ALS step (10 s of
    # a 13 s first step cold, 2.9 s warm) is kept at any threshold
    # (chip_smoke.py runs, PR 21; PERF.md). Everything is kept; the cost
    # is one small file per eager init op.
    jax.config.update("jax_persistent_cache_min_compile_time_secs", 0.0)


def _first_look_at_devices() -> None:
    """Where `pio train` and `pio deploy` first ask JAX for their
    devices, so that the seconds a process needs from its start to the
    chip (12-21 s on the v5e) are a number of the program's own:
    ``pio.process.to_device``."""
    from ..obs.startup import STARTUP

    seconds = STARTUP.process_to_device()
    log.info("devices after %.3f s of process", seconds)


def cmd_train(args) -> int:
    from ..workflow import Context, WorkflowParams, run_train

    _enable_compile_cache()
    # elastic multi-host bring-up BEFORE any jax device use; partial
    # config (coordinator without topology) fails loud in init_distributed
    num_processes = args.num_processes if args.num_processes is not None else 1
    process_id = args.process_id if args.process_id is not None else 0
    if (args.coordinator or args.num_processes is not None
            or args.process_id is not None):
        from ..parallel.mesh import init_distributed

        init_distributed(
            coordinator_address=args.coordinator,
            num_processes=args.num_processes,
            process_id=args.process_id,
        )
    _first_look_at_devices()
    engine_dir = Path(args.engine_dir)
    _verify_template_min_version(engine_dir)
    variant = _load_variant(engine_dir, args.engine_json)
    engine = _engine_from_variant(engine_dir, variant)
    engine_id, version, variant_id = _engine_ids(engine_dir, variant)
    engine_params = engine.engine_params_from_json(variant)
    ctx = Context(
        mode="Train",
        batch=args.batch,
        workflow_params=WorkflowParams(
            batch=args.batch,
            skip_sanity_check=args.skip_sanity_check,
            stop_after_read=args.stop_after_read,
            stop_after_prepare=args.stop_after_prepare,
        ),
        mesh_shape=_parse_mesh(args.mesh) if args.mesh else None,
        mesh_axes=("data", "model") if args.mesh else None,
        checkpoint_dir=args.checkpoint_dir,
        checkpoint_every=args.checkpoint_every,
        profile_dir=args.profile_dir,
        process_id=process_id,
        num_processes=num_processes,
    )
    iid = run_train(
        engine,
        engine_params,
        ctx,
        engine_id=engine_id,
        engine_version=version,
        engine_variant=variant_id,
        engine_factory=variant.get("engineFactory", ""),
        batch=args.batch,
        max_retries=args.max_retries,
        retry_backoff_s=args.retry_backoff_s,
        train_budget_s=args.train_budget_s or None,
        process_id=process_id,
        num_processes=num_processes,
    )
    _ok(f"Training completed. Engine instance: {iid}")
    return 0


def _parse_mesh(spec: str) -> tuple[int, ...]:
    return tuple(int(x) for x in spec.split("x"))


def cmd_eval(args) -> int:
    _enable_compile_cache()
    from ..workflow import Context, run_evaluation

    engine_dir = Path(args.engine_dir)
    evaluation, grid = _resolve_eval_grid(args, engine_dir)
    if args.fast:
        # rebuild the evaluation's engine as a FastEvalEngine: identical
        # components, but pipeline prefixes (datasource folds, prepared
        # data, trained models) memoize across grid variants — the
        # reference requires subclassing FastEvalEngine in code
        # (FastEvalEngine.scala:297); here it is one flag. Custom Engine
        # subclasses opt in with `fast_eval_compatible = True` (their
        # resolution hooks stay live; see FastEvalEngine.wrap).
        from ..controller.fast_eval import FastEvalEngine

        try:
            evaluation.engine = FastEvalEngine.wrap(evaluation.engine)
        except ValueError as e:
            _die(str(e))
    if not grid:
        _die("no EngineParams to evaluate (give an EngineParamsGenerator)")
    iid, result = run_evaluation(
        evaluation,
        grid,
        Context(mode="Evaluation", batch=args.batch),
        evaluation_class=args.evaluation,
        generator_class=args.engine_params_generator or "",
        batch=args.batch,
        best_json_path=str(engine_dir / "best.json"),
    )
    _ok(result.pretty_print())
    if args.fast:
        hits = dict(evaluation.engine.hit_counts)
        _ok(f"FastEval prefix cache hits: {hits or 'none'}")
    _ok(f"Evaluation completed. Instance: {iid}; best params -> best.json")
    return 0


def _resolve_eval_grid(args, engine_dir):
    """Shared eval/tune preamble: resolve the Evaluation (engine +
    metrics) and the EngineParams grid (an explicit generator wins over
    the evaluation's own list)."""
    from ..workflow import resolve_attr

    ev_obj = resolve_attr(args.evaluation, engine_dir=engine_dir)
    evaluation = ev_obj() if isinstance(ev_obj, type) else ev_obj
    if args.engine_params_generator:
        gen_obj = resolve_attr(args.engine_params_generator,
                               engine_dir=engine_dir)
        generator = gen_obj() if isinstance(gen_obj, type) else gen_obj
        grid = list(generator.engine_params_list)
    else:
        grid = list(getattr(evaluation, "engine_params_list", ()))
    return evaluation, grid


def cmd_tune(args) -> int:
    """`pio tune` (ISSUE 15): train the WHOLE EngineParams grid as one
    mesh-packed program (models/als.train_als_grid: per-rank vmapped
    λ/α lanes, one compiled dispatch per iteration), rank the trials,
    train the winner on the full data, stamp the leaderboard onto its
    EngineInstance, and — with --deploy — serve it behind the eval
    gate. Where `pio eval` only REPORTS the best params, tune closes
    the loop through deployment."""
    _enable_compile_cache()
    from ..workflow import Context, run_tune

    engine_dir = Path(args.engine_dir)
    evaluation, grid = _resolve_eval_grid(args, engine_dir)
    if not grid:
        _die("no EngineParams to tune (give an EngineParamsGenerator)")
    metrics = evaluation.all_metrics
    variant = _load_variant(engine_dir, args.engine_json)
    engine_id, version, variant_id = _engine_ids(engine_dir, variant)
    iid, tune, gate = run_tune(
        evaluation.engine,
        grid,
        metrics[0],
        metrics[1:],
        Context(mode="Evaluation", batch=args.batch),
        engine_id=engine_id,
        engine_version=version,
        engine_variant=variant_id,
        engine_factory=variant.get("engineFactory", ""),
        batch=args.batch,
        evaluator_class=args.evaluation,
        max_retries=args.max_retries,
        retry_backoff_s=args.retry_backoff_s,
        eval_gate=args.eval_gate,
        best_json_path=str(engine_dir / "best.json"),
        train_max_retries=args.train_max_retries,
        train_budget_s=args.train_budget_s or None,
    )
    _ok(tune.pretty_print())
    _ok(f"packed grid: {tune.grid_mode} "
        f"({len(tune.trials)} trial(s), {tune.grid_seconds:.2f}s)")
    _ok(f"Winner trial #{tune.winner.index} trained as instance {iid}; "
        "best params -> best.json")
    _ok(f"gate: {gate['decision']} (candidate={gate['candidate']}, "
        f"baseline={gate['baseline']}, threshold={gate['threshold']})")
    if not args.deploy:
        return 0
    if gate["decision"] == "hold":
        _ok("eval gate HELD deployment — the incumbent keeps serving. "
            "Deploy anyway with `pio deploy --engine-instance-id "
            f"{iid}`.")
        return 2
    from ..workflow.create_server import run_engine_server

    inst = _storage().get_metadata().engine_instance_get(iid)
    engine = _engine_from_variant(engine_dir, variant)
    run_engine_server(
        engine, inst,
        # the gate already vouched for THIS instance; never fall back
        # to an older one
        fallback=False,
        ip=args.ip, port=args.port, engine_dir=engine_dir)
    return 0


def _resolve_engine_instance(args):
    """Shared deploy/batchpredict preamble: engine dir checks, variant
    load, factory import, instance lookup. Returns (engine_dir, engine,
    instance); dies with a diagnostic when nothing deployable exists."""
    engine_dir = Path(args.engine_dir)
    _verify_template_min_version(engine_dir)
    variant = _load_variant(engine_dir, args.engine_json)
    engine = _engine_from_variant(engine_dir, variant)
    engine_id, version, variant_id = _engine_ids(engine_dir, variant)
    meta = _storage().get_metadata()
    if args.engine_instance_id:
        inst = meta.engine_instance_get(args.engine_instance_id)
        if inst is None:
            _die(f"Engine instance {args.engine_instance_id!r} not found.")
    else:
        inst = meta.engine_instance_get_latest_completed(
            engine_id, version, variant_id)
        if inst is None:
            _die(f"No COMPLETED training of engine {engine_id} found. "
                 "Run `pio train` first.")
    return engine_dir, engine, inst


def _retrieval_params(engine_dir: Path, args) -> dict | None:
    """The engine-params ``retrieval: {mode: exact|ann, nprobe,
    quantize, ...}`` block from engine.json (ISSUE 7), with
    ``--retrieval-mode`` overriding the mode from the command line.
    None when neither says anything (exact serving, zero new cost)."""
    block = _load_variant(engine_dir, args.engine_json).get("retrieval")
    block = dict(block) if isinstance(block, dict) else {}
    if getattr(args, "retrieval_mode", None):
        block["mode"] = args.retrieval_mode
    return block or None


def _deploy_variant(args) -> int:
    """``pio deploy --variant-of <port>`` (ISSUE 14): instead of binding
    a new server, register this engine as another serving variant of the
    engine server already running on that port. The bundle must live in
    THAT process, so the CLI only posts the recipe (engine dir + variant
    json + optional pinned instance) and the server deploys it."""
    import urllib.error
    import urllib.request

    engine_dir = Path(args.engine_dir)
    _verify_template_min_version(engine_dir)
    variant = _load_variant(engine_dir, args.engine_json)
    vid = args.variant_id or str(
        variant.get("variantId") or engine_dir.resolve().name)
    body = {
        "variantId": vid,
        "weight": args.weight,
        "engineDir": str(engine_dir.resolve()),
        "engineJson": args.engine_json,
        "batchWindowMs": args.batch_window_ms,
        "batchMax": args.batch_max,
        "batchInflight": args.batch_inflight,
        "deadlineMs": args.deadline_ms,
        "admission": args.admission,
        "admissionQueueHigh": args.admission_queue_high,
        "admissionWaitBudgetMs": args.admission_wait_budget_ms,
        "rateLimitQps": args.rate_limit_qps,
        "rateLimitBurst": args.rate_limit_burst,
        "brownoutTopk": args.brownout_topk,
        "sloLatencyMs": args.slo_latency_ms,
    }
    if args.engine_instance_id:
        body["engineInstanceId"] = args.engine_instance_id
    retrieval = _retrieval_params(engine_dir, args)
    if retrieval:
        body["retrieval"] = retrieval
    url = f"http://{args.ip if args.ip != '0.0.0.0' else '127.0.0.1'}" \
          f":{args.variant_of}/variants"
    req = urllib.request.Request(
        url, data=json.dumps(body).encode(), method="POST",
        headers={"Content-Type": "application/json"})
    try:
        with urllib.request.urlopen(req, timeout=300) as r:
            out = json.loads(r.read().decode())
    except urllib.error.HTTPError as e:
        try:
            msg = json.loads(e.read().decode()).get("message", str(e))
        except Exception:  # noqa: BLE001
            msg = str(e)
        _die(f"variant registration failed ({e.code}): {msg}")
    except OSError as e:
        _die(f"no engine server answering at {url}: {e}")
    _ok(f"Registered variant {out.get('variantId')!r} "
        f"(instance {out.get('engineInstanceId')}, "
        f"state {out.get('state')}, weight {out.get('weight')}) "
        f"on port {args.variant_of}")
    _ok(f"  promote with: pio variant promote {out.get('variantId')} "
        f"--url http://127.0.0.1:{args.variant_of}")
    return 0


def cmd_deploy(args) -> int:
    if args.variant_of:
        return _deploy_variant(args)
    _enable_compile_cache()
    _first_look_at_devices()
    from ..obs.startup import STARTUP
    from ..obs.trace import span
    from ..workflow.create_server import run_engine_server

    with span("deploy.resolve_engine", sink=STARTUP.phase):
        engine_dir, engine, inst = _resolve_engine_instance(args)
    run_engine_server(
        engine,
        inst,
        # a pinned --engine-instance-id must fail loud; the default
        # latest-COMPLETED pick may fall back past a corrupt blob
        fallback=not args.engine_instance_id,
        ip=args.ip,
        port=args.port,
        feedback_url=args.event_server_url if args.feedback else None,
        access_key=args.accesskey,
        batch_window_ms=args.batch_window_ms,
        batch_max=args.batch_max,
        batch_inflight=args.batch_inflight,
        deadline_ms=args.deadline_ms,
        dispatch_timeout_s=args.dispatch_timeout_s,
        degraded_cooldown_s=args.degraded_cooldown_s,
        admission=args.admission,
        admission_queue_high=args.admission_queue_high,
        admission_wait_budget_ms=args.admission_wait_budget_ms,
        rate_limit_qps=args.rate_limit_qps,
        rate_limit_burst=args.rate_limit_burst,
        brownout_topk=args.brownout_topk,
        engine_dir=engine_dir,
        retriever_mesh=_retriever_mesh(args.retriever_mesh),
        retrieval=_retrieval_params(engine_dir, args),
        slo_latency_ms=args.slo_latency_ms,
        flight_capacity=args.flight_capacity,
        flight_dump_dir=args.flight_dir,
        capture_dir=args.capture_dir,
        capture_sample=args.capture_sample,
        capture_ring=args.capture_ring,
        capture_max_mb=args.capture_max_mb,
        shadow_target=args.shadow_target,
        shadow_sample=args.shadow_sample,
        prewarm_async=args.prewarm_async,
    )
    return 0


def cmd_fleet(args) -> int:
    """ISSUE 17/18: the replicated serving fleet — start M replica
    processes behind a routing tier (optionally supervised:
    reap/respawn/quarantine), inspect per-replica health, drain a
    replica out of rotation, and roll a canary-gated restart wave."""
    return {"start": _fleet_start, "status": _fleet_status,
            "drain": _fleet_drain,
            "restart": _fleet_restart}[args.fleet_command](args)


def _fleet_start(args) -> int:
    from ..workflow.fleet import (fleet_state_path, run_fleet_router,
                                  spawn_replicas, write_fleet_state)

    router_ip = "127.0.0.1" if args.ip in ("0.0.0.0", "::") else args.ip
    router_url = f"http://{router_ip}:{args.port}"
    procs = []
    extra = []
    if args.replica_urls:
        # front EXISTING engine servers (e.g. on other hosts)
        urls = [u.strip().rstrip("/")
                for u in args.replica_urls.split(",") if u.strip()]
    else:
        if args.replicas < 1:
            _die("--replicas must be >= 1")
        extra = ["--engine-json", args.engine_json]
        for tok in args.replica_arg or []:
            extra.extend(tok.split())
        procs = spawn_replicas(args.engine_dir, args.replicas,
                               args.base_port, extra_args=tuple(extra))
        urls = [f"http://127.0.0.1:{args.base_port + i}"
                for i in range(args.replicas)]
    started = time.time()

    def _publish_state(sup=None) -> None:
        active, quarantined = [], []
        if sup is not None:
            for rep in sup.replicas:
                entry = {"name": rep.name, "url": rep.url,
                         "pid": (rep.proc.pid if rep.proc is not None
                                 else None),
                         "startedAt": started}
                (quarantined if rep.state == "quarantined"
                 else active).append(entry)
        else:
            active = [{"name": f"r{i}", "url": u,
                       "pid": (procs[i].pid if i < len(procs) else None),
                       "startedAt": started}
                      for i, u in enumerate(urls)]
        write_fleet_state(router_url, active, router_pid=os.getpid(),
                          router_started_at=started,
                          quarantined=quarantined)

    supervisor = None
    if args.supervise:
        if not procs:
            _die("--supervise needs locally spawned replicas "
                 "(it cannot respawn processes behind --replica-urls)")
        from ..workflow.supervise import FleetSupervisor

        def _respawn_one(rep):
            return spawn_replicas(args.engine_dir, 1, rep.port,
                                  extra_args=tuple(extra))[0]

        supervisor = FleetSupervisor(
            _respawn_one,
            [{"name": f"r{i}", "port": args.base_port + i, "url": u}
             for i, u in enumerate(urls)],
            max_respawns=args.max_respawns,
            crash_window_s=args.crash_window_s,
            quarantine_s=args.quarantine_s,
            state_writer=_publish_state)
        for i, p in enumerate(procs):
            supervisor.adopt(f"r{i}", p)
        supervisor.start()
    _publish_state(supervisor)
    state_dir = args.state_dir or str(
        fleet_state_path().parent / "fleet-router")
    _ok(f"fleet: router on {router_url}, {len(urls)} replica(s): "
        f"{', '.join(urls)}")
    if supervisor is not None:
        _ok(f"fleet: supervised (max {args.max_respawns} deaths per "
            f"{args.crash_window_s:.0f}s window, quarantine "
            f"{args.quarantine_s:.0f}s)")
    try:
        run_fleet_router(
            urls, ip=args.ip, port=args.port,
            supervisor=supervisor,
            probe_interval_s=args.probe_interval_s,
            breaker_reset_s=args.breaker_reset_s,
            default_deadline_ms=args.deadline_ms,
            max_hedges=args.max_hedges,
            spillover_inflight=args.spillover_inflight,
            journal_max=args.journal_max,
            slo_drain_burn=args.slo_drain_burn,
            canary_sample=args.canary_sample,
            canary_max_mismatch=args.canary_max_mismatch,
            state_dir=state_dir,
            collect_metrics=not args.no_collect_metrics,
            metrics_stale_after_s=args.metrics_stale_after_s,
            outlier_band=args.outlier_band,
            incident_dir=args.incident_dir,
        )
    finally:
        if supervisor is not None:
            supervisor.stop()
            supervisor.terminate_all()
        for p in procs:
            if p.poll() is None:
                p.terminate()
        for p in procs:
            try:
                p.wait(timeout=10)
            except Exception:  # noqa: BLE001 — SIGKILL the stragglers
                p.kill()
    return 0


def _fleet_router_url(args) -> str:
    if getattr(args, "router_url", None):
        return args.router_url.rstrip("/")
    from ..workflow.fleet import read_fleet_state

    state = read_fleet_state()
    if state and state.get("routerUrl"):
        return str(state["routerUrl"]).rstrip("/")
    return "http://127.0.0.1:8000"


def _fleet_status(args) -> int:
    import urllib.request

    if not getattr(args, "router_url", None):
        # ISSUE 18: a state file whose recorded PIDs are all gone means
        # there is nothing to probe — say so instead of timing out
        # against a dead URL
        from ..workflow.fleet import read_fleet_state

        state = read_fleet_state()
        if state and state.get("stale"):
            _die("fleet not running (stale state file): recorded PIDs "
                 f"are gone (last router {state.get('routerUrl')})")
    url = _fleet_router_url(args)
    try:
        with urllib.request.urlopen(f"{url}/fleet.json", timeout=5) as resp:
            st = json.loads(resp.read().decode())
    except Exception as e:  # noqa: BLE001
        _die(f"fleet router unreachable at {url}: {e}")
    # ISSUE 20: the merged observability view — windowed p99/qps per
    # replica and outlier flags. Absent (older router, collector
    # disabled) the status below simply omits those columns.
    windows: dict = {}
    outliers: dict = {}
    try:
        with urllib.request.urlopen(f"{url}/fleet/stats.json",
                                    timeout=5) as resp:
            fstats = json.loads(resp.read().decode())
        windows = fstats.get("replicas") or {}
        outliers = fstats.get("outliers") or {}
    except Exception:  # noqa: BLE001 — observability must not break status
        pass
    quarantined = st.get("quarantined") or []
    _ok(f"fleet router {url}: epoch {st['fleetEpoch']}, "
        f"{len(st['eligible'])}/{len(st['replicas'])} replica(s) eligible"
        f"{' [DRAINING]' if st.get('draining') else ''}"
        + (f", {len(quarantined)} quarantined" if quarantined else ""))
    for r in st["replicas"]:
        mark = ("quarantined" if r.get("quarantined")
                else "eligible" if r["name"] in st["eligible"]
                else "draining" if r["draining"] or r["adminDrained"]
                else f"breaker {r['breaker']}" if r["breaker"] != "closed"
                else "slo-drained" if r["sloDrained"]
                else "not ready")
        obs = ""
        w = (windows.get(r["name"]) or {}).get("window") or {}
        if w.get("qps") is not None:
            obs = f", qps {w['qps']:g}"
        if w.get("p99") is not None:
            obs += f", p99 {w['p99'] * 1e3:.2f}ms"
        flagged = outliers.get(r["name"]) or []
        if flagged:
            obs += f" [OUTLIER: {','.join(flagged)}]"
        if (windows.get(r["name"]) or {}).get("stale"):
            obs += " [metrics stale]"
        _ok(f"  {r['name']} {r['url']}: {r['status']}, "
            f"live={str(r['live']).lower()} ready={str(r['ready']).lower()}, "
            f"epoch {r['syncedEpoch']}/{st['fleetEpoch']} "
            f"(replica patch epoch {r['patchEpoch']}), "
            f"inflight {r['inflight']}{obs} [{mark}]")
    sup = st.get("supervisor")
    if sup:
        for r in sup.get("replicas", []):
            extras = []
            if r.get("state") == "backoff":
                extras.append(f"respawn in {r.get('backoffRemainingS')}s")
            if r.get("state") == "quarantined":
                extras.append(
                    f"cooldown {r.get('quarantineRemainingS')}s")
            _ok(f"  supervisor {r['name']}: {r['state']}, "
                f"{r.get('deathsInWindow', 0)} death(s) in window, "
                f"{r.get('respawns', 0)} respawn(s)"
                + (f" [{', '.join(extras)}]" if extras else ""))
    return 0


def _fleet_restart(args) -> int:
    import urllib.request

    url = _fleet_router_url(args)
    req = urllib.request.Request(
        f"{url}/fleet/restart?canary={args.canary_sample}",
        data=b"{}", headers={"Content-Type": "application/json"},
        method="POST")
    try:
        with urllib.request.urlopen(req, timeout=args.timeout_s) as resp:
            out = json.loads(resp.read().decode())
    except urllib.error.HTTPError as e:
        try:
            out = json.loads(e.read().decode())
        except Exception:  # noqa: BLE001
            _die(f"rolling restart failed against {url}: {e}")
        _die(f"rolling restart {out.get('outcome', 'failed')}: "
             f"{out.get('message') or json.dumps(out.get('wave', []))}")
    except Exception as e:  # noqa: BLE001
        _die(f"rolling restart failed against {url}: {e}")
    _ok(f"rolling restart {out['outcome']}: {out.get('restarted', 0)}/"
        f"{out.get('replicas', 0)} replica(s) restarted")
    for w in out.get("wave", []):
        _ok(f"  {w['replica']}: "
            + (f"restarted in {w.get('restartS')}s" if w.get("ok")
               else f"FAILED ({w.get('error')})"))
    canary = out.get("canary")
    if canary:
        _ok(f"  canary: {canary.get('sampled')} sampled, mismatch "
            f"fraction {canary.get('mismatchFraction')} "
            f"(fresh {canary.get('fresh')} vs baseline "
            f"{canary.get('baseline')})")
    return 0


def _fleet_drain(args) -> int:
    import urllib.request

    url = _fleet_router_url(args)
    body = json.dumps({"replica": args.replica,
                       "stop": args.stop}).encode()
    req = urllib.request.Request(
        f"{url}/fleet/drain", data=body,
        headers={"Content-Type": "application/json"}, method="POST")
    try:
        with urllib.request.urlopen(req, timeout=10) as resp:
            out = json.loads(resp.read().decode())
    except Exception as e:  # noqa: BLE001
        _die(f"drain failed against {url}: {e}")
    _ok(f"replica {out['replica']} draining"
        + (" (asked to /stop)" if out.get("stopped") else ""))
    return 0


def cmd_batchpredict(args) -> int:
    """Bulk offline inference: queries JSONL in, predictions JSONL out,
    through the SAME rehydrated engine + batched predict path `pio
    deploy` serves from — no HTTP in the loop. Output line shape:
    ``{"query": {...}, "prediction": {...}}`` (or ``"error"``); queries
    fail individually, never the whole run. (The reference line gained
    `pio batchpredict` after 0.9.2 — this fills the same offline-scoring
    role; Apache PredictionIO 0.13's BatchPredict.)"""
    _enable_compile_cache()
    from ..workflow.create_server import EngineServer

    engine_dir, engine, inst = _resolve_engine_instance(args)
    in_path, out_path = Path(args.input), Path(args.output)
    if in_path.resolve() == out_path.resolve():
        _die("--output must differ from --input (opening the output "
             "truncates it)")
    server = EngineServer(engine, inst, engine_dir=engine_dir,
                          batch_window_ms=0,  # offline: no micro-batcher
                          retriever_mesh=_retriever_mesh(args.retriever_mesh))

    n_ok = n_err = 0
    with open(in_path) as fin, open(out_path, "w") as fout:
        chunk: list[tuple[int, dict]] = []

        def flush():
            nonlocal n_ok, n_err
            if not chunk:
                return
            outcomes = server.serve_query_batch([q for _, q in chunk])
            for (lineno, q), (tag, payload) in zip(chunk, outcomes):
                if tag == "ok":
                    fout.write(json.dumps(
                        {"query": q, "prediction": payload}) + "\n")
                    n_ok += 1
                else:
                    fout.write(json.dumps(
                        {"query": q, "error": str(payload)}) + "\n")
                    n_err += 1
                    log.warning("line %d failed: %s", lineno, payload)
            chunk.clear()

        for lineno, line in enumerate(fin, 1):
            line = line.strip()
            if not line:
                continue
            try:
                q = json.loads(line)
                if not isinstance(q, dict):
                    raise ValueError("query must be a JSON object")
            except ValueError as e:
                fout.write(json.dumps(
                    {"raw": line[:2000], "error": f"bad JSON: {e}"}) + "\n")
                n_err += 1
                continue
            chunk.append((lineno, q))
            if len(chunk) >= args.batch_max:
                flush()
        flush()
    _ok(f"Batch predict complete: {n_ok} prediction(s), {n_err} error(s) "
        f"-> {out_path}")
    return 0 if n_err == 0 else 1


def _retriever_mesh(n):
    """Mesh for catalog-sharded serving (--retriever-mesh N): the item
    catalog shards over an N-device "model" axis instead of living
    replicated on one device (ops/retrieval.ShardedDeviceRetriever).
    ``auto`` defers the width to the catalog-size cost model
    (ops/retrieval.choose_shard_count) at deploy time, when the catalog
    length is known."""
    if isinstance(n, str):
        if n.strip().lower() == "auto":
            return "auto"
        try:
            n = int(n)
        except ValueError:
            _die(f"--retriever-mesh must be an integer or 'auto', got {n!r}")
    if not n or n <= 1:
        return None
    from ..parallel.mesh import make_mesh

    try:
        return make_mesh((n,), ("model",))
    except ValueError as e:  # more shards than devices
        _die(str(e))


def cmd_bench(args) -> int:
    """`pio bench backup`: synthetic backup throughput (one full backup,
    then incrementals over an unchanged home)."""
    from ..storage.backup import run_backup_bench

    rep = run_backup_bench(files=args.files, size_kb=args.size_kb,
                           rounds=args.rounds)
    if args.json:
        _ok(json.dumps(rep, indent=2, sort_keys=True))
        return 0
    _ok(f"backup bench: {rep['files']} files x {rep['sizeKb']}KB")
    for r in rep["rounds"]:
        kind = "full" if r["round"] == 0 else "incremental"
        _ok(f"  round {r['round']} ({kind}): {r['seconds']}s, "
            f"{r['mbWritten']}MB written ({r['mbPerS']}MB/s), "
            f"{r['dedupedFiles']} files deduped")
    return 0


def cmd_undeploy(args) -> int:
    import urllib.error
    import urllib.request

    url = f"http://{args.ip}:{args.port}/stop"
    try:
        with urllib.request.urlopen(url, timeout=5) as r:
            body = json.loads(r.read().decode())
        _ok(f"Undeploy requested: {body.get('message')}")
        return 0
    except (urllib.error.URLError, OSError, json.JSONDecodeError) as e:
        _die(f"cannot reach engine server at {url}: {e}")
    return 1


# ---------------------------------------------------------------------------
# servers / status / import / export
# ---------------------------------------------------------------------------

def cmd_stream(args) -> int:
    """Streaming online learning (ISSUE 10): tail the event server's
    write-ahead journal behind an independent follow cursor, fold each
    batch of events into user factors with the batched fold-in kernel,
    and hot-patch the deployed engine server via POST /reload/delta —
    cold-start users personalized within one batch window, no retrain."""
    _enable_compile_cache()
    from ..workflow import Context, prepare_deploy
    from ..workflow.streaming import StreamingUpdater

    engine_dir, engine, inst = _resolve_engine_instance(args)
    result = prepare_deploy(engine, inst, Context(mode="Serving"),
                            engine_dir=engine_dir)
    model = next((m for m in result.models
                  if hasattr(m, "fold_in_users")), None)
    if model is None:
        _die("no trained model supports fold-in (fold_in_users); "
             "streaming updates need a factorization model (ALS)")
    updater = StreamingUpdater(
        model,
        args.journal_dir,
        args.engine_url,
        name=args.follow_name,
        partitions=args.journal_partitions or None,
        batch_window_ms=args.batch_window_ms,
        max_records=args.max_records,
        eval_gate=args.eval_gate,
        eval_k=args.eval_k,
        solver=args.fold_in_solver,
        breaker_threshold=args.breaker_threshold,
        breaker_reset_s=args.breaker_reset_s,
        variant=args.variant,
    )
    _ok(f"Streaming updater: journal {args.journal_dir} -> "
        f"{args.engine_url} (model instance {inst.id}, gate "
        f"{args.eval_gate if args.eval_gate is not None else 'off'}). "
        f"Ctrl-C to stop.")
    try:
        updater.run_forever()
    except KeyboardInterrupt:
        updater.stop()
    _ok(f"Streaming updater stopped: {json.dumps(updater.stats())}")
    return 0


def cmd_eventserver(args) -> int:
    from ..api import run_event_server

    run_event_server(ip=args.ip, port=args.port, stats=args.stats,
                     journal_dir=args.journal_dir,
                     journal_fsync=args.journal_fsync,
                     journal_max_mb=args.journal_max_mb,
                     journal_partitions=args.journal_partitions,
                     admission=args.admission,
                     rate_limit_qps=args.rate_limit_qps,
                     rate_limit_burst=args.rate_limit_burst)
    return 0


def cmd_adminserver(args) -> int:
    from ..tools.admin import run_admin_server

    run_admin_server(ip=args.ip, port=args.port)
    return 0


def cmd_dashboard(args) -> int:
    from ..tools.dashboard import run_dashboard

    run_dashboard(ip=args.ip, port=args.port, engine_url=args.engine_url)
    return 0


def cmd_admin(args) -> int:
    """Operator plumbing. ``pio admin reap`` flips stale-heartbeat INIT
    engine instances (orphans of crashed/preempted trainers) to
    ABANDONED; the same sweep also runs automatically at train start.
    ``pio admin metrics`` dumps this process's telemetry registry —
    counters, gauges, and histogram quantiles (the in-process view of
    what a server exports at ``GET /metrics``).  ``pio admin fsck``
    audits the cross-store integrity invariants (blobs, checkpoints,
    journals, router epoch) and ``pio admin gc --blobs`` reclaims model
    blobs no non-retired engine instance references."""
    if args.admin_command == "fsck":
        from ..storage import backup as drb

        rep = drb.fsck(journal_dir=args.journal_dir,
                       checkpoint_dir=args.checkpoint_dir,
                       repair=args.repair)
        rc = 0 if not rep["violations"] else 1
        if args.json:
            _ok(json.dumps(rep, indent=2, sort_keys=True))
            return rc
        ck = rep["checked"]
        _ok(f"fsck: {rep['verdict']} "
            f"(blobs={ck['blobs']}, checkpoint steps={ck['checkpointSteps']}, "
            f"journal segments={ck['journalSegments']}, "
            f"router epoch={'checked' if ck['routerEpoch'] else 'n/a'})")
        for v in rep["violations"]:
            mark = "  [repaired]" if v["repaired"] else ""
            _ok(f"  {v['invariant']}: {v['path']}: {v['detail']}{mark}")
        if rep["orphanBlobs"]:
            _ok(f"  {len(rep['orphanBlobs'])} orphan blob(s) — reclaim "
                f"with `pio admin gc --blobs`")
        return rc
    if args.admin_command == "gc":
        if not args.blobs:
            _die("nothing to collect: pass --blobs")
        from ..storage import backup as drb

        rep = drb.gc_blobs(dry_run=args.dry_run)
        verb = "would delete" if args.dry_run else "deleted"
        if not rep["orphans"]:
            _ok("No orphaned model blobs.")
        for name in rep["orphans"]:
            _ok(f"  {verb} {name} (+ .sha256 sidecar)")
        return 0
    from ..workflow.supervisor import heartbeat_age_s, reap_orphans

    if args.admin_command == "metrics":
        if getattr(args, "url", None):
            return _admin_metrics_remote(args)
        from ..obs.metrics import METRICS

        snap = METRICS.snapshot()
        if args.json:
            _ok(json.dumps(snap, indent=2, sort_keys=True))
            return 0
        _print_metrics_snapshot(snap)
        return 0
    if args.admin_command == "flight":
        import urllib.request

        url = args.url.rstrip("/") + "/debug/flight.json"
        with urllib.request.urlopen(url, timeout=10) as r:
            snap = json.loads(r.read().decode())
        if args.json:
            _ok(json.dumps(snap, indent=2, sort_keys=True))
            return 0
        ctx = snap.get("context") or {}
        records = snap.get("records") or []
        _ok(f"flight recorder: {len(records)}/{snap.get('capacity')} "
            f"records, mode={ctx.get('mode', '?')}, "
            f"queueDepth={ctx.get('queueDepth', '?')}, "
            f"dumps={snap.get('dumps', 0)}")
        last = snap.get("lastDump")
        if last:
            _ok(f"  last incident dump: {last.get('reason')} -> "
                f"{last.get('path')}")
        for rec in records[-max(1, args.last):]:
            stages = rec.get("stagesMs") or {}
            top = max(stages, key=stages.get) if stages else "-"
            flags = []
            if rec.get("hung"):
                flags.append("HUNG")
            if rec.get("stalledStage"):
                flags.append(f"stalled@{rec['stalledStage']}")
            http = (rec.get("context") or {}).get("http", "?")
            tail = f" [{','.join(flags)}]" if flags else ""
            _ok(f"  {str(rec.get('requestId', '?'))[:12]:12s} "
                f"{rec.get('wallMs', 0.0):9.2f}ms http={http} "
                f"slowest={top}{tail}")
        return 0
    if args.admin_command == "reap":
        meta = _storage().get_metadata()
        reaped = reap_orphans(meta, stale_after_s=args.stale_after_s,
                              dry_run=args.dry_run)
        verb = "would reap" if args.dry_run else "reaped"
        if not reaped:
            _ok(f"No orphaned INIT engine instances older than "
                f"{args.stale_after_s:.0f}s.")
        for inst in reaped:
            age = heartbeat_age_s(inst)
            _ok(f"  {verb} {inst.id} (engine={inst.engine_id}, last "
                f"liveness {age:.0f}s ago) -> ABANDONED")
    return 0


def _print_metrics_snapshot(snap: dict) -> None:
    """The `pio admin metrics` table over a registry-snapshot-shaped
    dict ({counters, gauges, histograms}) — shared by the in-process,
    remote single-server and remote fleet-merged paths."""
    for section in ("counters", "gauges"):
        vals = snap.get(section) or {}
        if vals:
            _ok(f"{section}:")
        for name, v in sorted(vals.items()):
            if isinstance(v, dict):
                # fleet-merged gauge: min/max/sum rollup + per-replica
                by = v.get("byReplica") or {}
                reps = " ".join(f"{k}={val:g}"
                                for k, val in sorted(by.items()))
                _ok(f"  {name:56s} min={v.get('min', 0):g} "
                    f"max={v.get('max', 0):g} sum={v.get('sum', 0):g}"
                    + (f"  ({reps})" if reps else ""))
            else:
                _ok(f"  {name:56s} {v:g}")
    hists = snap.get("histograms") or {}
    if hists:
        _ok("histograms (seconds):")
    for name, h in sorted(hists.items()):
        _ok(f"  {name:44s} n={h['count']:<8d} "
            f"p50={h['p50'] * 1e3:9.3f}ms p95={h['p95'] * 1e3:9.3f}ms "
            f"p99={h['p99'] * 1e3:9.3f}ms")


def _admin_metrics_remote(args) -> int:
    """`pio admin metrics --url <base>`: ISSUE 20 bugfix. Pointed at a
    fleet router this used to show only the ROUTER PROCESS's registry
    with no hint a fleet existed; now the fleet surface is detected
    (GET /fleet/stats.json) and the merged snapshot is printed, with a
    breadcrumb to /fleet/metrics. A plain engine server (no fleet
    surface) falls through to its own /metrics page, parsed back into
    the same table."""
    import urllib.request

    from ..obs.aggregate import parse_prometheus
    from ..obs.metrics import _fmt_labels, quantile_from_counts

    base = args.url.rstrip("/")
    try:
        with urllib.request.urlopen(f"{base}/fleet/stats.json",
                                    timeout=10) as r:
            fstats = json.loads(r.read().decode())
    except Exception:  # noqa: BLE001 — not a fleet router
        fstats = None
    if isinstance(fstats, dict) and isinstance(fstats.get("merged"), dict):
        merged = fstats["merged"]
        if args.json:
            _ok(json.dumps(fstats, indent=2, sort_keys=True))
            return 0
        coll = fstats.get("collector") or {}
        _ok(f"fleet: merged across {coll.get('freshReplicas', '?')} fresh "
            f"replica(s) — Prometheus exposition at {base}/fleet/metrics")
        _print_metrics_snapshot(merged)
        for name, flagged in sorted((fstats.get("outliers") or {}).items()):
            _ok(f"outlier: {name} [{','.join(flagged)}]")
        return 0
    try:
        with urllib.request.urlopen(f"{base}/metrics", timeout=10) as r:
            text = r.read().decode()
    except OSError as e:
        _die(f"metrics unreachable at {base}: {e}")
    parsed = parse_prometheus(text)
    snap: dict = {"counters": {}, "gauges": {}, "histograms": {}}
    for kind in ("counters", "gauges"):
        for name, series in parsed[kind].items():
            for labels, v in series.items():
                key = name + _fmt_labels(tuple(n for n, _ in labels),
                                         tuple(val for _, val in labels))
                snap[kind][key] = v
    for name, h in parsed["histograms"].items():
        snap["histograms"][name] = {
            "count": h["count"], "sum": h["sum"],
            "p50": quantile_from_counts(h["bounds"], h["counts"], 0.50),
            "p95": quantile_from_counts(h["bounds"], h["counts"], 0.95),
            "p99": quantile_from_counts(h["bounds"], h["counts"], 0.99),
        }
    if args.json:
        _ok(json.dumps(snap, indent=2, sort_keys=True))
        return 0
    _print_metrics_snapshot(snap)
    return 0


def cmd_trace(args) -> int:
    """ISSUE 20: `pio trace <rid>` — one-command cross-process trace
    assembly. The X-PIO-Request-ID that already propagates router ->
    replica -> WAL becomes queryable: the router's /fleet/trace.json
    joins its hop log with every replica's flight-recorder records for
    the id, the ingest WAL is scanned for events carrying the id in
    their ``"t"`` field, and everything renders as one span tree."""
    import urllib.parse
    import urllib.request

    from ..obs.trace import render_span_tree, spans_from_waterfall

    rid = args.request_id
    nodes: list[dict] = []
    if args.url:
        # direct engine-server mode: no router join, just this
        # process's flight recorder
        base = args.url.rstrip("/")
        try:
            with urllib.request.urlopen(f"{base}/debug/flight.json",
                                        timeout=10) as r:
                body = json.loads(r.read().decode())
        except OSError as e:
            _die(f"engine server unreachable at {base}: {e}")
        for rec in body.get("records") or []:
            if isinstance(rec, dict) and rec.get("requestId") == rid:
                nodes.append(spans_from_waterfall(
                    rec, label=f"engine {base}"))
    else:
        router = _fleet_router_url(args)
        joined = None
        try:
            with urllib.request.urlopen(
                    f"{router}/fleet/trace.json?rid="
                    f"{urllib.parse.quote(rid)}", timeout=10) as r:
                joined = json.loads(r.read().decode())
        except Exception as e:  # noqa: BLE001 — WAL-only traces still render
            print(f"[WARN] fleet router unreachable at {router}: {e}",
                  file=sys.stderr)
        if joined:
            replica_recs = dict(joined.get("replicas") or {})
            for hop in joined.get("router") or []:
                replica = hop.get("replica")
                if replica is None:
                    nodes.append({
                        "label": "router hop: every attempt failed",
                        "ms": hop.get("ms"),
                        "detail": hop.get("error"), "children": []})
                    continue
                detail = [f"http {hop.get('http')}"]
                if hop.get("hedges"):
                    detail.append(f"hedges={hop['hedges']}")
                if hop.get("spillover"):
                    detail.append("spillover")
                node = {"label": f"router hop -> {replica}",
                        "ms": hop.get("ms"),
                        "detail": " ".join(detail),
                        "children": [
                            spans_from_waterfall(
                                rec, label=f"replica {replica}")
                            for rec in replica_recs.pop(replica, [])]}
                nodes.append(node)
            # replica records with no surviving router hop (the hop
            # ring is bounded) still render, just un-nested
            for name, recs in sorted(replica_recs.items()):
                for rec in recs:
                    nodes.append(spans_from_waterfall(
                        rec, label=f"replica {name}"))
    if args.wal_dir:
        from ..storage.journal import iter_journal_records

        for payload in iter_journal_records(args.wal_dir):
            try:
                d = json.loads(payload)
            except (ValueError, UnicodeDecodeError):
                continue
            if not isinstance(d, dict) or d.get("t") != rid:
                continue
            e = d.get("e") or {}
            nodes.append({
                "label": (f"ingest WAL: {e.get('event', 'event')} "
                          f"{e.get('entityType', '?')}/"
                          f"{e.get('entityId', '?')}"),
                "ms": None,
                "detail": f"app={d.get('a')} eventTime={e.get('eventTime')}",
                "children": []})
    if not nodes:
        _ok(f"no spans found for request id {rid}")
        return 1
    for line in render_span_tree(nodes, title=f"trace {rid}").splitlines():
        _ok(line)
    return 0


def cmd_profile(args) -> int:
    """``pio profile serve`` asks a LIVE engine server to capture a
    jax.profiler trace of itself (POST /debug/profile) — profiling the
    real serving process under real traffic, not a bench stand-in. The
    server brackets the window with flight-recorder snapshots so the
    trace can be lined up against the request waterfalls that fell
    inside it; ``--out`` saves those brackets locally."""
    import urllib.parse
    import urllib.request

    qs = {"seconds": str(args.seconds)}
    if args.trace_dir:
        qs["dir"] = args.trace_dir
    url = (args.url.rstrip("/") + "/debug/profile?"
           + urllib.parse.urlencode(qs))
    req = urllib.request.Request(url, method="POST")
    try:
        with urllib.request.urlopen(req, timeout=args.seconds + 30) as r:
            body = json.loads(r.read().decode())
    except OSError as e:
        _die(f"profile capture failed against {args.url}: {e}")
    _ok(f"Captured {body.get('seconds')}s profiler trace -> "
        f"{body.get('traceDir')} (on the server host)")
    _ok("  view with TensorBoard/XProf: tensorboard --logdir <traceDir>")
    if args.out:
        out = Path(args.out)
        out.mkdir(parents=True, exist_ok=True)
        for key, stem in (("flightBefore", "before"), ("flightAfter",
                                                       "after")):
            p = out / f"flight-{stem}.json"
            p.write_text(json.dumps(body.get(key), indent=2))
            _ok(f"  wrote {p}")
    return 0


def cmd_capture(args) -> int:
    """``pio capture start|stop`` toggles a live server's golden-traffic
    recording (POST /capture/{start,stop} — stop flushes the ring);
    ``pio capture export`` rewrites a local capture journal as JSONL."""
    if args.capture_command == "export":
        from ..obs.capture import export_capture

        if not Path(args.dir).is_dir():
            _die(f"capture directory {args.dir!r} not found")
        n = export_capture(args.dir, args.output)
        _ok(f"Exported {n} captured record(s) -> {args.output}")
        return 0
    import urllib.request

    url = f"{args.url.rstrip('/')}/capture/{args.capture_command}"
    req = urllib.request.Request(url, method="POST")
    try:
        with urllib.request.urlopen(req, timeout=30) as r:
            body = json.loads(r.read().decode())
    except OSError as e:
        _die(f"capture {args.capture_command} failed against {args.url}: "
             f"{e}")
    _ok(body.get("message", ""))
    cap = body.get("capture") or {}
    if cap:
        _ok(f"  dir={cap.get('directory')} captured={cap.get('captured')} "
            f"onDisk={cap.get('journalRecords')} "
            f"bytes={cap.get('journalBytes')}")
    return 0


def cmd_variant(args) -> int:
    """``pio variant list|weight|promote|retire`` (ISSUE 14) — manage
    the variant table of a running engine server: inspect the traffic
    split, re-weight the hash buckets, flip a candidate live, or take a
    variant out of rotation."""
    import urllib.error
    import urllib.request

    base = args.url.rstrip("/")

    def _call(path: str, method: str = "POST", payload: dict | None = None):
        req = urllib.request.Request(
            f"{base}{path}",
            data=(json.dumps(payload).encode()
                  if payload is not None else None),
            method=method,
            headers={"Content-Type": "application/json"})
        try:
            with urllib.request.urlopen(req, timeout=30) as r:
                return json.loads(r.read().decode())
        except urllib.error.HTTPError as e:
            try:
                msg = json.loads(e.read().decode()).get("message", str(e))
            except Exception:  # noqa: BLE001
                msg = str(e)
            _die(f"variant {args.variant_command} failed ({e.code}): {msg}")
        except OSError as e:
            _die(f"no engine server answering at {base}: {e}")

    if args.variant_command == "list":
        snap = _call("/variants.json", method="GET")
        _ok(f"{snap['count']} variant(s):")
        for v in snap["variants"]:
            share = v.get("trafficShare", 0.0)
            routed = v.get("routed", {})
            _ok(f"  {v['variantId']:<16} state={v['state']:<9} "
                f"weight={v['weight']:<6g} share={share:.1%} "
                f"instance={v.get('engineInstanceId')} "
                f"routed(hashed={routed.get('hashed', 0)} "
                f"forced={routed.get('forced', 0)} "
                f"default={routed.get('default', 0)})")
        return 0
    if args.variant_command == "weight":
        out = _call(f"/variants/{args.variant_id}/weight",
                    payload={"weight": args.weight})
        _ok(f"Variant {out.get('variantId')!r} weight -> "
            f"{out.get('weight')} (share {out.get('trafficShare', 0):.1%})")
        return 0
    if args.variant_command == "promote":
        out = _call(f"/variants/{args.variant_id}/promote")
        _ok(f"Promoted {out.get('promoted')!r} to live "
            f"(previous live: {out.get('previousLive')!r})")
        return 0
    # retire
    out = _call(f"/variants/{args.variant_id}/retire")
    _ok(f"Retired {out.get('variantId')!r} (weight 0; still reachable "
        f"via the X-PIO-Variant header for replay)")
    return 0


def cmd_replay(args) -> int:
    """``pio replay <capture-dir>`` re-issues captured golden traffic
    and prints the three-tier parity report (obs/replay.py). Target is
    either a live server (``--target URL``) or an in-process rehydration
    of an engine instance (``--engine-instance-id`` / latest COMPLETED),
    the same no-HTTP path `pio batchpredict` serves from."""
    from ..obs.capture import iter_capture
    from ..obs.replay import replay_records

    if not Path(args.capture_dir).is_dir():
        _die(f"capture directory {args.capture_dir!r} not found")
    records = list(iter_capture(args.capture_dir))
    if not records:
        _die(f"no readable capture records under {args.capture_dir!r}")
    if args.target:
        report = replay_records(records, target=args.target,
                                score_tol=args.score_tol)
    else:
        _enable_compile_cache()
        from ..workflow.create_server import EngineServer

        engine_dir, engine, inst = _resolve_engine_instance(args)
        server = EngineServer(
            engine, inst, engine_dir=engine_dir,
            batch_window_ms=0,  # offline: no micro-batcher
            fallback=not args.engine_instance_id,
            retrieval=_retrieval_params(engine_dir, args))
        report = replay_records(records, server=server,
                                score_tol=args.score_tol)
    if args.json:
        print(json.dumps(report, indent=2, default=str))
        return 0
    t = report["tiers"]
    _ok(f"Replayed {report['total']} record(s) "
        f"({report['skipped']} skipped): parity {report['parityPct']}%")
    _ok(f"  tiers: bitwise={t['bitwise']} topk_set={t['topk_set']} "
        f"score_tol={t['score_tol']} mismatch={t['mismatch']} "
        f"error={t['error']}")
    # ISSUE 14: the A/B read — parity per captured variant, so a capture
    # spanning an experiment diffs each arm against itself
    by_variant = report.get("variants") or {}
    if len(by_variant) > 1:
        _ok("  by variant:")
        for vid in sorted(by_variant):
            vt = by_variant[vid]
            vtiers = vt["tiers"]
            _ok(f"    {vid}: n={vt['total']} parity={vt['parityPct']}% "
                f"(bitwise={vtiers['bitwise']} "
                f"mismatch={vtiers['mismatch']} error={vtiers['error']})")
    lat = report["latencyMs"]
    _ok(f"  p50 latency ms: captured={lat['captured']} "
        f"replayed={lat['replayed']}")
    delta = report["provenance"]["delta"]
    if delta:
        _ok("  provenance delta (capture -> replay):")
        for field, pair in delta.items():
            _ok(f"    {field}: {pair['captured']!r} -> "
                f"{pair['replayed']!r}")
    else:
        _ok("  provenance identical between capture and replay")
    for m in report["mismatches"][:args.show_mismatches]:
        _ok(f"  [{m['tier']}] rid={m.get('rid')} "
            f"request={json.dumps(m.get('request'), default=str)}")
    return 0


def cmd_status(args) -> int:
    """(reference `pio status`: storage verification, Console.scala:1061+)"""
    _ok(f"predictionio_tpu {__version__}")
    from ..storage import Storage

    statuses = Storage.verify_all_data_objects()
    for repo, st in statuses.items():
        _ok(f"  {repo}: {st}")
    try:
        from ..workflow.supervisor import (
            DEFAULT_PEER_STALE_AFTER_S, DEFAULT_STALE_AFTER_S,
            heartbeat_age_s, host_heartbeats)
        from datetime import datetime, timezone

        running = Storage.get_metadata().engine_instance_get_by_status("INIT")
        for inst in running:
            age = heartbeat_age_s(inst)
            if age is None:
                mark, shown = "orphan?", "never"
            else:
                mark = ("live" if age < DEFAULT_STALE_AFTER_S
                        else "orphan? (reap with `pio admin reap`)")
                shown = f"{age:.0f}s ago"
            _ok(f"  training run {inst.id}: INIT, attempt={inst.attempt}, "
                f"last heartbeat {shown} [{mark}]")
            # elastic multi-host runs: one liveness line per process
            now = datetime.now(timezone.utc)
            for pid, entry in sorted(host_heartbeats(inst).items()):
                from ..workflow.supervisor import _parse_iso

                ts = _parse_iso(entry.get("ts", ""))
                h_age = (now - ts).total_seconds() if ts else None
                h_mark = ("live" if h_age is not None
                          and h_age < DEFAULT_PEER_STALE_AFTER_S
                          else "stale — peer presumed lost")
                h_shown = f"{h_age:.0f}s ago" if h_age is not None else "never"
                _ok(f"    host {pid}: attempt={entry.get('attempt', 0)}, "
                    f"heartbeat {h_shown} [{h_mark}]")
    except Exception as e:  # noqa: BLE001 — status must keep printing
        _ok(f"  training runs: unavailable ({e})")
    try:
        # ISSUE 17: per-replica serving liveness next to the training
        # heartbeats — same question ("what is alive?"), serving plane
        from ..workflow.fleet import read_fleet_state

        state = read_fleet_state()
        if state and state.get("stale"):
            _ok("  serving fleet: not running (stale state file — "
                "recorded PIDs are gone)")
        elif state:
            import urllib.request

            url = str(state.get("routerUrl", "")).rstrip("/")
            try:
                with urllib.request.urlopen(f"{url}/fleet.json",
                                            timeout=3) as resp:
                    st = json.loads(resp.read().decode())
            except Exception as e:  # noqa: BLE001
                _ok(f"  serving fleet at {url}: router unreachable ({e})")
            else:
                _ok(f"  serving fleet at {url}: epoch {st['fleetEpoch']}, "
                    f"{len(st['eligible'])}/{len(st['replicas'])} eligible")
                for r in st["replicas"]:
                    mark = ("quarantined" if r.get("quarantined")
                            else "eligible" if r["name"] in st["eligible"]
                            else "draining" if (r["draining"]
                                                or r["adminDrained"])
                            else f"breaker {r['breaker']}")
                    _ok(f"    replica {r['name']} {r['url']}: "
                        f"live={str(r['live']).lower()} "
                        f"ready={str(r['ready']).lower()}, "
                        f"epoch {r['syncedEpoch']}/{st['fleetEpoch']} "
                        f"[{mark}]")
    except Exception as e:  # noqa: BLE001 — status must keep printing
        _ok(f"  serving fleet: unavailable ({e})")
    if getattr(args, "checkpoint_dir", None):
        try:
            from ..workflow.checkpoint import ShardedTrainCheckpointer

            st = ShardedTrainCheckpointer(args.checkpoint_dir).shard_status()
            latest = (st["latest_complete"] if st["latest_complete"] is not None
                      else "none")
            _ok(f"  checkpoints at {args.checkpoint_dir}: "
                f"complete steps {st['complete']}, latest complete {latest}")
            if st["partial"]:
                _ok(f"    partial step(s) {st['partial']} — incomplete save "
                    "(no manifest); discarded at next resume")
            for entry in st["discarded"]:
                _ok(f"    discarded partial step {entry['step']} "
                    f"({entry['reason']}, {entry.get('ts', '?')})")
            for pid, step in sorted(st["hosts"].items()):
                _ok(f"    host {pid}: newest shard at step {step}")
        except Exception as e:  # noqa: BLE001
            _ok(f"  checkpoints at {args.checkpoint_dir}: unavailable ({e})")
    try:
        done = Storage.get_metadata().engine_instance_get_by_status("COMPLETED")
        for inst in done[:3]:  # newest first; keep status terse
            phases = json.loads(inst.phase_times) if inst.phase_times else []
            if not phases:
                continue
            total = sum(dt for _, dt in phases)
            breakdown = ", ".join(
                f"{p}={dt:.2f}s"
                for p, dt in sorted(phases, key=lambda x: -x[1]))
            _ok(f"  completed run {inst.id}: {total:.2f}s ({breakdown})")
            # ISSUE 12: per-attempt convergence summary from the run's
            # stamped ConvergenceTracker record
            try:
                attempts = (json.loads(inst.convergence)
                            if getattr(inst, "convergence", "") else [])
            except ValueError:
                attempts = []
            for n, att in enumerate(attempts):
                loss = att.get("finalLoss")
                step = att.get("meanStepSeconds")
                _ok(f"    convergence attempt {n}: "
                    f"{att.get('iterations', 0)} iteration(s), "
                    f"final loss "
                    f"{f'{loss:.4f}' if loss is not None else 'n/a'}, "
                    f"mean step "
                    f"{f'{step * 1e3:.1f}ms' if step is not None else 'n/a'}")
            # ISSUE 15: stamped eval result + tuning leaderboard
            if getattr(inst, "evaluator_results", ""):
                _ok(f"    eval: {inst.evaluator_results}")
            try:
                tdoc = (json.loads(inst.tuning)
                        if getattr(inst, "tuning", "") else None)
            except ValueError:
                tdoc = None
            if tdoc:
                rows = tdoc.get("trials", [])
                done_rows = sorted(
                    (r for r in rows if r.get("status") == "COMPLETED"),
                    key=lambda r: (r.get("score") is not None,
                                   r.get("score")),
                    reverse=not tdoc.get("lowerIsBetter"))
                _ok(f"    tuning: {len(rows)} trial(s), "
                    f"{tdoc.get('gridMode')} grid "
                    f"({tdoc.get('gridSeconds')}s), "
                    f"metric {tdoc.get('metricHeader')}")
                for r in done_rows[:3]:
                    star = ("  <== winner"
                            if r.get("trial") == tdoc.get("bestTrial")
                            else "")
                    _ok(f"      trial #{r.get('trial')}: "
                        f"{r.get('score')}{star}")
                for r in rows:
                    if r.get("status") != "COMPLETED":
                        _ok(f"      trial #{r.get('trial')} FAILED: "
                            f"{r.get('error')}")
    except Exception as e:  # noqa: BLE001
        _ok(f"  completed runs: unavailable ({e})")
    try:
        from ..storage.backup import status_lines as _dr_status

        for ln in _dr_status():
            _ok(f"  {ln}")
    except Exception as e:  # noqa: BLE001
        _ok(f"  disaster recovery: unavailable ({e})")
    try:
        import jax

        devs = jax.devices()
        _ok(f"  devices: {len(devs)} x {devs[0].platform if devs else '-'}")
    except Exception as e:  # noqa: BLE001
        _ok(f"  devices: unavailable ({e})")
    if all(s == "ok" for s in statuses.values()):
        _ok("(sleeping 5 seconds for all messages to show up...)"
            if False else "Your system is all ready to go.")
        return 0
    return 1


def _fmt_bytes(n) -> str:
    try:
        n = float(n)
    except (TypeError, ValueError):
        return "?"
    for unit in ("B", "KiB", "MiB", "GiB"):
        if abs(n) < 1024.0 or unit == "GiB":
            return f"{n:.1f}{unit}" if unit != "B" else f"{int(n)}B"
        n /= 1024.0
    return f"{n:.1f}GiB"


def _top_frame(stats: dict, prev: tuple[float, int] | None) -> list[str]:
    """Render one `pio top` frame from an engine /stats.json snapshot.
    ``prev`` is (monotonic_ts, requestCount) from the previous frame —
    the qps window. Pure function of its inputs (unit-testable)."""
    lines: list[str] = []
    mode = (stats.get("resilience") or {}).get("mode", "?")
    count = int(stats.get("requestCount") or 0)
    qps = None
    if prev is not None:
        dt = time.monotonic() - prev[0]
        if dt > 0:
            qps = max(0, count - prev[1]) / dt
    serving = (stats.get("latency") or {}).get("serving") or {}
    p50 = serving.get("p50")
    lines.append(
        f"pio top · mode={mode} · requests={count}"
        + (f" · qps={qps:.1f}" if qps is not None else "")
        + (f" · p50={p50 * 1e3:.2f}ms" if p50 else ""))
    slo = stats.get("slo") or {}
    breaching = [o["name"] for o in slo.get("objectives", [])
                 if o.get("breaching")]
    burns = [((o.get("windows") or {}).get("5m") or {}).get("burnRate")
             for o in slo.get("objectives", [])]
    burns = [b for b in burns if b is not None]
    lines.append(
        f"slo: {'BREACHING ' + ','.join(breaching) if breaching else 'ok'}"
        + (f" · max 5m burn={max(burns):.2f}x" if burns else ""))
    cache = stats.get("execCache") or {}
    if cache:
        lines.append(
            f"exec cache: {cache.get('size', 0)} entries "
            f"({cache.get('pinned', 0)} pinned) · "
            f"hit rate {cache.get('hitRate', 0.0):.0%} · "
            f"{cache.get('evictions', 0)} evictions")
    device = stats.get("device") or {}
    comps = device.get("components") or {}
    lines.append(
        f"hbm ledger: total {_fmt_bytes(device.get('totalBytes'))} · "
        f"watermark {_fmt_bytes(device.get('watermarkBytes'))}")
    for name, c in sorted(comps.items(),
                          key=lambda kv: -kv[1].get("bytes", 0)):
        flag = "  [analysisUnavailable]" if c.get("analysisUnavailable") \
            else ""
        lines.append(
            f"  {name:12s} {_fmt_bytes(c.get('bytes')):>10s}  "
            f"{c.get('entries', 0)} executable(s){flag}")
    for e in (device.get("topExecutables") or [])[:5]:
        lines.append(
            f"    {e.get('kind', '?'):8s} {_fmt_bytes(e.get('totalBytes')):>10s}"
            f"  compile={e.get('compileSeconds', 0.0):.2f}s  {e.get('key', '')[:48]}")
    waste = device.get("paddingWaste") or {}
    if waste.get("count"):
        lines.append(
            f"padding waste: p50={waste.get('p50', 0.0):.0%} "
            f"p95={waste.get('p95', 0.0):.0%} over {waste['count']} "
            "dispatch(es)")
    train = stats.get("train") or {}
    for source in sorted(train):
        block = train[source] or {}
        live = block.get("live")
        if live:
            hist = live.get("history") or []
            last = hist[-1] if hist else {}
            total = live.get("totalIterations")
            parts = [f"iter {live.get('iterations', 0)}"
                     + (f"/{total}" if total else "")]
            if last.get("loss") is not None:
                parts.append(f"loss={last['loss']:.4f}")
            if last.get("deltaNorm") is not None:
                parts.append(f"Δ={last['deltaNorm']:.3g}")
            if last.get("stepSeconds") is not None:
                parts.append(f"step={last['stepSeconds'] * 1e3:.0f}ms")
            lines.append(f"{source}: live · " + " · ".join(parts))
        attempts = block.get("attempts") or []
        if attempts:
            att = attempts[-1]
            loss = att.get("finalLoss")
            lines.append(
                f"{source}: {len(attempts)} finished attempt(s), last "
                f"{att.get('status', '?')} after "
                f"{att.get('iterations', 0)} iteration(s)"
                + (f", final loss {loss:.4f}" if loss is not None else ""))
    if not train:
        lines.append("train: no convergence telemetry yet")
    return lines


def _fleet_top_frame(fstats: dict) -> list[str]:
    """Render one `pio top --fleet` frame from a router
    /fleet/stats.json body: fleet header (merged qps/p50/p99/SLO) +
    one row per replica from the windowed signals. Pure function of
    its input (unit-testable), like _top_frame."""
    lines: list[str] = []
    replicas = fstats.get("replicas") or {}
    merged = fstats.get("merged") or {}
    serving = (merged.get("histograms") or {}).get(
        "pio_serving_latency_seconds") or {}
    qps = sum((r.get("window") or {}).get("qps") or 0.0
              for r in replicas.values())
    header = (f"pio top · fleet · epoch {fstats.get('fleetEpoch', '?')} · "
              f"{len(fstats.get('eligible') or [])}/{len(replicas)} "
              f"eligible · qps={qps:.1f}")
    if serving.get("p50") is not None:
        header += (f" · p50={serving['p50'] * 1e3:.2f}ms "
                   f"p99={serving['p99'] * 1e3:.2f}ms (merged)")
    lines.append(header)
    slo = fstats.get("slo") or {}
    breaching = [o["name"] for o in slo.get("objectives", [])
                 if o.get("breaching")]
    burns = [((o.get("windows") or {}).get("5m") or {}).get("burnRate")
             for o in slo.get("objectives", [])]
    burns = [b for b in burns if b is not None]
    lines.append(
        f"fleet slo: "
        f"{'BREACHING ' + ','.join(breaching) if breaching else 'ok'}"
        + (f" · max 5m burn={max(burns):.2f}x" if burns else "")
        + f" · over {slo.get('replicas', 0)} replica(s)")
    outliers = fstats.get("outliers") or {}
    lines.append(f"{'replica':10s} {'age':>6s} {'qps':>8s} {'p50':>9s} "
                 f"{'p99':>9s} {'err%':>6s} {'shed%':>6s}  flags")
    for name in sorted(replicas):
        r = replicas[name]
        w = r.get("window") or {}
        flags = []
        if r.get("stale"):
            flags.append("STALE")
        if outliers.get(name):
            flags.append("OUTLIER:" + ",".join(outliers[name]))
        age = r.get("ageSeconds")

        def _ms(v):
            return f"{v * 1e3:.2f}ms" if v is not None else "-"

        def _pct(v):
            return f"{v * 100:.1f}" if v is not None else "-"

        qps_s = f"{w['qps']:g}" if w.get("qps") is not None else "-"
        lines.append(
            f"{name:10s} {(f'{age:.1f}s' if age is not None else '-'):>6s} "
            f"{qps_s:>8s} "
            f"{_ms(w.get('p50')):>9s} {_ms(w.get('p99')):>9s} "
            f"{_pct(w.get('errorFraction')):>6s} "
            f"{_pct(w.get('shedRate')):>6s}  {' '.join(flags)}")
    coll = fstats.get("collector") or {}
    dropped = coll.get("droppedFamilies") or []
    if dropped:
        lines.append(f"merge: DROPPED families (bucket-bounds skew): "
                     f"{', '.join(dropped)}")
    return lines


def cmd_top(args) -> int:
    """ISSUE 12: `pio top` — one refreshing terminal view combining the
    serving posture (qps/p50/mode/SLO burn from /stats.json), the HBM
    ledger by component, and train/stream convergence progress.
    ISSUE 20: `--fleet` points it at a fleet router instead and renders
    the merged fleet header + per-replica table from /fleet/stats.json."""
    import urllib.request

    suffix = "/fleet/stats.json" if args.fleet else "/stats.json"
    url = args.url.rstrip("/") + suffix
    prev: tuple[float, int] | None = None
    frames = 0
    while True:
        try:
            with urllib.request.urlopen(url, timeout=10) as r:
                stats = json.loads(r.read().decode())
            if args.fleet:
                lines = _fleet_top_frame(stats)
            else:
                lines = _top_frame(stats, prev)
                prev = (time.monotonic(),
                        int(stats.get("requestCount") or 0))
        except OSError as e:
            lines = [f"pio top · {'fleet router' if args.fleet else 'engine server'}"
                     f" unreachable at {args.url}: {e}"]
        if not args.once:
            # clear + home, like top(1); plain print for --once so the
            # frame is capturable/testable
            print("\x1b[2J\x1b[H", end="")
        for ln in lines:
            _ok(ln)
        frames += 1
        if args.once:
            return 0
        try:
            time.sleep(args.interval)
        except KeyboardInterrupt:
            return 0


def cmd_backup(args) -> int:
    """Consistent, manifest-committed snapshot of every durable store
    under $PIO_HOME: sqlite databases through the online backup API,
    everything else behind a post-cut size fence; incremental by
    default (unchanged files hardlink to the previous complete
    backup)."""
    from ..storage import backup as drb

    try:
        rep = drb.create_backup(
            backup_dir=args.backup_dir, keep=args.keep,
            mode="full" if args.full else "incremental",
            journal_dir=args.journal_dir,
            checkpoint_dir=args.checkpoint_dir)
    except drb.BackupError as e:
        _die(str(e))
    if args.json:
        _ok(json.dumps(rep, indent=2, sort_keys=True))
        return 0
    _ok(f"backup #{rep['seq']} complete ({rep['mode']}"
        + (f", based on #{rep['basedOn']}" if rep["basedOn"] else "")
        + f"): {rep['files']} files, {_fmt_bytes(rep['bytes'])} written, "
          f"{rep['dedupedFiles']} hardlink-deduped, {rep['durationS']}s "
          f"-> {rep['dir']}")
    return 0


def cmd_restore(args) -> int:
    """Rebuild a home from a manifest-complete backup: every checksum
    re-verified before any file lands, refuses a non-empty target
    without --force (exit 2), then replays the backed-up WAL tail
    through the id-keyed drain path — point-in-time with --until."""
    from ..storage import Storage
    from ..storage import backup as drb

    target = args.target or Storage.home()
    root = args.backup_dir or str(Path(target) / "backups")
    try:
        rep = drb.restore(root, target, backup_id=args.backup_id,
                          force=args.force, until=args.until,
                          replay=not args.no_replay)
    except drb.RestoreRefused as e:
        _die(str(e), code=2)
    except drb.BackupError as e:
        _die(str(e))
    if args.json:
        _ok(json.dumps(rep, indent=2, sort_keys=True))
        return 0
    for s in rep["skippedPartial"]:
        _ok(f"warning: backup #{s} is incomplete or corrupt — ignored")
    cut = " (point-in-time cut applied, WAL tail dropped)" \
        if rep["walTruncated"] else ""
    _ok(f"restored backup #{rep['backup']} into {rep['target']}: "
        f"{rep['files']} files, {_fmt_bytes(rep['bytes'])}, "
        f"{rep['replayedRecords']} WAL record(s) replayed{cut}")
    return 0


def cmd_import(args) -> int:
    from .import_export import import_events, resolve_channel

    try:
        channel = resolve_channel(args.appid, args.channel)
    except ValueError as e:
        _die(str(e))
    n = import_events(args.input, args.appid, channel)
    _ok(f"Imported {n} events to app {args.appid}.")
    return 0


def cmd_export(args) -> int:
    from .import_export import export_events, resolve_channel

    try:
        channel = resolve_channel(args.appid, args.channel)
    except ValueError as e:
        _die(str(e))
    n = export_events(args.output, args.appid, channel)
    _ok(f"Exported {n} events from app {args.appid}.")
    return 0


def cmd_template(args) -> int:
    from .templates import get_template, list_templates

    if args.template_command == "list":
        for name, desc in list_templates():
            _ok(f"  {name:32s} {desc}")
    else:
        get_template(args.name, Path(args.directory or args.name))
        _ok(f"Engine template {args.name!r} created at {args.directory or args.name}")
    return 0


# ---------------------------------------------------------------------------
# parser
# ---------------------------------------------------------------------------

def _add_engine_args(p: argparse.ArgumentParser):
    p.add_argument("--engine-dir", default=".", help="engine directory")
    p.add_argument("--engine-json", default="engine.json",
                   help="engine variant file (reference --engine-variant)")


def build_parser() -> argparse.ArgumentParser:
    p = argparse.ArgumentParser(
        prog="pio", description="predictionio_tpu console"
    )
    p.add_argument("--verbose", "-v", action="store_true")
    sub = p.add_subparsers(dest="command", required=True)

    sp = sub.add_parser("version")

    sp = sub.add_parser("app")
    app_sub = sp.add_subparsers(dest="app_command", required=True)
    x = app_sub.add_parser("new")
    x.add_argument("name")
    x.add_argument("--description")
    x.add_argument("--access-key")
    x = app_sub.add_parser("list")
    x = app_sub.add_parser("show")
    x.add_argument("name")
    x = app_sub.add_parser("delete")
    x.add_argument("name")
    x = app_sub.add_parser("data-delete")
    x.add_argument("name")
    x.add_argument("--channel")
    x.add_argument("--before", metavar="ISO_TIME",
                   help="trim: delete only events with eventTime before "
                        "this ISO-8601 instant (default: delete ALL data)")
    x = app_sub.add_parser("channel-new")
    x.add_argument("name")
    x.add_argument("channel")
    x = app_sub.add_parser("channel-delete")
    x.add_argument("name")
    x.add_argument("channel")

    sp = sub.add_parser("accesskey")
    ak_sub = sp.add_subparsers(dest="ak_command", required=True)
    x = ak_sub.add_parser("new")
    x.add_argument("app_name")
    x.add_argument("--event", action="append")
    x = ak_sub.add_parser("list")
    x.add_argument("app_name", nargs="?")
    x = ak_sub.add_parser("delete")
    x.add_argument("key")

    for name in ("build", "unregister"):
        sp = sub.add_parser(name)
        _add_engine_args(sp)

    sp = sub.add_parser("train")
    _add_engine_args(sp)
    sp.add_argument("--batch", default="")
    sp.add_argument("--skip-sanity-check", action="store_true")
    sp.add_argument("--stop-after-read", action="store_true")
    sp.add_argument("--stop-after-prepare", action="store_true")
    sp.add_argument("--mesh", help="mesh shape, e.g. 4x2 (data x model)")
    sp.add_argument("--checkpoint-dir", default=None,
                    help="mid-training checkpoint directory; rerunning "
                         "train with the same dir resumes from the latest "
                         "saved step")
    sp.add_argument("--checkpoint-every", type=int, default=5,
                    help="checkpoint every N training iterations "
                         "(with --checkpoint-dir)")
    sp.add_argument("--profile-dir", default=None,
                    help="capture a jax.profiler trace of training into "
                         "this directory (view with TensorBoard/XProf)")
    sp.add_argument("--max-retries", type=int, default=2,
                    help="supervised retries for transient failures "
                         "(preemption/device-lost); each retry resumes "
                         "from the latest checkpoint (default 2)")
    sp.add_argument("--retry-backoff-s", type=float, default=1.0,
                    help="base of the jittered exponential retry backoff "
                         "in seconds (default 1.0)")
    sp.add_argument("--train-budget-s", type=float, default=0.0,
                    help="wall-clock budget for the whole training run; "
                         "past it the run aborts cleanly with status "
                         "ABORTED instead of hanging (0 = unlimited)")
    sp.add_argument("--coordinator", default=None, metavar="HOST:PORT",
                    help="jax.distributed coordinator address for elastic "
                         "multi-host training; every process passes the "
                         "same address (process 0 hosts it)")
    sp.add_argument("--num-processes", type=int, default=None,
                    help="total process count of the multi-host run; with "
                         "--checkpoint-dir each process writes only its "
                         "factor shard and a later run at a DIFFERENT "
                         "count resumes from the same manifests (N->M "
                         "elastic resume)")
    sp.add_argument("--process-id", type=int, default=None,
                    help="this process's id in [0, --num-processes); "
                         "process 0 commits checkpoint manifests")

    sp = sub.add_parser("eval")
    _add_engine_args(sp)
    sp.add_argument("evaluation", help="module:EvaluationClass")
    sp.add_argument("engine_params_generator", nargs="?",
                    help="module:EngineParamsGenerator")
    sp.add_argument("--batch", default="")
    sp.add_argument("--fast", action="store_true",
                    help="memoize pipeline prefixes across grid variants "
                         "(FastEvalEngine)")

    sp = sub.add_parser(
        "tune",
        help="mesh-packed hyperparameter sweep: train the WHOLE "
             "EngineParams grid as one compiled program, rank the "
             "trials, train the winner, and optionally deploy it behind "
             "an eval gate")
    _add_engine_args(sp)
    sp.add_argument("evaluation", help="module:EvaluationClass "
                                       "(engine + metrics)")
    sp.add_argument("engine_params_generator", nargs="?",
                    help="module:EngineParamsGenerator (default: the "
                         "evaluation's engine_params_list)")
    sp.add_argument("--batch", default="")
    sp.add_argument("--max-retries", type=int, default=0,
                    help="per-trial retries for transient scoring "
                         "failures; a trial that still fails becomes a "
                         "FAILED leaderboard row, never kills the sweep "
                         "(default 0)")
    sp.add_argument("--retry-backoff-s", type=float, default=0.25,
                    help="base of the per-trial jittered retry backoff "
                         "(default 0.25)")
    sp.add_argument("--train-max-retries", type=int, default=2,
                    help="supervised retries for the WINNER's full "
                         "training run (default 2)")
    sp.add_argument("--train-budget-s", type=float, default=0.0,
                    help="wall-clock budget for the winner's training "
                         "run (0 = unlimited)")
    sp.add_argument("--eval-gate", type=float, default=None,
                    metavar="DELTA",
                    help="promotion gate: deploy only if the winner's "
                         "score does not regress more than DELTA vs the "
                         "incumbent instance's stamped score (flipped "
                         "for lower-is-better metrics; default: "
                         "ungated)")
    sp.add_argument("--deploy", action="store_true",
                    help="after tuning, serve the winner's instance "
                         "(honors --eval-gate: a held gate exits 2 "
                         "without deploying)")
    sp.add_argument("--ip", default="0.0.0.0")
    sp.add_argument("--port", type=int, default=8000)

    sp = sub.add_parser("deploy")
    _add_engine_args(sp)
    sp.add_argument("--ip", default="0.0.0.0")
    sp.add_argument("--port", type=int, default=8000)
    sp.add_argument("--engine-instance-id")
    sp.add_argument("--variant-of", type=int, default=None, metavar="PORT",
                    help="register this engine as another serving variant "
                         "of the engine server already running on PORT "
                         "(same process, same device pool) instead of "
                         "binding a new server; the new variant starts as "
                         "a candidate with --weight traffic")
    sp.add_argument("--weight", type=float, default=0.0,
                    help="initial traffic weight for --variant-of "
                         "(hashed A/B share relative to the other "
                         "variants' weights; 0 = forced-header only)")
    sp.add_argument("--variant-id", default=None,
                    help="variant name for --variant-of (default: the "
                         "engine.json variantId, else the engine dir name)")
    sp.add_argument("--feedback", action="store_true")
    sp.add_argument("--event-server-url", default="http://localhost:7070")
    sp.add_argument("--accesskey")
    sp.add_argument("--batch-window-ms", type=float, default=1.0,
                    help="micro-batch window for concurrent queries "
                         "(0 disables batching)")
    sp.add_argument("--batch-max", type=int, default=128,
                    help="max queries per micro-batch")
    sp.add_argument("--batch-inflight", type=int, default=8,
                    help="max batched calls live at once, those in their "
                         "host work after the device step included "
                         "(degraded mode halves it). Not the depth of the "
                         "device's queue: a batch is cut only while fewer "
                         "than two are short of the end of their device "
                         "step")
    sp.add_argument("--retriever-mesh", default="0",
                    help="shard the serving catalog over this many devices "
                         "(model axis; 0/1 = single-device catalog; 'auto' "
                         "picks 1/2/4/8-way from the catalog-size cost "
                         "model at deploy time)")
    sp.add_argument("--retrieval-mode", choices=["exact", "ann"],
                    default=None,
                    help="override the engine-params retrieval.mode: 'ann' "
                         "serves from the quantized IVF index (exact "
                         "fallback below its min-items floor), 'exact' "
                         "forces brute-force scoring")
    sp.add_argument("--deadline-ms", type=float, default=0.0,
                    help="default end-to-end deadline per query in ms "
                         "(expired queries answer 504; 0 disables; the "
                         "X-PIO-Deadline-Ms request header can tighten it)")
    sp.add_argument("--dispatch-timeout-s", type=float, default=30.0,
                    help="stuck-dispatch watchdog: a batch dispatch "
                         "exceeding this reclaims its pipeline slot and "
                         "flips the server degraded (0 disables)")
    sp.add_argument("--degraded-cooldown-s", type=float, default=15.0,
                    help="seconds between half-open probe batches while "
                         "the server is degraded")
    sp.add_argument("--admission", action="store_true",
                    help="adaptive admission control: shed queries with "
                         "429 + Retry-After when queue depth, queue-wait "
                         "p99 or deadline-expiry rate say the server is "
                         "overloaded; enables brownout degradation")
    sp.add_argument("--admission-queue-high", type=int, default=64,
                    help="microbatch queue depth treated as full "
                         "overload pressure (admission signal)")
    sp.add_argument("--admission-wait-budget-ms", type=float, default=0.0,
                    help="queue-wait p99 treated as full overload "
                         "pressure (0 = half the --deadline-ms)")
    sp.add_argument("--rate-limit-qps", type=float, default=0.0,
                    help="per-client token-bucket rate limit (keyed on "
                         "access key; 0 disables; over-limit answers "
                         "429 + Retry-After)")
    sp.add_argument("--rate-limit-burst", type=float, default=0.0,
                    help="token-bucket burst headroom "
                         "(0 = 2x --rate-limit-qps)")
    sp.add_argument("--brownout-topk", type=int, default=10,
                    help="top-k clamp applied to queries while the "
                         "server is in brownout")
    sp.add_argument("--slo-latency-ms", type=float, default=0.0,
                    help="latency-SLO threshold in ms (bad = slower); "
                         "0 uses --deadline-ms, else 250")
    sp.add_argument("--flight-capacity", type=int, default=256,
                    help="flight recorder ring size: how many recent "
                         "request waterfalls /debug/flight.json and "
                         "incident dumps retain (default 256)")
    sp.add_argument("--flight-dir", default=None,
                    help="incident dump directory (default "
                         "$PIO_FLIGHT_DIR or ~/.pio_tpu/flight)")
    sp.add_argument("--capture-dir", default=None,
                    help="enable golden-traffic capture: persist sampled "
                         "request/response/provenance triples to this "
                         "journal directory (replay with `pio replay`)")
    sp.add_argument("--capture-sample", type=float, default=0.01,
                    help="fraction of served queries captured "
                         "(default 0.01; 1.0 captures everything)")
    sp.add_argument("--capture-ring", type=int, default=256,
                    help="in-memory capture ring size; the ring flushes "
                         "to disk when full and on incidents")
    sp.add_argument("--capture-max-mb", type=float, default=64.0,
                    help="on-disk capture journal cap in MiB; the oldest "
                         "captured segments are dropped past it")
    sp.add_argument("--shadow-target", default=None,
                    help="mirror sampled live traffic fire-and-forget to "
                         "this engine-server base URL and diff answers "
                         "online (pio_shadow_diff_total{tier})")
    sp.add_argument("--shadow-sample", type=float, default=1.0,
                    help="fraction of served queries shadow-mirrored")
    sp.add_argument("--prewarm-async", action="store_true",
                    help="bind the port before the executable prewarm "
                         "and run the prewarm in the background; "
                         "/health.json reports live-but-not-ready until "
                         "it completes (fleet replicas start this way "
                         "so the router can hold hashed traffic)")

    sp = sub.add_parser(
        "fleet",
        help="replicated serving fleet: M engine-server replicas "
             "behind a consistent-hash routing tier with per-replica "
             "breakers, hedged retry and delta fan-out (ISSUE 17)")
    f_sub = sp.add_subparsers(dest="fleet_command", required=True)
    x = f_sub.add_parser(
        "start",
        help="spawn N replica processes (pio deploy children sharing "
             "this storage config) and run the router in the foreground")
    _add_engine_args(x)
    x.add_argument("--ip", default="0.0.0.0")
    x.add_argument("--port", type=int, default=8000,
                   help="router port — clients keep talking to :8000")
    x.add_argument("--replicas", type=int, default=2,
                   help="replica processes to spawn on consecutive "
                        "ports starting at --base-port")
    x.add_argument("--base-port", type=int, default=8001)
    x.add_argument("--replica-urls", default=None,
                   help="comma-separated engine-server URLs to front "
                        "INSTEAD of spawning local replicas")
    x.add_argument("--replica-arg", action="append", default=[],
                   metavar="ARGS",
                   help="extra `pio deploy` arguments passed to every "
                        "spawned replica (repeatable; space-split)")
    x.add_argument("--probe-interval-s", type=float, default=1.0,
                   help="per-replica /health.json probe cadence; a dead "
                        "replica's breaker opens within one interval")
    x.add_argument("--breaker-reset-s", type=float, default=3.0,
                   help="open -> half-open probe window per replica")
    x.add_argument("--deadline-ms", type=float, default=0.0,
                   help="default end-to-end deadline the router enforces "
                        "and forwards (decremented) to replicas")
    x.add_argument("--max-hedges", type=int, default=1,
                   help="bounded hedged retries of an idempotent query "
                        "onto sibling replicas (0 disables)")
    x.add_argument("--spillover-inflight", type=int, default=32,
                   help="router-side in-flight requests on a hash owner "
                        "past which a hot key spills to the least-"
                        "loaded eligible replica")
    x.add_argument("--journal-max", type=int, default=64,
                   help="delta fan-out journal entries retained for "
                        "epoch reconciliation; a replica lagging past "
                        "the journal takes a full reload instead")
    x.add_argument("--slo-drain-burn", type=float, default=0.0,
                   help="drain a replica from hashed traffic while its "
                        "worst 5m SLO burn rate is at or above this "
                        "(0 disables the policy)")
    x.add_argument("--canary-sample", type=int, default=8,
                   help="recent queries replayed as the shadow-diff "
                        "canary after the first replica of a rolling "
                        "reload wave (0 disables the gate)")
    x.add_argument("--canary-max-mismatch", type=float, default=0.25,
                   help="mismatch-tier fraction above which the rolling "
                        "reload wave aborts with the old model still "
                        "serving on the remaining replicas")
    x.add_argument("--supervise", action="store_true",
                   help="own the replica processes: reap exits, respawn "
                        "a crashed replica on its original port with "
                        "jittered exponential backoff, quarantine a "
                        "crash-looping one (ISSUE 18)")
    x.add_argument("--max-respawns", type=int, default=5,
                   help="deaths inside --crash-window-s that flip a "
                        "replica from respawn-with-backoff to "
                        "quarantined")
    x.add_argument("--crash-window-s", type=float, default=60.0,
                   help="sliding window for crash-loop detection")
    x.add_argument("--quarantine-s", type=float, default=300.0,
                   help="cooldown before a quarantined replica is "
                        "retried")
    x.add_argument("--state-dir", default=None,
                   help="durable router state (fleet epoch marker + "
                        "delta journal); default "
                        "$PIO_HOME/run/fleet-router — a restarted "
                        "router resumes at the durable epoch floor")
    x.add_argument("--no-collect-metrics", action="store_true",
                   help="disable the fleet metric collector (no "
                        "/fleet/metrics, /fleet/stats.json merge, "
                        "outlier flags or incident bundles)")
    x.add_argument("--metrics-stale-after-s", type=float, default=10.0,
                   help="a replica whose last metrics scrape is older "
                        "than this is excluded from fleet merges "
                        "(its snapshot is kept and stamped ageSeconds)")
    x.add_argument("--outlier-band", type=float, default=0.75,
                   help="flag a replica pio_fleet_outlier when its "
                        "windowed p99/errorFraction/shedRate exceeds "
                        "the fleet median by this fraction")
    x.add_argument("--incident-dir", default=None,
                   help="correlated fleet-incident bundles directory "
                        "(default $PIO_HOME/run/fleet-incidents)")
    x = f_sub.add_parser(
        "status",
        help="per-replica liveness, readiness, breaker state, patch-"
             "epoch lag, windowed p99/qps and outlier flags from the "
             "router's /fleet.json + /fleet/stats.json",
        description="Print one row per replica: liveness, readiness, "
                    "breaker state, patch-epoch lag, windowed qps/p99 "
                    "from the router's metric collector, [OUTLIER: ...] "
                    "flags for replicas straying from the fleet median, "
                    "and [metrics stale] when the last scrape aged out.")
    x.add_argument("--router-url", default=None,
                   help="fleet router base URL (default: the recorded "
                        "$PIO_HOME/run/fleet.json, else "
                        "http://127.0.0.1:8000)")
    x = f_sub.add_parser(
        "drain",
        help="take one replica out of hashed rotation (it finishes "
             "in-flight work; the router stops routing to it)")
    x.add_argument("--router-url", default=None,
                   help="fleet router base URL (default: the recorded "
                        "$PIO_HOME/run/fleet.json, else "
                        "http://127.0.0.1:8000)")
    x.add_argument("--replica", required=True,
                   help="replica name (r0, r1, ...) or URL")
    x.add_argument("--stop", action="store_true",
                   help="also ask the replica to /stop (graceful "
                        "process exit after its own drain)")
    x = f_sub.add_parser(
        "restart",
        help="rolling restart wave: drain -> restart -> re-ready one "
             "replica at a time, gated by the shadow-diff canary after "
             "the first (requires a --supervise router)")
    x.add_argument("--router-url", default=None,
                   help="fleet router base URL (default: the recorded "
                        "$PIO_HOME/run/fleet.json, else "
                        "http://127.0.0.1:8000)")
    x.add_argument("--canary-sample", type=int, default=8,
                   help="recent queries replayed as the shadow-diff "
                        "canary after the first restarted replica "
                        "(0 disables the gate)")
    x.add_argument("--timeout-s", type=float, default=600.0,
                   help="client-side wait for the whole wave")

    sp = sub.add_parser("batchpredict")
    _add_engine_args(sp)
    sp.add_argument("--input", required=True,
                    help="queries file, one JSON object per line")
    sp.add_argument("--output", required=True,
                    help="predictions file (JSONL, query + prediction/error)")
    sp.add_argument("--engine-instance-id")
    sp.add_argument("--batch-max", type=int, default=128,
                    help="queries per batched predict call")
    sp.add_argument("--retriever-mesh", type=int, default=0,
                    help="shard the scoring catalog over this many devices")

    sp = sub.add_parser("bench")
    b_sub = sp.add_subparsers(dest="bench_command", required=True)
    x = b_sub.add_parser("backup",
                         help="synthetic backup throughput: one full "
                              "backup then incrementals over an "
                              "unchanged home (dedup should approach "
                              "100%%)")
    x.add_argument("--files", type=int, default=64,
                   help="synthetic blob count (default 64)")
    x.add_argument("--size-kb", type=int, default=256,
                   help="bytes per blob in KB (default 256)")
    x.add_argument("--rounds", type=int, default=2,
                   help="backups to take; round 0 is full, the rest "
                        "incremental (default 2)")
    x.add_argument("--json", action="store_true")

    sp = sub.add_parser("undeploy")
    sp.add_argument("--ip", default="localhost")
    sp.add_argument("--port", type=int, default=8000)

    sp = sub.add_parser("eventserver")
    sp.add_argument("--ip", default="0.0.0.0")
    sp.add_argument("--port", type=int, default=7070)
    sp.add_argument("--stats", action="store_true")
    sp.add_argument("--journal-dir", default=None,
                    help="enable durable ingestion: write-ahead journal "
                         "directory (events ack 201 after a durable "
                         "append; a background drainer feeds the backend)")
    sp.add_argument("--journal-fsync", default="batch",
                    choices=["always", "batch", "never"],
                    help="journal fsync policy: per-record, per-request "
                         "(default), or OS page cache")
    sp.add_argument("--journal-max-mb", type=int, default=256,
                    help="journal capacity; past it ingestion answers "
                         "503 + Retry-After (backpressure, default 256)")
    sp.add_argument("--journal-partitions", type=int, default=1,
                    help="shard the journal + drainers N ways by "
                         "hash(entityType, entityId): per-entity ordering, "
                         "concurrent fsync and drain; resizing N requires "
                         "drained journals (default 1)")
    sp.add_argument("--admission", action="store_true",
                    help="adaptive admission control: shed ingestion "
                         "with 429 + Retry-After when journal fill/lag "
                         "says the drainer is falling behind")
    sp.add_argument("--rate-limit-qps", type=float, default=0.0,
                    help="per-access-key token-bucket rate limit "
                         "(0 disables; over-limit answers 429)")
    sp.add_argument("--rate-limit-burst", type=float, default=0.0,
                    help="token-bucket burst headroom "
                         "(0 = 2x --rate-limit-qps)")

    sp = sub.add_parser("stream",
                        help="streaming online learning: tail the event "
                             "server's journal, fold events into user "
                             "factors, hot-patch the deployed engine "
                             "server (POST /reload/delta)")
    _add_engine_args(sp)
    sp.add_argument("--journal-dir", required=True,
                    help="the event server's write-ahead journal "
                         "directory to tail (read-only; an independent "
                         "follow cursor per partition, never the "
                         "drainer's cursor.json)")
    sp.add_argument("--engine-url", default="http://localhost:8000",
                    help="deployed engine server to hot-patch "
                         "(default http://localhost:8000)")
    sp.add_argument("--engine-instance-id",
                    help="fold in against this trained instance instead "
                         "of the latest COMPLETED one")
    sp.add_argument("--batch-window-ms", type=float, default=500.0,
                    help="poll/fold cadence: events are accumulated per "
                         "user and folded in one batched solve per "
                         "window (default 500)")
    sp.add_argument("--eval-gate", type=float, default=None,
                    help="eval-gated promotion: leave-one-out hit@k on "
                         "each batch's holdout slice; skip publishing "
                         "when the batch metric regresses more than this "
                         "below the current serving baseline (default: "
                         "gate off)")
    sp.add_argument("--eval-k", type=int, default=10,
                    help="k for the gate's holdout hit@k (default 10)")
    sp.add_argument("--journal-partitions", type=int, default=0,
                    help="journal partition count; 0 infers it from the "
                         "journal's partitions.json marker (default 0)")
    sp.add_argument("--follow-name", default="stream",
                    help="follow-cursor family name (follow-<name>.json); "
                         "distinct names = independent consumers")
    sp.add_argument("--max-records", type=int, default=1024,
                    help="max journal records per partition per cycle")
    sp.add_argument("--fold-in-solver", choices=["host", "device"],
                    default="host",
                    help="'host' publishes float64-solved factors that "
                         "bitwise-match the fold_in_user reference; "
                         "'device' dispatches the jitted batched "
                         "Cholesky kernel (f32)")
    sp.add_argument("--breaker-threshold", type=int, default=5,
                    help="consecutive publish failures that open the "
                         "delta-publish circuit breaker (default 5)")
    sp.add_argument("--breaker-reset-s", type=float, default=5.0,
                    help="seconds between half-open probes while the "
                         "publish breaker is open (default 5)")
    sp.add_argument("--variant", default=None,
                    help="target serving variant for /reload/delta "
                         "patches on a multi-variant server (unknown or "
                         "retired variants are rejected 400; default: "
                         "the live variant)")

    sp = sub.add_parser("adminserver")
    sp.add_argument("--ip", default="127.0.0.1")
    sp.add_argument("--port", type=int, default=7071)

    sp = sub.add_parser("dashboard")
    sp.add_argument("--ip", default="127.0.0.1")
    sp.add_argument("--port", type=int, default=9000)
    sp.add_argument("--engine-url", default="http://localhost:8000",
                    help="engine server whose SLO burn rates and stage "
                         "waterfalls /slo.json proxies "
                         "(default http://localhost:8000)")

    sp = sub.add_parser("status")
    sp.add_argument("--checkpoint-dir", default=None,
                    help="also report this elastic (sharded) checkpoint "
                         "directory: complete/partial steps, discarded "
                         "partial-save history, per-host shard state")

    sp = sub.add_parser("backup",
                        help="consistent, manifest-committed snapshot of "
                             "all durable state under $PIO_HOME "
                             "(incremental by default)")
    sp.add_argument("--backup-dir", default=None,
                    help="backup root (default $PIO_HOME/backups)")
    sp.add_argument("--keep", type=int, default=5,
                    help="retain this many manifest-complete backups, "
                         "drop-oldest (default 5)")
    sp.add_argument("--full", action="store_true",
                    help="copy every byte instead of hardlinking files "
                         "unchanged since the previous complete backup")
    sp.add_argument("--journal-dir", default=None,
                    help="also snapshot this ingest WAL directory when it "
                         "lives outside $PIO_HOME")
    sp.add_argument("--checkpoint-dir", default=None,
                    help="also snapshot this training checkpoint "
                         "directory when it lives outside $PIO_HOME")
    sp.add_argument("--json", action="store_true")

    sp = sub.add_parser("restore",
                        help="rebuild $PIO_HOME from a complete backup: "
                             "re-verifies every checksum, then replays "
                             "the backed-up WAL tail (point-in-time "
                             "with --until)")
    sp.add_argument("--backup-dir", default=None,
                    help="backup root (default <target>/backups)")
    sp.add_argument("--backup-id", type=int, default=None,
                    help="backup sequence number to restore "
                         "(default: newest complete)")
    sp.add_argument("--target", default=None,
                    help="home to restore into (default $PIO_HOME)")
    sp.add_argument("--force", action="store_true",
                    help="allow restoring onto a non-empty target; "
                         "without it a non-empty target exits 2")
    sp.add_argument("--until", default=None, metavar="TS|SEQ",
                    help="point-in-time cut: replay the WAL tail only up "
                         "to this ISO-8601 eventTime or 1-based record "
                         "ordinal, then drop the rest of the tail")
    sp.add_argument("--no-replay", action="store_true",
                    help="restore files only; skip replaying the WAL "
                         "tail into the event store")
    sp.add_argument("--json", action="store_true")

    sp = sub.add_parser("admin")
    a_sub = sp.add_subparsers(dest="admin_command", required=True)
    x = a_sub.add_parser("reap",
                         help="flip stale-heartbeat INIT engine instances "
                              "(orphans of dead trainers) to ABANDONED")
    x.add_argument("--stale-after-s", type=float, default=600.0,
                   help="an INIT instance whose last heartbeat (or start) "
                        "is older than this is an orphan (default 600)")
    x.add_argument("--dry-run", action="store_true",
                   help="list the orphans without changing their status")
    x = a_sub.add_parser("metrics",
                         help="dump a telemetry registry (counters, "
                              "gauges, histogram quantiles): this "
                              "process's by default, a live server's "
                              "with --url — a fleet router is detected "
                              "and the merged fleet snapshot printed")
    x.add_argument("--json", action="store_true",
                   help="machine-readable snapshot instead of the table")
    x.add_argument("--url", default=None,
                   help="live server base URL; a fleet router's merged "
                        "snapshot (/fleet/stats.json) is preferred, a "
                        "plain engine server's /metrics is parsed")
    x = a_sub.add_parser("flight",
                         help="fetch a live engine server's flight "
                              "recorder: the last N request waterfalls "
                              "with mode/queue context")
    x.add_argument("--url", default="http://localhost:8000",
                   help="engine server base URL "
                        "(default http://localhost:8000)")
    x.add_argument("--json", action="store_true",
                   help="raw /debug/flight.json instead of the table")
    x.add_argument("--last", type=int, default=20,
                   help="show only the newest N records (default 20)")
    x = a_sub.add_parser("fsck",
                         help="audit cross-store integrity: blobs vs "
                              "checksums, checkpoint manifests vs shards, "
                              "journal framing/cursors, router epoch "
                              "marker vs delta journal")
    x.add_argument("--repair", action="store_true",
                   help="quarantine corrupt blobs/steps under "
                        "$PIO_HOME/quarantine, truncate torn journal "
                        "segments, clamp cursors, re-seat a regressed "
                        "epoch marker (nothing is deleted)")
    x.add_argument("--journal-dir", default=None,
                   help="also audit this ingest WAL directory when it "
                        "lives outside $PIO_HOME")
    x.add_argument("--checkpoint-dir", default=None,
                   help="audit this checkpoint directory instead of "
                        "$PIO_HOME/checkpoints")
    x.add_argument("--json", action="store_true",
                   help="machine-readable report instead of the table")
    x = a_sub.add_parser("gc",
                         help="garbage-collect orphaned artifacts")
    x.add_argument("--blobs", action="store_true",
                   help="delete model blobs + .sha256 sidecars referenced "
                        "by no non-retired engine instance")
    x.add_argument("--dry-run", action="store_true",
                   help="list what would be deleted without deleting")

    sp = sub.add_parser("profile",
                        help="capture accelerator profiler traces")
    pr_sub = sp.add_subparsers(dest="profile_command", required=True)
    x = pr_sub.add_parser("serve",
                          help="capture a jax.profiler trace of a LIVE "
                               "engine server for --seconds, bracketed "
                               "by flight-recorder snapshots")
    x.add_argument("--url", default="http://localhost:8000",
                   help="engine server base URL "
                        "(default http://localhost:8000)")
    x.add_argument("--seconds", type=float, default=5.0,
                   help="capture window length (default 5, max 120)")
    x.add_argument("--trace-dir", default=None,
                   help="trace output directory ON THE SERVER HOST "
                        "(default: a fresh dir under its tmpdir)")
    x.add_argument("--out", default=None,
                   help="also write flight-before.json/flight-after.json "
                        "bracketing the window into this local directory")

    sp = sub.add_parser("capture",
                        help="golden-traffic capture control: toggle a "
                             "live server's recording, export a capture "
                             "journal as JSONL")
    c_sub = sp.add_subparsers(dest="capture_command", required=True)
    for verb, hint in (("start", "(re-)enable recording on a live "
                                 "server deployed with --capture-dir"),
                       ("stop", "stop recording and flush the ring so "
                                "everything captured is on disk")):
        x = c_sub.add_parser(verb, help=hint)
        x.add_argument("--url", default="http://localhost:8000",
                       help="engine server base URL "
                            "(default http://localhost:8000)")
    x = c_sub.add_parser("export",
                         help="rewrite a local capture journal as JSONL")
    x.add_argument("dir", help="capture journal directory")
    x.add_argument("--output", required=True,
                   help="JSONL output path (one capture record per line)")

    sp = sub.add_parser("variant",
                        help="manage a live engine server's variant "
                             "table: list the traffic split, re-weight "
                             "the hashed A/B buckets, promote a "
                             "candidate live, retire a variant")
    v_sub = sp.add_subparsers(dest="variant_command", required=True)
    x = v_sub.add_parser("list", help="show every registered variant: "
                                      "state, weight, traffic share, "
                                      "routed-query counts")
    x.add_argument("--url", default="http://localhost:8000",
                   help="engine server base URL "
                        "(default http://localhost:8000)")
    x = v_sub.add_parser("weight",
                         help="set a variant's traffic weight (hashed "
                              "share is weight / sum of weights; only "
                              "the affected hash buckets re-shuffle)")
    x.add_argument("variant_id")
    x.add_argument("weight", type=float)
    x.add_argument("--url", default="http://localhost:8000",
                   help="engine server base URL "
                        "(default http://localhost:8000)")
    x = v_sub.add_parser("promote",
                         help="flip a candidate live, swapping traffic "
                              "weights with the current live variant — "
                              "in-flight requests are never dropped")
    x.add_argument("variant_id")
    x.add_argument("--url", default="http://localhost:8000",
                   help="engine server base URL "
                        "(default http://localhost:8000)")
    x = v_sub.add_parser("retire",
                         help="take a variant out of hashed rotation "
                              "(still reachable via X-PIO-Variant for "
                              "replay); live variants need a promoted "
                              "replacement first")
    x.add_argument("variant_id")
    x.add_argument("--url", default="http://localhost:8000",
                   help="engine server base URL "
                        "(default http://localhost:8000)")

    sp = sub.add_parser("replay",
                        help="re-issue captured golden traffic and diff "
                             "answers at three tiers (bitwise / top-k "
                             "set / score tolerance)")
    _add_engine_args(sp)
    sp.add_argument("capture_dir", help="capture journal directory "
                                        "(from deploy --capture-dir)")
    sp.add_argument("--target", default=None,
                    help="live engine-server base URL to replay against; "
                         "omitted = rehydrate an instance in-process")
    sp.add_argument("--engine-instance-id",
                    help="in-process replay target instance (default: "
                         "latest COMPLETED training)")
    sp.add_argument("--retrieval-mode", choices=["exact", "ann"],
                    default=None,
                    help="override the engine-params retrieval.mode for "
                         "the in-process replay target")
    sp.add_argument("--score-tol", type=float, default=1e-6,
                    help="relative score tolerance for the score_tol "
                         "tier (default 1e-6)")
    sp.add_argument("--show-mismatches", type=int, default=10,
                    help="print at most N mismatched requests "
                         "(default 10)")
    sp.add_argument("--json", action="store_true",
                    help="full machine-readable report instead of the "
                         "summary")

    sp = sub.add_parser("top",
                        help="live terminal view of a deployed engine "
                             "server: qps/p50/mode/SLO burn, the HBM "
                             "ledger by component, train/stream progress")
    sp.add_argument("--url", default="http://localhost:8000",
                    help="engine server base URL "
                         "(default http://localhost:8000)")
    sp.add_argument("--interval", type=float, default=2.0,
                    help="refresh period in seconds (default 2)")
    sp.add_argument("--once", action="store_true",
                    help="render exactly one frame and exit (no screen "
                         "clear) — for scripts and tests")
    sp.add_argument("--fleet", action="store_true",
                    help="treat --url as a fleet router and render the "
                         "merged fleet header + per-replica table from "
                         "/fleet/stats.json (ISSUE 20)")

    sp = sub.add_parser(
        "trace",
        help="cross-process trace assembly: join one X-PIO-Request-ID "
             "across the fleet router hop, replica stage waterfalls and "
             "ingest WAL records into one rendered span tree")
    sp.add_argument("request_id",
                    help="the X-PIO-Request-ID to assemble (echoed on "
                         "every response and minted at ingress)")
    sp.add_argument("--router-url", default=None,
                    help="fleet router base URL (default: the recorded "
                         "$PIO_HOME/run/fleet.json, else "
                         "http://127.0.0.1:8000)")
    sp.add_argument("--url", default=None,
                    help="engine server base URL: skip the router join "
                         "and read this one server's flight recorder "
                         "directly")
    sp.add_argument("--wal-dir", default=None,
                    help="ingest WAL directory to scan for events "
                         "carrying this request id in their trace field")

    sp = sub.add_parser("import")
    sp.add_argument("what", nargs="?", choices=["events"], default="events",
                    help="what to import (only 'events'; optional for "
                         "backward compatibility)")
    sp.add_argument("--appid", type=int, required=True)
    sp.add_argument("--channel", default=None,
                    help="channel id or name (default: default channel)")
    sp.add_argument("--input", required=True)

    sp = sub.add_parser("export")
    sp.add_argument("what", nargs="?", choices=["events"], default="events",
                    help="what to export (only 'events'; optional for "
                         "backward compatibility)")
    sp.add_argument("--appid", type=int, required=True)
    sp.add_argument("--channel", default=None,
                    help="channel id or name (default: default channel)")
    sp.add_argument("--output", required=True)

    sp = sub.add_parser("template")
    t_sub = sp.add_subparsers(dest="template_command", required=True)
    x = t_sub.add_parser("list")
    x = t_sub.add_parser("get")
    x.add_argument("name")
    x.add_argument("directory", nargs="?")

    return p


COMMANDS = {
    "app": cmd_app,
    "accesskey": cmd_accesskey,
    "build": cmd_build,
    "unregister": cmd_unregister,
    "train": cmd_train,
    "eval": cmd_eval,
    "tune": cmd_tune,
    "deploy": cmd_deploy,
    "fleet": cmd_fleet,
    "batchpredict": cmd_batchpredict,
    "bench": cmd_bench,
    "undeploy": cmd_undeploy,
    "eventserver": cmd_eventserver,
    "stream": cmd_stream,
    "adminserver": cmd_adminserver,
    "dashboard": cmd_dashboard,
    "status": cmd_status,
    "top": cmd_top,
    "trace": cmd_trace,
    "backup": cmd_backup,
    "restore": cmd_restore,
    "admin": cmd_admin,
    "profile": cmd_profile,
    "capture": cmd_capture,
    "variant": cmd_variant,
    "replay": cmd_replay,
    "import": cmd_import,
    "export": cmd_export,
    "template": cmd_template,
}


def _apply_platform_override() -> None:
    """``PIO_PLATFORM=cpu`` (or ``tpu``) pins the jax backend before any
    verb touches the device — the reference's local-mode switch for
    small/CI runs on the host. The env var is set for child processes
    and the config for this one, where jax may already be imported."""
    plat = os.environ.get("PIO_PLATFORM")
    if not plat:
        return
    os.environ["JAX_PLATFORMS"] = plat
    import jax

    jax.config.update("jax_platforms", plat)


def main(argv: list[str] | None = None) -> int:
    args = build_parser().parse_args(argv)
    logging.basicConfig(
        level=logging.DEBUG if args.verbose else logging.INFO,
        format="[%(levelname)s] [%(name)s] %(message)s",
    )
    if args.command == "version":
        print(__version__)
        return 0
    _apply_platform_override()
    return COMMANDS[args.command](args)


if __name__ == "__main__":
    raise SystemExit(main())
