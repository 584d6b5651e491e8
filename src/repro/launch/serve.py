"""Quantized serving launcher — both repo workloads behind one CLI.

LM decode (the memory-wall demo, unchanged semantics):

  PYTHONPATH=src python -m repro.launch.serve --workload lm --arch qwen2-0.5b \
      --smoke --quant serve_w8a8 --kv-quant --tokens 32 --batch 4

SO(3) force-field inference through `repro.serving.QuantizedEngine`
(batched + bucketed + Pallas-kernel quantized — the paper's headline path):

  PYTHONPATH=src python -m repro.launch.serve --workload so3 --mode w8a8 \
      --graphs 32 --min-atoms 6 --max-atoms 48

The so3 workload builds an engine, warms up its shape classes, pushes a
stream of variable-size molecules through `infer_batch`, and reports
molecules/s, the weight-memory compression, and the served model's LEE
diagnostic (padding masked out).

Online serving demo (`repro.server`, docs/server.md) — Poisson traffic
through the dynamic micro-batching scheduler, latency percentiles and
dispatch stats instead of one-shot batch timing:

  PYTHONPATH=src python -m repro.launch.serve --workload so3 --server \
      --rate 20 --requests 200 --deadline-ms 25 \
      [--artifact model.npz]        # cold-start from a packed artifact

`--save-artifact path.npz` packs the engine's quantized weights to disk;
`--artifact path.npz` boots from one (skipping fp32 + quantization).

Multi-replica cluster demo (`repro.cluster`, docs/cluster.md) — the
same traffic fanned across N device-pinned engine replicas behind the
shape-aware router, with an optional zero-downtime rolling weight swap
mid-replay:

  XLA_FLAGS=--xla_force_host_platform_device_count=4 \
  PYTHONPATH=src python -m repro.launch.serve --workload so3 --server \
      --replicas 4 --rate 60 --requests 300 [--swap-artifact v2.npz]

`--md-session N` additionally streams a checkpointed N-step MD session
through the same pool beside the one-shot traffic (`repro.sessions`,
docs/sessions.md).

Runtime guardrails (`repro.guardrails`, docs/guardrails.md):
`--guardrails` arms the engine-side detectors (non-finite results are
withheld with a typed error instead of delivered); `--tiers
w4a8:2,w8a8:1,fp32:1` serves through a mixed-precision fleet whose
flagged requests transparently re-run one tier up; `--stall-timeout S`
arms the pool watchdog that quarantines and cold-restarts a replica
whose worker stalls:

  XLA_FLAGS=--xla_force_host_platform_device_count=4 \
  PYTHONPATH=src python -m repro.launch.serve --workload so3 --server \
      --tiers w4a8:2,w8a8:1,fp32:1 --guardrails --stall-timeout 5
"""
from __future__ import annotations

import argparse
import dataclasses
import json
import time

import jax
import jax.numpy as jnp
import numpy as np

from repro.launch.compile_cache import enable_compile_cache


# ---------------------------------------------------------------------------
# LM decode workload (KV-cached token loop)
# ---------------------------------------------------------------------------

def run_lm(args) -> None:
    from repro import configs
    from repro.models.lm import transformer as tfm
    from repro.quant.apply import quantize_params_tree, quantized_bytes

    cfg = (configs.get_smoke_config(args.arch) if args.smoke
           else configs.get_config(args.arch))
    cfg = dataclasses.replace(cfg, quant_mode=args.quant,
                              kv_quant=args.kv_quant,
                              dtype=jnp.float32 if args.smoke else cfg.dtype)

    params = tfm.init_lm(jax.random.PRNGKey(0),
                         dataclasses.replace(cfg, quant_mode="none"))
    fp32_bytes = sum(np.asarray(x).nbytes for x in jax.tree.leaves(params))
    if args.quant != "none":
        params = quantize_params_tree(params, cfg)
    served_bytes = quantized_bytes(params)
    cache = tfm.init_cache(cfg, args.batch, args.cache_len)
    cache_bytes = sum(np.asarray(x).nbytes for x in jax.tree.leaves(cache))

    @jax.jit
    def step(params, cache, tok, idx):
        logits, cache = tfm.decode_step(params, cfg, cache, tok, idx)
        nxt = jnp.argmax(logits, -1).astype(jnp.int32)[:, None]
        return nxt, cache

    tok = jnp.zeros((args.batch, 1), jnp.int32)
    if cfg.frontend != "token":
        tok = jnp.zeros((args.batch, 1, cfg.d_model), cfg.dtype)
    # warm
    nxt, cache = step(params, cache, tok, jnp.asarray(0, jnp.int32))
    jax.block_until_ready(nxt)
    t0 = time.monotonic()
    for i in range(1, args.tokens):
        nxt, cache = step(params, cache,
                          nxt if cfg.frontend == "token" else tok,
                          jnp.asarray(i, jnp.int32))
    jax.block_until_ready(nxt)
    dt = time.monotonic() - t0
    tps = (args.tokens - 1) * args.batch / dt
    print(f"arch={cfg.name} quant={args.quant} kv_quant={args.kv_quant}")
    print(f"weights: fp32 {fp32_bytes/1e6:.2f} MB -> served "
          f"{served_bytes/1e6:.2f} MB ({fp32_bytes/max(served_bytes,1):.2f}x)")
    print(f"kv-cache: {cache_bytes/1e6:.2f} MB for B={args.batch} "
          f"S={args.cache_len}")
    print(f"decode: {tps:.1f} tok/s ({dt/(args.tokens-1)*1e3:.1f} ms/step)")


# ---------------------------------------------------------------------------
# SO(3) force-field workload (QuantizedEngine)
# ---------------------------------------------------------------------------

def _artifact_mode(path: str) -> str:
    """The serving mode a packed artifact was quantized for."""
    from repro.server import load_artifact
    return load_artifact(path).serve.mode


def run_so3(args) -> None:
    from repro.models import so3krates as so3
    from repro.serving import QuantizedEngine, ServeConfig, random_graphs
    from repro.server import load_engine, save_artifact

    if args.artifact:
        # packed-artifact cold start: no fp32 tree, no quantization
        # pass. The mode is baked into the packed weights, so it comes
        # from the artifact unless the user explicitly asks (and an
        # explicit mismatch is an error, not a silent override).
        t0 = time.monotonic()
        mode = args.mode or _artifact_mode(args.artifact)
        serve = ServeConfig(mode=mode, bucket_sizes=tuple(args.buckets),
                            max_batch=args.max_batch, path=args.path)
        engine = load_engine(args.artifact, serve=serve)
        model_cfg = engine.model_cfg
        print(f"cold start from {args.artifact} in "
              f"{time.monotonic() - t0:.2f}s "
              "(packed weights, no quantization pass)")
    else:
        serve = ServeConfig(mode=args.mode or "w8a8",
                            bucket_sizes=tuple(args.buckets),
                            max_batch=args.max_batch,
                            path=args.path)
        model_cfg = so3.So3kratesConfig(feat=args.feat,
                                        vec_feat=args.vec_feat,
                                        n_layers=args.layers, n_rbf=8,
                                        dir_bits=args.dir_bits)
        engine = QuantizedEngine.from_config(model_cfg, serve=serve)
    if args.guardrails:
        from repro.guardrails import GuardrailConfig
        engine.guardrails = GuardrailConfig(check_finite=True)
        print("guardrails: non-finite results are withheld with a typed "
              "GuardrailViolation (docs/guardrails.md)")
    if args.save_artifact:
        nbytes = save_artifact(args.save_artifact, engine)
        print(f"packed artifact -> {args.save_artifact} "
              f"({nbytes / 1e3:.1f} KB)")

    mem = engine.memory_report()
    print(f"workload=so3 mode={engine.serve.mode} backend={engine.backend} "
          f"interpret={engine.interpret}")
    print(f"weights: fp32 {mem['fp32_bytes']/1e3:.1f} KB -> served "
          f"{mem['served_bytes']/1e3:.1f} KB ({mem['compression_x']}x)")

    if args.server:
        run_so3_server(engine, args)
        return

    graphs = random_graphs(args.graphs, args.min_atoms, args.max_atoms,
                           model_cfg.n_species, density=args.density)

    # warm the exact shape classes this traffic will use, so the timed
    # pass below measures steady-state throughput, not compilation
    t0 = time.monotonic()
    engine.infer_batch(graphs)
    print(f"warmup: compiled {len(engine.compiled_shapes)} shape "
          f"class(es) in {time.monotonic() - t0:.2f}s")

    t0 = time.monotonic()
    results = engine.infer_batch(graphs)
    dt = time.monotonic() - t0
    buckets_used = sorted({r.bucket_capacity for r in results})
    paths_used = sorted({r.path for r in results})
    print(f"infer_batch: {len(graphs)} molecules "
          f"({args.min_atoms}-{args.max_atoms} atoms) in {dt:.2f}s "
          f"-> {len(graphs)/dt:.1f} mol/s, buckets used {buckets_used}, "
          f"paths {paths_used} (dispatch {engine.dispatch_stats})")

    if args.lee:
        diag = engine.lee_diagnostic(graphs[:4], jax.random.PRNGKey(1),
                                     n_rotations=2)
        print(f"served-model LEE: mean {diag['lee_mean']:.2e} "
              f"max {diag['lee_max']:.2e} (padding masked)")


def run_so3_server(engine, args) -> None:
    """Online-serving demo: Poisson traffic through the dynamic
    micro-batching scheduler (`repro.server`) — or, with `--replicas`,
    through the multi-replica cluster pool (`repro.cluster`, one engine
    per JAX device) — latency percentiles and dispatch stats. With
    `--swap-artifact` a zero-downtime rolling weight swap fires halfway
    through the replay (docs/cluster.md)."""
    import threading

    from repro.server import (MicroBatchScheduler, SchedulerConfig,
                              SizeClass, TrafficConfig, make_traffic,
                              run_open_loop)

    mid = (args.min_atoms + args.max_atoms) // 2
    if mid + 1 > args.max_atoms:      # degenerate range: one size class
        size_mix = (SizeClass(args.min_atoms, args.max_atoms, 1.0),)
    else:
        size_mix = (SizeClass(args.min_atoms, mid, 0.5),
                    SizeClass(mid + 1, args.max_atoms, 0.5))
    cfg = TrafficConfig(
        rate_rps=args.rate, n_requests=args.requests,
        size_mix=size_mix,
        n_species=engine.model_cfg.n_species, density=args.density,
        seed=args.seed)
    traffic = make_traffic(cfg)
    max_batch = min(args.sched_batch, args.max_batch)

    if (args.replicas > 1 or args.swap_artifact or args.md_session
            or args.tiers):
        from repro.cluster import ClusterConfig, ClusterPool
        cluster = ClusterConfig(n_replicas=args.replicas,
                                max_batch=max_batch,
                                deadline_ms=args.deadline_ms,
                                max_queue=args.max_queue,
                                stall_timeout_s=args.stall_timeout)
        if args.tiers:
            # mixed-precision fleet: flagged w4a8 results re-run one
            # tier up (fresh random weights shared across the tiers —
            # a demo fleet, like the non-artifact engine above)
            plan = {}
            for part in args.tiers.split(","):
                t, _, k = part.partition(":")
                plan[t.strip()] = int(k or 1)
            pool = ClusterPool.from_tiers(
                engine.model_cfg, serve=engine.serve, tier_plan=plan,
                cluster=cluster, seed=args.seed,
                guardrails=engine.guardrails if args.guardrails else None)
        else:
            pool = ClusterPool.from_quantized(
                engine.model_cfg, engine.qparams, engine.serve, cluster,
                fp32_nbytes=engine.memory_report()["fp32_bytes"],
                artifact_version=engine.artifact_version,
                guardrails=engine.guardrails if args.guardrails else None)
        alert_bus = getattr(args, "_alert_bus", None)
        if alert_bus is not None:
            # fleet surfacing: alerts land in pool.stats()["alerts"] and
            # bump pool_events_total{event="alert"}
            pool.watch_alerts(alert_bus)
        swap_report = {}
        swap_thread = None
        session = session_mgr = None
        with pool:
            s0 = pool.stats()
            print(f"cluster: {pool.n_replicas} replicas on "
                  f"{[r['device'] for r in s0['replicas']]}, parallel "
                  f"warmup {s0['warmup_s']:.2f}s")
            pool.reset_stats()
            if args.md_session:
                session, session_mgr = _start_md_session(pool, engine,
                                                         args)
            if args.swap_artifact:
                # fire the rolling swap halfway through the replay; a
                # failure must surface after the replay, not vanish into
                # the timer thread's excepthook
                half = traffic[len(traffic) // 2][0]

                def do_swap():
                    try:
                        swap_report.update(
                            pool.swap_artifact(args.swap_artifact))
                    except BaseException as e:
                        swap_report["error"] = e
                swap_thread = threading.Timer(half, do_swap)
                swap_thread.start()
            res = run_open_loop(pool, traffic, rate_rps=args.rate)
            if swap_thread is not None:
                # a rolling swap warms each replacement engine before the
                # exchange, which can outlast a short replay — wait so the
                # report is real and the pool isn't torn down under a
                # thread that is mid-compilation
                if not swap_report:
                    print("replay done; waiting for the rolling swap to "
                          "finish...")
                swap_thread.join()
            if session is not None:
                session.wait()
                session_mgr.close()
            stats = pool.stats()
        _print_server_summary(res, stats, args, max_batch)
        if session is not None:
            print(f"md session: {session.steps_done} steps in "
                  f"{len(session.collected)} frames beside the replay, "
                  f"{session.n_checkpoints} checkpoints "
                  f"({session.checkpoint_dir}), "
                  f"artifact versions "
                  f"{sorted({f.artifact_version for f in session.collected})}")
        print(f"routing: {stats['router']['routed_per_replica']} "
              f"(shed {stats['n_shed']}, requeued "
              f"{stats['router']['n_requeued']})")
        if args.tiers or args.guardrails or args.stall_timeout:
            g = stats.get("guardrails", {})
            print(f"tiers: {stats.get('tiers')}  guardrails: flagged "
                  f"{g.get('n_flagged', 0)}, escalated "
                  f"{g.get('n_escalated', 0)}, quarantined "
                  f"{g.get('n_quarantined', 0)}, stalls detected "
                  f"{g.get('n_stalls_detected', 0)}")
        if swap_report.get("error") is not None:
            raise SystemExit(
                f"hot swap FAILED: {swap_report['error']} (traffic was "
                "unaffected — surviving weights kept serving)")
        if swap_report:
            pauses = [f"{r['pause_s'] * 1e3:.2f}ms"
                      for r in swap_report["replicas"]]
            print(f"hot swap -> {swap_report['version_tag']}: "
                  f"per-replica serve pauses {pauses} "
                  "(warmed before swap; zero requests dropped)")
        return

    sched_cfg = SchedulerConfig(max_batch=max_batch,
                                deadline_ms=args.deadline_ms,
                                max_queue=args.max_queue)
    with MicroBatchScheduler(engine, sched_cfg) as sched:
        print(f"warmup: {sched.warmup_s:.2f}s "
              f"({len(engine.compiled_shapes)} shape classes)")
        engine.reset_stats()    # keep the streaming phase unpolluted
        res = run_open_loop(sched, traffic, rate_rps=args.rate)
        stats = sched.stats()
    _print_server_summary(res, stats, args, max_batch)


def _start_md_session(pool, engine, args):
    """`--md-session N`: stream a checkpointed MD trajectory through the
    pool while the one-shot replay runs (repro.sessions,
    docs/sessions.md). Returns (session, manager); the caller waits and
    closes after the replay so both tenants share the replicas."""
    import tempfile

    import numpy as np

    from repro.md.engine import MDConfig
    from repro.sessions import SessionConfig, SessionManager

    n = max(args.min_atoms, (args.min_atoms + args.max_atoms) // 2)
    rng = np.random.default_rng(args.seed + 1)
    side = (n / (args.density or 0.1)) ** (1.0 / 3.0)
    species = rng.integers(0, engine.model_cfg.n_species,
                           n).astype(np.int32)
    coords = rng.uniform(0, side, size=(n, 3)).astype(np.float32)
    masses = np.full(n, 12.0, np.float32)
    record = min(50, args.md_session)
    chunk = 2 * record if 2 * record <= args.md_session else record
    scfg = SessionConfig(
        n_steps=args.md_session, chunk_steps=chunk, record_every=record,
        checkpoint_every=3,
        md=MDConfig(mode=engine.serve.mode, record_every=record))
    root = tempfile.mkdtemp(prefix="serve_md_session_")
    mgr = SessionManager(pool, root)
    session = mgr.start(species, coords, masses, config=scfg,
                        seed=args.seed)
    print(f"md session: {args.md_session} NVE steps ({n} atoms, "
          f"{scfg.n_chunks} chunks of {chunk}) streaming beside the "
          f"replay; checkpoints -> {session.checkpoint_dir}")
    return session, mgr


def _print_server_summary(res, stats, args, max_batch) -> None:
    s = res.summary()
    print(f"open loop: {args.requests} requests at {args.rate:.1f} req/s "
          f"offered ({args.min_atoms}-{args.max_atoms} atoms, "
          f"deadline {args.deadline_ms:.0f} ms, "
          f"micro-batch <= {max_batch})")
    print(f"latency: p50 {s['p50_ms']:.1f} ms  p95 {s['p95_ms']:.1f} ms  "
          f"p99 {s['p99_ms']:.1f} ms  max {s['max_ms']:.1f} ms")
    print(f"throughput: {s['throughput_rps']:.1f} req/s over "
          f"{s['span_s']:.1f}s span")
    print(f"batching: {stats['n_flushes']} flushes, mean batch "
          f"{stats['mean_batch']:.2f}, reasons {stats['flush_reasons']}, "
          f"max queue depth {stats['max_queue_depth']}")
    print(f"dispatch: {stats['engine_dispatch']}")


def _setup_obs(args):
    """`--metrics-out` / `--trace-out` / `--alerts-out`: arm the unified
    metrics plane, the per-request tracer, and the active health plane
    (SLO burn-rate evaluation + anomaly detectors; repro.obs,
    docs/observability.md).  Returns a cleanup callable that stops the
    health monitor, flushes the final export, and closes the sinks."""
    if not (args.metrics_out or args.trace_out or args.alerts_out):
        return lambda: None
    from repro.obs import (AlertBus, AnomalyMonitor, HealthMonitor,
                           JsonlTraceSink, PeriodicExporter, REGISTRY,
                           SLOEvaluator, TRACER, configure_tracing,
                           default_detectors, default_slos)
    sink = exporter = monitor = alerts_file = None
    if args.trace_out:
        sink = JsonlTraceSink(args.trace_out)
        configure_tracing(enabled=True, sink=sink)
        print(f"tracing: per-request spans -> {args.trace_out} "
              "(render with scripts/trace_report.py)")
    if args.metrics_out:
        exporter = PeriodicExporter(
            args.metrics_out, interval_s=args.export_interval,
            tracer=TRACER if sink is not None else None,
            trace_sink=None).start()
        print(f"metrics: Prometheus text exposition -> "
              f"{args.metrics_out} every {args.export_interval:.0f}s")
    if args.alerts_out:
        REGISTRY.set_enabled(True)     # the evaluators read the registry
        bus = AlertBus(registry=REGISTRY)
        alerts_file = open(args.alerts_out, "a", encoding="utf-8")

        def on_alert(alert):
            alerts_file.write(json.dumps(alert.to_json()) + "\n")
            alerts_file.flush()
            print(f"ALERT[{alert.severity}] {alert.name}: "
                  f"{alert.message}")
        bus.subscribe(on_alert)
        evaluator = SLOEvaluator(default_slos(), registry=REGISTRY,
                                 bus=bus)
        anomaly = AnomalyMonitor(default_detectors(), registry=REGISTRY,
                                 bus=bus)
        monitor = HealthMonitor([evaluator, anomaly],
                                interval_s=args.health_interval).start()
        args._alert_bus = bus      # cluster path: pool.watch_alerts
        print(f"health plane: {len(evaluator.slos)} SLOs + "
              f"{len(anomaly.detectors)} anomaly detectors every "
              f"{args.health_interval:.1f}s, alerts -> {args.alerts_out}")

    def cleanup():
        if monitor is not None:
            monitor.stop()         # one final evaluation step
        if exporter is not None:
            exporter.stop()        # joins + writes one final export
        if alerts_file is not None:
            alerts_file.close()
        if sink is not None:
            configure_tracing(enabled=False)
            sink.close()
            print(f"tracing: {sink.n_written} trace(s) written to "
                  f"{args.trace_out}")
    return cleanup


def main():
    ap = argparse.ArgumentParser(description=__doc__)
    ap.add_argument("--workload", default="lm", choices=["lm", "so3"])
    # lm options
    ap.add_argument("--arch")
    ap.add_argument("--smoke", action="store_true")
    ap.add_argument("--quant", default="none",
                    choices=["none", "serve_w8a8", "serve_w4a8"])
    ap.add_argument("--kv-quant", action="store_true")
    ap.add_argument("--batch", type=int, default=4)
    ap.add_argument("--cache-len", type=int, default=128)
    ap.add_argument("--tokens", type=int, default=32)
    # so3 options
    ap.add_argument("--mode", default=None,
                    choices=["fp32", "w8a8", "w4a8"],
                    help="serving mode (default: w8a8, or the artifact's "
                         "own mode when --artifact is given)")
    ap.add_argument("--graphs", type=int, default=16)
    ap.add_argument("--min-atoms", type=int, default=6)
    ap.add_argument("--max-atoms", type=int, default=32)
    ap.add_argument("--buckets", type=int, nargs="+", default=[16, 32, 64])
    ap.add_argument("--max-batch", type=int, default=32)
    ap.add_argument("--feat", type=int, default=32)
    ap.add_argument("--vec-feat", type=int, default=8)
    ap.add_argument("--layers", type=int, default=2)
    ap.add_argument("--dir-bits", type=int, default=8)
    ap.add_argument("--path", default="auto",
                    choices=["dense", "sparse", "auto"],
                    help="so3 execution path: dense O(n^2), or the "
                         "sparse O(E) edge list (sparse/auto; batches "
                         "whose cutoff graph overflows the bucket's edge "
                         "capacity fall back to dense, see dispatch "
                         "stats)")
    ap.add_argument("--density", type=float, default=None,
                    help="atoms per cubic Angstrom for the random graphs "
                         "(None = legacy dense cloud)")
    ap.add_argument("--lee", action="store_true",
                    help="also report the served model's LEE diagnostic")
    # so3 online-serving mode (repro.server, docs/server.md)
    ap.add_argument("--server", action="store_true",
                    help="stream Poisson traffic through the dynamic "
                         "micro-batching scheduler and report latency "
                         "percentiles + dispatch stats")
    ap.add_argument("--rate", type=float, default=20.0,
                    help="offered load in requests/s (--server)")
    ap.add_argument("--requests", type=int, default=200,
                    help="number of requests to stream (--server)")
    ap.add_argument("--deadline-ms", type=float, default=25.0,
                    help="micro-batching deadline (--server)")
    ap.add_argument("--sched-batch", type=int, default=8,
                    help="scheduler micro-batch flush size (--server)")
    ap.add_argument("--replicas", type=int, default=1,
                    help="serve through a repro.cluster pool of this many "
                         "engine replicas, one per JAX device (--server; "
                         "on CPU simulate devices with XLA_FLAGS="
                         "--xla_force_host_platform_device_count=N)")
    ap.add_argument("--max-queue", type=int, default=None,
                    help="bounded admission: shed requests beyond this "
                         "many queued per scheduler/replica (--server)")
    ap.add_argument("--swap-artifact",
                    help="rolling zero-downtime weight swap to this "
                         "packed artifact halfway through the --server "
                         "replay (implies the cluster path)")
    ap.add_argument("--md-session", type=int, default=0, metavar="STEPS",
                    help="also stream a checkpointed MD session of this "
                         "many NVE steps through the pool beside the "
                         "one-shot traffic (repro.sessions, "
                         "docs/sessions.md; --server, implies the "
                         "cluster path)")
    ap.add_argument("--guardrails", action="store_true",
                    help="arm the runtime result detectors: non-finite "
                         "energies/forces are withheld with a typed "
                         "error instead of delivered "
                         "(repro.guardrails, docs/guardrails.md)")
    ap.add_argument("--tiers", metavar="SPEC",
                    help="serve through a mixed-precision fleet, e.g. "
                         "'w4a8:2,w8a8:1,fp32:1' — flagged requests "
                         "transparently re-run one precision tier up "
                         "(--server, implies the cluster path)")
    ap.add_argument("--stall-timeout", type=float, default=None,
                    metavar="S",
                    help="arm the pool watchdog: a replica whose worker "
                         "is stuck on one flush/chunk longer than this "
                         "is quarantined and cold-restarted, its "
                         "requests requeued (--server cluster path)")
    ap.add_argument("--metrics-out", metavar="PATH",
                    help="export the unified metrics registry as "
                         "Prometheus text exposition to this file, "
                         "rewritten atomically every --export-interval "
                         "seconds (repro.obs, docs/observability.md)")
    ap.add_argument("--trace-out", metavar="PATH",
                    help="enable per-request tracing and append one "
                         "JSON trace per completed request to this "
                         "file; render the latency breakdown with "
                         "scripts/trace_report.py")
    ap.add_argument("--export-interval", type=float, default=5.0,
                    metavar="S",
                    help="metrics export period in seconds "
                         "(--metrics-out)")
    ap.add_argument("--alerts-out", metavar="PATH",
                    help="arm the active health plane: evaluate the "
                         "default SLO catalogue (burn-rate windows) and "
                         "anomaly detectors against the live registry "
                         "and append one JSON alert per line to this "
                         "file (repro.obs.slo, docs/observability.md); "
                         "watch live with scripts/obs_top.py")
    ap.add_argument("--health-interval", type=float, default=1.0,
                    metavar="S",
                    help="health-plane evaluation period in seconds "
                         "(--alerts-out)")
    ap.add_argument("--seed", type=int, default=0)
    ap.add_argument("--artifact",
                    help="cold-start the engine from a packed quantized "
                         "artifact (.npz) instead of quantizing fp32")
    ap.add_argument("--save-artifact",
                    help="pack the engine's quantized weights to this "
                         ".npz and continue")
    args = ap.parse_args()

    enable_compile_cache()
    cleanup_obs = _setup_obs(args)
    try:
        if args.workload == "lm":
            if not args.arch:
                ap.error("--workload lm requires --arch")
            run_lm(args)
        else:
            run_so3(args)
    finally:
        cleanup_obs()


if __name__ == "__main__":
    main()
