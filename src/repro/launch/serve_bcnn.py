"""Streaming BCNN serving driver — the paper's online individual-request
scenario (§6.3, Fig. 7) as a runnable service loop.

Builds the paper's 9-layer CIFAR-10 BCNN — random weights folded on the
spot, or TRAINED weights loaded from a deployment artifact
(``--artifact``, written by ``launch/train_bcnn.py --export-artifact``
via ``core/bcnn_artifact.py``) — and serves synthetic CIFAR-like images
through the continuously-stepped slot engine (``serve/bcnn_engine.py``).
Reports per-request latency percentiles and achieved throughput.

Usage (CPU-scale):
    PYTHONPATH=src python -m repro.launch.serve_bcnn --requests 32
    PYTHONPATH=src python -m repro.launch.serve_bcnn --rate 8 --slots 4
        # Poisson arrivals at 8 req/s; --rate 0 submits everything up front
    PYTHONPATH=src python -m repro.launch.serve_bcnn --pipeline-stages 2
        # serve through the stage-pipelined multi-device forward
        # (parallel/bcnn_pipeline.py; see docs/PIPELINE.md)
    PYTHONPATH=src python -m repro.launch.serve_bcnn --data-shards 2 \
        --offline --requests 64
        # the paper's large-batch scenario: one bulk batch through the
        # batch-sharded data-parallel forward
        # (parallel/bcnn_data_parallel.py; see docs/SERVING.md)
    PYTHONPATH=src python -m repro.launch.serve_bcnn \
        --artifact /tmp/bcnn_art
        # serve trained weights from a deployment artifact
        # (docs/TRAINING.md walks the full train → export → serve cycle)
    PYTHONPATH=src python -m repro.launch.serve_bcnn --replicas 2 --rate 8
        # FLEET tier: the async router (serve/router.py) over 2 engine
        # replicas, mixed online+bulk Poisson traffic with SLO-aware
        # scheduling; add --rolling-swap to hot-swap weights across the
        # fleet mid-drive without dropping a request (docs/SERVING.md
        # "Fleet serving")
    PYTHONPATH=src python -m repro.launch.serve_bcnn --replicas 1 \
        --autoscale --max-replicas 2 --rolling-swap
        # ELASTIC fleet (serve/autoscale.py): a controller thread walks
        # the replica count between --min/--max-replicas as offered load
        # crosses the hysteresis watermarks; bulk traffic is co-scheduled
        # in micro-chunks behind an --online-reserve (docs/SERVING.md
        # "Elastic fleet & co-scheduling")
"""
from __future__ import annotations

import argparse
import time

# --data-shards N needs N devices; on a plain-CPU host, simulate them
# before jax's first import (see launch/device_shim.py for the contract).
from repro.launch.device_shim import argv_flag_value, force_host_devices

force_host_devices(argv_flag_value("--data-shards"))

import jax
import numpy as np

from repro.configs import bcnn_cifar10 as pc
from repro.core import bcnn
from repro.data import SyntheticImages
from repro.launch import compile_cache
from repro.serve import BCNNEngine, drive_poisson


def parse_priority_mix(spec: str) -> dict[str, int]:
    """'online=3,bulk=1' → {"online": 3, "bulk": 1} (validated)."""
    mix = {}
    for part in spec.split(","):
        part = part.strip()
        if not part:
            continue
        name, _, w = part.partition("=")
        try:
            weight = int(w)
        except ValueError:
            raise SystemExit(f"--priority-mix: bad weight in {part!r} "
                             f"(want 'class=int,...')")
        if weight < 0:
            raise SystemExit(f"--priority-mix: negative weight in {part!r}")
        mix[name.strip()] = weight
    if not mix or not any(mix.values()):
        raise SystemExit("--priority-mix: no positive weights")
    return mix


def resolve_plan(packed, args):
    """``--autotune`` / ``--tuning-cache`` → the ExecutionPlan to serve
    with, or None (no tuning flags: the engines serve
    ``core/execution_plan.py::default_plan``).

    Cache-first protocol: a usable ``tuning`` section in ``--tuning-cache``
    (or, failing that, ``--artifact``) whose (backend, device kind,
    geometry) key matches THIS host is reused without measuring — the
    ``tuning: cache hit`` line is the operator's (and the CI smoke lane's)
    signal that no re-tuning happened. Only then does ``--autotune``
    measure (``kernels/autotune.py::autotune_packed``).
    """
    if not (args.autotune or args.tuning_cache):
        return None
    from repro.core import bcnn_artifact
    from repro.kernels import autotune as at
    tuning = None
    for cache_dir in (args.tuning_cache, args.artifact):
        if not cache_dir:
            continue
        try:
            tuning = bcnn_artifact.load_tuning(cache_dir)
        except bcnn_artifact.ArtifactError as e:
            print(f"tuning: cache at {cache_dir} unusable ({e})")
            tuning = None
        if tuning is not None:
            break
    plan, source = at.plan_for_host(packed, tuning)
    fusion = "on" if plan.conv_fusion else "off"
    if source == "cached":
        print(f"tuning: cache hit — reusing the stored plan "
              f"({plan.path} path, fusion {fusion}) without re-measuring")
    elif args.autotune:
        report = {}
        plan = at.autotune_packed(packed, report=report)
        fusion = "on" if plan.conv_fusion else "off"
        print(f"tuning: measured {report['n_candidates']} candidate(s) "
              f"({report['n_eligible']} eligible) → {plan.path} path, "
              f"fusion {fusion}")
    else:
        print("tuning: no usable cached plan for this host — serving the "
              "default heuristics (pass --autotune to measure)")
    return plan


def export_artifact(path, packed, plan, args):
    """``--export-artifact``: persist the served weights — and, when the
    plan is a measured one, its ``tuning`` section — so the next
    ``--artifact`` serve reuses the plan without re-tuning."""
    from repro.core import bcnn_artifact
    from repro.kernels import autotune as at
    tuning = (at.tuning_section(packed, plan)
              if plan is not None and plan.tuned else None)
    bcnn_artifact.save_packed(path, packed, tuning=tuning,
                              provenance={"seed": args.seed,
                                          "exported_by": "serve_bcnn"})
    print(f"exported artifact to {path}"
          + (" (with tuning section)" if tuning else ""))


def serve_fleet(packed, x, args, plan=None):
    """The fleet tier: async router over ``--replicas`` engine replicas,
    optionally elastic (``--autoscale``: a controller thread walks the
    replica count between the hysteresis watermarks as load changes)."""
    from repro.serve import AutoscaleConfig, Router, drive_mixed_poisson

    mix = parse_priority_mix(args.priority_mix)
    autoscale = None
    if args.autoscale:
        autoscale = AutoscaleConfig(
            min_replicas=args.min_replicas, max_replicas=args.max_replicas,
            up_watermark=pc.AUTOSCALE_UP_WATERMARK,
            down_watermark=pc.AUTOSCALE_DOWN_WATERMARK,
            window_s=pc.AUTOSCALE_WINDOW_S,
            cooldown_s=pc.AUTOSCALE_COOLDOWN_S,
            interval_s=pc.AUTOSCALE_INTERVAL_S)
    router = Router.from_packed(
        packed, n_replicas=args.replicas, n_slots=args.slots, plan=plan,
        max_queue=args.max_queue, history=max(4096, args.requests),
        online_reserve=args.online_reserve,
        bulk_chunk=args.bulk_chunk if args.bulk_chunk > 0 else None,
        autoscale=autoscale)
    unknown = set(mix) - set(router.class_names)
    if unknown:
        raise SystemExit(f"--priority-mix: unknown class(es) {sorted(unknown)}"
                         f" (router classes: {sorted(router.class_names)})")
    try:
        swap_to = None
        if args.rolling_swap:
            # hot-swap target: a re-seeded fold of the same architecture
            swap_to = bcnn.fold_model(bcnn.init(jax.random.PRNGKey(
                args.seed + 1)))
        elastic = (f", elastic {args.min_replicas}..{args.max_replicas} "
                   f"replicas (reserve {args.online_reserve})"
                   if autoscale else "")
        print(f"fleet: {args.replicas} replicas × {args.slots} slots, "
              f"admission queue {args.max_queue}, mix "
              + ", ".join(f"{k}={v}" for k, v in mix.items()) + elastic)
        if args.rate > 0:
            d = drive_mixed_poisson(router, x, args.rate, mix=mix,
                                    seed=args.seed, swap_to=swap_to)
            print(f"mixed Poisson arrivals @ {args.rate:.1f} req/s: "
                  f"{d['n_accepted']}/{d['n_offered']} admitted, "
                  f"{d['n_rejected']} shed")
            if swap_to is not None:
                print(f"  rolling swap mid-drive: weight epochs served = "
                      f"{sorted(d['epochs'])} (zero drops)")
        else:
            # bulk burst up front: with --autoscale this is the load step
            # that provably crosses the up-watermark (requests ≫ slots), so
            # the controller thread must scale up while the backlog drains
            reqs = router.submit_batch(x, cls="bulk")
            if autoscale is not None:
                # sample the burst into the pressure window synchronously —
                # the controller thread would get there too, but the smoke
                # lane asserts on the scale-up, so don't race the drain
                for _ in range(8):
                    if router.autoscaler.step() > 0:
                        break
            if swap_to is not None:
                router.rolling_swap(swap_to)
            for r in reqs:
                r.wait(timeout=120.0)
            print(f"batch-of-{args.requests} submitted up front via router")
            if swap_to is not None:
                print(f"  rolling swap mid-burst: weight epochs served = "
                      f"{sorted({r.epoch for r in reqs})} (zero drops)")
        for cls in router.class_names:
            st = router.stats(cls)
            if st["n"] == 0:
                continue
            miss = (f", deadline-miss {st['deadline_miss_frac']*100:.0f}%"
                    if st.get("deadline_miss_frac") is not None else "")
            print(f"  [{cls}] n={st['n']}  p50 {st['p50']*1e3:7.1f} ms  "
                  f"p95 {st['p95']*1e3:7.1f} ms  "
                  f"p99 {st['p99']*1e3:7.1f} ms{miss}")
        if autoscale is not None:
            a = router.autoscaler
            print(f"  autoscaler: {a.n_scale_ups} scale-up(s), "
                  f"{a.n_scale_downs} scale-down(s), timeline "
                  f"{[(round(t, 3), n) for t, n in a.timeline(args.replicas)]}")
            if (args.rate == 0 and args.max_replicas > args.replicas
                    and args.requests
                    > pc.AUTOSCALE_UP_WATERMARK * args.slots):
                # the burst held the pressure above the up-watermark for
                # its whole drain: a scale-up is guaranteed, not hoped for
                assert a.n_scale_ups >= 1, \
                    "burst crossed the up-watermark but no replica spawned"
        for rep in router.replicas_ever:
            live = "live" if rep in router.replicas else "retired"
            print(f"  replica {rep.id} ({live}): served {rep.served}, "
                  f"weight epoch {rep.epoch}, step compiled "
                  f"{rep.step_cache_size}×")
            assert rep.step_cache_size == 1, "replica recompiled"
    finally:
        router.shutdown()
    return 0


def main(argv=None):
    ap = argparse.ArgumentParser()
    ap.add_argument("--artifact", default="", metavar="DIR",
                    help="serve TRAINED weights from a deployment artifact "
                         "(core/bcnn_artifact.py, exported by "
                         "launch/train_bcnn.py --export-artifact) instead "
                         "of randomly initialized ones")
    ap.add_argument("--slots", type=int, default=pc.SERVE_N_SLOTS)
    ap.add_argument("--requests", type=int, default=32)
    ap.add_argument("--rate", type=float, default=0.0,
                    help="Poisson arrival rate in req/s; 0 = all up front")
    ap.add_argument("--pipeline-stages", type=int, default=pc.PIPELINE_STAGES,
                    help="cut the 9-layer forward into N cost-balanced "
                         "pipeline stages over the local devices "
                         "(parallel/bcnn_pipeline.py); 1 = single-device")
    ap.add_argument("--micro-batch", type=int,
                    default=pc.PIPELINE_MICRO_BATCH,
                    help="pipeline streaming granule (with --pipeline-stages)")
    ap.add_argument("--data-shards", type=int, default=pc.DATA_SHARDS,
                    help="replicate the packed network over N devices and "
                         "shard bulk batches across them "
                         "(parallel/bcnn_data_parallel.py); 0 = disabled")
    ap.add_argument("--data-micro-batch", type=int,
                    default=pc.DATA_MICRO_BATCH,
                    help="per-shard granule of the data-parallel forward "
                         "(with --data-shards)")
    ap.add_argument("--offline", action="store_true",
                    help="serve all --requests images as ONE bulk batch "
                         "through classify_batch (the paper's large-batch "
                         "scenario) instead of streaming them")
    ap.add_argument("--replicas", type=int, default=pc.ROUTER_REPLICAS,
                    help="serve through the async fleet router "
                         "(serve/router.py) over N engine replicas, each "
                         "stepped on its own thread; 1 = single engine, "
                         "no router (the default)")
    ap.add_argument("--priority-mix", default=pc.PRIORITY_MIX,
                    help="offered-traffic composition for the router "
                         "drive, 'class=weight,...' over the classes "
                         "online (deadline-carrying) and bulk "
                         "(best-effort)")
    ap.add_argument("--max-queue", type=int, default=pc.ROUTER_MAX_QUEUE,
                    help="router admission-queue bound; past it requests "
                         "are shed with a typed RouterOverload")
    ap.add_argument("--rolling-swap", action="store_true",
                    help="with --replicas >= 2 (or --autoscale): hot-swap "
                         "the fleet to a re-seeded weight set halfway "
                         "through the drive (rolling walk — traffic never "
                         "drops)")
    ap.add_argument("--autoscale", action="store_true",
                    help="elastic fleet (serve/autoscale.py): a controller "
                         "thread scales the replica count between "
                         "--min-replicas and --max-replicas as offered "
                         "load crosses the hysteresis watermarks "
                         "(AUTOSCALE_* in configs/bcnn_cifar10.py)")
    ap.add_argument("--min-replicas", type=int,
                    default=pc.AUTOSCALE_MIN_REPLICAS,
                    help="autoscaler floor (with --autoscale)")
    ap.add_argument("--max-replicas", type=int,
                    default=pc.AUTOSCALE_MAX_REPLICAS,
                    help="autoscaler ceiling (with --autoscale)")
    ap.add_argument("--online-reserve", type=int, default=pc.ONLINE_RESERVE,
                    help="per-replica dispatch slots bulk chunks may never "
                         "occupy (fleet tier) — keeps online latency flat "
                         "under a co-scheduled bulk batch; 0 disables")
    ap.add_argument("--bulk-chunk", type=int, default=pc.BULK_CHUNK,
                    help="micro-chunk size bulk batches are split into for "
                         "co-scheduling (fleet tier); 0 = one request per "
                         "image")
    ap.add_argument("--autotune", action="store_true",
                    help="measure-and-cache kernel autotuning "
                         "(kernels/autotune.py): reuse a matching cached "
                         "plan from --tuning-cache/--artifact if one "
                         "exists ('tuning: cache hit'), otherwise time "
                         "the legal candidate space on this device and "
                         "serve the winning ExecutionPlan (bit-exact by "
                         "construction)")
    ap.add_argument("--tuning-cache", default="", metavar="DIR",
                    help="artifact directory to read a cached tuning "
                         "section from (falls back to --artifact); stale "
                         "or foreign-device entries are ignored, never "
                         "an error")
    ap.add_argument("--export-artifact", default="", metavar="DIR",
                    help="after building the plan, export the served "
                         "weights (plus the tuned plan, with --autotune) "
                         "as a deployment artifact to DIR")
    ap.add_argument("--seed", type=int, default=0)
    args = ap.parse_args(argv)
    compile_cache.enable()

    if args.artifact:
        from repro.core import bcnn_artifact
        packed = bcnn_artifact.load_packed(args.artifact)
        prov = bcnn_artifact.load_manifest(args.artifact)["provenance"]
        print(f"serving artifact {args.artifact} "
              f"(trained {prov.get('steps', '?')} steps, "
              f"seed {prov.get('seed', '?')})")
    else:
        params = bcnn.init(jax.random.PRNGKey(args.seed))
        packed = bcnn.fold_model(params)
    x, _ = SyntheticImages(global_batch=args.requests,
                           seed=args.seed).batch(0)
    plan = resolve_plan(packed, args)
    if args.export_artifact:
        export_artifact(args.export_artifact, packed, plan, args)
    if args.replicas >= 2 or args.autoscale:
        return serve_fleet(packed, x, args, plan=plan)
    if args.rolling_swap:
        raise SystemExit("--rolling-swap needs --replicas >= 2 or "
                         "--autoscale (the rolling walk is a fleet-tier "
                         "operation)")
    eng = BCNNEngine.from_packed(packed, n_slots=args.slots, plan=plan,
                                 pipeline_stages=args.pipeline_stages,
                                 pipeline_micro_batch=args.micro_batch,
                                 data_shards=args.data_shards,
                                 data_micro_batch=args.data_micro_batch,
                                 history=max(4096, args.requests))
    if args.pipeline_stages > 1:
        plan = eng.forward.plan
        print(f"pipelined forward: {plan.n_stages} stages over "
              f"{len(set(eng.forward.devices))} device(s), "
              f"micro-batch {args.micro_batch}")
        for s in range(plan.n_stages):
            print(f"  stage {s}: {' + '.join(plan.stage_layers(s))}  "
                  f"(cost {plan.stage_costs[s]:.3g})")
    if eng.batch_forward is not None:
        plan = eng.batch_forward.plan
        print(f"data-parallel bulk forward: {plan.data_shards} shard(s) × "
              f"{plan.n_stages} stage(s), micro-batch {plan.micro_batch} "
              f"(chunk {plan.chunk}; classify_batch routes batches >= "
              f"{eng.batch_threshold})")

    if args.offline:
        # warm (one compile per plan — any batch size reuses it), then time
        eng.classify_batch(x)
        t0 = time.perf_counter()
        logits = eng.classify_batch(x)
        dt = time.perf_counter() - t0
        assert logits.shape == (args.requests, pc.N_CLASSES)
        routed = ("data-parallel forward" if eng.batch_forward is not None
                  and args.requests >= eng.batch_threshold else "slot path")
        print(f"offline batch of {args.requests}: {args.requests/dt:.1f} "
              f"img/s ({dt*1e3:.0f} ms wall, via {routed}; bulk forward "
              f"compiled {eng.batch_cache_size}×)")
        return 0

    if args.rate > 0:
        d = drive_poisson(eng, x, args.rate, seed=args.seed)
        out, st = d["results"], d["stats"]
        print(f"Poisson arrivals @ {args.rate:.1f} req/s:")
    else:
        eng.warmup()
        t0 = time.perf_counter()
        for img in x:
            eng.submit(img)
        out = eng.run()
        dt = time.perf_counter() - t0
        st = eng.stats(last_n=args.requests)
        print(f"batch-of-{args.requests} submitted up front "
              f"({dt:.2f}s wall):")
    assert len(out) == args.requests, "engine dropped requests"
    # throughput is None when the wall span was too short to estimate
    hz = (f"{st['throughput']:.1f}" if st["throughput"] is not None
          else "n/a")
    print(f"  served {st['n']}/{args.requests} requests, "
          f"{hz} img/s over {eng.steps_executed} steps "
          f"({args.slots} slots, step compiled {eng.step_cache_size}×)")
    print(f"  latency  p50 {st['p50']*1e3:7.1f} ms   "
          f"p95 {st['p95']*1e3:7.1f} ms   p99 {st['p99']*1e3:7.1f} ms")
    print(f"  queue-wait p50 {st['queue_p50']*1e3:5.1f} ms")
    return 0


if __name__ == "__main__":
    raise SystemExit(main())
