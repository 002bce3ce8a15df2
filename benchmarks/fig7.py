"""Paper Fig. 7: throughput & energy efficiency vs batch size, FPGA vs GPU.

Three layers of reproduction:

1. **Analytic** — the paper's own numbers: the FPGA curve is flat (streaming
   architecture, eq. 12 is batch-independent); the GPU curve scales with
   occupancy. We reproduce the published ratios (8.3× @ b16, ≈1× @ b512,
   75×/9.5× energy).

2. **Measured, offline (our implementation)** — wall-clock throughput of our
   deployment-path BCNN (packed bits + XNOR matmul, path="xla" so XLA
   executes natively on CPU) across batch sizes. The claim under test is
   *shape*: per-image time ≈ flat in batch for the streaming formulation.
   Absolute CPU numbers are not TPU-representative; the TPU projection
   comes from the roofline harness instead.

3. **Measured, online (``--online``)** — the paper's actual serving
   scenario: individual requests streamed through the slot engine
   (serve/bcnn_engine.py). Two curves: step wall-clock vs slot *occupancy*
   (the measured flat-vs-occupancy analogue of the paper's flat FPGA
   curve, with the jit step compiled exactly once across occupancies
   1..n_slots), and per-request latency percentiles vs offered Poisson
   load (queueing tail at a held throughput).

4. **Measured, pipelined (``--pipeline``)** — the paper's *spatial*
   parallelism story (§4, Fig. 5/6): the 9-layer forward cut into
   cost-balanced stages over a device mesh (parallel/bcnn_pipeline.py).
   Reports the analytic stage plans (Table 2 costs, eq. 12 bottleneck,
   fill/drain efficiency), measured throughput vs n_stages, per-stage
   wall-clock, and the engine step-time-vs-occupancy curve served through
   the pipelined forward (zero-recompile guard included). On CPU the
   harness forces ≥2 simulated host devices (XLA_FLAGS, set below before
   jax imports) so the multi-device path is exercised.

5. **Measured, offline data-parallel (``--offline``)** — the paper's
   *large-batch* scenario ("static data in large batch sizes", §6.3):
   throughput vs batch size × device count through the batch-sharded
   data-parallel forward (parallel/bcnn_data_parallel.py), including a
   ragged-batch bit-exactness check against ``forward_packed`` and the
   one-compile-per-plan guard. Uses the same simulated-device shim as
   ``--pipeline``.

6. **Measured, fleet router (``--router``)** — the same streaming
   discipline scaled *across* engines (serve/router.py): mixed
   online+bulk Poisson traffic offered to an async router over N
   replicated engines at fractions of measured fleet capacity. Reports
   offered rate vs per-priority-class p50/p95/p99 — the curve under test
   is the SLO scheduler's class separation (online tail protected while
   bulk soaks the slack) — plus the per-replica one-compile guard.

7. **Measured, elastic fleet (``--autoscale``)** — the serving fleet
   under a load STEP (low → burst → idle) with the autoscaler active
   (serve/autoscale.py): a deterministic pump-mode replay (injected
   virtual clock, 1 ms/tick) records the replica-count timeline
   1 → N → 1 and per-class latency through the step, then a wall-clock
   A/B on one replica pits co-scheduled bulk (micro-chunks behind an
   online reserve) against bulk-monopoly (the whole batch as one
   dispatch) at the same offered load — the claim under test is that
   co-scheduling keeps online p99 strictly below the monopoly tail.

Every ``--json`` dump embeds the deployment-plan metadata
(shards / stages / micro-batch) alongside the curves, so a dumped curve
is reproducible from the artifact alone (schema pinned by
tests/test_fig7_schema.py).

Run:  PYTHONPATH=src python benchmarks/fig7.py
          [--online | --pipeline | --offline] [--json out.json]
"""
from __future__ import annotations

import argparse
import json
import math
import sys
import time

# --pipeline/--offline need >1 device to demonstrate multi-device scaling;
# on a plain-CPU host, simulate them before jax's first import (see
# src/repro/launch/device_shim.py for the contract), keyed on the raw
# argv ("fig7-*" covers `-m benchmarks.run --only ...`).
from repro.launch.device_shim import force_host_devices

if any(a in ("--pipeline", "fig7-pipeline", "--offline", "fig7-offline")
       for a in sys.argv):
    force_host_devices(2)

import jax
import jax.numpy as jnp
import numpy as np

from repro.configs import bcnn_cifar10 as pc
from repro.core import bcnn
from repro.launch import compile_cache
from repro.serve import BCNNEngine, drive_poisson


def paper_curves() -> dict:
    """The paper's published operating points."""
    b = np.array(pc.FIG7_BATCH_SIZES, np.float64)
    # GPU occupancy model calibrated to the two published endpoints:
    # fps(b) = peak · b/(b + b_half);  fps(16)=749, fps(512)=6218
    # → b_half from the ratio.
    peak_ratio = pc.PAPER_GPU_XNOR_FPS_B512 / pc.PAPER_GPU_XNOR_FPS_B16
    # solve fps(b)=peak·b/(b+h): 6218/749 = (512/(512+h))/(16/(16+h))
    # → h ≈ 16·(r−1)/(1−16r/512)
    r = peak_ratio
    h = 16 * (r - 1) / (1 - 16 * r / 512)
    peak = pc.PAPER_GPU_XNOR_FPS_B512 * (512 + h) / 512
    gpu_fps = peak * b / (b + h)
    fpga_fps = np.full_like(b, float(pc.PAPER_FPGA_FPS))
    return {
        "batch": b, "fpga_fps": fpga_fps, "gpu_fps": gpu_fps,
        "fpga_eff": fpga_fps / pc.PAPER_FPGA_W,
        "gpu_eff": gpu_fps / pc.PAPER_GPU_W,
        "speedup_b16": float(fpga_fps[0] / gpu_fps[0]),
        "eff_ratio_b16": float((fpga_fps[0] / pc.PAPER_FPGA_W)
                               / (gpu_fps[0] / pc.PAPER_GPU_W)),
        "eff_ratio_b512": float((fpga_fps[-1] / pc.PAPER_FPGA_W)
                                / (gpu_fps[-1] / pc.PAPER_GPU_W)),
    }


def measured_curve(batches=(1, 4, 16, 64), reps: int = 3,
                   conv_strategy: str = pc.CONV_STRATEGY) -> dict:
    """Our packed BCNN per-image latency vs batch (XLA path, CPU).

    ``conv_strategy`` selects the binary-conv dataflow (core/bconv.py;
    default from configs/bcnn_cifar10.py): "direct" is the im2col-free path
    whose batch-insensitivity is the Fig. 7 claim under test; "im2col" is
    the patch-matmul baseline. On CPU both run as XLA-lowered references —
    the wall-clock contrast is dataflow shape, not the Pallas kernel.
    """
    params = bcnn.init(jax.random.PRNGKey(0))
    packed = bcnn.fold_model(params)
    out = {"batch": [], "img_per_s": [], "us_per_img": [],
           "conv_strategy": conv_strategy}
    for b in batches:
        x = jax.random.uniform(jax.random.PRNGKey(b), (b, 32, 32, 3))
        fn = lambda xx: bcnn.forward_packed(packed, xx, path="xla",
                                            conv_strategy=conv_strategy)
        fn(x).block_until_ready()                      # compile+warm
        t0 = time.perf_counter()
        for _ in range(reps):
            fn(x).block_until_ready()
        dt = (time.perf_counter() - t0) / reps
        out["batch"].append(b)
        out["img_per_s"].append(b / dt)
        out["us_per_img"].append(dt / b * 1e6)
    return out


def _occupancy_sweep(eng: BCNNEngine, n_slots: int, rng, reps: int) -> dict:
    """Step wall-clock with k of n_slots live, k = 1..n_slots — the
    measured flat-vs-occupancy curve (the paper's Fig. 7 FPGA analogue),
    shared by the online and pipelined harnesses so both measure the same
    way. Image generation + submission happen off the clock (the curve
    under test is the engine *step*, not host-side O(k) prep); timings
    are averaged over ``reps``."""
    occ = {"occupancy": [], "step_ms": [], "us_per_live_img": []}
    for k in range(1, n_slots + 1):
        dt = 0.0
        for _ in range(reps):
            for img in rng.random((k, 32, 32, 3), np.float32):
                eng.submit(img)
            t0 = time.perf_counter()
            eng.run()
            dt += time.perf_counter() - t0
        dt /= reps
        occ["occupancy"].append(k)
        occ["step_ms"].append(dt * 1e3)
        occ["us_per_live_img"].append(dt / k * 1e6)
    return occ


def online_curve(n_slots: int = pc.SERVE_N_SLOTS, n_requests: int = 24,
                 load_fracs=pc.FIG7_ONLINE_LOAD_FRACS, reps: int = 2,
                 conv_strategy: str = pc.CONV_STRATEGY,
                 seed: int = 0) -> dict:
    """Measured online-serving curves from the streaming BCNN engine.

    1. *Occupancy sweep*: step wall-clock with k of n_slots live,
       k = 1..n_slots. The streaming claim is that the step is flat in
       occupancy (slots are data, not shape) — per-*request* latency is
       batch-insensitive, the Fig. 7 FPGA-curve analogue. The jit cache is
       asserted to hold exactly ONE compilation across the whole sweep.
    2. *Load sweep*: Poisson arrivals at fractions of the measured
       full-occupancy capacity; reports achieved throughput + p50/p95/p99
       end-to-end request latency (the queueing tail the paper's
       batch-accumulating GPU baseline pays even harder).
    """
    params = bcnn.init(jax.random.PRNGKey(seed))
    packed = bcnn.fold_model(params)
    eng = BCNNEngine.from_packed(packed, n_slots=n_slots, path="xla",
                                 conv_strategy=conv_strategy)
    eng.warmup()
    rng = np.random.default_rng(seed)

    occ = _occupancy_sweep(eng, n_slots, rng, reps)
    compiles = eng.step_cache_size
    assert compiles == 1, (
        f"BCNN step recompiled: jit cache size {compiles} after occupancy "
        f"sweep 1..{n_slots} (streaming contract is exactly 1)")
    cap_hz = n_slots / (occ["step_ms"][-1] / 1e3)

    load = {"offered_hz": [], "achieved_hz": [], "p50_ms": [], "p95_ms": [],
            "p99_ms": [], "queue_p50_ms": []}
    for frac in load_fracs:
        imgs = rng.random((n_requests, 32, 32, 3)).astype(np.float32)
        d = drive_poisson(eng, imgs, rate_hz=frac * cap_hz,
                          seed=seed + 1, warmup=False)
        st = d["stats"]
        load["offered_hz"].append(d["offered_hz"])
        load["achieved_hz"].append(st["throughput"])
        for p in (50, 95, 99):
            load[f"p{p}_ms"].append(st[f"p{p}"] * 1e3)
        load["queue_p50_ms"].append(st["queue_p50"] * 1e3)

    return {"n_slots": n_slots, "n_requests": n_requests,
            "step_compilations": compiles, "capacity_hz": cap_hz,
            "occupancy_sweep": occ, "load_sweep": load,
            "conv_strategy": conv_strategy,
            "plan": {"data_shards": 1, "n_stages": 1, "micro_batch": None,
                     "n_slots": n_slots, "conv_fusion": pc.CONV_FUSION,
                     "fused_groups": [[list(g) for g in
                                       bcnn.plan_layer_groups()]]}}


def run_online(verbose: bool = True, **kw) -> dict:
    res = online_curve(**kw)
    if verbose:
        occ, load = res["occupancy_sweep"], res["load_sweep"]
        print(f"online serving (streaming BCNN engine, {res['n_slots']} "
              f"slots, XLA-on-CPU):")
        print("  occupancy sweep — the measured flat curve "
              "(paper Fig. 7 FPGA analogue):")
        for k, ms, us in zip(occ["occupancy"], occ["step_ms"],
                             occ["us_per_live_img"]):
            print(f"    {k}/{res['n_slots']} slots live: step "
                  f"{ms:7.1f} ms   {us:9.0f} us/live-img")
        flat = max(occ["step_ms"]) / min(occ["step_ms"])
        print(f"    step-time spread across occupancies: {flat:.2f}× "
              f"(streaming claim: ≈flat); jit compilations: "
              f"{res['step_compilations']} (contract: 1)")
        print(f"  capacity at full occupancy: {res['capacity_hz']:.1f} "
              f"img/s; Poisson load sweep ({res['n_requests']} req each):")
        for i in range(len(load["offered_hz"])):
            ach = load["achieved_hz"][i]   # None: span too short to estimate
            print(f"    offered {load['offered_hz'][i]:6.1f} req/s → "
                  f"achieved "
                  f"{f'{ach:6.1f}' if ach is not None else '   n/a'} img/s  "
                  f"p50 {load['p50_ms'][i]:7.1f} ms  "
                  f"p95 {load['p95_ms'][i]:7.1f} ms  "
                  f"p99 {load['p99_ms'][i]:7.1f} ms")
    return res


def router_curve(n_replicas: int = pc.FIG7_ROUTER_REPLICAS,
                 n_slots: int = pc.SERVE_N_SLOTS, n_requests: int = 32,
                 load_fracs=pc.FIG7_ROUTER_LOAD_FRACS,
                 mix: dict | None = None, reps: int = 2,
                 conv_strategy: str = pc.CONV_STRATEGY,
                 seed: int = 0) -> dict:
    """Measured fleet-router load sweep (serve/router.py): offered Poisson
    rate vs per-priority-class latency percentiles.

    Capacity is probed on ONE replica (the shared occupancy sweep) and
    scaled by the replica count; the sweep then offers mixed online+bulk
    traffic (default mix from ``configs.PRIORITY_MIX``'s classes, 3:1) at
    ``load_fracs`` of that fleet capacity through a threaded router over
    ``n_replicas`` live replicas. The curve under test is the SLO
    scheduler's class separation: online p99 should stay near the
    single-step floor while bulk absorbs the queueing tail. The
    zero-recompile contract is asserted PER REPLICA after the whole sweep
    (each replica owns one jit closure compiled exactly once)."""
    from repro.serve import Router, drive_mixed_poisson

    if mix is None:
        mix = {"online": 3, "bulk": 1}
    params = bcnn.init(jax.random.PRNGKey(seed))
    packed = bcnn.fold_model(params)
    rng = np.random.default_rng(seed)

    # capacity probe on a throwaway single engine (same folded weights)
    probe = BCNNEngine.from_packed(packed, n_slots=n_slots, path="xla",
                                   conv_strategy=conv_strategy)
    probe.warmup()
    occ = _occupancy_sweep(probe, n_slots, rng, reps)
    assert probe.step_cache_size == 1
    cap_hz = n_replicas * n_slots / (occ["step_ms"][-1] / 1e3)

    router = Router.from_packed(packed, n_replicas=n_replicas,
                                n_slots=n_slots, path="xla",
                                conv_strategy=conv_strategy,
                                history=max(4096, n_requests))
    load = {"offered_hz": [], "n_rejected": [], "per_class": []}
    try:
        for frac in load_fracs:
            imgs = rng.random((n_requests, 32, 32, 3)).astype(np.float32)
            d = drive_mixed_poisson(router, imgs, rate_hz=frac * cap_hz,
                                    mix=mix, seed=seed + 1)
            load["offered_hz"].append(d["offered_hz"])
            load["n_rejected"].append(d["n_rejected"])
            point = {}
            for nm, st in d["stats"].items():
                if st["n"] == 0:
                    point[nm] = {"n": 0}
                    continue
                point[nm] = {"n": st["n"],
                             "p50_ms": st["p50"] * 1e3,
                             "p95_ms": st["p95"] * 1e3,
                             "p99_ms": st["p99"] * 1e3}
            load["per_class"].append(point)
        replica_compiles = [rep.step_cache_size for rep in router.replicas]
        assert all(c == 1 for c in replica_compiles), (
            f"fleet replica recompiled: per-replica jit cache sizes "
            f"{replica_compiles} after load sweep (contract is exactly 1 "
            f"per replica)")
    finally:
        router.shutdown()

    return {"n_replicas": n_replicas, "n_slots": n_slots,
            "n_requests": n_requests, "mix": dict(mix),
            "capacity_hz": cap_hz, "occupancy_sweep": occ,
            "load_sweep": load, "replica_compilations": replica_compiles,
            "conv_strategy": conv_strategy,
            "plan": {"data_shards": 1, "n_stages": 1, "micro_batch": None,
                     "n_replicas": n_replicas, "n_slots": n_slots,
                     "conv_fusion": pc.CONV_FUSION,
                     "fused_groups": [[list(g) for g in
                                       bcnn.plan_layer_groups()]]}}


def run_router(verbose: bool = True, **kw) -> dict:
    res = router_curve(**kw)
    if verbose:
        load = res["load_sweep"]
        print(f"fleet router ({res['n_replicas']} replicas × "
              f"{res['n_slots']} slots, XLA-on-CPU, mix "
              + ", ".join(f"{k}={v}" for k, v in res["mix"].items())
              + "):")
        print(f"  fleet capacity estimate: {res['capacity_hz']:.1f} img/s; "
              f"per-replica jit compilations: "
              f"{res['replica_compilations']} (contract: 1 each)")
        for i, hz in enumerate(load["offered_hz"]):
            rej = load["n_rejected"][i]
            print(f"  offered {hz:6.1f} req/s"
                  + (f"  ({rej} shed)" if rej else ""))
            for nm, st in load["per_class"][i].items():
                if st["n"] == 0:
                    print(f"    [{nm}] no arrivals at this point")
                    continue
                print(f"    [{nm}] n={st['n']:3d}  "
                      f"p50 {st['p50_ms']:7.1f} ms  "
                      f"p95 {st['p95_ms']:7.1f} ms  "
                      f"p99 {st['p99_ms']:7.1f} ms")
    return res


class _TickClock:
    """Deterministic virtual clock for the pump-mode load-step replay:
    every reading advances 1 ms, so the autoscaler's window/cooldown and
    the recorded timeline are machine-independent."""

    def __init__(self, dt: float = 1e-3):
        self.t = 0.0
        self.dt = dt

    def __call__(self) -> float:
        self.t += self.dt
        return self.t


def _cosched_point(packed, online_imgs, bulk_imgs, *, n_slots: int,
                   reserve: int, chunk: int, conv_strategy: str) -> dict:
    """One co-scheduling A/B arm: ONE replica offered the same bulk batch
    + trailing online probes, pump-mode with the real clock. ``reserve``/
    ``chunk`` select the arm — (online_reserve, n_slots-chunks) is the
    co-scheduled fleet discipline, (0, whole-batch) the monopoly cliff."""
    from repro.serve import Router

    router = Router.from_packed(packed, n_replicas=1, n_slots=n_slots,
                                path="xla", conv_strategy=conv_strategy,
                                threaded=False, online_reserve=reserve,
                                max_queue=4 * (len(online_imgs)
                                               + len(bulk_imgs)))
    try:
        t0 = time.perf_counter()
        router.submit_batch(bulk_imgs, cls="bulk", chunk=chunk)
        for im in online_imgs:
            router.submit(im, cls="online")
        router.run_until_idle()
        wall = time.perf_counter() - t0
        st = router.stats("online")
        compiles = [r.step_cache_size for r in router.replicas_ever]
        assert all(c == 1 for c in compiles), (
            f"co-scheduling arm recompiled: {compiles}")
        return {"reserve": reserve, "chunk": chunk,
                "n_online": st["n"], "n_bulk": len(bulk_imgs),
                "online_p50_ms": st["p50"] * 1e3,
                "online_p95_ms": st["p95"] * 1e3,
                "online_p99_ms": st["p99"] * 1e3,
                "wall_ms": wall * 1e3,
                "replica_compilations": compiles}
    finally:
        router.shutdown()


def autoscale_curve(n_slots: int = 2, max_replicas: int = 3,
                    low_requests: int = 4, burst_online: int = 16,
                    burst_bulk: int = 8, online_probe: int = 6,
                    ab_bulk: int = 16, idle_pumps: int = 600,
                    conv_strategy: str = pc.CONV_STRATEGY,
                    seed: int = 0) -> dict:
    """Measured elastic-fleet curves (serve/autoscale.py + router
    co-scheduling).

    1. *Load step* (deterministic): pump-mode fleet on a virtual tick
       clock, starting at ONE replica. A low trickle holds the pressure
       under the up-watermark, a mixed online+bulk burst drives it far
       over (scale-up to ``max_replicas`` headroom), an idle tail drains
       the window back under the down-watermark (scale-down to the
       floor). Records the replica-count timeline, per-class latency
       percentiles in virtual ticks, and the one-compile-per-replica
       contract over every replica that EVER existed.
    2. *Co-scheduling A/B* (wall-clock): one replica offered an identical
       bulk batch + online probes twice — micro-chunked behind an online
       reserve vs the whole batch as one monopoly dispatch. The online
       tail must be strictly better co-scheduled.
    """
    from repro.serve import AutoscaleConfig, Router

    params = bcnn.init(jax.random.PRNGKey(seed))
    packed = bcnn.fold_model(params)
    rng = np.random.default_rng(seed)

    clock = _TickClock()
    cfg = AutoscaleConfig(min_replicas=1, max_replicas=max_replicas,
                          up_watermark=2.0, down_watermark=0.25,
                          window_s=0.004, cooldown_s=0.03, interval_s=1e-3)
    router = Router.from_packed(
        packed, n_replicas=1, n_slots=n_slots, path="xla",
        conv_strategy=conv_strategy, threaded=False, clock=clock,
        autoscale=cfg, online_reserve=1, bulk_chunk=2,
        max_queue=4 * (low_requests + burst_online + burst_bulk))
    try:
        # phase 1 — low: a trickle the seed replica absorbs alone
        for i in range(low_requests):
            router.submit(rng.random((32, 32, 3), np.float32) * 2 - 1,
                          cls="online")
            router.run_until_idle()
        # phase 2 — burst: mixed online+bulk, pressure >> up_watermark
        for i in range(burst_online):
            router.submit(rng.random((32, 32, 3), np.float32) * 2 - 1,
                          cls="online")
        router.submit_batch(
            rng.random((burst_bulk, 32, 32, 3), np.float32) * 2 - 1,
            cls="bulk")
        router.run_until_idle()
        # phase 3 — idle: the window drains, the fleet walks back down
        for _ in range(idle_pumps):
            router.pump()
        a = router.autoscaler
        timeline = a.timeline(1)
        per_class = {}
        for nm in router.class_names:
            st = router.stats(nm)
            per_class[nm] = ({"n": 0} if st["n"] == 0 else
                             {"n": st["n"],
                              "p50_ticks": st["p50"] / clock.dt,
                              "p95_ticks": st["p95"] / clock.dt,
                              "p99_ticks": st["p99"] / clock.dt})
        compiles = [r.step_cache_size for r in router.replicas_ever]
        assert all(c == 1 for c in compiles), (
            f"elastic fleet recompiled: per-replica jit cache sizes "
            f"{compiles} across the load step (contract is exactly 1 per "
            f"replica, spawned or retired)")
        load_step = {
            "phases": {"low": low_requests,
                       "burst_online": burst_online,
                       "burst_bulk": burst_bulk,
                       "idle_pumps": idle_pumps},
            "clock": "virtual (1 ms/tick)",
            "timeline": [[t, n] for t, n in timeline],
            "n_scale_ups": a.n_scale_ups,
            "n_scale_downs": a.n_scale_downs,
            "peak_replicas": max(n for _, n in timeline),
            "final_replicas": router.n_replicas,
            "per_class": per_class,
            "replica_compilations": compiles,
        }
    finally:
        router.shutdown()

    online_imgs = rng.random((online_probe, 32, 32, 3)).astype(np.float32)
    bulk_imgs = rng.random((ab_bulk, 32, 32, 3)).astype(np.float32)
    cosched = {
        "coscheduled": _cosched_point(packed, online_imgs, bulk_imgs,
                                      n_slots=n_slots, reserve=1,
                                      chunk=n_slots,
                                      conv_strategy=conv_strategy),
        "monopoly": _cosched_point(packed, online_imgs, bulk_imgs,
                                   n_slots=n_slots, reserve=0,
                                   chunk=ab_bulk,
                                   conv_strategy=conv_strategy),
    }
    return {"n_slots": n_slots,
            "config": {"min_replicas": cfg.min_replicas,
                       "max_replicas": cfg.max_replicas,
                       "up_watermark": cfg.up_watermark,
                       "down_watermark": cfg.down_watermark,
                       "window_s": cfg.window_s,
                       "cooldown_s": cfg.cooldown_s,
                       "interval_s": cfg.interval_s},
            "load_step": load_step, "coscheduling": cosched,
            "conv_strategy": conv_strategy,
            "plan": {"data_shards": 1, "n_stages": 1, "micro_batch": None,
                     "n_slots": n_slots, "conv_fusion": pc.CONV_FUSION,
                     "fused_groups": [[list(g) for g in
                                       bcnn.plan_layer_groups()]]}}


def run_autoscale(verbose: bool = True, **kw) -> dict:
    res = autoscale_curve(**kw)
    if verbose:
        ls, co = res["load_step"], res["coscheduling"]
        cfg = res["config"]
        print(f"elastic fleet ({res['n_slots']} slots/replica, "
              f"{cfg['min_replicas']}..{cfg['max_replicas']} replicas, "
              f"watermarks {cfg['down_watermark']}/{cfg['up_watermark']}, "
              f"XLA-on-CPU):")
        print(f"  load step low({ls['phases']['low']}) → "
              f"burst({ls['phases']['burst_online']} online + "
              f"{ls['phases']['burst_bulk']} bulk) → idle — replica "
              f"timeline ({ls['clock']}):")
        for t, n in ls["timeline"]:
            print(f"    t={t:8.3f}  {n} replica(s)")
        print(f"  {ls['n_scale_ups']} scale-up(s), {ls['n_scale_downs']} "
              f"scale-down(s); peak {ls['peak_replicas']}, settled back to "
              f"{ls['final_replicas']}; per-replica compiles "
              f"{ls['replica_compilations']} (contract: 1 each, ever)")
        for nm, st in ls["per_class"].items():
            if st["n"]:
                print(f"    [{nm}] n={st['n']:3d}  p50 "
                      f"{st['p50_ticks']:6.0f}  p95 {st['p95_ticks']:6.0f}  "
                      f"p99 {st['p99_ticks']:6.0f} ticks")
        print(f"  co-scheduling A/B ({co['monopoly']['n_bulk']} bulk images"
              f" + {co['monopoly']['n_online']} online probes, 1 replica, "
              f"wall-clock):")
        for mode in ("coscheduled", "monopoly"):
            p = co[mode]
            print(f"    {mode:12s} (reserve {p['reserve']}, chunk "
                  f"{p['chunk']:2d}): online p50 {p['online_p50_ms']:7.1f} "
                  f"ms  p99 {p['online_p99_ms']:7.1f} ms   "
                  f"(batch wall {p['wall_ms']:7.1f} ms)")
        ratio = (co["monopoly"]["online_p99_ms"]
                 / co["coscheduled"]["online_p99_ms"])
        print(f"    online p99 protected {ratio:.1f}× by co-scheduling "
              f"(claim: strictly better than the monopoly cliff)")
    return res


def pipeline_curve(stage_counts=pc.FIG7_PIPELINE_STAGE_COUNTS,
                   n_images: int = 16, micro_batch: int = 2,
                   n_slots: int = pc.SERVE_N_SLOTS, reps: int = 2,
                   conv_strategy: str = pc.CONV_STRATEGY,
                   seed: int = 0) -> dict:
    """Measured stage-pipeline curves (parallel/bcnn_pipeline.py).

    For each stage count: the analytic plan (Table 2 stage costs, eq. 12
    bottleneck, fill/drain efficiency at this micro-batch count), measured
    end-to-end throughput of a ``n_images`` batch through the pipelined
    forward (parity-checked against ``forward_packed``), per-stage
    wall-clock (the measured eq. 12 balance), and the engine
    step-time-vs-occupancy sweep served through the pipelined forward
    (per-stage jit compiled exactly once across the whole sweep).
    """
    from repro.parallel import bcnn_pipeline as bp

    params = bcnn.init(jax.random.PRNGKey(seed))
    packed = bcnn.fold_model(params)
    rng = np.random.default_rng(seed)
    x = rng.random((n_images, 32, 32, 3)).astype(np.float32)
    ref = np.asarray(bcnn.forward_packed(packed, jnp.asarray(x), path="xla",
                                         conv_strategy=conv_strategy))

    out = {"devices": [str(d) for d in jax.devices()],
           "n_images": n_images, "micro_batch": micro_batch,
           "conv_strategy": conv_strategy, "stages": []}
    n_micro = -(-n_images // micro_batch)
    for s in stage_counts:
        plan = bp.plan_bcnn_stages(s)
        sched = bp.schedule_stream(plan, n_micro)
        fwd = bp.make_pipelined_forward(packed, n_stages=s,
                                        micro_batch=micro_batch, path="xla",
                                        conv_strategy=conv_strategy)
        got = np.asarray(fwd(x))                    # compile + parity
        np.testing.assert_array_equal(got, ref)
        t0 = time.perf_counter()
        for _ in range(reps):
            jax.block_until_ready(fwd(x))
        dt = (time.perf_counter() - t0) / reps
        stage_ms = [t * 1e3 for t in fwd.stage_times(x)]

        # occupancy sweep through the engine riding this pipeline: the
        # streaming claim (flat step, ONE compile per stage) must survive
        # the extra pipeline layer
        eng = BCNNEngine.from_packed(packed, n_slots=n_slots, path="xla",
                                     conv_strategy=conv_strategy,
                                     pipeline_stages=s,
                                     pipeline_micro_batch=1)
        eng.warmup()
        occ = _occupancy_sweep(eng, n_slots, rng, reps)
        compiles = eng.step_cache_size
        assert compiles == 1, (
            f"pipelined step recompiled: per-stage jit cache {compiles} "
            f"after occupancy sweep 1..{n_slots} (contract is exactly 1)")

        out["stages"].append({
            "n_stages": s,
            "plan": {"data_shards": 1, "n_stages": s,
                     "micro_batch": micro_batch,
                     "conv_fusion": pc.CONV_FUSION,
                     "fused_groups": [[list(g) for g in
                                       bcnn.plan_layer_groups(
                                           plan.bounds[i], plan.bounds[i + 1])]
                                      for i in range(s)]},
            "bounds": list(plan.bounds),
            "stage_layers": [" + ".join(plan.stage_layers(i))
                             for i in range(s)],
            "stage_costs": list(plan.stage_costs),
            "bottleneck": plan.bottleneck,
            "balance": plan.balance,
            "fill_drain_efficiency": sched["efficiency"],
            "img_per_s": n_images / dt,
            "stage_ms": stage_ms,
            "occupancy_sweep": occ,
            "step_compilations": compiles,
        })
    return out


def run_pipeline(verbose: bool = True, **kw) -> dict:
    res = pipeline_curve(**kw)
    if verbose:
        print(f"stage-pipelined deployment forward "
              f"({len(res['devices'])} device(s), XLA-on-CPU, "
              f"micro-batch {res['micro_batch']}):")
        for st in res["stages"]:
            print(f"  {st['n_stages']} stage(s): "
                  f"{st['img_per_s']:6.1f} img/s   "
                  f"balance {st['balance']:.2f}   "
                  f"fill/drain eff {st['fill_drain_efficiency']:.2f}   "
                  f"compiles/stage {st['step_compilations']}")
            for i, (layers, c, ms) in enumerate(zip(
                    st["stage_layers"], st["stage_costs"], st["stage_ms"])):
                print(f"    stage {i}: {layers:<40s} "
                      f"cost {c:12.4g}   {ms:7.1f} ms")
            occ = st["occupancy_sweep"]
            steps = "  ".join(f"{k}:{ms:.0f}ms" for k, ms in
                              zip(occ["occupancy"], occ["step_ms"]))
            print(f"    engine occupancy sweep (step wall-clock): {steps}")
    return res


def offline_curve(batch_sizes=pc.FIG7_OFFLINE_BATCH_SIZES,
                  shard_counts=pc.FIG7_DATA_SHARD_COUNTS,
                  micro_batch: int = pc.DATA_MICRO_BATCH,
                  n_stages: int = 1, reps: int = 2,
                  conv_strategy: str = pc.CONV_STRATEGY,
                  seed: int = 0) -> dict:
    """Measured large-batch data-parallel curves (the paper's §6.3
    "static data in large batch sizes" scenario).

    For each device-shard count: build the batch-sharded forward
    (``parallel/bcnn_data_parallel.py::make_sharded_forward``), verify
    bit-exactness against ``forward_packed`` on a ragged batch, then sweep
    ``batch_sizes`` measuring end-to-end throughput. Every point reuses
    the ONE compiled chunk shape — the compile-count guard is asserted
    after the whole sweep. Shard counts the host cannot place (shards ×
    stages > devices) are reported in ``"skipped"`` rather than silently
    dropped. Each curve embeds its full deployment-plan metadata.
    """
    from repro.parallel.bcnn_data_parallel import make_sharded_forward

    params = bcnn.init(jax.random.PRNGKey(seed))
    packed = bcnn.fold_model(params)
    rng = np.random.default_rng(seed)
    out = {"devices": [str(d) for d in jax.devices()],
           "conv_strategy": conv_strategy, "n_stages": n_stages,
           "micro_batch": micro_batch, "curves": [], "skipped": []}
    for shards in shard_counts:
        if shards * n_stages > len(jax.devices()):
            out["skipped"].append(
                {"data_shards": shards,
                 "reason": f"{shards} shard(s) × {n_stages} stage(s) needs "
                           f"{shards * n_stages} devices, have "
                           f"{len(jax.devices())}"})
            continue
        fwd = make_sharded_forward(packed, data_shards=shards,
                                   micro_batch=micro_batch,
                                   n_stages=n_stages, path="xla",
                                   conv_strategy=conv_strategy)
        # bit-exactness on a ragged batch (one past a full chunk)
        xr = rng.random((fwd.plan.chunk + 1, 32, 32, 3)).astype(np.float32)
        ref = np.asarray(bcnn.forward_packed(packed, jnp.asarray(xr),
                                             path="xla",
                                             conv_strategy=conv_strategy))
        np.testing.assert_array_equal(np.asarray(fwd(xr)), ref)
        curve = {"plan": fwd.plan.describe(), "batch": [], "img_per_s": [],
                 "us_per_img": []}
        for b in batch_sizes:
            x = rng.random((b, 32, 32, 3)).astype(np.float32)
            jax.block_until_ready(fwd(x))                        # warm
            t0 = time.perf_counter()
            for _ in range(reps):
                jax.block_until_ready(fwd(x))
            dt = (time.perf_counter() - t0) / reps
            curve["batch"].append(b)
            curve["img_per_s"].append(b / dt)
            curve["us_per_img"].append(dt / b * 1e6)
        compiles = fwd.cache_size()
        assert compiles == 1, (
            f"sharded forward recompiled: cache size {compiles} after "
            f"batch sweep {list(batch_sizes)} at {shards} shard(s) "
            f"(contract is exactly one compile per plan)")
        curve["compilations"] = compiles
        out["curves"].append(curve)
    return out


def run_offline(verbose: bool = True, **kw) -> dict:
    res = offline_curve(**kw)
    if verbose:
        print(f"offline data-parallel batch serving "
              f"({len(res['devices'])} device(s), XLA-on-CPU, per-shard "
              f"micro-batch {res['micro_batch']}):")
        for c in res["curves"]:
            p = c["plan"]
            print(f"  {p['data_shards']} shard(s) × {p['n_stages']} "
                  f"stage(s) (chunk {p['chunk']}), compiled "
                  f"{c['compilations']}×:")
            for b, ips, us in zip(c["batch"], c["img_per_s"],
                                  c["us_per_img"]):
                print(f"    batch {b:4d}: {ips:8.1f} img/s  "
                      f"{us:9.0f} us/img")
        for s in res["skipped"]:
            print(f"  skipped {s['data_shards']} shard(s): {s['reason']}")
        if len(res["curves"]) > 1:
            base, top = res["curves"][0], res["curves"][-1]
            speedup = top["img_per_s"][-1] / base["img_per_s"][-1]
            print(f"  large-batch speedup "
                  f"{top['plan']['data_shards']}÷"
                  f"{base['plan']['data_shards']} shards: {speedup:.2f}×")
    return res


def xnor_lm_curve(n_slots: int = 4, prompt_len: int = 8, max_new: int = 16,
                  batches=(1, 2, 4, 8), reps: int = 2, seed: int = 0,
                  smoke: bool = True) -> dict:
    """Fig. 7-style prefill/decode throughput for the XNOR LM
    (models/xnor_lm.py) on the slot engine — the second binary workload's
    serving section of the perf record (BENCH_9+).

    1. *Prefill*: full-sequence packed forward (``mode="xnor"`` — both
       operands 1-bit) tokens/s vs batch; the streaming claim is flat
       per-token time.
    2. *Decode occupancy sweep*: the slot engine at occupancy
       k = 1..n_slots, generated-tokens/s per step — occupancy is data,
       so the jit cache must hold exactly ONE compilation across the
       sweep AND across a weight hot-swap
       (``XnorLMServeModel.swap_arrays``), re-measured post-swap.
    """
    from repro.configs import xnor_lm_tiny
    from repro.models import xnor_lm

    cfg = xnor_lm_tiny.SMOKE_CONFIG if smoke else xnor_lm_tiny.CONFIG
    params = xnor_lm.init(cfg, jax.random.PRNGKey(seed))
    packed = xnor_lm.fold(cfg, params)
    rng = np.random.default_rng(seed)
    seq = min(2 * prompt_len, cfg.max_len - 2)

    fwd = jax.jit(lambda t: xnor_lm.forward_packed(cfg, packed, t,
                                                   mode="xnor", path="xla"))
    prefill = {"batch": [], "tok_per_s": [], "ms_per_seq": []}
    for b in batches:
        toks = jnp.asarray(rng.integers(0, cfg.vocab_size, (b, seq)),
                           jnp.int32)
        fwd(toks).block_until_ready()          # compile off the clock
        t0 = time.perf_counter()
        for _ in range(reps):
            fwd(toks).block_until_ready()
        dt = (time.perf_counter() - t0) / reps
        prefill["batch"].append(b)
        prefill["tok_per_s"].append(b * seq / dt)
        prefill["ms_per_seq"].append(dt / b * 1e3)

    eng, model = xnor_lm.make_serving_engine(cfg, packed, n_slots=n_slots,
                                             mode="bw", path="xla")
    eng.submit([1], max_new_tokens=1)
    eng.run()                                  # the one compile, off the clock

    def decode_sweep() -> dict:
        out = {"occupancy": [], "step_ms": [], "tok_per_s": []}
        for k in range(1, n_slots + 1):
            dt = 0.0
            steps = 0
            for _ in range(reps):
                for _ in range(k):
                    prompt = rng.integers(0, cfg.vocab_size,
                                          (prompt_len,)).tolist()
                    eng.submit(prompt, max_new_tokens=max_new)
                s0 = eng.steps_executed
                t0 = time.perf_counter()
                eng.run()
                dt += time.perf_counter() - t0
                steps += eng.steps_executed - s0
            out["occupancy"].append(k)
            out["step_ms"].append(dt / steps * 1e3)
            out["tok_per_s"].append(k * max_new * reps / dt)
        return out

    decode = decode_sweep()
    compiles = eng.step_cache_size
    assert compiles == 1, (
        f"XNOR LM decode step recompiled: jit cache size {compiles} across "
        f"occupancies 1..{n_slots} (streaming contract is exactly 1)")

    # weight hot-swap mid-benchmark: same executable, fresh weights
    packed2 = xnor_lm.fold(cfg, xnor_lm.init(cfg, jax.random.PRNGKey(seed + 1)))
    eng.swap_params(model.swap_arrays(packed2))
    decode_post_swap = decode_sweep()
    swap_compiles = eng.step_cache_size
    assert swap_compiles == 1, (
        f"weight hot-swap recompiled the LM decode step "
        f"(jit cache size {swap_compiles})")

    return {"config": {"d_model": cfg.d_model, "n_layers": cfg.n_layers,
                       "n_heads": cfg.n_heads, "d_ff": cfg.d_ff,
                       "vocab_size": cfg.vocab_size,
                       "param_count": cfg.param_count()},
            "n_slots": n_slots, "prompt_len": prompt_len,
            "max_new": max_new, "seq": seq,
            "prefill": prefill, "decode": decode,
            "decode_post_swap": decode_post_swap,
            "step_compilations": compiles,
            "swap_step_compilations": swap_compiles}


def run_xnor_lm(verbose: bool = True, **kw) -> dict:
    res = xnor_lm_curve(**kw)
    if verbose:
        c = res["config"]
        print(f"XNOR LM serving (d={c['d_model']}, L={c['n_layers']}, "
              f"{c['param_count']:,} params, XLA-on-CPU):")
        pre = res["prefill"]
        print(f"  prefill (mode=xnor, seq={res['seq']}):")
        for b, tps, ms in zip(pre["batch"], pre["tok_per_s"],
                              pre["ms_per_seq"]):
            print(f"    batch {b:2d}: {tps:9.1f} tok/s  {ms:7.2f} ms/seq")
        for tag, dec in (("decode", res["decode"]),
                         ("decode post-swap", res["decode_post_swap"])):
            print(f"  {tag} (mode=bw, slot engine, {res['n_slots']} slots):")
            for k, ms, tps in zip(dec["occupancy"], dec["step_ms"],
                                  dec["tok_per_s"]):
                print(f"    {k}/{res['n_slots']} slots: step {ms:6.2f} ms  "
                      f"{tps:8.1f} tok/s")
        print(f"  jit compilations: {res['step_compilations']} before / "
              f"{res['swap_step_compilations']} after hot-swap "
              f"(contract: 1)")
    return res


def autotune_curve(n_slots: int = pc.SERVE_N_SLOTS, batch: int = 64,
                   reps: int = 3, seed: int = 0) -> dict:
    """Measured A/B of the autotuned plan vs the ``default_plan``
    heuristics (``kernels/autotune.py`` vs ``core/execution_plan.py``) at
    the two Fig. 7 operating points.

    One tuning run (real timer, this device), then for each plan a fresh
    engine measures:

    * *online*: full-occupancy slot-step wall time (the streaming point);
    * *offline*: one bulk ``classify_batch`` of ``batch`` images.

    Contracts asserted per plan: bit-identical logits between the two
    plans (a tuned plan may only be faster, never different) and
    ``step_cache_size == 1`` after both points. Feeds the ``autotune``
    section of the perf record (``benchmarks/gen_bench_record.py``), gated
    by ``tools/compare_bench.py`` (tuned ≥ default within the noise
    floor).
    """
    from repro.core import execution_plan
    from repro.kernels.autotune import autotune_packed

    packed = bcnn.fold_model(bcnn.init(jax.random.PRNGKey(seed)))
    rng = np.random.default_rng(seed)
    report: dict = {}
    plans = {"default": execution_plan.default_plan(packed),
             "tuned": autotune_packed(packed, report=report)}
    xb = rng.random((batch, 32, 32, 3)).astype(np.float32)
    points, logits = {}, {}
    for name, plan in plans.items():
        eng = BCNNEngine.from_packed(packed, n_slots=n_slots, plan=plan)
        eng.warmup()
        # online point: step wall time at full occupancy
        dt_on = 0.0
        for _ in range(reps):
            for img in xb[:n_slots]:
                eng.submit(img)
            t0 = time.perf_counter()
            eng.run()
            dt_on += time.perf_counter() - t0
        dt_on /= reps
        # offline point: one bulk classify_batch
        eng.classify_batch(xb)                              # warm
        t0 = time.perf_counter()
        out = eng.classify_batch(xb)
        dt_off = time.perf_counter() - t0
        logits[name] = np.asarray(out)
        compiles = eng.step_cache_size
        assert compiles == 1, (
            f"{name} plan recompiled: step jit cache size {compiles} after "
            f"the online + offline points (contract is exactly 1)")
        points[name] = {"plan": plan.describe(),
                        "online_step_ms": dt_on * 1e3,
                        "online_img_per_s": n_slots / dt_on,
                        "offline_img_per_s": batch / dt_off,
                        "step_compilations": compiles}
    np.testing.assert_array_equal(logits["tuned"], logits["default"])
    return {"n_slots": n_slots, "batch": batch,
            "n_candidates": report["n_candidates"],
            "n_eligible": report["n_eligible"],
            "bit_exact": True,
            "default": points["default"], "tuned": points["tuned"],
            "speedup_online": (points["tuned"]["online_img_per_s"]
                               / points["default"]["online_img_per_s"]),
            "speedup_offline": (points["tuned"]["offline_img_per_s"]
                                / points["default"]["offline_img_per_s"])}


def run_autotune(verbose: bool = True, **kw) -> dict:
    res = autotune_curve(**kw)
    if verbose:
        print(f"autotune A/B (tuned vs default_plan, "
              f"{res['n_candidates']} candidates measured, "
              f"{res['n_eligible']} eligible):")
        for name in ("default", "tuned"):
            p = res[name]
            print(f"  {name:7s}: path {p['plan']['path']}, fusion "
                  f"{'on' if p['plan']['conv_fusion'] else 'off'} — "
                  f"online {p['online_img_per_s']:8.1f} img/s, "
                  f"offline {p['offline_img_per_s']:8.1f} img/s "
                  f"(step compiled {p['step_compilations']}×)")
        print(f"  tuned/default speedup: online "
              f"{res['speedup_online']:.2f}×, offline "
              f"{res['speedup_offline']:.2f}× (logits bit-identical)")
    return res


def run(verbose: bool = True, measure: bool = True) -> dict:
    pa = paper_curves()
    res = {"paper": pa,
           "plan": {"data_shards": 1, "n_stages": 1, "micro_batch": None,
                    "conv_fusion": pc.CONV_FUSION,
                    "fused_groups": [[list(g) for g in
                                      bcnn.plan_layer_groups()]]}}
    if verbose:
        print("paper analytic (XNOR GPU kernel vs our FPGA config):")
        print(f"{'batch':>6s} {'FPGA FPS':>9s} {'GPU FPS':>9s} "
              f"{'FPGA/W':>8s} {'GPU/W':>7s}")
        for i, b in enumerate(pa["batch"]):
            print(f"{b:6.0f} {pa['fpga_fps'][i]:9.0f} {pa['gpu_fps'][i]:9.0f}"
                  f" {pa['fpga_eff'][i]:8.1f} {pa['gpu_eff'][i]:7.1f}")
        print(f"throughput ratio @16  : {pa['speedup_b16']:.1f}× "
              f"(paper: 8.3×)")
        print(f"energy-eff ratio @16  : {pa['eff_ratio_b16']:.0f}× "
              f"(paper: 75×)")
        print(f"energy-eff ratio @512 : {pa['eff_ratio_b512']:.1f}× "
              f"(paper: 9.5×)")
    if measure:
        for strat in ("im2col", "direct"):
            m = measured_curve(conv_strategy=strat)
            res[f"measured_{strat}"] = m
            if verbose:
                print(f"measured (our packed BCNN, XLA-on-CPU, "
                      f"conv={strat}):")
                for b, ips, us in zip(m["batch"], m["img_per_s"],
                                      m["us_per_img"]):
                    print(f"  batch {b:3d}: {ips:8.1f} img/s  "
                          f"{us:9.0f} us/img")
                flat = max(m["us_per_img"][1:]) / min(m["us_per_img"][1:])
                print(f"  per-image time spread (b≥4): {flat:.2f}× "
                      f"(streaming claim: ≈flat)")
        res["measured"] = res["measured_im2col"]       # back-compat alias
    return res


def _jsonable(x):
    """Recursively convert to JSON-ready values. Non-finite floats are
    REJECTED, not passed through: ``json.dump`` would otherwise emit bare
    ``Infinity``/``NaN`` — invalid JSON that breaks downstream parsers of
    the CI artifact (a measurement that cannot produce a number must say
    ``None``, e.g. ``serve/slots.py::latency_stats``'s throughput)."""
    if isinstance(x, dict):
        return {k: _jsonable(v) for k, v in x.items()}
    if isinstance(x, (list, tuple)):
        return [_jsonable(v) for v in x]
    if isinstance(x, np.ndarray):
        return _jsonable(x.tolist())
    if isinstance(x, np.generic):
        return _jsonable(x.item())
    if isinstance(x, float) and not math.isfinite(x):
        raise ValueError(
            f"non-finite float {x!r} in benchmark results: not valid JSON "
            f"(use None for undefined measurements)")
    return x


if __name__ == "__main__":
    ap = argparse.ArgumentParser()
    ap.add_argument("--online", action="store_true",
                    help="measure the streaming-engine serving curves "
                         "instead of the offline batch sweep")
    ap.add_argument("--pipeline", action="store_true",
                    help="measure the stage-pipelined multi-device forward "
                         "(parallel/bcnn_pipeline.py); on CPU this forces "
                         ">=2 simulated devices")
    ap.add_argument("--offline", action="store_true",
                    help="measure the large-batch data-parallel sweep "
                         "(parallel/bcnn_data_parallel.py): batch size × "
                         "device-shard count; on CPU this forces >=2 "
                         "simulated devices")
    ap.add_argument("--router", action="store_true",
                    help="measure the fleet-router load sweep "
                         "(serve/router.py): offered rate vs per-class "
                         "p99 over replicated engines")
    ap.add_argument("--autoscale", action="store_true",
                    help="measure the elastic fleet (serve/autoscale.py): "
                         "a deterministic low→burst→idle load step "
                         "recording the replica-count timeline, plus the "
                         "co-scheduled-bulk vs bulk-monopoly online-p99 "
                         "A/B")
    ap.add_argument("--xnor-lm", action="store_true",
                    help="measure the XNOR LM serving curves "
                         "(models/xnor_lm.py on the slot engine): prefill "
                         "tok/s vs batch and decode tok/s vs occupancy, "
                         "with the one-compile + hot-swap contracts")
    ap.add_argument("--autotune", action="store_true",
                    help="measure the autotuned-plan vs default-plan A/B "
                         "(kernels/autotune.py): online + offline "
                         "operating points, bit-exactness and one-compile "
                         "contracts asserted")
    ap.add_argument("--replicas", type=int, default=pc.FIG7_ROUTER_REPLICAS,
                    help="replica count for --router")
    ap.add_argument("--slots", type=int, default=pc.SERVE_N_SLOTS)
    ap.add_argument("--requests", type=int, default=24)
    ap.add_argument("--reps", type=int, default=2,
                    help="timing repetitions per measured point (--offline)")
    ap.add_argument("--json", default=None, metavar="PATH",
                    help="also dump the result dict as JSON")
    args = ap.parse_args()
    compile_cache.enable()
    if args.pipeline:
        out = run_pipeline(n_slots=args.slots)
    elif args.offline:
        out = run_offline(reps=args.reps)
    elif args.router:
        out = run_router(n_replicas=args.replicas, n_slots=args.slots,
                         n_requests=args.requests)
    elif args.autoscale:
        out = run_autoscale()
    elif args.xnor_lm:
        out = run_xnor_lm(n_slots=args.slots)
    elif args.autotune:
        out = run_autotune(n_slots=args.slots, reps=args.reps)
    elif args.online:
        out = run_online(n_slots=args.slots, n_requests=args.requests)
    else:
        out = run()
    if args.json:
        with open(args.json, "w") as f:
            json.dump(_jsonable(out), f, indent=2)
        print(f"wrote {args.json}")
