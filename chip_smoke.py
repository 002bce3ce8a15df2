"""Smoke test of the serving system on the TPU, through its own entry points.

Run from the root of a checkout, on a machine with a TPU:

    python chip_smoke.py             # one chip (the default)
    python chip_smoke.py --chips 4   # the paths that exist only across chips

One chip: the paper's Table 2 BCNN at full width (random weights from
``--seed``), served with the default ``ExecutionPlan`` (the Pallas ``mxu``
path on TPU):

1. online — ``BCNNEngine.from_packed`` answers a few dozen requests; the
   step must hold a Pallas kernel (``tpu_custom_call``) and compile once;
2. offline — one ``classify_batch`` of 256 images through the bulk
   data-parallel forward;
3. router — ``Router.from_packed`` with 2 replicas on the one chip; a
   replica that dies fails the run with its own error;
4. reference — the online, offline and router logits must equal the
   ``path="xla"`` forward on the same chip bit for bit, and their argmax
   must agree with ``forward_eval``, the float32 oracle;
5. LM — ``xnor-lm-tiny``: the packed forward with the weight-only
   ``binary_weight_matmul`` equals the ``path="xla"`` forward bit for bit,
   and a few decode steps through ``make_serving_engine`` give the tokens
   of the same engine on the ``path="xla"`` kernels.

``--chips 4``: ``make_sharded_forward(data_shards=4)`` and
``make_pipelined_forward(n_stages=4)``, each on 4 distinct TPU devices and
equal to the single-chip ``forward_packed`` logits. Nothing else runs.

The last line of standard output is one JSON object,
``{"ok": true, "device": {"platform": "tpu", "kind": ..., "count": N}}``.
Without a TPU the script exits non-zero before it builds a model: there is
no CPU fallback. Any failed check raises, so the exit code is non-zero and
no result line is printed. One process, no children.
"""
from __future__ import annotations

import argparse
import dataclasses
import json
import os
import sys
import time

sys.path.insert(0, os.path.join(os.path.dirname(os.path.abspath(__file__)),
                                "src"))

N_ONLINE = 48         # online requests (a few dozen)
N_OFFLINE = 256       # images in the offline classify_batch
N_ROUTER = 32         # router requests: online singles + one bulk batch
N_FOUR = 64           # images through each four-chip forward


def log(msg: str) -> None:
    print(msg, flush=True)


def require_tpu(n_chips: int):
    import jax
    devs = jax.devices()
    if devs[0].platform != "tpu":
        raise SystemExit(f"chip_smoke: JAX found no TPU (platform "
                         f"{devs[0].platform!r}); this script has no CPU "
                         f"fallback")
    if len(devs) < n_chips:
        raise SystemExit(f"chip_smoke: --chips {n_chips} needs {n_chips} "
                         f"TPU devices, JAX sees {len(devs)}")
    return devs


def count_cache_events() -> dict:
    """Persistent compilation cache hits/misses, from JAX's own events."""
    from jax import monitoring
    counts = {"hits": 0, "misses": 0}
    names = {"/jax/compilation_cache/cache_hits": "hits",
             "/jax/compilation_cache/cache_misses": "misses"}

    def on_event(event, **_):
        if event in names:
            counts[names[event]] += 1

    monitoring.register_event_listener(on_event)
    return counts


def timed(fn):
    t0 = time.perf_counter()
    out = fn()
    return out, time.perf_counter() - t0


def assert_equal(name: str, got, want) -> None:
    import numpy as np
    got, want = np.asarray(got), np.asarray(want)
    if got.shape != want.shape or not np.array_equal(got, want):
        bad = (int(np.sum(np.any(got != want, axis=-1)))
               if got.shape == want.shape else "all")
        raise AssertionError(f"{name}: logits differ from the reference "
                             f"({bad} of {len(want)} rows)")
    log(f"  {name}: {len(want)} rows bit-exact")


def wait_router(router, reqs, timeout_s: float = 300.0):
    """Logits of every request; a dead replica re-raises its own error
    instead of leaving its requests to time out."""
    deadline = time.perf_counter() + timeout_s
    while not all(r.done for r in reqs):
        for rep in router.replicas_ever:
            if rep.death_error is not None:
                raise RuntimeError(f"router replica {rep.id} died"
                                   ) from rep.death_error
        if time.perf_counter() > deadline:
            raise TimeoutError(f"router served {sum(r.done for r in reqs)}"
                               f"/{len(reqs)} requests in {timeout_s} s")
        time.sleep(0.01)
    return [r.wait(0) for r in reqs]


def one_chip(seed: int) -> None:
    import jax
    import jax.numpy as jnp
    import numpy as np

    from repro import configs
    from repro.configs import bcnn_cifar10 as pc
    from repro.core import bcnn, execution_plan
    from repro.data import SyntheticImages
    from repro.models import xnor_lm
    from repro.serve import BCNNEngine, Router

    params = bcnn.init(jax.random.PRNGKey(seed))
    packed = bcnn.fold_model(params)
    plan = execution_plan.default_plan(packed)
    n_slots = pc.SERVE_N_SLOTS
    x, _ = SyntheticImages(global_batch=N_OFFLINE, seed=seed).batch(0)
    log(f"plan: {json.dumps(plan.describe())}")
    assert plan.path == "mxu", f"default plan on TPU is {plan.path!r}"

    # the references: the XLA path on this chip, at the batch sizes the
    # serving paths run (slot-sized steps, one offline chunk)
    ref_fwd = bcnn.make_packed_forward(
        packed, plan=dataclasses.replace(plan, path="xla"))
    ref_slots = np.concatenate([np.asarray(ref_fwd(x[i:i + n_slots]))
                                for i in range(0, N_OFFLINE, n_slots)])
    ref_bulk = np.asarray(ref_fwd(x))
    oracle_top1 = np.asarray(jnp.argmax(bcnn.forward_eval(params, x), -1))

    # 1. online
    eng = BCNNEngine.from_packed(packed, n_slots=n_slots, plan=plan)
    _, compile_s = timed(eng.warmup)
    assert eng.plan.path == "mxu"
    hlo = eng.forward.lower(
        jnp.zeros((n_slots, *pc.INPUT_SHAPE), jnp.float32)).compile()
    assert "tpu_custom_call" in hlo.as_text(), \
        "the compiled step holds no Pallas kernel"
    rids = [eng.submit(img) for img in x[:N_ONLINE]]
    out, serve_s = timed(eng.run)
    assert len(out) == N_ONLINE, f"served {len(out)}/{N_ONLINE}"
    assert eng.step_cache_size == 1, f"step compiled {eng.step_cache_size}×"
    online = np.stack([out[r] for r in rids])
    log(f"online: {N_ONLINE} requests, {n_slots} slots, "
        f"{eng.steps_executed} steps in {serve_s:.3f} s; step compile "
        f"{compile_s:.1f} s, compiled {eng.step_cache_size}×, "
        f"tpu_custom_call present")

    # 2. offline
    off = BCNNEngine.from_packed(packed, n_slots=n_slots, plan=plan,
                                 data_shards=1, data_micro_batch=N_OFFLINE)
    offline, off_s = timed(lambda: off.classify_batch(x))
    assert off.batch_cache_size == 1, \
        f"bulk forward compiled {off.batch_cache_size}×"
    log(f"offline: classify_batch of {N_OFFLINE} in {off_s:.1f} s "
        f"(compile included), bulk forward compiled 1×")

    # 3. router
    router, fleet_s = timed(lambda: Router.from_packed(
        packed, n_replicas=2, n_slots=n_slots, plan=plan))
    try:
        n_single = N_ROUTER // 2
        reqs = [router.submit(img) for img in x[:n_single]]
        reqs += router.submit_batch(x[n_single:N_ROUTER], cls="bulk")
        routed = np.stack(wait_router(router, reqs))
        for rep in router.replicas_ever:
            assert rep.alive and rep.step_cache_size == 1, \
                (rep.id, rep.alive, rep.step_cache_size)
        assert router.replica_deaths == 0
        log(f"router: {N_ROUTER} requests over 2 replicas "
            f"(served {[rep.served for rep in router.replicas_ever]}), "
            f"fleet build {fleet_s:.1f} s, each replica compiled 1×")
    finally:
        router.shutdown()

    # 4. reference checks, on the chip
    log("reference (path='xla' on this chip):")
    assert_equal("online", online, ref_slots[:N_ONLINE])
    assert_equal("offline", offline, ref_bulk)
    assert_equal("router", routed, ref_slots[:N_ROUTER])
    for name, got in (("online", online), ("offline", offline),
                      ("router", routed)):
        top1 = np.argmax(got, -1)
        if not np.array_equal(top1, oracle_top1[:len(got)]):
            raise AssertionError(f"{name}: argmax disagrees with "
                                 f"forward_eval")
    log("  argmax agrees with forward_eval (float32 oracle) on all rows")

    # 5. LM decode
    cfg = configs.get_config("xnor-lm-tiny")
    lm_packed = xnor_lm.fold(cfg, xnor_lm.init(cfg, jax.random.PRNGKey(seed)))
    rng = np.random.default_rng(seed)
    toks = jnp.asarray(rng.integers(0, cfg.vocab_size, (4, 16)), jnp.int32)
    assert_equal("LM forward (bw)",
                 xnor_lm.forward_packed(cfg, lm_packed, toks, mode="bw"),
                 xnor_lm.forward_packed(cfg, lm_packed, toks, mode="bw",
                                        path="xla"))
    prompts = [rng.integers(0, cfg.vocab_size, (8,)).tolist()
               for _ in range(4)]
    decoded, lm_s = {}, {}
    for path in ("mxu", "xla"):
        lm, _ = xnor_lm.make_serving_engine(cfg, lm_packed, n_slots=4,
                                            max_len=64, mode="bw", path=path)
        ids = [lm.submit(p, max_new_tokens=8) for p in prompts]
        out, s_ = timed(lm.run)
        assert lm.step_cache_size == 1, \
            f"LM step compiled {lm.step_cache_size}×"
        decoded[path] = [out[i] for i in ids]
        lm_s[path] = s_
    assert all(len(t) == 8 for t in decoded["mxu"]), decoded["mxu"]
    assert decoded["mxu"] == decoded["xla"], decoded
    log(f"LM: xnor-lm-tiny, 4 requests × 8 tokens in {lm_s['mxu']:.1f} s "
        f"(compile included), step compiled 1×; tokens equal the "
        f"path='xla' engine's")


def four_chips(seed: int, devs) -> None:
    import jax
    import numpy as np

    from repro.core import bcnn, execution_plan
    from repro.data import SyntheticImages
    from repro.parallel.bcnn_data_parallel import make_sharded_forward
    from repro.parallel.bcnn_pipeline import make_pipelined_forward

    packed = bcnn.fold_model(bcnn.init(jax.random.PRNGKey(seed)))
    plan = execution_plan.default_plan(packed)
    assert plan.path == "mxu", f"default plan on TPU is {plan.path!r}"
    x, _ = SyntheticImages(global_batch=N_FOUR, seed=seed).batch(0)
    single = np.asarray(bcnn.make_packed_forward(packed, plan=plan)(x))
    log(f"single chip: forward_packed on {devs[0]} ({plan.path} plan)")

    sharded = make_sharded_forward(packed, data_shards=4,
                                   micro_batch=N_FOUR // 4, plan=plan)
    out, s = timed(lambda: np.asarray(sharded(x)))
    used = set(sharded.devices)
    assert len(used) == 4 and used <= set(devs), used
    assert sharded.cache_size() == 1
    log(f"sharded: data_shards=4 on {sorted(d.id for d in used)} "
        f"in {s:.1f} s (compile included)")
    assert_equal("sharded", out, single)

    piped = make_pipelined_forward(packed, n_stages=4, micro_batch=8,
                                   plan=plan)
    out, s = timed(lambda: np.asarray(piped(x)))
    used = set(piped.devices)
    assert len(used) == 4 and used <= set(devs), used
    log(f"pipelined: n_stages=4 on {[d.id for d in piped.devices]} "
        f"in {s:.1f} s (compile included)")
    assert_equal("pipelined", out, single)


def main(argv=None) -> int:
    ap = argparse.ArgumentParser(description=__doc__.split("\n\n")[0])
    ap.add_argument("--chips", type=int, default=1, choices=(1, 4))
    ap.add_argument("--seed", type=int, default=0)
    args = ap.parse_args(argv)

    devs = require_tpu(args.chips)
    from repro.launch import compile_cache
    cache_dir = compile_cache.enable()
    cache = count_cache_events()
    t0 = time.perf_counter()
    if args.chips == 4:
        four_chips(args.seed, devs)
    else:
        one_chip(args.seed)
    log(f"compile cache {cache_dir}: {cache['hits']} hits, "
        f"{cache['misses']} misses; total {time.perf_counter() - t0:.1f} s")
    import jax
    d = jax.devices()
    print(json.dumps({"ok": True, "device": {
        "platform": d[0].platform, "kind": d[0].device_kind,
        "count": len(d)}}), flush=True)
    return 0


if __name__ == "__main__":
    raise SystemExit(main())
