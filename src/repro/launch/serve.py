"""Batched serving driver: continuous batching over decode slots.

Demonstrates the paper's batch-size-insensitivity claim in its TPU form:
requests are admitted the moment a slot frees, so throughput holds at
small/irregular arrival batches (§6.3 / Fig. 7 analogue). The chip
benchmark (``bench/run.py``, cells in ``BENCHMARK.json``) measures it for
the binary CNNs.

Two model families share the one slot engine (``serve/engine.py``):

* published transformer architectures (``--arch`` from ``ARCH_MODULES``),
  served from fp training params;
* the XNOR LM (``--arch`` from ``BINARY_LM_MODULES``, e.g.
  ``xnor-lm-tiny``): `models/xnor_lm.py`'s binarized transformer folded to
  its packed deployment form — binary projections run as XNOR matmuls,
  and ``--swap`` exercises the packed-artifact hot-swap mid-run with the
  zero-recompile assertion (``step_cache_size == 1``) across it.

Usage (CPU-scale):
    PYTHONPATH=src python -m repro.launch.serve --arch qwen3-8b --smoke \
        --requests 16 --slots 4 --max-new 16
    PYTHONPATH=src python -m repro.launch.serve --arch xnor-lm-tiny --smoke \
        --requests 8 --slots 4 --max-new 8 --swap
"""
from __future__ import annotations

import argparse
import time

import jax
import numpy as np

from repro import configs
from repro.launch import compile_cache, mesh as mesh_lib
from repro.models import transformer, xnor_lm
from repro.serve import ServingEngine


def _run_requests(eng, cfg, args, rng):
    for _ in range(args.requests):
        prompt = rng.integers(0, cfg.vocab_size,
                              (args.prompt_len,)).tolist()
        fe = None
        if getattr(cfg, "family", None) == "audio":
            fe = rng.standard_normal(
                (cfg.encoder_seq, cfg.d_model)).astype(np.float32)
        eng.submit(prompt, max_new_tokens=args.max_new, frontend=fe)
    t0 = time.time()
    out = eng.run()
    return out, time.time() - t0


def main(argv=None):
    ap = argparse.ArgumentParser()
    ap.add_argument("--arch", default="qwen3-8b",
                    choices=configs.ARCH_NAMES + configs.BINARY_LM_NAMES)
    ap.add_argument("--smoke", action="store_true")
    ap.add_argument("--quant", default="none",
                    choices=["none", "binary", "binary_weights"])
    ap.add_argument("--requests", type=int, default=16)
    ap.add_argument("--slots", type=int, default=4)
    ap.add_argument("--prompt-len", type=int, default=8)
    ap.add_argument("--max-new", type=int, default=16)
    ap.add_argument("--max-len", type=int, default=128)
    ap.add_argument("--mode", default="bw", choices=["bw", "xnor"],
                    help="XNOR LM packed decode path: weight-only binary "
                         "matmul (bw) or full XNOR popcount (xnor)")
    ap.add_argument("--swap", action="store_true",
                    help="XNOR LM only: hot-swap a freshly folded packed "
                         "artifact halfway and assert zero recompiles")
    ap.add_argument("--seed", type=int, default=0)
    args = ap.parse_args(argv)
    compile_cache.enable()

    binary_lm = args.arch in configs.BINARY_LM_NAMES
    cfg = configs.get_config(args.arch, smoke=args.smoke, quant=args.quant)
    mesh = mesh_lib.make_local_mesh()
    rng = np.random.default_rng(args.seed)
    with mesh:
        if binary_lm:
            max_len = min(args.max_len, cfg.max_len)
            params = xnor_lm.init(cfg, jax.random.PRNGKey(args.seed))
            packed = xnor_lm.fold(cfg, params)
            eng, model = xnor_lm.make_serving_engine(
                cfg, packed, n_slots=args.slots, max_len=max_len,
                mode=args.mode)
            out, dt = _run_requests(eng, cfg, args, rng)
            assert eng.step_cache_size == 1, \
                f"recompile detected: {eng.step_cache_size} step caches"
            if args.swap:
                params2 = xnor_lm.init(cfg,
                                       jax.random.PRNGKey(args.seed + 1))
                eng.swap_params(model.swap_arrays(xnor_lm.fold(cfg, params2)))
                out2, dt2 = _run_requests(eng, cfg, args, rng)
                assert eng.step_cache_size == 1, \
                    "weight hot-swap must not recompile the decode step"
                assert len(out2) == args.requests
                out = {**out, **out2}   # rids are engine-wide monotonic
                dt += dt2
                print(f"hot-swap OK: step_cache_size == 1 across the swap")
        else:
            params = transformer.init_params(cfg,
                                             jax.random.PRNGKey(args.seed))
            eng = ServingEngine(cfg, params, n_slots=args.slots,
                                max_len=args.max_len)
            out, dt = _run_requests(eng, cfg, args, rng)
    n_req = args.requests * (2 if (binary_lm and args.swap) else 1)
    n_tok = sum(len(v) for v in out.values())
    print(f"served {len(out)}/{n_req} requests, {n_tok} tokens in "
          f"{dt:.2f}s ({n_tok / dt:,.1f} tok/s, "
          f"{eng.steps_executed} engine steps)")
    assert len(out) == n_req, "engine dropped requests"
    return 0


if __name__ == "__main__":
    raise SystemExit(main())
