"""Dry-run machinery: abstract lowering of every (arch × shape × mesh) cell,
plus the roofline-term extraction from the compiled artifact.

IMPORTANT: this module does NOT set XLA flags; the ``dryrun.py`` entry point
sets ``--xla_force_host_platform_device_count=512`` *before* importing jax.
Import this lib only from contexts that already configured devices.
"""
from __future__ import annotations

import json
import math
import os
import re
import time
from dataclasses import asdict, dataclass, field

import jax
import jax.numpy as jnp
import numpy as np
from jax.sharding import NamedSharding
from jax.sharding import PartitionSpec as P

from repro import configs
from repro.configs.base import InputShape
from repro.launch import hlo_analysis
from repro.launch import mesh as mesh_lib
from repro.models import transformer
from repro.parallel import sharding
from repro.train import optimizer as opt_lib
from repro.train import train_loop

# TPU v5e-class hardware constants (per chip) — DESIGN.md §7.
HW = {
    "peak_flops": 197e12,   # bf16 FLOP/s
    "hbm_bw": 819e9,        # bytes/s
    "link_bw": 50e9,        # bytes/s per ICI link direction
    "hbm_bytes": 16e9,
}


# ---------------------------------------------------------------------------
# abstract inputs
# ---------------------------------------------------------------------------

def _sds(shape, dtype):
    return jax.ShapeDtypeStruct(shape, dtype)


def input_specs(cfg, shape: InputShape) -> dict:
    """ShapeDtypeStruct stand-ins for every model input of this cell."""
    gb, s = shape.global_batch, shape.seq_len
    fe = None
    if cfg.family == "vlm":
        fe = _sds((gb, cfg.frontend_seq, cfg.d_model), jnp.bfloat16)
    if cfg.family == "audio":
        fe = _sds((gb, cfg.encoder_seq, cfg.d_model), jnp.bfloat16)
    if shape.kind == "train":
        return {"batch": transformer.Batch(
            tokens=_sds((gb, s), jnp.int32),
            targets=_sds((gb, s), jnp.int32),
            frontend=fe)}
    if shape.kind == "prefill":
        return {"tokens": _sds((gb, s), jnp.int32), "frontend": fe}
    # decode: one new token against a seq_len cache
    state = jax.eval_shape(
        lambda: transformer.init_serve_state(cfg, gb, s))
    if cfg.family == "audio":
        ekv = jax.eval_shape(
            lambda: (jnp.zeros((cfg.n_layers, gb, cfg.encoder_seq,
                                cfg.n_heads, cfg.head_dim), jnp.bfloat16),) * 2)
        state = transformer.ServeState(state.caches, ekv, state.length)
    return {"state": state, "tokens": _sds((gb, 1), jnp.int32),
            "frontend": fe}


def abstract_params(cfg, *, serving_packed: bool = False):
    if serving_packed:
        from repro.serve.packing import pack_params_for_serving
        return jax.eval_shape(
            lambda: pack_params_for_serving(
                transformer.init_params(cfg, jax.random.PRNGKey(0))))
    return jax.eval_shape(
        lambda: transformer.init_params(cfg, jax.random.PRNGKey(0)))


def abstract_train_state(cfg, adamw: opt_lib.AdamW):
    return jax.eval_shape(
        lambda: train_loop.init_train_state(cfg, jax.random.PRNGKey(0),
                                            adamw))


# ---------------------------------------------------------------------------
# collective parsing (post-SPMD optimized HLO → per-chip link bytes)
# ---------------------------------------------------------------------------

_DTYPE_BYTES = {"pred": 1, "s8": 1, "u8": 1, "s16": 2, "u16": 2, "bf16": 2,
                "f16": 2, "s32": 4, "u32": 4, "f32": 4, "s64": 8, "u64": 8,
                "f64": 8, "c64": 8, "c128": 16}

_COLL_RE = re.compile(
    r"=\s+(?P<outs>\(?[a-z0-9_,\[\]{}\s]*?\)?)\s+"
    r"(?P<op>all-reduce|all-gather|reduce-scatter|all-to-all|"
    r"collective-permute)(?P<start>-start)?\(")
_SHAPE_RE = re.compile(r"([a-z]+[0-9]*)\[([0-9,]*)\]")
_GROUPS_RE = re.compile(r"replica_groups=\{\{([0-9,]+)\}")
_GROUPS2_RE = re.compile(r"replica_groups=\[(\d+),(\d+)\]")


def _shape_bytes(type_str: str) -> int:
    total = 0
    for dt, dims in _SHAPE_RE.findall(type_str):
        if dt not in _DTYPE_BYTES:
            continue
        n = 1
        for d in dims.split(","):
            if d:
                n *= int(d)
        total += n * _DTYPE_BYTES[dt]
    return total


def parse_collectives(hlo_text: str) -> list[dict]:
    """Extract (op, out_bytes, group_size, link_bytes) per collective op.

    link_bytes models a ring schedule per chip:
        all-gather          (n−1)/n · out        (receives all other shards)
        all-reduce          2·(n−1)/n · out      (reduce-scatter + all-gather)
        reduce-scatter      (n−1)·out            (input = n·out streams through)
        all-to-all          (n−1)/n · out
        collective-permute  out
    """
    out = []
    for line in hlo_text.splitlines():
        m = _COLL_RE.search(line)
        if not m:
            continue
        op = m.group("op")
        nbytes = _shape_bytes(m.group("outs"))
        if nbytes == 0:
            continue
        g = _GROUPS_RE.search(line)
        if g:
            n = len(g.group(1).split(","))
        else:
            g2 = _GROUPS2_RE.search(line)
            n = int(g2.group(2)) if g2 else 2
        n = max(n, 2)
        if op == "all-gather":
            link = nbytes * (n - 1) / n
        elif op == "all-reduce":
            link = 2 * nbytes * (n - 1) / n
        elif op == "reduce-scatter":
            link = nbytes * (n - 1)
        elif op == "all-to-all":
            link = nbytes * (n - 1) / n
        else:  # collective-permute
            link = nbytes
        out.append({"op": op, "out_bytes": nbytes, "group": n,
                    "link_bytes": link})
    return out


# ---------------------------------------------------------------------------
# cell runner
# ---------------------------------------------------------------------------

@dataclass
class CellResult:
    arch: str
    shape: str
    mesh: str
    quant: str
    ok: bool
    error: str = ""
    compile_s: float = 0.0
    # per-chip numbers (trip-count-aware HLO analysis; see hlo_analysis.py)
    hlo_flops: float = 0.0
    hlo_bytes: float = 0.0
    coll_link_bytes: float = 0.0
    coll_counts: dict = field(default_factory=dict)
    dot_flops: float = 0.0
    # bytes that stay in VMEM on TPU (Pallas in-kernel bit-unpack); the
    # kernel-adjusted memory term is t_memory_kernel (see hlo_analysis)
    unpack_credit: float = 0.0
    convert_credit: float = 0.0
    t_memory_kernel: float = 0.0
    # raw XLA cost_analysis aggregates (count scan bodies ONCE — kept as a
    # lower-bound cross-check, not used for the roofline terms)
    xla_flops: float = 0.0
    xla_bytes: float = 0.0
    arg_bytes: float = 0.0
    temp_bytes: float = 0.0
    # roofline terms (seconds)
    t_compute: float = 0.0
    t_memory: float = 0.0
    t_collective: float = 0.0
    bottleneck: str = ""
    model_flops: float = 0.0
    useful_ratio: float = 0.0
    notes: str = ""

    def terms(self):
        return {"compute": self.t_compute, "memory": self.t_memory,
                "collective": self.t_collective}


def model_flops_for(cfg, shape: InputShape) -> float:
    """MODEL_FLOPS = 6·N·D (dense) / 6·N_active·D (MoE); decode: D = batch
    tokens per step; prefill: forward only → 2·N·D."""
    n_active = cfg.param_count(active_only=True)
    if shape.kind == "train":
        d = shape.global_batch * shape.seq_len
        return 6.0 * n_active * d
    if shape.kind == "prefill":
        d = shape.global_batch * shape.seq_len
        return 2.0 * n_active * d
    return 2.0 * n_active * shape.global_batch      # decode: 1 token/seq


def _lower_cell(cfg, shape: InputShape, mesh, *, microbatches: int = 1):
    """Build + lower + compile one cell. Returns (lowered, compiled).

    Decode cells use the weight-stationary serving shardings (§Perf iter 1)
    and, in binary modes, the packed 1-bit serving artifact (§Perf iter 2).
    """
    specs = input_specs(cfg, shape)
    packed = shape.kind == "decode" and cfg.quant in ("binary",
                                                      "binary_weights")
    params_abs = abstract_params(cfg, serving_packed=packed)
    if shape.kind == "decode":
        pshard = sharding.serving_param_shardings(params_abs, mesh)
    else:
        pshard = sharding.param_shardings(params_abs, mesh)

    if shape.kind == "train":
        adamw = opt_lib.AdamW(
            clip_latent_unit=(cfg.quant in ("binary", "binary_weights")))
        step = train_loop.make_train_step(cfg, adamw,
                                          microbatches=microbatches)
        state_abs = abstract_train_state(cfg, adamw)
        sshard = train_loop.TrainState(
            params=pshard,
            opt=opt_lib.AdamWState(
                step=NamedSharding(mesh, P()),
                m=pshard, v=pshard),
            ef=None)
        bshard = sharding.data_shardings(mesh, shape.global_batch,
                                         specs["batch"])
        fn = jax.jit(step, in_shardings=(sshard, bshard),
                     out_shardings=(sshard, NamedSharding(mesh, P())),
                     donate_argnums=(0,))
        lowered = fn.lower(state_abs, specs["batch"])
    elif shape.kind == "prefill":
        def prefill_fn(params, tokens, frontend):
            return transformer.prefill(cfg, params, tokens, frontend)
        tshard = sharding.data_shardings(mesh, shape.global_batch,
                                         specs["tokens"])
        fshard = (sharding.data_shardings(mesh, shape.global_batch,
                                          specs["frontend"])
                  if specs["frontend"] is not None else None)
        fn = jax.jit(prefill_fn,
                     in_shardings=(pshard, tshard, fshard),
                     out_shardings=NamedSharding(
                         mesh, sharding.batch_spec(mesh, shape.global_batch)
                         if shape.global_batch > 1 else P()))
        lowered = fn.lower(params_abs, specs["tokens"], specs["frontend"])
    else:  # decode
        serve = train_loop.make_serve_step(cfg)
        st_shard = sharding.state_shardings(specs["state"], mesh,
                                            shape.global_batch)
        tshard = sharding.data_shardings(mesh, shape.global_batch,
                                         specs["tokens"])
        fshard = (sharding.data_shardings(mesh, shape.global_batch,
                                          specs["frontend"])
                  if specs["frontend"] is not None else None)
        fn = jax.jit(serve,
                     in_shardings=(pshard, st_shard, tshard, fshard),
                     out_shardings=(NamedSharding(mesh, P()), st_shard),
                     donate_argnums=(1,))
        lowered = fn.lower(params_abs, specs["state"], specs["tokens"],
                           specs["frontend"])
    compiled = lowered.compile()
    return lowered, compiled


def analyze(compiled, mesh, cfg, shape: InputShape) -> dict:
    """Roofline terms from the compiled artifact (per-chip, post-SPMD).

    Primary numbers come from the trip-count-aware HLO analyzer
    (hlo_analysis.analyze_module) because XLA's cost_analysis counts every
    ``lax.scan`` body once. The raw XLA aggregates ride along as
    ``xla_flops``/``xla_bytes`` lower-bound cross-checks.
    """
    ca = compiled.cost_analysis() or {}
    hlo = hlo_analysis.analyze_module(compiled.as_text())
    flops, byts, link = hlo.flops, hlo.bytes, hlo.coll_link_bytes
    ma = compiled.memory_analysis()
    t_c = flops / HW["peak_flops"]
    t_m = byts / HW["hbm_bw"]
    t_l = link / HW["link_bw"]
    mf = model_flops_for(cfg, shape)
    chips = math.prod(mesh.shape.values())
    terms = {"compute": t_c, "memory": t_m, "collective": t_l}
    return {
        "hlo_flops": flops, "hlo_bytes": byts, "coll_link_bytes": link,
        "coll_counts": {k: round(v, 1) for k, v in hlo.coll_counts.items()},
        "dot_flops": hlo.dot_flops,
        "unpack_credit": hlo.unpack_credit,
        "convert_credit": hlo.convert_credit,
        "t_memory_kernel": max(byts - hlo.unpack_credit
                               - hlo.convert_credit, 0.0) / HW["hbm_bw"],
        "xla_flops": float(ca.get("flops", 0.0)),
        "xla_bytes": float(ca.get("bytes accessed", 0.0)),
        "arg_bytes": float(getattr(ma, "argument_size_in_bytes", 0)),
        "temp_bytes": float(getattr(ma, "temp_size_in_bytes", 0)),
        "t_compute": t_c, "t_memory": t_m, "t_collective": t_l,
        "bottleneck": max(terms, key=terms.get),
        "model_flops": mf,
        "useful_ratio": (mf / (flops * chips)) if flops else 0.0,
    }


def run_cell(arch: str, shape: InputShape, *, multi_pod: bool = False,
             quant: str = "none", microbatches: int = 0,
             pods: int = 0) -> CellResult:
    mesh = mesh_lib.make_production_mesh(multi_pod=multi_pod, pods=pods)
    n_pods = pods or (2 if multi_pod else 1)
    mesh_name = f"{n_pods}x16x16" if n_pods > 1 else "16x16"
    cfg = configs.get_config(arch, quant=quant)
    if microbatches == 0:   # default: per-arch grad accumulation (HBM fit)
        microbatches = cfg.train_microbatches if shape.kind == "train" else 1
    res = CellResult(arch=arch, shape=shape.name, mesh=mesh_name, quant=quant,
                     ok=False)
    t0 = time.time()
    try:
        with mesh:
            lowered, compiled = _lower_cell(cfg, shape, mesh,
                                            microbatches=microbatches)
            res.compile_s = time.time() - t0
            info = analyze(compiled, mesh, cfg, shape)
            for k, v in info.items():
                setattr(res, k, v)
            res.ok = True
    except Exception as e:  # noqa: BLE001 — cell failures are data
        res.error = f"{type(e).__name__}: {e}"[:500]
        res.compile_s = time.time() - t0
    return res


def cells_for(arch: str) -> list[InputShape]:
    return configs.get_shapes(arch)


def save_result(res: CellResult, out_dir: str):
    os.makedirs(out_dir, exist_ok=True)
    fname = f"{res.arch}__{res.shape}__{res.mesh}__{res.quant}.json"
    with open(os.path.join(out_dir, fname), "w") as f:
        json.dump(asdict(res), f, indent=1)
