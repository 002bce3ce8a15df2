"""Measure-and-cache kernel autotuner (ROADMAP item 4).

The heuristics baked into `core/execution_plan.py::default_plan` were tuned
on simulated-CPU runs; the FPGA-review surveys in PAPERS.md frame
per-platform specialization as the decisive accelerator lever. This module
is the software analogue: at engine startup (or artifact load) it times the
LEGAL candidate space on the actual device and returns the winning
`core/execution_plan.py::ExecutionPlan`, which
`core/bcnn_artifact.py::save_packed` persists into the deployment artifact
(``tuning`` manifest section) so the next load reuses it without
re-measuring.

Candidate space (per layer / group, legality shared with the heuristics):

* kernel ``path`` — backend-conditional: the Pallas variants ("vpu",
  "mxu") are only candidates on TPU (off-TPU they run under
  ``interpret=True``, a correctness emulator that must never win a timing
  race), "xla" always;
* conv ``strategy`` per binary conv layer — "direct" where
  `core/bconv.py::resolve_strategy` would allow it (per-position layout
  present, 32-aligned channels), "im2col" always;
* fused-pair (th, tw) output tiles — every power-of-two height whose halo
  scratch fits the `kernels/xnor_conv_fused.py::halo_scratch` VMEM budget
  (the exact legality rule ``pick_tiles`` uses), at the one width Mosaic
  accepts;
* cross-layer fusion on/off — the fused pair raced against its two-layer
  sequential fold;
* LM decode GEMM ``mode`` ("bw" | "xnor") via ``autotune_lm_mode``.

Timing protocol: ``warmup`` untimed calls (compile + cache warm), then
``reps`` timed calls, scored by the MEDIAN. The timer is injectable
(``timer=``) so tests pin deterministic winners with a fake clock. Before a
candidate may win it must reproduce the "xla" reference output bit-exactly
— a tuned plan can never change logits, only speed.

The tuned plan is keyed by (backend, device kind, model geometry)
(`core/execution_plan.py::plan_cache_key`); ``plan_for_host`` falls back to
``default_plan`` on any mismatch — stale or foreign-device cache entries
are ignored, never an error.
"""
from __future__ import annotations

import dataclasses
import time

import jax
import jax.numpy as jnp

from repro.core import bcnn, bconv, execution_plan
from repro.kernels import xnor_conv_fused as kfused
from repro.kernels.xnor_conv import tile_width

AUTOTUNE_REPS = 3          # timed calls per candidate (median wins)
AUTOTUNE_WARMUP = 1        # untimed warmup calls (compile + caches)
AUTOTUNE_BATCH = 2         # probe batch size for the timing forwards


def backend_paths(backend: str | None = None) -> tuple[str, ...]:
    """Kernel-path candidates for a backend. Pallas variants are TPU-only
    candidates: off-TPU they execute under interpret mode, whose timing
    says nothing about the real kernel."""
    backend = backend or jax.default_backend()
    return ("vpu", "mxu", "xla") if backend == "tpu" else ("xla",)


def strategy_candidates(fp, c: int) -> tuple[str, ...]:
    """Legal conv dataflows for a layer with input channel count ``c`` —
    the same rule `core/bconv.py::resolve_strategy` applies to "auto":
    "direct" needs the per-position layout and 32-aligned channels,
    "im2col" is always legal."""
    cands = []
    if fp.w_words_hw is not None and c % 32 == 0:
        cands.append("direct")
    cands.append("im2col")
    return tuple(cands)


def tile_candidates(ho: int, wo: int, *, pf: int, fhb: int, fwb: int,
                    oa: int, la: int,
                    budget: int = kfused.SCRATCH_BUDGET
                    ) -> tuple[tuple[int, int], ...]:
    """Every legal (th, tw) fused-pair output tile: power-of-two heights up
    to the default block for the extent whose
    `kernels/xnor_conv_fused.py::halo_scratch` fits ``budget``, at the one
    width the chip's compiler accepts
    (`kernels/xnor_conv.py::tile_width`). The ``pick_tiles`` heuristic
    choice is always a member when it fits the budget."""
    from repro.kernels.ops import _block_for
    tw = tile_width(wo)
    cands = []
    th = 1
    while th <= _block_for(ho, kfused.TH, floor=1):
        if kfused.halo_scratch(th, tw, pf=pf, fhb=fhb, fwb=fwb, oa=oa,
                               la=la) <= budget:
            cands.append((th, tw))
        th *= 2
    return tuple(cands)


def enumerate_candidates(packed, backend: str | None = None, *,
                         input_hw: tuple[int, int] = (32, 32)) -> dict:
    """The full legal candidate space, structured per layer/group —
    ``autotune_packed`` races exactly this set, and
    tests/test_autotune.py checks it against the legality rules."""
    space = {"paths": backend_paths(backend), "convs": {}, "pairs": {}}
    for idx in range(1, 6):
        fp = packed.convs[idx - 1]
        c = fp.k // (fp.fh * fp.fw)
        space["convs"][idx] = {"strategies": strategy_candidates(fp, c)}
    for group in bcnn.plan_layer_groups(conv_fusion=True):
        if len(group) != 2:
            continue
        i, j = group
        fa, fb = packed.convs[i - 1], packed.convs[j - 1]
        h, w = execution_plan._conv_resolution(i, input_hw)
        pf = 2 if bcnn.CONV_SPECS[j][2] else 1
        oa, la = fa.w_words_hw.shape
        space["pairs"][i] = {
            "pool_b": pf == 2,
            "tiles": tile_candidates(h // pf, w // pf, pf=pf, fhb=fb.fh,
                                     fwb=fb.fw, oa=oa, la=la),
        }
    return space


# ---------------------------------------------------------------------------
# Timing
# ---------------------------------------------------------------------------

def _block(x):
    for leaf in jax.tree_util.tree_leaves(x):
        if hasattr(leaf, "block_until_ready"):
            leaf.block_until_ready()
    return x


def measure(fn, *, timer=time.perf_counter, reps: int = AUTOTUNE_REPS,
            warmup: int = AUTOTUNE_WARMUP) -> float:
    """Median-of-``reps`` wall time of ``fn()`` after ``warmup`` untimed
    calls. ``timer`` is injectable for deterministic tests."""
    for _ in range(warmup):
        _block(fn())
    ts = []
    for _ in range(reps):
        t0 = timer()
        _block(fn())
        ts.append(timer() - t0)
    ts.sort()
    return ts[len(ts) // 2]


def _race(cands, ref, *, timer, reps, warmup, report_rows=None):
    """Race eligible candidates: each (label, fn) must reproduce ``ref``
    bit-exactly to be timed at all. Returns (best_label, best_time) or
    (None, None) when nothing is eligible."""
    best = (None, None)
    for label, fn in cands:
        out = fn()
        ok = bool(jnp.array_equal(out, ref))
        t = measure(fn, timer=timer, reps=reps, warmup=warmup) if ok else None
        if report_rows is not None:
            report_rows.append({"candidate": label, "eligible": ok,
                                "median_s": t})
        if ok and (best[1] is None or t < best[1]):
            best = (label, t)
    return best


# ---------------------------------------------------------------------------
# The tuner
# ---------------------------------------------------------------------------

def autotune_packed(packed, *, backend: str | None = None,
                    input_hw: tuple[int, int] = (32, 32),
                    batch: int = AUTOTUNE_BATCH,
                    timer=time.perf_counter, reps: int = AUTOTUNE_REPS,
                    warmup: int = AUTOTUNE_WARMUP, seed: int = 0,
                    lm_mode: str | None = None,
                    report: dict | None = None):
    """Measure the candidate space for ``packed`` on this device → the
    winning `core/execution_plan.py::ExecutionPlan` (``tuned=True``).

    Protocol (greedy, cheapest-first — documented in kernels/README.md):

    1. run the "xla" reference forward once, layer by layer, capturing
       every layer's input and reference output;
    2. per binary conv layer, race (path × strategy); per FC layer, race
       paths — the winning global ``path`` minimizes the summed per-layer
       medians;
    3. under the winning path, race every legal fused-pair tile against
       the pair's best sequential fold; ``conv_fusion`` wins iff the
       summed fused medians beat the summed sequential ones;
    4. assert the assembled plan's full forward is bit-exact with the
       reference before returning it.

    ``report`` (optional dict) is filled with per-candidate rows and
    counters for logs/benchmarks. ``timer``/``reps``/``warmup`` tune the
    measurement itself (tests inject a fake timer).
    """
    backend = backend or jax.default_backend()
    base = execution_plan.default_plan(packed, backend, input_hw=input_hw)
    paths = backend_paths(backend)
    rows = [] if report is not None else None

    key = jax.random.PRNGKey(seed)
    x01 = jax.random.uniform(key, (batch, *input_hw, 3), jnp.float32)

    # 1. xla reference: per-layer inputs + outputs
    ref_plan = dataclasses.replace(base, path="xla")
    inputs, refs = {}, {}
    h = x01
    for idx in range(bcnn.N_LAYERS):
        inputs[idx] = h
        h = bcnn.apply_packed_layer(packed, idx, h, ref_plan)
        refs[idx] = h
    logits_ref = h

    # 2. per-layer races → winning global path + per-layer strategies
    conv_best = {p: {} for p in paths}     # [path][idx] = (strategy, time)
    for idx in range(1, 6):
        fp = packed.convs[idx - 1]
        c = fp.k // (fp.fh * fp.fw)
        mp = bcnn.CONV_SPECS[idx][2]
        for p in paths:
            cands = []
            for s in strategy_candidates(fp, c):
                cands.append((
                    f"conv{idx}:{p}:{s}",
                    lambda fp=fp, idx=idx, mp=mp, p=p, s=s:
                        bconv.apply_packed(fp, inputs[idx], maxpool=mp,
                                           path=p, strategy=s)))
            label, t = _race(cands, refs[idx], timer=timer, reps=reps,
                             warmup=warmup, report_rows=rows)
            if label is not None:
                conv_best[p][idx] = (label.rsplit(":", 1)[1], t)
    fc_times = {}
    for p in paths:
        total, ok = 0.0, True
        for idx in (6, 7, 8):
            label, t = _race(
                [(f"fc{idx}:{p}",
                  lambda idx=idx, p=p: bcnn.apply_packed_layer(
                      packed, idx, inputs[idx],
                      dataclasses.replace(base, path=p)))],
                refs[idx], timer=timer, reps=reps, warmup=warmup,
                report_rows=rows)
            if label is None:
                ok = False
                break
            total += t
        if ok:
            fc_times[p] = total

    def path_total(p):
        if p not in fc_times or len(conv_best[p]) < 5:
            return None
        return sum(t for _, t in conv_best[p].values()) + fc_times[p]

    totals = {p: path_total(p) for p in paths}
    eligible = {p: t for p, t in totals.items() if t is not None}
    win_path = min(eligible, key=eligible.get) if eligible else base.path

    strategies = list(base.conv_strategy)
    for idx, (s, _) in conv_best.get(win_path, {}).items():
        strategies[idx] = s

    # 3. fused pairs under the winning path: tiles vs the sequential fold
    group_tiles, fused_total, seq_total = [], 0.0, 0.0
    space = enumerate_candidates(packed, backend, input_hw=input_hw)
    for i, pair in sorted(space["pairs"].items()):
        j = i + 1
        fa, fb = packed.convs[i - 1], packed.convs[j - 1]
        tiles = pair["tiles"] if win_path != "xla" else (None,)
        by_label = {f"pair{i}:{win_path}:tiles={tl}": tl for tl in tiles}
        cands = [(
            label,
            lambda fa=fa, fb=fb, i=i, tl=tl: bconv.apply_packed_pair(
                fa, fb, inputs[i], maxpool_b=pair["pool_b"],
                path=win_path, tiles=tl))
            for label, tl in by_label.items()]
        label, t = _race(cands, refs[j], timer=timer, reps=reps,
                         warmup=warmup, report_rows=rows)
        if label is None:
            group_tiles = []
            break
        tl = by_label[label]
        if tl is not None:
            th, tw = tl
            group_tiles.append((i, th, tw))
        else:
            dt = execution_plan.default_group_tiles(
                packed, ((i, j),), input_hw=input_hw)
            group_tiles.extend(dt)
        fused_total += t
        seq_total += (conv_best[win_path][i][1]
                      + conv_best[win_path][j][1])
    fusion = bool(group_tiles) and fused_total < seq_total

    plan = execution_plan.ExecutionPlan(
        path=win_path, conv_strategy=tuple(strategies),
        conv_fusion=fusion,
        group_tiles=tuple(group_tiles) if fusion else (),
        lm_mode=lm_mode or base.lm_mode, tuned=True)

    # 4. the tuned plan must be bit-exact end to end before it may ship
    tuned_logits = bcnn.forward_packed(packed, x01, plan=plan)
    if not jnp.array_equal(tuned_logits, logits_ref):
        raise AssertionError(
            "autotuned plan is not bit-exact with the xla reference — "
            f"refusing to ship it: {plan}")

    if report is not None:
        report["candidates"] = rows
        report["n_candidates"] = len(rows)
        report["n_eligible"] = sum(1 for r in rows if r["eligible"])
        report["path_totals"] = {p: totals[p] for p in paths}
        report["plan"] = plan.describe()
        report["key"] = execution_plan.plan_cache_key(packed, backend)
    return plan


def autotune_lm_mode(cfg, packed, *, path: str = "xla",
                     timer=time.perf_counter, reps: int = AUTOTUNE_REPS,
                     warmup: int = AUTOTUNE_WARMUP, seed: int = 0,
                     batch: int = 2, seq: int = 8) -> str:
    """Race the LM decode GEMM modes ("bw" weight-only vs "xnor"
    full-packed) on a probe forward — both are integer-exact and bitwise
    equal (`models/xnor_lm.py`), so this is purely a speed race. Returns
    the winning mode for `core/execution_plan.py::ExecutionPlan.lm_mode`."""
    from repro.models import xnor_lm
    key = jax.random.PRNGKey(seed)
    tokens = jax.random.randint(key, (batch, seq), 0, cfg.vocab_size)
    outs = {}
    for mode in ("bw", "xnor"):
        outs[mode] = xnor_lm.forward_packed(cfg, packed, tokens, mode=mode,
                                            path=path)
    if not jnp.array_equal(outs["bw"], outs["xnor"]):
        return execution_plan.DEFAULT_LM_MODE   # never ship a mismatch
    times = {
        mode: measure(
            lambda mode=mode: xnor_lm.forward_packed(
                cfg, packed, tokens, mode=mode, path=path),
            timer=timer, reps=reps, warmup=warmup)
        for mode in ("bw", "xnor")}
    return min(times, key=times.get)


# ---------------------------------------------------------------------------
# Cache glue: artifact tuning section in / out
# ---------------------------------------------------------------------------

def tuning_section(packed, plan, backend: str | None = None) -> dict:
    """The payload `core/bcnn_artifact.py::save_packed` persists (it adds
    the CRC + section version on top)."""
    return {"key": execution_plan.plan_cache_key(packed, backend),
            "plan": execution_plan.plan_to_dict(plan)}


def plan_for_host(packed, tuning: dict | None, backend: str | None = None):
    """Resolve the plan to serve with: the cached tuned plan when its
    (backend, device kind, geometry) key matches THIS host, else
    `core/execution_plan.py::default_plan`. Returns ``(plan, source)``
    where source is "cached" or "default" — stale/foreign entries fall
    back silently, never error."""
    if tuning:
        key = execution_plan.plan_cache_key(packed, backend)
        if tuning.get("key") == key:
            try:
                return execution_plan.plan_from_dict(tuning["plan"]), "cached"
            except (KeyError, TypeError, ValueError):
                pass                    # malformed plan payload → heuristics
    return execution_plan.default_plan(packed, backend), "default"
