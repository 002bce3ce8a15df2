"""Pipeline parallelism: the paper's eq. 12 bottleneck law applied to
transformer stages.

The paper's central architectural rule — system throughput = freq /
max(C_1..C_k), optimized by equalizing per-stage time (§4.3) — is exactly
the steady-state law of a 1F1B microbatch pipeline. This module reuses
``core.throughput.balance_stages`` (the same DP used to reproduce Table 3)
to cut a transformer's per-layer cost sequence into stages, and provides:

* ``plan_stages(cfg, n_stages)``   — analytic per-layer cost → boundaries
* ``schedule_1f1b(...)``           — bubble/throughput model of the schedule
* ``pipelined_forward(...)``       — an executable shard_map pipeline over a
  mesh axis using ``jax.lax.ppermute`` (double-buffered stage handoff — the
  TPU analogue of the paper's double-buffered memory channels)

tests/test_pipeline.py checks the balance invariants and that the
shard_map pipeline matches the sequential forward bit-for-bit.

The same planning/scheduling machinery, applied to the paper's own
heterogeneous 9-layer BCNN (conv stages with changing spatial dims + FC
stages, bit-packed stage boundaries), lives in ``parallel/bcnn_pipeline.py``.
"""
from __future__ import annotations

import functools

import jax
import jax.numpy as jnp
import numpy as np
from jax.sharding import PartitionSpec as P

from repro.core.throughput import balance_stages

# ---------------------------------------------------------------------------
# stage planning from the analytic cost model
# ---------------------------------------------------------------------------

def layer_costs(cfg, seq_len: int) -> list[float]:
    """Per-layer forward FLOPs (the C_l of eq. 12 for a transformer).

    ``cfg`` is any LM config from ``repro.configs`` (dense, SwiGLU, or MoE —
    MoE layers are costed at their activated-expert FLOPs); ``seq_len`` sets
    the attention term. Returns one cost per layer, length ``cfg.n_layers``.
    The BCNN analogue — per-layer binary-op counts from the paper's
    Table 2 — lives in ``parallel/bcnn_pipeline.py``.
    """
    d, f = cfg.d_model, cfg.d_ff
    hd = cfg.head_dim
    n_q = cfg.n_heads * hd
    n_kv = cfg.n_kv_heads * hd
    attn = 2.0 * (d * n_q + 2 * d * n_kv + n_q * d) + 4.0 * seq_len * d
    if cfg.is_moe:
        ffn = 2.0 * 3 * d * cfg.moe_d_ff * (cfg.top_k + cfg.n_shared_experts)
    else:
        ffn = 2.0 * (3 if cfg.mlp_type == "swiglu" else 2) * d * f
    return [attn + ffn] * cfg.n_layers


def plan_stages(cfg, n_stages: int, seq_len: int = 4096) -> list[int]:
    """Stage boundaries (len n_stages+1) minimizing the eq. 12 bottleneck.

    Thin wrapper: ``layer_costs`` → ``core.throughput.balance_stages`` (the
    exact DP also used for the paper's Table 3). ``bounds[s]:bounds[s+1]``
    is the half-open layer range of stage ``s``.
    """
    return balance_stages(layer_costs(cfg, seq_len), n_stages)


def stage_costs_from_bounds(costs: list[float],
                            bounds: list[int]) -> list[float]:
    """Per-stage summed cost for a ``balance_stages`` partition.

    ``costs`` are per-layer costs; ``bounds`` the n_stages+1 boundary
    indices. The max of the result is the eq. 12 bottleneck C_max that
    sets steady-state pipeline throughput.
    """
    return [float(sum(costs[bounds[i]:bounds[i + 1]]))
            for i in range(len(bounds) - 1)]


def schedule_1f1b(stage_costs: list[float], n_micro: int, *,
                  fwd_bwd_mult: float = 3.0) -> dict:
    """Steady-state model of the microbatch pipeline schedule.

    ``stage_costs`` are per-stage forward costs (any consistent unit),
    ``n_micro`` the number of microbatches in flight per step, and
    ``fwd_bwd_mult`` the per-microbatch work multiple relative to one
    forward: 3.0 models training 1F1B (fwd + ~2× bwd, the default, used by
    the LM pipeline), 1.0 models the inference-only fill/drain pipeline
    (``parallel/bcnn_pipeline.py`` — the paper's streaming deployment,
    where every tick is a forward).

    Returns a dict with ``bubble_fraction`` (fill/drain idle share),
    ``steady_rate`` (microbatches per unit time once full — the paper's
    eq. 12 corresponds to the n_micro→∞ limit, rate = 1/C_max),
    ``efficiency`` (ideal/real step time), and ``balance``
    (mean/max stage cost; 1.0 ⇔ perfectly equalized stages, the §4.3
    optimality condition).
    """
    s = len(stage_costs)
    c_max = max(stage_costs)
    total = sum(stage_costs)
    # per-microbatch cost = fwd_bwd_mult × fwd; fill+drain = (s−1) slots
    t_ideal = n_micro * fwd_bwd_mult * c_max
    t_real = t_ideal + (s - 1) * fwd_bwd_mult * c_max
    bubble = (s - 1) / (n_micro + s - 1)
    return {"bubble_fraction": bubble,
            "steady_rate": 1.0 / (fwd_bwd_mult * c_max),
            "efficiency": t_ideal / t_real,
            "balance": total / (s * c_max)}


# ---------------------------------------------------------------------------
# executable shard_map pipeline (ppermute stage handoff)
# ---------------------------------------------------------------------------

def pipelined_forward(stack_params, x, *, mesh, axis: str, apply_fn,
                      layers_per_stage: int):
    """Run a stacked-layer forward as a ppermute pipeline over ``axis``.

    stack_params: pytree stacked (L, …) with L = n_stages · layers_per_stage;
    x: (n_micro, B, S, D) microbatched activations (n_micro ≥ n_stages).
    apply_fn(layer_params, x) → x applies ONE layer.

    Classic loop: at tick t, stage s processes microbatch t−s; activations
    hop stage→stage+1 through ``ppermute`` (the double-buffered channel).
    Collective-permute overlaps with the next tick's compute — XLA schedules
    the independent send/recv behind the stage matmuls.
    """
    n_stages = mesh.shape[axis]
    n_micro = x.shape[0]
    assert n_micro % n_stages == 0, (n_micro, n_stages)

    def stage_chunk(params):    # (L,…) → (S, L/S, …) leading stage axis
        return jax.tree.map(
            lambda a: a.reshape(n_stages, layers_per_stage, *a.shape[1:]),
            params)

    perm = [(i, (i + 1) % n_stages) for i in range(n_stages)]

    @functools.partial(
        jax.shard_map, mesh=mesh,
        in_specs=(P(axis), P()),
        out_specs=P())
    def run(stage_params, mb):
        # stage_params: (1, layers_per_stage, …) — this stage's layers
        # mb: (n_micro, B, S, D) — replicated; stage 0 injects from it
        sp = jax.tree.map(lambda a: a[0], stage_params)
        stage_id = jax.lax.axis_index(axis)
        n_ticks = n_micro + n_stages - 1

        def apply_stage(h):
            def body(c, lp):
                return apply_fn(lp, c), None
            out, _ = jax.lax.scan(body, h, sp)
            return out

        def tick(carry, t):
            out_buf, recv = carry
            inject = jnp.where(t < n_micro, t, 0)
            h = jnp.where(stage_id == 0, mb[inject], recv)
            h = apply_stage(h)
            # last stage owns the result for microbatch t−(S−1)
            done_idx = t - (n_stages - 1)
            write = jnp.logical_and(stage_id == n_stages - 1, done_idx >= 0)
            slot = jnp.where(done_idx >= 0, done_idx, 0)
            out_buf = jnp.where(
                write,
                jax.lax.dynamic_update_index_in_dim(out_buf, h, slot, 0),
                out_buf)
            recv_next = jax.lax.ppermute(h, axis, perm)
            return (out_buf, recv_next), None

        def _vary(a):   # mark the zero init as device-varying over the axis
            return jax.lax.pcast(a, (axis,), to="varying")

        (out_buf, _), _ = jax.lax.scan(
            tick, (_vary(jnp.zeros_like(mb)), _vary(jnp.zeros_like(mb[0]))),
            jnp.arange(n_ticks))
        # non-final stages hold zeros — the sum collapses to the real result
        return jax.lax.psum(out_buf, axis)

    chunked = stage_chunk(stack_params)
    return run(chunked, x)


def sequential_forward(stack_params, x, *, apply_fn):
    """Reference: the same stacked layers without pipelining.

    ``stack_params`` is the (L, …) stacked pytree ``pipelined_forward``
    takes; ``x`` is either one microbatch (ndim ≤ 2 leading data dims) or a
    stack of them (vmapped over the leading axis). Used by
    tests/test_pipeline.py as the bit-for-bit oracle of the ppermute
    pipeline.
    """
    def body(c, lp):
        return apply_fn(lp, c), None

    def one(mb):
        out, _ = jax.lax.scan(body, mb, stack_params)
        return out
    return jax.vmap(one)(x) if x.ndim > 2 else one(x)


def elastic_stage_plan(costs: list[float], n_stages_old: int,
                       n_stages_new: int) -> tuple[list[int], list[int]]:
    """Re-balance stages when the pipeline width changes (elastic scaling).

    ``costs`` are per-layer costs (``layer_costs`` or any other model);
    ``n_stages_old``/``n_stages_new`` the pipeline widths before and after.
    Returns (old_bounds, new_bounds); parameters move between stages
    according to the boundary diff — used by train/checkpoint elastic
    restore to compute the minimal re-layout.
    """
    return (balance_stages(costs, n_stages_old),
            balance_stages(costs, n_stages_new))
