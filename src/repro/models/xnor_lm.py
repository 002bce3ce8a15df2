"""XNOR LM — a small binarized transformer on the paper's binary kernels.

The second binary workload (ROADMAP item 2): the repo proves the
binary-kernel + slot-serving architecture on the CIFAR-10 BCNN; this module
proves it **generalizes across network shapes**, FINN-style, by wiring the
same eq. 4/5/8 machinery through a transformer LM and serving it on the
existing LM slot engine (`serve/engine.py`).

Recipe (fp residual stream, binary compute):

* every projection (Q/K/V/O, MLP up/down) is a binary linear layer
  (`core/blinear.py`): latent fp weights binarized by sign (eq. 4),
  activations binarized before each projection, the matmul is the paper's
  XnorDotProduct (eq. 5) followed by inference BN;
* the MLP hidden activation is fully binary (BN + sign → ±1, eq. 8
  foldable); every other projection keeps its BN output in fp so the
  residual stream, norms (rmsnorm), softmax attention, embeddings, and the
  logit head stay full precision — the standard BNN-transformer split;
* learned absolute positional embeddings (no RoPE): decode positions come
  from the per-slot KV length, and the fp embedding add is trivially
  bit-exact between the train and packed forwards.

Three execution forms, mirroring `core/bcnn.py`:

* ``forward_train``   — differentiable STE forward (`core/blinear.py::
  apply_train` per projection);
* ``forward_packed`` / ``decode_step`` — deployment forward over packed
  int32 weight words. Two kernel modes produce identical integer
  agree-counts: ``mode="xnor"`` packs the binarized activations and calls
  `kernels/ops.py::xnor_matmul` (prefill / batch scoring), ``mode="bw"``
  feeds the ±1 activations straight to `kernels/ops.py::
  binary_weight_matmul` — the decode-critical weight-only kernel (packed
  weights stream HBM→VMEM at 1 bit/weight; a ±1×±1 bf16 product with f32
  accumulation is integer-exact, so both modes agree bit-for-bit);
* the serving adapter ``XnorLMServeModel`` — plugs the packed decode step
  into `serve/engine.py::ServingEngine` behind the model seam, with a
  `core/packed_model.py::split`-style static/array split so the engine's
  zero-recompile (``step_cache_size == 1``) and weight-hot-swap contracts
  are inherited unchanged.

Bit-exactness contract (tests/test_xnor_lm.py, tests/test_golden_kernels.py,
tests/test_properties.py): eager ``forward_train`` ≡ eager
``forward_packed`` **bitwise on every value**, not just on binarize
decisions — the ±1 f32 train matmul is integer-exact (sums ≪ 2²⁴), so it
equals the packed popcount counts exactly, and all downstream fp ops are
the same elementwise graph. Under the engine's jit, the BN arithmetic is
pinned by `core/normbinarize.py::bn_denom` barriers, same as the BCNN path.
"""
from __future__ import annotations

import dataclasses
from typing import NamedTuple

import jax
import jax.numpy as jnp

from repro.core import bitpack
from repro.core import blinear
from repro.core.binarize import binarize_ste
from repro.core.normbinarize import (BNParams, NBThreshold, fold_threshold,
                                     norm_binarize, norm_only)
from repro.kernels import ops

NEG_INF = -1e30


# --------------------------------------------------------------------- config
@dataclasses.dataclass(frozen=True)
class XnorLMConfig:
    """Shape of a binarized transformer LM.

    ``d_model``/``d_ff`` must be multiples of 32 so activations bit-pack
    without padding (`core/bitpack.py::PACK`); the weights' reduction axes
    are these same dims.
    """
    vocab_size: int = 256
    d_model: int = 64
    n_layers: int = 2
    n_heads: int = 2
    d_ff: int = 128
    max_len: int = 128
    family: str = "xnor_lm"

    def __post_init__(self):
        if self.d_model % bitpack.PACK:
            raise ValueError(f"d_model must be a multiple of {bitpack.PACK} "
                             f"(bit-packed reduction axis), got {self.d_model}")
        if self.d_ff % bitpack.PACK:
            raise ValueError(f"d_ff must be a multiple of {bitpack.PACK} "
                             f"(bit-packed reduction axis), got {self.d_ff}")
        if self.d_model % self.n_heads:
            raise ValueError(f"d_model {self.d_model} not divisible by "
                             f"n_heads {self.n_heads}")

    @property
    def head_dim(self) -> int:
        return self.d_model // self.n_heads

    def with_(self, **kw) -> "XnorLMConfig":
        return dataclasses.replace(self, **kw)

    def param_count(self) -> int:
        d, f = self.d_model, self.d_ff
        # each binary projection carries 4 BN stats vectors over its output
        per_block = (4 * (d * d + 4 * d)         # q/k/v/o
                     + (d * f + 4 * f)           # up
                     + (f * d + 4 * d)           # down
                     + 2 * d)                    # ln1, ln2
        return (self.vocab_size * d * 2 + self.max_len * d
                + self.n_layers * per_block + d)


# --------------------------------------------------------------------- params
class XnorBlockParams(NamedTuple):
    ln1: jnp.ndarray                  # (d,) rmsnorm scale, attention branch
    wq: blinear.BLinearParams
    wk: blinear.BLinearParams
    wv: blinear.BLinearParams
    wo: blinear.BLinearParams
    ln2: jnp.ndarray                  # (d,) rmsnorm scale, MLP branch
    w_up: blinear.BLinearParams       # d → d_ff, fully binary output (eq. 8)
    w_down: blinear.BLinearParams     # d_ff → d, fp BN output


class XnorLMParams(NamedTuple):
    tok_embed: jnp.ndarray            # (vocab, d) fp
    pos_embed: jnp.ndarray            # (max_len, d) fp learned absolute
    blocks: tuple                     # n_layers × XnorBlockParams
    ln_f: jnp.ndarray                 # (d,) final rmsnorm scale
    w_head: jnp.ndarray               # (d, vocab) fp logit head


class BProjPacked(NamedTuple):
    """One projection's deployment artifact: packed weight words + the BN
    stats (fp-output sites) + the folded eq. 8 threshold (binary-output
    sites). Statics (``k``, BN ``eps``) ride outside the array split."""
    w_words: jnp.ndarray              # (out, k//32) int32
    bn: BNParams
    thr: NBThreshold
    k: int


class XnorBlockPacked(NamedTuple):
    ln1: jnp.ndarray
    wq: BProjPacked
    wk: BProjPacked
    wv: BProjPacked
    wo: BProjPacked
    ln2: jnp.ndarray
    w_up: BProjPacked
    w_down: BProjPacked


class XnorLMPacked(NamedTuple):
    tok_embed: jnp.ndarray
    pos_embed: jnp.ndarray
    blocks: tuple                     # n_layers × XnorBlockPacked
    ln_f: jnp.ndarray
    w_head: jnp.ndarray


def init(cfg: XnorLMConfig, key) -> XnorLMParams:
    d, f = cfg.d_model, cfg.d_ff
    keys = jax.random.split(key, 3 + 6 * cfg.n_layers)
    blocks = []
    for i in range(cfg.n_layers):
        kq, kk, kv, ko, ku, kd = keys[3 + 6 * i: 9 + 6 * i]
        blocks.append(XnorBlockParams(
            ln1=jnp.ones((d,), jnp.float32),
            wq=blinear.init(kq, d, d), wk=blinear.init(kk, d, d),
            wv=blinear.init(kv, d, d), wo=blinear.init(ko, d, d),
            ln2=jnp.ones((d,), jnp.float32),
            w_up=blinear.init(ku, d, f), w_down=blinear.init(kd, f, d)))
    return XnorLMParams(
        tok_embed=jax.random.normal(keys[0], (cfg.vocab_size, d)) * 0.02,
        pos_embed=jax.random.normal(keys[1], (cfg.max_len, d)) * 0.02,
        blocks=tuple(blocks),
        ln_f=jnp.ones((d,), jnp.float32),
        w_head=jax.random.normal(keys[2], (d, cfg.vocab_size)) * d ** -0.5)


def fold(cfg: XnorLMConfig, params: XnorLMParams) -> XnorLMPacked:
    """Offline deployment build: pack every projection's weights (eq. 4)
    and fold its BN into the eq. 8 threshold (host float64 — see
    `core/normbinarize.py::fold_threshold`)."""

    def fold_proj(p: blinear.BLinearParams) -> BProjPacked:
        k = p.w.shape[1]
        bn = BNParams(p.bn_mean, p.bn_var, p.bn_gamma, p.bn_beta)
        return BProjPacked(w_words=bitpack.pack_pm1(p.w), bn=bn,
                           thr=fold_threshold(bn, cnum=k), k=k)

    blocks = tuple(XnorBlockPacked(
        ln1=b.ln1, wq=fold_proj(b.wq), wk=fold_proj(b.wk),
        wv=fold_proj(b.wv), wo=fold_proj(b.wo), ln2=b.ln2,
        w_up=fold_proj(b.w_up), w_down=fold_proj(b.w_down))
        for b in params.blocks)
    return XnorLMPacked(tok_embed=params.tok_embed,
                        pos_embed=params.pos_embed, blocks=blocks,
                        ln_f=params.ln_f, w_head=params.w_head)


# ------------------------------------------------------------ shared fp spine
def _rms(x: jnp.ndarray, scale: jnp.ndarray) -> jnp.ndarray:
    ms = jnp.mean(jnp.square(x), axis=-1, keepdims=True)
    return x * jax.lax.rsqrt(ms + 1e-6) * scale


def _attn_full(cfg: XnorLMConfig, q, k, v) -> jnp.ndarray:
    """Causal softmax attention, (B, S, H, hd) → (B, S, H, hd), f32."""
    s = q.shape[1]
    sc = jnp.einsum("bqhd,bkhd->bhqk", q, k,
                    preferred_element_type=jnp.float32) * cfg.head_dim ** -0.5
    mask = jnp.tril(jnp.ones((s, s), bool))
    sc = jnp.where(mask[None, None], sc, NEG_INF)
    w = jax.nn.softmax(sc, axis=-1)
    return jnp.einsum("bhqk,bkhd->bqhd", w, v,
                      preferred_element_type=jnp.float32)


def _block(cfg: XnorLMConfig, blk, x: jnp.ndarray, proj, attn) -> jnp.ndarray:
    """One pre-norm block over a projection-apply callback.

    ``proj(layer_params, a_pm1, out)`` with ``out`` in {"fp", "pm1"}
    dispatches to the train or packed projection; ``attn(q, k, v)`` is the
    (full-sequence or cached-decode) attention. Both forwards share this
    exact fp graph — the bit-exactness contract's backbone.
    """
    b, s, d = x.shape
    h, hd = cfg.n_heads, cfg.head_dim
    a = binarize_ste(_rms(x, blk.ln1))                       # ±1 (eq. 4)
    q = proj(blk.wq, a, "fp").reshape(b, s, h, hd)
    k = proj(blk.wk, a, "fp").reshape(b, s, h, hd)
    v = proj(blk.wv, a, "fp").reshape(b, s, h, hd)
    ctx = attn(q, k, v).reshape(b, s, d)
    x = x + proj(blk.wo, binarize_ste(ctx), "fp")
    u = proj(blk.w_up, binarize_ste(_rms(x, blk.ln2)), "pm1")   # binary hidden
    return x + proj(blk.w_down, u, "fp")


def _head(params, x: jnp.ndarray) -> jnp.ndarray:
    return _rms(x, params.ln_f) @ params.w_head


# ------------------------------------------------------------- train forward
def _proj_train(p: blinear.BLinearParams, a_pm1, out: str) -> jnp.ndarray:
    return blinear.apply_train(p, a_pm1, binarize_out=(out == "pm1"))


def forward_train(cfg: XnorLMConfig, params: XnorLMParams,
                  tokens: jnp.ndarray) -> jnp.ndarray:
    """Differentiable STE forward: (B, S) int tokens → (B, S, vocab) logits."""
    b, s = tokens.shape
    x = params.tok_embed[tokens] + params.pos_embed[:s][None]
    for blk in params.blocks:
        x = _block(cfg, blk, x, _proj_train,
                   lambda q, k, v: _attn_full(cfg, q, k, v))
    return _head(params, x)


def loss_fn(cfg: XnorLMConfig, params: XnorLMParams, tokens, targets):
    logits = forward_train(cfg, params, tokens)
    logp = jax.nn.log_softmax(logits)
    return -jnp.mean(jnp.take_along_axis(logp, targets[..., None], axis=-1))


# ------------------------------------------------------------ packed forward
def _agree_counts(pp: BProjPacked, a_pm1: jnp.ndarray, *, mode: str,
                  path: str) -> jnp.ndarray:
    """Integer agree-counts y_l (eq. 5) from ±1 activations, either kernel.

    "xnor": binarize → bit-pack → full XNOR matmul (both operands 1-bit).
    "bw":   ±1 f32 activations × packed weights via the weight-only decode
            kernel; its y_lo output maps back exactly via y_l=(y_lo+k)/2.
    """
    if mode == "xnor":
        words = bitpack.pack_bits(bitpack.encode_pm1(a_pm1))
        return ops.xnor_matmul(words, pp.w_words, k=pp.k, path=path)
    if mode != "bw":
        raise ValueError(f"unknown kernel mode {mode!r}; use 'xnor' or 'bw'")
    y_lo = ops.binary_weight_matmul(a_pm1, pp.w_words, k=pp.k)
    return ((y_lo + pp.k) * 0.5).astype(jnp.int32)


def _make_proj_packed(mode: str, path: str):
    def proj(pp: BProjPacked, a_pm1, out: str) -> jnp.ndarray:
        y_l = _agree_counts(pp, a_pm1, mode=mode, path=path)
        if out == "pm1":
            return bitpack.decode_pm1(norm_binarize(y_l, pp.thr))
        return norm_only(y_l, pp.bn, pp.k)
    return proj


def forward_packed(cfg: XnorLMConfig, packed: XnorLMPacked,
                   tokens: jnp.ndarray, *, mode: str = "xnor",
                   path: str = "mxu") -> jnp.ndarray:
    """Deployment full-sequence forward (prefill / batch scoring).

    Bitwise-equal to ``forward_train`` in eager execution for either
    ``mode`` — the parity tier's central assertion.
    """
    b, s = tokens.shape
    x = packed.tok_embed[tokens] + packed.pos_embed[:s][None]
    proj = _make_proj_packed(mode, path)
    for blk in packed.blocks:
        x = _block(cfg, blk, x, proj,
                   lambda q, k, v: _attn_full(cfg, q, k, v))
    return _head(packed, x)


# ------------------------------------------------------------- decode / serve
class XnorServeState(NamedTuple):
    """Per-slot decode state: fp KV caches + per-slot filled length."""
    k_cache: jnp.ndarray              # (L, B, max_len, H, hd) f32
    v_cache: jnp.ndarray              # (L, B, max_len, H, hd) f32
    length: jnp.ndarray               # (B,) int32


def init_serve_state(cfg: XnorLMConfig, batch: int,
                     max_len: int) -> XnorServeState:
    shape = (cfg.n_layers, batch, max_len, cfg.n_heads, cfg.head_dim)
    return XnorServeState(k_cache=jnp.zeros(shape, jnp.float32),
                          v_cache=jnp.zeros(shape, jnp.float32),
                          length=jnp.zeros((batch,), jnp.int32))


def decode_step(cfg: XnorLMConfig, packed: XnorLMPacked,
                state: XnorServeState, tokens: jnp.ndarray, *,
                mode: str = "bw", path: str = "mxu"):
    """One cached decode step: (B, 1) tokens → ((B, 1, vocab), new state).

    Per-slot positions come from ``state.length`` (scatter write + masked
    attention, the `models/attention.py` idiom) so co-resident slots at
    different depths share one jitted step — occupancy is data.
    """
    b = tokens.shape[0]
    h, hd, d = cfg.n_heads, cfg.head_dim, cfg.d_model
    proj = _make_proj_packed(mode, path)
    rows = jnp.arange(b)
    pos = jnp.minimum(state.length, packed.pos_embed.shape[0] - 1)
    x = packed.tok_embed[tokens[:, 0]][:, None] + packed.pos_embed[pos][:, None]
    new_k, new_v = [], []
    for li, blk in enumerate(packed.blocks):
        kc, vc = state.k_cache[li], state.v_cache[li]

        def attn(q, k, v, kc=kc, vc=vc):
            kc2 = kc.at[rows, state.length].set(k[:, 0], mode="drop")
            vc2 = vc.at[rows, state.length].set(v[:, 0], mode="drop")
            new_k.append(kc2)
            new_v.append(vc2)
            sc = jnp.einsum("bqhd,bshd->bhqs", q, kc2,
                            preferred_element_type=jnp.float32) * hd ** -0.5
            kv_pos = jnp.arange(kc.shape[1])
            valid = kv_pos[None, None, None, :] <= state.length[
                :, None, None, None]
            w = jax.nn.softmax(jnp.where(valid, sc, NEG_INF), axis=-1)
            return jnp.einsum("bhqs,bshd->bqhd", w, vc2,
                              preferred_element_type=jnp.float32)

        x = _block(cfg, blk, x, proj, attn)
    logits = _head(packed, x)
    new_state = XnorServeState(k_cache=jnp.stack(new_k),
                               v_cache=jnp.stack(new_v),
                               length=state.length + 1)
    return logits, new_state


def greedy_decode(cfg: XnorLMConfig, packed: XnorLMPacked,
                  prompt: list[int], n_steps: int, *, mode: str = "bw",
                  path: str = "mxu", max_len: int | None = None) -> list[int]:
    """Eager greedy reference: feed the prompt through ``decode_step`` one
    token at a time (exactly what the slot engine does), then generate
    ``n_steps`` tokens. The golden tier pins its output."""
    state = init_serve_state(cfg, 1, max_len or cfg.max_len)
    out: list[int] = []
    toks = list(prompt)
    for i in range(len(prompt) + n_steps - 1):
        tok = jnp.asarray([[toks[i] if i < len(toks) else out[-1]]],
                          jnp.int32)
        logits, state = decode_step(cfg, packed, state, tok, mode=mode,
                                    path=path)
        if i >= len(prompt) - 1:
            out.append(int(jnp.argmax(logits[0, -1])))
            toks.append(out[-1])
    return out


# --------------------------------------------------- static/array split, swap
def _is_arr(x) -> bool:
    return hasattr(x, "shape") and hasattr(x, "dtype")


def split_packed(packed: XnorLMPacked):
    """(array leaves, rebuild closure) — the hot-swap contract, mirroring
    `core/packed_model.py::split`: arrays ride as jit arguments (two packed
    LMs with identical shapes hit the same executable — zero recompiles on
    ``ServingEngine.swap_params``), statics (k, BN eps) rebuild inside the
    trace."""
    leaves, treedef = jax.tree_util.tree_flatten(
        packed, is_leaf=lambda x: x is None)
    mask = tuple(_is_arr(l) for l in leaves)
    arrays = tuple(l for l, m in zip(leaves, mask) if m)
    statics = tuple(None if m else l for l, m in zip(leaves, mask))

    def rebuild(arrs) -> XnorLMPacked:
        it = iter(arrs)
        return jax.tree_util.tree_unflatten(
            treedef, [next(it) if m else s for m, s in zip(mask, statics)])

    return arrays, rebuild


def assert_swap_compatible(old: XnorLMPacked, new: XnorLMPacked) -> tuple:
    """Validate ``new`` hot-swaps into a step built from ``old`` with zero
    recompiles (identical structure/statics/shapes/dtypes); returns the new
    array tuple in ``split_packed`` order."""
    lo, to = jax.tree_util.tree_flatten(old, is_leaf=lambda x: x is None)
    ln, tn = jax.tree_util.tree_flatten(new, is_leaf=lambda x: x is None)
    if to != tn:
        raise ValueError(f"packed tree structure differs: {to} != {tn}")
    for i, (a, b) in enumerate(zip(lo, ln)):
        if _is_arr(a) != _is_arr(b):
            raise ValueError(f"leaf {i}: array/static kind mismatch "
                             f"({type(a).__name__} vs {type(b).__name__})")
        if _is_arr(a):
            if tuple(a.shape) != tuple(b.shape) or a.dtype != b.dtype:
                raise ValueError(
                    f"leaf {i}: shape/dtype mismatch {a.shape}/{a.dtype} vs "
                    f"{b.shape}/{b.dtype} — a swap must come from fold() of "
                    f"an identically-shaped XnorLMParams")
        elif a != b:
            raise ValueError(f"leaf {i}: static mismatch {a!r} != {b!r} "
                             f"(k/eps must be identical)")
    return tuple(l for l in ln if _is_arr(l))


class XnorLMServeModel:
    """`serve/engine.py::ServingEngine` model adapter for the packed LM.

    The engine jits ``decode_step(params, state, tokens)`` once; here
    ``params`` is the flat array tuple from ``split_packed`` and the static
    skeleton is closed over — so a weight hot-swap
    (``engine.swap_params(model.swap_arrays(new_packed))``) reuses the
    compiled executable (``step_cache_size`` stays 1).
    """
    family = "xnor_lm"

    def __init__(self, cfg: XnorLMConfig, packed: XnorLMPacked, *,
                 mode: str = "bw", path: str = "mxu", plan=None):
        self.cfg = cfg
        self.arrays, self._rebuild = split_packed(packed)
        self._packed_ref = packed
        if plan is not None:    # ExecutionPlan wins over per-knob kwargs
            mode, path = plan.lm_mode, plan.path
        self._mode, self._path = mode, path

    def init_state(self, n_slots: int, max_len: int) -> XnorServeState:
        return init_serve_state(self.cfg, n_slots, max_len)

    def decode_step(self, arrays, state, tokens):
        return decode_step(self.cfg, self._rebuild(arrays), state, tokens,
                           mode=self._mode, path=self._path)

    def reset_slot(self, state: XnorServeState, i: int,
                   n_slots: int) -> XnorServeState:
        return XnorServeState(k_cache=state.k_cache.at[:, i].set(0),
                              v_cache=state.v_cache.at[:, i].set(0),
                              length=state.length.at[i].set(0))

    def swap_arrays(self, new_packed: XnorLMPacked) -> tuple:
        """Validate + return the replacement array tuple for
        ``ServingEngine.swap_params`` (zero recompiles)."""
        arrs = assert_swap_compatible(self._packed_ref, new_packed)
        self._packed_ref = new_packed
        return arrs


def make_serving_engine(cfg: XnorLMConfig, packed: XnorLMPacked, *,
                        n_slots: int = 4, max_len: int | None = None,
                        eos_id: int = -1, mode: str = "bw",
                        path: str = "mxu", plan=None):
    """Packed LM → a live slot engine. Returns ``(engine, model)``; keep
    the model around for ``swap_arrays`` on hot-swaps. ``plan`` (a
    ``core/execution_plan.py::ExecutionPlan``) overrides ``mode``/``path``
    with its ``lm_mode``/``path`` — the tuner's decode-GEMM choice
    (``kernels/autotune.py::autotune_lm_mode``)."""
    from repro.serve.engine import ServingEngine
    model = XnorLMServeModel(cfg, packed, mode=mode, path=path, plan=plan)
    eng = ServingEngine(cfg, model.arrays,
                        n_slots=n_slots, max_len=max_len or cfg.max_len,
                        eos_id=eos_id, model=model)
    return eng, model
