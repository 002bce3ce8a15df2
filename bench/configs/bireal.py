"""The Bi-Real Net family: builds the system under test, and the reference.

``build`` makes the weights from the seed, hands them to the program
(``core/bireal.py::fold``) and serves them through
``serve/bcnn_engine.py::BCNNEngine.classify_batch``, bulk calls in a closed
loop. ``reference_logits`` is the plain network in ``jax.numpy``, written
from the paper (Liu et al., arXiv:1808.00278, on the ResNet-18 topology)
and importing nothing of the program.

Faults a run can plant in the timed path (``harness.run_cell(fault=)``):
``"answer"`` alters one answer in eight, ``"half"`` leaves out the second
half of each batch, and ``"control"`` serves the reference in bfloat16, the
precision below the stated float32, in the program's place. Each has to
come out not correct.

The weights are random, on dyadic grids that make every value of the
residual stream exact in float32, in the program and in the reference:

* the stem's weights are integers in {-1, 0, 1} and the pixels integers in
  [0, 255], so every product and sum is an integer, exact even on one
  bfloat16 pass of the matrix unit; its BN scale is a multiple of the grid
  ``s`` and its shift an odd multiple of ``s / 2``, so the stream starts
  on (Z + 1/2) * s, and the max-pool keeps it there;
* a block's alpha is an integer and its BN scale and shift multiples of
  ``s``: the binary conv's count times them is a multiple of ``s``, and so
  is what the block adds;
* a downsample's 2x2 average of a stream on (Z + 1/2) * g lies on
  (g / 4) * Z; its 1x1 weights are in {-1, 0, 1}, its BN scale an integer
  and its shift an odd multiple of g / 8, so the branch, and the stream
  after the block, lie on (Z + 1/2) * (g / 4). Every block of the stage
  adds a multiple of ``s``, which is a multiple of g / 4.

Magnitudes stay far inside float32's 24 bits at each stage's grid, so no
sign decision can land on or near zero; only the global average pool
(a sum of 49 and a division) and the FC round.
"""
from __future__ import annotations

import numpy as np

from bench import bireal_cost, generator
# the program's Bi-Real Net; a checkout without it fails here, at once
import repro.core.bireal  # noqa: F401

GRID = 2.0 ** -4
NEG_SHARE = 0.2           # share of BN scales below zero (flipped)


# ------------------------------------------------------------------ weights
def blocks_of(cfg: dict):
    """[(in channels, out channels, stride)] for every block, in order."""
    return [(c.c, c.o, c.stride) for c in bireal_cost.binary_convs(cfg)]


def make_weights(cfg: dict, seed: int) -> dict:
    """All weights from the seed (host), then on the device."""
    import jax
    rng = generator.rng_for(seed, "weights")

    def ints(shape, lo, hi):
        return rng.integers(lo, hi, shape).astype(np.float32)

    def signed(shape, lo, hi):
        return ints(shape, lo, hi) * np.where(
            rng.random(shape) < NEG_SHARE, -1.0, 1.0).astype(np.float32)

    stem = cfg["stem"]
    k, c0, cin = stem["kernel"], stem["channels"], cfg["input_shape"][2]
    sw = ints((k, k, cin, c0), -1, 2)
    scale = signed((c0,), 1, 2) * GRID
    # centre the pixels' mid-grey and the max-pool's lift (integers)
    centre = (np.round(127.5 * sw.sum((0, 1, 2))) * scale
              + np.round(110.0 * np.sqrt((sw ** 2).sum((0, 1, 2))))
              * np.abs(scale))
    raw = {"stem": {"w": sw, "scale": scale,
                    "shift": (ints((c0,), -64, 64) + 0.5) * GRID - centre},
           "blocks": []}
    g = GRID
    for c, o, stride in blocks_of(cfg):
        blk = {"w": np.where(rng.random((o, 3, 3, c)) < 0.5, 1.0,
                             -1.0).astype(np.float32),
               "alpha": ints((o,), 1, 3),
               "scale": signed((o,), 1, 17) * GRID,
               "shift": ints((o,), -64, 64) * GRID, "down": None}
        if stride != 1 or c != o:
            g = g / (stride * stride)
            blk["down"] = {
                "w": ints((c, o), -1, 2) * (rng.random((c, o)) < 2.0 / c),
                "scale": signed((o,), 1, 3),
                "shift": (ints((o,), -64, 64) + 0.5) * g}
        raw["blocks"].append(blk)
    c_last = cfg["stages"][-1][0]
    raw["fc"] = {"w": (rng.standard_normal((c_last, cfg["n_classes"]))
                       / np.sqrt(c_last) * GRID / 64).astype(np.float32),
                 "b": rng.standard_normal(cfg["n_classes"]).astype(
                     np.float32)}
    return jax.device_put(raw)


def to_program(raw: dict):
    """The program's own parameter tree, holding the same arrays."""
    from repro.core import bireal

    def bn(p):
        return bireal.BN(p["scale"], p["shift"])

    blocks = tuple(
        bireal.BlockParams(b["w"], b["alpha"], bn(b), None if b["down"] is None
                           else bireal.DownParams(b["down"]["w"],
                                                  bn(b["down"])))
        for b in raw["blocks"])
    return bireal.BiRealParams(raw["stem"]["w"], bn(raw["stem"]), blocks,
                               raw["fc"]["w"], raw["fc"]["b"])


# ---------------------------------------------------------------- reference
def reference_logits(cfg: dict, raw: dict, pixels, dtype=None):
    """The plain network: (N, H, W, 3) uint8 pixels -> (N, classes) logits.

    ``dtype`` float32 (the stated precision, products at HIGHEST) or
    bfloat16 (the control: every array and every product rounded to it).
    """
    import jax
    import jax.numpy as jnp
    dtype = dtype or jnp.float32
    hp = jax.lax.Precision.HIGHEST
    dn = ("NHWC", "HWIO", "NHWC")

    def c(a):
        return jnp.asarray(a).astype(dtype)

    def conv(x, w_hwio, stride, pad):
        return jax.lax.conv_general_dilated(
            c(x), c(w_hwio), (stride, stride), ((pad, pad), (pad, pad)),
            dimension_numbers=dn, precision=hp, preferred_element_type=dtype)

    def sign(z):
        return jnp.where(z >= 0, 1.0, -1.0).astype(dtype)

    stem, pool = cfg["stem"], cfg["stem"]["pool"]
    p = raw["stem"]
    x = conv(pixels, p["w"], stem["stride"], stem["kernel"] // 2) \
        * c(p["scale"]) + c(p["shift"])
    pk, ps, pp = pool["kernel"], pool["stride"], pool["pad"]
    x = jax.lax.reduce_window(x, jnp.array(-jnp.inf, dtype), jax.lax.max,
                              (1, pk, pk, 1), (1, ps, ps, 1),
                              ((0, 0), (pp, pp), (pp, pp), (0, 0)))
    for blk, (_, _, stride) in zip(raw["blocks"], blocks_of(cfg)):
        # binary conv: sign(W) over sign(x), zero padding of 1
        y = conv(sign(x), jnp.transpose(sign(blk["w"]), (1, 2, 3, 0)),
                 stride, 1)
        y = y * c(blk["alpha"]) * c(blk["scale"]) + c(blk["shift"])
        d = blk["down"]
        if d is None:
            x = y + x
            continue
        avg = jax.lax.reduce_window(x, jnp.array(0, dtype), jax.lax.add,
                                    (1, stride, stride, 1),
                                    (1, stride, stride, 1), "VALID") \
            / (stride * stride)
        sc = jnp.einsum("nhwc,co->nhwo", avg, c(d["w"]), precision=hp,
                        preferred_element_type=dtype)
        x = y + sc * c(d["scale"]) + c(d["shift"])
    feat = jnp.mean(x, axis=(1, 2))
    logits = jnp.matmul(feat, c(raw["fc"]["w"]), precision=hp,
                        preferred_element_type=dtype) + c(raw["fc"]["b"])
    return logits.astype(jnp.float32)


def reference_in_blocks(cfg, raw, images: np.ndarray, dtype=None,
                        block: int = 128) -> np.ndarray:
    """The reference over ``images`` in fixed-size blocks (one compile)."""
    import jax
    import jax.numpy as jnp
    block = min(block, len(images))
    fn = jax.jit(lambda r, x: reference_logits(cfg, r, x, dtype))
    out = []
    for s in range(0, len(images), block):
        x = images[s:s + block]
        pad = block - len(x)
        if pad:
            x = np.concatenate([x, np.zeros((pad,) + x.shape[1:], x.dtype)])
        out.append(np.asarray(fn(raw, jnp.asarray(x)))[:block - pad])
    return np.concatenate(out)


def make_images(seed: int, n: int, shape) -> np.ndarray:
    """8-bit pixels, uniform over [0, 255]."""
    return generator.rng_for(seed, "images").integers(
        0, 256, (n, *shape), dtype=np.uint8)


def logit_check(cfg, raw, images, got: np.ndarray) -> dict:
    """Widest logit gap between ``got`` and the reference."""
    ref = reference_in_blocks(cfg, raw, images)
    return {"name": "logit_max_abs_diff",
            "value": float(np.max(np.abs(got - ref))),
            "limit": float(cfg["limits"]["logit_max_abs_diff"])}


# ------------------------------------------------------------------ systems
# the binary convs' custom calls in the device trace (kernels/ops.py)
KERNELS = {"binconv": ("xnor_conv2d",)}


class Offline:
    """Bulk batches through ``classify_batch``, one caller, closed loop."""

    last_step_items = 0

    def __init__(self, cfg, mix, sched, seed, fault=None):
        from repro.core import bireal, execution_plan
        from repro.serve import BCNNEngine
        self.cfg, self.seed, self.fault = cfg, seed, fault
        self.raw = make_weights(cfg, seed)
        self.packed = bireal.fold(to_program(self.raw),
                                  bireal.geometry(cfg))
        self.plan = execution_plan.default_plan(self.packed)
        self.batch = int(mix["batch"])
        pool = make_images(seed, int(mix["pool_batches"]) * self.batch,
                           cfg["input_shape"])
        self.batches = pool.reshape(-1, self.batch, *pool.shape[1:])
        self.engine = BCNNEngine.from_packed(
            self.packed, n_slots=int(cfg["n_slots"]), plan=self.plan,
            data_shards=int(cfg["data_shards"]),
            data_micro_batch=int(cfg["data_micro_batch"]))
        self.outputs: list = []
        self.control = None

    def warmup(self) -> None:
        for j in range(2):
            self.engine.classify_batch(self.batches[j % len(self.batches)])
        if self.fault == "control":
            import jax.numpy as jnp
            self.control = reference_in_blocks(
                self.cfg, self.raw,
                self.batches.reshape(-1, *self.batches.shape[2:]),
                jnp.bfloat16).reshape(len(self.batches), self.batch, -1)

    def call(self, j: int) -> None:
        out = self.engine.classify_batch(self.batches[j % len(self.batches)])
        if self.control is not None:
            out = self.control[j % len(self.batches)]
        elif self.fault == "half":
            out = out.copy()
            out[len(out) // 2:] = 0.0
        elif self.fault == "answer":
            # one answer in eight, so that the sampled check meets some
            out = out.copy()
            out[::8] += 1.0
        self.outputs.append(out)

    def kernel_families(self) -> dict:
        return KERNELS

    def counters(self) -> dict:
        return {"plan": self.plan.path,
                "bulk_compiles": self.engine.batch_cache_size}

    def check(self, run) -> list:
        if not self.outputs:
            return [{"name": "answered", "value": 1.0, "limit": 0.0}]
        rng = generator.rng_for(self.seed, "check")
        rows = int(self.cfg["check_rows"])
        calls = rng.choice(len(self.outputs),
                           size=min(4, len(self.outputs)), replace=False)
        per = rows // len(calls)
        got, images = [], []
        for j in calls:
            r = np.sort(rng.choice(self.batch, size=min(per, self.batch),
                                   replace=False))
            got.append(self.outputs[j][r])
            images.append(self.batches[j % len(self.batches)][r])
        return [logit_check(self.cfg, self.raw, np.concatenate(images),
                            np.concatenate(got))]

    def release(self) -> None:
        self.engine = None
        self.packed = None


def build(cfg: dict, mix: dict, sched, seed: int, fault=None):
    if mix["entry"] != "classify_batch":
        raise ValueError("the Bi-Real family serves bulk classify_batch "
                         f"calls only, not {mix['entry']!r}")
    return Offline(cfg, mix, sched, seed, fault)
