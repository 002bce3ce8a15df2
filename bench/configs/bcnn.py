"""The Table 2 BCNN family: builds the system under test, and the reference.

``build`` makes the weights on the device from the seed, in one jitted
call, hands them to the program (``core/bcnn.py::fold_model``), and serves
them through ``serve/bcnn_engine.py::BCNNEngine``: ``step`` for an open
loop, ``classify_batch`` for a closed one. ``reference_logits`` is the
plain network in ``jax.numpy``, written from the paper and importing
nothing of the program.

Faults a run can plant in the timed path (``harness.run_cell(fault=)``):
``"answer"`` alters answers where they are produced, ``"half"`` (bulk)
leaves out the second half of each batch, and ``"control"`` serves the
reference in bfloat16, the precision below the stated float32, in the
program's place. Each has to come out not correct.

The weights are random, with two choices that make the comparison exact
on every bit: CONV-1's weights lie on the 2-bit grid {-1, 0, 1} (so its
quantisation scale is 1 and its products are integers), and every
binarising BN has beta 0 and a mean half a step off the lattice its
pre-activation lives on. A threshold then sits at least half a count from
every reachable count, and no rounding of the BN arithmetic can decide a
bit; only FC-3's float logits carry rounding.
"""
from __future__ import annotations

import numpy as np

from bench import generator

SIGN_NEG_SHARE = 0.15     # share of BN channels with gamma < 0 (flipped)


# ------------------------------------------------------------------ weights
def layer_shapes(cfg: dict):
    """[(kind, in, out, pool)] for the nine layers, from the config."""
    ch = [cfg["input_shape"][2]] + list(cfg["conv_channels"])
    layers = [("conv", ch[i], ch[i + 1], bool(cfg["maxpool"][i]))
              for i in range(len(cfg["conv_channels"]))]
    fc = cfg["fc_features"]
    layers += [("fc", fc[i], fc[i + 1], False) for i in range(len(fc) - 1)]
    return layers


def make_weights(cfg: dict, seed: int) -> dict:
    """All weights, on the device, from one jitted call."""
    import jax
    import jax.numpy as jnp

    layers = layer_shapes(cfg)
    f = cfg["filter_size"]

    def bn(key, width, spread, on_lattice, binary):
        km, kv, kg, ks, kb = jax.random.split(key, 5)
        mean = jax.random.normal(km, (width,)) * 0.25 * spread
        if binary:
            # beta 0, mean half a step off the pre-activation's lattice
            mean = (2 * jnp.floor(mean / 2) + 1 if on_lattice == 2
                    else jnp.floor(mean) + 0.5)
            beta = jnp.zeros((width,))
        else:
            beta = jax.random.normal(kb, (width,)) * 0.1
        var = spread ** 2 * jax.random.uniform(kv, (width,), minval=0.5,
                                               maxval=2.0)
        gamma = jax.random.uniform(kg, (width,), minval=0.5, maxval=1.5)
        gamma = jnp.where(jax.random.uniform(ks, (width,)) < SIGN_NEG_SHARE,
                          -gamma, gamma)
        return {"mean": mean, "var": var, "gamma": gamma, "beta": beta}

    def make(key):
        keys = jax.random.split(key, 2 * len(layers))
        out = []
        for i, (kind, cin, cout, _) in enumerate(layers):
            kw, kb = keys[2 * i], keys[2 * i + 1]
            last = i == len(layers) - 1
            if i == 0:
                # 2-bit grid {-1, 0, 1}; one +1 pins the quantisation scale
                w = jax.random.randint(kw, (cout, f, f, cin), -1, 2)
                w = w.astype(jnp.float32).at[0, 0, 0, 0].set(1.0)
                # 6-bit inputs in [-31, 31] (variance ~31^2/3), 2/3 nonzero
                spread = float(np.sqrt(f * f * cin * (2 / 3) * 31 ** 2 / 3))
                p = bn(kb, cout, spread, 1, True)
            else:
                shape = (cout, f, f, cin) if kind == "conv" else (cout, cin)
                w = jnp.where(jax.random.bernoulli(kw, 0.5, shape), 1.0, -1.0)
                k = int(np.prod(shape[1:]))
                p = bn(kb, cout, float(np.sqrt(k)), 2, not last)
            p["w"] = w
            out.append(p)
        return out

    hi, lo = (int(seed) >> 31) & 0x7FFFFFFF, int(seed) & 0x7FFFFFFF
    key = jax.random.fold_in(jax.random.PRNGKey(lo), hi)
    return jax.jit(make)(key)


def to_program(raw: list):
    """The program's own parameter tree, holding the same arrays."""
    from repro.core import bcnn, bconv, blinear

    def fields(p):
        return (p["w"], p["mean"], p["var"], p["gamma"], p["beta"])

    return bcnn.BCNNParams(
        conv1=bconv.FpConvParams(*fields(raw[0])),
        convs=tuple(bconv.BConvParams(*fields(p)) for p in raw[1:6]),
        fcs=tuple(blinear.BLinearParams(*fields(p)) for p in raw[6:9]))


def check_program_shapes(cfg: dict) -> None:
    """The program's Table 2 widths are fixed in code: refuse a config that
    states others."""
    from repro.core import bcnn
    want = [(c_in, c_out, pool) for kind, c_in, c_out, pool
            in layer_shapes(cfg) if kind == "conv"]
    fcs = [(a, b) for kind, a, b, _ in layer_shapes(cfg) if kind == "fc"]
    if want != list(bcnn.CONV_SPECS) or fcs != list(bcnn.FC_SPECS):
        raise ValueError("the configuration's widths differ from the "
                         "program's Table 2 network")


# ---------------------------------------------------------------- reference
def reference_logits(cfg: dict, raw: list, x01, dtype=None):
    """The plain network: (N, 32, 32, 3) images in [0, 1) -> (N, 10) logits.

    ``dtype`` float32 (the stated precision, products at HIGHEST) or
    bfloat16 (the control: every array and every product rounded to it).
    """
    import jax
    import jax.numpy as jnp
    dtype = dtype or jnp.float32
    hp = jax.lax.Precision.HIGHEST
    eps = cfg["bn_eps"]
    dn = ("NHWC", "HWIO", "NHWC")

    def bn(y, p):
        c = lambda a: a.astype(dtype)  # noqa: E731
        return (y - c(p["mean"])) / jnp.sqrt(c(p["var"]) + eps) \
            * c(p["gamma"]) + c(p["beta"])

    def sign(z):
        return jnp.where(z >= 0, 1.0, -1.0).astype(dtype)

    def conv(a, w, padding):
        return jax.lax.conv_general_dilated(
            a.astype(dtype), jnp.transpose(w, (1, 2, 3, 0)).astype(dtype),
            (1, 1), padding, dimension_numbers=dn, precision=hp,
            preferred_element_type=dtype)

    layers = layer_shapes(cfg)
    # CONV-1: 6-bit inputs in [-31, 31] times 2-bit weights (paper eq. 7)
    a = jnp.round(jnp.clip(x01, 0.0, 1.0) * 62.0 - 31.0)
    w = raw[0]["w"]
    scale = jnp.maximum(jnp.max(jnp.abs(w)), 1e-8)
    w2 = jnp.round(jnp.clip(w / scale, -1.0, 1.0)) * scale
    a = sign(bn(conv(a, w2, "SAME"), raw[0]))
    for i in range(1, 6):
        # binary conv: +-1 weights, padding with -1 (bit 0 in the packed maps)
        ap = jnp.pad(a, ((0, 0), (1, 1), (1, 1), (0, 0)),
                     constant_values=-1.0)
        y = conv(ap, sign(raw[i]["w"]), "VALID")
        if layers[i][3]:
            y = jax.lax.reduce_window(y, jnp.array(-jnp.inf, dtype),
                                      jax.lax.max, (1, 2, 2, 1),
                                      (1, 2, 2, 1), "VALID")
        a = sign(bn(y, raw[i]))
    a = a.reshape(a.shape[0], -1)
    for i in range(6, 9):
        y = jnp.matmul(a, sign(raw[i]["w"]).T, precision=hp,
                       preferred_element_type=dtype)
        a = bn(y, raw[i]) if i == 8 else sign(bn(y, raw[i]))
    return a.astype(jnp.float32)


def reference_in_blocks(cfg, raw, images: np.ndarray, dtype=None,
                        block: int = 256) -> np.ndarray:
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
    return generator.rng_for(seed, "images").random((n, *shape),
                                                      dtype=np.float32)


def control_logits(cfg, raw, images) -> np.ndarray:
    """The control: the reference in bfloat16, every array, sum and
    product rounded to it."""
    import jax.numpy as jnp
    return reference_in_blocks(cfg, raw, images, jnp.bfloat16)


def logit_check(cfg, raw, images, got: np.ndarray) -> dict:
    """Widest logit gap between ``got`` and the reference."""
    ref = reference_in_blocks(cfg, raw, images)
    return {"name": "logit_max_abs_diff",
            "value": float(np.max(np.abs(got - ref))),
            "limit": float(cfg["limits"]["logit_max_abs_diff"])}


# ------------------------------------------------------------------ systems
# the custom calls' names in the device trace (kernels/ops.py entry points)
KERNELS = {"conv": ("xnor_conv2d", "xnor_conv2d_pair"),
           "fc": ("xnor_matmul",)}


class _Base:
    last_step_items = 0

    def __init__(self, cfg, mix, seed, fault):
        from repro.core import bcnn, execution_plan
        check_program_shapes(cfg)
        self.cfg, self.mix, self.seed, self.fault = cfg, mix, seed, fault
        self.raw = make_weights(cfg, seed)
        self.packed = bcnn.fold_model(to_program(self.raw))
        self.plan = execution_plan.default_plan(self.packed)

    def kernel_families(self) -> dict:
        return KERNELS

    def release(self) -> None:
        self.engine = None
        self.packed = None


class Online(_Base):
    """Single images through the slot engine's step."""

    def __init__(self, cfg, mix, sched, seed, fault=None):
        from repro.serve import BCNNEngine
        super().__init__(cfg, mix, seed, fault)
        self.n_slots = int(cfg["n_slots"])
        self.images = make_images(seed, int(mix["image_pool"]),
                                  cfg["input_shape"])
        self.engine = BCNNEngine.from_packed(
            self.packed, n_slots=self.n_slots, plan=self.plan)
        self.rid_to_i: dict[int, int] = {}
        self.admitted: dict[int, float] = {}
        self.control = None

    def warmup(self) -> None:
        self.engine.warmup()
        for img in self.images[:4 * self.n_slots]:
            self.engine.submit(img)
        self.engine.run()
        if self.fault == "control":
            self.control = control_logits(self.cfg, self.raw, self.images)

    def image_of(self, i: int) -> np.ndarray:
        return self.images[i % len(self.images)]

    def submit(self, i: int) -> None:
        self.rid_to_i[self.engine.submit(self.image_of(i))] = i

    @property
    def active(self) -> bool:
        return self.engine.sched.any_active

    def step(self) -> list:
        out = self.engine.step()
        self.last_step_items = len(out)
        # the step's answers are the newest of the scheduler's finished
        # requests; their admission stamps are read now, before the
        # engine's bounded history lets them go
        finished = self.engine.sched.finished
        for k in range(1, len(out) + 1):
            r = finished[-k]
            self.admitted[self.rid_to_i[r.rid]] = r.t_admit
        events = [(self.rid_to_i[r], "done", v) for r, v in out.items()]
        if self.control is not None:
            events = [(i, kind, self.control[i % len(self.images)])
                      for i, kind, _ in events]
        if self.fault == "answer" and events:
            i, kind, v = events[0]
            events[0] = (i, kind, v + 1.0)
        return events

    def admissions(self) -> dict:
        return self.admitted

    def counters(self) -> dict:
        return {"plan": self.plan.path,
                "engine_steps": self.engine.steps_executed,
                "step_compiles": self.engine.step_cache_size}

    def check(self, run) -> list:
        done = [i for i, r in enumerate(run.reqs) if r.t_done is not None]
        if not done:
            return [{"name": "answered", "value": 1.0, "limit": 0.0}]
        rng = generator.rng_for(self.seed, "check")
        k = min(int(self.cfg["check_rows"]), len(done))
        pick = np.sort(rng.choice(done, size=k, replace=False))
        got = np.stack([run.reqs[i].value for i in pick])
        images = np.stack([self.image_of(i) for i in pick])
        return [logit_check(self.cfg, self.raw, images, got)]


class Offline(_Base):
    """Bulk batches through ``classify_batch``, one caller, closed loop."""

    def __init__(self, cfg, mix, sched, seed, fault=None):
        from repro.serve import BCNNEngine
        super().__init__(cfg, mix, seed, fault)
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
            self.control = control_logits(
                self.cfg, self.raw, self.batches.reshape(
                    -1, *self.batches.shape[2:])).reshape(
                        len(self.batches), self.batch, -1)

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
            r = np.sort(rng.choice(self.batch, size=per, replace=False))
            got.append(self.outputs[j][r])
            images.append(self.batches[j % len(self.batches)][r])
        return [logit_check(self.cfg, self.raw, np.concatenate(images),
                            np.concatenate(got))]


def build(cfg: dict, mix: dict, sched, seed: int, fault=None):
    cls = {"step": Online, "classify_batch": Offline}[mix["entry"]]
    return cls(cfg, mix, sched, seed, fault)
