"""Compile the Pallas kernels and the serving forward for a TPU v5e chip.

Every other kernel test runs Pallas in interpret mode, which accepts block
shapes, casts, reshapes and VMEM footprints that the chip's compiler
(Mosaic) refuses. These tests compile the real kernels, ``interpret=False``,
at the paper's Table 2 widths for a *described* ``v5e:2x2`` topology — the
TPU compiler is installed even where no chip is attached — so a kernel the
chip would refuse fails here. Nothing runs; results are checked elsewhere
(tests/test_kernels.py, tests/test_xnor_conv*.py).

The topology is described inside the module-scoped ``topo`` fixture, never
at import time: only one process at a time may load the TPU library, and
every test worker imports every test file.
"""
import jax
import jax.numpy as jnp
import pytest
from jax.sharding import SingleDeviceSharding

from repro.core import bcnn, bireal, execution_plan
from repro.kernels import ops

BATCH = 4               # the serving engine's slot count (SERVE_N_SLOTS)
# Table 2 binary conv layers: name → (C, O, H)
CONVS = {"conv2": (128, 128, 32), "conv3": (128, 256, 16),
         "conv4": (256, 256, 16), "conv5": (256, 512, 8),
         "conv6": (512, 512, 8)}
# fused pairs: name → (C, OA, OB, H); the second member max-pools
PAIRS = {"conv3_4": (128, 256, 256, 16), "conv5_6": (256, 512, 512, 8)}
# FC layers: name → (K, N, fused NormBinarize)
FCS = {"fc1": (8192, 1024, True), "fc3": (1024, 10, False)}


@pytest.fixture(scope="module")
def topo():
    from jax.experimental import topologies
    try:
        return topologies.get_topology_desc(platform="tpu",
                                            topology_name="v5e:2x2")
    except Exception as e:
        pytest.skip(f"no v5e:2x2 topology can be described here: {e}")


@pytest.fixture(scope="module")
def one_chip(topo):
    return SingleDeviceSharding(topo.devices[0])


@pytest.fixture(autouse=True)
def no_persistent_cache():
    """A compile for a described chip is written to the persistent cache
    but cannot be read back without one: keep the cache out of it."""
    from jax.experimental.compilation_cache import compilation_cache as cc
    was = jax.config.jax_enable_compilation_cache
    jax.config.update("jax_enable_compilation_cache", False)
    cc.reset_cache()
    yield
    jax.config.update("jax_enable_compilation_cache", was)
    cc.reset_cache()


def _compile(fn, *args) -> str:
    """Compile for the described chip; returns the compiled HLO text."""
    return jax.jit(fn).lower(*args).compile().as_text()


def _sds(one_chip, shape, dtype):
    return jax.ShapeDtypeStruct(shape, dtype, sharding=one_chip)


@pytest.mark.parametrize("name", sorted(CONVS))
@pytest.mark.parametrize("path", ["mxu", "vpu"])
def test_xnor_conv2d_compiles(one_chip, path, name):
    c, o, h = CONVS[name]
    k = 9 * c

    def conv(a, w, tc, tf):
        return ops.xnor_conv2d(a, w, k=k, fh=3, fw=3, thr_c=tc, thr_flip=tf,
                               path=path, interpret=False)

    hlo = _compile(conv, _sds(one_chip, (BATCH, h, h, c), jnp.int8),
                   _sds(one_chip, (o, k // 32), jnp.int32),
                   _sds(one_chip, (o,), jnp.float32),
                   _sds(one_chip, (o,), jnp.bool_))
    assert "tpu_custom_call" in hlo


# the binary convs Bi-Real Net-18 adds (unfused, int32 counts out):
# name → (C, O, H, stride)
BIREAL_CONVS = {"stage1": (64, 64, 56, 1), "stage2_down": (64, 128, 56, 2),
                "stage4": (512, 512, 7, 1)}


@pytest.mark.parametrize("name", sorted(BIREAL_CONVS))
def test_bireal_conv_compiles(one_chip, name):
    c, o, h, stride = BIREAL_CONVS[name]
    k = 9 * c

    def conv(a, w):
        return ops.xnor_conv2d(a, w, k=k, fh=3, fw=3, stride=stride, pad=1,
                               path="mxu", interpret=False)

    hlo = _compile(conv, _sds(one_chip, (BATCH, h, h, c), jnp.int8),
                   _sds(one_chip, (o, k // 32), jnp.int32))
    assert "tpu_custom_call" in hlo


# Bi-Real Net-18 at its published widths
BIREAL18 = bireal.geometry(
    {"input_shape": [224, 224, 3], "input_dtype": "uint8",
     "stem": {"channels": 64, "kernel": 7, "stride": 2,
              "pool": {"kernel": 3, "stride": 2, "pad": 1}},
     "stages": [[64, 4, 1], [128, 4, 2], [256, 4, 2], [512, 4, 2]],
     "n_classes": 1000})


def test_bireal_forward_compiles(one_chip, monkeypatch):
    """The whole Bi-Real Net-18 forward at its published widths, 8 images
    of 224x224 uint8 pixels, on the default plan (Pallas ``mxu``)."""
    from repro.core import packed_model
    monkeypatch.setattr(ops, "_on_tpu", lambda: True)
    packed = bireal.fold(bireal.init(0, BIREAL18), BIREAL18)
    plan = execution_plan.default_plan(packed, backend="tpu")
    arrays, rebuild = packed_model.split(packed)

    def step(arrs, x):
        return rebuild(arrs).forward(x, plan)

    hlo = _compile(step,
                   tuple(_sds(one_chip, a.shape, a.dtype) for a in arrays),
                   _sds(one_chip, (8, 224, 224, 3), jnp.uint8))
    assert hlo.count("tpu_custom_call") >= 16


def test_bireal_bulk_chunk_takes_the_wire_form(one_chip, topo, monkeypatch):
    """The bulk path's chunk unit (``ShardedForward``'s) for Bi-Real Net-18
    at 128 images: its input parameter is the row-major wire form, laid
    out in tiles of whole rows, so the host fills it by tile copies and
    transposes no element; a 4-D image batch would be given a batch-minor
    layout here. The forward inside is whole: every binary conv's
    kernel."""
    from repro.launch.mesh import make_data_mesh
    from repro.parallel.bcnn_data_parallel import chunk_unit
    from jax.sharding import NamedSharding, PartitionSpec as P
    monkeypatch.setattr(ops, "_on_tpu", lambda: True)
    packed = bireal.fold(bireal.init(0, BIREAL18), BIREAL18)
    plan = execution_plan.default_plan(packed, backend="tpu")
    mesh = make_data_mesh(1, devices=topo.devices[:1])
    arrays, unit = chunk_unit(packed, mesh, plan, 128)
    rep = NamedSharding(mesh, P())
    hlo = unit.lower(
        tuple(jax.ShapeDtypeStruct(a.shape, a.dtype, sharding=rep)
              for a in arrays),
        jax.ShapeDtypeStruct((128, 224 * 224 * 3 // 4), jnp.uint32,
                             sharding=NamedSharding(mesh, P("data")))
    ).compile().as_text()
    params = hlo.split("entry_computation_layout={(", 1)[1].split(")->")[0]
    wire = params.rsplit(", ", 1)[1]
    assert wire.startswith("u32[128,37632]{1,0:T(8,128)"), wire
    assert hlo.count("tpu_custom_call") >= 16


@pytest.mark.parametrize("name", sorted(PAIRS))
@pytest.mark.parametrize("path", ["mxu", "vpu"])
def test_fused_pair_compiles(one_chip, path, name):
    c, oa, ob, h = PAIRS[name]
    ka, kb = 9 * c, 9 * oa

    def pair(a, wa, wb, ca, fa, cb, fb):
        return ops.xnor_conv2d_pair(
            a, wa, wb, ka=ka, kb=kb, fha=3, fwa=3, fhb=3, fwb=3, pool_b=True,
            thr_a_c=ca, thr_a_flip=fa, thr_b_c=cb, thr_b_flip=fb, path=path,
            interpret=False)

    hlo = _compile(pair, _sds(one_chip, (BATCH, h, h, c), jnp.int8),
                   _sds(one_chip, (oa, ka // 32), jnp.int32),
                   _sds(one_chip, (ob, kb // 32), jnp.int32),
                   _sds(one_chip, (oa,), jnp.float32),
                   _sds(one_chip, (oa,), jnp.bool_),
                   _sds(one_chip, (ob,), jnp.float32),
                   _sds(one_chip, (ob,), jnp.bool_))
    assert "tpu_custom_call" in hlo


@pytest.mark.parametrize("name", sorted(FCS))
@pytest.mark.parametrize("path", ["mxu", "vpu"])
def test_xnor_matmul_compiles(one_chip, path, name):
    k, n, fused = FCS[name]
    args = [_sds(one_chip, (BATCH, k // 32), jnp.int32),
            _sds(one_chip, (n, k // 32), jnp.int32)]
    if fused:
        args += [_sds(one_chip, (n,), jnp.float32),
                 _sds(one_chip, (n,), jnp.bool_)]

    def matmul(a, w, tc=None, tf=None):
        return ops.xnor_matmul(a, w, k=k, thr_c=tc, thr_flip=tf, path=path,
                               interpret=False)

    assert "tpu_custom_call" in _compile(matmul, *args)


@pytest.mark.parametrize("m,k,n", [(4, 128, 384), (4, 1024, 1024),
                                   (4, 8192, 256)])
def test_binary_weight_matmul_compiles(one_chip, m, k, n):
    """The LM decode GEMM: one K step (K ≤ 4096 bits) and a K loop."""
    def bw(a, w, s):
        return ops.binary_weight_matmul(a, w, k=k, scale=s, interpret=False)

    hlo = _compile(bw, _sds(one_chip, (m, k), jnp.float32),
                   _sds(one_chip, (n, k // 32), jnp.int32),
                   _sds(one_chip, (n,), jnp.float32))
    assert "tpu_custom_call" in hlo


def test_default_plan_forward_compiles(one_chip, monkeypatch):
    """The serving step as the engine builds it on a TPU: the default plan
    (Pallas ``mxu``, direct convs) over the full packed net at 4 slots."""
    monkeypatch.setattr(ops, "_on_tpu", lambda: True)
    packed = bcnn.fold_model(bcnn.init(jax.random.PRNGKey(0)))
    plan = execution_plan.default_plan(packed, backend="tpu")
    assert plan.path == "mxu"
    arrays, rebuild = bcnn.split_packed(packed)

    def step(arrs, x01):
        return bcnn.forward_packed(rebuild(arrs), x01, plan=plan)

    hlo = _compile(step,
                   tuple(_sds(one_chip, a.shape, a.dtype) for a in arrays),
                   _sds(one_chip, (BATCH, 32, 32, 3), jnp.float32))
    assert "tpu_custom_call" in hlo


def test_layer_scopes_leave_the_kernel_names(one_chip, monkeypatch):
    """Each layer group runs under its named scope (``bcnn.group_scope``),
    which reaches the operations' metadata; the kernels' custom calls keep
    the names a device trace finds them by."""
    monkeypatch.setattr(ops, "_on_tpu", lambda: True)
    packed = bcnn.fold_model(bcnn.init(jax.random.PRNGKey(0)))
    plan = execution_plan.default_plan(packed, backend="tpu")
    arrays, rebuild = bcnn.split_packed(packed)

    def step(arrs, x01):
        return bcnn.forward_packed(rebuild(arrs), x01, plan=plan)

    hlo = _compile(step,
                   tuple(_sds(one_chip, a.shape, a.dtype) for a in arrays),
                   _sds(one_chip, (BATCH, 32, 32, 3), jnp.float32))
    scopes = []
    for line in hlo.splitlines():
        if "custom-call(" in line and "pallas_call" in line:
            name = line.split(" = ", 1)[0].strip().lstrip("%")
            assert name.rpartition(".")[0] in (
                "xnor_conv2d", "xnor_conv2d_pair", "xnor_matmul"), name
            # op_name="jit(step)/<scope>/jit(<kernel>)/pallas_call"
            scopes.append(line.split('op_name="', 1)[1].split("/")[1])
    # one kernel per group, CONV-1 (a plain conv) aside
    groups = bcnn.plan_layer_groups(conv_fusion=plan.conv_fusion)
    assert sorted(scopes) == sorted(bcnn.group_scope(g) for g in groups[1:])
