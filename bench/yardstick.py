"""Operations and bytes the benchmark counts, and the chip's peaks.

Everything here is computed from shapes alone and kept with the benchmark,
so no change to the program can move it. The arithmetic is the paper's:

* BCNN (Table 2): a layer's binary MACs are out_h * out_w * out_ch * fh *
  fw * in_ch (conv, counted before the max-pool) and in * out (FC); ops are
  2 * MACs. Over the nine layers that is 2 * 616,966,144 =
  1,233,932,288 ops per image, the count behind the paper's 7.663 TOPS at
  6,218 FPS.
* A kernel's least time is the larger of ops / peak and bytes / HBM
  bandwidth. Binary ops count at the int8 peak whatever path computes them;
  bytes are the packed input, the weights and the output at the widths the
  layer's interface defines.
"""
from __future__ import annotations

import json
from dataclasses import dataclass
from pathlib import Path

PEAKS_FILE = Path(__file__).with_name("peaks.json")


def load_peaks(device_kind: str, path: Path = PEAKS_FILE) -> dict:
    """The peaks of ``device_kind``; a kind missing from the table is an
    error, never a default."""
    table = json.loads(Path(path).read_text())
    if device_kind not in table:
        raise KeyError(f"device kind {device_kind!r} is not in {path.name} "
                       f"(known: {sorted(table)})")
    return table[device_kind]


def least_time(ops: float, nbytes: float, ops_per_s: float,
               bytes_per_s: float) -> tuple[float, str]:
    """(seconds, bound) of the roofline: the larger of compute and memory."""
    t_ops, t_mem = ops / ops_per_s, nbytes / bytes_per_s
    return (t_ops, "compute") if t_ops >= t_mem else (t_mem, "memory")


# --------------------------------------------------------------------- BCNN
@dataclass(frozen=True)
class ConvLayer:
    name: str
    h: int          # output height before the max-pool (= input height)
    w: int
    c: int          # input channels
    o: int          # output channels
    pool: bool
    f: int = 3


@dataclass(frozen=True)
class FcLayer:
    name: str
    k: int          # input features
    o: int          # output features
    binary_out: bool = True


BCNN_CONVS = (
    ConvLayer("conv1", 32, 32, 3, 128, False),
    ConvLayer("conv2", 32, 32, 128, 128, True),
    ConvLayer("conv3", 16, 16, 128, 256, False),
    ConvLayer("conv4", 16, 16, 256, 256, True),
    ConvLayer("conv5", 8, 8, 256, 512, False),
    ConvLayer("conv6", 8, 8, 512, 512, True),
)
BCNN_FCS = (
    FcLayer("fc1", 8192, 1024),
    FcLayer("fc2", 1024, 1024),
    FcLayer("fc3", 1024, 10, binary_out=False),
)


def bcnn_layers(cfg: dict) -> tuple[tuple[ConvLayer, ...],
                                     tuple[FcLayer, ...]]:
    """The conv and FC layers a BCNN configuration file states (its
    ``input_shape``, ``conv_channels``, ``maxpool``, ``filter_size`` and
    ``fc_features``)."""
    h, w, c = cfg["input_shape"]
    convs = []
    for i, (o, pool) in enumerate(zip(cfg["conv_channels"], cfg["maxpool"])):
        convs.append(ConvLayer(f"conv{i + 1}", h, w, c, o, bool(pool),
                               cfg["filter_size"]))
        c = o
        if pool:
            h, w = h // 2, w // 2
    fc = cfg["fc_features"]
    fcs = tuple(FcLayer(f"fc{i + 1}", fc[i], fc[i + 1],
                        binary_out=i < len(fc) - 2)
                for i in range(len(fc) - 1))
    return tuple(convs), fcs


def conv_macs(layer: ConvLayer) -> int:
    return layer.h * layer.w * layer.o * layer.f * layer.f * layer.c


def bcnn_macs_per_image(convs=BCNN_CONVS, fcs=BCNN_FCS) -> int:
    return (sum(conv_macs(c) for c in convs)
            + sum(fc.k * fc.o for fc in fcs))


def bcnn_ops_per_image(convs=BCNN_CONVS, fcs=BCNN_FCS) -> int:
    return 2 * bcnn_macs_per_image(convs, fcs)


def conv_cost(layer: ConvLayer, n: int) -> tuple[int, int]:
    """(ops, bytes) of one binary conv call over ``n`` images: packed input
    bits, packed filters, and the {0,1} int8 output map before the pool."""
    ops = 2 * n * conv_macs(layer)
    nbytes = (n * layer.h * layer.w * layer.c // 8
              + layer.o * layer.f * layer.f * layer.c // 8
              + n * layer.h * layer.w * layer.o)
    return ops, nbytes


def fc_cost(layer: FcLayer, n: int) -> tuple[int, int]:
    """(ops, bytes) of one XNOR matmul over ``n`` rows: packed input words,
    packed weights, and packed output bits (float32 logits for FC-3)."""
    ops = 2 * n * layer.k * layer.o
    out = n * layer.o // 8 if layer.binary_out else n * layer.o * 4
    return ops, n * layer.k // 8 + layer.o * layer.k // 8 + out


def kernel_least_time(layers, n: int, peaks: dict) -> tuple[float, str]:
    """Summed least time of ``layers`` (ConvLayer/FcLayer) over ``n`` images,
    and the bound that binds the most of it."""
    total, by_bound = 0.0, {"compute": 0.0, "memory": 0.0}
    for layer in layers:
        cost = conv_cost if isinstance(layer, ConvLayer) else fc_cost
        ops, nbytes = cost(layer, n)
        t, bound = least_time(ops, nbytes, peaks["int8_ops_per_s"],
                              peaks["hbm_bytes_per_s"])
        total += t
        by_bound[bound] += t
    return total, max(by_bound, key=by_bound.get)
