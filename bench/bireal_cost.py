"""Operations and bytes of Bi-Real Net, from the configuration file alone.

Kept with the benchmark, so no change to the program can move them
(``bench/yardstick.py`` holds the Table 2 counts and the peaks):

* a binary conv's MACs are out_h * out_w * out * 3 * 3 * in, counted at
  its stride; its ops (2 per MAC) count at the int8 peak, whatever path
  computes them. Its bytes are the packed input bits, the packed filters
  and the int32 agree-counts it writes (the unfused interface);
* the real layers (the stem conv, the 1x1 downsamples, the FC) count their
  MACs the same way, at the bf16 peak; pools and the elementwise affine,
  residual add and re-sign are not counted.

At the published widths (224x224x3, stages of 64, 128, 256 and 512
channels, four blocks each, 1000 classes) an image takes 1,676,279,808
binary MACs and 137,793,536 real ones: the stem 118,013,952, each of the
three downsamples 6,422,528, the FC 512,000.
"""
from __future__ import annotations

from dataclasses import dataclass

from bench import yardstick

FILTER = 3


@dataclass(frozen=True)
class BinConv:
    name: str
    h: int          # input height
    w: int
    c: int          # input channels
    o: int          # output channels
    stride: int

    @property
    def ho(self) -> int:
        return (self.h + 2 * (FILTER // 2) - FILTER) // self.stride + 1

    @property
    def wo(self) -> int:
        return (self.w + 2 * (FILTER // 2) - FILTER) // self.stride + 1

    @property
    def macs(self) -> int:
        return self.ho * self.wo * self.o * FILTER * FILTER * self.c


def _conv_out(n: int, k: int, stride: int, pad: int) -> int:
    return (n + 2 * pad - k) // stride + 1


def binary_convs(cfg: dict) -> tuple[BinConv, ...]:
    """The binary convs the configuration states, in order."""
    h, w, _ = cfg["input_shape"]
    stem = cfg["stem"]
    pool = stem["pool"]
    h, w = (_conv_out(_conv_out(n, stem["kernel"], stem["stride"],
                                stem["kernel"] // 2),
                      pool["kernel"], pool["stride"], pool["pad"])
            for n in (h, w))
    c = stem["channels"]
    out = []
    for s, (o, n, stride) in enumerate(cfg["stages"]):
        for b in range(n):
            conv = BinConv(f"stage{s + 1}.block{b + 1}", h, w, c, o,
                           stride if b == 0 else 1)
            out.append(conv)
            h, w, c = conv.ho, conv.wo, o
    return tuple(out)


def real_macs(cfg: dict) -> dict[str, int]:
    """MACs per image of each real layer that multiplies: the stem conv,
    the 1x1 downsamples and the FC."""
    h, w, cin = cfg["input_shape"]
    stem = cfg["stem"]
    k = stem["kernel"]
    ho = _conv_out(h, k, stem["stride"], k // 2)
    wo = _conv_out(w, k, stem["stride"], k // 2)
    out = {"stem": ho * wo * stem["channels"] * k * k * cin}
    for conv in binary_convs(cfg):
        if conv.stride != 1 or conv.c != conv.o:
            out[conv.name + ".down"] = conv.ho * conv.wo * conv.o * conv.c
    out["fc"] = cfg["stages"][-1][0] * cfg["n_classes"]
    return out


def binary_macs_per_image(cfg: dict) -> int:
    return sum(conv.macs for conv in binary_convs(cfg))


def real_macs_per_image(cfg: dict) -> int:
    return sum(real_macs(cfg).values())


def binconv_cost(conv: BinConv, n: int) -> tuple[int, int]:
    """(ops, bytes) of one binary conv call over ``n`` images."""
    ops = 2 * n * conv.macs
    nbytes = (n * conv.h * conv.w * conv.c // 8
              + conv.o * FILTER * FILTER * conv.c // 8
              + n * conv.ho * conv.wo * conv.o * 4)
    return ops, nbytes


def binconv_least_time(cfg: dict, n: int, peaks: dict) -> float:
    """Summed least time of every binary conv over ``n`` images: each the
    larger of its ops at the int8 peak and its bytes at HBM bandwidth."""
    return sum(yardstick.least_time(*binconv_cost(conv, n),
                                    peaks["int8_ops_per_s"],
                                    peaks["hbm_bytes_per_s"])[0]
               for conv in binary_convs(cfg))


def least_compute_s_per_image(cfg: dict, peaks: dict) -> float:
    """An image's binary ops at the int8 peak plus its real FLOPs at the
    bf16 peak."""
    return (2 * binary_macs_per_image(cfg) / peaks["int8_ops_per_s"]
            + 2 * real_macs_per_image(cfg) / peaks["bf16_flops_per_s"])
