"""The benchmark's own op and byte counts, against numbers worked by hand."""
import pytest

from bench import yardstick as ys


def test_bcnn_ops_per_image_is_the_papers_count():
    # 2 x 616,966,144 MACs: the count behind 7.663 TOPS at 6,218 FPS
    assert ys.bcnn_macs_per_image() == 616_966_144
    assert ys.bcnn_ops_per_image() == 1_233_932_288
    assert ys.bcnn_ops_per_image() * 6218 / 1e12 == pytest.approx(7.6726,
                                                                  abs=1e-3)


# (layer, macs, bytes for one image): bytes are packed input bits, packed
# filters and the int8 {0,1} output map before the pool
CONV_CASES = [
    ("conv1", 3_538_944, 32 * 32 * 3 // 8 + 128 * 9 * 3 // 8 + 32 * 32 * 128),
    ("conv2", 150_994_944, 32 * 32 * 128 // 8 + 128 * 9 * 128 // 8
     + 32 * 32 * 128),
    ("conv3", 75_497_472, 16 * 16 * 128 // 8 + 256 * 9 * 128 // 8
     + 16 * 16 * 256),
    ("conv4", 150_994_944, 16 * 16 * 256 // 8 + 256 * 9 * 256 // 8
     + 16 * 16 * 256),
    ("conv5", 75_497_472, 8 * 8 * 256 // 8 + 512 * 9 * 256 // 8
     + 8 * 8 * 512),
    ("conv6", 150_994_944, 8 * 8 * 512 // 8 + 512 * 9 * 512 // 8
     + 8 * 8 * 512),
]


@pytest.mark.parametrize("name,macs,nbytes", CONV_CASES)
def test_conv_layer_ops_and_bytes(name, macs, nbytes):
    layer = next(c for c in ys.BCNN_CONVS if c.name == name)
    assert ys.conv_macs(layer) == macs
    assert ys.conv_cost(layer, 1) == (2 * macs, nbytes)
    ops, b = ys.conv_cost(layer, 256)
    assert ops == 512 * macs
    # the filters are read once per call, the maps once per image
    filt = layer.o * 9 * layer.c // 8
    assert b == 256 * (nbytes - filt) + filt


@pytest.mark.parametrize("name,k,o,out_bytes", [
    ("fc1", 8192, 1024, 1024 // 8), ("fc2", 1024, 1024, 1024 // 8),
    ("fc3", 1024, 10, 10 * 4)])
def test_fc_layer_ops_and_bytes(name, k, o, out_bytes):
    layer = next(f for f in ys.BCNN_FCS if f.name == name)
    assert ys.fc_cost(layer, 1) == (2 * k * o, k // 8 + o * k // 8
                                    + out_bytes)


def test_least_time_names_the_binding_bound():
    peaks = ys.load_peaks("TPU v5 lite")
    t, bound = ys.least_time(393e12, 1.0, peaks["int8_ops_per_s"],
                             peaks["hbm_bytes_per_s"])
    assert (t, bound) == (1.0, "compute")
    t, bound = ys.least_time(1.0, 819e9, peaks["int8_ops_per_s"],
                             peaks["hbm_bytes_per_s"])
    assert (t, bound) == (1.0, "memory")
    # Table 2 at 256 images: every binary conv is bound by compute
    _, bound = ys.kernel_least_time(ys.BCNN_CONVS[1:], 256, peaks)
    assert bound == "compute"


def test_unknown_device_kind_is_an_error():
    with pytest.raises(KeyError):
        ys.load_peaks("TPU v99")


def test_layers_come_from_the_configuration_file():
    import json
    from pathlib import Path
    cfg = json.loads((Path(__file__).resolve().parents[2] / "bench"
                      / "configs" / "bcnn-table2.json").read_text())
    assert ys.bcnn_layers(cfg) == (ys.BCNN_CONVS, ys.BCNN_FCS)
    # a narrower network changes the count; the yardstick follows the file
    narrow = dict(cfg, conv_channels=[64, 64, 128, 128, 256, 256],
                  fc_features=[4096, 512, 512, 10])
    convs, fcs = ys.bcnn_layers(narrow)
    assert [c.o for c in convs] == narrow["conv_channels"]
    assert convs[5] == ys.ConvLayer("conv6", 8, 8, 256, 256, True)
    assert ys.bcnn_ops_per_image(convs, fcs) < ys.bcnn_ops_per_image()
