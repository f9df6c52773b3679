"""Operation, byte and parameter counts against the published numbers."""
import pytest

from benchlib import harness


def _config(name):
    mod = harness.load_module(harness.BENCH / "configs" / f"{name}.py", "t_")
    conf = harness.load_json(harness.BENCH / "configs" / f"{name}.json")
    return mod, conf


def test_darknet19_operations_per_image():
    # YOLO9000, Table 6: Darknet-19 needs 5.58 billion operations at 224.
    mod, conf = _config("darknet19")
    assert mod.flops_per_image(conf) == pytest.approx(5.58e9, rel=0.01)


def test_darknet19_parameters():
    # 20.9M parameters: 19 convolutions with batch-norm (4 per channel) and
    # the 1000-way 1x1 convolution with its bias.
    mod, conf = _config("darknet19")
    assert mod.param_count(conf) == pytest.approx(20.9e6, rel=0.01)
    convs = [l for l in conf["layers"] if l["type"] == "convolutional"]
    assert len(convs) == 19


def test_darknet19_conv_bytes_count_each_tensor_once():
    mod, conf = _config("darknet19")
    flops, nbytes = mod.conv_work(conf, 1)[0]   # 224x224x3 -> 224x224x32
    assert flops == 2 * 224 * 224 * 32 * 9 * 3
    assert nbytes == 4 * (224 * 224 * 3 + 27 * 32 + 2 * 32 + 224 * 224 * 32)
    assert mod.conv_work(conf, 8)[0][0] == 8 * flops
