"""Operation counts against values worked out by hand."""

import os

from perfbench.lib import arch

CONFIGS = os.path.join(os.path.dirname(os.path.dirname(__file__)), "configs")


def load(name):
    return arch.load_config(os.path.join(CONFIGS, f"{name}.json"))


def test_resnet50_first_bottleneck_by_hand():
    layers = {l["key"]: l for l in arch.matmul_layers(load("resnet50-224"))}
    # res2_0 sees 56x56x64: 1x1 64->64, 3x3 64->64, 1x1 64->256, and the
    # projection 1x1 64->256, all at stride 1
    hw = 56 * 56
    assert layers["res2_0_a_conv"]["macs"] == hw * 64 * 64
    assert layers["res2_0_b_conv"]["macs"] == hw * 9 * 64 * 64
    assert layers["res2_0_c_conv"]["macs"] == hw * 64 * 256
    assert layers["res2_0_sc_conv"]["macs"] == hw * 64 * 256
    # the stem: 7x7x3 -> 64 at stride 2 gives 112x112
    assert layers["stem_conv"]["macs"] == 112 * 112 * 49 * 3 * 64
    # res3_0 strides in its first 1x1: 56 -> 28
    assert layers["res3_0_a_conv"]["macs"] == 28 * 28 * 256 * 128


def test_vgg16_first_two_layers_by_hand():
    cfg = load("vgg16-224")
    layers = {l["key"]: l for l in arch.matmul_layers(cfg)}
    assert layers[0]["macs"] == 224 * 224 * 9 * 3 * 64
    assert layers[1]["macs"] == 224 * 224 * 9 * 64 * 64
    # the head: 7*7*512 -> 4096 -> 4096 -> 1000
    assert layers[18]["macs"] == 25088 * 4096
    assert layers[20]["macs"] == 4096 * 1000


def test_whole_models_are_the_published_size():
    r, v = load("resnet50-224"), load("vgg16-224")
    assert 3.8e9 < arch.forward_macs(r) < 4.2e9
    assert 15.3e9 < arch.forward_macs(v) < 15.6e9
    assert abs(arch.num_params(r) - 25.557e6) < 5e3
    assert abs(arch.num_params(v) - 138.358e6) < 5e3
    assert arch.train_flops_per_example(v) == 6 * arch.forward_macs(v)


def test_configuration_files_state_source_and_cuts():
    for name in ("resnet50-224", "vgg16-224"):
        cfg = load(name)
        assert cfg["reduced"] == [] and cfg["source"] and cfg["assumed"]
        assert (cfg["image"], cfg["channels"], cfg["num_classes"]) == \
            (224, 3, 1000)
