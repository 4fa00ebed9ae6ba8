"""The FLOPs functions against the published counts."""

from benchmarks import harness

FULL = {"input_shape": [224, 224, 3], "label_shape": [1000]}


def test_resnet50_forward_macs():
    # 3.86 GMACs: He et al. quote 3.8e9 multiply-adds for the 50-layer
    # net; the 4.1 of torchvision's v1.5 strides in the 3x3 instead.
    ref = harness.load_module("reference", "resnet50.py")
    assert abs(ref.forward_macs(FULL) / 1e9 - 3.858) < 0.01
    assert 3.8e9 <= ref.forward_macs(FULL) <= 4.1e9


def test_vgg16_forward_macs():
    # 15.5 GMACs: configuration D, 15.3 in the 13 convolutions.
    ref = harness.load_module("reference", "vgg16.py")
    assert abs(ref.forward_macs(FULL) / 1e9 - 15.47) < 0.01


def test_tables_name_the_program_leaves():
    ref = harness.load_module("reference", "resnet50.py")
    rows = ref.conv_table(FULL)
    assert len(rows) == 54 and rows[0][-1] == 112 and rows[-2][-1] == 7
