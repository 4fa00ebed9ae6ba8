"""Model zoo.

Reference parity: `deeplearning4j-zoo` (`zoo/ZooModel.java:40-52`,
`ModelSelector.java`) — catalog: LeNet, AlexNet, VGG16/19, GoogLeNet,
ResNet50, InceptionResNetV1, FaceNetNN4Small2, SimpleCNN,
TextGenerationLSTM. All NHWC / TPU-layout; conv stacks compile onto the MXU
with no helper seam.

`init_pretrained()` mirrors `ZooModel.initPretrained()`: loads weights from
the local cache dir (`~/.deeplearning4j_tpu/zoo/<name>.zip`); this
environment has no egress, so absent files raise with the expected path
instead of downloading.
"""

from deeplearning4j_tpu.observe.trace import span as _span

with _span("import.zoo"):
    from deeplearning4j_tpu.zoo.base import ZooModel, ZOO_REGISTRY
    from deeplearning4j_tpu.zoo.models import (
        LeNet, AlexNet, SimpleCNN, VGG16, VGG19, TextGenerationLSTM,
    )
    from deeplearning4j_tpu.zoo.resnet import ResNet50
    from deeplearning4j_tpu.zoo.inception import (
        GoogLeNet, InceptionResNetV1, FaceNetNN4Small2,
    )
    from deeplearning4j_tpu.zoo.transformer import (
        HybridLatentExpertTransformer, HybridLinearSparseTransformer,
        HybridStateSpaceTransformer, LatentSparseTransformer, LoopedSandwichTransformer,
        SparseSandwichTransformer, TextGenerationTransformer,
    )
    from deeplearning4j_tpu.zoo.pretrained import (
        PRETRAINED_CATALOG, PretrainedType, fetch_pretrained, load_pretrained,
        sniff_format,
    )
    from deeplearning4j_tpu.zoo.imagenet import ImageNetLabels

__all__ = [
    "PRETRAINED_CATALOG", "PretrainedType", "fetch_pretrained",
    "load_pretrained", "sniff_format", "ImageNetLabels",
    "ZooModel", "ZOO_REGISTRY", "LeNet", "AlexNet", "SimpleCNN", "VGG16",
    "VGG19", "TextGenerationLSTM", "ResNet50", "GoogLeNet",
    "InceptionResNetV1", "FaceNetNN4Small2", "TextGenerationTransformer",
    "SparseSandwichTransformer", "HybridLinearSparseTransformer",
    "LatentSparseTransformer", "HybridStateSpaceTransformer",
    "LoopedSandwichTransformer", "HybridLatentExpertTransformer",
]
