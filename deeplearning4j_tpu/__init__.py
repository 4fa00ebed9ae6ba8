"""deeplearning4j_tpu — a TPU-native deep-learning framework.

A ground-up JAX/XLA/Pallas re-design with the capabilities of Deeplearning4j
(reference: marcelomata/deeplearning4j). Where the reference runs an eager,
op-at-a-time JVM runtime over libnd4j/cuDNN, this framework expresses every
model as pure-functional layer graphs compiled into ONE jitted, sharded XLA
computation per training step, with parallelism expressed as `jax.sharding`
annotations over a device mesh rather than threads/Spark/Aeron.

Top-level subpackages
---------------------
- ``nn``        layer/vertex configs + pure-functional implementations
                (reference: deeplearning4j-nn `nn/conf`, `nn/layers`)
- ``models``    MultiLayerNetwork / ComputationGraph runtimes
                (reference: `nn/multilayer/MultiLayerNetwork.java`,
                `nn/graph/ComputationGraph.java`)
- ``optim``     updaters, solver loop, listeners
                (reference: `nn/updater`, `optimize/`)
- ``eval``      Evaluation / ROC / regression metrics (reference: `eval/`)
- ``data``      DataSet, iterators, async prefetch, canned datasets
                (reference: deeplearning4j-core `datasets/`)
- ``parallel``  mesh/data/tensor/pipeline/sequence parallelism + inference
                (reference: deeplearning4j-scaleout — redesigned over ICI)
- ``nlp``       SequenceVectors/Word2Vec-class embedding training
                (reference: deeplearning4j-nlp-parent)
- ``zoo``       model catalog (reference: deeplearning4j-zoo)
- ``keras_import``  Keras .h5 importer (reference: deeplearning4j-modelimport)
- ``ops``       Pallas TPU kernels + custom XLA ops
- ``utils``     serde, pytree/param-view helpers, dtype policy
"""

__version__ = "0.2.0"

from deeplearning4j_tpu.observe.trace import span as _span

with _span("import.deeplearning4j_tpu"):
    from deeplearning4j_tpu.nn.inputs import InputType
    from deeplearning4j_tpu.nn.activations import Activation
    from deeplearning4j_tpu.nn.losses import LossFunction
    from deeplearning4j_tpu.nn.initializers import WeightInit


_LAZY = {
    "NeuralNetConfiguration": ("deeplearning4j_tpu.nn.config",
                               "NeuralNetConfiguration"),
    "MultiLayerNetwork": ("deeplearning4j_tpu.models", "MultiLayerNetwork"),
    "ComputationGraph": ("deeplearning4j_tpu.models", "ComputationGraph"),
    "Evaluation": ("deeplearning4j_tpu.eval", "Evaluation"),
    "save_model": ("deeplearning4j_tpu.models.serialize", "save_model"),
    "load_model": ("deeplearning4j_tpu.models.serialize", "load_model"),
}


def __getattr__(name):
    """Lazy convenience access to the workhorse classes — avoids importing
    the heavier models/eval/serialize modules (and their transitive deps)
    until first use; resolved attributes are cached in the module dict so
    repeat accesses are plain lookups."""
    if name in _LAZY:
        import importlib

        mod, attr = _LAZY[name]
        value = getattr(importlib.import_module(mod), attr)
        globals()[name] = value
        return value
    raise AttributeError(f"module 'deeplearning4j_tpu' has no "
                         f"attribute {name!r}")


__all__ = [
    "InputType",
    "Activation",
    "LossFunction",
    "WeightInit",
    "NeuralNetConfiguration",
    "MultiLayerNetwork",
    "ComputationGraph",
    "Evaluation",
    "save_model",
    "load_model",
    "__version__",
]
