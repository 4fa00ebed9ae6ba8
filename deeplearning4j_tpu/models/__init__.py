"""Model runtimes: MultiLayerNetwork (sequential) and ComputationGraph (DAG).

Reference parity: `nn/multilayer/MultiLayerNetwork.java` and
`nn/graph/ComputationGraph.java`. The eager per-op loop of the reference
becomes one jitted XLA computation per train step here.
"""

from deeplearning4j_tpu.observe.trace import span as _span

with _span("import.models"):
    from deeplearning4j_tpu.models.multilayer import MultiLayerNetwork
    from deeplearning4j_tpu.models.computation_graph import ComputationGraph
    from deeplearning4j_tpu.models.fusion import fuse_conv_bn

__all__ = ["MultiLayerNetwork", "ComputationGraph", "fuse_conv_bn"]
