"""Graph embeddings: graph API, random walks, DeepWalk.

Reference parity: `deeplearning4j-graph/` — graph structures
(`graph/api/IGraph.java`, `graph/graph/Graph.java`), random-walk iterators
(`graph/iterator/RandomWalkIterator.java`, `WeightedRandomWalkIterator.java`),
DeepWalk (`graph/models/deepwalk/DeepWalk.java`) with degree-based Huffman
coding (`graph/models/deepwalk/GraphHuffman.java`), vector queries
(`graph/models/GraphVectors.java`) and serialization
(`graph/models/loader/GraphVectorSerializer.java`).
"""

from deeplearning4j_tpu.observe.trace import span as _span

with _span("import.graph"):
    from deeplearning4j_tpu.graph.api import (
        Edge, Graph, NoEdgeHandling, Vertex, load_edge_list,
        load_weighted_edge_list,
    )
    from deeplearning4j_tpu.graph.walks import (
        Node2VecWalker, RandomWalker, WeightedWalker, generate_walks,
    )
    from deeplearning4j_tpu.graph.deepwalk import (
        DeepWalk, GraphHuffman, Node2Vec,
    )

__all__ = [
    "Edge", "Graph", "NoEdgeHandling", "Vertex", "load_edge_list",
    "load_weighted_edge_list", "Node2VecWalker", "RandomWalker",
    "WeightedWalker", "generate_walks", "DeepWalk", "GraphHuffman",
    "Node2Vec",
]
