"""Clustering + spatial search + t-SNE.

Reference parity: deeplearning4j-core `clustering/` (KMeans, VPTree for
k-NN, kdtree/quadtree/sptree) and `plot/BarnesHutTsne.java`.

TPU redesign: KMeans Lloyd iterations and t-SNE run as jitted dense matrix
computations (pairwise-distance matmuls on the MXU) — the reference's
Barnes-Hut tree approximations exist to avoid O(n²) on CPU; on TPU the
dense O(n²) form is faster for the dataset sizes these tools serve, so
BarnesHutTsne here is exact-t-SNE with the same API. VPTree remains a host
structure (serving-time k-NN needs low-latency single queries, not
throughput).
"""

from deeplearning4j_tpu.observe.trace import span as _span

with _span("import.clustering"):
    from deeplearning4j_tpu.clustering.kmeans import KMeansClustering
    from deeplearning4j_tpu.clustering.vptree import VPTree
    from deeplearning4j_tpu.clustering.kdtree import KDTree
    from deeplearning4j_tpu.clustering.tsne import BarnesHutTsne

__all__ = ["KMeansClustering", "VPTree", "KDTree", "BarnesHutTsne"]
