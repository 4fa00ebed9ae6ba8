"""Data pipeline: DataSet containers, iterators, async prefetch, datasets.

Reference parity: ND4J `DataSet`/`MultiDataSet` + deeplearning4j-core
`datasets/` (iterators, fetchers) + dl4j-nn `datasets/iterator/`
(AsyncDataSetIterator and decorators).
"""

from deeplearning4j_tpu.observe.trace import span as _span

with _span("import.data"):
    from deeplearning4j_tpu.data.dataset import DataSet, MultiDataSet
    from deeplearning4j_tpu.data.iterators import (
        DataSetIterator, ArrayDataSetIterator, AsyncDataSetIterator,
        MultipleEpochsIterator, EarlyTerminationDataSetIterator,
        BenchmarkDataSetIterator, FileSplitDataSetIterator, as_iterator,
    )

__all__ = [
    "DataSet", "MultiDataSet", "DataSetIterator", "ArrayDataSetIterator",
    "AsyncDataSetIterator", "MultipleEpochsIterator",
    "EarlyTerminationDataSetIterator", "BenchmarkDataSetIterator",
    "FileSplitDataSetIterator",
    "as_iterator",
]
