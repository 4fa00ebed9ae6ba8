"""Evaluation metrics.

Reference parity: `eval/` in deeplearning4j-nn — Evaluation (confusion
matrix / precision / recall / F1), EvaluationBinary, RegressionEvaluation,
ROC family. Metrics accumulate batch-wise on host numpy (tiny data), matching
the reference's streaming eval design.
"""

from deeplearning4j_tpu.observe.trace import span as _span

with _span("import.eval"):
    from deeplearning4j_tpu.eval.evaluation import Evaluation, ConfusionMatrix
    from deeplearning4j_tpu.eval.regression import RegressionEvaluation
    from deeplearning4j_tpu.eval.roc import ROC, ROCBinary, ROCMultiClass
    from deeplearning4j_tpu.eval.binary import EvaluationBinary
    from deeplearning4j_tpu.eval.meta import Prediction, RecordMetaData

__all__ = [
    "Evaluation", "ConfusionMatrix", "RegressionEvaluation", "ROC",
    "ROCBinary", "ROCMultiClass", "EvaluationBinary",
    "Prediction", "RecordMetaData",
]
