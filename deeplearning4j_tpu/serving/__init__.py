"""Serving: the model-serving control plane + REST endpoints.

Reference parity: deeplearning4j-nearestneighbor-server
(`NearestNeighborsServer.java:37`, `NearestNeighbor.java:19` — REST k-NN
over a VPTree) plus the model server. The control plane
(registry/scheduler/metrics) is the TPU-native extension: multi-model
hosting with hot-swap, continuous batching, admission control, and a
/metrics surface over the ParallelInference data plane.
"""

from deeplearning4j_tpu.observe.trace import span as _span

with _span("import.serving"):
    from deeplearning4j_tpu.serving.http_base import (
        HttpError, JsonHttpServer, StreamResponse,
    )
    from deeplearning4j_tpu.serving.inference_server import (
        InferenceServer, ModelServer,
    )
    from deeplearning4j_tpu.serving.knn_server import NearestNeighborsServer
    from deeplearning4j_tpu.serving.kv_pool import (
        IncompatibleSessionSwapError, KVSlotPool, SlotPoolExhaustedError,
    )
    from deeplearning4j_tpu.serving.metrics import ServingStats
    from deeplearning4j_tpu.serving.prefix_cache import PrefixCache
    from deeplearning4j_tpu.serving.registry import (
        DeployRolledBackError, ModelEntry, ModelRegistry,
    )
    from deeplearning4j_tpu.serving.scheduler import (
        AdmissionPolicy, ContinuousBatchingScheduler, DeadlineExceededError,
        RequestShedError, SchedulerClosedError, WorkerCrashError,
    )
    from deeplearning4j_tpu.serving.sessions import (
        DecodeSession, DecodeSessionManager,
    )

__all__ = [
    "AdmissionPolicy", "ContinuousBatchingScheduler", "DecodeSession",
    "DecodeSessionManager", "DeadlineExceededError",
    "DeployRolledBackError", "HttpError", "IncompatibleSessionSwapError",
    "InferenceServer", "JsonHttpServer", "KVSlotPool", "ModelEntry",
    "ModelRegistry", "ModelServer", "NearestNeighborsServer",
    "PrefixCache",
    "RequestShedError", "SchedulerClosedError", "ServingStats",
    "SlotPoolExhaustedError", "StreamResponse", "WorkerCrashError",
]
