"""Parallelism over TPU device meshes.

Reference parity (redesigned): deeplearning4j-scaleout's five data-parallel
flavors (SURVEY §2.4) — ParallelWrapper AVERAGING / SHARED_GRADIENTS, Spark
parameter averaging, Aeron parameter server, hogwild embeddings — all
collapse on TPU into sharded jit over a `jax.sharding.Mesh` with XLA
collectives over ICI (allreduce replaces quantized-gradient queues,
treeAggregate, and the PS daemon at once; SURVEY §5 'distributed
communication backend').

Extensions beyond the reference (required for TPU scale, SURVEY §7 step 7):
tensor/sequence parallelism as extra mesh axes, ring attention for long
context, multi-host DCN initialization.
"""

from deeplearning4j_tpu.observe.trace import span as _span

with _span("import.parallel"):
    from deeplearning4j_tpu.parallel.mesh import (
        MeshContext, MeshSpec, current_mesh_context, device_count,
        local_device_count, make_mesh, set_mesh_context, use_mesh_context,
    )
    from deeplearning4j_tpu.parallel.data_parallel import ParallelWrapper
    from deeplearning4j_tpu.parallel.sharding import (
        ShardingRules, shard_params, replicate, batch_sharding,
        fsdp_rules, tensor_parallel_rules,
    )
    from deeplearning4j_tpu.parallel.inference import ParallelInference
    from deeplearning4j_tpu.parallel.distributed import initialize_distributed
    from deeplearning4j_tpu.parallel.pipeline import (
        PipelineParallel, PipelinedNetwork, make_pipeline_fn,
        make_pipeline_1f1b_fn, partition_for_pipeline, stack_stage_params,
        split_microbatches,
    )
    from deeplearning4j_tpu.parallel.moe import (
        MoEFeedForward, moe_ffn, top_k_gating, expert_sharding, expert_mesh,
    )
    from deeplearning4j_tpu.parallel.training_master import (
        TrainingMaster, ParameterAveragingTrainingMaster,
        DistributedTrainingMaster, PhaseStats, distributed_evaluate,
        export_timeline_html,
    )
    from deeplearning4j_tpu.parallel.estimator import NetworkEstimator
    from deeplearning4j_tpu.parallel.checkpoint import ShardedCheckpointer
    from deeplearning4j_tpu.parallel.elastic import ElasticTrainer, PreemptionHandler
    from deeplearning4j_tpu.parallel.async_ps import AsyncParameterServer, AsyncTrainer
    from deeplearning4j_tpu.parallel.chaos import (
        CheckpointIOFault, FailingIterator, InjectedFault, SigtermAtStep,
        StallingIterator,
    )

__all__ = [
    "ShardedCheckpointer", "ElasticTrainer", "PreemptionHandler",
    "CheckpointIOFault", "FailingIterator", "InjectedFault", "SigtermAtStep",
    "StallingIterator",
    "AsyncParameterServer", "AsyncTrainer",
    "MeshContext", "MeshSpec", "current_mesh_context", "set_mesh_context",
    "use_mesh_context",
    "make_mesh", "device_count", "local_device_count",
    "ParallelWrapper", "ParallelInference",
    "ShardingRules", "shard_params", "replicate", "batch_sharding",
    "fsdp_rules", "tensor_parallel_rules", "initialize_distributed",
    "PipelineParallel", "PipelinedNetwork", "make_pipeline_fn",
    "make_pipeline_1f1b_fn", "partition_for_pipeline", "stack_stage_params",
    "split_microbatches",
    "MoEFeedForward", "moe_ffn", "top_k_gating", "expert_sharding",
    "expert_mesh",
    "TrainingMaster", "ParameterAveragingTrainingMaster",
    "DistributedTrainingMaster", "PhaseStats", "NetworkEstimator",
    "distributed_evaluate", "export_timeline_html",
]
