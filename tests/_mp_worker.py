"""Multi-controller worker process for test_distributed_multiprocess.py.

Run as `python tests/_mp_worker.py` with env:
  MP_NPROC / MP_PID / MP_DEVS   — process grid + local virtual devices
  JAX_COORDINATOR_ADDRESS / JAX_NUM_PROCESSES / JAX_PROCESS_ID — picked up
      by initialize_distributed() (the env-var path under test)
  MP_OUTDIR                     — shared scratch dir (checkpoints, results)

This is the reference's "distributed without a cluster" strategy (Spark
`local[N]` — spark/BaseSparkTest.java:89) mapped to JAX's multi-controller
runtime: N real OS processes, each with a few virtual CPU devices, wired by
`jax.distributed.initialize` over a localhost coordinator. Everything that
would run on a real multi-host pod slice runs here: global mesh over all
processes' devices, per-process host_local_shard feeding, cross-process
collectives inside the jitted step, and the sharded checkpointer writing
one `process-<k>/` directory per host.
"""

import os
import sys

os.environ.setdefault("JAX_PLATFORMS", "cpu")
devs = int(os.environ.get("MP_DEVS", "2"))
flags = os.environ.get("XLA_FLAGS", "")
if "xla_force_host_platform_device_count" not in flags:
    os.environ["XLA_FLAGS"] = (
        flags + f" --xla_force_host_platform_device_count={devs}").strip()

import jax  # noqa: E402

import numpy as np  # noqa: E402

sys.path.insert(0, os.path.dirname(os.path.dirname(os.path.abspath(__file__))))

from deeplearning4j_tpu import InputType  # noqa: E402
from deeplearning4j_tpu.models import MultiLayerNetwork  # noqa: E402
from deeplearning4j_tpu.nn.config import NeuralNetConfiguration  # noqa: E402
from deeplearning4j_tpu.nn.layers import DenseLayer, OutputLayer  # noqa: E402
from deeplearning4j_tpu.optim.updaters import Sgd  # noqa: E402
from deeplearning4j_tpu.parallel import ParallelWrapper, make_mesh  # noqa: E402
from deeplearning4j_tpu.parallel.checkpoint import (  # noqa: E402
    ShardedCheckpointer,
)
from deeplearning4j_tpu.parallel.distributed import (  # noqa: E402
    initialize_distributed, process_count, process_index,
    sync_global_devices,
)
from deeplearning4j_tpu.parallel.training_master import (  # noqa: E402
    DistributedTrainingMaster, ParameterAveragingTrainingMaster,
    _allgather_host,
)

N, D, CLASSES, BATCH, EPOCHS = 64, 8, 4, 16, 2


def make_data():
    rng = np.random.default_rng(123)
    x = rng.standard_normal((N, D)).astype(np.float32)
    w = rng.standard_normal((D, CLASSES))
    y = np.eye(CLASSES, dtype=np.float32)[(x @ w).argmax(-1)]
    return x, y


def make_net():
    return MultiLayerNetwork(
        (NeuralNetConfiguration.builder()
         .seed(7).updater(Sgd(0.1)).activation("tanh")
         .list(DenseLayer(n_out=16),
               OutputLayer(n_out=CLASSES, activation="softmax"))
         .set_input_type(InputType.feed_forward(D))
         .build())).init()


def flat_params(net):
    """All param leaves flattened into one float64 vector (parity checks)."""
    return np.concatenate(
        [np.asarray(l).ravel().astype(np.float64)
         for l in jax.tree_util.tree_leaves(net.params_tree)])


def make_graph_net():
    from deeplearning4j_tpu.models import ComputationGraph

    conf = (NeuralNetConfiguration.builder()
            .seed(11).updater(Sgd(0.1)).activation("tanh")
            .graph_builder().add_inputs("in")
            .add_layer("d", DenseLayer(n_out=8), "in")
            .add_layer("out", OutputLayer(n_out=CLASSES,
                                          activation="softmax"), "d")
            .set_outputs("out")
            .set_input_types(InputType.feed_forward(D)).build())
    return ComputationGraph(conf).init()


def main():
    nproc = int(os.environ["MP_NPROC"])
    pid = int(os.environ["MP_PID"])
    outdir = os.environ["MP_OUTDIR"]

    initialize_distributed()  # env-var path: JAX_COORDINATOR_ADDRESS etc.
    assert process_count() == nproc, (process_count(), nproc)
    assert process_index() == pid, (process_index(), pid)
    assert len(jax.devices()) == nproc * devs, jax.devices()
    assert len(jax.local_devices()) == devs

    x, y = make_data()
    net = make_net()

    master = DistributedTrainingMaster(mesh=make_mesh({"data": -1}),
                                       collect_training_stats=True)
    master.execute_training(net, x, y, batch_size=BATCH, epochs=EPOCHS)
    stats = master.training_stats()
    assert stats and np.isfinite(stats[-1].score), stats

    # Sharded checkpoint: every process writes its own process-<k>/ dir.
    ckpt = ShardedCheckpointer(os.path.join(outdir, "ckpt"), async_save=False)
    ckpt.save(net, step=net.iteration, position={"batch_in_epoch": 0})
    sync_global_devices("ckpt-written")

    # Cross-process restore INSIDE the pod: a fresh model + wrapper on this
    # same process grid restores the union of all processes' manifests.
    net2 = make_net()
    pw2 = ParallelWrapper(net2, mesh=make_mesh({"data": -1}),
                          prefetch_buffer=0)
    ckpt2 = ShardedCheckpointer(os.path.join(outdir, "ckpt"))
    ckpt2.restore_into_wrapper(pw2)
    for a, b in zip(jax.tree_util.tree_leaves(net.params_tree),
                    jax.tree_util.tree_leaves(net2.params_tree)):
        np.testing.assert_allclose(np.asarray(a), np.asarray(b),
                                   rtol=1e-6, atol=1e-7)
    assert net2.iteration == net.iteration

    # ComputationGraph DP across processes: dict-shaped batches flow
    # through _batch_args(host=True) + per-process global-batch assembly.
    gnet = make_graph_net()
    DistributedTrainingMaster(mesh=make_mesh({"data": -1})).execute_training(
        gnet, x, y, batch_size=BATCH, epochs=1)
    gflat = flat_params(gnet)
    gg = _allgather_host(gflat)
    np.testing.assert_allclose(gg[0], gg[1], rtol=1e-6, atol=1e-8)
    if pid == 0:
        np.save(os.path.join(outdir, "cg_params.npy"), gflat)

    # Distributed evaluation: per-shard eval + cross-process merge
    # (SparkDl4jMultiLayer.evaluate(JavaRDD) analogue).
    from deeplearning4j_tpu.parallel.training_master import (
        distributed_evaluate,
    )

    ev = distributed_evaluate(net, x, y, batch_size=BATCH)
    assert int(ev.confusion.matrix.sum()) == N   # every example counted once
    if pid == 0:
        np.save(os.path.join(outdir, "eval_confusion.npy"),
                np.asarray(ev.confusion.matrix))

    # Parameter averaging ACROSS processes: local SGD over DCN — each
    # process trains num_workers logical workers on its host shard, then
    # params average over the process boundary (the Spark
    # driver<->executor flow; global workers = 2 procs x 2 = 4).
    net_pa = make_net()
    pam = ParameterAveragingTrainingMaster(
        num_workers=2, batch_size=8, averaging_frequency=2)
    pam.execute_training(net_pa, x, y, epochs=1)
    flat_pa = flat_params(net_pa)
    g = _allgather_host(flat_pa)
    np.testing.assert_allclose(g[0], g[1], rtol=1e-6, atol=1e-8)
    if pid == 0:
        np.save(os.path.join(outdir, "pa_params.npy"), flat_pa)

    # Sequence parallelism ACROSS processes: ring attention's ppermute
    # ring spans both hosts (the multi-host long-context path; single-
    # process coverage lives in test_parallel.py).
    import jax.numpy as jnp
    from jax.sharding import NamedSharding, PartitionSpec as P

    from deeplearning4j_tpu.parallel.distributed import put_global
    from deeplearning4j_tpu.parallel.ring_attention import (
        attention, ring_self_attention,
    )

    mesh2 = make_mesh({"seq": -1})
    r = np.random.default_rng(5)
    q, k, v = (r.standard_normal((2, 8, 2, 4)).astype(np.float32)
               for _ in range(3))
    sh = NamedSharding(mesh2, P(None, "seq", None, None))
    out = ring_self_attention(put_global(q, sh), put_global(k, sh),
                              put_global(v, sh), mesh2, axis="seq",
                              causal=True)
    ref = np.asarray(attention(jnp.asarray(q), jnp.asarray(k),
                               jnp.asarray(v), causal=True))
    for shd in out.addressable_shards:   # local shards vs global oracle
        np.testing.assert_allclose(np.asarray(shd.data), ref[shd.index],
                                   rtol=1e-4, atol=1e-5)
    sync_global_devices("ring-checked")

    if pid == 0:
        flat = {f"p{i}": np.asarray(l) for i, l in
                enumerate(jax.tree_util.tree_leaves(net.params_tree))}
        np.savez(os.path.join(outdir, "final_params.npz"),
                 score=np.float64(net.score_),
                 iteration=np.int64(net.iteration), **flat)
    sync_global_devices("done")
    print(f"WORKER_OK pid={pid} score={net.score_:.6f} "
          f"iters={net.iteration} ring=ok")


if __name__ == "__main__":
    main()
