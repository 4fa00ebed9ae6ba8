"""The one general generator: reads a mix's data file and makes, from the
seed, the host batches a training job feeds.

A mix names its `runner` and, for the `fit` runner:

    pool_batches   how many distinct host batches the job cycles through
    warmup_steps   steps of the first `fit()`, outside the window

What one batch holds is the configuration's to say: its `generator` names
a file of `benchmarks/generators/` (`dense_one_hot` where it names none),
which gives `make_batch(config, seed, index, rows) -> (x, y)` from the
configuration's `input_shape`, `label_shape` and whatever else it reads.

Batch i of a seed is always the same array, whatever the pool's size, so
the reference (which makes only the first few) and the job agree.
"""

from __future__ import annotations

from concurrent.futures import ThreadPoolExecutor

from benchmarks import harness


def make_pool(traffic: dict, config: dict, seed: int, rows: int,
              count: int | None = None) -> list:
    """`count` batches (the mix's pool by default), made on a few threads:
    numpy's generators release the GIL."""
    make_batch = harness.load_module(
        "generators", config.get("generator", "dense_one_hot") + ".py"
    ).make_batch
    n = int(traffic["pool_batches"] if count is None else count)
    with ThreadPoolExecutor(max_workers=min(4, n)) as pool:
        return list(pool.map(
            lambda i: make_batch(config, seed, i, rows), range(n)))
