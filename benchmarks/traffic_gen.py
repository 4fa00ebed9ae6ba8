"""The one general generator: reads a mix's data file and makes, from the
seed, the host batches a training job feeds.

A mix names its `runner` and, for the `fit` runner:

    pool_batches   how many distinct host batches the job cycles through
    warmup_steps   steps of the first `fit()`, outside the window

The shapes are the configuration's: `input_shape` and `label_shape`, of
one row. Features are float32 of mean 0 and variance 1 (uniform); labels
are float32 one-hot over the last axis of `label_shape`, so [1000] is a
class per image and [T, 64] a class per time step.

Batch i of a seed is always the same array, whatever the pool's size, so
the reference (which makes only the first few) and the job agree.
"""

from __future__ import annotations

import math
from concurrent.futures import ThreadPoolExecutor

import numpy as np


def make_batch(config: dict, seed: int, index: int, rows: int):
    rng = np.random.default_rng([int(seed), int(index)])
    x = rng.random((rows, *config["input_shape"]), dtype=np.float32)
    x -= np.float32(0.5)
    x *= np.float32(math.sqrt(12.0))
    *steps, classes = config["label_shape"]
    hot = rng.integers(0, classes, (rows, *steps))
    y = np.zeros((rows, *steps, classes), np.float32)
    np.put_along_axis(y, hot[..., None], 1.0, axis=-1)
    return x, y


def make_pool(traffic: dict, config: dict, seed: int, rows: int,
              count: int | None = None) -> list:
    """`count` batches (the mix's pool by default), made on a few threads:
    numpy's generators release the GIL."""
    n = int(traffic["pool_batches"] if count is None else count)
    with ThreadPoolExecutor(max_workers=min(4, n)) as pool:
        return list(pool.map(
            lambda i: make_batch(config, seed, i, rows), range(n)))
