"""Generator `dense_one_hot`: real features and one-hot labels, for the
nets that take images or dense sequences.

The shapes are the configuration's `input_shape` and `label_shape`, of one
row. Features are float32 of mean 0 and variance 1 (uniform); labels are
float32 one-hot over the last axis of `label_shape`, so [1000] is a class
per image and [T, 64] a class per time step.
"""

from __future__ import annotations

import math

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
