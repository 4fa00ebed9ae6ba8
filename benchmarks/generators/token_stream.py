"""Generator `token_stream`: integer ids and next-token targets, for a
language model's training cell.

A row is `T + 1` ids drawn uniformly over the configuration's
`vocabulary_held` (`T = input_shape[0]`): `x` is the first `T`, `y` the
last `T`, both int32 `[rows, T]`. Documents are concatenated as
pretraining does, so every position has a target, there is no mask, and
the batch stays a pair.
"""

from __future__ import annotations

import numpy as np


def make_batch(config: dict, seed: int, index: int, rows: int):
    rng = np.random.default_rng([int(seed), int(index)])
    ids = rng.integers(0, int(config["vocabulary_held"]),
                       (rows, int(config["input_shape"][0]) + 1),
                       dtype=np.int32)
    return ids[:, :-1], ids[:, 1:]
