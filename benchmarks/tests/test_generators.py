"""The batch makers of `benchmarks/generators/`, found by the name a
configuration gives: `dense_one_hot` makes the bytes the one generator made
before it became a file, `token_stream` makes ids and next-token targets."""

import hashlib

import numpy as np
import pytest

from benchmarks import harness, traffic_gen

# sha256 of x.tobytes() + y.tobytes() of 4 rows of seed 7, from
# `traffic_gen.make_batch` of the parent (PR 26's tree); the two accepted
# configurations have the same shapes, so the same bytes
PARENT = {0: "02bcd2eb944e7256a2ef4295f122be4993e6297cd502a3d03f114adca68cd6d7",
          5: "32703a5d8a00749979e36fe7e8a28fd16288f4a46f9b10811e3001ad1cd7537c"}
TOKENS = harness.load_json("tests", "configs", "tokens_tiny.json")


@pytest.mark.parametrize("config", ["resnet50", "vgg16"])
@pytest.mark.parametrize("index", sorted(PARENT))
def test_dense_one_hot_makes_the_parents_bytes(config, index):
    config = harness.load_json("configs", config + ".json")
    assert "generator" not in config        # the default is this one
    pool = traffic_gen.make_pool({"pool_batches": index + 1}, config, 7, 4)
    x, y = pool[index]
    assert x.dtype == y.dtype == np.float32
    assert x.shape == (4, 224, 224, 3) and y.shape == (4, 1000)
    assert hashlib.sha256(x.tobytes() + y.tobytes()).hexdigest() \
        == PARENT[index]


def test_token_stream_makes_ids_and_next_token_targets():
    pool = traffic_gen.make_pool({"pool_batches": 3}, TOKENS, 2 ** 31 + 9, 8)
    for x, y in pool:
        assert x.dtype == y.dtype == np.int32
        assert x.shape == y.shape == (8, TOKENS["input_shape"][0])
        assert x.min() >= 0 and y.min() >= 0
        assert max(x.max(), y.max()) < TOKENS["vocabulary_held"]
        assert (y[:, :-1] == x[:, 1:]).all()
    assert not (pool[0][0] == pool[1][0]).all()
    assert len({int(v) for v in pool[0][0].ravel()}) > 100   # not constant


def test_a_batch_does_not_depend_on_the_pools_size():
    small = traffic_gen.make_pool({"pool_batches": 2}, TOKENS, 11, 8)
    large = traffic_gen.make_pool({"pool_batches": 8}, TOKENS, 11, 8,
                                  count=5)
    assert len(large) == 5
    for (x, y), (x2, y2) in zip(small, large):
        assert (x == x2).all() and (y == y2).all()


def test_an_unknown_generator_is_an_error():
    with pytest.raises(FileNotFoundError):
        traffic_gen.make_pool({"pool_batches": 1},
                              dict(TOKENS, generator="no_such"), 1, 2)
