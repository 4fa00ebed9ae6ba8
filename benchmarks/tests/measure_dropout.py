#!/usr/bin/env python3
"""What `vgg16`'s one departure from its source costs (by hand, on the
chip; no test calls it):

    chiprun -- python3 benchmarks/tests/measure_dropout.py --seed 7 \
        --seconds 10

One run of `vgg16_fit` as `run.py` drives it, but with the source's
dropout of 0.5 before both 4096-wide layers left on. Its `correct` is
false by construction (the reference draws no masks) and means nothing;
read `train_items_per_s` beside a run of the cell as committed.
"""

import argparse
import json
import os
import sys

sys.path.insert(0, os.path.dirname(os.path.dirname(os.path.dirname(
    os.path.abspath(__file__)))))

from benchmarks import harness  # noqa: E402
from benchmarks import run as bench_run  # noqa: E402


SOURCE_DROPOUT = 0.5     # arXiv:1409.1556 section 3.1; zoo.VGG16


def main(argv=None) -> int:
    ap = argparse.ArgumentParser()
    ap.add_argument("--seed", type=int, required=True)
    ap.add_argument("--seconds", type=float, required=True)
    args = ap.parse_args(argv)
    with open(os.path.join(harness.ROOT, "BENCHMARK.json")) as fh:
        cell = bench_run.load_cell(json.load(fh), "vgg16_fit")
    cell["config_data"]["dropout"] = SOURCE_DROPOUT
    result = bench_run.run_cell(cell, seed=args.seed, seconds=args.seconds,
                                trace=False)
    print(json.dumps({"dropout": SOURCE_DROPOUT, **result}), flush=True)
    return 0


if __name__ == "__main__":
    sys.exit(main())
