#!/usr/bin/env python3
"""The plain reference as a process of its own.

`runners/fit.py` starts it BEFORE its own process touches JAX, waits for
it and reads its JSON file: a chip belongs to one process at a time, and
run this way the float32 reference may take the whole chip while
`memory_peak_bytes` of the run stays the program's. It makes the weights
and the first batches from the seed exactly as the runner does, follows
the steps in `--mode` and writes losses, per-leaf norms and its own peak of
device memory.

    python benchmarks/reference_main.py \
        --config benchmarks/configs/resnet50.json \
        --traffic benchmarks/traffic/fit_stream.json \
        --chips 1 --seed 7 --steps 3 --out ref.json
"""

from __future__ import annotations

import argparse
import json
import os
import sys
import time

sys.path.insert(0, os.path.dirname(os.path.dirname(os.path.abspath(__file__))))

from benchmarks import harness, traffic_gen  # noqa: E402


def reference_numbers(config: dict, traffic: dict, *, chips: int, seed: int,
                      steps: int, mode: str, devices=None) -> dict:
    """Follow `steps` steps of the cell on `devices` (the first `chips`
    of JAX's by default). The global batch is split over them by rows;
    batch statistics stay those of the whole batch."""
    import jax
    import jax.numpy as jnp
    import numpy as np
    from jax.sharding import Mesh, NamedSharding, PartitionSpec as P

    t0, phases = time.perf_counter(), {}
    ref = harness.load_module("reference", config["reference"] + ".py")
    follow = harness.load_module("reference", "follow.py")
    rule = harness.load_module("reference", "rules",
                               config["updater"]["rule"] + ".py")
    devices = list(devices or jax.devices()[:chips])
    mesh = Mesh(np.array(devices), ("data",))
    rows = NamedSharding(mesh, P("data"))
    whole = NamedSharding(mesh, P())
    dtype = jnp.dtype(config["dtype"])
    params = jax.tree_util.tree_map(
        lambda a: a.astype(dtype).astype(jnp.float32),
        ref.init_params(seed, config))
    params = jax.block_until_ready(jax.device_put(params, whole))
    phases["devices_and_weights"] = time.perf_counter() - t0
    batches = traffic_gen.make_pool(
        traffic, config, seed, config["batch_per_chip"] * chips, count=steps)
    phases["batches"] = time.perf_counter() - t0 - sum(phases.values())
    out = follow.follow(ref.loss_fn, rule, params, batches,
                        config["updater"], mode=mode,
                        shard=lambda a: jax.device_put(a, rows),
                        row_blocks=int(config.get("reference_row_blocks", 1)))
    phases["steps"] = time.perf_counter() - t0 - sum(phases.values())
    out["phases"] = phases
    return out


def main(argv=None) -> int:
    ap = argparse.ArgumentParser(description=__doc__.split("\n\n")[0])
    ap.add_argument("--config", required=True,
                    help="the configuration's file, from the checkout's root")
    ap.add_argument("--traffic", required=True, help="the mix's file")
    ap.add_argument("--chips", type=int, required=True)
    ap.add_argument("--seed", type=int, required=True)
    ap.add_argument("--steps", type=int, default=3)
    ap.add_argument("--mode", default="float32")
    ap.add_argument("--out", required=True)
    ap.add_argument("--require-chip", type=int, default=1)
    args = ap.parse_args(argv)
    t0 = time.perf_counter()
    config = harness.load_json(harness.ROOT, args.config)
    traffic = harness.load_json(harness.ROOT, args.traffic)
    harness.enable_compile_cache()
    if args.require_chip:
        try:
            harness.require_chips(args.chips)
        except harness.NoChip as e:
            print(f"reference: needs the chip: {e}", file=sys.stderr)
            return 2
    with harness.XlaLog() as xla:
        out = reference_numbers(config, traffic, chips=args.chips,
                                seed=args.seed, steps=args.steps,
                                mode=args.mode)
    import jax
    import numpy as np

    np.savez(args.out + ".npz", **out.pop("grad_sample"))
    out["memory_peak_bytes"] = harness.device_facts(
        jax.devices()[:args.chips])["memory_peak_bytes"]
    out["seconds"] = time.perf_counter() - t0
    out["phases"]["imports_and_cache"] = out["seconds"] - sum(
        out["phases"].values())
    out.update(xla.facts())
    with open(args.out, "w", encoding="utf-8") as fh:
        json.dump(out, fh)
    return 0


if __name__ == "__main__":
    sys.exit(main())
