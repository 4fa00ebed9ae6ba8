#!/usr/bin/env python3
"""How many routed pairs fall elsewhere when a group-limited router reads
bfloat16 activations (by hand, on the chip; no test calls it):

    chiprun -- python3 benchmarks/tests/measure_grouped_routing.py --seeds 2

`measure_routing.py` is `trinity_large`'s (one flat choice, its own block).
Here a token makes two discrete choices, 3 of 8 groups and then 6 experts
inside them, and either can fall the other side of a tie. The program
routes in float32 from bf16 activations, the reference from float32 ones.
This follows the plain reference of `deepseek_v2` at the cell's own size
twice, in "float32" and in "bfloat16" mode (operands rounded as the
program's are), and counts, for every expert layer: the (token, expert)
pairs on experts HELD that one has and the other has not, the tokens whose
kept GROUPS differ, and the tokens that reach an expert held in one and not
in the other. Prints a line a seed and layer.
"""

import argparse
import json
import os
import sys

sys.path.insert(0, os.path.dirname(os.path.dirname(os.path.dirname(
    os.path.abspath(__file__)))))

from benchmarks import harness, traffic_gen  # noqa: E402


def main(argv=None) -> int:
    ap = argparse.ArgumentParser()
    ap.add_argument("--config", default="benchmarks/configs/deepseek_v2.json")
    ap.add_argument("--seeds", type=int, default=2)
    ap.add_argument("--first-seed", type=int, default=2 ** 31 + 11)
    args = ap.parse_args(argv)
    import jax
    import jax.numpy as jnp

    cfg = harness.load_json(harness.ROOT, args.config)
    traffic = harness.load_json("traffic", "fit_stream.json")
    ref = harness.load_module("reference", cfg["reference"] + ".py")
    harness.enable_compile_cache()
    first, count = ref.experts_held(cfg)
    e, groups = cfg["n_routed_experts"], cfg["n_group"]
    dtype = jnp.dtype(cfg["dtype"])

    @jax.jit
    def pairs(params, x):
        out = []
        for i in range(cfg["first_k_dense_replace"],
                       cfg["num_hidden_layers"]):
            p = params[f"layer{i + 1}_prenormblock"]
            held, kept = [], []
            for mode in ("float32", "bfloat16"):
                h = ref.hidden_states(params, x, cfg, mode, upto=i)[0]
                h = h + ref.mla(p, ref._norm(h, p["ln1_g"],
                                             cfg["rms_norm_eps"]), cfg, mode)
                b = ref._norm(h, p["ln2_g"], cfg["rms_norm_eps"])
                if mode == "bfloat16":
                    b = b.astype(jnp.bfloat16).astype(jnp.float32)
                sel, _ = ref.route(p, b, cfg)
                chosen = jnp.zeros((b.shape[0], e), bool)
                chosen = chosen.at[jnp.arange(b.shape[0])[:, None],
                                   sel].set(True)
                held.append(chosen[:, first:first + count])
                kept.append(jnp.any(chosen.reshape(-1, groups, e // groups),
                                    axis=-1))
            out.append((held[0].sum(), held[1].sum(),
                        (held[0] ^ held[1]).sum(),
                        jnp.any(kept[0] ^ kept[1], axis=-1).sum(),
                        (held[0].any(-1) ^ held[1].any(-1)).sum()))
        return out

    for s in range(args.seeds):
        seed = args.first_seed + 7919 * s
        params = jax.tree_util.tree_map(
            lambda a: a.astype(dtype).astype(jnp.float32),
            ref.init_params(seed, cfg))
        x, _ = traffic_gen.make_pool(traffic, cfg, seed,
                                     cfg["batch_per_chip"], count=1)[0]
        for layer, row in enumerate(pairs(params, jnp.asarray(x)),
                                    start=cfg["first_k_dense_replace"] + 1):
            a, b, d, g, r = map(int, row)
            print(json.dumps({"seed": seed, "layer": layer,
                              "held_pairs_float32": a,
                              "held_pairs_bfloat16": b, "in_one_only": d,
                              "tokens_whose_groups_differ": g,
                              "tokens_reaching_in_one_only": r}), flush=True)
        # off the device before the next seed's: two do not fit
        for leaf in jax.tree_util.tree_leaves(params):
            leaf.delete()
        del params
    return 0


if __name__ == "__main__":
    sys.exit(main())
