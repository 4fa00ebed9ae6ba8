#!/usr/bin/env python3
"""How many routed pairs fall elsewhere when the router reads bfloat16
activations (by hand, on the chip; no test calls it):

    chiprun -- python3 benchmarks/tests/measure_routing.py --seeds 2

Routing is a discrete choice. The program routes in float32 from bf16
activations, the reference from float32 ones, so near a tie a token's
fourth expert differs. This follows the plain reference of
`trinity_large` at the cell's own size twice, in "float32" and in
"bfloat16" mode (operands rounded as the program's are), and counts, for
every expert layer, the (token, expert) pairs on experts HELD that one
has and the other has not. Prints a line a seed and layer.
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
    ap.add_argument("--config", default="benchmarks/configs/trinity_large.json")
    ap.add_argument("--seeds", type=int, default=2)
    ap.add_argument("--first-seed", type=int, default=2 ** 31 + 11)
    args = ap.parse_args(argv)
    import jax
    import jax.numpy as jnp

    cfg = harness.load_json(harness.ROOT, args.config)
    traffic = harness.load_json("traffic", "fit_stream.json")
    ref = harness.load_module("reference", cfg["reference"] + ".py")
    harness.enable_compile_cache()
    first, count = cfg["experts_held"]
    dtype = jnp.dtype(cfg["dtype"])

    @jax.jit
    def pairs(params, x):
        """Per expert layer: held pairs in float32, in bfloat16, and those
        in one and not the other."""
        out = []
        for i in range(cfg["num_dense_layers"], cfg["num_hidden_layers"]):
            name = f"layer{i + 1}_sandwichtransformerblock"
            held = []
            for mode in ("float32", "bfloat16"):
                h = ref.hidden_states(params, x, cfg, mode, upto=i)[0]
                h = h + ref._norm(ref._attention(
                    params[name], ref._norm(h, params[name]["ln1_g"],
                                            cfg["rms_norm_eps"]),
                    cfg, cfg["layer_types"][i], mode),
                    params[name]["ln2_g"], cfg["rms_norm_eps"])
                b = ref._norm(h, params[name]["ln3_g"], cfg["rms_norm_eps"])
                if mode == "bfloat16":
                    b = b.astype(jnp.bfloat16).astype(jnp.float32)
                sel, _ = ref.route(params[name], b, cfg)
                chosen = jnp.zeros((b.shape[0], cfg["num_experts"]), bool)
                chosen = chosen.at[jnp.arange(b.shape[0])[:, None],
                                   sel].set(True)
                held.append(chosen[:, first:first + count])
            out.append((held[0].sum(), held[1].sum(),
                        (held[0] ^ held[1]).sum()))
        return out

    for s in range(args.seeds):
        seed = args.first_seed + 7919 * s
        params = jax.tree_util.tree_map(
            lambda a: a.astype(dtype).astype(jnp.float32),
            ref.init_params(seed, cfg))
        x, _ = traffic_gen.make_pool(traffic, cfg, seed,
                                     cfg["batch_per_chip"], count=1)[0]
        for layer, (a, b, d) in enumerate(pairs(params, jnp.asarray(x)),
                                          start=cfg["num_dense_layers"] + 1):
            print(json.dumps({"seed": seed, "layer": layer,
                              "held_pairs_float32": int(a),
                              "held_pairs_bfloat16": int(b),
                              "in_one_only": int(d)}), flush=True)
        # off the device before the next seed's: two do not fit
        for leaf in jax.tree_util.tree_leaves(params):
            leaf.delete()
        del params
    return 0


if __name__ == "__main__":
    sys.exit(main())
