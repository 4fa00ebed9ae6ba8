#!/usr/bin/env python3
"""How many chosen blocks fall elsewhere when the selection reads bfloat16
activations (by hand, on the chip; no test calls it):

    chiprun -- python3 benchmarks/tests/measure_selection.py --seeds 2

The block selection of a `minicpm4` layer is a discrete choice. The
program scores its blocks from bf16 queries and keys, the reference from
float32 ones, so near a tie a token's sixty-fourth block differs. This
follows the plain reference of `minicpm_sala` at the cell's own size up to
the selecting layer's q and k twice, in "float32" and in "bfloat16" mode
(operands rounded as the program's are, and q and k themselves rounded to
bf16 before the scores), and counts the (token, KV group, block) visits
that one has and the other has not. Prints a line a seed.
"""

import argparse
import json
import os
import sys

sys.path.insert(0, os.path.dirname(os.path.dirname(os.path.dirname(
    os.path.abspath(__file__)))))

from benchmarks import harness, traffic_gen  # noqa: E402

ROWS = 128


def main(argv=None) -> int:
    ap = argparse.ArgumentParser()
    ap.add_argument("--config", default="benchmarks/configs/minicpm_sala.json")
    ap.add_argument("--seeds", type=int, default=2)
    ap.add_argument("--first-seed", type=int, default=2 ** 31 + 11)
    args = ap.parse_args(argv)
    import jax
    import jax.numpy as jnp

    cfg = harness.load_json(harness.ROOT, args.config)
    traffic = harness.load_json("traffic", "fit_stream.json")
    ref = harness.load_module("reference", cfg["reference"] + ".py")
    harness.enable_compile_cache()
    dtype = jnp.dtype(cfg["dtype"])
    sizes = ref.sparse_sizes(cfg)
    h, hkv, dh = (cfg["num_attention_heads"], cfg["num_key_value_heads"],
                  cfg["head_dim"])
    t = cfg["input_shape"][0]

    @jax.jit
    def visits(params, x):
        """Per selecting layer: visits in float32, in bfloat16, and those
        in one and not the other."""
        out = []
        for i, kind in enumerate(cfg["mixer_types"]):
            if kind != "minicpm4" or t <= sizes["dense_len"]:
                continue
            p = params[f"layer{i + 1}_prenormblock"]
            chosen = []
            for mode in ("float32", "bfloat16"):
                hid = ref.hidden_states(params, x, cfg, mode, upto=i)[0]
                a = ref._norm(hid, p["ln1_g"], cfg["rms_norm_eps"])
                q, k, _, _ = ref._qkv(p, a, h, hkv, dh, cfg["rms_norm_eps"],
                                      mode)
                if mode == "bfloat16":
                    q, k = (z.astype(jnp.bfloat16).astype(jnp.float32)
                            for z in (q, k))
                chosen.append(jax.lax.map(
                    lambda args: ref.chosen_blocks(args[0], k, args[1],
                                                   sizes, dh ** -0.5),
                    (q.reshape(t // ROWS, ROWS, h, dh),
                     jnp.arange(t).reshape(t // ROWS, ROWS))))
            out.append((chosen[0].sum(), chosen[1].sum(),
                        (chosen[0] ^ chosen[1]).sum()))
        return out

    for s in range(args.seeds):
        seed = args.first_seed + 7919 * s
        params = jax.tree_util.tree_map(
            lambda a: a.astype(dtype).astype(jnp.float32),
            ref.init_params(seed, cfg))
        x, _ = traffic_gen.make_pool(traffic, cfg, seed,
                                     cfg["batch_per_chip"], count=1)[0]
        for a, b, d in visits(params, jnp.asarray(x)):
            print(json.dumps({"seed": seed, "visits_float32": int(a),
                              "visits_bfloat16": int(b),
                              "in_one_only": int(d)}), flush=True)
        # off the device before the next seed's: two do not fit
        for leaf in jax.tree_util.tree_leaves(params):
            leaf.delete()
        del params
    return 0


if __name__ == "__main__":
    sys.exit(main())
