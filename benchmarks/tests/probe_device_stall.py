#!/usr/bin/env python3
"""Does the chip stall under no benchmark at all? By hand, on the chip:

    chiprun -- python3 benchmarks/tests/probe_device_stall.py none 18

One process, `rounds` rounds of 14 s: a jitted 8192^3 bf16 product, blocked
on each time, while a thread touches a fresh 77 MB host array every 50 ms
as the fit loop's feed does. Every gap over 0.7 s between two returns is
printed with its place in the round. `asarray` first reads 550 MB of
float32 from the device into numpy each round (what set-up does since
PR 28); `none` reads nothing. PR 28 saw `vgg16_fit` windows of 19.2 and
22.8 s instead of 11.76 (2 of 20 runs of the change, 0 of 7 of the parent)
and suspected its own device-to-host reads; an earlier form of this probe
(three arrays a read) cleared them: over 18 rounds each `none` stalled once for 12.67 s in the device loop with the
feed thread running on, `asarray` never (my chip runs, PR 28).
"""

import json
import sys
import threading
import time

import numpy as np


def main(variant: str, rounds: int) -> int:
    import jax
    import jax.numpy as jnp

    print(json.dumps({"variant": variant,
                      "device": jax.devices()[0].device_kind}), flush=True)
    make = jax.jit(lambda k: jax.random.normal(k, (33280, 4096), jnp.float32))
    work = jax.jit(lambda a: (a @ a).sum())
    a = jnp.ones((8192, 8192), jnp.bfloat16)
    work(a).block_until_ready()
    gaps, t_round, stop = [], [time.perf_counter()], threading.Event()

    def watch(who, last):
        now = time.perf_counter()
        if now - last > 0.7:
            gaps.append([who, round(now - t_round[0], 2),
                         round(now - last, 2)])
        return now

    def feed():
        last = time.perf_counter()
        while not stop.is_set():
            np.ones((77_000_000 // 4,), np.float32).__imul__(2)
            time.sleep(0.05)
            last = watch("feed", last)

    thread = threading.Thread(target=feed, daemon=True)
    thread.start()
    for r in range(rounds):
        tree = make(jax.random.PRNGKey(r)).block_until_ready()
        t_round[0] = time.perf_counter()
        if variant == "asarray":
            np.asarray(tree)
        del tree
        last = time.perf_counter()
        while time.perf_counter() - t_round[0] < 14.0:
            work(a).block_until_ready()
            last = watch("device", last)
        print(json.dumps({"round": r, "gaps": gaps}), flush=True)
        gaps.clear()
    stop.set()
    thread.join(timeout=5)
    return 0


if __name__ == "__main__":
    sys.exit(main(sys.argv[1], int(sys.argv[2])))
