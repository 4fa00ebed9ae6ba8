"""The comparison that decides `correct` for a training cell.

Each number compared has a limit of its own (`benchmarks/limits/<cell>.json`,
with the readings it was set from in PERF.md). Norms are compared by the
worst leaf: the gap between the program's norm and the reference's, not the
norm of their difference, against the reference's norm of that leaf or of
the median leaf, whichever is larger, since some gradients are all but zero.
"""

from __future__ import annotations

import math
import statistics


def loss_gap(program, reference) -> float:
    """Largest |program - reference| / |reference| over the steps."""
    if len(program) != len(reference) or not reference:
        return math.inf
    gaps = [abs(p - r) / abs(r) for p, r in zip(program, reference)]
    return max(g if math.isfinite(g) else math.inf for g in gaps)


def worst_leaf_gap(program: dict, reference: dict):
    """(gap, leaf) of the leaf whose norm is farthest from the reference's."""
    floor = statistics.median(reference.values())
    worst, where = 0.0, None
    for leaf, ref in reference.items():
        got = program.get(leaf, math.nan)
        gap = abs(got - ref) / max(ref, floor, 1e-30)
        if not math.isfinite(gap):
            gap = math.inf
        if gap >= worst:
            worst, where = gap, leaf
    if set(program) - set(reference):
        return math.inf, sorted(set(program) - set(reference))[0]
    return worst, where


def leaf_diff_shares(program: dict, reference: dict) -> dict:
    """Per leaf, |g_program - g_reference| / |g_reference| over the sampled
    elements; leaves whose reference gradient is exactly zero are left out
    (the branch kernels of a residual block whose last scale is 0: none
    at the committed `residual_gamma`)."""
    import numpy as np

    out = {}
    for leaf, ref in reference.items():
        ref = np.asarray(ref, np.float64)
        got = np.asarray(program[leaf], np.float64)
        den = float(np.sum(np.square(ref)))
        if den > 0:
            share = math.sqrt(float(np.sum(np.square(got - ref))) / den)
            out[leaf] = share if math.isfinite(share) else math.inf
        elif float(np.sum(np.square(got))) > 0:
            out[leaf] = math.inf
    return out


def grad_diff_share(program: dict, reference: dict, weights: dict):
    """(median leaf, whole gradient) of |g_program - g_reference| /
    |g_reference|. The one number of a DIFFERENCE: the gaps between norms
    move with the square of a rounding error, this moves with the error
    itself, so it is what tells one precision from the next. The median
    over leaves is the value compared: a few leaves' gradients are small
    residues of large terms that cancel (the first batch norm's shift reads
    10 times its own length off in bfloat16) and swing the whole-gradient
    share from seed to seed, which is printed beside it."""
    import numpy as np

    if set(program) != set(reference) or any(
            np.shape(program[k]) != np.shape(reference[k])
            for k in reference):
        return math.inf, math.inf
    shares = leaf_diff_shares(program, reference)
    if not shares:
        return math.inf, math.inf
    num = den = 0.0
    for leaf, ref in reference.items():
        ref = np.asarray(ref, np.float64)
        got = np.asarray(program[leaf], np.float64)
        num += weights[leaf] * float(np.sum(np.square(got - ref)))
        den += weights[leaf] * float(np.sum(np.square(ref)))
    whole = math.sqrt(num / den) if den > 0 else math.inf
    return statistics.median(shares.values()), whole


def whole_norm_gap(program: dict, reference: dict) -> float:
    """|norm of all leaves together: program - reference| / reference."""
    def whole(norms):
        return math.sqrt(sum(v * v for v in norms.values()))

    if set(program) != set(reference) or whole(reference) == 0:
        return math.inf
    gap = abs(whole(program) - whole(reference)) / whole(reference)
    return gap if math.isfinite(gap) else math.inf


def first_steps(program: dict, reference: dict) -> dict:
    """The numbers compared, by name, from two `follow`-shaped dicts.

    The parameters' change is compared as ONE norm over all leaves, not by
    the worst leaf: the configurations keep their parameters in bfloat16,
    where a leaf near 1.0 (a batch-norm scale) or a wide dense kernel does
    not move at all under an update below its half-ulp, so the worst
    leaf of a sound run already reads what the fault reads, 1.0 (chip
    runs, PR 24). The worst leaf is printed beside it, with no limit."""
    grad, grad_leaf = worst_leaf_gap(program["grad_norm"],
                                     reference["grad_norm"])
    delta, delta_leaf = worst_leaf_gap(program["delta_norm"],
                                       reference["delta_norm"])
    return {
        "loss_gap": {"value": loss_gap(program["loss"], reference["loss"])},
        "grad_norm_gap": {"value": grad, "leaf": grad_leaf},
        "delta_norm_gap": {
            "value": whole_norm_gap(program["delta_norm"],
                                    reference["delta_norm"]),
            "worst_leaf": delta, "leaf": delta_leaf},
        "grad_diff_share": dict(zip(("value", "whole_gradient"),
                                    grad_diff_share(
            program["grad_sample"], reference["grad_sample"],
            reference["grad_sample_weight"]))),
    }


def judge(numbers: dict, limits: dict) -> bool:
    """Attach each limit, print-ready, and say whether all hold."""
    ok = True
    for name, row in numbers.items():
        if name not in limits:
            raise KeyError(f"no limit for {name!r}")
        row["limit"] = limits[name]
        row["holds"] = bool(row["value"] <= limits[name])
        ok = ok and row["holds"]
    return ok
