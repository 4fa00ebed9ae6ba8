"""Full-batch solvers: backtracking line search, nonlinear conjugate
gradient, and L-BFGS.

Reference parity: `optimize/Solver.java:43-64`, `optimize/solvers/
{ConjugateGradient,LBFGS,BackTrackLineSearch}.java` + `BaseOptimizer.java`.
The reference drives these eagerly (one ND4J op at a time, line-search
probes as separate host round-trips); here each solver is ONE jittable
computation over the raveled parameter vector — the whole iteration loop,
line-search probes included, traces into a single XLA program
(`lax.scan` over iterations, `lax.while_loop` for the backtracking), so a
full optimize() is a single device dispatch.

These are batch methods: the loss closure must be deterministic (no
dropout rng), matching the reference's use (full-batch second-order-ish
optimization, e.g. small-data scientific fits and t-SNE's internal
optimizer).
"""

from __future__ import annotations

from typing import Callable, NamedTuple

import jax
import jax.numpy as jnp
from jax import lax
from jax.flatten_util import ravel_pytree


def backtrack_line_search(f: Callable[[jnp.ndarray], jnp.ndarray],
                          x: jnp.ndarray, f0, g: jnp.ndarray,
                          d: jnp.ndarray, *, initial_step: float = 1.0,
                          c: float = 1e-4, rho: float = 0.5,
                          max_steps: int = 20):
    """Armijo backtracking: largest alpha in {s, s*rho, s*rho^2, ...} with
    f(x + alpha d) <= f0 + c * alpha * g.d. Returns (alpha, f_new); alpha=0
    (no step) when no trial satisfies the condition — the reference's
    BackTrackLineSearch.java:48 bails the same way after maxIterations.
    Jittable: the probe loop is a `lax.while_loop`."""
    gd = jnp.vdot(g, d)

    def cond(carry):
        alpha, fval, it = carry
        return jnp.logical_and(it < max_steps, fval > f0 + c * alpha * gd)

    def body(carry):
        alpha, _, it = carry
        alpha = alpha * rho
        return alpha, f(x + alpha * d), it + 1

    alpha0 = jnp.asarray(initial_step, x.dtype)
    alpha, fval, it = lax.while_loop(
        cond, body, (alpha0, f(x + alpha0 * d), jnp.asarray(0)))
    ok = fval <= f0 + c * alpha * gd
    return jnp.where(ok, alpha, 0.0), jnp.where(ok, fval, f0)


class SolverResult(NamedTuple):
    x: jnp.ndarray           # final parameter vector
    loss: jnp.ndarray        # final loss
    history: jnp.ndarray     # per-iteration loss trajectory [iterations]


def minimize_cg(f: Callable, x0: jnp.ndarray, *, iterations: int = 100,
                max_line_search: int = 20) -> SolverResult:
    """Polak-Ribiere+ nonlinear conjugate gradient with Armijo line search
    and automatic restart (beta clamped at 0, direction reset when not a
    descent direction). Reference: `optimize/solvers/ConjugateGradient.java`
    (same PR formula + restart-on-non-descent)."""
    vg = jax.value_and_grad(f)
    f0, g0 = vg(x0)

    def step(carry, _):
        x, fval, g, d = carry
        # normalize direction scale so initial_step=1 probes a sane range
        dnorm = jnp.linalg.norm(d)
        d_unit = d / jnp.maximum(dnorm, 1e-12)
        alpha, fnew = backtrack_line_search(
            f, x, fval, g, d_unit, max_steps=max_line_search)
        x_new = x + alpha * d_unit
        fnew, g_new = vg(x_new)
        beta = jnp.maximum(
            jnp.vdot(g_new, g_new - g) / jnp.maximum(jnp.vdot(g, g), 1e-30),
            0.0)  # PR+
        d_new = -g_new + beta * d
        # restart with steepest descent if d_new isn't a descent direction
        d_new = jnp.where(jnp.vdot(d_new, g_new) < 0, d_new, -g_new)
        return (x_new, fnew, g_new, d_new), fnew

    (x, fval, _, _), hist = lax.scan(
        step, (x0, f0, g0, -g0), None, length=iterations)
    return SolverResult(x, fval, hist)


def minimize_lbfgs(f: Callable, x0: jnp.ndarray, *, iterations: int = 100,
                   history: int = 10,
                   max_line_search: int = 20) -> SolverResult:
    """L-BFGS with the standard two-loop recursion over a circular (s, y)
    history and Armijo backtracking. Reference:
    `optimize/solvers/LBFGS.java` (m=4 default there; 10 here).
    Fixed-size buffers keep everything jit-compatible."""
    vg = jax.value_and_grad(f)
    n = x0.shape[0]
    m = history
    f0, g0 = vg(x0)

    S0 = jnp.zeros((m, n), x0.dtype)
    Y0 = jnp.zeros((m, n), x0.dtype)
    rho0 = jnp.zeros((m,), x0.dtype)

    def two_loop(g, S, Y, rho, k):
        """Standard two-loop recursion; entries with rho==0 are inactive."""
        def bwd(i, carry):
            q, a = carry
            idx = jnp.mod(k - 1 - i, m)
            ai = rho[idx] * jnp.vdot(S[idx], q)
            ai = jnp.where(rho[idx] > 0, ai, 0.0)
            q = q - ai * Y[idx]
            return q, a.at[idx].set(ai)

        q, a = lax.fori_loop(0, m, bwd, (g, jnp.zeros((m,), g.dtype)))
        # initial Hessian scaling gamma = s.y / y.y of the newest pair
        newest = jnp.mod(k - 1, m)
        sy = jnp.vdot(S[newest], Y[newest])
        yy = jnp.vdot(Y[newest], Y[newest])
        gamma = jnp.where(yy > 0, sy / jnp.maximum(yy, 1e-30), 1.0)
        r = gamma * q

        def fwd(i, r):
            idx = jnp.mod(k - m + i, m)
            bi = rho[idx] * jnp.vdot(Y[idx], r)
            corr = (a[idx] - bi) * S[idx]
            return r + jnp.where(rho[idx] > 0, corr, 0.0)

        return lax.fori_loop(0, m, fwd, r)

    def step(carry, _):
        x, fval, g, S, Y, rho, k = carry
        d = -two_loop(g, S, Y, rho, k)
        # fall back to steepest descent if not a descent direction
        d = jnp.where(jnp.vdot(d, g) < 0, d, -g)
        alpha, _ = backtrack_line_search(
            f, x, fval, g, d, max_steps=max_line_search)
        x_new = x + alpha * d
        fnew, g_new = vg(x_new)
        s = x_new - x
        y = g_new - g
        sy = jnp.vdot(s, y)
        # curvature condition: only store useful pairs
        store = sy > 1e-10
        idx = jnp.mod(k, m)
        S = jnp.where(store, S.at[idx].set(s), S)
        Y = jnp.where(store, Y.at[idx].set(y), Y)
        rho = jnp.where(store, rho.at[idx].set(1.0 / jnp.maximum(sy, 1e-30)),
                        rho)
        k = jnp.where(store, k + 1, k)
        return (x_new, fnew, g_new, S, Y, rho, k), fnew

    (x, fval, *_), hist = lax.scan(
        step, (x0, f0, g0, S0, Y0, rho0, jnp.asarray(0)), None,
        length=iterations)
    return SolverResult(x, fval, hist)


def minimize_gd(f: Callable, x0: jnp.ndarray, *, iterations: int = 100,
                max_line_search: int = 20) -> SolverResult:
    """Line (steepest) gradient descent — gradient direction + line search.
    Reference: `optimize/solvers/LineGradientDescent.java`."""
    vg = jax.value_and_grad(f)
    f0, g0 = vg(x0)

    def step(carry, _):
        x, fval, g = carry
        d = -g / jnp.maximum(jnp.linalg.norm(g), 1e-12)
        alpha, _ = backtrack_line_search(
            f, x, fval, g, d, max_steps=max_line_search)
        x_new = x + alpha * d
        fnew, g_new = vg(x_new)
        return (x_new, fnew, g_new), fnew

    (x, fval, _), hist = lax.scan(step, (x0, f0, g0), None, length=iterations)
    return SolverResult(x, fval, hist)


_ALGOS = {
    "conjugate_gradient": minimize_cg,
    "cg": minimize_cg,
    "lbfgs": minimize_lbfgs,
    "line_gradient_descent": minimize_gd,
}


class Solver:
    """Model-level solver driver. Reference: `optimize/Solver.java` —
    builds the optimizer for the model's configured algorithm and runs
    `optimize()` against one (full) batch.

    The model's parameter pytree is raveled into one flat vector (the
    moral equivalent of the reference's flattened params view,
    `MultiLayerNetwork.params()`), minimized, and written back."""

    def __init__(self, model, algo: str = "lbfgs", *, iterations: int = 100,
                 history: int = 10):
        if algo not in _ALGOS:
            raise ValueError(
                f"Unknown solver algorithm {algo!r}; known: {sorted(_ALGOS)}")
        self.model = model
        self.algo = algo
        self.iterations = iterations
        self.history = history
        self._jitted = None
        self._refresh = None

    def optimize(self, features, labels, fmask=None, lmask=None):
        """Run the configured solver to convergence on ONE batch; returns
        the loss trajectory. Deterministic loss (no dropout)."""
        model = self.model
        x0, unravel = ravel_pytree(model.params_tree)
        if features is not None and not isinstance(features,
                                                   (list, tuple, dict)):
            features = jnp.asarray(features)
        if labels is not None and not isinstance(labels, (list, tuple, dict)):
            labels = jnp.asarray(labels)

        minimize = _ALGOS[self.algo]
        kw = {"iterations": self.iterations}
        if self.algo == "lbfgs":
            kw["history"] = self.history

        if self._jitted is None:
            # Masks/states are jit ARGUMENTS (None is a valid empty pytree),
            # not closure captures — each batch's masks and the current BN
            # state are honored, and shape changes retrace naturally.
            def run(flat, feats, labs, fm, lm, states):
                def flat_loss(v):
                    loss, _ = model._loss(unravel(v), states, feats, labs,
                                          fm, lm, None, train=True)
                    return loss
                return minimize(flat_loss, flat, **kw)

            def refresh(flat, feats, labs, fm, lm, states):
                _, ns = model._loss(unravel(flat), states, feats, labs,
                                    fm, lm, None, train=True)
                return ns
            self._jitted = jax.jit(run)
            self._refresh = jax.jit(refresh)
        res = self._jitted(x0, features, labels, fmask, lmask,
                           model.state_tree)
        model.params_tree = unravel(res.x)
        # Persistent layer state (BN running mean/var): the reference's
        # solvers run a train-mode forward per iteration PLUS several
        # line-search probes, decay-blending running stats toward the
        # batch every time — so the blend sees ~4x `iterations` updates,
        # enough for the default 0.9 decay to converge (0.9^40 ≈ 1.5%).
        # Mirror that multiplicity (capped — geometric convergence).
        stateful = getattr(model, "_stateful", set())
        if stateful and model.state_tree:
            states = model.state_tree
            for _ in range(min(4 * self.iterations, 60)):
                ns = self._refresh(res.x, features, labels, fmask, lmask,
                                   states)
                states = {
                    # merged: a counter layer's sums are the step's to
                    # write and not in `ns`
                    n: ({**states[n], **ns[n]}
                        if n in stateful and n in ns else states[n])
                    for n in states
                }
            model.state_tree = states
        # graft: allow-sync(final loss readback, once per fit)
        model.score_ = float(res.loss)
        return res.history


def fit_with_solver(model, features, labels, fmasks=None, lmasks=None):
    """Shared non-SGD fit dispatch for MultiLayerNetwork/ComputationGraph:
    cache a Solver on the model (invalidated when the configured algorithm
    or iteration count changes), run one full-batch optimize, return the
    final loss."""
    conf = model.conf
    cached = model._solver
    if (cached is None or cached.algo != conf.optimization_algo
            or cached.iterations != conf.solver_iterations):
        model._solver = Solver(model, conf.optimization_algo,
                               iterations=conf.solver_iterations)
    hist = model._solver.optimize(features, labels, fmasks, lmasks)
    return float(hist[-1])
