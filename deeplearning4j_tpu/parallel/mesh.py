"""Device-mesh construction — and the ONE sharding spine.

The mesh is the TPU-native replacement for the reference's device zoo
(`ParallelWrapper.createZooIfNeccessary:539-553` pinning threads to GPUs via
AffinityManager): instead of N threads × N model replicas, ONE program is
compiled over a `jax.sharding.Mesh` and XLA lays collectives onto ICI.

Axis conventions (used by all trainers/rules in this package):
  data  — batch (data parallel)
  model — tensor parallel (hidden/feature dims)
  pipe  — pipeline stages
  seq   — sequence/context parallel (ring attention)
  expert — MoE expert parallel

This module is also the single OWNER of placement: `MeshContext` bundles
the mesh with one `ShardingRules` and derives every sharding the trainers
need (batch, params, optimizer state, replicated). Everything downstream
(`ParallelWrapper`, `TrainingExecutor`, `DevicePrefetchIterator`,
checkpoint restore) consumes the context instead of inventing its own
`NamedSharding`s — graft-lint GL501 flags `Mesh(...)`/`jax.devices()`
construction anywhere else.
"""

from __future__ import annotations

import contextlib
import dataclasses
import threading
from typing import Dict, Optional, Sequence

import jax
import numpy as np
from jax.sharding import Mesh, NamedSharding, PartitionSpec as P

AXIS_DATA = "data"
AXIS_MODEL = "model"
AXIS_PIPE = "pipe"
AXIS_SEQ = "seq"
AXIS_EXPERT = "expert"


@dataclasses.dataclass(frozen=True)
class MeshSpec:
    """Named axis sizes; -1 for one axis means 'all remaining devices'."""

    axes: Dict[str, int]

    def resolve(self, n_devices: int) -> Dict[str, int]:
        sizes = dict(self.axes)
        wild = [k for k, v in sizes.items() if v == -1]
        fixed = int(np.prod([v for v in sizes.values() if v != -1])) or 1
        if len(wild) > 1:
            raise ValueError("At most one axis may be -1")
        if wild:
            if n_devices % fixed:
                raise ValueError(
                    f"{n_devices} devices not divisible by fixed axes {sizes}")
            sizes[wild[0]] = n_devices // fixed
        total = int(np.prod(list(sizes.values())))
        if total != n_devices:
            raise ValueError(
                f"Mesh axes {sizes} use {total} devices but {n_devices} "
                f"are available")
        return sizes


def device_count() -> int:
    return jax.device_count()


def local_device_count() -> int:
    return jax.local_device_count()


def make_mesh(axes: Optional[Dict[str, int]] = None,
              devices: Optional[Sequence] = None) -> Mesh:
    """Build a Mesh. Default: 1-D data-parallel over all devices.

    On multi-host TPU slices, `jax.devices()` is globally ordered so the
    trailing mesh axes land on ICI-adjacent chips — put the
    highest-bandwidth-demand axis (model/seq) LAST, data FIRST so its
    collectives can ride DCN if the mesh spans slices (scaling-book recipe).
    """
    devices = list(devices if devices is not None else jax.devices())
    if axes is None:
        axes = {AXIS_DATA: len(devices)}
    sizes = MeshSpec(dict(axes)).resolve(len(devices))
    names = tuple(sizes)
    shape = tuple(sizes[n] for n in names)
    arr = np.asarray(devices).reshape(shape)
    return Mesh(arr, names)


class MeshContext:
    """The sharding spine: one mesh × one rule set × every placement.

    Bundles a (possibly multi-axis) `Mesh` with a single `ShardingRules`
    and derives from them ALL the shardings training needs:

      batch       — leading dim over `batch_axis` (data parallel)
      params      — per-leaf from the rules (replicated when no rules)
      optimizer   — moments follow their param's spec when it shards
                    anything (FSDP/tensor parallel); otherwise they are
                    sharded across the REPLICA axis (`batch_axis`) on the
                    first evenly-divisible dim — cross-replica weight-
                    update sharding (arXiv:2004.13336), an ~Nx per-device
                    HBM cut that replicated-moment training wastes.

    Rule precedence for a param leaf: first matching (layer_glob,
    param_glob) rule wins; no match → `rules.default` (replicated).
    Moment leaves inherit the param's resolved spec before the replica-
    axis fallback applies. `shard_opt_state=False` is the escape hatch
    back to fully-replicated optimizer state.

    Construct these HERE (or let `ParallelWrapper` do it); the active
    context is what `DevicePrefetchIterator` and the fused-update policy
    consult, installed for the duration of a fit by `use_mesh_context`.
    """

    def __init__(self, mesh: Optional[Mesh] = None, rules=None, *,
                 batch_axis: str = AXIS_DATA,
                 model_axis: str = AXIS_MODEL,
                 shard_opt_state: bool = True):
        self.mesh = mesh if mesh is not None else make_mesh()
        if batch_axis not in self.mesh.axis_names:
            raise ValueError(
                f"Mesh {self.mesh.axis_names} has no {batch_axis!r} axis")
        self.rules = rules
        self.batch_axis = batch_axis
        self.model_axis = model_axis
        self.shard_opt_state = bool(shard_opt_state)
        self.data_size = int(self.mesh.shape[batch_axis])
        self.replicated = NamedSharding(self.mesh, P())

    # ------------------------------------------------------------ batch
    def batch_spec(self, ndim: int) -> P:
        return P(self.batch_axis, *([None] * (ndim - 1)))

    def batch_sharding(self, ndim: int) -> NamedSharding:
        return NamedSharding(self.mesh, self.batch_spec(ndim))

    def batch_sharding_like(self, x):
        """NamedSharding tree for a batch leaf/dict (None passes through)."""
        if x is None:
            return None
        if isinstance(x, dict):
            return {k: self.batch_sharding_like(v) for k, v in x.items()}
        return self.batch_sharding(x.ndim)

    def put_batch(self, x):
        """ONE device_put landing a host batch pre-sharded over the batch
        axis. Leaves whose leading dim does not divide the axis fall back
        to a plain (unsharded) put — callers that pad (ParallelWrapper)
        never hit the fallback."""
        if x is None:
            return None
        if isinstance(x, dict):
            return {k: self.put_batch(v) for k, v in x.items()}
        nd = getattr(x, "ndim", 0)
        if nd >= 1 and x.shape[0] % self.data_size == 0 and x.shape[0] > 0:
            return jax.device_put(x, self.batch_sharding(nd))
        return jax.device_put(x)

    # ----------------------------------------------------------- params
    def _param_spec(self, layer_name: str, param_name: str, leaf) -> P:
        if self.rules is None:
            return P()
        spec = self.rules.spec_for(layer_name, param_name)
        nd = getattr(leaf, "ndim", None)
        if nd is not None and len(spec) > nd:
            spec = P()
        return spec

    def param_shardings(self, tree):
        """NamedSharding tree matching a {layer: {param: leaf}} tree.
        Param-name rules apply at the LEAF key, so nested structures keep
        working."""
        return self._tree_shardings(tree, self._param_spec)

    def state_shardings(self, tree):
        """Layer running state (batch-norm stats, ...) stays replicated."""
        return jax.tree_util.tree_map(lambda _: self.replicated, tree)

    # -------------------------------------------------- optimizer state
    def moment_spec(self, layer_name: str, param_name: str, leaf) -> P:
        """Spec for one optimizer-moment leaf (shaped like its param)."""
        spec = self._param_spec(layer_name, param_name, leaf)
        if any(a is not None for a in spec):
            return spec                 # FSDP/TP: moments follow the param
        if not self.shard_opt_state or self.data_size <= 1:
            return P()
        shape = getattr(leaf, "shape", ())
        for i, d in enumerate(shape):
            if d > 0 and d % self.data_size == 0:
                return P(*([None] * i), self.batch_axis)
        return P()                      # too small to split evenly

    def opt_shardings(self, tree, moment_keys=None):
        """NamedSharding tree for an updater-state tree
        ({layer: {"m": {param: leaf}, ...}} or {layer: ()}). Leaves under
        a state key in `moment_keys` (default: every param-shaped moment
        key any built-in updater declares) get `moment_spec`; anything
        else replicates."""
        if moment_keys is None:
            from deeplearning4j_tpu.optim.updaters import MOMENT_STATE_KEYS
            moment_keys = MOMENT_STATE_KEYS

        def spec_fn(layer_name, param_name, leaf, _state_key=None):
            if _state_key is not None and _state_key in moment_keys:
                return self.moment_spec(layer_name, param_name, leaf)
            return self._param_spec(layer_name, param_name, leaf)

        return self._tree_shardings(tree, spec_fn, state_keyed=True)

    # ---------------------------------------------------------- helpers
    def _tree_shardings(self, tree, spec_fn, *, state_keyed: bool = False):
        """Walk {layer: subtree}; rules apply at the LEAF key (so updater
        state like {'m': {'W': ...}} resolves against param 'W'), with the
        top-level state key ('m', 'v', ...) threaded through when
        `state_keyed` so moments can diverge from their param's spec."""
        def build(layer_name, sub, state_key=None):
            if not isinstance(sub, dict):
                return jax.tree_util.tree_map(
                    lambda _: self.replicated, sub)
            out = {}
            for k, v in sub.items():
                if isinstance(v, dict):
                    sk = k if state_keyed and state_key is None else state_key
                    out[k] = build(layer_name, v, sk)
                else:
                    spec = (spec_fn(layer_name, k, v) if state_key is None
                            else spec_fn(layer_name, k, v, state_key))
                    out[k] = NamedSharding(self.mesh, spec)
            return out

        return {ln: build(ln, sub) for ln, sub in tree.items()}


# The active spine. A process normally has exactly ONE MeshContext (the
# ROADMAP's "one mesh for data x model x optimizer-state parallelism");
# the thread-local stack exists so concurrent fits (serving + training
# in one process) cannot see each other's mesh mid-trace.
_SPINE_TLS = threading.local()
_SPINE_DEFAULT: Optional[MeshContext] = None


def set_mesh_context(ctx: Optional[MeshContext]) -> Optional[MeshContext]:
    """Install `ctx` as the process-wide default spine; returns the
    previous default (restore it when done)."""
    global _SPINE_DEFAULT
    prev, _SPINE_DEFAULT = _SPINE_DEFAULT, ctx
    return prev


def current_mesh_context() -> Optional[MeshContext]:
    """The innermost `use_mesh_context` on this thread, else the
    process default, else None (single-device semantics everywhere)."""
    stack = getattr(_SPINE_TLS, "stack", None)
    if stack:
        return stack[-1]
    return _SPINE_DEFAULT


@contextlib.contextmanager
def use_mesh_context(ctx: Optional[MeshContext]):
    """Scope `ctx` as the active spine for this thread (trainers wrap
    their dispatch loops in this so batch placement and trace-time
    policies agree on the mesh)."""
    stack = getattr(_SPINE_TLS, "stack", None)
    if stack is None:
        stack = _SPINE_TLS.stack = []
    stack.append(ctx)
    try:
        yield ctx
    finally:
        stack.pop()


def shard_map_compat(fn, mesh, in_specs, out_specs, *, check: bool = False):
    """The package's one `jax.shard_map` entry point. `check=True` keeps
    jax's default vma checking (pipeline's psum-reduced outputs pass
    it); False disables it (ring attention's merged partials do not)."""
    kw = {} if check else {"check_vma": False}
    return jax.shard_map(fn, mesh=mesh, in_specs=in_specs,
                         out_specs=out_specs, **kw)
