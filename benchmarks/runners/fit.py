"""Runner `fit`: one training job through the program's `fit()`.

A pool of seeded host batches is handed out cyclically by an iterator of
the benchmark's own, which stops handing out when `--seconds` have passed
since the first timed batch. One `fit()` call, one epoch, spans the window,
so the executor's one host sync at the epoch's end closes it:

    train_items_per_s = batches handed out x global batch
                        / (first hand-out -> fit() returned)

Set-up builds ONE net (and, where the mix says so, its `ParallelWrapper`),
gives it the benchmark's weights, drives it through its first
`warmup_steps` steps with that same iterator and `fit()` call, and hands the
same object to the window. Those first steps are what `correct` compares
with the plain reference, which ran in a process of its own before this one
touched JAX (`benchmarks/reference_main.py`) and is not part of `setup_s`.
"""

from __future__ import annotations

import json
import os
import subprocess
import sys
import time

from benchmarks import (
    compare, harness, span_reduce, trace_reduce, traffic_gen, xplane_schema,
)


def run_reference(cell: dict, seed: int, steps: int, require_chip: bool):
    """(numbers, wall seconds) of the reference child."""
    os.makedirs(harness.SCRATCH, exist_ok=True)
    out = os.path.join(harness.SCRATCH, f"reference_{cell['name']}.json")
    if os.path.exists(out):
        os.remove(out)
    t0 = time.perf_counter()
    done = subprocess.run(
        [sys.executable, os.path.join(harness.BENCH_DIR, "reference_main.py"),
         "--config", cell["config_file"], "--traffic", cell["traffic_file"],
         "--chips", str(cell["chips"]), "--seed", str(seed),
         "--steps", str(steps), "--out", out,
         "--require-chip", str(int(require_chip))],
        stdout=sys.stderr, check=False)
    wall = time.perf_counter() - t0
    if done.returncode == 2 and require_chip:
        raise harness.NoChip("the reference found no chip")
    if done.returncode != 0:
        raise RuntimeError(f"reference exited with {done.returncode}")
    import numpy as np

    with open(out, encoding="utf-8") as fh:
        numbers = json.load(fh)
    with np.load(out + ".npz") as samples:
        numbers["grad_sample"] = {k: samples[k] for k in samples.files}
    os.remove(out)
    os.remove(out + ".npz")
    return numbers, wall


def _stream(pool, *, steps=None, seconds=None):
    """An iterator on the program's `DataSetIterator` protocol that hands
    the pool out cyclically, for `steps` batches or until `seconds` have
    passed since the first hand-out."""
    from deeplearning4j_tpu.data.dataset import DataSet
    from deeplearning4j_tpu.data.iterators import DataSetIterator

    batches = [DataSet(x, y) for x, y in pool]

    class Stream(DataSetIterator):
        handed = 0
        t_first = None
        t_last = None

        def reset(self):
            self.handed, self.t_first, self.t_last = 0, None, None

        def __next__(self):
            now = time.perf_counter()
            if self.t_first is None:
                self.t_first = now
            if steps is not None and self.handed >= steps:
                raise StopIteration
            if seconds is not None and now - self.t_first >= seconds:
                raise StopIteration
            ds = batches[self.handed % len(batches)]
            self.handed += 1
            self.t_last = now
            return ds

        @property
        def batch_size(self):
            return batches[0].num_examples()

    return Stream()


class FirstSteps(harness.Listener):
    """Reads, on the device and without a host sync, each warm-up step's
    loss, and the first gradient's per-leaf norms and samples out of the
    optimizer's state after step one (how is the update rule's to say)."""

    def __init__(self, first_grad):
        self._first_grad = first_grad
        self.losses, self.grad, self.sample = [], None, None

    def iteration_done(self, model, iteration, epoch, score):
        self.losses.append(score)
        if len(self.losses) == 1:
            self.grad, self.sample = self._first_grad(model.updater_state)


def dl4j_trace_beacon(v):
    """The clock link's program: jitted it is `trace_reduce.BEACON` in the
    trace's `XLA Modules` line, and does no work to speak of."""
    return v + 1


class TraceWindow(harness.Listener):
    """The profiler over the window's first `seconds`. It is started just
    BEFORE the window (starting it stalls the host for a second or two,
    which inside the window drained the device's queue and read as 55 to
    70% idle: chip runs, PR 24) and stopped from the fit loop's own thread.
    Device ops only. With the host tracer on, even at its lowest level,
    the runtime's per-chunk events of the host-side layout change of every
    batch made a 330 MB trace and a dispatch of 0.4 s (chip run, PR 24).

    A device plane counts from the moment its tracer started, a
    millisecond or two into opening the session, which no host clock read
    gives (chip run, PR 25). So five beacons tie the trace's zero to the
    program's span clock, `time.perf_counter_ns()`: runs of a trivial
    program, each blocked on between two reads of that clock, three once
    the session is open and two before it stops (the first of those drains
    the device's queue, the second finds it idle). `span_reduce.clock_link`
    reads `beacons_ns` against the runs' events. (After the program's
    `utils/profiling.DeviceTrace`, which writes files; this keeps the
    trace in memory.) Traced runs only: no untraced run pays for it."""

    def __init__(self, stream, seconds):
        self.stream, self.seconds = stream, seconds
        self.session, self.xspace, self.stop_s = None, None, None
        self.beacons_ns = []

    def _beacon(self, runs: int):
        for _ in range(runs):
            t0 = time.perf_counter_ns()
            self._program(self._one).block_until_ready()
            self.beacons_ns.append([t0, time.perf_counter_ns()])

    def start(self):
        import jax
        import jax.numpy as jnp
        from jax._src.lib import _profiler

        jax.devices()           # the backend before the tracer, as JAX does
        self._program = jax.jit(dl4j_trace_beacon)
        self._one = jnp.zeros((8, 128), jnp.float32)
        self._program(self._one).block_until_ready()   # compiled out here
        options = jax.profiler.ProfileOptions()
        options.python_tracer_level = 0
        options.host_tracer_level = 0
        options.enable_hlo_proto = False
        self.session = _profiler.ProfilerSession(options)
        self._beacon(3)

    def iteration_done(self, model, iteration, epoch, score):
        if self.session is not None and self.stream.t_first is not None \
                and time.perf_counter() - self.stream.t_first >= self.seconds:
            self.stop()

    def stop(self):
        if self.session is not None:
            self._beacon(2)
            t0 = time.perf_counter()
            self.xspace, self.session = self.session.stop(), None
            self.stop_s = time.perf_counter() - t0

    def on_fit_end(self, model):
        self.stop()


def _rounded_on_host(made: dict, dtype) -> dict:
    """The reference's float32 weights `made`, off the device and in the
    dtype the net is trained in, as numpy arrays. Each leaf is rounded on
    the host (numpy rounds to nearest even, as the device's cast does: the
    same bits) and its device buffer dropped before the next, so the
    device never holds a second copy and no program is compiled."""
    import jax
    import numpy as np

    for arr in jax.tree_util.tree_leaves(made):
        arr.copy_to_host_async()
    rounded = {}
    for name in list(made):
        rounded[name] = {}
        for leaf, arr in made.pop(name).items():
            rounded[name][leaf] = np.asarray(arr).astype(dtype)
            arr.delete()
    return rounded


def _place_weights(net, shapes: dict, weights: dict):
    """Give the net the benchmark's `weights` (host arrays). Every leaf
    of the net (`shapes`, as `harness.init_in_one_program` returns them)
    must be one the reference made."""
    import jax

    missing = set(weights) - set(shapes)
    if missing:
        raise RuntimeError(f"the program lacks {sorted(missing)}")
    for name, leaves in shapes.items():
        made = weights.get(name, {})
        if set(made) != set(leaves):
            raise RuntimeError(
                f"vertex {name!r}: the program has leaves {sorted(leaves)}, "
                f"the reference {sorted(made)}")
        for leaf, struct in leaves.items():
            if made[leaf].shape != struct.shape:
                raise RuntimeError(
                    f"{name}/{leaf}: program {struct.shape}, "
                    f"reference {made[leaf].shape}")
    net.params_tree = jax.device_put(
        {name: weights.get(name, {}) for name in shapes})


def _delta_norms(params, initial) -> list:
    """Per leaf, in `tree_leaves` order, the norm of (parameters now -
    `initial`, the host copy of them as placed), read on the host a leaf
    at a time: no second copy of the parameters visits the device. In
    float32 a million elements at a time, summed in float64: whole-leaf
    temporaries of VGG16's 103M-element kernel cost 2.8 s of set-up in
    page faults (chip run, PR 28)."""
    import jax
    import numpy as np

    def norm(now, was, chunk=1 << 20):
        now, was = np.asarray(now).reshape(-1), was.reshape(-1)
        total = 0.0
        for i in range(0, now.size, chunk):
            d = (now[i:i + chunk].astype(np.float32)
                 - was[i:i + chunk].astype(np.float32))
            total += float(np.sum(np.square(d), dtype=np.float64))
        return float(np.sqrt(total))

    return [norm(a, b) for a, b in zip(jax.tree_util.tree_leaves(params),
                                       jax.tree_util.tree_leaves(initial))]


def _histograms():
    from deeplearning4j_tpu.observe import get_registry

    reg = get_registry()
    return {name: (reg.histogram(name).count, reg.histogram(name).sum)
            for name in ("train_etl_ms", "train_dispatch_ms")}


def prepare(cell: dict, seed: int, used, stamp=lambda name: None) -> dict:
    """Set-up proper: ONE net (and its wrapper), the benchmark's weights,
    the pool, and the first `warmup_steps` steps through `fit()`, read for
    the comparison. Returns the trainer the window goes on with."""
    import jax
    import jax.numpy as jnp
    import numpy as np

    config, traffic = cell["config_data"], cell["traffic_data"]
    chips, steps = cell["chips"], int(traffic["warmup_steps"])
    follow = harness.load_module("reference", "follow.py")
    ref_mod = harness.load_module("reference", config["reference"] + ".py")
    model = harness.load_module("models", config["model"] + ".py")
    rule = harness.load_module("reference", "rules",
                               config["updater"]["rule"] + ".py")
    global_batch = config["batch_per_chip"] * chips

    # Of the model's size the device holds, in turn: the reference's
    # float32 tree alone; nothing; the optimizer's state; that and the
    # parameters. Never more than the step's own arguments.
    initial = _rounded_on_host(ref_mod.init_params(seed, config),
                               jnp.dtype(config["dtype"]))
    net = model.build(config, seed)
    _place_weights(net, harness.init_in_one_program(net), initial)
    stamp("net_and_weights")
    trainer = net
    if traffic.get("wrapper") == "ParallelWrapper":
        from deeplearning4j_tpu.parallel import ParallelWrapper, make_mesh

        trainer = ParallelWrapper(
            net, mesh=make_mesh({"data": chips}, devices=list(used)))
    elif traffic.get("wrapper"):
        raise KeyError(f"unknown wrapper {traffic['wrapper']!r}")
    pool = traffic_gen.make_pool(traffic, config, seed, global_batch)
    stamp("pool")

    @jax.jit
    def first_grad(state):
        g = rule.first_gradient(state, config["updater"])
        return follow.leaf_norms(g), follow.leaf_samples(g)

    names = follow.leaf_paths(initial)
    first = FirstSteps(first_grad)
    net.set_listeners(first)
    trainer.fit(_stream(pool, steps=steps), epochs=1)
    program = {
        "loss": [float(x) for x in first.losses],
        "grad_norm": dict(zip(names, map(float, np.asarray(first.grad)))),
        "delta_norm": dict(zip(names, _delta_norms(net.params_tree,
                                                   initial))),
        "grad_sample": dict(zip(names, map(np.asarray, first.sample))),
    }
    stamp("first_steps")
    return {"net": net, "trainer": trainer, "pool": pool,
            "program": program, "global_batch": global_batch,
            "forward_macs_per_item": ref_mod.forward_macs(config)}


def run(cell: dict, *, seed: int, seconds: float, trace: bool,
        require_chip: bool, t_start: float, limits=None) -> dict:
    config, traffic = cell["config_data"], cell["traffic_data"]
    chips, steps = cell["chips"], int(traffic["warmup_steps"])
    if limits is None:
        limits = harness.load_json("limits", cell["name"] + ".json")
    reference, ref_wall = run_reference(cell, seed, steps, require_chip)
    harness.say("reference", seconds=ref_wall, loss=reference["loss"],
                phases=reference.get("phases"),
                xla_compiles=reference["xla_compiles"],
                memory_peak_bytes=reference["memory_peak_bytes"])

    import jax
    import numpy as np

    cache_dir = harness.enable_compile_cache()
    used = (harness.require_chips(chips) if require_chip
            else jax.devices()[:chips])
    if len(used) < chips:
        raise harness.NoChip(f"{chips} devices asked, {len(used)} seen")
    peaks = harness.peaks_for(used[0].device_kind) if require_chip else None
    phases, t_phase = {}, [time.perf_counter()]
    phases["start_to_devices"] = t_phase[0] - t_start - ref_wall
    peak_after = {}     # the process's peak so far, at each phase's end

    def stamp(name):
        now = time.perf_counter()
        phases[name] = now - t_phase[0]
        t_phase[0] = now
        peak_after[name] = harness.device_facts(used)["memory_peak_bytes"]

    with harness.XlaLog() as xla_setup:
        ready = prepare(cell, seed, used, stamp)
    net, trainer, pool = ready["net"], ready["trainer"], ready["pool"]
    program, global_batch = ready["program"], ready["global_batch"]

    # ------------------------------------------------------------ window
    log = harness.LossLog()
    stream = _stream(pool, seconds=seconds)
    listeners = [log]
    tracer = None
    if trace:
        tracer = TraceWindow(stream, min(float(traffic["trace_seconds"]),
                                         seconds / 2.0))
        listeners.append(tracer)
        tracer.start()
    net.set_listeners(*listeners)
    hist_before = _histograms()
    peak_setup = harness.device_facts(used)["memory_peak_bytes"]
    t_window = time.perf_counter()
    setup_s = t_window - t_start - ref_wall
    with harness.XlaLog() as xla_window:
        trainer.fit(stream, epochs=1)
        t_end = time.perf_counter()
    hist_after = _histograms()
    losses = log.host()
    elapsed = t_end - stream.t_first
    items_per_s = stream.handed * global_batch / elapsed

    # ----------------------------------------------------------- correct
    numbers = compare.first_steps(program, reference)
    numbers["window_loss_not_finite"] = {
        "value": int(np.sum(~np.isfinite(losses)))}
    numbers["window_steps_missing"] = {
        "value": int(stream.handed - len(losses))}
    on = harness.platforms(net.params_tree, net.updater_state)
    numbers["state_off_device"] = {
        "value": int(on != [used[0].platform]), "on": on}
    correct = compare.judge(numbers, {
        "window_loss_not_finite": 0, "window_steps_missing": 0,
        "state_off_device": 0, **limits})
    for name, row in numbers.items():
        harness.say("check", name=name, **row)

    device = harness.device_facts(used)
    run_facts = {
        "workload": cell["name"], "seed": seed, "chips": chips,
        "global_batch": global_batch, "steps": int(stream.handed),
        "window_s": elapsed, "handing_out_s": stream.t_last - stream.t_first,
        "items_per_s": items_per_s, "setup_s": setup_s,
        "reference_s": ref_wall, "loss_first": float(losses[0]),
        "loss_last": float(losses[-1]),
        "program_first_losses": program["loss"],
        "reference_first_losses": reference["loss"],
        "xla_compiles_in_window": xla_window.compiled,
        "compile_cache_dir": cache_dir,
        "setup_xla": xla_setup.facts(),
        "forward_macs_per_item": ready["forward_macs_per_item"],
        "setup_phases_s": phases,
        "item": config["item"], "peaks": peaks,
        **({"tokens_per_item": config["tokens_per_item"]}
           if "tokens_per_item" in config else {}),
        "device_kind": device["kind"], "platform": device["platform"],
        "memory_peak_bytes": device["memory_peak_bytes"],
        "memory_peak_setup_bytes": peak_setup,
        "memory_peak_after_phase_bytes": peak_after,
        "memory_stats": {k: int(v) for k, v in
                         (used[0].memory_stats() or {}).items()},
    }
    harness.say("run", **run_facts)

    result = {"correct": bool(correct), "attempted": int(stream.handed),
              "failed": int(numbers["window_loss_not_finite"]["value"]
                            + numbers["window_steps_missing"]["value"]),
              "metrics": {}, "device": device}
    if not trace:
        values = {"train_items_per_s": items_per_s, "setup_s": setup_s}
        for m in cell["end_to_end"]:
            result["metrics"][m["name"]] = {"value": values[m["name"]],
                                            "unit": m["unit"]}
        return result

    devices = [d.id for d in used]
    space = reduction = clock = scopes = None
    if tracer.xspace:
        space = xplane_schema.xspace_class()()
        space.ParseFromString(tracer.xspace)
        reduction = trace_reduce.reduce_space(
            space, devices=devices,
            skip_s=float(traffic.get("trace_skip_s", 0.0)))
        clock = span_reduce.clock_link(space, tracer.beacons_ns)
        scopes = span_reduce.by_scope(space, devices)
        harness.say("trace", bytes=len(tracer.xspace),
                    stop_s=tracer.stop_s, clock=clock, **{
                        k: reduction[k] for k in (
                            "window_s", "busy_s", "idle_share_worst",
                            "category_s", "main_module", "main_module_runs",
                            "main_module_runs_per_s", "main_module_period_ms")
                        if reduction is not None},
                    lines=trace_reduce.line_counts(space))
    # What a reader gets: `trace`, the reduction (None where no op ran on
    # a TPU); `xspace`, the parsed trace itself; `spans`, the subtree of
    # the window's `fit` root out of the program's span store; `clock`,
    # where the trace's zero lies on the spans' clock; `scopes`, device op
    # time by `jax.named_scope`, summed once for every reader; `registry`
    # and `run` as before.
    facts = {
        "trace": reduction, "xspace": space, "clock": clock,
        "scopes": scopes,
        "spans": span_reduce.window(span_reduce.program_spans()),
        "registry": {k: {"count": hist_after[k][0] - hist_before[k][0],
                         "sum": hist_after[k][1] - hist_before[k][1]}
                     for k in hist_after},
        "run": run_facts,
    }
    for m in cell["per_layer"]:
        reader = harness.load_module("layer_metrics", m["name"] + ".py")
        value = reader.read(facts)
        if value is not None:
            result["metrics"][m["name"]] = {"value": float(value),
                                            "unit": m["unit"]}
    if reduction is not None:
        result["device"]["busy_s"] = reduction["busy_s"]
        result["device"]["window_s"] = reduction["window_s"]
        result["breakdown"] = dict(
            reduction["breakdown"],
            **_by_layer_and_span(space, devices, facts, peaks))
    return result


def _by_layer_and_span(space, devices, facts, peaks) -> dict:
    """`layers`: the ten scopes with most device time, as
    `span_reduce.layers` gives them (ms a step forward and backward,
    achieved TFLOP/s and HBM GB/s, the nearer roof and the share of it).
    `gaps`: the first chip's idle seconds since the window's `fit` span
    began, by the innermost host span that covers each gap, where the
    clocks are linked."""
    _, runs = span_reduce.main_module(space, devices)
    out = {"layers": span_reduce.layers(facts["scopes"], len(runs),
                                        len(devices), peaks)}
    fit = next((s for s in facts["spans"] if s["name"] == "fit"), None)
    if facts["clock"] is not None and fit is not None:
        # from the window's start on: the opening beacons ran before it
        zero = facts["clock"]["zero_ns"]
        start_ps = (fit["start_ns"] - zero) * 1000.0
        gaps = span_reduce.gaps_by_span(
            [[max(a, start_ps), b]
             for a, b in span_reduce.idle_gaps(space, devices)
             if b > start_ps], facts["spans"], zero)
        out["gaps"] = [[name, row["s"]] for name, row in gaps.items()][
            :span_reduce.TOP]
    return out
