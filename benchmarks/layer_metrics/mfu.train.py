"""Model FLOP/s utilisation: forward+backward FLOPs per item counted from
the configuration's shapes (3 x 2 x forward multiply-accumulates; nothing
recomputed counts) x items per second, over chips x the bf16 peak of
`benchmarks/peaks.json`. An end-to-end utilisation, not a kernel's.

Items per second are those of the traced window: runs of the train step's
program per second x the global batch, the runs taken from the trace's
`XLA Modules` line as (runs - 1) / (last start - first start) (until PR 27
the runs that START in the window over its length, one run too many). The
traced run's own host-clock rate is not used: stopping the profiler stalls
the fit loop for seconds inside the window."""


def read(facts):
    run, trace = facts["run"], facts["trace"]
    if run["platform"] != "tpu" or run["peaks"] is None or trace is None \
            or trace["main_module_runs_per_s"] is None:
        return None
    items_per_s = trace["main_module_runs_per_s"] * run["global_batch"]
    flops = 6.0 * run["forward_macs_per_item"] * items_per_s
    return 100.0 * flops / (run["chips"] * run["peaks"]["bf16_flops_per_s"])
