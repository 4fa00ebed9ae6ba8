"""Utilities: JSON serde registry, pytree/param-view helpers, dtype policy."""

from deeplearning4j_tpu.observe.trace import span as _span

with _span("import.utils"):
    from deeplearning4j_tpu.utils.serde import register_serde, to_json, from_json, config_to_dict, config_from_dict
    from deeplearning4j_tpu.utils.pytrees import flatten_params, unflatten_params, param_count, tree_norm
    from deeplearning4j_tpu.utils.timesource import (
        NTPTimeSource, SystemClockTimeSource, TimeSource, TimeSourceProvider,
    )
    from deeplearning4j_tpu.utils.profiling import (
        ProfilerListener, peak_flops, peak_ici_bytes,
        step_flops, trace,
    )

__all__ = [
    "register_serde", "to_json", "from_json", "config_to_dict", "config_from_dict",
    "flatten_params", "unflatten_params", "param_count", "tree_norm",
    "TimeSource", "SystemClockTimeSource", "NTPTimeSource",
    "TimeSourceProvider", "ProfilerListener", "peak_flops",
    "peak_ici_bytes", "step_flops", "trace",
]
