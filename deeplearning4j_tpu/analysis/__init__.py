"""graft-lint — tracer-safety & recompile-hazard static analysis.

The static counterpart of the runtime observability core: the
RecompileWatchdog and HostSyncMonitor (observe/) catch jit-cache churn
and host syncs *after* they ship; this package catches the patterns
that cause them at review time, over plain ASTs. The analyzer modules
are stdlib-only — linting never traces, compiles, or touches a device.

    python -m deeplearning4j_tpu.analysis deeplearning4j_tpu tests \
        --strict --baseline .graftlint-baseline.json

Public API:

    lint_paths(paths) / lint_file(path) / lint_source(src) -> [Finding]
    RULES                         — rule registry (id -> Rule)
    RULES_VERSION                 — bumped on rule-semantics changes;
                                    invalidates the result cache
    RUNTIME_RULE_HINTS            — runtime-event kind -> static rules
                                    (the watchdog/monitor/lockmon/
                                    donatemon cross-check)
    load_baseline/apply_baseline/write_baseline/prune_baseline
    Program / CallGraph           — whole-program call graph (callgraph.py)
    analyze_lock_program/sources/paths      — GL7xx lockset pass
    analyze_shardflow_program/sources/paths — GL8xx sharding/donation
                                              dataflow pass
    lint_files_cached             — (mtime, sha) result cache over
                                    `.graftlint-cache.json` (cache.py)
"""

from deeplearning4j_tpu.observe.trace import span as _span

with _span("import.analysis"):
    from deeplearning4j_tpu.analysis.baseline import (   # noqa: F401
        apply_baseline, load_baseline, prune_baseline, write_baseline,
    )
    from deeplearning4j_tpu.analysis.cache import (      # noqa: F401
        CACHE_FILE, lint_files_cached,
    )
    from deeplearning4j_tpu.analysis.callgraph import (  # noqa: F401
        CallGraph, Program,
    )
    from deeplearning4j_tpu.analysis.engine import (     # noqa: F401
        DEFAULT_HOT_PREFIXES, Finding, is_hot, lint_file, lint_files,
        lint_paths, lint_source,
    )
    from deeplearning4j_tpu.analysis.locks import (      # noqa: F401
        analyze_lock_paths, analyze_lock_program, analyze_lock_sources,
    )
    from deeplearning4j_tpu.analysis.rules import (      # noqa: F401
        RULES, RULES_VERSION, RUNTIME_RULE_HINTS, Rule, runtime_hint,
    )
    from deeplearning4j_tpu.analysis.shardflow import (  # noqa: F401
        analyze_shardflow_paths, analyze_shardflow_program,
        analyze_shardflow_sources,
    )

__all__ = [
    "CACHE_FILE", "CallGraph", "DEFAULT_HOT_PREFIXES", "Finding",
    "Program", "RULES", "RULES_VERSION", "RUNTIME_RULE_HINTS", "Rule",
    "analyze_lock_paths", "analyze_lock_program", "analyze_lock_sources",
    "analyze_shardflow_paths", "analyze_shardflow_program",
    "analyze_shardflow_sources", "apply_baseline", "is_hot", "lint_file",
    "lint_files", "lint_files_cached", "lint_paths", "lint_source",
    "load_baseline", "prune_baseline", "runtime_hint", "write_baseline",
]
