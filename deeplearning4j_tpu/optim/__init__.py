"""Optimization: updaters (optimizer rules), LR schedules, solver loop.

Reference parity: ND4J `GradientUpdater` impls applied through
`nn/updater/UpdaterBlock.java:101-160` and the solver loop in
`optimize/solvers/BaseOptimizer.java` / `StochasticGradientDescent.java`.
"""

from deeplearning4j_tpu.observe.trace import span as _span

with _span("import.optim"):
    from deeplearning4j_tpu.optim.updaters import (
        Updater, Sgd, Adam, AdaMax, Nadam, AMSGrad, Nesterovs, AdaGrad, AdaDelta,
        RmsProp, NoOp,
    )
    from deeplearning4j_tpu.optim.schedules import (
        Schedule, FixedSchedule, StepSchedule, ExponentialSchedule, InverseSchedule,
        PolySchedule, SigmoidSchedule, MapSchedule, WarmupCosineSchedule,
    )
    from deeplearning4j_tpu.optim.solvers import (
        Solver, backtrack_line_search, minimize_cg, minimize_gd, minimize_lbfgs,
    )
    from deeplearning4j_tpu.optim.executor import LossTracker, TrainingExecutor

__all__ = [
    "Solver", "backtrack_line_search", "minimize_cg", "minimize_gd",
    "minimize_lbfgs", "LossTracker", "TrainingExecutor",
    "Updater", "Sgd", "Adam", "AdaMax", "Nadam", "AMSGrad", "Nesterovs",
    "AdaGrad", "AdaDelta", "RmsProp", "NoOp",
    "Schedule", "FixedSchedule", "StepSchedule", "ExponentialSchedule",
    "InverseSchedule", "PolySchedule", "SigmoidSchedule", "MapSchedule",
    "WarmupCosineSchedule",
]
