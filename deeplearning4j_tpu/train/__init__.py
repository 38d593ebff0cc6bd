from deeplearning4j_tpu.train.listeners import (
    TrainingListener, ScoreIterationListener, PerformanceListener,
    CollectScoresIterationListener, TimeIterationListener,
    EvaluativeListener, CheckpointListener, ProfilerListener,
    DivergenceListener, ExpertLoadListener, TrainingDivergedError,
)
from deeplearning4j_tpu.train.resilience import (
    CheckpointManager, FaultPolicy, FitReport, PreemptionGuard,
    ResilientTrainer,
)
from deeplearning4j_tpu.train.solvers import (
    BackTrackLineSearch, ConjugateGradient, LBFGS, LineGradientDescent,
)

__all__ = [
    "TrainingListener", "ScoreIterationListener", "PerformanceListener",
    "CollectScoresIterationListener", "TimeIterationListener",
    "EvaluativeListener", "CheckpointListener", "ProfilerListener",
    "DivergenceListener", "ExpertLoadListener", "TrainingDivergedError",
    "CheckpointManager", "FaultPolicy", "FitReport", "PreemptionGuard",
    "ResilientTrainer",
    "BackTrackLineSearch", "LineGradientDescent", "ConjugateGradient",
    "LBFGS",
]
