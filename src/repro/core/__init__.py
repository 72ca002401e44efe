"""The paper's contribution: Sequence Length Warmup + its instrumentation."""
from repro.core.curriculum import apply_seqlen  # noqa: F401
from repro.core.regulators import (  # noqa: F401
    BatchSizeRegulator,
    ControllerState,
    LRScheduleRegulator,
    Regulator,
    RegulatorStack,
    SeqLenRegulator,
    StepPlan,
    StepTelemetry,
    VarianceLRThrottle,
    build_stack,
    predict_trajectory,
)
from repro.core.pacing import (  # noqa: F401
    bucket_ladder,
    quantize,
    raw_seqlen,
    seqlen_at,
)
from repro.core.recovery import (  # noqa: F401
    DivergenceDetector,
    DivergenceError,
    DivergenceEvent,
    RecoveryConfig,
    RecoveryHook,
    RecoveryRegulator,
    RollbackController,
    StateRing,
)
from repro.core.stability import (  # noqa: F401
    LossRatioTracker,
    momentum_stats,
    pearson,
    variance_stats,
)
from repro.core.tuning import (  # noqa: F401
    TuneResult,
    significant_fluctuation,
    tune_slw,
)
