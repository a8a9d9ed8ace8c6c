from repro.core.objective import (
    EllLogisticRegression,
    LogisticRegression,
    Objective,
    get_objective,
    params_from_flat,
    register_objective,
    registered_objectives,
)
from repro.core.objectives import (
    MLPObjective,
    NonconvexLogistic,
    mlp_lm_objective,
)
from repro.core.svrg import svrg_epoch, run_svrg, sweep_spec as svrg_sweep_spec
from repro.core.asysvrg import (
    AsyRunResult,
    asysvrg_epoch,
    run_asysvrg,
    make_delay_schedule,
)
from repro.core.sweep import (
    ALGOS,
    SweepSpec,
    SweepResult,
    SweepPlan,
    make_grid,
    plan_sweep,
    run_sweep,
)
from repro.core.hogwild import hogwild_epoch, run_hogwild
from repro.core.compression import (
    topk_compress,
    randk_compress,
    int8_compress,
    ErrorFeedbackState,
    compressed_update,
)

__all__ = [
    "EllLogisticRegression",
    "LogisticRegression",
    "Objective",
    "register_objective",
    "get_objective",
    "registered_objectives",
    "params_from_flat",
    "MLPObjective",
    "NonconvexLogistic",
    "mlp_lm_objective",
    "svrg_epoch",
    "run_svrg",
    "svrg_sweep_spec",
    "ALGOS",
    "AsyRunResult",
    "asysvrg_epoch",
    "run_asysvrg",
    "make_delay_schedule",
    "SweepSpec",
    "SweepResult",
    "SweepPlan",
    "make_grid",
    "plan_sweep",
    "run_sweep",
    "hogwild_epoch",
    "run_hogwild",
    "topk_compress",
    "randk_compress",
    "int8_compress",
    "ErrorFeedbackState",
    "compressed_update",
]
