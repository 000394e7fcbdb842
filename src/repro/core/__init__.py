"""ParetoBandit core: the paper's contribution as a composable JAX module."""
from repro.core.types import (  # noqa: F401
    ArmPrior,
    HyperParams,
    PacerState,
    RouterConfig,
    RouterState,
    Statics,
    init_state,
    log_normalized_cost,
    with_hyperparams,
)
from repro.core.router import (  # noqa: F401
    BatchDecision,
    Decision,
    run_stream,
    run_stream_batched,
    select,
    select_batch,
    step,
    step_batch,
    update,
    update_batch,
)
from repro.core.backend import RoutingBackend, get_backend  # noqa: F401
from repro.core.registry import add_arm, delete_arm, set_price  # noqa: F401
from repro.core.scenario import (  # noqa: F401
    AddArm,
    BudgetChange,
    DeleteArm,
    HyperShift,
    Param,
    PriceChange,
    QualityShift,
    ScenarioParams,
    ScenarioSpec,
    Timeline,
    TrafficMixShift,
    retime,
)
from repro.core.montecarlo import (  # noqa: F401
    MonteCarloResult,
    run_monte_carlo,
    sample_timelines,
)
from repro.core.sweep import (  # noqa: F401
    GridResult,
    run_grid,
    run_scenario_grid,
)
from repro.core.warmup import (  # noqa: F401
    apply_warmup,
    fit_offline_prior,
    n_eff_to_t_adapt,
    scale_prior,
    t_adapt_to_n_eff,
)
