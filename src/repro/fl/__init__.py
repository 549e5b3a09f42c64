"""Composable federated engine: Strategy x Executor x DeviceProfile x
FleetDynamics x Aggregator x Callback, replacing the seed's monolithic
``run_federated``.

    from repro.fl import FederatedEngine, CAFLL, BatchedExecutor

    engine = FederatedEngine(model, fl, dataset, strategy="cafl",
                             executor="batched",
                             aggregator="fedbuff",   # default: "sync"
                             callbacks=[LoggingCallback()])
    result = engine.run()

The seed API (``repro.core.run_federated``) remains a thin wrapper.
"""
from repro.constraints import (  # noqa: F401
    AdaptiveStep, Constraint, ConstraintReport, ConstraintSet,
    DeadlineAwareKnobPolicy, DeadzoneSubgradient, DualController,
    KnobPolicy, PIController, PaperKnobPolicy, make_constraints,
    make_controller, make_knob_policy, paper_constraints,
    register_constraint,
)
from repro import spans  # noqa: F401  (``from repro.fl import spans``)
from repro.core.client import ClientResult, ClientRunner  # noqa: F401
from repro.core.server import FLResult, RoundRecord  # noqa: F401
from repro.fl.aggregator import (  # noqa: F401
    Aggregator, ClientReport, ConstantStaleness, FedBuffAggregator,
    MaskedSumAggregator, PolynomialStaleness, ServerUpdate,
    StalenessPolicy, StalenessWeightedAggregator, SyncAggregator,
    canonical_order, make_aggregator, make_staleness_policy,
    report_order_key,
)
from repro.fl.callbacks import (  # noqa: F401
    CheckpointCallback, HistoryWriterCallback, LoggingCallback,
    RoundCallback,
)
from repro.fl.clock import (  # noqa: F401
    TIME_MODES, EventQueue, KnobRoundTime, RoundTimeModel, SimClock,
    TimedReport, make_round_time, seconds_to_target,
)
from repro.fl.device import (  # noqa: F401
    DEFAULT_PROFILE, ClientInfo, DeviceProfile, FleetClass, make_fleet,
    uniform_fleet,
)
from repro.fl.dynamics import (  # noqa: F401
    AlwaysAvailable, AvailabilityModel, BernoulliChurn, ClientSampler,
    DeadlineStragglers, FleetDynamics, FullParticipation, NoStragglers,
    PeriodicAvailability, ResourceAwareSampler, RoundPlan,
    RoundRobinSampler, StragglerModel, UniformSampler, make_dynamics,
)
from repro.fl.engine import FederatedEngine  # noqa: F401
from repro.fl.executor import (  # noqa: F401
    BatchedExecutor, ClientExecutor, SequentialExecutor, make_executor,
)
from repro.fl.strategy import (  # noqa: F401
    CAFLL, FedAvg, FederatedStrategy, ServerOpt, make_strategy,
)
