"""MOCHA core: the paper's contribution as a composable JAX library."""
from repro.core.dual import (DualState, FederatedData, compute_v,
                             dual_objective, duality_gap, init_state,
                             per_task_error, primal_objective, primal_weights,
                             r_star, with_xnorm2)
from repro.core.engine import (ENGINES, LocalEngine, PallasEngine,
                               RoundEngine, ShardedEngine, get_engine)
from repro.core.losses import (HINGE, LOGISTIC, LOSSES, SMOOTH_HINGE, SQUARED,
                               Loss, get_loss)
from repro.core.minibatch import (MiniBatchConfig, MiniBatchResult, run_mb_sdca,
                                  run_mb_sgd)
from repro.core.mocha import HISTORY_KEYS, MochaConfig, RunResult, run_cocoa
from repro.core.systems_model import (NETWORKS, Network, RoundEvent,
                                      SystemsConfig, SystemsTrace,
                                      population_rates)
from repro.core.regularizers import (REGULARIZERS, Clustered, Graphical,
                                     MeanRegularized, Probabilistic,
                                     Regularizer, sigma_prime, spd_inverse)
from repro.core.subproblem import (batched_local_sdca, local_sdca,
                                   local_sdca_idx, measure_theta, row_norms,
                                   solve_exact, subproblem_value)
from repro.core.sweep import SweepResult, stack_federations, sweep_errors
from repro.core.theta import (BudgetConfig, drop_masked_budgets,
                              presample_budgets, round_budgets,
                              round_key_schedule, validate_assumption2)
