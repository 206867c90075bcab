"""P2P Swarm Learning core — the paper's contribution as a composable module.

`SwarmSession` is the public entry point (one API over a `SwarmState` pytree
for the engine and gossip backends); everything else is machinery it
composes and the function-form ground truths the tests pin down.
"""
from repro.core.engine import (  # noqa: F401
    SwarmEngine, active_weights, gate_decisions, gated_commit, host_commit,
    mixing_matrix, propose_merge, strategy_propose,
)
from repro.core.merge_impl import (  # noqa: F401
    FisherStrategy, GradMatchStrategy, MergeStrategy, MixStrategy,
    fisher_merge, get_strategy, gradmatch_merge, merge, mix, stack_params,
    topo_weighted_merge, unstack_params,
)
from repro.core.session import SwarmSession, SwarmState  # noqa: F401
from repro.core.topology import (  # noqa: F401
    build_matrix, dynamic_matrix, fedavg_weights, full_matrix,
    mixing_matrix_traced, ring_matrix, spectral_gap,
)
