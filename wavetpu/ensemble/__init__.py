"""Ensemble-batched solves: many independent problems in ONE XLA program.

`batched.py` vmaps the existing step families (both schemes, incl. the
flagship compensated velocity form) over a leading lane axis - the
throughput model of the TPU fluid-flow framework (arXiv:2108.11076):
aggregate Gcell/s comes from keeping B independent simulations resident
as one batched program, not from more single-run tuning.  `sharded.py`
composes the lane axis with the device mesh (shard_map-of-vmap) so a
multi-chip host batches SHARDED solves.  The serve layer (wavetpu/serve)
sits on top.
"""

from wavetpu.ensemble.batched import (
    EnsembleResult,
    EnsembleSolver,
    LaneSpec,
    solve_ensemble,
)
from wavetpu.ensemble.sharded import (
    ShardedEnsembleSolver,
    solve_ensemble_sharded,
)

__all__ = [
    "EnsembleResult",
    "EnsembleSolver",
    "LaneSpec",
    "ShardedEnsembleSolver",
    "solve_ensemble",
    "solve_ensemble_sharded",
]
