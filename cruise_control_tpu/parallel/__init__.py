"""Multi-device parallelism: ONE mesh-native engine layer (mesh.py).

Every multi-device mode is a view of the same shard_map'd program over an
explicit 2D ``Mesh((restart, model))`` (see mesh.py module docstring):

  * sharded.py  — Mesh(1, n): one chain, candidate axis sharded n ways;
  * portfolio.py — Mesh(n, 1): independent SA chains racing to the best
    objective (data parallelism over restarts);
  * grid.py     — Mesh(R, M): a portfolio OF candidate-sharded chains.

The jit/shard_map/collective plumbing lives ONLY in mesh.py; the three
mode modules are thin, named views of it.
"""

from cruise_control_tpu.parallel.grid import GridEngine
from cruise_control_tpu.parallel.mesh import (
    MODEL_AXIS,
    RESTART_AXIS,
    MeshEngine,
    default_mesh,
    grid_mesh,
    model_mesh,
    normalize_mesh,
    shard_map_unchecked,
)
from cruise_control_tpu.parallel.model_shard import ShardPlan
from cruise_control_tpu.parallel.portfolio import portfolio_run
from cruise_control_tpu.parallel.sharded import ShardedEngine

__all__ = [
    "GridEngine",
    "MODEL_AXIS",
    "MeshEngine",
    "RESTART_AXIS",
    "ShardPlan",
    "ShardedEngine",
    "default_mesh",
    "grid_mesh",
    "model_mesh",
    "normalize_mesh",
    "portfolio_run",
    "shard_map_unchecked",
]
