"""Candidate-sharded optimization: one chain, K candidates split over devices.

``ShardedEngine`` is the 1-chain view of the shared mesh engine layer
(parallel/mesh.py): ``Mesh((restart=1, model=n))``.  Each step the full-K
candidate stream is drawn from the replicated key, each device evaluates
objective deltas for its K/n slice, and one tiled ``all_gather`` of the
candidate COLUMNS reassembles the full-K bundle for the global conflict
resolution that runs identically everywhere.  The model and carry are
replicated, so a 1-device and an n-device run of the same seeded anneal
produce byte-identical placements (mesh.py module docstring).

This file is deliberately thin: every jit/shard_map/collective lives in
parallel/mesh.py, shared verbatim with grid.py and portfolio.py.  The
pre-round-6 replica/partition-axis sharding implementation that used to
live here (per-shard RNG streams, psum'd aggregate refresh) was replaced —
it made 1-vs-N parity impossible and ran ~22% slower than the plain engine
at n=1.  Replica/partition-axis sharding now exists as
the mesh engine's sharded-MODEL mode (parallel/model_shard.py +
``MeshEngine(model_shard_min_partitions=...)``), which keeps every RNG
draw replicated and resolves row gathers by ownership psums — parity
preserved, per-chip model memory ~1/n.

Reference analog: none — the reference optimizer is a single-threaded Java
loop (analyzer/goals/AbstractGoal.java:66-107).
"""

from __future__ import annotations

from cruise_control_tpu.parallel.mesh import (
    MODEL_AXIS,
    MeshEngine,
    model_mesh,
    shard_map_unchecked,
)

__all__ = ["MODEL_AXIS", "ShardedEngine", "model_mesh", "shard_map_unchecked"]


class ShardedEngine(MeshEngine):
    """One annealing chain whose candidate axis is sharded over the mesh.

    Constructor contract (state, chain, mesh, constraint, options, config,
    bucket) is inherited unchanged from MeshEngine; a 1D ``(model,)`` mesh
    (``model_mesh()``) is normalized to the canonical 2D ``(restart=1,
    model=n)`` layout.  ``run()`` executes the plain engine's fused
    multi-round schedule; at n=1 the traced program IS the plain fused
    program (no collective is emitted)."""
