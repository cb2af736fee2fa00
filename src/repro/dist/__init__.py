"""Distributed substrate: sharding rules, meshes, checkpointing, elasticity.

This package is the layer between the pure StarTrail math in
:mod:`repro.core` and everything that runs on real device grids:

  * :mod:`repro.dist.sharding`  — logical-axis -> mesh-axis rule sets and
    ``partition_tree`` (PartitionSpec trees from spec ``axes_tree``\\ s).
  * :mod:`repro.dist.meshes`    — ``refine_mesh`` (factor a flat ``model``
    axis into the concentric ``(sp_grp, sp_ring, sp_team)`` axes with
    ``P = C^2 * R``) and ``local_mesh_for_tests`` (forced-host-device CPU
    meshes).
  * :mod:`repro.dist.checkpoint`— atomic, optionally async tree
    save/restore with a ``latest_step`` scan for fault-tolerant restarts.
  * :mod:`repro.dist.elastic`   — ``plan_mesh`` (degrade gracefully on node
    loss) and ``StragglerDetector`` (windowed slow-step watermark).

The full contract (rule-set names, semantics, on-disk layout) is documented
in ``docs/ARCHITECTURE.md``.
"""

from repro.dist import checkpoint, elastic, meshes, sharding

__all__ = ["checkpoint", "elastic", "meshes", "sharding"]
