"""Shared SPMD constants — the single source of truth for mesh axis names.

Every ``Mesh`` constructor, ``PartitionSpec`` and collective call site in the
package names the actor axis through :data:`AXIS_ACTORS` instead of a string
literal, so the axis name is declared exactly once. The static-analysis
layers consume the same declaration: ``tools/rxgblint``'s SPMD002 mesh-axis
catalog and ``tools/rxgbverify``'s jaxpr schedule checks both resolve
``AXIS_*`` constants from this module by AST (never importing it), which is
why the module must stay stdlib-only with plain string assignments at module
scope — no computed values, no imports that drag in jax.
"""

#: the data-parallel mesh axis: one slot per logical actor rank (the
#: TPU-native replacement for the reference's one-OS-process-per-actor
#: topology; see engine.py module docstring)
AXIS_ACTORS = "actors"

#: the feature-parallel mesh axis (``feature_parallel`` > 1): histogram
#: feature columns are partitioned over this axis so each chip builds and
#: allreduces only its [N/R, F/C] tile. Histograms psum over
#: :data:`AXIS_ACTORS` only; this axis carries the tiny per-node best-split
#: election gather and the winning feature's bin-column broadcast (see
#: ops/feature_shard.py FeatureShard).
AXIS_FEATURES = "features"

#: synthesized per-row fill for an optional column absent on SOME shards
#: (or streamed chunks) while present on others — the ONE table consumed by
#: both the materialized concat (``engine._concat_shards``) and the
#: streamed ingest (``stream/ingest._concat_optional``), so the
#: streamed/materialized parity contract cannot drift column by column.
#: (qid is absent: its -1 fill is materialized-only — streamed qid gates.)
SHARD_COLUMN_FILLS = {
    "label": 0.0,
    "weight": 1.0,
    "base_margin": 0.0,
    "label_lower_bound": 0.0,
    "label_upper_bound": float("inf"),
}

__all__ = ["AXIS_ACTORS", "AXIS_FEATURES", "SHARD_COLUMN_FILLS"]
