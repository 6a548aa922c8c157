"""The feature-parallel shard context.

:class:`FeatureShard` is the trace-time context of the 2D row x feature mesh
(``feature_parallel`` > 1). It names the feature mesh axis and carries the
three collective helpers the sharded growers need — the shard-0 broadcast of
histogram-derived node totals, the owner-broadcast of a winning feature's bin
column (one ``[N]`` psum per level, so partition update stays O(rows) not
O(rows x F)), and global feature-index arithmetic. All cross-shard traffic it
emits rides the feature axis; the histogram allreduce itself stays on the
actors axis.
"""

import jax
import jax.numpy as jnp


class FeatureShard:
    """Trace-time context of the feature-parallel mesh axis.

    Constructed by the engine per traced round body when
    ``feature_parallel`` > 1 and threaded through the growers; ``None``
    means the 1D row mesh and every consumer takes its legacy path (the
    C=1-is-bitwise contract). All methods are called under ``shard_map``
    over the 2D mesh, where ``bins`` is this chip's ``[N/R, F_pad/C]``
    tile and feature indices in split records are GLOBAL (padded) indices.
    """

    def __init__(self, axis: str, num_shards: int, f_padded: int,
                 f_real: int, counter=None):
        self.axis = axis
        self.num_shards = int(num_shards)
        #: padded global feature count (a multiple of ``num_shards``)
        self.f_padded = int(f_padded)
        #: real (unpadded) feature count
        self.f_real = int(f_real)
        #: AllreduceBytes counter with the FEATURE-axis ring extent (the
        #: actors-axis traffic is counted by the growers' own counter)
        self.counter = counter

    def offset(self, f_local: int):
        """This shard's first global feature index (traced)."""
        return jax.lax.axis_index(self.axis) * f_local

    def slice_cols(self, arr, f_local: int, axis: int = 0):
        """Slice a global per-feature array down to this shard's columns."""
        return jax.lax.dynamic_slice_in_dim(
            arr, self.offset(f_local), f_local, axis=axis
        )

    def bcast_from_shard0(self, x):
        """Replicate shard 0's value across the feature axis.

        Used for histogram-READOUT node totals (``hist[:, 0]`` bucket
        sums): every shard reads a different feature column, whose f32
        rounding differs, and node totals feeding leaf weights must be
        identical on every chip — so the column the 1D program reads
        (global feature 0, owned by shard 0) wins.
        """
        if self.counter is not None:
            self.counter.add_allreduce(x)
        is_shard0 = jax.lax.axis_index(self.axis) == 0
        return jax.lax.psum(
            jnp.where(is_shard0, x, jnp.zeros_like(x)), self.axis
        )

    def bin_column(self, bins, f_global):
        """Every row's bin value at a GLOBAL feature index — the winning
        feature's bin column, broadcast from its owner shard.

        ``f_global`` is [N] int32 (per-row, typically ``feature[pos]``).
        Exactly one shard owns each feature, so the masked psum is an
        owner-broadcast: one [N] int32 collective per call — O(rows), the
        partition-update cost contract of the 2D mesh.
        """
        f_local = bins.shape[1]
        off = self.offset(f_local)
        local_f = jnp.clip(f_global - off, 0, f_local - 1)
        bv = jnp.take_along_axis(
            bins.astype(jnp.int32), local_f[:, None], axis=1
        )[:, 0]
        own = (f_global >= off) & (f_global < off + f_local)
        contrib = jnp.where(own, bv, 0)
        if self.counter is not None:
            self.counter.add_allreduce(contrib)
        return jax.lax.psum(contrib, self.axis)
