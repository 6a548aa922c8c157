"""Pluggable histogram providers + the feature-parallel shard context.

Histogram construction used to be selected by a ``hist_impl`` STRING that
was re-interpreted at three separate layers (``engine.resolve_hist_impl``,
the branch ladder in ``ops/grow.py``'s per-level ``_build_raw``, and
``ops/histogram.py``'s ``build_histogram``). This module replaces that
spread with one protocol object: a :class:`HistogramProvider` owns the
whole decision of HOW a ``[n_nodes, F, n_bins+1, 2]`` gradient histogram is
accumulated from (possibly compacted, possibly presorted) rows, and the
growers are provider-blind. Providers are registered by name, so an
alternative implementation (a future kernel, a debugging reference, an A/B
candidate in bench.py) plugs in by registration instead of by editing the
dispatch ladders:

    register_histogram_provider("mine", MyProvider)
    params = {"hist_impl": "mine", ...}

Every provider is a frozen dataclass (hashable — it rides inside the
jit-static :class:`~xgboost_ray_tpu.ops.grow.GrowConfig`-adjacent closures)
constructed with the two knobs all builds share: ``precision`` (the MXU
accumulation contract, see ``ops/histogram.py``) and ``chunk`` (row-chunk
length for the scanning builds).

The second half of this module is :class:`FeatureShard`: the trace-time
context of the 2D row x feature mesh (``feature_parallel`` > 1). It names
the feature mesh axis and carries the three collective helpers the sharded
growers need — the shard-0 broadcast of histogram-derived node totals, the
owner-broadcast of a winning feature's bin column (one ``[N]`` psum per
level, so partition update stays O(rows) not O(rows x F)), and global
feature-index arithmetic. All cross-shard traffic it emits rides the
feature axis; the histogram allreduce itself stays on the actors axis.
"""

import dataclasses
from typing import Optional, Tuple, Type

import jax
import jax.numpy as jnp

from xgboost_ray_tpu.ops.histogram import (
    hist_onehot,
    hist_partition,
    hist_partition_presorted,
    hist_scatter,
)


def _gather_rows(bins, gh, rows_sel):
    """Materialize a compacted row selection for gather-based builds.

    ``rows_sel`` indexes the FULL bins/gh with the sentinel ``n`` for unused
    slots; sentinel slots clamp to the last row with zeroed gh so they
    contribute nothing. ``None`` passes the full arrays through.
    """
    if rows_sel is None:
        return bins, gh
    n = bins.shape[0]
    rows_c = jnp.minimum(rows_sel, n - 1)
    ok = (rows_sel < n)[:, None].astype(gh.dtype)
    return bins[rows_c], gh[rows_c] * ok


#: the widest build a grower can ask for: the deepest level of the deepest
#: tree params.py admits (max_depth 14). The K-lane checks ask
#: ``uses_order`` with it to learn whether a provider ever reads an order.
WIDEST_BUILD_NODES = 1 << 13


@dataclasses.dataclass(frozen=True)
class HistogramProvider:
    """One histogram build strategy behind a uniform interface.

    ``build`` returns the ``[n_nodes, F_local, n_bins_total, 2]`` float32
    histogram for one tree level (or lossguide step). The grower supplies
    whatever row layout it maintains; a provider consumes what it needs:

    * ``pos`` — per-row (or per-selected-slot) node index, always present;
    * ``order``/``counts`` — rows stably sorted by node + per-node counts,
      maintained by the grower iff some level of the tree builds from them,
      and handed to the builds for which :meth:`uses_order` is True;
    * ``rows_sel`` — a compacted row-id view (sibling subtraction's
      smaller-child selection or a sampling selection), sentinel ``n`` for
      unused slots. Presorted builds consume it directly as the row order;
      gather builds materialize it first.
    """

    precision: str = "highest"
    chunk: int = 8192

    #: registry key (subclasses override)
    name = "base"

    def uses_order(self, n_nodes: int) -> bool:
        """Does a build over ``n_nodes`` node slots read the presorted
        order/counts layout (the O(N) stable segment split the grower then
        keeps across levels)? The grower asks once a level with the fan-out
        it is about to build, and tracks an order iff any level says yes."""
        return False

    def build(self, bins, gh, pos, n_nodes, n_bins_total, *, order=None,
              counts=None, rows_sel=None):
        raise NotImplementedError


@dataclasses.dataclass(frozen=True)
class ScatterHistogram(HistogramProvider):
    """One flat XLA scatter-add — correct everywhere, the CPU default."""

    name = "scatter"

    def build(self, bins, gh, pos, n_nodes, n_bins_total, *, order=None,
              counts=None, rows_sel=None):
        bins_g, gh_g = _gather_rows(bins, gh, rows_sel)
        return hist_scatter(bins_g, gh_g, pos, n_nodes, n_bins_total)


@dataclasses.dataclass(frozen=True)
class OnehotHistogram(HistogramProvider):
    """Row-chunked one-hot x (grad, hess) matmuls on the MXU."""

    name = "onehot"

    def build(self, bins, gh, pos, n_nodes, n_bins_total, *, order=None,
              counts=None, rows_sel=None):
        bins_g, gh_g = _gather_rows(bins, gh, rows_sel)
        return hist_onehot(bins_g, gh_g, pos, n_nodes, n_bins_total,
                           chunk=self.chunk, precision=self.precision)


@dataclasses.dataclass(frozen=True)
class PartitionHistogram(HistogramProvider):
    """Node-contiguous presorted blocks: FLOPs independent of node fan-out."""

    name = "partition"

    def uses_order(self, n_nodes: int) -> bool:
        return True

    def build(self, bins, gh, pos, n_nodes, n_bins_total, *, order=None,
              counts=None, rows_sel=None):
        order_in = rows_sel if rows_sel is not None else order
        if order_in is None:
            # no maintained layout (standalone callers): sort here
            return hist_partition(bins, gh, pos, n_nodes, n_bins_total,
                                  precision=self.precision)
        return hist_partition_presorted(
            bins, gh, order_in, counts, n_nodes, n_bins_total,
            precision=self.precision,
        )


#: widest right-hand side, in matmul columns (2 per node slot), that
#: ``mixed`` gives to the dense build; beyond it the presorted blocks. Set
#: from the v5e at 11M x 28 x 256 (PERF.md §6, PR 30): the dense build costs
#: 469 / 921 / 2,010 / 3,980 ms at 256 / 512 / 1,024 / 2,048 node slots; the
#: compacted presorted build costs 765 ms at 256 slots and no less with more
#: blocks, and needs an order update of 185 ms or more a level. So dense wins
#: through 512 slots (921 < 765 + 185); at 1,024 slots the presorted side was
#: not measured (158 s of compile a point) and keeps the level.
DENSE_MAX_COLUMNS = 1024


@dataclasses.dataclass(frozen=True)
class MixedHistogram(HistogramProvider):
    """The chip's default: the dense one-hot build (``hist_onehot``: one pass
    over all rows, no row order, the node on the matmul's right-hand side)
    while a level's ``2 * n_nodes`` columns fit ``DENSE_MAX_COLUMNS``, the
    presorted node-uniform blocks beyond. Under sibling subtraction every
    level of a tree of ``max_depth <= 11`` is under the crossover, and such
    a tree keeps no order at all (``build_tree`` asks :meth:`uses_order`
    with each level's fan-out)."""

    name = "mixed"

    def uses_order(self, n_nodes: int) -> bool:
        return 2 * n_nodes > DENSE_MAX_COLUMNS

    def build(self, bins, gh, pos, n_nodes, n_bins_total, *, order=None,
              counts=None, rows_sel=None):
        form = PartitionHistogram if self.uses_order(n_nodes) else OnehotHistogram
        return form(self.precision, self.chunk).build(
            bins, gh, pos, n_nodes, n_bins_total,
            order=order, counts=counts, rows_sel=rows_sel,
        )


@dataclasses.dataclass(frozen=True)
class VmappedKProvider(HistogramProvider):
    """Histogram build seam for the vmapped-K (multi-candidate HPO) round.

    Under ``jax.vmap`` over the lane axis the delegate build's scatter/
    matmul primitives batch mechanically — each lane accumulates its own
    ``[n_nodes, F, nbt, 2]`` histogram from its own (sampled) gh — so the
    default implementation simply delegates to a base provider and lets
    vmap's batching rules do the stacking. The point of routing through the
    registry anyway is the seam: a TPU kernel that folds the K axis into
    one scatter (lane-major node index ``k * n_nodes + pos``) registers a
    subclass here and every grower picks it up through ``cfg.hist_provider``
    with zero grower changes, exactly like any other ``hist_impl``.

    ``base`` must name a provider that reads no order at any fan-out
    (``uses_order(WIDEST_BUILD_NODES)`` False): the presorted-partition
    layouts maintain ONE row order per tree, but vmapped lanes sample and
    route rows independently, so a shared order table would be wrong for
    every lane but one.
    """

    base: str = "scatter"

    name = "vmapped_k"

    def delegate(self) -> HistogramProvider:
        prov = resolve_hist_provider(self.base, self.precision, self.chunk)
        if prov.uses_order(WIDEST_BUILD_NODES):
            raise NotImplementedError(
                f"hist_impl {self.base!r} maintains a presorted row order "
                "and cannot back the vmapped-K build (per-lane row "
                "routing diverges); use a gather-based provider"
            )
        return prov

    def build(self, bins, gh, pos, n_nodes, n_bins_total, *, order=None,
              counts=None, rows_sel=None):
        return self.delegate().build(
            bins, gh, pos, n_nodes, n_bins_total,
            order=order, counts=counts, rows_sel=rows_sel,
        )


def vmapped_k_impl(base: str) -> str:
    """Return (registering on first use) the ``hist_impl`` name of the
    vmapped-K provider delegating to ``base`` — e.g. ``vmapped_k[scatter]``.
    The engine's vmapped path resolves its configured impl through this so
    the lane-batched build is a first-class registry citizen."""
    if base == "auto":
        base = default_hist_impl()
    name = f"vmapped_k[{base}]"
    if name not in _PROVIDERS:
        cls = dataclasses.make_dataclass(
            f"VmappedK_{base}",
            [("base", str, dataclasses.field(default=base))],
            bases=(VmappedKProvider,),
            frozen=True,
        )
        cls.name = name
        register_histogram_provider(name, cls)
    return name


_PROVIDERS = {
    cls.name: cls
    for cls in (ScatterHistogram, OnehotHistogram, PartitionHistogram,
                MixedHistogram, VmappedKProvider)
}


def register_histogram_provider(
    name: str, cls: Type[HistogramProvider], overwrite: bool = False
) -> None:
    """Register a provider class under ``name`` (then usable as a
    ``hist_impl`` value). ``cls`` must construct from ``(precision, chunk)``
    keywords. Re-registering a builtin requires ``overwrite=True``."""
    if not overwrite and name in _PROVIDERS:
        raise ValueError(f"histogram provider {name!r} already registered")
    if name == "auto":
        raise ValueError("'auto' is the backend-default selector, not a "
                         "registrable provider name")
    _PROVIDERS[name] = cls


def available_hist_impls() -> Tuple[str, ...]:
    """Valid ``hist_impl`` values: 'auto' plus every registered provider."""
    return ("auto",) + tuple(sorted(_PROVIDERS))


def default_hist_impl() -> str:
    """Backend policy behind ``hist_impl='auto'``: scatter on CPU (parity
    tests), mixed on accelerators (the dense one-hot MXU build while the
    level's columns fit the MXU, node-contiguous partitioning beyond)."""
    return "scatter" if jax.default_backend() == "cpu" else "mixed"


def resolve_hist_provider(
    impl: str, precision: str = "highest", chunk: int = 8192
) -> HistogramProvider:
    """The one string -> provider resolution point."""
    if impl == "auto":
        impl = default_hist_impl()
    cls = _PROVIDERS.get(impl)
    if cls is None:
        # defense-in-depth behind parse_params: a typo'd or removed impl
        # (e.g. the deleted 'pallas') must not silently become scatter
        raise ValueError(
            f"unknown histogram provider {impl!r}; registered: "
            f"{sorted(_PROVIDERS)}"
        )
    return cls(precision=precision, chunk=chunk)


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
