"""The gather / scatter forms that ``build_tree``'s level loop used before the
dense one-hot forms (``grow.lookup_by_node``, ``grow.bin_of_feature``,
``histogram.node_sums_dense`` / ``node_counts_dense``): kept under tests/ as
the reference the dense forms are held to, helper by helper and tree by tree.
"""

import contextlib

import jax.numpy as jnp

from xgboost_ray_tpu.ops import grow
from xgboost_ray_tpu.ops.histogram import node_sums


def lookup_by_node_gather(pos, *tables):
    return [t[pos] for t in tables]


def bin_of_feature_gather(bins, f_of_row):
    return jnp.take_along_axis(
        bins.astype(jnp.int32), f_of_row[:, None], axis=1
    )[:, 0]


def node_sums_scatter(gh, pos, n_nodes):
    """``node_sums`` under the dense forms' convention that a row at slot -1
    (a finished row) adds nowhere: its gh is zeroed, as the level loop did."""
    live = (pos >= 0)[:, None]
    return node_sums(
        jnp.where(live, gh, jnp.zeros((), gh.dtype)), jnp.maximum(pos, 0), n_nodes
    )


def node_counts_scatter(pos, n_nodes):
    return jnp.zeros((n_nodes,), jnp.int32).at[jnp.maximum(pos, 0)].add(
        (pos >= 0).astype(jnp.int32)
    )


@contextlib.contextmanager
def gather_form():
    """``build_tree`` traced inside this block routes rows, counts live rows
    and sums nodes with the per-row gathers and scatter-adds. Yields the set
    of reference forms a trace has used so far, so that a caller can tell a
    fresh trace from a cached one."""
    forms = {
        "lookup_by_node": lookup_by_node_gather,
        "bin_of_feature": bin_of_feature_gather,
        "node_sums_dense": node_sums_scatter,
        "node_counts_dense": node_counts_scatter,
    }
    saved = {name: getattr(grow, name) for name in forms}
    used = set()

    def noting(name, fn):
        def form(*args):
            used.add(name)
            return fn(*args)

        return form

    for name, fn in forms.items():
        setattr(grow, name, noting(name, fn))
    try:
        yield used
    finally:
        for name, fn in saved.items():
            setattr(grow, name, fn)
