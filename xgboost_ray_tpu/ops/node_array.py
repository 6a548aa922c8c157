"""Breadth-first node-array forest layout: FIL-style level-synchronous walk.

The padded-heap layout (``grow.py``) stores each tree as its own
``[heap]`` vector and ``ops/predict.py`` walks it depth-first per tree
under a ``vmap`` — every level of the walk gathers from a *different*
region of every tree's private heap. The GPU inference analysis of
XGBoost's forests (arXiv:1806.11248, the layout RAPIDS FIL productized)
observes that batched tree traversal is memory-bound and wants the
opposite layout: **struct-of-arrays with all trees' level-k nodes
contiguous**, so one traversal step for the whole ensemble is a few wide
vectorized gathers from one contiguous slab instead of T strided
per-tree walks.

This module is that layout for our padded heaps. It is a *pure
permutation* of the heap — node ``(tree t, level k, slot p)`` lives at

    ``level_base(k) + t * 2**k + p``  with  ``level_base(k) = T * (2**k - 1)``

and corresponds to per-tree heap index ``2**k - 1 + p`` — so the walk
below performs the *same* elementwise routing arithmetic on the *same*
float values as ``predict.py``'s ``_walk_one_tree``: every row reaches the
same leaves (leaf outputs are integer-identical), and the summed margins
agree to the serve layer's cross-program parity bound (pinned by
``tests/test_serve_pool.py``). Only the
six fields the raw-x walk reads are materialized (feature, split_bin,
threshold, default_left, is_leaf, value); the SHAP kernels need
``base_weight``/``cover`` path statistics that do not level-map, so
``contribs`` stays on the heap program (the serve layer routes it
there).
"""

import functools
from typing import NamedTuple, Optional

import numpy as np

import jax
import jax.numpy as jnp

from xgboost_ray_tpu.ops.grow import Tree, cat_mask_const as _cat_mask_const


class NodeForest(NamedTuple):
    """Breadth-first node-array ensemble: each field flat ``[T * heap]``,
    level-major (all trees' level-k nodes contiguous, ``2**k`` per tree)."""

    feature: jnp.ndarray       # int32  [T * heap]
    split_bin: jnp.ndarray     # int32  [T * heap]
    threshold: jnp.ndarray     # float32[T * heap]
    default_left: jnp.ndarray  # bool   [T * heap]
    is_leaf: jnp.ndarray       # bool   [T * heap]
    value: jnp.ndarray         # float32[T * heap]


def _level_base(k: int, num_trees: int) -> int:
    return num_trees * ((1 << k) - 1)


def forest_to_node_array(forest: Tree, max_depth: int) -> NodeForest:
    """Permute a stacked padded-heap forest (fields ``[T, heap]``) into the
    level-major node-array layout. Host-side numpy; called once per model
    at predictor construction."""
    if forest.left is not None:
        raise NotImplementedError(
            "the node_array serving layout is the level-major permutation "
            "of a padded heap; a forest in the linked layout "
            "(grow_policy='lossguide', max_depth=0: no depth bound) has no "
            "such heap. Serve it with layout='heap' (the walk takes both "
            "tree layouts) or train with a positive max_depth."
        )
    feature = np.asarray(forest.feature)
    t, heap = feature.shape
    if heap != (1 << (max_depth + 1)) - 1:
        raise ValueError(
            f"heap width {heap} does not match max_depth {max_depth} "
            f"(expected {(1 << (max_depth + 1)) - 1})"
        )

    def permute(field, dtype):
        arr = np.asarray(field)
        # slab k is arr[:, 2^k-1 : 2^(k+1)-1] flattened tree-major: the
        # reshape(-1) of the [T, 2^k] slice lands (t, p) at t*2^k + p,
        # exactly the position formula the walk gathers with
        return np.concatenate([
            arr[:, (1 << k) - 1:(1 << (k + 1)) - 1].reshape(-1)
            for k in range(max_depth + 1)
        ]).astype(dtype, copy=False)

    return NodeForest(
        feature=permute(forest.feature, np.int32),
        split_bin=permute(forest.split_bin, np.int32),
        threshold=permute(forest.threshold, np.float32),
        default_left=permute(forest.default_left, bool),
        is_leaf=permute(forest.is_leaf, bool),
        value=permute(forest.value, np.float32),
    )


def _num_trees(na: NodeForest, max_depth: int) -> int:
    return int(na.value.shape[0]) // ((1 << (max_depth + 1)) - 1)


def _step_right_na(na, pos, xv, f, cat_mask):
    """``predict._step_right`` on node-array gathers: identical elementwise
    ops on identical values, so routing decisions are bitwise the same."""
    present_right = xv >= na.threshold[pos]
    if cat_mask is not None:
        code = jnp.round(xv).astype(jnp.int32)
        present_right = jnp.where(
            cat_mask[f], code != na.split_bin[pos], present_right
        )
    return jnp.where(jnp.isnan(xv), ~na.default_left[pos], present_right)


def _walk_levels(na: NodeForest, x: jnp.ndarray, max_depth: int, cat_mask):
    """Level-synchronous ensemble walk. x: [N, F] raw (may contain NaN).

    Returns ``(leaf_value [T, N], leaf_heap_idx [T, N])`` — the per-tree
    leaf value and per-tree heap index each row lands on, matching the
    depth-first walk exactly: a row freezes at its first leaf; a row that
    never meets a leaf reads the level-``max_depth`` node it reaches, just
    as ``_walk_one_tree`` returns ``value[idx]`` for its final ``idx``.
    """
    n = x.shape[0]
    t = _num_trees(na, max_depth)
    row = jnp.arange(n, dtype=jnp.int32)[None, :]      # [1, N]
    t_col = jnp.arange(t, dtype=jnp.int32)[:, None]    # [T, 1]
    p = jnp.zeros((t, n), jnp.int32)                   # slot within level
    done = jnp.zeros((t, n), bool)
    val = jnp.zeros((t, n), jnp.float32)
    hidx = jnp.zeros((t, n), jnp.int32)
    num_features = x.shape[1]
    for k in range(max_depth):
        pos = _level_base(k, t) + (t_col << k) + p     # [T, N] flat gather
        leaf_here = na.is_leaf[pos]
        newly = leaf_here & ~done
        val = jnp.where(newly, na.value[pos], val)
        hidx = jnp.where(newly, ((1 << k) - 1) + p, hidx)
        done = done | leaf_here
        f = jnp.clip(na.feature[pos], 0, num_features - 1)
        xv = x[row, f]                                  # [T, N] row gather
        go_right = _step_right_na(na, pos, xv, f, cat_mask)
        p = jnp.where(done, p, 2 * p + go_right.astype(jnp.int32))
    pos = _level_base(max_depth, t) + (t_col << max_depth) + p
    val = jnp.where(done, val, na.value[pos])
    hidx = jnp.where(done, hidx, ((1 << max_depth) - 1) + p)
    return val, hidx


@functools.partial(jax.jit, static_argnames=("max_depth", "num_outputs", "num_parallel_tree", "ntree_limit", "cat_features"))
def predict_margin_na(
    na: NodeForest,
    x: jnp.ndarray,            # [N, F] float32 raw features
    base_margin: jnp.ndarray,  # [N, K] starting margin
    max_depth: int,
    num_outputs: int,
    num_parallel_tree: int = 1,
    ntree_limit: int = 0,
    tree_weights: Optional[jnp.ndarray] = None,  # [T] per-tree scale (DART)
    cat_features: tuple = (),
) -> jnp.ndarray:
    """Node-array twin of ``predict.predict_margin``: same leaf matrix,
    same accumulation expression. The compiler may still order the tree sum
    differently in the two programs, so the [N, K] margins agree to
    ``serve.predictor.PARITY_ULPS``, not bitwise."""
    t = _num_trees(na, max_depth)
    cat_mask = _cat_mask_const(cat_features, x.shape[1])
    leaf, _ = _walk_levels(na, x, max_depth, cat_mask)  # [T, N]
    if tree_weights is not None:
        leaf = leaf * tree_weights[:, None]
    if ntree_limit:
        keep = jnp.arange(t) < ntree_limit
        leaf = jnp.where(keep[:, None], leaf, 0.0)
    if num_outputs == 1:
        margin = base_margin[:, 0] + leaf.sum(axis=0) / num_parallel_tree
        return margin[:, None]
    cls = (jnp.arange(t) // num_parallel_tree) % num_outputs
    onehot = jax.nn.one_hot(cls, num_outputs, dtype=leaf.dtype)  # [T, K]
    return base_margin + (leaf.T @ onehot) / num_parallel_tree


@functools.partial(jax.jit, static_argnames=("max_depth", "cat_features"))
def predict_leaf_index_na(
    na: NodeForest, x: jnp.ndarray, max_depth: int, cat_features: tuple = ()
) -> jnp.ndarray:
    """Node-array twin of ``predict.predict_leaf_index``: per-tree leaf
    heap index per row, [N, T] int32 — integer-identical by construction."""
    cat_mask = _cat_mask_const(cat_features, x.shape[1])
    _, hidx = _walk_levels(na, x, max_depth, cat_mask)
    return hidx.T.astype(jnp.int32)
