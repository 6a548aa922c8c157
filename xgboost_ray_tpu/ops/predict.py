"""Vectorized tree-ensemble prediction on raw feature values.

TPU-native replacement for xgboost's C++ prediction kernel
(``model.predict(local_data)`` in the reference actor,
``xgboost_ray/main.py:795-810``).

The tree layouts of ``grow.py`` (padded heap, or linked for a leaf-wise
forest no depth bounds) make prediction a fixed-length gather walk:
``max_depth`` steps of (feature gather, compare, child index), identical for
every row — no data-dependent control flow, so the whole ensemble walk jits
into one fused XLA program. Trees are vmapped; per-class routing for
multiclass sums tree outputs round-robin into K margins.

``predict_margin``, ``predict_leaf_index`` and ``predict_contribs`` (Saabas)
walk both layouts through ``child_index``; exact TreeSHAP
(``predict_contribs_exact``, ``predict_interactions``) enumerates the bottom
slots of a padded heap and refuses a linked forest (``require_heap``).
"""

import functools
from typing import Optional

import jax
import jax.numpy as jnp

from xgboost_ray_tpu.ops.grow import (
    Tree,
    cat_mask_const as _cat_mask_const,
    child_index,
    walk_to_leaf,
)


def require_heap(forest: Tree, what: str) -> None:
    """Refuse a linked forest where ``what`` needs the padded heap."""
    if forest.left is not None:
        raise NotImplementedError(
            f"{what} is not implemented for the linked tree layout of a "
            f"forest grown with grow_policy='lossguide', max_depth=0 (no "
            f"depth bound): it enumerates the slots of a depth-bounded "
            f"padded heap. Train with a positive max_depth for it."
        )


def _step_right(tree, idx, xv, f, cat_mask):
    """Routing rule shared by every raw-x walk: numeric = threshold compare,
    categorical = code equality (candidate category goes left), missing =
    learned default."""
    present_right = xv >= tree.threshold[idx]
    if cat_mask is not None:
        code = jnp.round(xv).astype(jnp.int32)
        present_right = jnp.where(
            cat_mask[f], code != tree.split_bin[idx], present_right
        )
    return jnp.where(jnp.isnan(xv), ~tree.default_left[idx], present_right)


def _walk_one_tree(
    tree: Tree, x: jnp.ndarray, max_depth: int, cat_mask=None
) -> jnp.ndarray:
    """x: [N, F] raw (may contain NaN). Returns leaf values [N]."""
    return tree.value[_leaf_of_rows(tree, x, max_depth, cat_mask)]


def _leaf_of_rows(tree: Tree, x: jnp.ndarray, max_depth: int, cat_mask):
    """Leaf slot of every row of raw ``x`` in one tree."""
    n, num_features = x.shape

    def go_right_at(idx):
        f = jnp.clip(tree.feature[idx], 0, num_features - 1)
        xv = jnp.take_along_axis(x, f[:, None], axis=1)[:, 0]
        return _step_right(tree, idx, xv, f, cat_mask)

    return walk_to_leaf(tree, jnp.zeros((n,), jnp.int32), max_depth,
                        go_right_at)


@functools.partial(jax.jit, static_argnames=("max_depth", "num_outputs", "num_parallel_tree", "ntree_limit", "cat_features"))
def predict_margin(
    forest: Tree,  # stacked trees: each field [T, heap]
    x: jnp.ndarray,  # [N, F] float32 raw features
    base_margin: jnp.ndarray,  # [N, K] starting margin
    max_depth: int,
    num_outputs: int,
    num_parallel_tree: int = 1,
    ntree_limit: int = 0,
    tree_weights: Optional[jnp.ndarray] = None,  # [T] per-tree scale (DART)
    cat_features: tuple = (),
) -> jnp.ndarray:
    """Sum leaf values of all trees into per-class margins. Returns [N, K]."""
    t = forest.feature.shape[0]
    cat_mask = _cat_mask_const(cat_features, x.shape[1])
    leaf = jax.vmap(lambda tr: _walk_one_tree(tr, x, max_depth, cat_mask))(forest)  # [T, N]
    if tree_weights is not None:
        leaf = leaf * tree_weights[:, None]
    if ntree_limit:
        keep = jnp.arange(t) < ntree_limit
        leaf = jnp.where(keep[:, None], leaf, 0.0)
    if num_outputs == 1:
        margin = base_margin[:, 0] + leaf.sum(axis=0) / num_parallel_tree
        return margin[:, None]
    # tree t belongs to class (t // num_parallel_tree) % K (round-major layout)
    cls = (jnp.arange(t) // num_parallel_tree) % num_outputs
    onehot = jax.nn.one_hot(cls, num_outputs, dtype=leaf.dtype)  # [T, K]
    return base_margin + (leaf.T @ onehot) / num_parallel_tree


@functools.partial(jax.jit, static_argnames=("max_depth", "num_outputs", "num_parallel_tree", "ntree_limit", "cat_features"))
def predict_contribs(
    forest: Tree,  # stacked trees: each field [T, heap]
    x: jnp.ndarray,  # [N, F] float32 raw features
    max_depth: int,
    num_outputs: int,
    num_parallel_tree: int = 1,
    ntree_limit: int = 0,
    tree_weights: Optional[jnp.ndarray] = None,
    cat_features: tuple = (),
) -> jnp.ndarray:
    """Per-feature prediction contributions (xgboost ``pred_contribs`` with
    ``approx_contribs=True`` — Saabas path attribution; reference surface:
    ``xgb.Booster.predict`` passed through at ``xgboost_ray/main.py:795-810``).

    Walking x's path, each split's expected-value change
    ``base_weight[child] - base_weight[node]`` is credited to the split
    feature; the bias column gets ``base_weight[root]``. The credits telescope,
    so each row of the result sums exactly to that row's margin (minus the
    base-score offset, which the caller adds to the bias column).

    Returns [T-summed] contributions ``[N, K, F+1]`` (bias last).

    Trees are accumulated with ``lax.scan`` (not vmap) so peak memory is the
    O(N*K*F) accumulator, never a [T, N, F] intermediate.
    """
    n, num_features = x.shape
    t = forest.feature.shape[0]
    cat_mask = _cat_mask_const(cat_features, num_features)

    scale = jnp.ones((t,), jnp.float32)
    if tree_weights is not None:
        scale = scale * tree_weights
    if ntree_limit:
        scale = jnp.where(jnp.arange(t) < ntree_limit, scale, 0.0)
    scale = scale / num_parallel_tree
    cls = (jnp.arange(t) // num_parallel_tree) % num_outputs
    onehot = jax.nn.one_hot(cls, num_outputs, dtype=jnp.float32) * scale[:, None]  # [T, K]

    def tree_step(acc, args):
        tree, oh = args  # Tree of [heap] fields, [K]
        feat_acc, bias_acc = acc
        idx = jnp.zeros((n,), jnp.int32)
        contrib = jnp.zeros((n, num_features), jnp.float32)
        for _ in range(max_depth):
            stepped = ~tree.is_leaf[idx] & (tree.feature[idx] >= 0)
            f = jnp.clip(tree.feature[idx], 0, num_features - 1)
            xv = jnp.take_along_axis(x, f[:, None], axis=1)[:, 0]
            go_right = _step_right(tree, idx, xv, f, cat_mask)
            nxt = jnp.where(stepped, child_index(tree, idx, go_right), idx)
            delta = jnp.where(
                stepped, tree.base_weight[nxt] - tree.base_weight[idx], 0.0
            )
            contrib = contrib.at[jnp.arange(n), f].add(delta)
            idx = nxt
        feat_acc = feat_acc + jnp.einsum("nf,k->nkf", contrib, oh)
        bias_acc = bias_acc + tree.base_weight[0] * oh
        return (feat_acc, bias_acc), None

    acc0 = (
        jnp.zeros((n, num_outputs, num_features), jnp.float32),
        jnp.zeros((num_outputs,), jnp.float32),
    )
    (feat_part, bias_part), _ = jax.lax.scan(tree_step, acc0, (forest, onehot))
    bias = jnp.broadcast_to(bias_part[None, :, None], (n, num_outputs, 1))
    return jnp.concatenate([feat_part, bias], axis=2)


def _shap_weight_table(max_depth: int):
    """Ctab[m, k] = k! (m-1-k)! / m!  — the Shapley permutation weight for a
    coalition of size k among m players (0 outside k < m)."""
    import numpy as np

    fact = [1.0]
    for i in range(1, max_depth + 2):
        fact.append(fact[-1] * i)
    ctab = np.zeros((max_depth + 1, max(max_depth, 1)), np.float32)
    for m in range(1, max_depth + 1):
        for k in range(m):
            ctab[m, k] = fact[k] * fact[m - 1 - k] / fact[m]
    return jnp.asarray(ctab)


def _shap_path_data(tree: Tree, x: jnp.ndarray, slot: jnp.ndarray,
                    max_depth: int, cat_mask):
    """Root-to-leaf path data for one bottom slot of the padded heap.

    Every leaf is represented by exactly one *canonical* slot (the one whose
    remaining steps below the leaf all go left), so summing slot contributions
    enumerates each leaf once. Returns per-step lists over s in [0, D):
    features ``fs`` (scalar), zero-fractions ``zs`` (scalar, cover ratio),
    one-fractions ``os`` ([N], does x follow this branch), ``valids`` (scalar
    bool, real split on a canonical path) — duplicates already merged into
    their first occurrence (TreeSHAP's repeated-feature rule) — plus the leaf
    value ``v_leaf`` and player count ``m``.
    """
    n, num_features = x.shape
    d = max_depth
    nodes = [jnp.int32(0)]
    bits = []
    for s in range(d):
        b = ((slot >> (d - 1 - s)) & 1).astype(jnp.int32)
        bits.append(b)
        nodes.append(2 * nodes[-1] + 1 + b)
    leaf_found = jnp.zeros((), bool)
    leaf_d = jnp.int32(d)
    for depth, node in enumerate(nodes):
        hit = tree.is_leaf[node] & ~leaf_found
        leaf_d = jnp.where(hit, jnp.int32(depth), leaf_d)
        leaf_found = leaf_found | tree.is_leaf[node]
    canon = leaf_found
    for s in range(d):
        canon = canon & ((s < leaf_d) | (bits[s] == 0))
    v_leaf = jnp.stack([tree.value[i] for i in nodes])[leaf_d]

    zs, os_, fs, valids = [], [], [], []
    for s in range(d):
        i_n, i_c = nodes[s], nodes[s + 1]
        valid = canon & (s < leaf_d)
        f = jnp.clip(tree.feature[i_n], 0, num_features - 1)
        z = jnp.where(
            tree.cover[i_n] > 0.0,
            tree.cover[i_c] / jnp.maximum(tree.cover[i_n], 1e-12),
            0.0,
        )
        xv = jnp.take(x, f, axis=1)
        go_right = _step_right(tree, i_n, xv, f, cat_mask)
        o = (go_right.astype(jnp.int32) == bits[s]).astype(jnp.float32)
        zs.append(z)
        os_.append(o)
        fs.append(f)
        valids.append(valid)

    # merge repeated features into their first occurrence (z,o multiply)
    for s in range(1, d):
        merged = jnp.zeros((), bool)
        for j in range(s):
            can = valids[j] & valids[s] & (fs[j] == fs[s]) & ~merged
            zs[j] = jnp.where(can, zs[j] * zs[s], zs[j])
            os_[j] = jnp.where(can, os_[j] * os_[s], os_[j])
            merged = merged | can
        valids[s] = valids[s] & ~merged

    m = sum(v.astype(jnp.int32) for v in valids)
    return fs, zs, os_, valids, v_leaf, m, canon


def _poly_extend(q, z, o, valid):
    """Multiply coefficient array ``q`` [N, D+1] by (z + o*t) where valid."""
    shifted = jnp.concatenate([jnp.zeros_like(q[:, :1]), q[:, :-1]], axis=1)
    return jnp.where(valid, z * q + o[:, None] * shifted, q)


def _poly_unwind(q, z, o, max_depth: int):
    """Divide q [N, D+1] by (z + o*t); o is the 0/1 indicator [N].

    o == 1: downward recurrence r[k-1] = q[k] - z r[k];
    o == 0: r[k] = q[k] / z (guarded — z == 0 means the dead branch already
    zeroed the polynomial, so 0 is the correct quotient).
    """
    d = max_depth
    r1 = [None] * d
    acc = q[:, d]
    for k in range(d - 1, -1, -1):
        r1[k] = acc
        acc = q[:, k] - z * acc
    r1 = jnp.stack(r1, axis=1)  # [N, D]
    r0 = jnp.where(z > 0.0, q[:, :d] / jnp.maximum(z, 1e-12), 0.0)
    return jnp.where(o[:, None] > 0.5, r1, r0)


def _shap_one_tree(tree: Tree, x: jnp.ndarray, max_depth: int, cat_mask):
    """Exact TreeSHAP (Lundberg et al.) for one padded-heap tree.

    Returns (phi [N, F], expected_value scalar): phi rows satisfy the
    efficiency axiom  sum_f phi[n, f] = margin(x_n) - expected_value.
    """
    n, num_features = x.shape
    d = max_depth
    ctab = _shap_weight_table(d)

    def slot_contrib(slot):
        fs, zs, os_, valids, v_leaf, m, canon = _shap_path_data(
            tree, x, slot, d, cat_mask
        )
        q = jnp.zeros((n, d + 1), jnp.float32).at[:, 0].set(1.0)
        for s in range(d):
            q = _poly_extend(q, zs[s], os_[s], valids[s])
        w = ctab[m]  # [D] permutation weights for this slot's player count
        phi = jnp.zeros((n, num_features), jnp.float32)
        for s in range(d):
            r = _poly_unwind(q, zs[s], os_[s], d)  # [N, D]
            contrib = v_leaf * (os_[s] - zs[s]) * (r @ w)
            contrib = jnp.where(valids[s], contrib, 0.0)
            phi = phi.at[:, fs[s]].add(contrib)
        e_slot = v_leaf
        for s in range(d):
            e_slot = e_slot * jnp.where(valids[s], zs[s], 1.0)
        e_slot = jnp.where(canon, e_slot, 0.0)
        return phi, e_slot

    if d == 0:
        return jnp.zeros((n, num_features), jnp.float32), tree.value[0]

    def slot_step(acc, slot):
        phi_acc, e_acc = acc
        phi, e = slot_contrib(slot)
        return (phi_acc + phi, e_acc + e), None

    (phi_tot, e_tot), _ = jax.lax.scan(
        slot_step,
        (jnp.zeros((n, num_features), jnp.float32), jnp.float32(0.0)),
        jnp.arange(2 ** d, dtype=jnp.int32),
    )
    return phi_tot, e_tot


@functools.partial(jax.jit, static_argnames=("max_depth", "num_outputs", "num_parallel_tree", "ntree_limit", "cat_features"))
def predict_contribs_exact(
    forest: Tree,  # stacked trees: each field [T, heap]
    x: jnp.ndarray,  # [N, F] float32 raw features
    max_depth: int,
    num_outputs: int,
    num_parallel_tree: int = 1,
    ntree_limit: int = 0,
    tree_weights: Optional[jnp.ndarray] = None,
    cat_features: tuple = (),
) -> jnp.ndarray:
    """Exact TreeSHAP contributions (xgboost ``pred_contribs`` default).

    Reference surface: ``xgb.Booster.predict(pred_contribs=True)`` passed
    through at ``xgboost_ray/main.py:795-810``. Per tree, each leaf's
    conditional-expectation weight polynomial is built over the path's unique
    features (EXTEND), then each player's Shapley weight is recovered by
    synthetic division (UNWIND); the bias column carries the cover-weighted
    tree expectation, so rows sum exactly to the margin.

    Returns [N, K, F+1] (bias last), trees accumulated with ``lax.scan``.
    """
    require_heap(forest, "Exact TreeSHAP (pred_contribs; approx_contribs="
                         "True walks any layout)")
    n, num_features = x.shape
    t = forest.feature.shape[0]
    cat_mask = _cat_mask_const(cat_features, num_features)

    scale = jnp.ones((t,), jnp.float32)
    if tree_weights is not None:
        scale = scale * tree_weights
    if ntree_limit:
        scale = jnp.where(jnp.arange(t) < ntree_limit, scale, 0.0)
    scale = scale / num_parallel_tree
    cls = (jnp.arange(t) // num_parallel_tree) % num_outputs
    onehot = jax.nn.one_hot(cls, num_outputs, dtype=jnp.float32) * scale[:, None]  # [T, K]

    def tree_step(acc, args):
        tree, oh = args
        feat_acc, bias_acc = acc
        phi, e_tree = _shap_one_tree(tree, x, max_depth, cat_mask)
        feat_acc = feat_acc + jnp.einsum("nf,k->nkf", phi, oh)
        bias_acc = bias_acc + e_tree * oh
        return (feat_acc, bias_acc), None

    acc0 = (
        jnp.zeros((n, num_outputs, num_features), jnp.float32),
        jnp.zeros((num_outputs,), jnp.float32),
    )
    (feat_part, bias_part), _ = jax.lax.scan(tree_step, acc0, (forest, onehot))
    bias = jnp.broadcast_to(bias_part[None, :, None], (n, num_outputs, 1))
    return jnp.concatenate([feat_part, bias], axis=2)


def _shap_interactions_one_tree(tree: Tree, x: jnp.ndarray, max_depth: int,
                                cat_mask):
    """Exact SHAP interaction values for one tree.

    Returns (phi_mat [N, F, F], phi_bias [N, F], phi_plain [N, F], e_tree):

    * off-diagonal (Lundberg's definition, what xgboost's
      PredictInteractionContributions computes): Phi[i,j] = (phi_j with i
      conditioned present - phi_j with i conditioned absent) / 2, obtained by
      unwinding i from the path polynomial;
    * phi_bias[i] = (E[tree | i present] - E[tree | i absent]) / 2 — the
      feature-bias interaction column xgboost emits;
    * diagonal: Phi[i,i] = phi_i - sum_{j != i} Phi[i,j] - phi_bias[i], so
      each feature row (including its bias entry) sums to phi_i.
    """
    n, num_features = x.shape
    d = max_depth
    ctab = _shap_weight_table(d)

    def slot_contrib(slot):
        fs, zs, os_, valids, v_leaf, m, canon = _shap_path_data(
            tree, x, slot, d, cat_mask
        )
        q = jnp.zeros((n, d + 1), jnp.float32).at[:, 0].set(1.0)
        for s in range(d):
            q = _poly_extend(q, zs[s], os_[s], valids[s])

        w_m = ctab[m]          # weights for m players (plain phi)
        w_m1 = ctab[jnp.maximum(m - 1, 0)]  # weights with player i removed
        phi_mat = jnp.zeros((n, num_features, num_features), jnp.float32)
        phi_bias = jnp.zeros((n, num_features), jnp.float32)
        phi_plain = jnp.zeros((n, num_features), jnp.float32)

        e_slot = v_leaf
        for s in range(d):
            e_slot = e_slot * jnp.where(valids[s], zs[s], 1.0)
        e_slot = jnp.where(canon, e_slot, 0.0)

        for s in range(d):
            r_s = _poly_unwind(q, zs[s], os_[s], d)
            contrib = v_leaf * (os_[s] - zs[s]) * (r_s @ w_m)
            contrib = jnp.where(valids[s], contrib, 0.0)
            phi_plain = phi_plain.at[:, fs[s]].add(contrib)

        for i in range(d):
            # bias interaction: conditional tree expectations differ by the
            # z_i -> o_i swap in the cover product
            prod_rest = jnp.ones((n,), jnp.float32) * v_leaf
            for j in range(d):
                if j != i:
                    prod_rest = prod_rest * jnp.where(valids[j], zs[j], 1.0)
            b_i = 0.5 * (os_[i] - zs[i]) * prod_rest
            b_i = jnp.where(valids[i] & canon, b_i, 0.0)
            phi_bias = phi_bias.at[:, fs[i]].add(b_i)

            # polynomial with player i unwound
            q_i = _poly_unwind(q, zs[i], os_[i], d)
            q_i = jnp.concatenate([q_i, jnp.zeros((n, 1), jnp.float32)], axis=1)
            for j in range(d):
                if j == i:
                    continue
                pair_valid = valids[i] & valids[j]
                r = _poly_unwind(q_i, zs[j], os_[j], d)
                base = (os_[j] - zs[j]) * (r @ w_m1)
                # condition on i present (weight o_i) vs absent (weight z_i)
                delta = 0.5 * v_leaf * base * (os_[i] - zs[i])
                delta = jnp.where(pair_valid, delta, 0.0)
                phi_mat = phi_mat.at[:, fs[i], fs[j]].add(delta)
        return phi_mat, phi_bias, phi_plain, e_slot

    if d == 0:
        z = jnp.zeros((n, num_features, num_features), jnp.float32)
        zf = jnp.zeros((n, num_features), jnp.float32)
        return z, zf, zf, tree.value[0]

    def slot_step(acc, slot):
        mat_a, bias_a, plain_a, e_a = acc
        mat, bias, plain, e = slot_contrib(slot)
        return (mat_a + mat, bias_a + bias, plain_a + plain, e_a + e), None

    acc0 = (
        jnp.zeros((n, num_features, num_features), jnp.float32),
        jnp.zeros((n, num_features), jnp.float32),
        jnp.zeros((n, num_features), jnp.float32),
        jnp.float32(0.0),
    )
    (phi_mat, phi_bias, phi_plain, e_tree), _ = jax.lax.scan(
        slot_step, acc0, jnp.arange(2 ** d, dtype=jnp.int32)
    )
    # diagonal absorbs the remainder so each feature row (with its bias
    # entry) sums to phi_plain
    row_off = phi_mat.sum(axis=2) - jnp.einsum("nii->ni", phi_mat)
    diag = phi_plain - row_off - phi_bias
    eye = jnp.eye(num_features, dtype=jnp.float32)
    phi_mat = phi_mat * (1.0 - eye) + diag[:, :, None] * eye
    return phi_mat, phi_bias, phi_plain, e_tree


@functools.partial(jax.jit, static_argnames=("max_depth", "num_outputs", "num_parallel_tree", "ntree_limit", "cat_features"))
def predict_interactions(
    forest: Tree,
    x: jnp.ndarray,
    max_depth: int,
    num_outputs: int,
    num_parallel_tree: int = 1,
    ntree_limit: int = 0,
    tree_weights: Optional[jnp.ndarray] = None,
    cat_features: tuple = (),
) -> jnp.ndarray:
    """SHAP interaction values (xgboost ``pred_interactions``): [N, K, F+1, F+1].

    Matches xgboost's output contract: Phi[i, bias] = Phi[bias, i] is the
    feature-bias interaction, each feature row sums to that feature's plain
    contribution, the bias-bias cell absorbs the remainder of the tree
    expectation, and the grand total equals the margin.
    """
    require_heap(forest, "SHAP interaction values (pred_interactions)")
    n, num_features = x.shape
    t = forest.feature.shape[0]
    cat_mask = _cat_mask_const(cat_features, num_features)

    scale = jnp.ones((t,), jnp.float32)
    if tree_weights is not None:
        scale = scale * tree_weights
    if ntree_limit:
        scale = jnp.where(jnp.arange(t) < ntree_limit, scale, 0.0)
    scale = scale / num_parallel_tree
    cls = (jnp.arange(t) // num_parallel_tree) % num_outputs
    onehot = jax.nn.one_hot(cls, num_outputs, dtype=jnp.float32) * scale[:, None]

    def tree_step(acc, args):
        tree, oh = args
        mat_acc, fbias_acc, e_acc = acc
        phi_mat, phi_bias, _, e_tree = _shap_interactions_one_tree(
            tree, x, max_depth, cat_mask
        )
        mat_acc = mat_acc + jnp.einsum("nfg,k->nkfg", phi_mat, oh)
        fbias_acc = fbias_acc + jnp.einsum("nf,k->nkf", phi_bias, oh)
        e_acc = e_acc + e_tree * oh
        return (mat_acc, fbias_acc, e_acc), None

    acc0 = (
        jnp.zeros((n, num_outputs, num_features, num_features), jnp.float32),
        jnp.zeros((n, num_outputs, num_features), jnp.float32),
        jnp.zeros((num_outputs,), jnp.float32),
    )
    (mat_part, fbias_part, e_part), _ = jax.lax.scan(
        tree_step, acc0, (forest, onehot)
    )
    out = jnp.zeros((n, num_outputs, num_features + 1, num_features + 1), jnp.float32)
    out = out.at[:, :, :num_features, :num_features].set(mat_part)
    out = out.at[:, :, :num_features, num_features].set(fbias_part)
    out = out.at[:, :, num_features, :num_features].set(fbias_part)
    # bias-bias absorbs the remainder of the expectation so the bias row also
    # sums to the plain bias contribution (and the grand total to the margin)
    out = out.at[:, :, num_features, num_features].set(
        jnp.broadcast_to(e_part[None, :], (n, num_outputs))
        - fbias_part.sum(axis=2)
    )
    return out


@functools.partial(jax.jit, static_argnames=("max_depth", "cat_features"))
def predict_leaf_index(
    forest: Tree, x: jnp.ndarray, max_depth: int, cat_features: tuple = ()
) -> jnp.ndarray:
    """Per-tree leaf heap index for each row (xgboost pred_leaf analog). [N, T]."""
    cat_mask = _cat_mask_const(cat_features, x.shape[1])
    return jax.vmap(
        lambda tree: _leaf_of_rows(tree, x, max_depth, cat_mask)
    )(forest).T
