"""Seeded wide dense rows (Epsilon-shaped: thousands of float features),
made on the device in blocks.

``datagen.py``'s generator for another shape: the same seeding (seed, then
stream, then block), the same grid (``datagen.grid``; ``levels=257`` is the
stand-in every committed configuration runs, for the reason that module
gives), the same return (the pair ``(x, y)`` on the host). Three things
differ.

Its 500,000-row blocks would be 4 GB on the device at 2,000 features, so a
block here is ``BLOCK_ROWS`` rows (66 MB of float32 at 2,000 features; the
generator's own device peak stays at a few such arrays, far under the
trained program's).

On the grid a column of a block is no independent draw a row but a seeded
shuffle of the same multiset: every inner level ``BLOCK_ROWS / (levels - 1)``
times, the two end levels half as often, each column shuffled on its own.
``datagen.py`` draws every value alone and leaves it to 11M rows that the
sketch returns the same cut points for every seed: a level there holds
43,000 rows and the sketch's quantiles miss its edge by 1,700 at most. At
400,000 rows a level holds 1,560 and its edge moves by 320 (one sigma): the
first two runs of this cell, on independent draws, compiled the round
program anew on each seed, which is what the grid is there to prevent
(PERF.md section 4). With equal counts only the last, cut block's rows
vary (6,784 of 400,000), and every seed gives the sketch the same cut
points. To the trees the columns are what they were: standard normals on
257 values, independent of each other.

And its label, the sign of four terms over five columns, would have every
tree split the same five features of 2,000: the label here is the sign of

* a dense linear term over the first ``LINEAR`` columns with weights that
  decay like ``1 / (1 + j / 20)`` and alternate in sign,
* ``len(PAIRS)`` pairwise products on the columns after them,
* unit normal noise,

so that no feature carries more than a few percent of the signal, a tree of
256 leaves chooses among some two hundred columns, and split election over
the whole feature axis is exercised at every node. ``epsilon_normalized``'s
unit-norm rows are left out: features are independent standard normals (on
the grid), as in ``datagen.py``.
"""

import functools

import numpy as np

import datagen

BLOCK_ROWS = 8192
LINEAR = 200
LINEAR_SCALE = 0.6
LINEAR_DECAY = 20.0
# (column offset after the linear ones, column offset, weight)
PAIRS = ((0, 1, 0.5), (2, 3, 0.4), (4, 5, 0.3))
MIN_FEATURES = LINEAR + 6


def linear_weights():
    """The ``LINEAR`` weights of the label's dense term."""
    j = np.arange(LINEAR)
    return (LINEAR_SCALE * (-1.0) ** j / (1.0 + j / LINEAR_DECAY)).astype(
        np.float32)


@functools.lru_cache(maxsize=None)
def _block_fn(features, levels):
    import jax
    import jax.numpy as jnp

    weights = jnp.asarray(linear_weights())
    if levels is not None:
        table = jnp.asarray(datagen.grid(levels))
        # the grid index of a block's i-th smallest value: every inner level
        # BLOCK_ROWS / (levels - 1) times, the two end levels half as often
        ranks = (np.arange(BLOCK_ROWS) + 0.5) * (levels - 1) / BLOCK_ROWS
        strata = jnp.asarray(np.round(ranks).astype(np.int32))

    @jax.jit
    def block(key):
        kx, kn = jax.random.split(key)
        if levels is None:
            x = jax.random.normal(kx, (BLOCK_ROWS, features), jnp.float32)
        else:
            x = table[jax.random.permutation(
                kx, jnp.broadcast_to(strata[:, None], (BLOCK_ROWS, features)),
                axis=0, independent=True)]
        logits = jnp.sum(x[:, :LINEAR] * weights[None, :], axis=1)
        for a, b, w in PAIRS:
            logits = logits + w * x[:, LINEAR + a] * x[:, LINEAR + b]
        noise = jax.random.normal(kn, (BLOCK_ROWS,), jnp.float32)
        return x, (logits + noise > 0).astype(jnp.float32)

    return block


def make(rows, features, seed, stream=0, levels=None):
    """``(x [rows, features] float32, y [rows] float32)`` on the host.

    ``stream`` separates the sets of one seed (0 train, 1 validation);
    ``levels`` is the configuration's ``data`` (``datagen.py``'s text)."""
    import jax

    if features < MIN_FEATURES:
        raise ValueError(
            f"the generator's label uses features 0..{MIN_FEATURES - 1}")
    seed = int(seed)
    key = jax.random.fold_in(
        jax.random.fold_in(jax.random.key(seed % datagen._SEED_MOD),
                           seed // datagen._SEED_MOD),
        stream,
    )
    block = _block_fn(features, levels)
    x = np.empty((rows, features), np.float32)
    y = np.empty((rows,), np.float32)
    starts = range(0, rows, BLOCK_ROWS)
    # one block ahead: the device makes block i + 1 while block i is copied
    ahead = block(jax.random.fold_in(key, 0))
    for i, lo in enumerate(starts):
        hi = min(lo + BLOCK_ROWS, rows)
        xb, yb = ahead
        if i + 1 < len(starts):
            ahead = block(jax.random.fold_in(key, i + 1))
        x[lo:hi] = np.asarray(xb)[: hi - lo]
        y[lo:hi] = np.asarray(yb)[: hi - lo]
    return x, y
