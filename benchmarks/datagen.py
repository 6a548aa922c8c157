"""Seeded HIGGS-shaped data, made on the device in blocks.

The benchmark's copy of ``bench.make_higgs_like`` (same learnable structure:
four informative terms plus unit noise, the label is their sign), with the
draws moved to ``jax.random`` so that 11M x 28 values cost seconds, not the
12 s of the numpy generator, and so that every block is one call of one
jitted program. Blocks keep the generator's own device peak (about 100 MB)
far under the trained program's, so ``memory_peak_bytes`` stays the
program's.

``levels=None`` gives what the source has and a user sends: 28 continuous
standard-normal floats. ``levels=257`` lays every feature onto a fixed grid
of 257 values of equal mass (the two end values have half a share). The
trainer bins both into the same 256 equal-mass bins, but on the grid its
sketch returns the same cut points for every seed. That is a stand-in, not
the users' data: the round programs bake the cut points in as constants, so
with continuous features every new seed is a new program to the compile
cache, which at 11M rows no run outlasts (PERF.md, Open questions, first
row). A configuration on the grid measures the set-up of a data set the
cache has seen, never the set-up of a fresh one.
"""

import functools
import statistics

import numpy as np

BLOCK_ROWS = 500_000
END_LEVEL = 3.2  # about the mean of a normal's tail beyond its 1/512 quantile
# jax.random.key takes 32 signed bits; the driver's seeds are larger
_SEED_MOD = 2**31 - 1


def grid(levels):
    """The ``levels`` values a feature on the grid can take, ascending."""
    inv = statistics.NormalDist().inv_cdf
    n = levels - 1
    inner = [inv(k / n) for k in range(1, n)]
    return np.asarray([-END_LEVEL] + inner + [END_LEVEL], np.float32)


@functools.lru_cache(maxsize=None)
def _block_fn(features, levels):
    import jax
    import jax.numpy as jnp
    from jax.scipy.special import ndtr

    table = None if levels is None else jnp.asarray(grid(levels))

    @jax.jit
    def block(key):
        kx, kn = jax.random.split(key)
        x = jax.random.normal(kx, (BLOCK_ROWS, features), jnp.float32)
        if table is not None:
            n = levels - 1
            x = table[jnp.clip(jnp.round(n * ndtr(x)), 0, n).astype(jnp.int32)]
        logits = (0.8 * x[:, 0] - 0.6 * x[:, 1] + 0.4 * x[:, 2] * x[:, 3]
                  + 0.3 * x[:, 4])
        noise = jax.random.normal(kn, (BLOCK_ROWS,), jnp.float32)
        return x, (logits + noise > 0).astype(jnp.float32)

    return block


def make(rows, features, seed, stream=0, levels=None):
    """``(x [rows, features] float32, y [rows] float32)`` on the host.

    ``stream`` separates the sets of one seed (0 train, 1 validation);
    ``levels`` is the configuration's ``data`` (see the module's text)."""
    import jax

    if features < 5:
        raise ValueError("the generator's label uses features 0..4")
    seed = int(seed)
    key = jax.random.fold_in(
        jax.random.fold_in(jax.random.key(seed % _SEED_MOD),
                           seed // _SEED_MOD),
        stream,
    )
    block = _block_fn(features, levels)
    x = np.empty((rows, features), np.float32)
    y = np.empty((rows,), np.float32)
    for i, lo in enumerate(range(0, rows, BLOCK_ROWS)):
        hi = min(lo + BLOCK_ROWS, rows)
        xb, yb = block(jax.random.fold_in(key, i))
        x[lo:hi] = np.asarray(xb)[: hi - lo]
        y[lo:hi] = np.asarray(yb)[: hi - lo]
    return x, y
