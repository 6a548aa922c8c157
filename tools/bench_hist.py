"""Microbenchmark the histogram implementations (the tpu_hist hot op).

Run on real hardware to check ``default_hist_impl``'s accelerator default:

    python tools/bench_hist.py                    # ambient backend
    JAX_PLATFORMS=cpu python tools/bench_hist.py  # CPU sanity

Prints per-(impl, n_nodes) timings plus a full build_tree comparison.

Timing: each kernel is one jitted call timed on the host clock around
``block_until_ready`` (the iteration index perturbs the input so no call is
served from a cached result); the median of ``--repeats`` calls after one
compiling warm-up is printed. The header names the device it ran on.
"""

import argparse
import time

import numpy as np


def _time_calls(jax, jnp, make_body, operands, repeats):
    """Median wall time of ``make_body(i, *operands)`` over ``repeats``
    blocked calls. Operands are jit arguments (not closed-over constants)
    so the traced program matches production shapes."""
    fn = jax.jit(make_body)
    out = fn(jnp.int32(0), *operands).block_until_ready()  # compile
    assert np.isfinite(float(out))
    times = []
    for r in range(repeats):
        t0 = time.perf_counter()
        fn(jnp.int32(r + 1), *operands).block_until_ready()
        times.append(time.perf_counter() - t0)
    return float(np.median(times))


def main():
    parser = argparse.ArgumentParser()
    parser.add_argument("--rows", type=int, default=1_000_000)
    parser.add_argument("--features", type=int, default=28)
    parser.add_argument("--max-bin", type=int, default=256)
    parser.add_argument("--depth", type=int, default=6)
    parser.add_argument("--repeats", type=int, default=8)
    parser.add_argument("--impls", nargs="+",
                        default=["scatter", "onehot"])
    args = parser.parse_args()

    import jax
    import jax.numpy as jnp

    from xgboost_ray_tpu.ops import binning
    from xgboost_ray_tpu.ops.grow import GrowConfig, build_tree
    from xgboost_ray_tpu.ops.histogram import build_histogram
    from xgboost_ray_tpu.ops.split import SplitParams

    dev0 = jax.devices()[0]
    print(f"platform={dev0.platform} device_kind={dev0.device_kind} "
          f"device_count={len(jax.devices())} rows={args.rows} "
          f"features={args.features} bins={args.max_bin}", flush=True)

    rng = np.random.RandomState(0)
    nbt = args.max_bin + 1
    bins_np = rng.randint(0, nbt, size=(args.rows, args.features))
    bins = jnp.asarray(bins_np.astype(
        np.uint8 if nbt <= 256 else np.int16))
    gh = jnp.asarray(rng.randn(args.rows, 2).astype(np.float32))

    for n_nodes in (1, 8, 64):
        pos = jnp.asarray(
            rng.randint(0, n_nodes, size=args.rows).astype(np.int32))
        for impl in args.impls:
            try:
                def body(i, b, g0, p, impl=impl, nn=n_nodes):
                    # perturb gh by the iteration index so XLA cannot CSE
                    g = g0 + (i.astype(jnp.float32) * 1e-12)
                    h = build_histogram(b, g, p, nn, nbt, impl=impl)
                    return h.sum()

                dt = _time_calls(jax, jnp, body, (bins, gh, pos),
                                 args.repeats)
                print(f"  hist n_nodes={n_nodes:3d} {impl:10s} "
                      f"{dt * 1e3:9.2f} ms", flush=True)
            except Exception as exc:  # noqa: BLE001
                print(f"  hist n_nodes={n_nodes:3d} {impl:10s} FAILED: "
                      f"{str(exc)[:120]}", flush=True)

    # full tree builds (includes row routing and the split search)
    x = rng.randn(args.rows, args.features).astype(np.float32)
    cuts = jnp.asarray(binning.sketch_cuts_np(x[:100_000], args.max_bin))
    for impl, prec in [(i, p) for i in args.impls
                       for p in ("fast", "highest")]:
        try:
            cfg = GrowConfig(max_depth=args.depth, max_bin=args.max_bin,
                             split=SplitParams(), hist_impl=impl,
                             hist_precision=prec)

            def body(i, b, g0, c, cfg=cfg):
                g = g0 + (i.astype(jnp.float32) * 1e-12)
                tree = build_tree(b, g, c, cfg)[0]
                return tree.value.sum()

            dt = _time_calls(jax, jnp, body, (bins, gh, cuts),
                             max(2, args.repeats // 2))
            print(f"  tree depth={args.depth} {impl:10s} {prec:8s} "
                  f"{dt * 1e3:9.2f} ms", flush=True)
        except Exception as exc:  # noqa: BLE001
            print(f"  tree depth={args.depth} {impl:10s} {prec:8s} FAILED: "
                  f"{str(exc)[:120]}", flush=True)


if __name__ == "__main__":
    main()
