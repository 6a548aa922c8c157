"""Microbenchmark the histogram implementations (the tpu_hist hot op).

Run on real hardware to check ``default_hist_impl``'s accelerator default:

    python tools/bench_hist.py                    # ambient backend
    JAX_PLATFORMS=cpu python tools/bench_hist.py  # CPU sanity

Prints per-(impl, n_nodes) timings plus a full build_tree comparison.

    python tools/bench_hist.py --radix-sweep --rows 11000000

is the probe the dense build's radix rule (``ops.histogram.onehot_radix``)
was read from: the bin index factored as ``b = hi * L + lo``, the one-hot of
``hi`` on the matmul's left and ``lo`` beside the node on its right, timed
per (L, columns, layout, tile) and printed as the table PERF.md quotes. The
rows ``shipped`` are the package's build with the radix forced (``--stages``
with nothing after it runs those alone: 31 builds, a minute of one chip);
the rows ``probe`` are this file's own forms of it (``probe_build``), stage
``layouts`` (which way the right-hand side lies, features batched or
grouped to full MXU tiles) and stage ``forms`` (the order of a feature's
columns, and how ``lo`` masks them), each followed by the tiles around its
fastest configurations; ``--widths``, ``--radices``, ``--masks`` and
``--max-bin`` narrow a run, ``--out`` keeps one JSON line a configuration.

Timing: each kernel is one jitted call timed on the host clock around
``block_until_ready`` (the iteration index perturbs the input so no call is
served from a cached result); the median of ``--repeats`` calls after one
compiling warm-up is printed. The header names the device it ran on.
"""

import argparse
import json
import os
import sys
import time

import numpy as np

# run as a script: sys.path[0] is tools/
sys.path.insert(0, os.path.dirname(os.path.dirname(os.path.abspath(__file__))))


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


#: layouts of the factored build ``probe_build`` knows: the right-hand side
#: with the rows leading (``rk``: [rows, columns], today's) or the rows minor
#: (``kr``: [columns, rows], contracted against the one-hot's minor axis)
LAYOUTS = ("rk", "kr")


def probe_build(bins, gh, pos, n_nodes, nb_reg, *, radix, layout, group,
                ftile, chunk, order="lc", mask="select"):
    """``hist_onehot``'s loop with the bin index factored, laid out as the
    arguments say; returns the regular bins' ``[F, nb_reg, 2 * n_nodes]``.

    Per chunk and per tile of ``ftile`` features: the one-hot of ``hi`` is
    ``[ftile, H, rows]`` (``H = ceil(nb_reg / radix)``), a feature's
    right-hand side the chunk's ``[rows, C]`` masked by its ``lo`` compare,
    ``radix * C`` columns. ``group`` = 1 contracts them as a ``dot_general``
    batched over the features; ``group`` = G stacks G features' one-hots
    into one ``[G * H, rows]`` left-hand side against their stacked
    right-hand sides and keeps the G diagonal blocks of the product (G-fold
    wasted MXU work, fuller tiles). ``order`` is the order of a feature's
    columns, ``lo`` major (``lc``: column ``lo * C + c``) or minor (``cl``);
    ``mask`` how ``lo`` masks them: a select or a product with the 0/1
    compare, or (``key``, rows-leading only) one compare of the row's
    ``lo * n_nodes + node`` against each column's own key. bf16 operands,
    f32 accumulation.
    """
    import jax
    import jax.numpy as jnp

    from xgboost_ray_tpu.ops.histogram import _for_row_chunks

    n, num_features = bins.shape
    shift = radix.bit_length() - 1
    assert 1 << shift == radix and ftile % group == 0 and layout in LAYOUTS
    n_hi = -(-nb_reg // radix)
    width = 2 * n_nodes
    n_ftiles = -(-num_features // ftile)
    f_pad = n_ftiles * ftile - num_features
    n_groups = ftile // group
    hi_ids = jnp.arange(n_hi, dtype=jnp.int32)
    lo_ids = jnp.arange(radix, dtype=jnp.int32)
    node_of_col = jnp.arange(width, dtype=jnp.int32) // 2
    col_is_hess = (jnp.arange(width, dtype=jnp.int32) % 2).astype(bool)
    dt = jnp.bfloat16
    zero = jnp.zeros((), dt)

    def chunk_step(acc, pk, bc, ghk):
        rows = pk.shape[0]
        bct = bc.T.astype(jnp.int32)
        if f_pad:
            bct = jnp.pad(bct, ((0, f_pad), (0, 0)), constant_values=nb_reg)
        ghc = ghk.astype(dt)
        if layout == "rk":
            of_col = jnp.where(col_is_hess[None, :], ghc[:, 1:2], ghc[:, 0:1])
            rhs = jnp.where(pk[:, None] == node_of_col[None, :], of_col, zero)
        else:
            ght = ghc.T
            of_col = jnp.where(col_is_hess[:, None], ght[1:2], ght[0:1])
            rhs = jnp.where(node_of_col[:, None] == pk[None, :], of_col, zero)

        def ftile_step(t, acc):
            cols = (bct if n_ftiles == 1 else jax.lax.dynamic_slice_in_dim(
                bct, t * ftile, ftile, axis=0))
            hi = cols >> shift
            lo = (cols & (radix - 1)).reshape(n_groups, group, rows)
            oh = (hi[:, None, :] == hi_ids[None, :, None]).astype(dt)
            oh = oh.reshape(n_groups, group * n_hi, rows)
            def masked(sel, of):
                if mask == "mul":
                    return sel.astype(dt) * of
                return jnp.where(sel, of, zero)

            if mask == "key":
                assert layout == "rk" and group == 1
                j = jnp.arange(radix * width, dtype=jnp.int32)
                j_lo, j_c = ((j // width, j % width) if order == "lc"
                             else (j % radix, j // radix))
                live = (pk >= 0) & (pk < n_nodes)
                key = (lo.reshape(ftile, rows) * n_nodes
                       + jnp.where(live, pk, -radix * n_nodes)[None, :])
                gh_of_col = jnp.where((j_c % 2).astype(bool)[None, :],
                                      ghc[:, 1:2], ghc[:, 0:1])
                of_f = jnp.where(
                    key[:, :, None] == (j_lo * n_nodes + j_c // 2)[None, None],
                    gh_of_col[None], zero)
                out = jax.lax.dot_general(
                    oh, of_f, (((2,), (1,)), ((0,), (0,))),
                    preferred_element_type=jnp.float32)
            elif layout == "rk":
                sel = (lo.transpose(0, 2, 1)[:, :, :, None]
                       == lo_ids[None, None, None, :])  # [ng, rows, G, L]
                if order == "lc":
                    of_f = masked(sel[..., None], rhs[None, :, None, None, :])
                else:
                    of_f = masked(sel[:, :, :, None, :],
                                  rhs[None, :, None, :, None])
                out = jax.lax.dot_general(
                    oh, of_f.reshape(n_groups, rows, group * radix * width),
                    (((2,), (1,)), ((0,), (0,))),
                    preferred_element_type=jnp.float32)
            else:
                sel = lo[:, :, None, :] == lo_ids[None, None, :, None]
                if order == "lc":
                    of_f = masked(sel[:, :, :, None, :],
                                  rhs[None, None, None, :, :])
                else:
                    of_f = masked(sel[:, :, None, :, :],
                                  rhs[None, None, :, None, :])
                out = jax.lax.dot_general(
                    oh, of_f.reshape(n_groups, group * radix * width, rows),
                    (((2,), (2,)), ((0,), (0,))),
                    preferred_element_type=jnp.float32)
            if group > 1:  # the diagonal blocks of [G * H, G * L * C]
                out = out.reshape(n_groups, group, n_hi, group, radix * width)
                eye = jnp.eye(group, dtype=bool)[None, :, None, :, None]
                out = jnp.where(eye, out, 0.0).sum(axis=3)
            out = out.reshape(ftile, n_hi, radix * width)
            if order == "cl":
                out = out.reshape(ftile, n_hi, width, radix).transpose(
                    0, 1, 3, 2).reshape(ftile, n_hi, radix * width)
            if n_ftiles == 1:
                return acc + out
            return jax.lax.dynamic_update_slice_in_dim(
                acc,
                jax.lax.dynamic_slice_in_dim(acc, t * ftile, ftile, axis=0)
                + out, t * ftile, axis=0)

        if n_ftiles == 1:
            return ftile_step(0, acc)
        return jax.lax.fori_loop(0, n_ftiles, ftile_step, acc)

    acc = _for_row_chunks(
        chunk_step,
        jnp.zeros((n_ftiles * ftile, n_hi, radix * width), jnp.float32),
        chunk, pos, bins, gh)
    return acc[:num_features].reshape(
        num_features, n_hi * radix, width)[:, :nb_reg]


def _chunk_for(ftile, n_hi, columns):
    """8192 rows, halved until a step's one-hot and right-hand sides stay
    under 2^24 elements together: where PR 30's build fell off a cliff. The
    factored build did not (the tile stage widens the chunk again, and
    44M elements a step read best at 32 columns)."""
    chunk = 8192
    while ftile * chunk * (n_hi + columns) > 1 << 24 and chunk > 1024:
        chunk //= 2
    return chunk


def _sweep_grid(num_features, nb_reg, widths, radices, masks, stage):
    """The probe's configurations (``probe_build``'s keywords and the
    columns), in the order of the issue's step 1. Stage ``layouts``: both
    orientations of the right-hand side, batched over the features and
    grouped to 128 one-hot rows, at two tiles. Stage ``forms``: the batched
    layout's column order and mask."""
    full = -(-num_features // 8) * 8  # features padded to the widest group
    grid = []
    for radix in radices:
        if radix == 1:
            continue
        n_hi = -(-nb_reg // radix)
        fill = max(1, 128 // n_hi)  # G * H = 128
        for cols in widths:
            def add(ftile, **form):
                grid.append(dict(
                    radix=radix, cols=cols, ftile=ftile,
                    chunk=_chunk_for(ftile, n_hi, radix * cols), **form))

            if stage == "layouts":
                for layout in LAYOUTS:
                    add(7, layout=layout, group=1)
                    add(num_features, layout=layout, group=1)
                    if fill > 1:
                        add(full if num_features % fill else num_features,
                            layout=layout, group=fill)
            else:
                for order in ("lc", "cl"):
                    for mask in masks:
                        for ftile in (7, num_features):
                            add(ftile, layout="rk", group=1, order=order,
                                mask=mask)
    return grid


def _tile_grid(rows, num_features):
    """Tiles around each width's fastest configuration so far."""
    top = {}
    for r in rows:
        if r["build"] == "probe" and "ms" in r and (
                r["cols"] not in top or r["ms"] < top[r["cols"]]["ms"]):
            top[r["cols"]] = r
    grid = []
    for best in top.values():
        cfg = {k: v for k, v in best.items()
               if k not in ("build", "ms", "wall_s")}
        for chunk in (cfg["chunk"] // 4, cfg["chunk"] // 2,
                      cfg["chunk"] * 2, cfg["chunk"] * 4):
            grid.append(dict(cfg, chunk=chunk))
        for ftile in (4, 7, 14, 28, 32):
            if ftile != cfg["ftile"] and ftile % cfg["group"] == 0:
                grid.append(dict(cfg, ftile=ftile, chunk=8192))
    return grid


def radix_sweep(args, jax, jnp, bins, gh, rng):
    """The (L, C, layout, tile) probe: one JSON line a configuration in
    ``--out`` and the table of the best milliseconds a (L, C) at the end."""
    from xgboost_ray_tpu.ops import histogram

    nbt = args.max_bin + 1
    nb_reg = args.max_bin
    rows = []
    pos_of = {}

    def pos_for(cols):
        if cols not in pos_of:
            pos_of[cols] = jnp.asarray(rng.randint(
                0, cols // 2, size=args.rows).astype(np.int32))
        return pos_of[cols]

    def record(row, body, cols):
        try:
            t0 = time.perf_counter()
            ms = _time_calls(jax, jnp, body, (bins, gh, pos_for(cols)),
                             args.repeats) * 1e3
            row["ms"] = round(ms, 3)
            row["wall_s"] = round(time.perf_counter() - t0, 1)
        except Exception as exc:  # noqa: BLE001 - a layout the chip refuses
            row["error"] = str(exc)[:200]
        rows.append(row)
        print(json.dumps(row), flush=True)
        if args.out:
            with open(args.out, "a") as fh:
                fh.write(json.dumps(row) + "\n")

    def timed(build):
        """``build(bins, gh, pos)`` as ``_time_calls`` wants it: gh perturbed
        by the iteration index, the histogram reduced to a scalar."""
        def body(i, b, g0, p):
            return build(b, g0 + (i.astype(jnp.float32) * 1e-12), p).sum()
        return body

    widths = tuple(args.widths)
    radices = tuple(args.radices)
    for cols in widths:
        for radix in radices:
            if radix > 1 and cols > 64:
                continue
            record({"build": "shipped", "radix": radix, "cols": cols,
                    "rule": histogram.onehot_radix(cols // 2, nb_reg)},
                   timed(lambda b, g, p, cols=cols, radix=radix:
                         histogram._hist_onehot(b, g, p, cols // 2, nbt,
                                                8192, "fast", radix)),
                   cols)

    def run(grid):
        for cfg in grid:
            cfg = dict(cfg)
            cols = cfg.pop("cols")
            record({"build": "probe", "cols": cols, **cfg},
                   timed(lambda b, g, p, cols=cols, cfg=cfg: probe_build(
                       b, g, p, cols // 2, nb_reg, **cfg)),
                   cols)

    for stage in args.stages:
        run(_sweep_grid(args.features, nb_reg, [c for c in widths if c <= 64],
                        radices, args.masks, stage))
        run(_tile_grid(rows, args.features))

    print("\nbest ms a build, by radix (rows) and right-hand-side columns:")
    table_cols = sorted({r["cols"] for r in rows})
    print("build    L " + "".join(f"{c:>9d}" for c in table_cols))
    for build in ("shipped", "probe"):
        for radix in radices:
            cells = []
            for cols in table_cols:
                ms = [r["ms"] for r in rows if r["build"] == build
                      and r["radix"] == radix and r["cols"] == cols
                      and "ms" in r]
                cells.append(f"{min(ms):9.2f}" if ms else "        -")
            if any(c.strip() != "-" for c in cells):
                print(f"{build:8s}{radix:2d} " + "".join(cells))


def main():
    parser = argparse.ArgumentParser()
    parser.add_argument("--rows", type=int, default=1_000_000)
    parser.add_argument("--features", type=int, default=28)
    parser.add_argument("--max-bin", type=int, default=256)
    parser.add_argument("--depth", type=int, default=6)
    parser.add_argument("--repeats", type=int, default=8)
    parser.add_argument("--impls", nargs="+",
                        default=["scatter", "onehot"])
    parser.add_argument("--radix-sweep", action="store_true",
                        help="the (L, C, layout, tile) probe of the dense "
                             "build's factored bin index")
    parser.add_argument("--widths", nargs="*", type=int,
                        default=[2, 4, 8, 16, 32, 64, 128],
                        help="radix sweep: right-hand-side columns "
                             "(2 x node slots) to probe; past 64 the "
                             "package's build at radix 1 alone")
    parser.add_argument("--radices", nargs="*", type=int,
                        default=[1, 2, 4, 8, 16],
                        help="radix sweep: radices to probe")
    parser.add_argument("--masks", nargs="*",
                        default=["select", "mul", "key"],
                        choices=["select", "mul", "key"],
                        help="radix sweep, stage forms: how lo masks the "
                             "right-hand side")
    parser.add_argument("--stages", nargs="*", default=["layouts", "forms"],
                        choices=["layouts", "forms"],
                        help="radix sweep: which of the probe's grids to run "
                             "beside the package's build (none: that alone)")
    parser.add_argument("--out", default="",
                        help="radix sweep: append one JSON line a "
                             "configuration to this file")
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

    if args.radix_sweep:
        if args.out:
            os.makedirs(os.path.dirname(args.out) or ".", exist_ok=True)
        radix_sweep(args, jax, jnp, bins, gh, rng)
        return

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
