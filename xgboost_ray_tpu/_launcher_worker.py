"""Per-process bootstrap spawned by ``launcher.launch_distributed``.

The process joins the ``jax.distributed`` world BEFORE any backend is
initialized, then runs the fn. Which backend that is comes from the
environment the launcher passed (``JAX_PLATFORMS``/``XLA_FLAGS``).

Usage (internal): python -m xgboost_ray_tpu._launcher_worker <payload> <result>
"""

import os
import pickle
import sys


def main() -> int:
    payload_path, result_path = sys.argv[1], sys.argv[2]

    with open(payload_path, "rb") as f:
        payload = pickle.load(f)
    ctx = payload["ctx"]
    if hasattr(ctx, "heartbeat"):
        # first touch BEFORE the slow imports: the watchdog's stall clock
        # should start at bootstrap, not at spawn + interpreter startup
        ctx.heartbeat()

    # chaos hook (kill/hang/straggle this process, RXGB_FAULT_PLAN env).
    # A plain package import is correct here: unpickling ctx above already
    # imported xgboost_ray_tpu.launcher (LaunchContext's defining module)
    # and with it the whole package — importing jax modules does not
    # initialize a backend, so jax.distributed.initialize below still runs
    # first. Using the package's own faults instance keeps ONE plan/counter
    # state per process (a standalone copy would double-parse the env plan).
    from xgboost_ray_tpu import faults

    faults.fire(
        "launcher.worker", process_id=ctx.process_id, attempt=ctx.attempt
    )

    fn, args = pickle.loads(payload["fn_args"])

    import jax

    jax.distributed.initialize(
        coordinator_address=ctx.coordinator_address,
        num_processes=ctx.num_processes,
        process_id=ctx.process_id,
    )
    if hasattr(ctx, "heartbeat"):
        # first post-join liveness touch; worker fns take over per round
        ctx.heartbeat()

    result = fn(ctx, *args)

    tmp = f"{result_path}.tmp"
    with open(tmp, "wb") as f:
        pickle.dump(result, f)
    os.replace(tmp, result_path)
    try:
        # orderly disconnect; the result file is already committed, so a
        # teardown-time error must not fail the worker
        jax.distributed.shutdown()
    except Exception:  # noqa: BLE001
        pass
    return 0


if __name__ == "__main__":
    sys.exit(main())
