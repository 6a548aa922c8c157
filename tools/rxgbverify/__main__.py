"""CLI: ``python -m tools.rxgbverify [--json F] [--sarif F] [--fingerprints F]``.

Traces the config matrix on a hermetic 8-device virtual CPU mesh and runs
every VER* check. Exit status mirrors rxgblint: 0 = clean, 1 = findings,
2 = usage error.
"""

import argparse
import json
import os
import sys


def _force_cpu_mesh() -> None:
    """Virtual 8-device CPU mesh (same as tests/conftest.py): must run
    BEFORE the first jax import. If jax is already imported (in-process test
    invocation under conftest) the environment is trusted as-is."""
    if "jax" in sys.modules:
        return
    os.environ["JAX_PLATFORMS"] = "cpu"
    flags = os.environ.get("XLA_FLAGS", "")
    if "xla_force_host_platform_device_count" not in flags:
        os.environ["XLA_FLAGS"] = (
            flags + " --xla_force_host_platform_device_count=8"
        ).strip()


def _program_entry(t) -> dict:
    rec = t.record
    entry = {
        "name": rec.name,
        "meta": dict(rec.meta),
        "donate_argnums": list(rec.donate_argnums),
        "registrations": rec.registrations,
    }
    if t.ok:
        entry["fingerprint"] = t.fingerprint
        entry["collectives"] = [c.describe() for c in t.analysis.collectives]
    else:
        entry["error"] = t.error
    return entry


def main(argv=None) -> int:
    from tools.rxgbverify.checks import VERIFY_RULES

    parser = argparse.ArgumentParser(
        prog="rxgbverify",
        description=(
            "jaxpr-level SPMD schedule / precision-flow / recompile-drift "
            "verifier for xgboost_ray_tpu"
        ),
    )
    parser.add_argument(
        "--json", metavar="FILE",
        help="write the machine-readable report (the CI artifact)",
    )
    parser.add_argument(
        "--sarif", metavar="FILE",
        help="write findings as SARIF 2.1.0 for code-review annotations",
    )
    parser.add_argument(
        "--fingerprints", metavar="FILE",
        help="write the {program: fingerprint} drift artifact",
    )
    parser.add_argument(
        "--quick", action="store_true",
        help="trace the reduced matrix (the tier-1 test subset) instead of "
             "the full grower x hist_quant x sampling x world grid",
    )
    parser.add_argument(
        "--list-checks", action="store_true", help="print the check catalog"
    )
    args = parser.parse_args(argv)

    if args.list_checks:
        for code in sorted(VERIFY_RULES):
            print(f"{code}: {VERIFY_RULES[code]}")
        return 0

    _force_cpu_mesh()
    from tools import sarif as sarif_mod
    from tools.rxgblint import catalog
    from tools.rxgbverify import checks as checks_mod
    from tools.rxgbverify.matrix import trace_matrix

    traced = trace_matrix(quick=args.quick)
    if not traced:
        print("rxgbverify: no programs registered — registry wiring broken",
              file=sys.stderr)
        return 2
    findings = checks_mod.run_checks(
        traced, catalog.mesh_axes(), root=catalog.REPO_ROOT
    )
    traced.sort(key=lambda t: t.key())
    programs = {t.key(): _program_entry(t) for t in traced}
    counts = {}
    for f in findings:
        counts[f.rule] = counts.get(f.rule, 0) + 1

    # artifacts + exit status settle BEFORE stdout (a closed pipe must not
    # turn findings into a pass — same hardening as rxgblint)
    if args.json:
        with open(args.json, "w") as fh:
            json.dump(
                {
                    "tool": "rxgbverify",
                    "checks": VERIFY_RULES,
                    "quick": bool(args.quick),
                    "programs": programs,
                    "counts": counts,
                    "findings": [f.to_dict() for f in findings],
                },
                fh, indent=2, sort_keys=True,
            )
            fh.write("\n")
    if args.fingerprints:
        with open(args.fingerprints, "w") as fh:
            json.dump(
                {
                    "tool": "rxgbverify",
                    "programs": {
                        k: v.get("fingerprint", v.get("error", ""))
                        for k, v in programs.items()
                    },
                },
                fh, indent=2, sort_keys=True,
            )
            fh.write("\n")
    if args.sarif:
        with open(args.sarif, "w") as fh:
            fh.write(sarif_mod.to_sarif_json(
                "rxgbverify", VERIFY_RULES,
                [
                    # the annotation target is the registration site; the
                    # program key carries the config context
                    {**f.to_dict(), "message": f"{f.program}: {f.message}"}
                    for f in findings
                ],
            ) + "\n")
    status = 1 if findings else 0

    try:
        for f in findings:
            print(f.render())
        n_coll = sum(
            len(t.analysis.collectives) for t in traced if t.ok
        )
        print(
            f"rxgbverify: {len(traced)} programs traced, {n_coll} "
            f"collectives, {len(findings)} finding(s)"
        )
    except BrokenPipeError:
        os.dup2(os.open(os.devnull, os.O_WRONLY), sys.stdout.fileno())
    return status


if __name__ == "__main__":
    try:
        sys.exit(main())
    except BrokenPipeError:
        sys.exit(1)
