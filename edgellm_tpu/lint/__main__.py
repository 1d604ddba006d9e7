"""graphlint CLI: ``python -m edgellm_tpu.lint``.

Exit 0 when every layer is clean, 1 when any finding survives. ``--json``
writes the merged machine-readable report (the CI artifact).

The graph layer traces real entry points over a 2-stage pipeline, so the
spoofed multi-device CPU topology must be configured BEFORE jax initializes
its backends — this module sets the env vars first and only then imports
anything that pulls in jax (same bootstrap as tests/conftest.py).
"""
from __future__ import annotations

import argparse
import os
import sys


def _bootstrap_jax() -> None:
    os.environ.setdefault("JAX_PLATFORMS", "cpu")
    flags = os.environ.get("XLA_FLAGS", "")
    if "xla_force_host_platform_device_count" not in flags:
        os.environ["XLA_FLAGS"] = (
            flags + " --xla_force_host_platform_device_count=8").strip()
    import jax

    # backends are lazy, so forcing the platform here lands before first
    # device use even when jax was imported earlier in the process
    jax.config.update("jax_platforms", "cpu")


def main(argv=None) -> int:
    ap = argparse.ArgumentParser(
        prog="python -m edgellm_tpu.lint",
        description="graphlint: AST footgun rules, thread/lock-discipline "
                    "rules (EG1xx) + jaxpr-level graph contracts for the "
                    "split-decode stack (REPRODUCING §8)")
    ap.add_argument("--json", metavar="PATH", default=None,
                    help="write the merged JSON report here")
    ap.add_argument("--sarif", metavar="PATH", default=None,
                    help="also write the report as SARIF 2.1.0 (all layers)")
    ap.add_argument("--ast-only", action="store_true",
                    help="run only the AST rule layer (no jax import)")
    ap.add_argument("--graph-only", action="store_true",
                    help="run only the graph-contract layer")
    ap.add_argument("--thread-only", action="store_true",
                    help="run only the thread/lock-discipline layer "
                         "(EG1xx; no jax import)")
    ap.add_argument("--lattice-only", action="store_true",
                    help="run only the config-lattice verifier (latticelint:"
                         " AOT footprint + donation + pairwise compat)")
    ap.add_argument("--matrix", metavar="PATH", default=None,
                    help="where the lattice layer writes the capability "
                         "matrix (CI uploads capability_matrix.json)")
    ap.add_argument("--no-mypy", action="store_true",
                    help="skip the scoped mypy --strict layer")
    ap.add_argument("--show-suppressed", action="store_true",
                    help="list every '# graphlint: disable=' marker with "
                         "file:line (audit trail for silenced findings)")
    ap.add_argument("paths", nargs="*",
                    help="AST/thread-lint these files instead of the package "
                         "(graph layer always targets the real package)")
    args = ap.parse_args(argv)
    only_flags = [args.ast_only, args.graph_only, args.thread_only,
                  args.lattice_only]
    if sum(only_flags) > 1:
        ap.error("--ast-only, --graph-only, --thread-only and "
                 "--lattice-only are mutually exclusive")
    if args.lattice_only and args.paths:
        ap.error("--lattice-only lints configs/, not source paths")

    from .report import LintReport, merge, to_sarif

    pkg_root = os.path.dirname(os.path.dirname(os.path.abspath(__file__)))
    repo_root = os.path.dirname(pkg_root)
    findings_by_layer = []
    checked: list = []
    skipped: list = []

    if not (args.graph_only or args.thread_only or args.lattice_only):
        from .ast_rules import iter_package_files, lint_paths

        targets = args.paths or list(iter_package_files(pkg_root))
        findings_by_layer.append(lint_paths(targets))

        if not args.no_mypy and not args.paths:
            from .typecheck import run_typecheck

            ty_findings, ty_skips = run_typecheck(repo_root)
            findings_by_layer.append(ty_findings)
            skipped.extend(ty_skips)

    if not (args.ast_only or args.graph_only or args.lattice_only):
        # pure-AST layer like the EG00x rules: runs pre-jax-bootstrap
        from .threadlint import lint_files as thread_lint_files
        from .threadlint import lint_package as thread_lint_package

        if args.paths:
            findings_by_layer.append(thread_lint_files(args.paths))
        else:
            findings_by_layer.append(thread_lint_package(pkg_root))

    if not (args.ast_only or args.thread_only or args.lattice_only):
        _bootstrap_jax()
        from .entrypoints import run_graph_checks

        g_findings, g_checked, g_skips = run_graph_checks()
        findings_by_layer.append(g_findings)
        checked.extend(g_checked)
        skipped.extend(g_skips)

    if not (args.ast_only or args.thread_only or args.graph_only
            or args.paths):
        # layer 4: the config-lattice verifier (AOT footprints, donation
        # coverage, pairwise feature compat) + the capability-matrix artifact
        _bootstrap_jax()
        from .lattice import run_lattice_checks, write_matrix

        l_findings, l_checked, l_skips, matrix = run_lattice_checks()
        findings_by_layer.append(l_findings)
        checked.extend(l_checked)
        skipped.extend(l_skips)
        if args.matrix:
            write_matrix(matrix, args.matrix)

    if args.show_suppressed:
        from .ast_rules import collect_suppressions, iter_package_files

        sup_targets = args.paths or list(iter_package_files(pkg_root))
        marks = collect_suppressions(sup_targets)
        print(f"suppressions: {len(marks)} marker(s)")
        for path, line, rules in marks:
            what = "all rules" if rules is None else ",".join(sorted(rules))
            print(f"  {path}:{line}: disable={what}")

    report = LintReport(findings=merge(*findings_by_layer),
                        checked_contracts=checked, skipped=skipped)
    if args.json:
        with open(args.json, "w", encoding="utf-8") as f:
            f.write(report.to_json() + "\n")
    if args.sarif:
        with open(args.sarif, "w", encoding="utf-8") as f:
            f.write(to_sarif(report) + "\n")
    print(report.summary())
    return 0 if report.ok else 1


if __name__ == "__main__":
    sys.exit(main())
