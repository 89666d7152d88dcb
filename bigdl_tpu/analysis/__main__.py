"""CLI: ``python -m bigdl_tpu.analysis <model-name|all|path...>``.

Model targets (names from ``models/registry.py``, or ``all``) run the
static shape/dtype pass over the freshly built model; path targets run
the tracer-leak AST lint.  Exit status is nonzero when any
error-severity diagnostic fires (``--fail-on`` adjusts the bar), so the
command drops straight into CI.

Examples::

    python -m bigdl_tpu.analysis resnet            # one zoo model
    python -m bigdl_tpu.analysis all -v            # every model, verbose
    python -m bigdl_tpu.analysis bigdl_tpu/ tools/ # AST lint
    python -m bigdl_tpu.analysis --list-rules      # the rule catalog
"""

from __future__ import annotations

import argparse
import os
import sys
from typing import List

from bigdl_tpu.analysis.diagnostics import RULES, Report, Severity


def _list_rules() -> None:
    width = max(len(r) for r in RULES)
    for rule, (severity, desc) in sorted(RULES.items()):
        print(f"{rule:<{width}}  {str(severity):<7}  {desc}")


def _check_one_model(name: str, args) -> Report:
    from bigdl_tpu.analysis.api import check_model
    from bigdl_tpu.analysis.shape_pass import format_spec
    from bigdl_tpu.models import registry

    text = not args.json  # --json must emit NOTHING but the JSON array
    if text:
        print(f"== {name} ==")
    try:
        model = registry.build_model(name, args.num_classes)
        spec = registry.input_spec(name, args.batch)
    except Exception as e:  # noqa: BLE001 - construction errors are findings
        report = Report(suppress=args.suppress)
        report.add("shape/mismatch",
                   f"model construction failed: "
                   f"{type(e).__name__}: {e}")  # main() prefixes the name
        if text:
            print(report.format())
        return report
    res = check_model(model, spec, suppress=args.suppress)
    if text and args.verbose:
        for row in res.layers:
            print(f"  {row.path:<60} {format_spec(row.out)}")
    if text and res.out is not None:
        print(f"  input  {format_spec(spec)}")
        print(f"  output {format_spec(res.out)}")
    if text:
        print(res.report.format())
    return res.report


def main(argv=None) -> int:
    p = argparse.ArgumentParser(
        prog="bigdl_tpu.analysis",
        description="static graph checker + tracer-leak linter")
    p.add_argument("targets", nargs="*",
                   help="model names (see models/registry.py), 'all', "
                        "or file/directory paths to AST-lint")
    p.add_argument("--lint", action="store_true",
                   help="treat every target as a path to lint")
    p.add_argument("-b", "--batch", type=int, default=2,
                   help="batch size for the abstract input spec")
    p.add_argument("--num-classes", type=int, default=0)
    p.add_argument("-v", "--verbose", action="store_true",
                   help="print the per-layer output-spec table")
    p.add_argument("--json", action="store_true",
                   help="emit diagnostics as JSON")
    p.add_argument("--suppress", action="append", default=[],
                   metavar="RULE", help="suppress a rule id (repeatable)")
    p.add_argument("--fail-on", choices=("error", "warning", "never"),
                   default="error")
    p.add_argument("--list-rules", action="store_true",
                   help="print the rule catalog and exit")
    args = p.parse_args(argv)

    if args.list_rules:
        _list_rules()
        return 0
    if not args.targets:
        p.error("no targets; pass model names, 'all', or paths")

    from bigdl_tpu.models import registry

    model_targets: List[str] = []
    path_targets: List[str] = []
    for t in args.targets:
        if not args.lint and t == "all":
            model_targets.extend(registry.model_names())
        elif not args.lint and t in registry.MODELS:
            model_targets.append(t)
        elif os.path.exists(t):
            path_targets.append(t)
        else:
            p.error(f"target {t!r} is neither a registry model "
                    f"({registry.model_names()}) nor an existing path")

    combined = Report(suppress=args.suppress)
    for name in model_targets:
        report = _check_one_model(name, args)
        for d in report:  # combined/JSON view must name the model
            d.where = f"{name}:{d.where}" if d.where else name
        combined.extend(report)
    if path_targets:
        from bigdl_tpu.analysis.ast_lint import lint_paths

        report = lint_paths(path_targets, suppress=args.suppress)
        if not args.json:
            print(report.format())
        combined.extend(report)

    if args.json:
        print(combined.to_json())
    if args.fail_on == "never":
        return 0
    bar = Severity.ERROR if args.fail_on == "error" else Severity.WARNING
    return 1 if any(d.severity >= bar for d in combined) else 0


if __name__ == "__main__":
    sys.exit(main())
