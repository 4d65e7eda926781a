"""CLI: `python -m dgraph_tpu.analysis [--format=text|json] [paths...]`.

Exit status 0 = no unwaived findings, 1 = findings (the build-failing
condition tier-1's tests/test_lint.py enforces), 2 = usage error.
Default scan set: the whole dgraph_tpu package.
"""

from __future__ import annotations

import argparse
import json
import pathlib
import sys

from dgraph_tpu.analysis import Analyzer, default_paths


def main(argv: list[str] | None = None) -> int:
    ap = argparse.ArgumentParser(
        prog="python -m dgraph_tpu.analysis",
        description="graftlint: AST invariant checker (rules R1-R12, "
                    "incl. the graftrace lock-discipline rules)")
    ap.add_argument("paths", nargs="*", type=pathlib.Path,
                    help="files/dirs to scan (default: the package)")
    ap.add_argument("--format", choices=("text", "json"),
                    default="text")
    ap.add_argument("--show-waived", action="store_true",
                    help="text mode: also print waived findings")
    ap.add_argument("--facts", action="store_true",
                    help="text mode: print the facts inventory totals")
    args = ap.parse_args(argv)

    repo_root = pathlib.Path(__file__).resolve().parents[2]
    paths = args.paths or default_paths(repo_root)
    a = Analyzer(repo_root=repo_root)
    a.run(paths)

    if args.format == "json":
        print(json.dumps(a.to_json(), indent=2))
    else:
        for f in a.findings:
            if f.waived and not args.show_waived:
                continue
            print(f.format())
        counts = a.counts()
        print(f"graftlint: {len(a.unwaived())} finding(s), "
              f"{sum(counts['waived'].values())} waived, "
              f"{len(a.contexts)} file(s) scanned")
        if args.facts:
            print("facts:", json.dumps(a.facts["totals"]))
    return 1 if a.unwaived() else 0


if __name__ == "__main__":
    sys.exit(main())
