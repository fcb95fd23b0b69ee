"""bbtpu-lint CLI: `python -m bloombee_tpu.analysis`.

Exit codes: 0 clean (all findings baselined or suppressed), 1 new
findings or env-docs drift, 2 usage error.

The AST lint itself never imports jax — only `--dump-env-table` /
`--check-env-docs` import the package (to populate the env.declare
registry), which is why scripts/analyze.sh pins JAX_PLATFORMS=cpu.
"""

from __future__ import annotations

import argparse
import sys
from pathlib import Path

from bloombee_tpu.analysis.core import (
    load_baseline,
    load_source_files,
    run_rules,
    write_baseline,
)
from bloombee_tpu.analysis.rules import make_rules

DEFAULT_PATHS = ["bloombee_tpu"]
ENV_TABLE_BEGIN = "<!-- bbtpu-env-table:begin -->"
ENV_TABLE_END = "<!-- bbtpu-env-table:end -->"
LOCK_TABLE_BEGIN = "<!-- bbtpu-lock-table:begin -->"
LOCK_TABLE_END = "<!-- bbtpu-lock-table:end -->"


def find_root(start: Path | None = None) -> Path:
    """Repo root = nearest ancestor holding the bloombee_tpu package,
    so the CLI works from any cwd inside the checkout."""
    cur = (start or Path.cwd()).resolve()
    for cand in (cur, *cur.parents):
        if (cand / "bloombee_tpu" / "__init__.py").exists():
            return cand
    return cur


def resolve_root(paths: list[str]) -> Path:
    """find_root from cwd, else from the path arguments — running
    `python -m bloombee_tpu.analysis /abs/checkout/...` from an
    unrelated cwd must still relativize findings against the checkout,
    or their fingerprints can never match the committed baseline."""
    root = find_root()
    if (root / "bloombee_tpu" / "__init__.py").exists():
        return root
    for p in paths:
        cand = find_root(Path(p))
        if (cand / "bloombee_tpu" / "__init__.py").exists():
            return cand
    return root


def default_baseline(root: Path) -> Path:
    return root / "bloombee_tpu" / "analysis" / "baseline.txt"


def build_env_table() -> str:
    """The authoritative BBTPU_* switch table, straight from the
    env.declare registry (imports the declaring modules)."""
    from bloombee_tpu.utils import env

    env.import_declaring_modules()
    return env.describe().strip()


def check_env_docs(root: Path, readme: str) -> int:
    """Fail when README's generated env table drifted from the live
    registry — an undeclared switch can't appear (BB005 catches raw
    reads), and a declared-but-undocumented one fails here."""
    path = root / readme
    if not path.exists():
        print(f"env-docs: {readme} not found", file=sys.stderr)
        return 1
    text = path.read_text(encoding="utf-8")
    try:
        _, rest = text.split(ENV_TABLE_BEGIN, 1)
        documented, _ = rest.split(ENV_TABLE_END, 1)
    except ValueError:
        print(
            f"env-docs: {readme} lacks the generated switch table "
            f"markers ({ENV_TABLE_BEGIN} ... {ENV_TABLE_END}); "
            "insert them and run scripts/analyze.sh --fix-env-docs",
            file=sys.stderr,
        )
        return 1
    live = build_env_table()
    if documented.strip() != live:
        doc_lines = set(documented.strip().splitlines())
        live_lines = set(live.splitlines())
        for line in sorted(live_lines - doc_lines):
            print(f"env-docs: missing from {readme}: {line}",
                  file=sys.stderr)
        for line in sorted(doc_lines - live_lines):
            print(f"env-docs: stale in {readme}: {line}",
                  file=sys.stderr)
        print(
            f"env-docs: {readme} env-switch table drifted from the "
            "env.declare registry; regenerate with "
            "scripts/analyze.sh --fix-env-docs",
            file=sys.stderr,
        )
        return 1
    return 0


def fix_env_docs(root: Path, readme: str) -> int:
    """Rewrite the README's marker-delimited table from the registry."""
    path = root / readme
    text = path.read_text(encoding="utf-8")
    try:
        head, rest = text.split(ENV_TABLE_BEGIN, 1)
        _, tail = rest.split(ENV_TABLE_END, 1)
    except ValueError:
        print(f"env-docs: {readme} lacks the table markers",
              file=sys.stderr)
        return 1
    path.write_text(
        head
        + ENV_TABLE_BEGIN
        + "\n"
        + build_env_table()
        + "\n"
        + ENV_TABLE_END
        + tail,
        encoding="utf-8",
    )
    print(f"env-docs: regenerated table in {readme}")
    return 0


def _replace_marked(
    root: Path, relpath: str, begin: str, end: str, body: str,
    check_only: bool, what: str,
) -> int:
    """Shared engine for the generated README/ARCHITECTURE tables:
    compare (check) or rewrite (fix) the marker-delimited region."""
    path = root / relpath
    if not path.exists():
        print(f"{what}: {relpath} not found", file=sys.stderr)
        return 1
    text = path.read_text(encoding="utf-8")
    try:
        head, rest = text.split(begin, 1)
        current, tail = rest.split(end, 1)
    except ValueError:
        print(
            f"{what}: {relpath} lacks the generated table markers "
            f"({begin} ... {end})", file=sys.stderr,
        )
        return 1
    if check_only:
        if current.strip() != body.strip():
            print(
                f"{what}: {relpath} drifted from "
                "analysis/lock_hierarchy.py; regenerate with "
                "scripts/analyze.sh --fix-lock-docs",
                file=sys.stderr,
            )
            return 1
        return 0
    path.write_text(
        head + begin + "\n" + body.strip() + "\n" + end + tail,
        encoding="utf-8",
    )
    print(f"{what}: regenerated table in {relpath}")
    return 0


def check_lock_docs(root: Path, fix: bool = False) -> int:
    """ARCHITECTURE.md's lock-hierarchy table is generated from the
    declared registry, same contract as the README env table: drift
    fails the gate, --fix-lock-docs rewrites it."""
    from bloombee_tpu.analysis import lock_hierarchy

    return _replace_marked(
        root, "ARCHITECTURE.md", LOCK_TABLE_BEGIN, LOCK_TABLE_END,
        lock_hierarchy.describe(), check_only=not fix, what="lock-docs",
    )


def render_json(findings, files, baselined: int) -> str:
    """Machine-readable finding list for editor/CI integration. The
    human text format stays byte-stable; tooling parses this instead."""
    import json

    return json.dumps(
        {
            "findings": [
                {
                    "rule": f.code,
                    "fingerprint": f.fingerprint(),
                    "path": f.path,
                    "line": f.line,
                    "location": f"{f.path}:{f.line}",
                    "message": f.message,
                    "chain": list(f.chain),
                }
                for f in findings
            ],
            "files": len(files),
            "baselined": baselined,
        },
        indent=1,
        sort_keys=True,
    )


def main(argv=None) -> int:
    parser = argparse.ArgumentParser(
        prog="python -m bloombee_tpu.analysis", description=__doc__
    )
    parser.add_argument(
        "paths", nargs="*",
        help=f"files/dirs to analyze (default: {' '.join(DEFAULT_PATHS)})",
    )
    parser.add_argument("--baseline", default=None,
                        help="baseline file (default: "
                        "bloombee_tpu/analysis/baseline.txt)")
    parser.add_argument("--no-baseline", action="store_true",
                        help="report every finding, baselined or not")
    parser.add_argument("--update-baseline", action="store_true",
                        help="rewrite the baseline from current findings")
    parser.add_argument("--select", default=None,
                        help="comma-separated BB codes to run (e.g. "
                        "BB001,BB005)")
    parser.add_argument("--list-rules", action="store_true",
                        help="print the rule catalog and exit")
    parser.add_argument("--dump-env-table", action="store_true",
                        help="print the BBTPU_* switch table from the "
                        "env.declare registry and exit")
    parser.add_argument("--check-env-docs", action="store_true",
                        help="additionally verify README's generated "
                        "env table matches the registry")
    parser.add_argument("--fix-env-docs", action="store_true",
                        help="regenerate README's env table and exit")
    parser.add_argument("--check-lock-docs", action="store_true",
                        help="additionally verify ARCHITECTURE.md's "
                        "generated lock-hierarchy table matches "
                        "analysis/lock_hierarchy.py")
    parser.add_argument("--fix-lock-docs", action="store_true",
                        help="regenerate ARCHITECTURE.md's lock table "
                        "and exit")
    parser.add_argument("--json", action="store_true",
                        help="emit new findings as JSON on stdout "
                        "(rule, fingerprint, path:line, call chain); "
                        "summary stays on stderr")
    parser.add_argument("--readme", default="README.md")
    args = parser.parse_args(argv)

    root = resolve_root(args.paths)
    if args.list_rules:
        for r in make_rules():
            print(f"{r.code}  {r.name}: {r.summary}")
        return 0
    if args.dump_env_table:
        print(build_env_table())
        return 0
    if args.fix_env_docs:
        return fix_env_docs(root, args.readme)
    if args.fix_lock_docs:
        return check_lock_docs(root, fix=True)

    rules = make_rules()
    if args.select:
        want = {c.strip().upper() for c in args.select.split(",")}
        unknown = want - {r.code for r in rules}
        if unknown:
            parser.error(f"unknown rule code(s): {sorted(unknown)}")
        rules = [r for r in rules if r.code in want]

    files, findings = load_source_files(
        root, args.paths or DEFAULT_PATHS
    )
    findings = findings + run_rules(files, rules)
    findings.sort(key=lambda f: (f.path, f.line, f.code))

    baseline_path = (
        Path(args.baseline) if args.baseline else default_baseline(root)
    )
    if args.update_baseline:
        write_baseline(baseline_path, findings)
        print(
            f"baseline: wrote {len(findings)} finding(s) to "
            f"{baseline_path}"
        )
        return 0

    baseline = (
        set() if args.no_baseline else load_baseline(baseline_path)
    )
    new = [f for f in findings if f.fingerprint() not in baseline]
    old = len(findings) - len(new)
    if args.json:
        print(render_json(new, files, old))
    else:
        for f in new:
            print(f.render())

    rc = 0
    if new:
        print(
            f"bbtpu-lint: {len(new)} new finding(s) "
            f"({old} baselined) across {len(files)} file(s)",
            file=sys.stderr,
        )
        rc = 1
    else:
        print(
            f"bbtpu-lint: clean — {len(files)} file(s), "
            f"{old} baselined finding(s)",
            file=sys.stderr if args.json else sys.stdout,
        )
    stale = baseline - {f.fingerprint() for f in findings}
    if stale and not args.no_baseline:
        # informational: a fixed finding leaves a dead baseline line
        print(
            f"bbtpu-lint: note: {len(stale)} stale baseline entr"
            f"{'y' if len(stale) == 1 else 'ies'} (fixed findings); "
            "run --update-baseline to prune",
            file=sys.stderr,
        )
    if args.check_env_docs:
        rc = max(rc, check_env_docs(root, args.readme))
    if args.check_lock_docs:
        rc = max(rc, check_lock_docs(root))
    return rc
