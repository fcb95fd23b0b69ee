#!/usr/bin/env python3
"""Spread of a set of runs, with the driver's estimator (cellbench/stats.py).

    python3 cellbench/spread.py <set1 dir or files...> [-- <set2 ...>]

Each argument is a file whose last line is a result line of cellbench/run.py
(or a directory of such *.out files); `--` separates two sets of same-code
runs. Host-clock candidates that the cell does not report as end-to-end
metrics are read from the run's `candidates` line. Prints, per metric, each set's median, the wide spread (all runs) and
the trimmed one (farthest run left out), and the bound the contract's rule
gives: five times the wider of the two wide spreads, never under 1%.
"""

from __future__ import annotations

import json
import pathlib
import sys

ROOT = pathlib.Path(__file__).resolve().parent.parent
sys.path.insert(0, str(ROOT))

from cellbench import stats  # noqa: E402


def read_set(args: list[str]) -> dict[str, list[float]]:
    files: list[pathlib.Path] = []
    for a in args:
        p = pathlib.Path(a)
        files += sorted(p.glob("*.out")) if p.is_dir() else [p]
    values: dict[str, list[float]] = {}
    for f in files:
        lines = f.read_text().strip().splitlines()
        if not lines:
            continue
        last = json.loads(lines[-1])
        if "metrics" not in last:
            continue
        for name, m in last["metrics"].items():
            values.setdefault(name, []).append(m["value"])
        # the candidates a cell does not report as end-to-end metrics (yet)
        for line in lines[:-1]:
            if '"phase": "candidates"' in line:
                for name, v in json.loads(line).items():
                    if name.endswith(("_p50", "_p95", "_per_s")) and (
                            name not in last["metrics"]):
                        values.setdefault(name + " (candidate)", []).append(v)
        values.setdefault("_correct", []).append(float(last["correct"]))
    return values


def main(argv: list[str]) -> int:
    split = argv.index("--") if "--" in argv else len(argv)
    sets = [read_set(argv[:split])] + (
        [read_set(argv[split + 1:])] if split < len(argv) else [])
    for name in sorted(sets[0]):
        if name.startswith("_"):
            print(name, [s.get(name) for s in sets])
            continue
        spreads = [stats.driver_spread(s[name]) for s in sets
                   if len(s.get(name, ())) >= 3]
        if not spreads:
            continue
        widest = max(s["wide"] for s in spreads)
        mean_trimmed = sum(s["trimmed"] for s in spreads) / len(spreads)
        bound = max(0.01, 5 * widest)
        print(f"{name:24s} " + "  ".join(
            f"n={s['n']} med={s['median']:.5g} wide={s['wide']:.4f} "
            f"trim={s['trimmed']:.4f}" for s in spreads)
            + f"  | rule bound={bound:.4f} widest/bound={widest / bound:.2f}"
              f" mean_trim/bound={mean_trimmed / bound:.2f}")
    return 0


if __name__ == "__main__":
    sys.exit(main(sys.argv[1:]))
