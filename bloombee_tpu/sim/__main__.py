"""``python -m bloombee_tpu.sim [--require]``: run the swarm simulator.

Runs each requested scenario (default: all three) with ≥1000 virtual
sessions on the virtual clock, prints the per-scenario JSON report, and
with ``--require`` exits 1 when any metastability gate fails — shedding
that never reconverges, retry amplification past bound, promotion
flapping, a session starved while capacity existed — the same gate idiom
as ``python -m bloombee_tpu.utils.ledger --require``.
"""

from __future__ import annotations

import argparse
import json
import sys

from bloombee_tpu.sim.scenarios import SCENARIOS, run_scenario
from bloombee_tpu.utils import clock, env


def _main() -> None:
    ap = argparse.ArgumentParser(
        prog="python -m bloombee_tpu.sim", description=__doc__
    )
    ap.add_argument(
        "--require", action="store_true",
        help="exit 1 when any scenario's metastability gate fails",
    )
    ap.add_argument(
        "--scenarios", default=",".join(SCENARIOS),
        help=f"comma-separated subset of: {', '.join(SCENARIOS)}",
    )
    ap.add_argument(
        "--sessions", type=int, default=None,
        help="virtual sessions per scenario (default BBTPU_SIM_SESSIONS)",
    )
    ap.add_argument(
        "--seed", type=int, default=None,
        help="workload seed (default BBTPU_SIM_SEED)",
    )
    ap.add_argument(
        "--smoke", action="store_true",
        help="~200-session quick profile (chaos matrix)",
    )
    ap.add_argument(
        "--json", dest="json_path", default=None,
        help="also write the full report to this file",
    )
    args = ap.parse_args()

    names = [n.strip() for n in args.scenarios.split(",") if n.strip()]
    unknown = [n for n in names if n not in SCENARIOS]
    if unknown:
        ap.error(f"unknown scenario(s): {', '.join(unknown)}")
    sessions = args.sessions
    if sessions is None:
        sessions = 200 if args.smoke else int(env.get("BBTPU_SIM_SESSIONS"))

    wall0 = clock.perf_counter()
    report = {"scenarios": {}, "sessions_per_scenario": sessions}
    failures: list[str] = []
    for name in names:
        result = run_scenario(name, sessions=sessions, seed=args.seed)
        report["scenarios"][name] = result
        failures.extend(result["failures"])
        m = result["metrics"]
        print(
            f"[sim] {name}: {m['completed']}/{m['sessions']} completed, "
            f"ttft p95 {m['ttft_p95_s']:.2f}s, tbt p95 "
            f"{m['tbt_p95_s'] * 1000:.0f}ms, shed {m['shed_total']}, "
            f"retry amp {m['retry_amplification']:.2f}, "
            f"promotions {m['promotions']}, rebalances "
            f"{m['rebalances_moved']} ({result['wall_s']:.1f}s wall, "
            f"{result['advances']} advances)"
        )
    report["ok"] = not failures
    report["failures"] = failures
    report["wall_s"] = round(clock.perf_counter() - wall0, 3)
    if args.json_path:
        with open(args.json_path, "w") as f:
            json.dump(report, f, indent=2, default=str)
    else:
        print(json.dumps(report, indent=2, default=str))

    if failures:
        for f in failures:
            print(f"[sim] GATE FAILED: {f}", file=sys.stderr)
        if args.require:
            sys.exit(1)
    elif args.require:
        print(f"[sim] all gates passed ({report['wall_s']:.1f}s wall)")


if __name__ == "__main__":
    _main()
