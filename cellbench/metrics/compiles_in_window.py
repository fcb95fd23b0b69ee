"""Executor: span-step programs compiled in the server during the window
(jitwatch's steady-state recompiles via rpc_info: compiles attributed to a
dispatch after the warm-up fence, persistent-cache loads left out). Each costs
0.6-4.2 s of the compute thread on a cold cache. The benchmark's warm-up
drives every bucket the schedule can reach, so 0 is expected; which fused
groups form depends on arrival times, and a program the warm-up missed is the
program's cost under this traffic: it is reported here, and the outputs stay
correct."""

from cellbench import stats


def read(ctx: dict):
    return stats.delta(ctx, "steady_state_recompiles")
