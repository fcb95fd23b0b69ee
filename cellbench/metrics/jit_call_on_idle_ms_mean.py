"""Executor and jitted step: the jit call's wall time where the launch found
the device idle (asked in the one task of 32 read in full), ms a launch, all
kinds (`jit_idle_ms` / `launches_on_idle`
of `rpc_info["memory"]["host_path"]`, info1 - info0): the call's own cost,
with no back-pressure of the device's queue in it (a call on a busy device
holds both: `jit_busy_ms`). None for a program without the account or a
window in which no launch found the device idle."""

from cellbench import hostpath


def read(ctx: dict):
    rec = hostpath.total(ctx)
    if not rec or not rec["launches_on_idle"]:
        return None
    return rec["jit_idle_ms"] / rec["launches_on_idle"]
