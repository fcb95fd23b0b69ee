"""Executor and jitted step: of the wall time of the window's tasks on the
compute thread, all kinds, the part the thread was NOT running
((`cpu_wall_ms` - `cpu_ms`) / `cpu_wall_ms` of
`rpc_info["memory"]["host_path"]`, info1 - info0: the thread's CPU,
`time.thread_time_ns` beside every span's wall clock, in the one task of 32
the program reads in full, against the wall of those tasks), %: waits for the
interpreter lock, the scheduler, a blocking call or the device queue's
back-pressure. None for a program without the account or a window with no
task read in full."""

from cellbench import hostpath


def read(ctx: dict):
    rec = hostpath.total(ctx)
    return rec and hostpath.share(rec["cpu_wall_ms"] - rec["cpu_ms"],
                                  rec["cpu_wall_ms"])
