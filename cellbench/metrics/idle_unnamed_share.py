"""Executor and jitted step: the device idle while the innermost span open
on the compute thread was `bbtpu.task` or `bbtpu.dispatch` itself, so under
no span that names the work, over all idle seconds of the traced 5 s
(`hosttrace.json` `idle.by_span_s`), %. The device-clock side of
`host_task_unnamed_share`."""

from cellbench import hostpath


def read(ctx: dict):
    return hostpath.idle_by_span_share(ctx, "bbtpu.task", "bbtpu.dispatch")
