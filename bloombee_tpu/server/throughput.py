"""Server throughput measurement + announcement.

Port of /root/reference/src/bloombee/server/throughput.py:44-345: measure
real decode steps through the span executor and fold the result into the
announced ServerInfo so client routing can rank servers. Measured on every
start: it is a compile the warm-up shares plus a few decode steps, and a
number cached from another build or device would be announced as this
one's.
"""

from __future__ import annotations

import logging

import numpy as np

from bloombee_tpu.utils import clock

logger = logging.getLogger(__name__)


async def measure_and_announce(server, batch: int = 1, steps: int = 8) -> float:
    """Measure inference rps and fold it into announcements."""
    from bloombee_tpu.server.compute_queue import PRIORITY_TRAINING

    d = server.spec.hidden_size
    async with server.manager.allocate(batch, steps + 8) as handle:
        hidden = np.zeros((batch, 1, d), np.float32)

        def decode():
            # route through the compute queue: it is the single
            # serialization point for device work and the shared donated
            # KV arena. Each step returns its result fetched to the host
            # (executor.decode's default), so the clock below stops on
            # finished work, not on an enqueue
            return server.compute.submit(
                PRIORITY_TRAINING, server.executor.decode, handle, hidden
            )

        await decode()  # compile
        # real wall time on purpose: this is a hardware measurement
        # (announced rps), not a timing decision — a scaled test
        # clock must not inflate it
        t0 = clock.perf_counter()
        for _ in range(steps):
            await decode()
        rps = steps / max(clock.perf_counter() - t0, 1e-9)
    logger.info("measured %.2f inference rps", rps)
    server.throughput = rps
    server.inference_rps = rps
    return rps
