"""Compute-cost model: what a dispatch costs in device seconds.

The simulator replaces ``SpanExecutor`` with ``clock.sleep(cost)`` on the
compute thread; this module decides the cost: a fixed per-dispatch
overhead (jit call + host sync) plus per-row work for fused ragged decode
and per-token work for prefill chunks, both scaling with the span's block
count.

The defaults are CPU magnitudes from before the system ran on a chip; they
were not measured on a TPU. The scenario gates compare runs under the same
constants, so they decide on ratios, not on these absolute values.
"""

from __future__ import annotations

import dataclasses


@dataclasses.dataclass
class CostModel:
    """Per-dispatch device-seconds model, all knobs in milliseconds."""

    dispatch_ms: float = 2.0  # fixed jit-call + host-sync overhead
    decode_row_ms_per_block: float = 0.25  # one decode row, one block
    prefill_tok_ms_per_block: float = 0.05  # one prefill token, one block
    hop_rtt_ms: float = 10.0  # client<->server wire round trip

    def decode_group_s(self, rows: int, blocks: int) -> float:
        """One fused decode dispatch of `rows` coalesced sessions."""
        return (
            self.dispatch_ms
            + self.decode_row_ms_per_block * blocks * max(1, rows)
        ) / 1000.0

    def prefill_chunk_s(self, tokens: int, blocks: int) -> float:
        """One prefill-chunk dispatch of `tokens` total tokens."""
        return (
            self.dispatch_ms
            + self.prefill_tok_ms_per_block * blocks * max(1, tokens)
        ) / 1000.0

    def group_s(self, kind: str, rows: int, tokens: int,
                blocks: int) -> float:
        if kind == "decode":
            return self.decode_group_s(rows, blocks)
        return self.prefill_chunk_s(tokens, blocks)
