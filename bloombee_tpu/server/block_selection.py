"""Swarm load balancing: which blocks should a new server host?

Port of /root/reference/src/bloombee/server/block_selection.py:12-95:
build the per-block aggregate-throughput vector from announced spans, pick
the contiguous window with minimum total throughput (the least-served
region), and decide whether an existing server should move
(`should_choose_other_blocks` with the balance_quality=0.75 hysteresis so
servers don't thrash).
"""

from __future__ import annotations

from collections import deque

import numpy as np

from bloombee_tpu.swarm.data import ModuleInfo, RemoteSpanInfo
from bloombee_tpu.swarm.load import predicted_queue_delay_s
from bloombee_tpu.utils import clock, env

BALANCE_QUALITY = 0.75

env.declare(
    "BBTPU_MEASURED_REBALANCE", bool, True,
    "weight the rebalance objective by live load adverts: a server's "
    "contribution to per-block throughput is discounted by its predicted "
    "queue delay (staleness-discounted, hostile-advert-bounded — same "
    "term the client router uses), so chronically hot spans attract "
    "movers and idle spans shed them. Servers without a load advert keep "
    "their static throughput, so a swarm with no adverts reduces to the "
    "static Petals objective (cold-start fallback). Off = static "
    "objective always",
)


def _effective_throughput(server, now: float | None) -> float:
    """A server's load-discounted contribution to block throughput: the
    static announced rate divided by (1 + predicted queue delay). The
    delay term is the shared swarm/load.py reading of the advert —
    bounded by LOAD_DELAY_CAP_S, so a hostile advert can shrink only its
    OWN server's weight and only ~11x; absent/stale adverts contribute 0
    delay, leaving the static throughput untouched."""
    t = server.throughput or 0.0
    return t / (1.0 + predicted_queue_delay_s(server, now))


def block_throughputs(
    module_infos: list[ModuleInfo],
    measured: bool = False,
    now: float | None = None,
) -> np.ndarray:
    """Aggregate announced throughput per block. With measured=True each
    server's contribution is discounted by its live load advert (see
    _effective_throughput); with no adverts in the swarm the result is
    identical to the static aggregate."""
    if measured and now is None:
        now = clock.now()
    out = np.zeros(len(module_infos))
    for i, info in enumerate(module_infos):
        for server in info.servers.values():
            if measured:
                out[i] += _effective_throughput(server, now)
            else:
                out[i] += server.throughput or 0.0
    return out


def choose_best_blocks(
    module_infos: list[ModuleInfo],
    spans: dict[str, RemoteSpanInfo],
    num_blocks: int,
    spec=None,
) -> tuple[int, int]:
    """Least-served contiguous window of `num_blocks`; given the model's
    `spec`, among the windows the family can serve
    (`ModelSpec.span_unsupported`: a span of whole periods, one that does
    not cut a cross-decoder off the layers it reads)."""
    tput = block_throughputs(module_infos)
    num_blocks = min(num_blocks, len(tput))
    best_start, best_sum, reason = None, float("inf"), None
    for start in range(len(tput) - num_blocks + 1):
        if spec is not None:
            why = spec.span_unsupported(start, start + num_blocks)
            if why is not None:
                reason = why
                continue
        s = float(tput[start : start + num_blocks].sum())
        if s < best_sum:
            best_start, best_sum = start, s
    if best_start is None:
        raise ValueError(
            f"no window of {num_blocks} blocks can be served: {reason}"
        )
    return best_start, best_start + num_blocks


def _best_landing(
    without: np.ndarray, n: int, t: float
) -> tuple[float | None, int | None]:
    """Best window of length `n` to add throughput `t` onto `without`:
    returns (resulting bottleneck min, window start), maximizing the min.
    O(blocks) — equivalent to copying the array per candidate start and
    taking its min (the naive O(blocks^2) form this replaced; equivalence
    is property-tested in tests/test_rebalance.py), because the candidate
    min decomposes into min(prefix-min before the window, window-min + t,
    suffix-min after), with window minima from one monotonic-deque sweep.
    Ties keep the earliest start, matching the naive scan order."""
    b = len(without)
    if n <= 0 or n > b:
        return None, None
    inf = float("inf")
    prefix = np.empty(b + 1)  # prefix[i] = min(without[:i])
    prefix[0] = inf
    np.minimum.accumulate(without, out=prefix[1:])
    suffix = np.empty(b + 1)  # suffix[i] = min(without[i:])
    suffix[b] = inf
    suffix[:b] = np.minimum.accumulate(without[::-1])[::-1]
    best, best_start = None, None
    dq: deque[int] = deque()  # indices of increasing window candidates
    for i in range(b):
        while dq and without[dq[-1]] >= without[i]:
            dq.pop()
        dq.append(i)
        start = i - n + 1
        if dq[0] < start:
            dq.popleft()
        if start >= 0:
            m = min(
                float(prefix[start]),
                float(without[dq[0]]) + t,
                float(suffix[start + n]),
            )
            if best is None or m > best:
                best, best_start = m, start
    return best, best_start


def _rebalance_decision(
    peer_id: str,
    module_infos: list[ModuleInfo],
    spans: dict[str, RemoteSpanInfo],
    measured: bool | None = None,
    now: float | None = None,
) -> tuple[tuple[int, int] | None, bool]:
    """(target, skipped_by_hysteresis): the move decision plus whether a
    strictly-better landing existed but fell inside the BALANCE_QUALITY
    margin (surfaced as the rebalance_skipped_hysteresis counter)."""
    my_span = spans.get(peer_id)
    if my_span is None:
        return None, False
    if measured is None:
        measured = bool(env.get("BBTPU_MEASURED_REBALANCE"))
    if now is None:
        now = clock.now()
    tput = block_throughputs(module_infos, measured=measured, now=now)
    current_min = float(tput.min())

    # simulate leaving: subtract the same contribution block_throughputs
    # added for me (load-discounted in measured mode)
    mine = (
        _effective_throughput(my_span.server_info, now)
        if measured
        else (my_span.server_info.throughput or 0.0)
    )
    without = tput.copy()
    without[my_span.start : my_span.end] -= mine
    # best place to re-land. The mover lands with its STATIC throughput
    # even in measured mode: moving drains its queue, so its current
    # congestion should not follow it to the new span (that asymmetry is
    # what makes hot spans attract movers and lets a hot mover escape).
    n = my_span.length
    best, best_start = _best_landing(
        without, n, my_span.server_info.throughput or 0.0
    )
    if best is None or (best_start, best_start + n) == (
        my_span.start, my_span.end
    ):
        # in measured mode "re-land where I am, minus my queue" can look
        # like an improvement; staying put is never a move
        return None, False
    if best * BALANCE_QUALITY > current_min:
        return (best_start, best_start + n), False
    # a strictly better landing exists but not by enough to beat the
    # thrash-guard margin
    return None, best > current_min


def rebalance_target(
    peer_id: str,
    module_infos: list[ModuleInfo],
    spans: dict[str, RemoteSpanInfo],
    measured: bool | None = None,
    now: float | None = None,
) -> tuple[int, int] | None:
    """The (start, end) this server should move its span to, or None when
    staying put is within the hysteresis margin. Simulates leaving and
    re-landing at every window, keeping the one that maximizes the swarm's
    bottleneck (minimum per-block) throughput; a move only wins if it
    beats the current bottleneck by more than BALANCE_QUALITY (reference
    should_choose_other_blocks, block_selection.py:40-95). With
    measured=True (default via BBTPU_MEASURED_REBALANCE) per-server
    throughput is discounted by live load adverts; a swarm with no
    adverts degrades to the static objective."""
    target, _ = _rebalance_decision(
        peer_id, module_infos, spans, measured=measured, now=now
    )
    return target


def should_choose_other_blocks(
    peer_id: str,
    module_infos: list[ModuleInfo],
    spans: dict[str, RemoteSpanInfo],
) -> bool:
    """Would moving this server's span improve the swarm's bottleneck
    throughput by more than the hysteresis margin?"""
    if spans.get(peer_id) is None:
        return True
    return rebalance_target(peer_id, module_infos, spans) is not None


def kv_token_bytes(spec, itemsize: int = 2) -> int:
    """Bytes ONE cached token takes in ONE layer: K and V head slabs, or
    what a latent-attention family's page holds (one latent and one rotary
    key, models/spec.py `MlaSpec.page_payload`)."""
    if spec.mla is not None:
        return sum(s[0] for s in spec.mla.page_payload) * itemsize
    return 2 * spec.num_key_value_heads * spec.head_dim * itemsize


def estimate_span_bytes(spec, dtype, start: int, end: int) -> int:
    """Parameter bytes of blocks [start, end), counted by layer KIND (a
    family whose first layers are dense and the rest sparse)."""
    return sum(
        estimate_block_bytes(spec, dtype, layer) for layer in range(start, end)
    )


def estimate_block_bytes(spec, dtype, layer: int | None = None) -> int:
    """Parameter bytes of one block (reference block_utils.get_block_size:
    param count x dtype width, meta-device instantiation not needed — the
    spec already knows the shapes). `layer` says WHICH block where the kinds
    differ (default: the last one, the kind most of the model has)."""
    import numpy as np

    d, i = spec.hidden_size, spec.intermediate_size
    h, kv, hd = (
        spec.num_attention_heads, spec.num_key_value_heads, spec.head_dim,
    )
    attn = d * h * hd + 2 * d * kv * hd + h * hd * d
    if layer is None:
        layer = spec.num_hidden_layers - 1
    if spec.one_sublayer:
        # ONE sublayer and one norm, as stored (models/nemotron_h.py): the
        # mixer's in_proj and an expert's intermediate width in whole lanes,
        # two matrices an expert; the recurrence's vectors and the router's
        # bias stay float32 whatever the dtype
        from bloombee_tpu.models.layout import lane_padded

        ssm, kind = spec.ssm, spec.layer_type(layer)
        params, f32 = {
            "mamba": (
                d * lane_padded(ssm.proj_dim) + ssm.d_ssm * d
                + (ssm.conv + 1) * ssm.conv_dim + ssm.d_ssm, 3 * ssm.heads),
            "moe": (
                spec.experts_held[1] * 2 * d
                * lane_padded(spec.moe_intermediate_size)
                + d * spec.num_experts
                + 2 * d * spec.moe_shared_intermediate, spec.num_experts),
            "full": (attn, 0),
        }[kind]
        itemsize = np.dtype(dtype).itemsize if dtype is not None else 2
        return (params + d) * itemsize + f32 * 4
    if spec.attn_gate:
        attn += d * h * hd + 2 * hd  # the gate rows of q_proj, q/k norms
    linear = spec.gdn is not None and spec.layer_type(layer) == "linear"
    if linear:
        # a delta-rule mixer in attention's place: in_proj (q | k | v | z),
        # b | a as stored (two whole lanes), out_proj, the taps, the gated
        # norm, A_log / dt_bias; with a decay a key channel (kimi_linear)
        # in_proj is q | k | v, the narrow products three whole lanes, the
        # decay's and the gate's second halves, a dt_bias a channel
        from bloombee_tpu.models.layout import LANES

        g = spec.gdn
        attn = (
            d * g.proj_dim + g.d_value * d + g.conv * g.conv_dim
            + g.value_dim + g.value_heads
        ) + (
            d * 3 * LANES + g.gate_rank * (g.d_key + g.d_value) + g.d_key
            if g.gate_rank else d * 2 * LANES + g.value_heads
        )
    if spec.mamba is not None:
        # a SambaY layer by its kind: q | k | v (k, v as PAIRS of 128) and
        # o with their biases; a cross layer q and o only; a Mamba-1 mixer
        # (in_proj, x_proj as stored: whole lanes, dt_proj, out_proj, taps,
        # A, D, biases); a gated memory unit's two projections
        from bloombee_tpu.models.layout import lane_padded

        mb, kind = spec.mamba, spec.layer_type(layer)
        c = mb.d_inner
        attn = {
            "mamba": 3 * d * c + c * lane_padded(mb.x_proj_dim)
            + mb.dt_rank * c + c * (mb.conv + mb.state + 3),
            "gmu": 2 * d * c,
            "cross": 2 * d * d + 2 * d + hd + 2,
        }.get(kind, 2 * d * d + 2 * d * kv * hd + 2 * d + 2 * kv * hd + hd + 2)
    if spec.mla is not None and not linear:
        m = spec.mla
        # the query through its low-rank pair and norm, or ONE full-rank
        # projection (`q_rank` 0)
        query = (
            d * m.q_rank + m.q_rank * h * m.qk_dim + m.q_rank if m.q_rank
            else d * h * m.qk_dim
        )
        attn = (
            query + d * (m.kv_rank + m.rope_dim)
            + m.kv_rank * h * (m.nope_dim + m.v_dim) + h * m.v_dim * d
            + m.kv_rank
        )
    if spec.num_experts and spec.mlp_kind(layer) == "sparse" and (
        spec.moe_intermediate_size
    ):
        # the experts this server HOLDS, the router over all of them, the
        # shared experts
        mlp = (
            spec.experts_held[1] * 3 * d * spec.moe_intermediate_size
            + d * spec.num_experts + 3 * d * spec.moe_shared_intermediate
            + (d if spec.moe_shared_gate else 0)
        )
    elif spec.num_experts and spec.mlp_kind(layer) == "sparse":
        mlp = spec.num_experts * 3 * d * i + d * spec.num_experts
    elif spec.mlp_type == "silu" or spec.mlp_type == "gelu_tanh_gated":
        mlp = 3 * d * i
    else:
        mlp = 2 * d * i
    norms = 4 * d
    mixer = 0
    if spec.ssm is not None:
        # a state-space mixer beside attention: in_proj, out_proj, the
        # convolution's taps and bias, the grouped norm, A_log / D / dt_bias
        # (in_proj as it is stored: padded to whole lanes, models/layout.py)
        from bloombee_tpu.models.layout import lane_padded

        ssm = spec.ssm
        mixer = (
            d * lane_padded(ssm.proj_dim) + ssm.d_ssm * d
            + (ssm.conv + 1) * ssm.conv_dim + ssm.d_ssm + 3 * ssm.heads
        )
    itemsize = np.dtype(dtype).itemsize if dtype is not None else 2
    return (attn + mlp + norms + mixer) * itemsize


def choose_num_blocks(
    spec, dtype, num_pages: int, page_size: int, memory_fraction: float = 0.8,
    max_batch: int = 8,
) -> int:
    """How many blocks fit in this device's memory, after the KV arena
    (reference Server._choose_num_blocks, server.py:427-477). The CPU
    backend has no device memory to budget and serves the whole model; an
    accelerator that reports no memory limit is an error, not a licence
    to load everything."""
    import numpy as np

    import jax

    device = jax.devices()[0]
    if device.platform == "cpu":
        return spec.num_hidden_layers
    limit = (device.memory_stats() or {}).get("bytes_limit")
    if not limit:
        raise RuntimeError(
            f"{device} reports no memory limit; pass --num-blocks or "
            "--blocks to size the span by hand"
        )
    per_block = estimate_block_bytes(spec, dtype)
    arena_bytes = (
        num_pages * page_size * kv_token_bytes(spec, np.dtype(dtype).itemsize)
    )  # per layer (k+v, or a latent page)
    # a family with recurrent state also holds a slot per sequence and layer
    from bloombee_tpu.kv.arena import state_slot_bytes
    from bloombee_tpu.kv.cache_manager import state_slots_for

    state_bytes = 0
    if spec.recurrent is not None:
        state_bytes = state_slots_for(
            spec, num_pages, page_size, max_batch
        ) * state_slot_bytes(spec.recurrent, np.dtype(dtype).itemsize)
    budget = limit * memory_fraction
    if spec.one_sublayer:
        # the periods differ in length: the longest window of whole periods
        # whose weights and arena rows fit (at least the shortest period)
        cuts = spec.period_starts()
        fits = [
            b - a for a in cuts for b in cuts if b > a and (
                estimate_span_bytes(spec, dtype, a, b)
                + spec.arena_layers(a, b)[0] * arena_bytes
                + spec.arena_layers(a, b)[1] * state_bytes
            ) <= budget
        ]
        return max(fits or [min(b - a for a, b in zip(cuts, cuts[1:]))])
    if spec.gdn is not None:
        # the kinds interleave: count by whole periods, each layer's own
        # weights and the ONE arena it has a row in
        per = spec.period
        kv_layers, state_layers = spec.arena_layers(0, per)
        period = (
            estimate_span_bytes(spec, dtype, 0, per)
            + kv_layers * arena_bytes + state_layers * state_bytes
        )
        n = per * int(budget // period)
        return max(per, min(n, spec.num_hidden_layers // per * per))
    n = int(budget // (per_block + arena_bytes + state_bytes))
    if spec.mamba is not None:
        # whole (mixer, attention) pairs; which windows of them a server
        # may take is `choose_best_blocks`'s (`span_unsupported`)
        return max(2, min(n - n % 2, spec.num_hidden_layers))
    return max(1, min(n, spec.num_hidden_layers))


def _bump(server, counter: str) -> None:
    """Increment an optional counter attribute (fake/minimal servers in
    tests don't carry the counter surface; skip them silently)."""
    try:
        setattr(server, counter, getattr(server, counter, 0) + 1)
    except (AttributeError, TypeError):
        pass


async def rebalance_if_needed(server) -> bool:
    """Periodic check driven by the server's supervisor loop: fetch swarm
    state, decide, and MOVE (drain, reload the new span, re-announce) via
    server.rebalance_to. Returns True when a move happened (reference
    server.py:479-542 _should_choose_other_blocks + restart loop). Every
    decision feeds a counter (rebalances_moved / rebalances_failed /
    rebalance_skipped_hysteresis) surfaced via rpc_info + health --probe."""
    from bloombee_tpu.swarm.spans import compute_spans

    infos = await server.registry.get_module_infos(
        server.model_uid, range(server.spec.num_hidden_layers)
    )
    # a DRAINING server is leaving: its span is not real coverage, so the
    # balance decision must see the post-departure swarm
    target, skipped = _rebalance_decision(
        server.server_id, infos, compute_spans(infos, include_draining=False)
    )
    if skipped:
        _bump(server, "rebalance_skipped_hysteresis")
    if target is None or target == (server.start_block, server.end_block):
        return False
    try:
        await server.rebalance_to(*target)
    except Exception:
        # rebalance_to's own failure path re-announces the old span; the
        # supervisor tick logs and retries next period
        _bump(server, "rebalances_failed")
        raise
    _bump(server, "rebalances_moved")
    return True
