"""SpanExecutor: host-side orchestration around the jitted span step.

Covers the roles of the reference's TransformerBackend.inference_step plumbing
(/root/reference/src/bloombee/server/backend.py:487-789): cache select/update,
mask choice, chunked prefill (`_estimate_max_chunk_length`, backend.py:839-845)
— but with bucketed static shapes instead of dynamic ones. Each distinct
(batch, tokens, pages) bucket compiles once; subsequent steps reuse the cached
executable (the CUDA-graph role of the reference's cuda_graphs.py).
"""

from __future__ import annotations

import collections
import functools
import logging
import threading

import numpy as np

import jax
import jax.numpy as jnp

import ml_dtypes

from bloombee_tpu.kv.arena import page_view_free, rows_fill_pages
from bloombee_tpu.kv.cache_manager import CacheHandle, CacheManager
from bloombee_tpu.models.spec import ModelSpec
from bloombee_tpu.runtime.step import (
    experts_form,
    pack_plan,
    pack_ragged_plan,
    pack_chunk_on_flash,
    pack_cross_tail,
    pack_ragged_ssm_tail,
    pack_step_payload,
    span_step_packed,
    span_step_ragged,
)
from bloombee_tpu.ops.linear_attention import sequence_blocks
from bloombee_tpu.ops.moe import reach_fields
from bloombee_tpu.ops.pallas.flash_attention import flash_takes, flash_tiles
from bloombee_tpu.ops.pallas.paged_attention import (
    _pages_per_step,
    walk_bounds,
)
from bloombee_tpu.runtime.layer_body import chunk_run_pages
from bloombee_tpu.utils import env, jitwatch

logger = logging.getLogger(__name__)

env.declare(
    "BBTPU_FLASH_ATTENTION", bool, True,
    "use the Pallas flash kernel for eligible long prefill steps (T>=128, "
    "causal, uniform context lengths, no tree/window/alibi/softcap)",
)
env.declare(
    "BBTPU_PAGED_ATTENTION", bool, True,
    "use the Pallas paged-attention kernel for eligible single-token decode "
    "steps (T=1, dense arena, no tree/window/alibi/softcap); TPU backend "
    "only unless BBTPU_PAGED_INTERPRET asks for the interpreter (tests)",
)
env.declare(
    "BBTPU_PAGED_MIN_CONTEXT", int, 512,
    "use the paged decode kernel only when the bucketed context is at least "
    "this many tokens (the crossover vs the dense gather path; where it "
    "really lies on the current chip is not measured)",
)
env.declare(
    "BBTPU_PAGED_INTERPRET", bool, False,
    "run the paged kernels in Pallas interpreter mode: the only way onto "
    "the kernel path off a TPU (CPU parity tests; far too slow to serve)",
)
env.declare(
    "BBTPU_FLASH_INTERPRET", bool, False,
    "run the flash prefill kernel in Pallas interpreter mode: the only way "
    "onto the kernel path off a TPU (CPU parity tests; far too slow to "
    "serve)",
)
env.declare(
    "BBTPU_SP_MIN_TOKENS", int, 1024,
    "spread a session's prefill over the --sp mesh (ring attention) only "
    "when the prompt has at least this many tokens; short prefills stay "
    "single-chip (chunk overhead + collectives would dominate)",
)
env.declare(
    "BBTPU_PREFILL_CHUNK", int, 0,
    "stall-free scheduling (Sarathi-Serve): split prefills into chunks of "
    "at most this many tokens, each a separate compute-queue task so "
    "queued decode steps run between chunks (0 = monolithic prefill, one "
    "queue task for the whole prompt). Rounded to a power of two so every "
    "chunk hits the same compiled bucket",
)


def _kernels_available(interpret_switch: str) -> bool:
    """Pallas kernels compile for a TPU; on any other backend the kernel
    path is taken only through the explicit interpret switch (tests)."""
    return jax.default_backend() == "tpu" or bool(env.get(interpret_switch))


def next_pow2(n: int, floor: int = 1) -> int:
    v = floor
    while v < n:
        v *= 2
    return v


def prefill_chunk_len(budget: int, cap: int | None = None) -> int:
    """The chunk length a `budget`-token prefill budget plans with: the
    budget rounded DOWN to a power of two (never above the operator's
    budget; `cap` bounds it further, e.g. at max_chunk_tokens), so that
    every full chunk compiles into the SAME (batch, tokens) bucket. 0 where
    the budget disables chunking."""
    if budget <= 0:
        return 0
    b = next_pow2(int(budget))
    if b > budget:
        b //= 2
    if cap is not None:
        while b > cap:
            b //= 2
    return max(1, b)


def plan_prefill_chunks(
    t: int, budget: int, cap: int | None = None
) -> list[tuple[int, int]]:
    """Split a t-token prefill into [start, end) chunk spans of
    `prefill_chunk_len(budget, cap)` tokens each. budget<=0 or t<=budget ->
    one whole-prompt span, i.e. chunking disabled."""
    if budget <= 0 or t <= budget:
        return [(0, t)]
    b = prefill_chunk_len(budget, cap)
    if t <= b:
        return [(0, t)]
    return [(s, min(s + b, t)) for s in range(0, t, b)]


def chunk_reply_rows(reply_rows: int | None, s: int, e: int,
                     tokens: int) -> int | None:
    """How many of a chunk's rows [s, e) of a `tokens`-row step are among
    the step's last `reply_rows` (None: every row is read)."""
    if reply_rows is None:
        return None
    return max(0, e - max(s, tokens - reply_rows))


@functools.partial(jax.jit, donate_argnames=("arena_k", "arena_v"))
def _arena_write_all(arena_k, arena_v, slots, k_new, v_new):
    """Scatter every layer's new KV rows [L, N, Hkv, hd] into the donated
    arena (the sp-prefill landing step; quantized slabs quantize inside
    arena_write): ONE scatter into the flat arena, layer l's rows at its
    offset slots — the same addressing as a span step's layer."""
    from bloombee_tpu.kv.arena import (
        arena_tokens,
        arena_write,
        flat_arena,
        layer_slots,
        stacked_arena,
    )

    num_layers = arena_k.shape[0]
    s_tot = arena_tokens(arena_k, k_new.shape[2])
    all_slots = layer_slots(
        slots[None, :], jnp.arange(num_layers, dtype=slots.dtype)[:, None],
        s_tot, num_layers,
    ).reshape(-1)
    new_k, new_v = arena_write(
        flat_arena(arena_k), flat_arena(arena_v), all_slots,
        k_new.reshape(-1, *k_new.shape[2:]),
        v_new.reshape(-1, *v_new.shape[2:]),
    )
    return stacked_arena(new_k, num_layers), stacked_arena(new_v, num_layers)


class SpanExecutor:
    def __init__(
        self,
        stacked_params: dict,
        spec: ModelSpec,
        manager: CacheManager,
        max_chunk_tokens: int = 512,
        compute_dtype=jnp.bfloat16,
        start_block: int = 0,
        mesh=None,  # jax.sharding.Mesh with a "tp" axis: TP-sharded serving
        adapters: dict[str, dict] | None = None,  # name -> stacked factors
        host_layers: list | None = None,  # weight-offload: per-layer host
        # param pytrees for the span's LAST len(host_layers) layers; they
        # stream to the device per step with one-ahead prefetch (reference
        # FlexGen Policy weight percentages / convert_block.py
        # PipelineParallelWrapper pre-forward H2D)
        attn_sparsity: float = 1.0,  # <1: keep only the top
        # attn_sparsity*(S-1) past keys per query plus the newest token
        # (reference FlexGen Policy.attn_sparsity,
        # pytorch_backend.py:564-638); approximate — dense path only
        sp_mesh=None,  # (tp=1, sp) mesh: long prefills (>= SP_MIN_TOKENS)
        # spread over the sp chips via ring attention, K/V landing in the
        # paged arena; decode stays single-chip (parallel/sp_serving.py)
    ):
        if not 0.0 < attn_sparsity <= 1.0:
            raise ValueError(f"attn_sparsity in (0, 1], got {attn_sparsity}")
        self.attn_sparsity = float(attn_sparsity)
        if spec.recurrent is not None:
            # one recurrent-state slot a sequence, read and written by the
            # scanned span step only: the paths below address K/V pages
            # layer by layer or chip by chip and have no way to carry it
            for on, what in (
                (mesh is not None, "--tp (tensor-parallel serving)"),
                (sp_mesh is not None, "--sp (sequence-parallel prefill)"),
                (bool(host_layers), "weight offload"),
                (spec.heterogeneous, "heterogeneous spans"),
            ):
                if on:
                    raise ValueError(
                        f"{what} unsupported for {spec.family}: the "
                        "recurrent state beside the KV arena is carried by "
                        "the single-chip span step only"
                    )
            if manager.state is None:
                raise ValueError(
                    f"{spec.family} needs a CacheManager with state slots "
                    "(ssm=spec.recurrent, state_slots=...)"
                )
        reason = spec.span_unsupported(
            start_block, start_block + manager.num_layers
        )
        if reason is not None:
            raise ValueError(reason)
        if spec.kinds_interleave or spec.mamba is not None:
            want = spec.arena_layers(
                start_block, start_block + manager.num_layers
            )
            if (manager.kv_layers, manager.state_layers) != want:
                raise ValueError(
                    f"{spec.family} needs a CacheManager whose K/V arena has "
                    f"a row a full layer (a layer that WRITES keys and "
                    f"values) and whose state arena a row a linear one (a "
                    f"layer with recurrent state) (arena_layers={want}), "
                    f"got {(manager.kv_layers, manager.state_layers)}"
                )
        if (spec.mla is None and manager.quant is None
                and not spec.heterogeneous):
            # the arena's layout is the rule's (kv/arena.py `folds`): the
            # step programs address what the shape says, nothing else
            from bloombee_tpu.kv.arena import folds

            want = mesh is None and folds(
                spec.num_key_value_heads, spec.head_dim,
                manager.arena["k"].dtype,
            )
            if manager.folded != want:
                raise ValueError(
                    f"{spec.family}: the K/V arena is stored "
                    f"{'folded' if manager.folded else 'unfolded'} where "
                    f"{spec.num_key_value_heads} KV heads x {spec.head_dim}"
                    f"{' under --tp' if mesh is not None else ''} take the "
                    "other layout (kv/arena.py `folds`; CacheManager("
                    "sharded=True) for a mesh)"
                )
        if spec.mamba is not None:
            for on, what in (
                (bool(adapters), "LoRA adapters"),
                (attn_sparsity < 1.0, "--attn-sparsity"),
            ):
                if on:
                    raise ValueError(
                        f"{what} unsupported for {spec.family}: its layers "
                        "run the SambaY span step only (runtime/sambay.py)"
                    )
        if spec.one_sublayer and adapters:
            raise ValueError(
                f"LoRA adapters unsupported for {spec.family}: its mixer "
                "and expert layers have no projection an adapter names"
            )
        if spec.gdn is not None:
            if adapters:
                raise ValueError(
                    f"LoRA adapters unsupported for {spec.family}: the "
                    "full layers' q_proj is stored split (query and gate "
                    "rows, or a latent query's nope and rope rows) and the "
                    "linear layers have no projection an adapter names"
                )
        if spec.mla is not None:
            # the latent page is attended by the single-chip span step's own
            # paths (runtime/layer_body.py `_mla_attention`); nothing shards
            # a latent over heads or streams one layer's weights at a time
            for on, what in (
                (mesh is not None, "--tp (tensor-parallel serving)"),
                (sp_mesh is not None, "--sp (sequence-parallel prefill)"),
                (bool(host_layers), "weight offload"),
                (manager.quant is not None, "--kv-quant"),
                (attn_sparsity < 1.0, "--attn-sparsity"),
            ):
                if on:
                    raise ValueError(
                        f"{what} unsupported for {spec.family}: the cache "
                        "holds one latent and one rotary key a token, "
                        "attended by the single-chip span step only"
                    )
        if spec.moe_router == "sigmoid":
            # afmoe: the sharded expert path (parallel/spmd.py) routes
            # through the softmax forms only, ring attention has no window
            # and no layer without positions, and a span of a dense layer
            # before sparse ones is two stacks, which the layer-by-layer
            # offload chain does not walk
            for on, what in (
                (mesh is not None, "--tp (tensor-parallel serving)"),
                (sp_mesh is not None, "--sp (sequence-parallel prefill)"),
                (bool(host_layers), "weight offload"),
            ):
                if on:
                    raise ValueError(
                        f"{what} unsupported for {spec.family}: its sigmoid "
                        "router with a bias-corrected choice and its window "
                        "layers with positions among full layers without "
                        "run in the single-chip span step only"
                    )
        self.mesh = mesh
        self.sp_mesh = sp_mesh
        self._sp_params = None
        if sp_mesh is not None:
            if mesh is not None:
                raise ValueError(
                    "sp prefill + TP serving not supported together yet"
                )
            if host_layers:
                raise ValueError(
                    "sp prefill + weight offload not supported together"
                )
            if spec.heterogeneous:
                raise ValueError(
                    "sp prefill + heterogeneous head_dim spans not "
                    "supported together"
                )
            if manager.quant is not None:
                # _sp_eligible would silently never fire (quantized arenas
                # attend quantized KV during single-chip prefill; ring
                # attends full precision) while the replicated param copy
                # still costs every sp chip — fail at startup instead
                raise ValueError(
                    "sp prefill + quantized KV arena not supported "
                    "together (single-chip prefill attends quantized KV; "
                    "ring attention would change the numerics)"
                )
            from bloombee_tpu.parallel.sp_serving import (
                place_sp_params,
                sp_unsupported,
            )

            reason = sp_unsupported(spec, stacked_params)
            if reason is not None:
                raise ValueError(f"sp prefill unavailable: {reason}")
            # a replicated copy over the sp chips (the single-chip decode
            # path keeps its own placement; span params are a small price
            # next to the long-context KV this feature exists to serve)
            self._sp_params = place_sp_params(stacked_params, sp_mesh)
        self.host_layers = list(host_layers or [])
        self.resident = manager.num_layers - len(self.host_layers)
        if self.host_layers:
            if spec.heterogeneous:
                raise ValueError(
                    "weight offload + heterogeneous head_dim spans not "
                    "supported together"
                )
            if manager.quant is not None:
                raise ValueError(
                    "weight offload + quantized KV arena not supported "
                    "together"
                )
            if self.resident < 0:
                raise ValueError(
                    f"{len(self.host_layers)} host layers > "
                    f"{manager.num_layers} span layers"
                )
            lead = jax.tree.leaves(stacked_params)[0].shape[0] if (
                self.resident > 0
            ) else 0
            if self.resident and lead != self.resident:
                raise ValueError(
                    f"resident params stack has {lead} layers, expected "
                    f"{self.resident}"
                )
        if mesh is not None:
            from bloombee_tpu.parallel import serving as tp_serving

            if spec.heterogeneous:
                # per-layer geometry: q heads/experts must divide; layers
                # whose KV heads don't divide replicate their K/V
                tp_serving.check_tp_divides(
                    spec, mesh.devices.size, hetero=True
                )
                stacked_params = tp_serving.place_hetero_span_params(
                    stacked_params, mesh, spec, start_block
                )
            else:
                tp_serving.check_tp_divides(spec, mesh.devices.size)
                if stacked_params is not None:  # fully-offloaded: no prefix
                    stacked_params = tp_serving.place_span_params(
                        stacked_params, mesh
                    )
            manager.arena = tp_serving.place_arena_for(
                spec, manager.arena, mesh
            )
            if adapters:
                # low-rank factors are small: replicate over the mesh and let
                # GSPMD partition the delta einsums as it sees fit
                adapters = {
                    name: tp_serving.replicated(f, mesh)
                    for name, f in adapters.items()
                }
        self.adapters = adapters or {}
        self.params = stacked_params
        self.spec = spec
        self.manager = manager
        # per-layer sliding windows (gemma-style alternating layers); layer
        # types are indexed by ABSOLUTE block id, so the span offset matters
        self.windows = tuple(
            spec.window_for_layer(start_block + i)
            for i in range(manager.num_layers)
        )
        self.max_chunk_tokens = max_chunk_tokens
        self.compute_dtype = compute_dtype
        self.start_block = start_block
        # ship hidden states over the host link at half width when computing
        # in bf16 (transfer latency/bandwidth is the bottleneck; SURVEY.md
        # section 3.3 timing decomposition)
        self.transfer_dtype = np.dtype(
            ml_dtypes.bfloat16 if compute_dtype == jnp.bfloat16 else np.float32
        )
        self.page_size = manager.page_size
        # which attention path each device dispatch took, and how often a
        # failing Pallas kernel gave way to the dense path (each one is a
        # bug to report: rpc_info / health --probe surface both)
        self.attn_dispatches = {"flash": 0, "paged": 0, "ragged": 0,
                                "dense": 0}
        self.kernel_fallbacks = 0
        self._paged_broken = False
        # bucket tags of the fused ragged programs this executor has run
        # (`ragged_bucket`): a server that is past its warm-up fuses only
        # into these
        self.ragged_buckets_run: set[str] = set()
        # a family with experts: device dispatches by the form its experts
        # took (ops/moe.py), counted from the bucket's rows
        self.moe_dispatches = {"grouped": 0, "tiled": 0, "dense": 0}
        # the tile the last chunk's flash kernel multiplied, by layer kind
        # (`_flash_form`, made once a (rows, page bucket)); None until a
        # chunk has attended through it. The windows are those of the
        # span's layers that attend (a linear, Mamba or GMU layer does not),
        # each with the number of such layers
        self.flash_form: str | None = None
        self._flash_forms: dict[tuple[int, int], str | None] = {}
        self._attn_windows = collections.Counter(
            w for i, w in enumerate(self.windows)
            if spec.layer_type(start_block + i) in ("full", "sliding", "cross")
        )
        # a server that holds a share of the experts (spec.moe_held): what
        # each step's rows reached of them per sparse layer (ops/moe.py
        # `held_reach`), handed out of the step program as a device array
        # and read at the next `fetch`, off the compute thread
        self._reach_pending = collections.deque(maxlen=256)
        self._reach_lock = threading.Lock()
        self.moe_reach = {
            "steps": 0, "rows": 0, "routed_pairs_here": 0,
            "rows_with_held_expert": 0, "held_hit_last": [],
        }
        # a SambaY span that holds the cross-decoder (spec.mamba): the rows
        # its steps ran through the self-decoder and through the
        # cross-decoder, the steps that ended after the shared layer (short)
        # and those that went on (long), and the cross layers' reads of the
        # ONE shared K/V row (cross layers x long steps)
        self.sambay = {
            "self_rows": 0, "cross_rows": 0, "short_steps": 0,
            "long_steps": 0, "shared_kv_reads": 0,
        }
        # every family with window layers: summed over the steps' sequences
        # at their context before the step, the K/V tokens the arena held
        # for them, and those in window layers' pages that no later query
        # can see (what a page table a layer kind would free)
        self.kv_held = {"kv_held_tokens": 0, "window_dead_tokens": 0}
        # dispatches that held a sequence of more than one row, by how its
        # K/V rows went into the arena: one index a page (`_page_groups`)
        # or one a row
        self.kv_writes = {"chunk_page_writes": 0, "chunk_row_writes": 0}
        # decode dispatches whose rows attend through the paged decode
        # kernel: the turns (grid steps) its calls walked over the span's
        # attending layers, and those of them that held a live page
        # (ops/pallas/paged_attention.py `walk_bounds`, the wrapper's own
        # rule on the step's padded lengths)
        self.kv_walk = {"turns": 0, "live_turns": 0}
        # whether both slabs' page view is free (kv/arena.py
        # `page_view_free`, by their shape; an int4 arena, one sharded over
        # a mesh and a heterogeneous span's per-layer slabs keep the row
        # scatter)
        self._page_view_free = bool(
            manager.quant is None
            and mesh is None
            and not spec.heterogeneous
            # an attention layer in a run of ONE repeat (a period of a
            # one-sublayer family that stands alone in its span): the
            # compiler unrolls that scan, and the page write's read of the
            # old page then costs a copy of the whole arena a slab (88 MB
            # four times a chunk: my chip run, PR 54); such a span keeps the
            # row scatter
            and not (spec.one_sublayer and any(
                n == 1 and "full" in kinds for kinds, n in spec.period_runs(
                    start_block, start_block + manager.num_layers)))
            and all(
                page_view_free(manager.arena[key].shape[1:],
                               manager.arena[key].dtype)
                for key in ("k", "v")
            )
        )
        self._window_layers = sum(w > 0 for w in self.windows)
        # layers that are one sublayer each: how many of each kind the span
        # holds, as the `bbtpu.step` span says it ("mamba:6+moe:6+full:2":
        # neither the comma nor the ";" the profiler cuts an id at)
        self._kinds = "+".join(
            f"{kind}:{n}" for kind, n in collections.Counter(
                spec.layer_type(start_block + i)
                for i in range(manager.num_layers)
            ).items()
        ) if spec.one_sublayer else ""
        # a chunk may attend through flash where no layer has a window, or
        # every window is the family's `flash_window` (window layers among
        # full ones: runtime/layer_body.py `_flash_by_window`)
        self._flash_windows_ok = all(
            w in (0, spec.flash_window) for w in self.windows
        )
        self._cross_layers = 0
        if spec.mamba is not None:
            self._cross_layers = sum(
                spec.layer_type(start_block + i) == "cross"
                for i in range(manager.num_layers)
            )

    # ------------------------------------------------------------------ steps
    def prefill(
        self,
        handle: CacheHandle,
        hidden: np.ndarray,
        commit: bool = True,
        layers: tuple[int, int] | None = None,
        fetch: bool = True,
        adapter: str | None = None,
        reply_rows: int | None = None,
    ):
        """Run full-sequence prefill, chunked to bound attention logits memory
        (reference: backend.py:525-531 chunked inference).

        With fetch=False the (lazy) device array is returned instead of a
        host copy — callers fetch it OUTSIDE the serialized compute queue so
        concurrent sessions' d2h round trips overlap (one session's step
        cannot hide its own dependent round trip; another session's can).
        """
        outs = []
        t = hidden.shape[1]
        if self._sp_eligible(handle, t, commit, layers, adapter):
            return self._sp_prefill(handle, hidden, fetch)
        for start in range(0, t, self.max_chunk_tokens):
            chunk = hidden[:, start : start + self.max_chunk_tokens]
            outs.append(
                self._step(
                    handle, chunk, commit=commit, layers=layers, fetch=fetch,
                    adapter=adapter,
                    reply_rows=chunk_reply_rows(
                        reply_rows, start, start + chunk.shape[1], t
                    ),
                )
            )
        if len(outs) == 1:
            return outs[0]
        cat = np.concatenate if fetch else jnp.concatenate
        return cat(outs, axis=1)

    def prefill_chunk(
        self,
        handle: CacheHandle,
        hidden: np.ndarray,
        commit: bool = False,
        layers: tuple[int, int] | None = None,
        fetch: bool = False,
        adapter: str | None = None,
        reply_rows: int | None = None,  # as `_step`'s
    ):
        """Run ONE chunk of a resumable chunked prefill (Sarathi-Serve
        stall-free batching): the caller slices the prompt with
        `plan_prefill_chunks` and submits each chunk as its OWN compute
        task, letting decode steps interleave between chunks.

        The position offset carries automatically: `_step` reads the
        handle's current context length (which includes earlier chunks'
        speculative tokens) as the rotary/write start. Chunks should run
        with commit=False — speculative writes let a mid-prefill abort
        free every partial page via `manager.rollback`; the caller commits
        the handle once after the final chunk, exactly like the batched
        decode path."""
        if hidden.shape[1] > self.max_chunk_tokens:
            # one queue task must stay one device dispatch — feeding a
            # chunk bigger than the attention-memory bound would silently
            # re-monolith the schedule
            raise ValueError(
                f"prefill chunk of {hidden.shape[1]} tokens exceeds "
                f"max_chunk_tokens={self.max_chunk_tokens}"
            )
        return self._step(
            handle, hidden, commit=commit, layers=layers, fetch=fetch,
            adapter=adapter, reply_rows=reply_rows,
        )

    def prefill_chunked(
        self,
        handle: CacheHandle,
        hidden: np.ndarray,
        chunk_tokens: int,
        commit: bool = True,
        layers: tuple[int, int] | None = None,
        fetch: bool = True,
        adapter: str | None = None,
        reply_rows: int | None = None,
    ):
        """Whole-prompt prefill via the chunked path, all chunks in ONE
        call (no queue re-entry — warmup and tests; the server drives
        chunks through the compute queue itself). Token-identical to
        `prefill`: same program, same buckets, positions carried across
        chunks; speculative writes committed after the last chunk."""
        spans = plan_prefill_chunks(
            hidden.shape[1], chunk_tokens, cap=self.max_chunk_tokens
        )
        outs = []
        try:
            for s, e in spans:
                outs.append(
                    self.prefill_chunk(
                        handle, hidden[:, s:e], commit=False, layers=layers,
                        fetch=fetch, adapter=adapter,
                        reply_rows=chunk_reply_rows(
                            reply_rows, s, e, hidden.shape[1]
                        ),
                    )
                )
        except Exception:
            if self.manager.epoch_valid(handle):
                self.manager.rollback(handle)
            raise
        if commit:
            self.manager.commit(handle)
        if len(outs) == 1:
            return outs[0]
        cat = np.concatenate if fetch else jnp.concatenate
        return cat(outs, axis=1)

    def _sp_eligible(self, handle, t, commit, layers, adapter) -> bool:
        """Sequence-parallel prefill fires for a FRESH full-span committed
        prefill of a long prompt (starts all zero); everything else takes
        the single-chip chunked path."""
        return bool(
            self.sp_mesh is not None
            and commit
            and layers is None
            and adapter is None
            # (quantized arenas are rejected at __init__ — sp_mesh and
            # manager.quant can never coexist here)
            and t >= env.get("BBTPU_SP_MIN_TOKENS")
            # is_fresh, NOT a bare length check: a host-parked session's
            # table length reads 0 while its real KV sits in the park —
            # sp-prefilling it from position 0 would orphan that KV and
            # blow the unpark invariant on the next decode
            and self.manager.is_fresh(handle)
        )

    def _sp_prefill(self, handle, hidden: np.ndarray, fetch: bool):
        """Whole-prompt prefill over the sp mesh (ring attention), K/V
        scattered into the paged arena so decode continues single-chip
        (parallel/sp_serving.py)."""
        from bloombee_tpu.parallel.sp_serving import sp_prefill

        b, t, d = hidden.shape
        sp = self.sp_mesh.devices.shape[1]
        # pow2 bucket FIRST (compile count stays O(log T), same contract
        # as the single-chip path), then round up to a multiple of sp for
        # the ring chunks
        t_pad = next_pow2(t)
        t_pad = -(-t_pad // sp) * sp
        h_pad = np.zeros((b, t_pad, d), dtype=self.transfer_dtype)
        h_pad[:, :t] = hidden.astype(self.transfer_dtype)
        slots = self.manager.write_slots(handle, t, commit=True)  # [b*t]
        with jitwatch.region("sp_prefill", f"b{b},t{t_pad}"):
            out, ks, vs = sp_prefill(
                self._sp_params, h_pad, self.sp_mesh, spec=self.spec
            )
        # pad tokens write to the drop slot; real tokens land in their
        # assigned pages
        oob = self.manager.capacity_tokens
        slots_pad = np.full((b, t_pad), oob, np.int32)
        slots_pad[:, :t] = slots.reshape(b, t)
        dev0 = jax.devices()[0]
        l = self.manager.num_layers
        hkv = ks.shape[3]
        hd = ks.shape[4]
        k_new = jax.device_put(
            ks.reshape(l, b * t_pad, hkv, hd), dev0
        )
        v_new = jax.device_put(
            vs.reshape(l, b * t_pad, hkv, hd), dev0
        )
        arena = self.manager.arena

        def _run(_use_kernel: bool):
            with jitwatch.region("arena_write_all", f"b{b},t{t_pad}"):
                return _arena_write_all(
                    arena["k"], arena["v"],
                    jnp.asarray(slots_pad.reshape(-1)), k_new, v_new,
                )

        (new_k, new_v), _ = self._dispatch(_run, False, arena, "sp prefill")
        self.manager.arena = {"k": new_k, "v": new_v}
        out = out[:, :t]
        if not fetch:
            return out
        return self.fetch(out)

    def decode(
        self,
        handle: CacheHandle,
        hidden: np.ndarray,
        commit: bool = True,
        tree_mask: np.ndarray | None = None,
        layers: tuple[int, int] | None = None,
        depths: np.ndarray | None = None,
        fetch: bool = True,
        adapter: str | None = None,
    ):
        return self._step(
            handle, hidden, commit=commit, tree_mask=tree_mask, layers=layers,
            depths=depths, fetch=fetch, adapter=adapter,
        )

    def decode_group(
        self,
        handles: list[CacheHandle],
        hiddens: list[np.ndarray],  # per-member [b_i, 1, D], same dtype
        layers: tuple[int, int] | None = None,
        adapter: str | None = None,
    ):
        """Row-stack several sessions' single-token decode steps into ONE
        span dispatch (Orca-style continuous batching over the paged
        arena: each row's attention reads only its own pages, so the
        merged step is numerically identical to the members run alone).
        The total row count shares `_step`'s pow2 batch bucketing, so the
        merged widths hit the same compile cache as big single-session
        batches.

        KV writes are SPECULATIVE (commit=False): the caller commits the
        combined handle only after the dispatch succeeds, so a failed
        batch rolls back cleanly and can replay row-by-row without ghost
        tokens in any member's page table.

        Returns (out, combined_handle): `out` is the lazy [sum(b_i), 1, D]
        device result (slice rows per member, fetch off-queue), and the
        combined handle is what the caller commits or rolls back.

        Thin delegation onto `ragged_group`, whose pure-decode fast path
        runs exactly this packed dispatch; the [R, D] -> [R, 1, D] reshape
        back to the historical contract is a lazy view."""
        out, combined = self.ragged_group(
            handles, hiddens, layers=layers, adapter=adapter
        )
        with jitwatch.span("bbtpu.slice"):
            return out[:, None, :], combined

    def ragged_unsupported(self, has_tree: bool = False) -> str | None:
        """Why this executor can't run the universal ragged dispatch; None
        when it can. These configs have their own step machinery (offload
        layer chain, hetero span, decode-only top-k) that the ragged path
        doesn't replicate — the server falls back to separate dispatches,
        byte-for-byte the flags-off behavior. TP-mesh spans are SUPPORTED:
        the payload replicates over the mesh and GSPMD shards the dense
        attend_ragged over heads, exactly like the packed step (the Pallas
        ragged kernel stays single-chip-only via the use_kernel gate).
        Tree rows additionally exclude sliding-window layers: the ragged
        tree mask replaces causality outright, and window clipping against
        depth-positioned tree tokens only exists on the solo dense path."""
        if self.host_layers:
            return "weight offload"
        if self.spec.heterogeneous:
            return "heterogeneous span"
        if self.attn_sparsity < 1.0:
            return "sparse (top-k) attention"
        if has_tree and any(w > 0 for w in self.windows):
            return "sliding-window layers"
        if has_tree and self.spec.recurrent is not None:
            return "recurrent state (tree rows would branch it)"
        if has_tree and self.spec.mla is not None:
            return "latent attention (no tree mask in its kernels)"
        return None

    @property
    def one_chunk_a_pack(self) -> bool:
        """A ragged pack may hold ONE sequence of more than one row: the
        state-space mixer and latent attention run their chunk form on one
        (runtime/layer_body.py)."""
        return self.spec.recurrent is not None or self.spec.mla is not None

    def _ragged_bucket(self, counts: list[int], total_lens, t_max: int):
        """(rb, sb, pb, tag) of the fused ragged program for sequences of
        `counts` new rows each that reach `total_lens` cached tokens."""
        rb = next_pow2(sum(counts))
        sb = next_pow2(len(counts))
        pages_needed = int(
            max(-(-int(n) // self.page_size) for n in total_lens)
        )
        pb = min(
            next_pow2(max(pages_needed, 1), floor=4),
            self.manager.capacity_tokens // self.page_size,
        )
        tag = f"r{rb},s{sb},p{pb}" + (f",t{t_max}" if t_max else "")
        return rb, sb, pb, tag

    def ragged_bucket(
        self, handles: list[CacheHandle], hiddens: list[np.ndarray],
        tree_masks: list | None = None,
    ) -> str:
        """The bucket tag under which `ragged_group` would run this mix as
        ONE fused program (rows, sequences, pages and, with tree rows, the
        tree width), read off the table without writing to it. Whether the
        program exists already: `tag in self.ragged_buckets_run`."""
        counts, total_lens = [], []
        for handle, hid in zip(handles, hiddens):
            t_i = int(hid.shape[1])
            counts.extend([t_i] * int(hid.shape[0]))
            total_lens.extend(
                int(n) + t_i for n in self.manager.context_lens(handle)
            )
        has_tree = any(tm is not None for tm in tree_masks or ())
        t_max = next_pow2(max(counts)) if has_tree else 0
        return self._ragged_bucket(counts, total_lens, t_max)[3]

    def ragged_group(
        self,
        handles: list[CacheHandle],
        hiddens: list[np.ndarray],  # per-member [b_i, t_i, D], same dtype
        tree_masks: list | None = None,  # per-member [b_i, t_i, t_i] bool
        # or None for causal members (decode rows / the prefill chunk)
        depths_list: list | None = None,  # per-member [b_i, t_i] i32, None
        # for causal members (positions run sequentially from the start)
        layers: tuple[int, int] | None = None,
        adapter: str | None = None,
        reply_rows: list | None = None,  # per member, as `_step`'s: the
        # last n rows of each of its sequences are read (None: every row)
    ):
        """THE universal ragged dispatch: N sessions' rows — single-token
        decodes, linearized tree-verify rows, at most one multi-token
        prefill chunk — pack row-major into ONE pow2 bucket [1, R, D] and
        run as ONE jitted span dispatch over an ephemeral combined handle.
        Per-token (q_seq, q_pos) carry the member structure into the
        ragged kernel (dense attend_ragged for kernel-ineligible configs
        and TP-mesh spans, where GSPMD shards the rows' heads over the
        mesh). Members are CAUSAL by default; a member whose entry in
        `tree_masks`/`depths_list` is non-None contributes TREE rows.
        When any tree member is present the whole dispatch takes the
        tree-mask variant, and causal members ride along as
        lower-triangular rows at sequential depths — exactly causality, so
        the fused step stays token-identical to the members run alone.

        KV writes are SPECULATIVE for every member; commit/rollback stays
        per-member with the CALLER as recovery owner (decodes
        commit/rollback, the chunk commits on its last chunk /
        truncate_speculative's on failure, tree members truncate and
        replay solo — block_server._dispatch_ragged).

        Returns (out, combined_handle): `out` is the lazy [R, D] device
        result in member-major token order (slice b_i * t_i row blocks
        per member, fetch off-queue)."""
        n_members = len(handles)
        if tree_masks is None:
            tree_masks = [None] * n_members
        if depths_list is None:
            depths_list = [None] * n_members
        has_tree = any(tm is not None for tm in tree_masks)
        if (
            not has_tree
            and all(int(hid.shape[1]) == 1 for hid in hiddens)
        ):
            # pure single-token decodes: the legacy packed path IS this
            # dispatch (same [B, 1, D] bucket family as big single-session
            # batches, byte-for-byte PR-2 continuous batching — including
            # on offloaded/hetero/sparse spans the ragged packing gates
            # off). [B, 1, D] -> [R, D] is a lazy view, not a copy.
            with jitwatch.span("bbtpu.pack"):
                combined = self.manager.combine_handles(handles)
                hidden = np.concatenate(hiddens, axis=0)
            # recovery owner: the caller commits/rolls back the combined
            # handle around this dispatch
            out = self._step(  # bbtpu: noqa[BB001]
                combined, hidden, commit=False, layers=layers, fetch=False,
                adapter=adapter,
            )
            with jitwatch.span("bbtpu.slice"):
                return out.reshape(out.shape[0], out.shape[2]), combined
        reason = self.ragged_unsupported(has_tree=has_tree)
        if reason is not None:
            raise ValueError(f"ragged_group unsupported: {reason}")
        spec = self.spec
        from bloombee_tpu.models.checkpoint import resolve_adapter

        lora = resolve_adapter(self.adapters, adapter)
        with jitwatch.span("bbtpu.pack"):
            combined = self.manager.combine_handles(handles)
            self.manager.ensure_resident(combined)

            d = spec.hidden_size
            counts: list[int] = []
            row_blocks = []
            for hid in hiddens:
                b_i, t_i, d_i = hid.shape
                assert d_i == d
                counts.extend([t_i] * b_i)
                row_blocks.append(hid.reshape(b_i * t_i, d))
            n_seqs = len(counts)
            r = sum(counts)
            # a family with recurrent state runs the chunk form on ONE
            # sequence a pack (the server sends a chunk of several
            # sequences on its own; a pack of single rows went the packed
            # way above, and tree rows are refused)
            multi = [i for i, c in enumerate(counts) if c > 1]
            if self.one_chunk_a_pack and len(multi) != 1:
                raise ValueError(
                    "ragged_group unsupported: recurrent state or a latent "
                    "cache (a pack takes ONE sequence of more than one row)"
                )
            # the tree-mask variant keeps every row's in-step width static:
            # causal members' rows become lower-triangular tree rows, so one
            # t_max bucket covers the whole mix
            t_max = next_pow2(max(counts)) if has_tree else 0

            starts = self.manager.context_lens(combined)  # [B] before write
            # recovery owner: block_server._dispatch_ragged rolls decodes
            # back, truncates the chunk and every tree member to their
            # pre-dispatch lengths if this dispatch fails
            slots = self.manager.write_slots_ragged(  # bbtpu: noqa[BB001]
                combined, counts, commit=False
            )  # [R]
            total_lens = self.manager.context_lens(combined)  # [B] after write

            rb, sb, pb, tag = self._ragged_bucket(counts, total_lens, t_max)
            oob = self.manager.capacity_tokens  # out-of-bounds slot =>
            # dropped write

            h_pad = np.zeros((1, rb, d), dtype=self.transfer_dtype)
            h_pad[0, :r] = np.concatenate(row_blocks, axis=0).astype(
                self.transfer_dtype
            )
            slots_pad = np.full((rb,), oob, dtype=np.int32)
            slots_pad[:r] = slots
            positions = np.zeros((1, rb), dtype=np.int32)
            # padding rows own no sequence (q_seq >= B): fully masked in the
            # kernel, sliced away with the pad rows
            q_seq = np.full((rb,), sb, dtype=np.int32)
            if has_tree:
                nt = np.zeros((sb,), dtype=np.int32)
                tree_rows = np.zeros((rb, t_max), dtype=np.int32)
            off = 0
            s_i = 0
            for m_i, hid in enumerate(hiddens):
                b_i, t_i, _ = hid.shape
                tm = tree_masks[m_i]
                dep = depths_list[m_i]
                if tm is not None:
                    tm = np.asarray(tm, dtype=bool)
                    dep = np.asarray(dep, dtype=np.int32)
                for row in range(b_i):
                    if tm is not None:
                        positions[0, off : off + t_i] = starts[s_i] + dep[row]
                    else:
                        positions[0, off : off + t_i] = starts[s_i] + np.arange(
                            t_i, dtype=np.int32
                        )
                    q_seq[off : off + t_i] = s_i
                    if has_tree:
                        nt[s_i] = t_i
                        if tm is not None:
                            tree_rows[off : off + t_i, :t_i] = tm[row]
                        else:
                            # causal rows under the tree mask: token j sees
                            # in-step tokens 0..j at sequential depths — the
                            # lower triangle is exactly causal attention
                            tree_rows[off : off + t_i, :t_i] = np.tril(
                                np.ones((t_i, t_i), dtype=np.int32)
                            )
                    off += t_i
                    s_i += 1
            pt_pad = np.zeros((sb, pb), dtype=np.int32)
            pt_pad[:n_seqs] = self.manager.page_table(combined, pb)
            lens_pad = np.zeros((sb,), dtype=np.int32)
            lens_pad[:n_seqs] = total_lens
            num_layers = self.manager.num_layers
            layer_active = np.ones((num_layers,), dtype=np.int32)
            if layers is not None:
                layer_active[:] = 0
                layer_active[layers[0] : layers[1]] = 1
            if has_tree:
                plan = pack_ragged_plan(
                    slots_pad, pt_pad, positions, lens_pad, q_seq, layer_active,
                    nt=nt, tree_rows=tree_rows,
                )
            else:
                plan = pack_ragged_plan(
                    slots_pad, pt_pad, positions, lens_pad, q_seq, layer_active
                )
            step_kwargs = {"t_max": t_max} if has_tree else {}
            if self.one_chunk_a_pack:
                row0 = np.zeros((sb,), np.int32)
                row0[:n_seqs] = np.cumsum([0] + counts[:-1])
                counts_pad = np.zeros((sb,), np.int32)
                counts_pad[:n_seqs] = counts
                plan = np.concatenate([plan, pack_ragged_ssm_tail(
                    self._state_slots_padded(combined, sb), row0,
                    counts_pad, multi[0],
                )])
            cross_idx = None
            if spec.mamba is not None:
                self._whole_span_only(layers)
                cross_idx, seq_i = [], 0
                for m_i, hid in enumerate(hiddens):
                    b_i, t_i, _ = hid.shape
                    want = None if reply_rows is None else reply_rows[m_i]
                    n_i = t_i if want is None else min(int(want), t_i)
                    for _ in range(b_i):
                        end = int(row0[seq_i]) + t_i
                        cross_idx.extend(range(end - n_i, end))
                        seq_i += 1
                plan = np.concatenate([plan, pack_cross_tail(cross_idx, rb)])

            # ragged-kernel eligibility: what every paged kernel needs
            # (_paged_kernel_ok), a dense arena, the [R*H, hd] VMEM budget,
            # single-chip (Pallas kernels don't GSPMD-partition — TP-mesh
            # spans run the dense attend_ragged path). Ineligible configs run
            # attend_ragged — still ONE dispatch.
            use_kernel = bool(
                self._paged_kernel_ok(pb * self.page_size)
                and self.mesh is None
                and self.manager.quant is None
                # latent attention's kernels block their queries
                # themselves; a family with linear layers attends a pack
                # sequence by sequence (layer_body.py `_attend_by_rows`)
                and (spec.mla is not None or spec.kinds_interleave
                     or spec.mamba is not None
                     or rb * spec.num_attention_heads <= 2048)
            )

            payload = pack_step_payload(h_pad, plan)
        with jitwatch.span("bbtpu.h2d"):
            if self.mesh is not None:
                # commit the h2d payload replicated over the tp mesh; the
                # sharded params/arena make GSPMD split the per-head work
                from bloombee_tpu.parallel import serving as tp_serving

                payload_dev = tp_serving.replicated(payload, self.mesh)
            else:
                payload_dev = jnp.asarray(payload)
        arena = self._arena()

        def _run(use_kernel_now: bool):
            with jitwatch.region("span_step_ragged", tag):
                return span_step_ragged(
                    self.params,
                    arena["k"],
                    arena["v"],
                    payload_dev,
                    lora,
                    arena.get("state"),
                    spec=spec,
                    r=rb,
                    n_seqs=sb,
                    page_size=self.page_size,
                    max_pages=pb,
                    windows=self.windows,
                    use_kernel=use_kernel_now,
                    **step_kwargs,
                )

        result, used_kernel = self._dispatch(
            _run, use_kernel, arena, "ragged group step"
        )
        with jitwatch.span("bbtpu.counters"):
            self.attn_dispatches["ragged" if used_kernel else "dense"] += 1
            self.ragged_buckets_run.add(tag)
            # where a pack's one chunk attends through the flash kernel, it
            # is rb rows wide there (runtime/step.py `pack_chunk_on_flash`)
            flash_now = used_kernel and pack_chunk_on_flash(spec)
            self.kv_writes["chunk_row_writes"] += 1
            out = self._keep_arena(
                result, "fused", r, starts, self._count_moe(rb, used_kernel),
                cross_rows=None if cross_idx is None else len(cross_idx),
                flash=self._flash_form(rb, pb) if flash_now else None,
                rule_rows=rb,
            )
        # (as `_step`'s: the step's device buffers die under a name)
        with jitwatch.span("bbtpu.release"):
            del payload_dev, result, arena, _run
        with jitwatch.span("bbtpu.slice"):
            return out[0, :r], combined

    def fetch(self, out) -> np.ndarray:
        """Materialize a fetch=False result on host in the wire dtype
        (blocks on the device round trip — call off the compute queue).
        A list of per-chunk results concatenates along the token axis.

        This is the package's ONE deliberate d2h chokepoint: results go
        straight onto the wire, so the sync is the contract, not a leak.
        Dispatchers pass fetch=False and call this off-queue (jitwatch
        counts any call that lands on the compute thread as a hot-path
        sync — the convoy BB011 flags statically)."""
        jitwatch.host_sync("executor.fetch")
        if isinstance(out, (list, tuple)):
            host = np.concatenate(  # bbtpu: noqa[BB011] wire-bound d2h by contract; hot dispatchers use fetch=False and fetch off-queue
                [np.asarray(o) for o in out], axis=1
            ).astype(self.transfer_dtype)
        else:
            host = np.asarray(out).astype(self.transfer_dtype)  # bbtpu: noqa[BB011] wire-bound d2h by contract; hot dispatchers use fetch=False and fetch off-queue
        self._drain_reach()
        return host

    def _drain_reach(self) -> None:
        """Count what the finished steps reached of the held experts: each
        is one zero-length `bbtpu.moe_reach` span (the step's `kind` and
        `rows`; per sparse layer `held_hit`, `routed_pairs_here`,
        `rows_with_held_expert` and, under a sigmoid router with a bias,
        `bias_moved_pairs` and `routed_pairs`) and an increment of
        `moe_reach` (rpc_info).
        Only arrays the device has finished are read: no wait is added.
        Several fetch threads may come here at once: a step is taken, and
        the sums moved, under the lock; the read and the span are not."""
        done = []
        with self._reach_lock:
            while self._reach_pending and self._reach_pending[0][2].is_ready():
                done.append(self._reach_pending.popleft())
        for kind, rows, reach in done:
            cols = [
                [int(v) for v in col] for col in np.asarray(reach).T  # bbtpu: noqa[BB011] a finished [layers, 3 or 5] counter, read off the compute thread
            ]
            # the first three of `reach_fields`; a router whose choice a
            # bias corrects counts `bias_moved_pairs` and `routed_pairs` too
            by_field = dict(zip(reach_fields(len(cols) > 3), cols))
            with jitwatch.span(
                "bbtpu.moe_reach", kind=kind, rows=rows,
                # `;`-separated: the profiler cuts an id at a comma
                **{k: ";".join(map(str, v)) for k, v in by_field.items()},
            ):
                pass
            with self._reach_lock:
                m = self.moe_reach
                m["steps"] += 1
                m["rows"] += rows
                m["held_hit_last"] = by_field.pop("held_hit")
                for k, v in by_field.items():
                    m[k] = m.get(k, 0) + sum(v)

    def decode_n(
        self,
        handle: CacheHandle,
        ids: np.ndarray,  # [B] int: the input token of the first step
        n: int,
        client_params: dict,  # embed + norm + lm_head (checkpoint's trio)
        eos_token_id: int | None = None,
        finished: np.ndarray | None = None,  # [B] bool rows already at EOS
        adapter: str | None = None,
    ):
        """Run N greedy decode steps entirely on device and return the [B, n]
        selected token ids as a lazy device array (caller fetches off-queue).

        One jitted lax.scan does embed -> span -> norm+head -> argmax per
        step (runtime/decode_loop.py), so an RPC pays ONE host<->device round
        trip for n tokens instead of n round trips. Valid only when this
        span is the whole model (the server checks), dense, fully
        device-resident, and un-sharded. N is bucketed to the next power of
        two; padding steps write to out-of-bounds slots (dropped) and their
        tokens are sliced away, so no garbage reaches the KV arena.
        """
        spec = self.spec
        if self.host_layers or spec.heterogeneous or self.mesh is not None:
            raise ValueError(
                "decode_n needs a dense, fully device-resident, un-sharded "
                "span"
            )
        if self.manager.quant is not None:
            raise ValueError("decode_n + quantized KV arena not supported")
        if spec.recurrent is not None:
            raise ValueError("decode_n + recurrent state not supported")
        if spec.mla is not None:
            raise ValueError("decode_n + latent attention not supported")
        if self.attn_sparsity < 1.0:
            # the per-step path recomputes top-k from the CURRENT context
            # length every step; a k frozen at trace time would diverge
            raise ValueError("decode_n + attn_sparsity not supported")
        from bloombee_tpu.models.checkpoint import resolve_adapter

        lora = resolve_adapter(self.adapters, adapter)
        with jitwatch.span("bbtpu.pack"):
            self.manager.ensure_resident(handle)
            b = int(ids.shape[0])
            bb = next_pow2(b)
            nb = next_pow2(n)
            arena_tokens = self.manager.capacity_tokens
            lens_now = self.manager.context_lens(handle)
            final_max = int(lens_now.max()) + n
            pb = min(
                next_pow2(max(-(-final_max // self.page_size), 1), floor=4),
                arena_tokens // self.page_size,
            )
            oob = arena_tokens
            layer_active = np.ones((self.manager.num_layers,), np.int32)
            pt_pad = np.zeros((bb, pb), np.int32)
            lens_pad = np.zeros((bb,), np.int32)
            pos_pad = np.zeros((bb, 1), np.int32)
            plans = []
            for i in range(nb):
                slots_pad = np.full((bb, 1), oob, np.int32)
                if i < n:
                    slots_pad[:b, 0] = self.manager.write_slots(
                        handle, 1, commit=True
                    )
                    total_lens = self.manager.context_lens(handle)
                    pt_pad[:b] = self.manager.page_table(handle, pb)
                    lens_pad[:b] = total_lens
                    pos_pad[:b, 0] = total_lens - 1
                plans.append(
                    pack_plan(slots_pad, pt_pad, pos_pad, lens_pad, layer_active)
                )
            plans = np.stack(plans)

            # paged gating uses the STARTING length's page bucket (the same
            # bucket the per-step path sees on the chunk's first step), so a
            # chunk beginning below the paged crossover stays dense like its
            # per-step equivalent. A chunk that CROSSES the crossover keeps one
            # kernel throughout (the flag is static over the scan) while the
            # per-step path would switch mid-way — the kernels agree to ~1e-5,
            # so an exact argmax tie at the boundary could in principle flip;
            # everywhere else greedy outputs are bitwise identical.
            pb_start = min(
                next_pow2(
                    max(-(-(int(lens_now.max()) + 1) // self.page_size), 1),
                    floor=4,
                ),
                arena_tokens // self.page_size,
            )
            use_paged = self._paged_kernel_ok(pb_start * self.page_size)
            ids_pad = np.zeros((bb,), np.int32)
            ids_pad[:b] = np.asarray(ids).reshape(-1)
            fin_pad = np.ones((bb,), bool)  # padding rows never select real ids
            fin_pad[:b] = (
                np.asarray(finished, dtype=bool) if finished is not None else False
            )
        with jitwatch.span("bbtpu.h2d"):
            ids_dev, fin_dev, plans_dev = (
                jnp.asarray(ids_pad), jnp.asarray(fin_pad),
                jnp.asarray(plans),
            )
        arena = self.manager.arena

        from bloombee_tpu.runtime.decode_loop import decode_loop

        def _run(use_paged_now: bool):
            with jitwatch.region("decode_loop", f"b{bb},n{nb},p{pb}"):
                return decode_loop(  # bbtpu: noqa[BB012] eos_id is a per-model token constant (cardinality 1 per checkpoint), not a request shape
                    client_params, self.params, arena["k"], arena["v"],
                    ids_dev, fin_dev, plans_dev, lora,
                    spec=spec, page_size=self.page_size, max_pages=pb,
                    eos_id=(
                        -1 if eos_token_id is None else int(eos_token_id)
                    ),
                    compute_dtype=self.compute_dtype,
                    windows=self.windows,
                    use_paged=use_paged_now,
                )

        (toks, new_k, new_v), used_paged = self._dispatch(
            _run, use_paged, arena, "decode_n"
        )
        self.attn_dispatches["paged" if used_paged else "dense"] += 1
        self._count_moe(bb, used_paged)
        self.manager.arena = {"k": new_k, "v": new_v}
        with jitwatch.span("bbtpu.slice"):
            return toks[:b, :n]

    def _place_step_inputs(self, payload, tm_pad):
        """Commit one step's packed (payload, tree mask) to the device —
        replicated over the tp mesh when serving sharded."""
        with jitwatch.span("bbtpu.h2d"):
            if self.mesh is not None:
                from bloombee_tpu.parallel import serving as tp_serving

                return (
                    tp_serving.replicated(payload, self.mesh),
                    tp_serving.replicated(tm_pad, self.mesh)
                    if tm_pad is not None else None,
                )
            return (
                jnp.asarray(payload),
                jnp.asarray(tm_pad) if tm_pad is not None else None,
            )

    def _arena(self) -> dict:
        """What a span step is handed and donates: the K/V arena and, for a
        family with recurrent state, the state arena under "state"."""
        arena = dict(self.manager.arena)
        if self.manager.state is not None:
            arena["state"] = self.manager.state
        return arena

    def _whole_span_only(self, layers) -> None:
        if layers is not None and tuple(layers) != (
            0, self.manager.num_layers
        ):
            raise ValueError(
                f"{self.spec.family}: a session runs the whole span (got "
                f"layers {tuple(layers)}): its runs of layer pairs are "
                "scanned whole, and the cross-decoder reads what the shared "
                "layers of THIS step left"
            )

    def _keep_arena(self, result, kind: str, rows: int, starts,
                    experts: str | None = None,
                    cross_rows: int | None = None,
                    flash: str | None = None, write: str = "rows",
                    rule_rows: int = 0, decode_pages: int | None = None):
        """Store a span step's returned arenas (K, V and, where the family
        has one, the state arena) on the manager; returns the step's output.
        A step of a latent-attention family, of one with linear-attention
        layers among attention layers or of one with window layers among
        full ones is stamped as it is dispatched,
        the zero-length span `bbtpu.step`: its `kind` ("decode" | "chunk" |
        "fused"), real `rows` and `context` (its sequences' mean cached
        tokens before it, `starts`): the attention core's time follows the
        context, and a trace's reader has to know WHICH steps it holds;
        `experts` is the form its experts took (`_count_moe`), `arena` the
        K/V slabs' layout ("folded" | "unfolded": kv/arena.py `folds`),
        `flash` the tile a chunk's flash kernel multiplied (`_flash_form`),
        `write` how the rows went into the arena ("pages" | "rows":
        `_page_groups`), `kinds` the layers of each kind a span of layers
        that are one sublayer each holds, `rule_blocks` the blocks a delta-rule family's chunk
        form took in its one batched pass over the `rule_rows` a chunk-form
        sequence spans in the program (ops/linear_attention.py
        `sequence_blocks`: 8 for a 512-row chunk, 1 for the single-block
        form; no field on a step of decode rows alone), `decode_pages` the
        logical pages a grid step of a decode step's paged decode calls
        streams (`_pages_per_step` at the step's page bucket and the span's
        page rows; no field on a chunk, nor where the rows attend through
        another kernel or none).
        `kind` and `rows` are also kept beside what the rows reached of the
        held experts, where the step says it."""
        if self._window_layers:
            lens = np.asarray(starts, np.int64)
            self.kv_held["kv_held_tokens"] += int(
                self.manager.kv_layers * lens.sum()
            )
            self.kv_held["window_dead_tokens"] += int(
                self._window_layers * np.maximum(
                    lens - self.spec.sliding_window, 0
                ).sum()
            )
        if self.spec.mamba is not None and self._cross_layers:
            # a span that ends with the cross-decoder: `cross_rows` of the
            # step's `rows` went through it (None: all of them)
            cross_rows = rows if cross_rows is None else cross_rows
            c = self.sambay
            c["self_rows"] += rows
            c["cross_rows"] += cross_rows
            c["long_steps" if cross_rows else "short_steps"] += 1
            c["shared_kv_reads"] += self._cross_layers * bool(cross_rows)
        if (self.spec.mla is not None or self.spec.kinds_interleave
                or self.spec.mamba is not None or self.spec.flash_window):
            with jitwatch.span(
                "bbtpu.step", kind=kind, rows=rows,
                **({"kinds": self._kinds} if self._kinds else {}),
                context=int(np.mean(starts)),
                arena="folded" if self.manager.folded else "unfolded",
                write=write,
                **({"experts": experts} if experts else {}),
                **({"flash": flash} if flash else {}),
                **({"cross_rows": cross_rows} if self._cross_layers else {}),
                **({"rule_blocks": sequence_blocks(
                    rule_rows, self.spec.gdn.chunk,
                    self.spec.gdn.channel_decay,
                )} if self.spec.gdn is not None and rule_rows else {}),
                **({"decode_pages": decode_pages} if decode_pages else {}),
            ):
                pass
        if self.spec.moe_held is not None:
            *result, reach = result
            self._reach_pending.append((kind, rows, reach))
        out, new_k, new_v, *rest = result
        self.manager.arena = {"k": new_k, "v": new_v}
        if rest:
            self.manager.state = rest[0]
        return out

    def _state_slots_padded(self, handle, bucket: int) -> np.ndarray:
        """[bucket] state slots of the handle's sequences; padding rows get
        the pool's size, which no slot has (read clamped, write dropped)."""
        slots = np.full((bucket,), self.manager.num_state_slots, np.int32)
        if self.manager.state is not None:
            slots[: handle.batch_size] = self.manager.state_slots(handle)
        return slots

    def _count_moe(self, rows: int, kernels: bool) -> str | None:
        """Count the dispatch under the form its experts took (ops/moe.py:
        the list form counts as `grouped`) and return it."""
        if not self.spec.num_experts:
            return None
        form = experts_form(self.spec, self.params, rows, kernels)
        self.moe_dispatches["grouped" if form == "list" else form] += 1
        return form

    def _flash_form(self, rows: int, max_pages: int) -> str | None:
        """The tile the flash kernel multiplies where a step's chunk of
        `rows` rows a sequence attends at the `max_pages` bucket, by the kind
        of attention layer the span holds: "full:128x512x4" or
        "window:256x512x6+full:256x512x6" (block_q x block_k x query heads a
        tile; the kinds joined by "+", neither the comma the profiler cuts an
        id at nor the ";" of an id that is a list of counts:
        utils/jitwatch.py); None where the kernel takes no layer's call. Made
        once a (rows, bucket), as the step's program is, from what the layers
        themselves decide by: the run a layer gathers (`chunk_run_pages`),
        the test its caller makes of it (`flash_takes`) and the kernel's
        own rule (`flash_tiles`)."""
        key = (rows, max_pages)
        if key not in self._flash_forms:
            spec = self.spec
            itemsize = np.dtype(self.compute_dtype).itemsize
            kinds = {}
            for window in sorted(self._attn_windows, reverse=True):
                keys = self.page_size * chunk_run_pages(
                    rows, window, self.page_size, max_pages
                )
                if flash_takes(rows, keys):
                    kinds["window" if window else "full"] = "x".join(map(
                        str, flash_tiles(
                            rows, keys, spec.gqa_groups, spec.head_dim,
                            itemsize,
                        )
                    ))
            self._flash_forms[key] = "+".join(
                f"{k}:{v}" for k, v in kinds.items()
            ) or None
        form = self._flash_forms[key]
        self.flash_form = form or self.flash_form
        return form

    def _page_groups(self, slots_pad: np.ndarray) -> bool:
        """Whether a dispatch's K/V rows go into the arena one index a PAGE:
        its padded slots come as page groups (kv/arena.py `rows_fill_pages`:
        a prompt chunk that starts on a page boundary) and both slabs' page
        view is free by their shape. Read from the dispatch's own slots and
        shapes, as `use_flash` is; the step program takes the answer as a
        static argument."""
        return self._page_view_free and rows_fill_pages(
            slots_pad, self.page_size, self.manager.capacity_tokens
        )

    @staticmethod
    def _arena_ready(arena) -> bool | None:
        """Whether the device has finished everything it was given: the
        arena a dispatch donates is the last program's output, so its being
        ready (asked without blocking, no reference kept) says so. A K/V
        arena of no rows says nothing: the first slab that holds bytes is
        asked, so a span of mixers alone asks its state arena. Asked by the
        witness only (`jitwatch.launch`)."""
        last = next((a for a in jax.tree.leaves(arena) if a.size), None)
        return None if last is None else last.is_ready()

    @staticmethod
    def _arena_consumed(arena) -> bool:
        return any(
            getattr(a, "is_deleted", lambda: False)()
            for a in jax.tree.leaves(arena)
        )

    def _paged_kernel_ok(self, context_tokens: int) -> bool:
        """What every use of a paged Pallas kernel needs, whatever the step:
        no kernel has failed on this device, the model's attention is one
        the kernels compute (no ALiBi, no logit soft-cap), the context
        bucket is past the crossover below which the dense gather is
        cheaper, and the kernels are switched on and can run here. Each
        caller adds what its own step needs (mesh, arena quantisation, row
        budget, sparsity)."""
        return bool(
            self._kernels_ok()
            and context_tokens >= env.get("BBTPU_PAGED_MIN_CONTEXT")
            and not self.spec.alibi
            and not self.spec.attn_logit_softcap
        )

    def _kernels_ok(self) -> bool:
        """The part of `_paged_kernel_ok` that is no matter of attention: no
        kernel has failed on this device, and the kernels are switched on
        and can run here."""
        return bool(
            not self._paged_broken
            and env.get("BBTPU_PAGED_ATTENTION")
            and _kernels_available("BBTPU_PAGED_INTERPRET")
        )

    def _dispatch(self, run, use_kernel: bool, arena, where: str):
        """Run one donated-arena dispatch: `run(use_kernel)` -> result.
        Returns (result, kernel actually used).

        A failure after donation consumed the arena leaves deleted
        buffers: rebuild so the server survives (sessions replay), then
        re-raise. A failure BEFORE donation (a compile error surfaces at
        call time) on the Pallas path retries once on the dense path; that
        keeps the server answering, but it is a kernel bug, so it is
        logged, counted in kernel_fallbacks, and the kernel path stays
        off for the life of the process."""
        jitwatch.launch(self._arena_ready, arena)
        try:
            return run(use_kernel), use_kernel
        except Exception:
            if self._arena_consumed(arena):
                self._rebuild_after_failure(where)
                raise
            if not use_kernel:
                raise
            logger.exception(
                "Pallas attention kernel failed in %s; retrying on the "
                "dense path", where,
            )
            result = run(False)
            self._paged_broken = True
            self.kernel_fallbacks += 1
            return result, False

    def _rebuild_after_failure(self, where: str) -> None:
        """A failure consumed the donated arena mid-chain: without a fresh
        arena every later step would compute on deleted buffers, bricking
        the server. Rebuild (zeroed) and bump the epoch so pre-rebuild
        sessions fail loudly and their clients replay (advisor, round 2)."""
        logger.error(
            "%s failed after the donated arena was consumed; rebuilding a "
            "fresh arena — live sessions' KV is lost and their clients "
            "must replay", where,
        )
        self.manager.rebuild_arena()
        if self.mesh is not None:
            # the fresh slabs land on the default device; a TP server must
            # re-place them or every later step runs with an unsharded
            # arena against sharded params (x tp HBM + a recompile)
            from bloombee_tpu.parallel import serving as tp_serving

            self.manager.arena = tp_serving.place_arena_for(
                self.spec, self.manager.arena, self.mesh
            )

    def _run_offloaded(
        self, h_pad, slots_pad, pt_pad, positions, lens_pad, layer_active,
        tm_pad, lora, bb, tb, pb, use_flash, use_paged, attn_topk=0,
        t_real=None,
    ):
        """Weight-offload step: scan the device-resident prefix, then stream
        each offloaded layer's params host->device with ONE-AHEAD prefetch
        (jax transfers are async, so layer l+1's H2D copy overlaps layer l's
        compute — the copy-engine overlap of the reference's
        PipelineParallelWrapper pre-forward H2D, convert_block.py:138-263).
        The arena never leaves the device; each layer_step scatters its rows
        into the donated arena in place."""
        from bloombee_tpu.runtime.step import layer_step

        ak, av = self.manager.arena["k"], self.manager.arena["v"]
        resident = self.resident
        # under TP, every per-step input commits replicated to the mesh
        # and each streamed host layer places SHARDED (its H2D bytes split
        # across the tp chips); single-chip keeps plain transfers
        if self.mesh is not None:
            from bloombee_tpu.parallel import serving as tp_serving

            place_rep = functools.partial(
                tp_serving.replicated, mesh=self.mesh
            )
            place_layer = functools.partial(
                tp_serving.place_layer_params, mesh=self.mesh
            )
        else:
            place_rep = jnp.asarray
            place_layer = jax.device_put
        tm_dev = place_rep(tm_pad) if tm_pad is not None else None
        use_tm = tm_pad is not None

        la_res = layer_active[:resident].copy()
        if resident and la_res.any():
            plan_res = pack_plan(
                slots_pad, pt_pad, positions, lens_pad, la_res
            )
            lora_res = (
                jax.tree.map(lambda x: x[:resident], lora)
                if lora is not None else None
            )
            hidden, ak, av = span_step_packed(
                self.params, ak, av,
                place_rep(pack_step_payload(h_pad, plan_res)), tm_dev,
                lora_res,
                spec=self.spec, b=bb, t=tb, page_size=self.page_size,
                max_pages=pb, use_tree_mask=use_tm,
                windows=self.windows[:resident], use_flash=use_flash,
                use_paged=use_paged, attn_topk=attn_topk,
                t_real=t_real,
            )
        else:
            hidden = place_rep(h_pad)

        idxs = [
            l for l in range(resident, self.manager.num_layers)
            if layer_active[l]
        ]
        if not idxs:
            return hidden, ak, av
        plan1 = place_rep(
            pack_plan(
                slots_pad, pt_pad, positions, lens_pad,
                np.ones((1,), np.int32),
            )
        )
        nxt = place_layer(self.host_layers[idxs[0] - resident])
        for i, l in enumerate(idxs):
            cur, nxt = nxt, (
                place_layer(self.host_layers[idxs[i + 1] - resident])
                if i + 1 < len(idxs) else None
            )
            lora_l = (
                jax.tree.map(lambda x: x[l], lora)
                if lora is not None else None
            )
            hidden, ak, av = layer_step(  # bbtpu: noqa[BB012] window is per-layer checkpoint config (few distinct values per model), not a request shape
                cur, ak, av, hidden, plan1, jnp.int32(l), tm_dev, lora_l,
                spec=self.spec, page_size=self.page_size, max_pages=pb,
                use_tree_mask=use_tm, window=int(self.windows[l]),
                use_flash=use_flash, use_paged=use_paged,
                attn_topk=attn_topk, t_real=t_real,
            )
        return hidden, ak, av

    # --------------------------------------------------------------- internals
    def _step(
        self,
        handle: CacheHandle,
        hidden: np.ndarray,
        commit: bool,
        tree_mask: np.ndarray | None = None,
        layers: tuple[int, int] | None = None,
        depths: np.ndarray | None = None,
        fetch: bool = True,
        adapter: str | None = None,
        reply_rows: int | None = None,  # the caller reads only the last n
        # rows of each sequence (0: none). Only a span that ends with layers
        # that write no cache acts on it (a SambaY cross-decoder runs on
        # those rows alone, and the others come back as zeros); None, and
        # every other family: every row
    ):
        spec = self.spec
        from bloombee_tpu.models.checkpoint import resolve_adapter

        lora = resolve_adapter(self.adapters, adapter)
        b, t, d = hidden.shape
        assert d == spec.hidden_size
        if spec.recurrent is not None and (tree_mask is not None or depths is not None):
            raise ValueError(
                "tree verify unsupported: a recurrent state cannot branch "
                "over tree rows or be cut back to the accepted ones"
            )
        if spec.mla is not None and (
            tree_mask is not None or depths is not None
        ):
            raise ValueError(
                "tree verify unsupported: latent attention's kernels take "
                "no tree mask"
            )

        with jitwatch.span("bbtpu.pack"):
            # over-subscribed servers may have parked this session's KV to
            # host while it was idle; bring it back before writing
            self.manager.ensure_resident(handle)
            starts = self.manager.context_lens(handle)  # [B] before write
            slots = self.manager.write_slots(handle, t, commit=commit)  # [B*T]
            total_lens = self.manager.context_lens(handle)  # [B] after write

            # buckets; tree steps keep T exact — the tree mask's key-position
            # arithmetic in step._attend_paged assumes the written token count
            # equals T (tree shapes are already bucketed by the drafter)
            bb = next_pow2(b)
            tb = t if (t == 1 or tree_mask is not None) else next_pow2(t)
            arena_tokens = self.manager.capacity_tokens
            pages_needed = int(
                max(-(-int(l) // self.page_size) for l in total_lens)
            )
            pb = min(
                next_pow2(max(pages_needed, 1), floor=4),
                arena_tokens // self.page_size,
            )

            oob = arena_tokens  # out-of-bounds slot => dropped write
            h_pad = np.zeros((bb, tb, d), dtype=self.transfer_dtype)
            h_pad[:b, :t] = hidden.astype(self.transfer_dtype)
            slots_pad = np.full((bb, tb), oob, dtype=np.int32)
            slots_pad[:b, :t] = slots.reshape(b, t)
            # rotary positions: sequential for plain steps; start + per-node tree
            # depth for tree steps (reference: tree rotary ids, backend.py:944)
            positions = np.zeros((bb, tb), dtype=np.int32)
            if depths is not None:
                positions[:b, :t] = starts[:, None] + np.asarray(depths)[:, :t]
            else:
                positions[:b, :t] = (
                    starts[:, None] + np.arange(t, dtype=np.int32)[None, :]
                )
            pt_pad = np.zeros((bb, pb), dtype=np.int32)
            pt_pad[:b] = self.manager.page_table(handle, pb)
            lens_pad = np.zeros((bb,), dtype=np.int32)
            lens_pad[:b] = total_lens
            num_layers = self.manager.num_layers
            layer_active = np.ones((num_layers,), dtype=np.int32)
            if layers is not None:
                layer_active[:] = 0
                layer_active[layers[0] : layers[1]] = 1
            plan = pack_plan(slots_pad, pt_pad, positions, lens_pad, layer_active)
            if spec.recurrent is not None:
                plan = np.concatenate(
                    [plan, self._state_slots_padded(handle, bb)]
                )
            cross_rows = None
            if spec.mamba is not None:
                self._whole_span_only(layers)
                if tb > 1:
                    n_i = t if reply_rows is None else min(int(reply_rows), t)
                    cross_idx = [
                        i * tb + j for i in range(b)
                        for j in range(t - n_i, t)
                    ]
                    cross_rows = len(cross_idx)
                    plan = np.concatenate(
                        [plan, pack_cross_tail(cross_idx, bb * tb)]
                    )
            tm_pad = None
            if tree_mask is not None:
                tm_pad = np.zeros((bb, tb, tb), dtype=bool)
                tm_pad[:b, :t, :t] = tree_mask

            # paged-kernel eligibility (per-seq lens may differ — masked
            # in-kernel; sliding windows ride as traced scalars, skipping
            # out-of-window pages outright). Short contexts stay on the dense
            # path — the gather is cheap there and the kernel's page-granular
            # grid costs more than it saves (measured crossover ~512 tokens).
            # T==1: plain decode (int4 arenas dequantize in-kernel).
            # T>1 (round-4 verdict #5): tree-verify steps (tree mask applied
            # in-kernel; tree+window stays dense — depth-positioned windows
            # don't fit the kernel's arithmetic) and short multi-token chunks
            # below flash's T>=128 domain, bounded by the [T*H, hd] VMEM
            # budget; dense arenas only.
            t1_ok = tb == 1 and self.manager.quant in (None, "int4")
            chunk_ok = (
                1 < tb < 128
                and self.manager.quant is None
                and tb * self.spec.num_attention_heads <= 2048
                and (tree_mask is None or all(w == 0 for w in self.windows))
            )
            if self.spec.mla is not None or self.spec.mamba is not None:
                # latent attention: the paged decode kernel for T == 1, the
                # flash form over the gathered latent rows for any chunk
                # (it blocks its queries itself); `use_paged` says kernels
                # may run in this program, the experts' kernel forms too.
                # A SambaY span likewise attends row by row and chunk by
                # chunk in its own step (runtime/sambay.py)
                t1_ok = chunk_ok = True
            use_paged = bool(
                self._paged_kernel_ok(pb * self.page_size)
                and self.attn_sparsity >= 1.0  # kernel has no top-k path
                and self.mesh is None  # Pallas kernels don't GSPMD-partition
                and not self.spec.heterogeneous
                and (t1_ok or chunk_ok)
            )

            # flash eligibility: per-row starts/lens ride into the kernel as
            # traced vectors, so MIXED-length batches engage flash too; the
            # only row-shape requirement left is that every row wrote exactly
            # this step's t tokens (ragged commit_lens replay writes a padded
            # rectangle first, satisfying this during the step). Windows: a
            # family whose window layers stand among full ones
            # (`spec.flash_window`) runs both kinds on the kernel, a window
            # layer's with the window static and only its pages gathered
            # (runtime/layer_body.py `_flash_by_window`); a span whose
            # layers ALL have a window and whose family has no full layer
            # (mistral) keeps the dense chunk it had: its kernel form is a
            # change of that family's programs, measured on its own
            s_ctx = pb * self.page_size
            use_flash = bool(
                self.mesh is None  # Pallas kernels don't GSPMD-partition
                # (attn_sparsity is decode-only, so flash PREFILL is unaffected)
                and not self.spec.heterogeneous
                and self.spec.mla is None  # its own flash form (use_paged)
                and self.spec.mamba is None  # likewise
                and tree_mask is None
                and flash_takes(tb, s_ctx)
                and not self.spec.alibi
                and not self.spec.attn_logit_softcap
                and self._flash_windows_ok
                and np.all(total_lens == starts + t)
                and env.get("BBTPU_FLASH_ATTENTION")
                and _kernels_available("BBTPU_FLASH_INTERPRET")
            )

            # the arena write by page: read from the slots (`_page_groups`);
            # the offloaded step keeps the row scatter
            page_groups = not self.host_layers and self._page_groups(slots_pad)

            attn_topk = 0
            if self.attn_sparsity < 1.0 and tb == 1 and tree_mask is None:
                # decode-only approximation (FlexGen applies sparsity at
                # generation only): sparsifying prefill would corrupt the
                # cached context every layer feeds the next. k derives from the
                # pow2 bucket of the largest TRUE row length — attn_topk is a
                # static jit arg, so an exact per-step k would retrace the span
                # every few tokens; pow2 bucketing caps compiles at O(log S) at
                # the cost of k being up to 2x looser right after a boundary.
                attn_topk = max(
                    1,
                    int(
                        self.attn_sparsity
                        * (next_pow2(int(total_lens.max())) - 1)
                    ),
                )

            if not self.host_layers:
                # one host buffer for the one h2d: a copy of the step's rows
                # (a chunk's 5-8 MB), so it is packing and not the transfer
                payload = pack_step_payload(h_pad, plan)

        arena = self._arena()
        if self.host_layers:
            def _run_off(use_paged_now: bool):
                with jitwatch.region("layer_step", f"b{bb},t{tb},p{pb}"):
                    return self._run_offloaded(
                        h_pad, slots_pad, pt_pad, positions, lens_pad,
                        layer_active, tm_pad, lora, bb, tb, pb, use_flash,
                        use_paged_now, attn_topk, t_real=t,
                    )

            (out, new_k, new_v), use_paged = self._dispatch(
                _run_off, use_paged, arena, "offloaded step"
            )
            self.manager.arena = {"k": new_k, "v": new_v}
            self._count_moe(bb * tb, use_paged)
        elif self.spec.heterogeneous:
            from bloombee_tpu.runtime.hetero import span_step_hetero

            payload_dev, tm_dev = self._place_step_inputs(payload, tm_pad)

            def _run_hetero(_use_kernel: bool):
                with jitwatch.region(
                    "span_step_hetero", f"b{bb},t{tb},p{pb}"
                ):
                    return span_step_hetero(  # bbtpu: noqa[BB012] layer_active is the hetero residency mask — one value per (span, offload split), not per request
                        self.params,
                        arena["k"],
                        arena["v"],
                        payload_dev,
                        tm_dev,
                        lora,
                        spec=spec,
                        b=bb,
                        t=tb,
                        page_size=self.page_size,
                        max_pages=pb,
                        use_tree_mask=tree_mask is not None,
                        start_block=self.start_block,
                        layer_active=tuple(int(x) for x in layer_active),
                        attn_topk=attn_topk,
                    )

            (out, new_k, new_v), _ = self._dispatch(
                _run_hetero, False, arena, "hetero span step"
            )
            self.manager.arena = {"k": new_k, "v": new_v}
            self._count_moe(bb * tb, False)
        else:
            payload_dev, tm_dev = self._place_step_inputs(payload, tm_pad)

            # a chunk that attends through flash runs no paged kernel, yet
            # Pallas kernels run in its program: its experts may take a
            # kernel form where what every such kernel needs holds
            flash_experts = bool(
                use_flash and not use_paged and self._kernels_ok()
                and experts_form(spec, self.params, bb * tb, True) != "dense"
            )

            def _run(kernels_now: bool):
                with jitwatch.region(
                    "span_step_packed", f"b{bb},t{tb},p{pb}"
                ):
                    return span_step_packed(
                        self.params,
                        arena["k"],
                        arena["v"],
                        payload_dev,
                        tm_dev,
                        lora,
                        arena.get("state"),
                        attn_topk=attn_topk,
                        spec=spec,
                        b=bb,
                        t=tb,
                        page_size=self.page_size,
                        max_pages=pb,
                        use_tree_mask=tree_mask is not None,
                        windows=self.windows,
                        use_flash=use_flash,
                        use_paged=use_paged and kernels_now,
                        t_real=t,
                        expert_kernels=flash_experts and kernels_now,
                        page_groups=page_groups,
                    )

            result, kernels_used = self._dispatch(
                _run, use_paged or flash_experts, arena, "span step"
            )
            use_paged = use_paged and kernels_used
            # what the step was, for the counters and the `bbtpu.step`
            # stamp: paid on the compute thread after every launch
            with jitwatch.span("bbtpu.counters"):
                # a chunk attends through the flash kernel: the plain
                # path's `use_flash`, or a SambaY span's own step with
                # kernels on, which takes every sequence with more than one
                # row as a chunk (runtime/sambay.py `_diff_attend`)
                flash_now = use_flash or (
                    self.spec.mamba is not None and use_paged and tb > 1
                )
                # decode rows stream their pages through
                # `paged_decode_attention` (the latent and the int4 kernels
                # keep grids of their own)
                decode_kernel = (
                    t == 1 and use_paged and spec.mla is None
                    and self.manager.quant is None
                )
                decode_pages = _pages_per_step(
                    pb, self.page_size * spec.num_key_value_heads
                ) if decode_kernel else None
                out = self._keep_arena(
                    result, "decode" if t == 1 else "chunk", b * t, starts,
                    self._count_moe(bb * tb, kernels_used),
                    cross_rows=cross_rows,
                    flash=self._flash_form(tb, pb) if flash_now else None,
                    write="pages" if page_groups else "rows",
                    rule_rows=tb if tb > 1 else 0,
                    decode_pages=decode_pages,
                )
                if decode_kernel:
                    for window, layers in self._attn_windows.items():
                        _, extent, live = walk_bounds(
                            lens_pad, window, self.page_size, decode_pages,
                            np)
                        self.kv_walk["turns"] += layers * bb * int(extent)
                        self.kv_walk["live_turns"] += layers * int(live)
            # the step's buffers on the device die HERE, under a name, and
            # not at the function's return, where no span would own their
            # destructors: the donated arena's old arrays, the payload, the
            # program's result tuple (the output and the new arenas live on)
            with jitwatch.span("bbtpu.release"):
                del payload_dev, tm_dev, result, arena, _run
        if t > 1:
            self.kv_writes[
                "chunk_page_writes" if page_groups else "chunk_row_writes"
            ] += 1
        path = "paged" if use_paged else "flash" if use_flash else "dense"
        self.attn_dispatches[path] += 1
        with jitwatch.span("bbtpu.slice"):
            out = out[:b, :t]
        if not fetch:
            return out  # lazy device array; caller fetches off-queue
        # keep the transfer dtype (bf16 when computing in bf16): this array
        # goes straight onto the wire (reply or server-to-server push)
        return self.fetch(out)
