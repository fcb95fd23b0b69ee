"""ModelSpec: the static shape/hyperparameter description of a model family.

This is the hashable static argument threaded through every jitted function —
the TPU-native replacement for the reference's Distributed*Config carrying an HF
config object around (/root/reference/src/bloombee/models/llama/config.py:16-19).
Keeping it a frozen dataclass of primitives means it can be a `jax.jit` static
arg and a compilation-cache key.
"""

from __future__ import annotations

import dataclasses
from typing import Any


@dataclasses.dataclass(frozen=True)
class SsmSpec:
    """A state-space (Mamba-2 SSD) mixer beside attention in every layer
    (falcon_h1). One nested descriptor, hashable like the spec that holds
    it. Per sequence and layer the mixer keeps a recurrent state
    [heads, head_dim, state] and the last `conv - 1` rows of the
    convolution's input; both live in the state arena (kv/arena.py)."""

    heads: int
    head_dim: int
    state: int  # d_state
    groups: int  # heads // groups heads share one B and one C
    conv: int  # depthwise causal convolution width
    chunk: int  # SSD chunk length (quadratic inside, recurrent across)
    in_multiplier: float = 1.0  # on the mixer's input
    # on in_proj's output segments z | x | B | C | dt
    multipliers: tuple[float, ...] = (1.0, 1.0, 1.0, 1.0, 1.0)
    out_multiplier: float = 1.0  # on the mixer's output

    @property
    def d_ssm(self) -> int:
        return self.heads * self.head_dim

    @property
    def conv_dim(self) -> int:
        """Channels the convolution runs over: x | B | C."""
        return self.d_ssm + 2 * self.groups * self.state

    @property
    def state_shape(self) -> tuple[int, ...]:
        """One sequence's recurrent state in one layer (kv/arena.py)."""
        return (self.heads, self.head_dim, self.state)

    @property
    def tail_shape(self) -> tuple[int, int]:
        """The convolution's carried input rows."""
        return (self.conv - 1, self.conv_dim)

    @property
    def proj_dim(self) -> int:
        """in_proj's output: z | x | B | C | dt."""
        return self.d_ssm + self.conv_dim + self.heads


@dataclasses.dataclass(frozen=True)
class GdnSpec:
    """A delta-rule linear-attention mixer IN PLACE of attention in the
    layers of kind "linear" (qwen3_next, kimi_linear: three of every four).
    Nested and hashable like `SsmSpec`. Per sequence and linear layer the
    mixer keeps ONE matrix a value head, S [value_heads, key_dim,
    value_dim], and the last `conv - 1` rows of the convolution's input
    (channels q | k | v); both live in the state arena, which then has a row
    a LINEAR layer and none for the others (`ModelSpec.cache_rows`). Key
    head g serves value heads g * r .. g * r + r - 1, r = value_heads //
    key_heads.

    Two published forms, one mixer (runtime/layer_body.py `_gdn_mixer`), the
    differences read from here. Gated DeltaNet (qwen3_next, the defaults):
    ONE scalar decay a head, in_proj makes q | k | v | z, the output is
    norm(o) * silu(z). Kimi delta attention (kimi_linear): `channel_decay`,
    a decay a KEY CHANNEL, made by a low-rank pair of `gate_rank` (f_a, f_b)
    with a dt_bias a channel; in_proj makes q | k | v only; the output is
    norm(o) * sigmoid(gate), the gate a second low-rank pair (g_a, g_b)."""

    key_heads: int
    value_heads: int
    key_dim: int  # per head
    value_dim: int  # per head
    conv: int  # depthwise causal convolution width
    chunk: int = 64  # block length of the chunk form (triangular inside)
    channel_decay: bool = False  # the decay is a vector over key_dim (KDA)
    gate_rank: int = 0  # > 0: the decay and a SIGMOID output gate come from
    # low-rank pairs of this rank; 0: `a` and a silu(z) gate out of in_proj

    @property
    def d_key(self) -> int:
        return self.key_heads * self.key_dim

    @property
    def d_value(self) -> int:
        return self.value_heads * self.value_dim

    @property
    def conv_dim(self) -> int:
        """Channels the convolution runs over: q | k | v."""
        return 2 * self.d_key + self.d_value

    @property
    def proj_dim(self) -> int:
        """in_proj's output as stored: q | k | v | z (no z where the gate
        has projections of its own)."""
        return self.conv_dim + (0 if self.gate_rank else self.d_value)

    @property
    def scope(self) -> str:
        """The prefix of the mixer's device scopes (`<scope>_proj`,
        `<scope>_conv`, `<scope>_rule`): a trace tells the two rules apart."""
        return "kda" if self.channel_decay else "gdn"

    @property
    def state_shape(self) -> tuple[int, ...]:
        return (self.value_heads, self.key_dim, self.value_dim)

    @property
    def tail_shape(self) -> tuple[int, int]:
        return (self.conv - 1, self.conv_dim)


@dataclasses.dataclass(frozen=True)
class Mamba1Spec:
    """A Mamba-1 selective-scan mixer IN PLACE of attention in the layers of
    kind "mamba" (phi4flash / SambaY: every other layer of the first half
    plus one). Nested and hashable like `SsmSpec`. Per sequence and Mamba
    layer the mixer keeps S [state, d_inner] float32 (the decay is
    exp(dt[c] * A[c, n]) a channel AND a state column; stored state-major so
    the channels lie along the lanes) and the last `conv - 1` rows of the
    convolution's input [conv - 1, d_inner]; both live in the state arena,
    which has a row a MAMBA layer and none for the others."""

    d_inner: int
    state: int
    conv: int
    dt_rank: int

    @property
    def state_shape(self) -> tuple[int, ...]:
        return (self.state, self.d_inner)

    @property
    def tail_shape(self) -> tuple[int, int]:
        return (self.conv - 1, self.d_inner)

    @property
    def x_proj_dim(self) -> int:
        """x_proj's output: dt's low-rank input | B | C."""
        return self.dt_rank + 2 * self.state


# the layer kinds of a SambaY stack (`ModelSpec.mamba`), as `layer_types`
# names them: which arena a kind OWNS a row of ("state" | "kv" | none)
SAMBAY_OWNS = {"mamba": "state", "sliding": "kv", "full": "kv", "gmu": None,
               "cross": None}


@dataclasses.dataclass(frozen=True)
class MlaSpec:
    """Multi-head latent attention (deepseek_v2; kimi_linear's full layers):
    queries and keys/values go through low-rank projections with their own
    RMSNorms, and the cache keeps per token and layer ONE latent of
    `kv_rank` values (after its norm) and ONE shared key of `rope_dim`
    values for all heads (after rotary, where the family has positions
    there: `rope`) instead of per-head K and V. Nested and hashable like
    `SsmSpec`; it declares the page payload the arena has to hold
    (`page_payload`)."""

    q_rank: int  # q_lora_rank; 0: ONE full-rank q_proj and no query norm
    # (DeepSeek-V2-Lite, kimi_linear), stored as its nope and rope rows
    kv_rank: int  # kv_lora_rank: the cached latent's width
    nope_dim: int  # per head, the part of q/k without positions
    rope_dim: int  # per head q, ONE shared k: the rotary part
    v_dim: int  # per head
    # YaRN (rope_scaling type "yarn"); factor 1.0 = plain rotary
    rope_factor: float = 1.0
    rope_original_max: int = 4096
    rope_beta_fast: float = 32.0
    rope_beta_slow: float = 1.0
    rope_mscale: float = 1.0
    rope_mscale_all_dim: float = 0.0
    # False (kimi_linear's `mla_use_nope`): the `rope_dim` columns of the
    # queries and of the shared key are plain score dimensions, no rotary
    rope: bool = True

    @property
    def qk_dim(self) -> int:
        return self.nope_dim + self.rope_dim

    @property
    def page_payload(self) -> tuple[tuple[int, ...], tuple[int, ...]]:
        """Trailing shape of a token's row in the arena's two slabs: the
        latent in `k`, the rotary key in `v` (two slabs, not one of
        kv_rank + rope_dim: the absorbed kernel reads the latent alone as
        its values). The rotary key's row is padded with zeros to whole
        lanes: with 64 values in the minor dimension every span-step
        program re-laid the whole slab out, once a run."""
        from bloombee_tpu.models.layout import lane_padded

        return (self.kv_rank,), (lane_padded(self.rope_dim),)

    @property
    def token_bytes(self) -> int:
        """Bytes a cached token takes in ONE layer at 2 bytes a value."""
        return 2 * sum(shape[0] for shape in self.page_payload)

    @property
    def softmax_scale(self) -> float:
        """qk_dim ** -0.5, times mscale ** 2 under YaRN with
        `mscale_all_dim` (the published modeling_deepseek.py)."""
        from bloombee_tpu.ops.rotary import yarn_mscale

        scale = self.qk_dim**-0.5
        if self.rope_factor != 1.0 and self.rope_mscale_all_dim:
            scale *= yarn_mscale(
                self.rope_factor, self.rope_mscale_all_dim
            ) ** 2
        return scale


def _repeats(kinds: tuple[str, ...]) -> list[tuple[tuple[str, ...], int]]:
    """A list of kinds as runs of a repeated unit, greedily from the left: a
    pair of kinds that comes again at once, else one layer (or a stretch of
    one kind)."""
    runs, i = [], 0
    while i < len(kinds):
        pair = tuple(kinds[i : i + 2])
        n = 1
        while len(pair) == 2 and pair[0] != pair[1] and tuple(
            kinds[i + 2 * n : i + 2 * n + 2]
        ) == pair:
            n += 1
        if n > 1:
            runs.append((pair, n))
            i += 2 * n
            continue
        n = 1
        while i + n < len(kinds) and kinds[i + n] == kinds[i]:
            n += 1
        runs.append(((kinds[i],), n))
        i += n
    return runs


@dataclasses.dataclass(frozen=True)
class ModelSpec:
    family: str
    hidden_size: int
    intermediate_size: int
    num_attention_heads: int
    num_key_value_heads: int
    head_dim: int
    num_hidden_layers: int
    vocab_size: int
    rms_norm_eps: float = 1e-6
    rope_theta: float = 10000.0
    tie_word_embeddings: bool = False
    max_position_embeddings: int = 4096
    # MoE (Mixtral-style); 0 experts = dense MLP
    num_experts: int = 0
    num_experts_per_tok: int = 0
    # router semantics: Mixtral masks then softmaxes over the top-k;
    # Qwen3-MoE softmaxes over ALL experts first, selects top-k, and
    # optionally renormalizes (norm_topk_prob)
    moe_pre_softmax: bool = False
    moe_norm_topk: bool = False
    # Qwen3-style per-head q/k RMSNorm
    qk_norm: bool = False
    # each layer's KIND, one pattern repeated down the stack (`layer_type`);
    # empty = every layer attends in full. Three meanings by family:
    # "sliding" | "full" where window layers stand among full ones (gemma2,
    # gemma4, afmoe; ("sliding",) alone: mistral) and a kind sets the
    # attention's window, perhaps its rotary base or whether it has positions
    # at all (`rope_window_only`); "linear" | "full" where a delta-rule
    # mixer takes attention's place (`gdn`: one period, qwen3_next, or every
    # layer's kind where the model ends on a short period, kimi_linear); all
    # 32 layers' kinds of a SambaY stack (`mamba`). A kind never says which
    # MLP a layer has (`mlp_kind`).
    layer_types: tuple[str, ...] = ()
    sliding_window: int = 0
    # Falcon/Bloom-style extras
    alibi: bool = False
    parallel_attn: bool = False
    num_ln_in_parallel_attn: int = 0
    attention_multiplier: float | None = None
    # Gemma-style logit soft-capping / embedding scaling
    logits_soft_cap: float = 0.0
    embedding_multiplier: float = 1.0
    # Per-layer rope theta override for sliding layers (Gemma3-style)
    rope_local_theta: float = 0.0
    # block structure knobs
    # "rms1p": RMSNorm whose stored weight is zero-centred, x/rms * (1 + w),
    # on the layer norms and q_norm / k_norm (qwen3_next); a client folds
    # the 1 into its final norm at load and runs "rms"
    norm_type: str = "rms"  # "rms" | "ln" | "rms1p"
    # "relu2": UNGATED, down(relu(up(x)) ** 2), two matrices (nemotron_h:
    # the routed experts and the shared one; no `gate` leaf anywhere)
    mlp_type: str = "silu"  # "silu" | "gelu" | "gelu_tanh_gated" | "relu2"
    sandwich_norms: bool = False  # Gemma2-style post-attn/post-ffn norms
    attn_logit_softcap: float = 0.0
    # Gemma-4-style heterogeneous attention geometry: full-attention layers
    # use their own head_dim / kv head count (reference backend.py:243-306
    # per-block-index KV descriptors) and may alias V to K
    global_head_dim: int = 0  # 0 = same as head_dim
    num_global_key_value_heads: int = 0  # 0 = same as num_key_value_heads
    k_eq_v_full: bool = False  # full layers share one K=V projection
    # this layer's resolved per-layer overrides (set by spec_for_layer)
    k_eq_v: bool = False
    # a state-space mixer beside attention in every layer (falcon_h1);
    # None = attention and MLP only
    ssm: SsmSpec | None = None
    # muP-style scalar multipliers, applied where the published code applies
    # them (1.0 = absent): attention input / keys / attention output, the
    # MLP's gate and output, the client's logits
    attention_in_multiplier: float = 1.0
    key_multiplier: float = 1.0
    attention_out_multiplier: float = 1.0
    mlp_multipliers: tuple[float, float] = (1.0, 1.0)
    lm_head_multiplier: float = 1.0
    # latent attention (deepseek_v2); None = per-head K and V in the cache
    mla: MlaSpec | None = None
    # deepseek-style sparse layers: the softmax router picks `top_k` among
    # the experts of the `moe_topk_groups` best of `moe_groups` groups and
    # scales the weights (moe_groups 0 = no groups); shared experts of one
    # fused width run beside the routed ones; the first
    # `first_dense_layers` layers of the MODEL have a dense MLP of
    # `intermediate_size`, the others experts of `moe_intermediate_size`
    moe_groups: int = 0
    moe_topk_groups: int = 0
    moe_route_scale: float = 1.0
    moe_shared_intermediate: int = 0
    moe_intermediate_size: int = 0
    first_dense_layers: int = 0
    # the experts this server holds, [first, first + count) of the router's
    # numbering (run_server --experts); None = all of them. The router
    # still scores all `num_experts`; a pair whose expert is not held adds
    # nothing here (its chip adds it)
    moe_held: tuple[int, int] | None = None
    # the shared expert's output is scaled by sigmoid(x @ w), w [D]
    moe_shared_gate: bool = False
    # a delta-rule mixer in the layers `layer_types` calls "linear"
    # (qwen3_next, kimi_linear); None = every layer attends
    gdn: GdnSpec | None = None
    # rotary on the first `rotary_dim` of a head's dims only (0 = all)
    rotary_dim: int = 0
    # positions in the WINDOW layers only (afmoe): a "full" layer's queries
    # and keys get no positional encoding at all, a "sliding" layer's the
    # rotary of `rope_theta` (runtime/step.py `_rope_by_window`)
    rope_window_only: bool = False
    # the router's scores: "softmax" (Mixtral's, Qwen3-MoE's, DeepSeek-V2's
    # forms above) or "sigmoid" (afmoe: scores = sigmoid(logits) of ALL
    # experts, the top-k of scores + `expert_bias` where the layer holds one,
    # the weights the UNBIASED scores of the chosen, renormalised iff
    # `moe_norm_topk`, times `moe_route_scale`: ops/moe.py `route_topk`)
    moe_router: str = "softmax"
    # q_proj makes a query AND an output gate a head: the attention output
    # is multiplied by sigmoid(gate) before o_proj (stored split at load:
    # `q_proj` the query rows, `q_gate_proj` the gate rows)
    attn_gate: bool = False
    # a SambaY stack (phi4flash): `layer_types` names every layer's kind,
    # "mamba" | "sliding" | "full" in the self-decoder (the first half plus
    # two), "gmu" | "cross" in the cross-decoder, whose layers keep no cache:
    # a gated memory unit reads the LAST mamba layer's scan output for the
    # same rows, a cross layer the ONE full layer's K/V pages. Attention is
    # differential (two softmaxes a head pair, a learned lambda, a sub-norm)
    # and has no positional encoding. In this family `head_dim` and
    # `num_key_value_heads` describe a K/V PAIR as the arena holds it
    # (k1 | k2 of 2 x 64, v of 128), `num_attention_heads` the query halves
    mamba: Mamba1Spec | None = None
    # layers that are ONE sublayer each behind ONE norm, x + f(norm(x))
    # (nemotron_h): `layer_types` names every layer's kind, "mamba" (the
    # state-space mixer `ssm` alone: a row in the state arena), "moe" (the
    # expert layer alone: no row in either arena) or "full" (attention
    # alone: a row in the K/V arena). A span is whole periods, a period
    # closed by its full layer or by the model's end (`span_unsupported`),
    # scanned as runs of repeated kinds (`period_runs`)
    one_sublayer: bool = False
    # False: no layer's queries and keys get a positional encoding at all
    # (nemotron_h: positions come from the state-space layers)
    rope: bool = True

    @property
    def recurrent(self):
        """The descriptor of the family's recurrent state (`SsmSpec` |
        `GdnSpec` | `Mamba1Spec`: all give `state_shape` and `tail_shape`),
        None without one. What refuses to cut, copy or park a cache asks
        this."""
        if self.mamba is not None:
            return self.mamba
        return self.ssm if self.ssm is not None else self.gdn

    def cache_rows(self, start: int, end: int) -> tuple[tuple[str, int], ...]:
        """For each layer of the span [start, end): which arena it uses
        ("state" | "kv" | "none") and its row there, the layer's index
        AMONG ITS KIND in the span. Only a family whose kinds differ in
        their cache (`gdn`, `mamba`) has arenas of fewer rows than layers;
        the K/V arena's rows are latent pages where the full layers attend
        latents (`mla` beside `gdn`: kimi_linear).
        A SambaY cross layer owns no row and READS the full layer's: its
        entry is ("kv", that row); a gated memory unit's is ("none", -1)."""
        rows, n = [], {"state": 0, "kv": 0}
        if self.mamba is not None:
            full_row = -1
            for i in range(start, end):
                kind = self.layer_type(i)
                arena = SAMBAY_OWNS[kind]
                if arena is not None:
                    if kind == "full":
                        full_row = n[arena]
                    rows.append((arena, n[arena]))
                    n[arena] += 1
                else:
                    rows.append(
                        ("kv", full_row) if kind == "cross" else ("none", -1)
                    )
            return tuple(rows)
        for i in range(start, end):
            arena = self.layer_arena(i)
            if arena is None:
                rows.append(("none", -1))
                continue
            rows.append((arena, n[arena]))
            n[arena] += 1
        return tuple(rows)

    def layer_arena(self, layer_idx: int) -> str | None:
        """The arena the layer at this ABSOLUTE index keeps its cache in,
        "state" | "kv", or None (an expert layer that is a layer of its
        own). Not asked of a SambaY stack (`SAMBAY_OWNS`) nor of a family
        whose every layer has a row in each arena it has (`ssm` beside
        attention)."""
        kind = self.layer_type(layer_idx)
        if self.one_sublayer:
            return {"mamba": "state", "full": "kv"}.get(kind)
        return "state" if self.gdn is not None and kind == "linear" else "kv"

    def arena_layers(self, start: int, end: int) -> tuple[int, int]:
        """(rows of the K/V arena, rows of the state arena) of a span: its
        layer count each, except where the kinds differ in their cache."""
        n = end - start
        if self.mamba is not None:
            owns = [
                SAMBAY_OWNS[self.layer_type(i)] for i in range(start, end)
            ]
            return owns.count("kv"), owns.count("state")
        if not self.kinds_interleave:
            return n, (n if self.ssm is not None else 0)
        kinds = [arena for arena, _ in self.cache_rows(start, end)]
        return kinds.count("kv"), kinds.count("state")

    @property
    def kinds_interleave(self) -> bool:
        """Layer kinds that keep DIFFERENT caches stand among each other,
        each with a row in its own arena only, and the span is scanned as
        runs of periods (runtime/step.py `_scan_periods`): delta-rule
        layers among full ones (`gdn`), or layers that are one sublayer
        each (`one_sublayer`). A SambaY stack has its own step."""
        return self.gdn is not None or self.one_sublayer

    @property
    def cross_start(self) -> int:
        """The first layer of a SambaY stack's cross-decoder (its layers
        keep no cache and run only on the rows a caller reads)."""
        return self.layer_types.index("gmu")

    def span_unsupported(self, start: int, end: int) -> str | None:
        """Why this family cannot serve the span [start, end); None when it
        can. Linear layers among full ones are scanned period by period
        (runtime/step.py `_scan_periods`), a period ending on its full
        layer, so a span holds whole periods from a period's first layer:
        runs of LIKE periods (`period_runs`), at most two (the period with
        the model's leading dense layer before the others, or the model's
        short last period after them). Window layers among full ones
        (gemma2, afmoe) are ONE kind of cache and one scan, the window a
        value that rides it: such a span may be cut anywhere, and so may a
        dense layer before sparse ones (two runs, `_scan_runs`)."""
        if self.mamba is not None:
            shared = self.cross_start - 2  # the last mamba layer: `m`'s source
            if start % 2 or end % 2 or end <= start:
                return (
                    f"a {self.family} span must hold whole (mixer, attention)"
                    f" pairs from an even layer (got [{start}, {end}))"
                )
            if end > shared and (start > shared
                                 or end != self.num_hidden_layers):
                return (
                    f"a {self.family} span may not cut layers {shared}-"
                    f"{self.num_hidden_layers - 1} (got [{start}, {end})): "
                    f"layers from {self.cross_start} on read layer "
                    f"{shared}'s scan output and layer {shared + 1}'s K/V "
                    "pages, which live on the server that ran those layers"
                )
            return None
        if self.one_sublayer:
            return self._sublayer_span_unsupported(start, end)
        if self.gdn is None:
            return None
        if (
            end <= start or end > self.num_hidden_layers
            or (start and self.layer_type(start - 1) != "full")
            or self.layer_type(end - 1) != "full"
        ):
            per = self.period
            tail = self.num_hidden_layers % per
            return (
                f"a {self.family} span must hold whole periods of "
                f"{per} layers {self.layer_types[:per]} from a period's "
                f"first layer" + (
                    f" (the model's last period has {tail})" if tail else ""
                ) + f" (got [{start}, {end})): the step scans periods, and "
                "each kind's stack and arena have one row a period's layer"
            )
        if len(self.period_runs(start, end)) > 2:
            return (
                f"a {self.family} span may hold two runs of like periods "
                f"(got [{start}, {end}): "
                f"{[n for _, n in self.period_runs(start, end)]} periods of "
                "three kinds: the period with the leading dense layer, "
                "the whole ones, the model's short last one)"
            )
        return None

    def period_starts(self) -> tuple[int, ...]:
        """Where a span of a `one_sublayer` family may be cut: layer 0, the
        layer after every full layer, and the model's end."""
        cuts = [0] + [
            i + 1 for i, kind in enumerate(self.layer_types) if kind == "full"
        ]
        if cuts[-1] != self.num_hidden_layers:
            cuts.append(self.num_hidden_layers)
        return tuple(cuts)

    def _sublayer_span_unsupported(self, start: int, end: int) -> str | None:
        cuts = self.period_starts()
        if end > start and start in cuts and end in cuts:
            return None
        pattern = "".join(
            {"mamba": "M", "moe": "E", "full": "*"}[k]
            for k in self.layer_types
        )
        if not 0 <= start < end <= self.num_hidden_layers:
            why = f"the model has layers 0-{self.num_hidden_layers - 1}"
        elif start not in cuts:
            why = (
                f"layer {start} stands inside the period that layer "
                f"{max(c for c in cuts if c < start)} opens"
            )
        else:
            why = (
                f"layer {end - 1} closes no period: the period it stands in "
                f"ends with layer {min(c for c in cuts if c > end) - 1}"
            )
        return (
            f"a {self.family} span must hold whole periods of the pattern "
            f"{pattern}, a period closed by its attention layer (*) or by "
            f"the model's end: it may be cut at layers {list(cuts)} (got "
            f"[{start}, {end}): {why}): servers are cut at the pattern's own "
            "seams, so that a span's runs of kinds, its stacks and both "
            "arenas' rows are whole periods'"
        )

    @property
    def period(self) -> int:
        """Layers of the FIRST period of a family whose linear layers stand
        among full ones: up to and with its full layer."""
        return self.layer_types.index("full") + 1

    def period_runs(
        self, start: int, end: int
    ) -> tuple[tuple[tuple[str, ...], int], ...]:
        """The span [start, end) of whole periods as runs of LIKE periods:
        ((what each layer of a period is, how many such periods), ...). A
        layer is "linear" | "full", with "+dense" where its MLP is dense
        and the family's others have experts: periods differ where the
        model's leading dense layer stands in one, or the last is short."""
        if self.one_sublayer:
            return self._sublayer_runs(start, end)
        runs, period = [], []
        for i in range(start, end):
            kind = self.layer_type(i)
            if self.num_experts and self.mlp_kind(i) == "dense":
                kind += "+dense"
            period.append(kind)
            if kind.startswith("full"):
                if runs and runs[-1][0] == tuple(period):
                    runs[-1][1] += 1
                else:
                    runs.append([tuple(period), 1])
                period = []
        return tuple((sig, n) for sig, n in runs)

    def _sublayer_runs(self, start: int, end: int):
        """`period_runs` of a `one_sublayer` span: the list of kinds as runs
        of a REPEATED unit. Like periods that follow each other are one run
        whose unit is the period (EMEMEM*EMEMEM* is (E, M, E, M, E, M, *) x
        2: the pattern's own repeat, four of the published model's seven
        periods). A period that stands alone (the published first period of
        6 layers, the last of 9, the tail with no full layer) is factored
        greedily from the left into a pair of kinds that comes again at
        once ((moe, mamba) x 3), else one layer ((full,) x 1): a scan's
        body is the unit, so such a period traces three layer bodies and
        not nine."""
        periods, period = [], []
        for i in range(start, end):
            period.append(self.layer_type(i))
            if period[-1] == "full":
                periods.append(tuple(period))
                period = []
        if period:
            periods.append(tuple(period))
        runs, i = [], 0
        while i < len(periods):
            n = 1
            while i + n < len(periods) and periods[i + n] == periods[i]:
                n += 1
            if n > 1:
                runs.append((periods[i], n))
            else:
                runs.extend(_repeats(periods[i]))
            i += n
        return tuple(runs)

    @property
    def experts_held(self) -> tuple[int, int]:
        return self.moe_held or (0, self.num_experts)

    def mlp_kind(self, layer_idx: int) -> str:
        """"dense" or "sparse": a property of the LAYER (absolute index)."""
        if self.num_experts and layer_idx >= self.first_dense_layers:
            return "sparse"
        return "dense"

    def window_for_layer(self, layer_idx: int) -> int:
        """The attention window of the layer at this ABSOLUTE index, 0 for
        none: a query sees its last `sliding_window` keys, itself included,
        in a "sliding" layer. It masks and lets kernels skip what lies below
        it; the arena still holds every layer's pages at full length."""
        return (
            self.sliding_window
            if self.layer_type(layer_idx) == "sliding"
            else 0
        )

    @property
    def flash_window(self) -> int:
        """The STATIC window a prefill chunk's flash kernel takes in a
        family whose window layers stand among full ones in one scan
        (runtime/layer_body.py picks the branch by the layer's traced
        window); 0 where every layer of the family is of one kind, and such
        a span keeps the chunk path it had."""
        if (
            self.sliding_window
            and {"sliding", "full"} <= set(self.layer_types)
            and self.mamba is None  # its own step (runtime/sambay.py)
        ):
            return self.sliding_window
        return 0

    @property
    def gqa_groups(self) -> int:
        return self.num_attention_heads // self.num_key_value_heads

    def layer_type(self, layer_idx: int) -> str:
        if not self.layer_types:
            return "full"
        return self.layer_types[layer_idx % len(self.layer_types)]

    # ------------------------------------------------ per-layer geometry
    @property
    def heterogeneous(self) -> bool:
        """Layers differ in attention geometry (head_dim / kv heads)."""
        return bool(
            (self.global_head_dim and self.global_head_dim != self.head_dim)
            or (
                self.num_global_key_value_heads
                and self.num_global_key_value_heads
                != self.num_key_value_heads
            )
        )

    def head_dim_for_layer(self, layer_idx: int) -> int:
        if self.layer_type(layer_idx) == "full" and self.global_head_dim:
            return self.global_head_dim
        return self.head_dim

    def kv_heads_for_layer(self, layer_idx: int) -> int:
        if (
            self.layer_type(layer_idx) == "full"
            and self.num_global_key_value_heads
        ):
            return self.num_global_key_value_heads
        return self.num_key_value_heads

    def theta_for_layer(self, layer_idx: int) -> float:
        """Sliding layers may use a local rope base (Gemma3/4 style)."""
        if self.layer_type(layer_idx) == "sliding" and self.rope_local_theta:
            return self.rope_local_theta
        return self.rope_theta

    def spec_for_layer(self, layer_idx: int) -> "ModelSpec":
        """A uniform ModelSpec describing exactly this layer (static, so
        per-layer variants are jit cache keys like the base spec)."""
        full = self.layer_type(layer_idx) == "full"
        return dataclasses.replace(
            self,
            head_dim=self.head_dim_for_layer(layer_idx),
            num_key_value_heads=self.kv_heads_for_layer(layer_idx),
            rope_theta=self.theta_for_layer(layer_idx),
            k_eq_v=self.k_eq_v_full and full,
            global_head_dim=0,
            num_global_key_value_heads=0,
        )

    @classmethod
    def from_hf_config(cls, config: Any) -> "ModelSpec":
        """Build from a transformers PretrainedConfig (duck-typed)."""
        from bloombee_tpu.models.auto import spec_from_hf_config

        return spec_from_hf_config(config)
