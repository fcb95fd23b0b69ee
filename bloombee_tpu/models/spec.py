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
    """A gated-DeltaNet linear-attention mixer IN PLACE of attention in the
    layers of kind "linear" (qwen3_next: three of every four). Nested and
    hashable like `SsmSpec`. Per sequence and linear layer the mixer keeps
    ONE matrix a value head, S [value_heads, key_dim, value_dim], and the
    last `conv - 1` rows of the convolution's input (channels q | k | v);
    both live in the state arena, which then has a row a LINEAR layer and
    none for the others (`ModelSpec.cache_rows`). Key head g serves value
    heads g * r .. g * r + r - 1, r = value_heads // key_heads."""

    key_heads: int
    value_heads: int
    key_dim: int  # per head
    value_dim: int  # per head
    conv: int  # depthwise causal convolution width
    chunk: int = 64  # block length of the chunk form (triangular inside)

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
        """in_proj's output as stored: q | k | v | z."""
        return self.conv_dim + self.d_value

    @property
    def state_shape(self) -> tuple[int, ...]:
        return (self.value_heads, self.key_dim, self.value_dim)

    @property
    def tail_shape(self) -> tuple[int, int]:
        return (self.conv - 1, self.conv_dim)


@dataclasses.dataclass(frozen=True)
class MlaSpec:
    """Multi-head latent attention (deepseek_v2): queries and keys/values
    go through low-rank projections with their own RMSNorms, and the cache
    keeps per token and layer ONE latent of `kv_rank` values (after its
    norm) and ONE rotary key of `rope_dim` values shared by all heads (after
    rotary) instead of per-head K and V. Nested and hashable like `SsmSpec`;
    it declares the page payload the arena has to hold (`page_payload`)."""

    q_rank: int  # q_lora_rank
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
    # Gemma-style sliding-window layers: pattern of layer types, e.g.
    # ("sliding", "sliding", "full", ...); empty = all full attention.
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
    mlp_type: str = "silu"  # "silu" | "gelu" | "gelu_tanh_gated"
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
    # a gated-DeltaNet mixer in the layers `layer_types` calls "linear"
    # (qwen3_next); None = every layer attends
    gdn: GdnSpec | None = None
    # rotary on the first `rotary_dim` of a head's dims only (0 = all)
    rotary_dim: int = 0
    # q_proj makes a query AND an output gate a head: the attention output
    # is multiplied by sigmoid(gate) before o_proj (stored split at load:
    # `q_proj` the query rows, `q_gate_proj` the gate rows)
    attn_gate: bool = False

    @property
    def recurrent(self):
        """The descriptor of the family's recurrent state (`SsmSpec` |
        `GdnSpec`: both give `state_shape` and `tail_shape`), None without
        one. What refuses to cut, copy or park a cache asks this."""
        return self.ssm if self.ssm is not None else self.gdn

    def cache_rows(self, start: int, end: int) -> tuple[tuple[str, int], ...]:
        """For each layer of the span [start, end): which arena it uses
        ("state" | "kv") and its row there, the layer's index AMONG ITS KIND
        in the span. Only a family whose kinds differ in their cache
        (`gdn`) has arenas of fewer rows than layers."""
        rows, n = [], {"state": 0, "kv": 0}
        for i in range(start, end):
            arena = (
                "state" if self.gdn is not None
                and self.layer_type(i) == "linear" else "kv"
            )
            rows.append((arena, n[arena]))
            n[arena] += 1
        return tuple(rows)

    def arena_layers(self, start: int, end: int) -> tuple[int, int]:
        """(rows of the K/V arena, rows of the state arena) of a span: its
        layer count each, except where the kinds differ in their cache."""
        n = end - start
        if self.gdn is None:
            return n, (n if self.ssm is not None else 0)
        kinds = [arena for arena, _ in self.cache_rows(start, end)]
        return kinds.count("kv"), kinds.count("state")

    def span_unsupported(self, start: int, end: int) -> str | None:
        """Why this family cannot serve the span [start, end); None when it
        can. A periodic pattern of layer kinds is scanned period by period
        (runtime/step.py `_scan_periods`), so a span holds whole periods
        from a period's first layer."""
        if self.gdn is None:
            return None
        per = len(self.layer_types)
        if start % per or (end - start) % per or end <= start:
            return (
                f"a {self.family} span must hold whole periods of "
                f"{per} layers {self.layer_types} from a period's first "
                f"layer (got [{start}, {end})): the step scans periods, and "
                "each kind's stack and arena have one row a period's layer"
            )
        return None

    @property
    def experts_held(self) -> tuple[int, int]:
        return self.moe_held or (0, self.num_experts)

    def mlp_kind(self, layer_idx: int) -> str:
        """"dense" or "sparse": a property of the LAYER (absolute index)."""
        if self.num_experts and layer_idx >= self.first_dense_layers:
            return "sparse"
        return "dense"

    def window_for_layer(self, layer_idx: int) -> int:
        return (
            self.sliding_window
            if self.layer_type(layer_idx) == "sliding"
            else 0
        )

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
