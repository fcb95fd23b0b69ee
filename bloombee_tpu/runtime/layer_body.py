"""Family-generic transformer layer body for the paged span step.

One implementation covers every supported family via ModelSpec switches
(all resolved at trace time — the compiled program contains no branches):

- llama / qwen3 / mixtral: RMSNorm, rotary, GQA, gated-SiLU or MoE MLP,
  optional per-head q/k norm (qwen3)
- gemma2-style: sandwich norms, gated tanh-GELU MLP, attention logit
  soft-capping, alternating sliding-window layers (per-layer window rides
  the scan)
- bloom: LayerNorm(+bias), ALiBi instead of rotary, plain 4h GELU MLP,
  biased projections
- falcon: LayerNorm, rotary, MQA/GQA, parallel attention+MLP residual

Replaces the reference's per-family Wrapped*Block zoo
(/root/reference/src/bloombee/models/*/block.py) — there the per-family code
wraps HF torch modules; here the differences are data (spec fields + param
keys), so every family runs through the same scan/paged-attention machinery.

Every part of a layer sits in a `jax.named_scope` (HLO metadata only: no op,
shape or donation changes), so a device trace can say whose an op is:
`norm`, `attn_proj` (q/k/v/o projections, QK-norm, rotary), `arena_write`,
`arena_gather`, `attention` (the Pallas call or the XLA attend), `mlp` or
`moe_router` + `moe_experts` (ops/moe.py). A move op of a step that carries
none of them is nothing a layer asked for, and that absence is the reading
(`scan_slab_move_share`).

A family with a state-space mixer (`spec.ssm`, falcon_h1) runs it beside
attention on the same normed input, under three more scopes: `ssm_proj`
(in_proj, gate, grouped norm, out_proj), `ssm_scan` (convolution, dt, the
recurrence: one step a decode row, the chunk form for a prefill chunk) and
`state_io` (a sequence's slot read out of and written into the state arena).
The mixer sees a step as flat rows plus `SsmRows`, which says which rows are
whose: a packed decode step, a solo chunk and the fused ragged pack are one
code path. Rows past a sequence's real count (bucket tails, padding rows) get
dt = 0, which neither decays nor feeds the state, and are left out of the
convolution's new tail.

A family whose layer kinds differ (`spec.gdn`: qwen3_next, kimi_linear) has
LINEAR layers with a delta-rule mixer in attention's place (`_gdn_layer`:
the scopes `gdn_proj` (in_proj, gated norm, out_proj), `gdn_conv`,
`gdn_rule` (one rule step a decode row, the triangular chunk form for a
prefill chunk) and `state_io`; `kda_proj`, `kda_conv`, `kda_rule` where the
decay is a vector a key channel: `GdnSpec.scope`), told from its FULL layers
by the keys their params hold. qwen3_next's full layers run the generic body
with a gated query projection (`attn_gate`), rotary on the first
`rotary_dim` dims and `1 + w` norms; a fused pack of theirs is attended
sequence by sequence (`_attend_by_rows`). kimi_linear's full layers are
latent attention without positions (`_mla_layer`, `MlaSpec.rope` False).

A family whose layers are ONE sublayer each (`spec.one_sublayer`:
nemotron_h) runs `_sublayer` for a mixer layer (`_ssm_mixer` alone, the
scopes above) and for an expert layer (`_mlp` alone: `moe_router`,
`moe_shared`, `moe_experts`), and the generic body without rotary
(`spec.rope` False) and without an MLP for an attention layer; a layer's
kind is read from the keys its params hold.

A layer never owns its K/V slab as an array: the span step hands it the
whole arena viewed flat plus slot and page ids already offset to the layer
(runtime/step.py `_scan_layers`); runtime/hetero.py hands it a per-layer
slab and plain ids. To the code below both are "a slab and ids into it".
"""

from __future__ import annotations

import contextlib
import threading
from typing import NamedTuple

import jax
import jax.numpy as jnp
from jax import lax

from bloombee_tpu.kv.arena import arena_write, gather_pages, heads_view
from bloombee_tpu.models.layout import LANES, in_axis_of, project
from bloombee_tpu.models.spec import ModelSpec
from bloombee_tpu.models.wquant import maybe_dequantize
from bloombee_tpu.ops import apply_rotary, rms_norm, silu_mlp
from bloombee_tpu.ops.rotary import _rotate_half
from bloombee_tpu.ops.alibi import alibi_slopes
from bloombee_tpu.ops.attention import NEG_INF, repeat_kv
from bloombee_tpu.ops.moe import moe_mlp, relu2
from bloombee_tpu.ops.norms import layer_norm
from bloombee_tpu.ops.pallas.flash_attention import BLOCK_K, flash_takes
from bloombee_tpu.ops.linear_attention import (
    gdn_sequence,
    gdn_step,
    kda_sequence,
    kda_step,
    l2_normalize,
)
from bloombee_tpu.ops.ssm import conv_taps, ssd_sequence, ssm_step
from bloombee_tpu.utils import env


def _norm(x, params, key, spec):
    with jax.named_scope("norm"):
        if spec.norm_type == "ln":
            return layer_norm(
                x, params[key], params.get(f"{key}_bias"), spec.rms_norm_eps
            )
        return rms_norm(
            x, params[key], spec.rms_norm_eps, spec.norm_type == "rms1p"
        )


def _proj(x, params, key, lora=None):
    # quantized projections dequantize here; XLA fuses the convert+scale
    # into the matmul's operand read (no dense copy lands in HBM). q/k/v
    # are stored output-major, as the step reads them (models/layout.py)
    w = maybe_dequantize(params[key], x.dtype, in_axis_of(key))
    y = project(x, w, key)
    b = params.get(f"{key.removesuffix('_proj')}_bias")
    if b is not None:
        y = y + b
    if lora is not None and key in lora:
        # per-request LoRA (reference utils/peft.py LoraLinear forward):
        # y += (x A) B with the alpha/r scaling folded into B at load.
        # Factors stay unmerged so one base weight serves every adapter.
        f = lora[key]
        y = y + (x @ f["a"].astype(x.dtype)) @ f["b"].astype(x.dtype)
    return y


class _Reach(threading.local):
    sink: list | None = None


_reach = _Reach()


@contextlib.contextmanager
def collecting_reach():
    """The list a sparse layer traced inside this block appends its
    `ops/moe.py held_reach` to (a server told which experts it holds:
    `spec.moe_held`). The span step's scan opens it around one layer's body
    and carries what it finds out as the scan's ys: the MLP sits three calls
    under the layer body, and no signature between them changes for it."""
    prev, _reach.sink = _reach.sink, []
    try:
        yield _reach.sink
    finally:
        _reach.sink = prev


def _mlp(x, params, spec, lora=None):
    # the MLP's kind is the LAYER's: a family whose first layers are dense
    # (deepseek_v2) hands such a layer no router
    if spec.num_experts and ("router" in params or "router_t" in params):
        with jax.named_scope("moe_experts"):
            # an ungated expert (`mlp_type` "relu2") holds no gate leaf
            gate, up, down = (
                maybe_dequantize(params[k], x.dtype) if k in params else None
                for k in ("experts_gate", "experts_up", "experts_down")
            )
        out = moe_mlp(
            x,
            # the router's weight [D, E], or (deepseek_v2: 160 columns are
            # no whole lanes) output-major [E, D] as the checkpoint has it
            params.get("router"),
            gate,
            up,
            down,
            spec.num_experts_per_tok,
            pre_softmax=spec.moe_pre_softmax,
            norm_topk=spec.moe_norm_topk,
            # the step lifted the stacks out of the scan (runtime/step.py
            # `lift_expert_stacks`): a kernel form, by index
            expert_base=params.get("expert_base"),
            interpret=env.get("BBTPU_PAGED_INTERPRET"),
            groups=spec.moe_groups,
            topk_groups=spec.moe_topk_groups,
            route_scale=spec.moe_route_scale,
            held=spec.moe_held,
            router_logits=(
                _router_logits_f32(x, params["router_t"])
                if "router_t" in params else None
            ),
            reach_out=_reach.sink,
            sigmoid=spec.moe_router == "sigmoid",
            # afmoe: a buffer beside the router that corrects its CHOICE
            router_bias=params.get("expert_bias"),
            activation=spec.mlp_type if gate is None else "silu",
        )
        if spec.moe_shared_intermediate:
            with jax.named_scope("moe_shared"):
                if gate is None:
                    # the shared expert has the routed ones' form
                    shared = relu2(x @ maybe_dequantize(
                        params["shared_up"], x.dtype
                    )) @ maybe_dequantize(params["shared_down"], x.dtype)
                else:
                    shared = silu_mlp(
                        x, *(
                            maybe_dequantize(params[k], x.dtype) for k in
                            ("shared_gate", "shared_up", "shared_down")
                        ),
                    )
                if spec.moe_shared_gate:
                    # qwen3_next: ONE shared expert, scaled a row by
                    # sigmoid(x @ w); every chip computes it alike
                    shared = shared * jax.nn.sigmoid(jnp.einsum(
                        "...d,d->...", x, params["shared_gate_w"],
                        preferred_element_type=jnp.float32,
                    ))[..., None].astype(shared.dtype)
                out = out + shared.astype(out.dtype)
        return out
    with jax.named_scope("mlp"):
        return _dense_mlp(x, params, spec, lora)


def _router_logits_f32(x, router_t):
    """The router's product in float32 at full precision, as the published
    DeepSeek-V2 gate computes it (`F.linear(x.float(), w.float())`): a score
    rounded to bfloat16 flips a near-tie, and with weights times 16 a
    flipped expert is no rounding. `router_t` is [E, D], output-major."""
    with jax.named_scope("moe_router"):
        return jnp.einsum(
            "...d,ed->...e", x.astype(jnp.float32),
            router_t.astype(jnp.float32), precision=lax.Precision.HIGHEST,
        )


def _dense_mlp(x, params, spec, lora=None):
    mlp_lora = lora is not None and any(
        k in lora for k in ("gate_proj", "up_proj", "down_proj")
    )
    if spec.mlp_type == "silu":
        # falcon_h1 scales the gate before the activation and the output
        # after the down projection (HF FalconH1MLP); every other family
        # has (1, 1) and traces no multiply
        gate_mult, out_mult = (
            None if m == 1.0 else m for m in spec.mlp_multipliers
        )
        if mlp_lora:
            # lora-aware composition (the fused silu_mlp takes raw matrices,
            # so the adapterized path spells it out)
            g = _proj(x, params, "gate_proj", lora)
            if gate_mult is not None:
                g = g * gate_mult
            u = _proj(x, params, "up_proj", lora)
            y = _proj(jax.nn.silu(g) * u, params, "down_proj", lora)
            return y if out_mult is None else y * out_mult
        return silu_mlp(
            x,
            maybe_dequantize(params["gate_proj"], x.dtype),
            maybe_dequantize(params["up_proj"], x.dtype),
            maybe_dequantize(params["down_proj"], x.dtype),
            gate_mult, out_mult,
        )
    if spec.mlp_type == "gelu_tanh_gated":
        g = _proj(x, params, "gate_proj", lora)
        u = _proj(x, params, "up_proj", lora)
        return _proj(jax.nn.gelu(g, approximate=True) * u, params,
                     "down_proj", lora)
    # plain 4h GELU: "gelu" = exact/erf (falcon), "gelu_tanh" = tanh (bloom)
    h = jax.nn.gelu(
        _proj(x, params, "up_proj", lora), approximate=spec.mlp_type != "gelu"
    )
    return _proj(h, params, "down_proj", lora)


class SsmRows(NamedTuple):
    """Whose the flat rows of a step are, for the mixer. Built once a step
    (runtime/step.py), the same for every layer."""

    q_seq: jax.Array  # [R] owning sequence of a row (>= S: a padding row)
    row0: jax.Array  # [S] a sequence's first row
    nt: jax.Array  # [S] a sequence's REAL rows in this step (0: padding)
    fresh: jax.Array  # [S] bool: the sequence stands at position 0, so its
    # state is empty whatever the slot holds (a new session, a replay)
    chunk_seqs: jax.Array  # [n] sequences that take the chunk form
    window: int  # static: rows a chunk-form sequence may span
    step_form: bool  # static: sequences with nt == 1 take one recurrence step


def packed_rows(b: int, t: int, q_positions, real, t_real) -> SsmRows:
    """Whose the flat rows of a packed [B, T] step are: sequence i owns rows
    i*T..i*T+T-1, of which `t_real` are real (a bucket's tail is not); a
    padding row of the batch bucket (`real` [B] false) has none."""
    seqs = jnp.arange(b, dtype=jnp.int32)
    n = jnp.asarray(t if t_real is None else t_real, jnp.int32)
    return SsmRows(
        q_seq=jnp.repeat(seqs, t),
        row0=seqs * t,
        nt=jnp.where(real, n, 0),
        fresh=q_positions[:, 0] == 0,
        chunk_seqs=seqs if t > 1 else seqs[:0],
        window=t,
        step_form=t == 1,
    )


def packed_ssm_rows(b: int, t: int, q_positions, state_slots, num_slots,
                    t_real) -> SsmRows:
    """`packed_rows` for a family with recurrent state: a padding row of
    the batch bucket is one whose state slot is out of range."""
    real = (state_slots >= 0) & (state_slots < num_slots)
    return packed_rows(b, t, q_positions, real, t_real)


def _ssm_mixer(spec: ModelSpec, params: dict, x, state: dict, slots,
               rows: SsmRows):
    """The state-space mixer on flat rows: x [R, D] (the layer's normed
    input) -> (m [R, D] before `out_multiplier`, the state arena).
    `state` is the flat state arena, `slots` [S] each sequence's row of it
    (out of range: read clamped, write dropped)."""
    ssm = spec.ssm
    r = x.shape[0]
    s = rows.row0.shape[0]
    h, p, g, n = ssm.heads, ssm.head_dim, ssm.groups, ssm.state
    f32 = jnp.float32
    with jax.named_scope("ssm_proj"):
        mup = jnp.concatenate([
            jnp.full((width,), mult, x.dtype) for width, mult in zip(
                (ssm.d_ssm, ssm.d_ssm, g * n, g * n, h), ssm.multipliers)
        ])
        # the stored in_proj is zero-padded to whole lanes (models/layout.py).
        # The product is taken at the STORED width and cut after the barrier:
        # without it the compiler moves the cut onto the weight, and a
        # [D, proj_dim] window of the layer's slice is no tile-aligned view,
        # so every layer copies its in_proj out of the stack first (96 MB,
        # 1.2 ms of a 13.2 ms chunk at 34B widths)
        zxbcdt = _proj(x * ssm.in_multiplier, params, "ssm_in_proj")
        zxbcdt = lax.optimization_barrier(zxbcdt)[:, : ssm.proj_dim] * mup
        z = zxbcdt[:, : ssm.d_ssm]
        xbc = zxbcdt[:, ssm.d_ssm : ssm.d_ssm + ssm.conv_dim]
        dt = zxbcdt[:, ssm.d_ssm + ssm.conv_dim :]
    with jax.named_scope("state_io"):
        tails = state["conv"].at[slots].get(mode="clip")
        tails = jnp.where(rows.fresh[:, None, None], 0, tails)
    with jax.named_scope("ssm_scan"):
        taps, new_tails = conv_taps(xbc, tails, rows.q_seq, rows.row0, rows.nt)
        conv = jnp.einsum(
            "rkc,kc->rc", taps.astype(f32), params["ssm_conv_w"].astype(f32)
        ) + params["ssm_conv_b"].astype(f32)
        conv = jax.nn.silu(conv)
        xs = conv[:, : ssm.d_ssm].reshape(r, h, p)
        bm = conv[:, ssm.d_ssm : ssm.d_ssm + g * n].reshape(r, g, n)
        cm = conv[:, ssm.d_ssm + g * n :].reshape(r, g, n)
        dt = jax.nn.softplus(dt.astype(f32) + params["ssm_dt_bias"])
        a = -jnp.exp(params["ssm_a_log"])
        d_skip = params["ssm_d"]
    y = jnp.zeros((r, h, p), f32)
    oob = state["ssm"].shape[0]
    writes = []  # (slots [k], states [k, H, P, N])
    if rows.step_form:
        one = rows.nt == 1
        at = jnp.clip(rows.row0, 0, r - 1)
        with jax.named_scope("state_io"):
            s0 = state["ssm"].at[slots].get(mode="clip")
            s0 = jnp.where(rows.fresh[:, None, None, None], 0.0, s0)
        with jax.named_scope("ssm_scan"):
            y_s, s_new = ssm_step(
                xs[at], jnp.where(one[:, None], dt[at], 0.0), a, bm[at],
                cm[at], d_skip, s0,
            )
            y = y.at[jnp.where(one, rows.row0, r)].set(y_s, mode="drop")
        writes.append((jnp.where(one, slots, oob), s_new))
    w = rows.window
    whole = s == 1 and w == r  # static: one sequence owns every row
    for i in range(rows.chunk_seqs.shape[0]):
        c = rows.chunk_seqs[i]
        r0, n_c, slot_c = rows.row0[c], rows.nt[c], slots[c]
        with jax.named_scope("state_io"):
            s0 = state["ssm"].at[slot_c].get(mode="clip")
            s0 = jnp.where(rows.fresh[c], 0.0, s0)
        with jax.named_scope("ssm_scan"):
            def take(z_rows):
                if whole:
                    return z_rows
                pad = jnp.zeros((w, *z_rows.shape[1:]), z_rows.dtype)
                return lax.dynamic_slice_in_dim(
                    jnp.concatenate([z_rows, pad]), r0, w
                )

            valid = jnp.arange(w, dtype=jnp.int32) < n_c
            y_c, s_c = ssd_sequence(
                take(xs), jnp.where(valid[:, None], take(dt), 0.0), a,
                take(bm), take(cm), d_skip, s0, ssm.chunk,
            )
            y_c = jnp.where(valid[:, None, None], y_c, 0.0)
            if whole:
                y = y + y_c
            else:
                y = y + lax.dynamic_update_slice_in_dim(
                    jnp.zeros((r + w, h, p), f32), y_c, r0, 0
                )[:r]
        writes.append((slot_c[None], s_c[None]))
    with jax.named_scope("state_io"):
        ssm_arena = state["ssm"]
        for slots_w, s_w in writes:
            ssm_arena = ssm_arena.at[slots_w].set(s_w, mode="drop")
        state = {
            "ssm": ssm_arena,
            "conv": state["conv"].at[slots].set(
                new_tails.astype(state["conv"].dtype), mode="drop"
            ),
        }
    with jax.named_scope("ssm_proj"):
        # the gate BEFORE the grouped norm (mamba_norm_before_gate false)
        y = y.reshape(r, ssm.d_ssm) * jax.nn.silu(z.astype(f32))
        y = y.reshape(r, g, -1)
        y = y * lax.rsqrt(
            jnp.mean(y * y, axis=-1, keepdims=True) + spec.rms_norm_eps
        )
        y = params["ssm_norm"] * y.reshape(r, ssm.d_ssm).astype(x.dtype)
        return _proj(y, params, "ssm_out_proj"), state


def _mix(spec, params, x, ssm):
    """The mixer's share of the layer's residual update and the new state
    arena: (None, None) for a family without one. `ssm` is (state arena,
    this layer's slots, SsmRows) as the step hands them down."""
    if ssm is None:
        return None, None
    state, slots, rows = ssm
    m, state = _ssm_mixer(
        spec, params, x.reshape(-1, x.shape[-1]), state, slots, rows
    )
    return m.reshape(x.shape) * spec.ssm.out_multiplier, state


def _window_rows(z, r0, w: int):
    """Rows r0 .. r0 + w of `z` [R, ...] (zeros past the end): the rows a
    pack's multi-row sequence may span."""
    pad = jnp.zeros((w, *z.shape[1:]), z.dtype)
    return lax.dynamic_slice_in_dim(jnp.concatenate([z, pad]), r0, w)


def _place_rows(rows_c, r0, r: int):
    """The inverse: `rows_c` [w, ...] at rows r0 .. of an [r, ...] of zeros."""
    w = rows_c.shape[0]
    return lax.dynamic_update_slice_in_dim(
        jnp.zeros((r + w, *rows_c.shape[1:]), rows_c.dtype), rows_c, r0, 0
    )[:r]


def _gdn_mixer(spec: ModelSpec, params: dict, x, state: dict, slots,
               rows: SsmRows):
    """The delta-rule mixer (models/spec.py GdnSpec) on flat rows: x
    [R, D] (the layer's normed input) -> (m [R, D], the state arena).
    `state` is the flat state arena, `slots` [S] each sequence's row of it
    for THIS layer (out of range: read clamped, write dropped). A sequence
    with one row takes one rule step, one with more the chunk form
    (ops/linear_attention.py), exactly as `_ssm_mixer` tells them apart.
    Rows past a sequence's real count get beta = 0 and g = 0, which neither
    decay nor feed S, and are left out of the convolution's new tail.

    Gated DeltaNet (qwen3_next) and Kimi delta attention (kimi_linear) are
    this one mixer: the convolution, the state's reads and writes and the
    row bookkeeping are shared; what the descriptor tells apart is where
    the decay and the output gate come from (`_gdn_inputs`), the rule (a
    scalar or a vector decay) and the gate's activation, and the scopes'
    prefix (`GdnSpec.scope`)."""
    gdn = spec.gdn
    r = x.shape[0]
    s = rows.row0.shape[0]
    hk, hv, dk, dv = gdn.key_heads, gdn.value_heads, gdn.key_dim, gdn.value_dim
    rep = hv // hk
    f32 = jnp.float32
    scope = gdn.scope
    rule_step, rule_sequence = (
        (kda_step, kda_sequence) if gdn.channel_decay
        else (gdn_step, gdn_sequence)
    )
    with jax.named_scope(f"{scope}_proj"):
        qkv, a, b, z = _gdn_inputs(gdn, params, x)
    with jax.named_scope("state_io"):
        tails = state["conv"].at[slots].get(mode="clip")
        tails = jnp.where(rows.fresh[:, None, None], 0, tails)
    with jax.named_scope(f"{scope}_conv"):
        taps, new_tails = conv_taps(qkv, tails, rows.q_seq, rows.row0, rows.nt)
        conv = jax.nn.silu(jnp.einsum(
            "rkc,kc->rc", taps.astype(f32), params["gdn_conv_w"].astype(f32)
        ))
    with jax.named_scope(f"{scope}_rule"):
        q = l2_normalize(conv[:, : gdn.d_key].reshape(r, hk, dk)) * dk**-0.5
        k = l2_normalize(
            conv[:, gdn.d_key : 2 * gdn.d_key].reshape(r, hk, dk)
        )
        # key head g serves value heads g * rep .. g * rep + rep - 1
        if rep > 1:
            q, k = jnp.repeat(q, rep, axis=1), jnp.repeat(k, rep, axis=1)
        v = conv[:, 2 * gdn.d_key :].reshape(r, hv, dv)
        beta = jax.nn.sigmoid(b)
        # [R, Hv], or with a decay a key channel [R, Hv, dk]
        dt = jax.nn.softplus(a + params["gdn_dt_bias"].reshape(a.shape[1:]))
        shape = (hv,) + (1,) * (dt.ndim - 2)
        g = -jnp.exp(params["gdn_a_log"]).reshape(shape) * dt

    def keep(mask):  # a row mask [n] against g [n, Hv] or [n, Hv, dk]
        return mask[(slice(None),) + (None,) * (g.ndim - 1)]

    o = jnp.zeros((r, hv, dv), f32)
    oob = state["ssm"].shape[0]
    writes = []  # (slots [n], states [n, Hv, dk, dv])
    if rows.step_form:
        one = rows.nt == 1
        at = jnp.clip(rows.row0, 0, r - 1)
        with jax.named_scope("state_io"):
            s0 = state["ssm"].at[slots].get(mode="clip")
            s0 = jnp.where(rows.fresh[:, None, None, None], 0.0, s0)
        with jax.named_scope(f"{scope}_rule"):
            o_s, s_new = rule_step(
                q[at], k[at], v[at], jnp.where(keep(one), g[at], 0.0),
                jnp.where(one[:, None], beta[at], 0.0), s0,
            )
            o = o.at[jnp.where(one, rows.row0, r)].set(o_s, mode="drop")
        writes.append((jnp.where(one, slots, oob), s_new))
    w = rows.window
    whole = s == 1 and w == r  # static: one sequence owns every row
    for i in range(rows.chunk_seqs.shape[0]):
        c = rows.chunk_seqs[i]
        r0, n_c, slot_c = rows.row0[c], rows.nt[c], slots[c]
        with jax.named_scope("state_io"):
            s0 = state["ssm"].at[slot_c].get(mode="clip")
            s0 = jnp.where(rows.fresh[c], 0.0, s0)  # gdn
        with jax.named_scope(f"{scope}_rule"):
            def take(z_rows):
                return z_rows if whole else _window_rows(z_rows, r0, w)

            real = jnp.arange(w, dtype=jnp.int32) < n_c  # no bucket tail
            o_c, s_c = rule_sequence(
                take(q), take(k), take(v),
                jnp.where(keep(real), take(g), 0.0),
                jnp.where(real[:, None], take(beta), 0.0), s0, gdn.chunk,
            )
            o_c = jnp.where(real[:, None, None], o_c, 0.0)
            o = o + (o_c if whole else _place_rows(o_c, r0, r))
        writes.append((slot_c[None], s_c[None]))
    with jax.named_scope("state_io"):
        rule_arena = state["ssm"]
        for slots_w, s_w in writes:
            rule_arena = rule_arena.at[slots_w].set(s_w, mode="drop")
        state = {
            "ssm": rule_arena,
            "conv": state["conv"].at[slots].set(
                new_tails.astype(state["conv"].dtype), mode="drop"
            ),
        }
    with jax.named_scope(f"{scope}_proj"):
        # the gated norm: per value head, PLAIN weight, then silu(z), or
        # where the gate has projections of its own sigmoid(gate)
        y = o * lax.rsqrt(
            jnp.mean(o * o, axis=-1, keepdims=True) + spec.rms_norm_eps
        )
        y = params["gdn_norm"] * y.astype(x.dtype)
        gate = jax.nn.sigmoid if gdn.gate_rank else jax.nn.silu
        y = y * gate(z.reshape(r, hv, dv).astype(f32)).astype(x.dtype)
        return _proj(y.reshape(r, gdn.d_value), params, "gdn_out_proj"), state


def _gdn_inputs(gdn, params: dict, x):
    """The delta-rule mixer's projections of its normed input x [R, D]:
    (q | k | v before the convolution [R, conv_dim], the decay's input
    before its bias and softplus (float32 [R, Hv], or a key channel's
    [R, Hv, dk]), beta before its sigmoid (float32 [R, Hv]), the output
    gate before its activation [R, d_value])."""
    f32 = jnp.float32
    hv = gdn.value_heads
    if gdn.gate_rank:
        # Kimi delta attention: in_proj is q | k | v; ONE product makes the
        # three narrow ones, f_a | g_a | b, each in lanes of its own
        # (models/kimi_linear.py); the decay and the gate come up through
        # their second halves
        qkv, low = lax.optimization_barrier((
            _proj(x, params, "gdn_in_proj"), _proj(x, params, "gdn_low_proj"),
        ))
        rank = gdn.gate_rank
        a = _proj(low[:, :rank], params, "gdn_f_b_proj").astype(f32)
        z = _proj(low[:, LANES : LANES + rank], params, "gdn_g_b_proj")
        b = low[:, 2 * LANES : 2 * LANES + hv].astype(f32)
        return qkv, a.reshape(-1, hv, gdn.key_dim), b, z
    # q | k | v | z, every cut a whole number of lanes (the loader
    # regrouped in_proj_qkvz: models/qwen3_next.py)
    # the products are taken at the STORED widths and cut after the
    # barrier: without it the compiler moves a cut onto the weight and
    # every layer copies its in_proj out of the stack first (50 MB a
    # layer in a decode step, as Falcon-H1's in_proj did)
    qkvz, ba = lax.optimization_barrier((
        _proj(x, params, "gdn_in_proj"), _proj(x, params, "gdn_ba_proj"),
    ))
    ba = ba.astype(f32)
    lanes = ba.shape[-1] // 2
    return (qkvz[:, : gdn.conv_dim], ba[:, lanes : lanes + hv], ba[:, :hv],
            qkvz[:, gdn.conv_dim :])


def _gdn_layer(spec, hidden, params, k_slab, v_slab, ssm, lora=None):
    """A whole LINEAR layer of a family with gated-DeltaNet layers among
    attention layers, on [B, T, D] (or the ragged [1, R, D]) rows: the
    mixer in attention's place, then the MLP. The K/V slabs pass through
    untouched: this kind of layer has no row in them."""
    state, slots, rows = ssm
    x = _norm(hidden, params, "input_layernorm", spec)
    m, state = _gdn_mixer(
        spec, params, x.reshape(-1, x.shape[-1]), state, slots, rows
    )
    return _finish_layer(
        spec, params, hidden, x, m.reshape(hidden.shape), k_slab, v_slab,
        lora, state,
    )


def _sublayer(spec, hidden, params, k_slab, v_slab, ssm, lora=None):
    """A whole layer of a family whose layers are ONE sublayer each
    (`spec.one_sublayer`: nemotron_h) that is no attention layer, on
    [B, T, D] (or the ragged [1, R, D]) rows: x + f(norm(x)), f the
    state-space mixer alone (a layer that holds `ssm_in_proj`: `ssm` is its
    row of the state arena, which comes back as a fourth value) or the
    expert layer alone (no row in either arena). The K/V slabs pass through
    untouched. The family's attention layers run the generic body, which
    ends after the attention's residual (`_residual_mlp`)."""
    x = _norm(hidden, params, "input_layernorm", spec)
    if "ssm_in_proj" in params:
        mix, state = _mix(spec, params, x, ssm)
        return hidden + mix, k_slab, v_slab, state
    return hidden + _mlp(x, params, spec, lora), k_slab, v_slab


def _mla_attention(spec: ModelSpec, page_size: int, params: dict, x,
                   c_slab, pe_slab, cos, sin, slots, page_table, q_pos,
                   total_lens, rows: SsmRows, kernels: bool, lora=None):
    """Latent attention (models/spec.py MlaSpec) on flat rows: x [R, D] (the
    layer's normed input) -> (attention output [R, D] after o_proj, the two
    slabs). ABSORBED throughout: queries go through W_kvb's key half into
    the latent's space, attend the cached latents and rotary keys, and the
    result comes back through W_kvb's value half; per-head keys and values
    of a context never exist. A sequence with one row (a decode row) streams
    its latent pages out of the arena (ops/pallas/latent_attention.py
    `paged_decode_attention_latent`); one with more (a prefill chunk) runs
    the flash form over its gathered latent rows. The packed decode step,
    the solo chunk and the fused ragged pack are this one code path, told
    apart by `rows`. `cos`/`sin` are [R, rope_dim]."""
    from bloombee_tpu.ops.pallas.latent_attention import (
        latent_attend_dense,
        latent_flash_attention,
        paged_decode_attention_latent,
    )

    mla = spec.mla
    r = x.shape[0]
    h = spec.num_attention_heads
    scale = mla.softmax_scale
    interpret = env.get("BBTPU_PAGED_INTERPRET")
    pe_pad = pe_slab.shape[-1] - mla.rope_dim  # the slab's rows are whole lanes
    kv_b_k, kv_b_v = (
        maybe_dequantize(params[k], x.dtype, in_axis_of(k))
        for k in ("kv_b_k", "kv_b_v")
    )

    def rope(z, cos, sin):
        # the loader stored the rotary rows de-interleaved (evens, then
        # odds: models/deepseek_v2.py), so this is the plain half-rotation.
        # A family without positions here (`mla.rope` False: kimi_linear)
        # keeps the columns as plain score dimensions
        if not mla.rope:
            return z
        return z * cos.astype(z.dtype) + _rotate_half(z) * sin.astype(z.dtype)

    # everything per head is kept HEAD-major [H, R, .]: the head is the
    # batch dimension of both absorb products, so that is how they leave
    # their results, and the flash form takes and gives that layout
    with jax.named_scope("attn_proj"):
        with jax.named_scope("mla_q"):
            # q_rank 0: ONE full-rank projection, the same two keys fed
            # the hidden rows, and no query norm
            c_q = rms_norm(
                _proj(x, params, "q_a_proj", lora), params["q_a_norm"],
                spec.rms_norm_eps,
            ) if mla.q_rank else x
            # the barrier keeps the per-head reshape on the PRODUCT: without
            # it a decode program moves it onto the weight and every layer
            # copies its q_b_rope out of the stack first (25 MB a layer)
            q_nope, q_rope = lax.optimization_barrier((
                _proj(c_q, params, "q_b_nope", lora),
                _proj(c_q, params, "q_b_rope", lora),
            ))
            q_nope = q_nope.reshape(r, h, mla.nope_dim).transpose(1, 0, 2)
            q_pe = rope(
                q_rope.reshape(r, h, mla.rope_dim), cos[:, None], sin[:, None]
            ).transpose(1, 0, 2)
            q_pe = jnp.pad(q_pe, ((0, 0), (0, 0), (0, pe_pad)))
        with jax.named_scope("mla_kv"):
            ckv = _proj(x, params, "kv_a_proj", lora)
            c_kv = rms_norm(
                ckv[:, : mla.kv_rank], params["kv_a_norm"], spec.rms_norm_eps
            )
            k_pe = jnp.pad(
                rope(ckv[:, mla.kv_rank :], cos, sin), ((0, 0), (0, pe_pad))
            )
    with jax.named_scope("arena_write"), jax.named_scope("latent_io"):
        c_slab, pe_slab = arena_write(c_slab, pe_slab, slots, c_kv, k_pe)

    def absorb_q(qn):  # [H, n, nope] -> [H, n, C]
        with jax.named_scope("attn_proj"), jax.named_scope("mla_absorb"):
            return jnp.einsum("hrn,hnc->hrc", qn, kv_b_k)

    def absorb_o(o):  # [H, n, C] -> [n, H, v]
        with jax.named_scope("attn_proj"), jax.named_scope("mla_absorb"):
            return jnp.einsum("hrc,hvc->rhv", o, kv_b_v)

    def gathered(pages):  # [n, NP] -> ([n, S, C], [n, S, R])
        with jax.named_scope("arena_gather"), jax.named_scope("latent_io"):
            return (
                gather_pages(c_slab, pages, page_size).astype(x.dtype),
                gather_pages(pe_slab, pages, page_size).astype(x.dtype),
            )

    heads = jnp.zeros((r, h, mla.v_dim), x.dtype)
    if rows.step_form:
        # the sequences with one row: absorbed on those rows alone
        one = rows.nt == 1
        at = jnp.clip(rows.row0, 0, r - 1)
        lens_one = jnp.where(one, total_lens, 0)
        ql_one = absorb_q(q_nope[:, at])  # [H, S, C]
        qp_one = q_pe[:, at]
        if kernels:
            with jax.named_scope("attention"), jax.named_scope(
                "mla_attention"
            ):
                o = paged_decode_attention_latent(
                    ql_one.transpose(1, 0, 2), qp_one.transpose(1, 0, 2),
                    c_slab, pe_slab, page_table, lens_one,
                    page_size=page_size, scale=scale, interpret=interpret,
                ).transpose(1, 0, 2)
        else:
            c_ctx, pe_ctx = gathered(page_table)
            with jax.named_scope("attention"), jax.named_scope(
                "mla_attention"
            ):
                o = latent_attend_dense(
                    ql_one.transpose(1, 0, 2)[:, :, None],
                    qp_one.transpose(1, 0, 2)[:, :, None], c_ctx, pe_ctx,
                    (lens_one - 1)[:, None], lens_one, scale,
                )[:, :, 0].transpose(1, 0, 2)
        heads = heads.at[jnp.where(one, rows.row0, r)].set(
            absorb_o(o), mode="drop"
        )
    w = rows.window
    whole = rows.row0.shape[0] == 1 and w == r  # one sequence owns every row
    for i in range(rows.chunk_seqs.shape[0]):
        c = rows.chunk_seqs[i]
        r0, n_c = rows.row0[c], rows.nt[c]

        def take(z):  # rows r0 .. r0 + w of [H, R, .]
            if whole:
                return z
            pad = jnp.zeros((h, w, z.shape[-1]), z.dtype)
            return lax.dynamic_slice_in_dim(
                jnp.concatenate([z, pad], axis=1), r0, w, axis=1
            )

        q_lat = absorb_q(take(q_nope))
        c_ctx, pe_ctx = gathered(page_table[c][None])
        start = q_pos[jnp.clip(r0, 0, r - 1)]
        with jax.named_scope("attention"), jax.named_scope("mla_attention"):
            if kernels:
                o_c = latent_flash_attention(
                    q_lat, take(q_pe), c_ctx[0], pe_ctx[0], start,
                    total_lens[c], n_c, scale=scale, interpret=interpret,
                )
            else:
                o_c = latent_attend_dense(
                    q_lat[None], take(q_pe)[None], c_ctx, pe_ctx,
                    (start + jnp.arange(w, dtype=jnp.int32))[None],
                    total_lens[c][None], scale,
                )[0]
        real = (jnp.arange(w, dtype=jnp.int32) < n_c)[:, None, None]
        heads_c = jnp.where(real, absorb_o(o_c.astype(x.dtype)), 0)
        if whole:
            heads = heads + heads_c
        else:
            heads = heads + lax.dynamic_update_slice_in_dim(
                jnp.zeros((r + w, h, mla.v_dim), x.dtype), heads_c, r0, 0
            )[:r]
    with jax.named_scope("attn_proj"):
        out = _proj(heads.reshape(r, h * mla.v_dim), params, "o_proj", lora)
    return out, c_slab, pe_slab


def _mla_layer(spec, page_size, hidden, params, c_slab, pe_slab, cos, sin,
               slots, page_table, q_positions, total_lens, rows, kernels,
               lora=None):
    """A whole layer of a latent-attention family on [B, T, D] (or the
    ragged [1, R, D]) rows."""
    d = hidden.shape[-1]
    x = _norm(hidden, params, "input_layernorm", spec)
    attn, c_slab, pe_slab = _mla_attention(
        spec, page_size, params, x.reshape(-1, d), c_slab, pe_slab,
        cos.reshape(-1, cos.shape[-1]), sin.reshape(-1, sin.shape[-1]),
        slots, page_table, q_positions.reshape(-1), total_lens, rows,
        kernels, lora,
    )
    return _finish_layer(
        spec, params, hidden, x, attn.reshape(hidden.shape), c_slab, pe_slab,
        lora,
    )


def attn_scale(spec: ModelSpec) -> float:
    return (
        spec.attention_multiplier
        if spec.attention_multiplier is not None
        else spec.head_dim**-0.5
    )


def attend_paged(
    spec: ModelSpec,
    q: jax.Array,  # [B, T, H, hd]
    k_ctx: jax.Array,  # [B, S, Hkv, hd]
    v_ctx: jax.Array,
    q_positions: jax.Array,  # [B, T]
    total_lens: jax.Array,  # [B]
    tree_mask: jax.Array | None,
    window,  # traced int32 scalar; 0 = full attention
    attn_topk: int = 0,  # >0: keep only the top-k keys per query (FlexGen
    # Policy.attn_sparsity, pytorch_backend.py:564-638 _sparse_attention_value
    # — there the top-k of past weights plus the newest token; here the
    # equivalent pre-softmax mask, so kept weights renormalize)
) -> jax.Array:
    b, t = q.shape[:2]
    s = k_ctx.shape[1]
    key_pos = jnp.arange(s, dtype=jnp.int32)[None, None, :]  # [1, 1, S]
    q_pos = q_positions[:, :, None]  # [B, T, 1]
    valid = key_pos < total_lens[:, None, None]
    mask = valid & (key_pos <= q_pos)
    mask &= (window <= 0) | (key_pos > (q_pos - window))
    if tree_mask is not None:
        # current step's tokens sit at cache positions total-T..total-1;
        # their mutual visibility comes from the tree mask
        # (reference: backend.py:596-652)
        step_start = (total_lens - t)[:, None, None]
        in_step = (key_pos >= step_start) & (key_pos < total_lens[:, None, None])
        rel = jnp.clip(key_pos - step_start, 0, t - 1)
        tree_on_keys = jnp.take_along_axis(
            tree_mask, jnp.broadcast_to(rel, (b, t, s)), axis=2
        )
        mask = jnp.where(in_step, tree_on_keys & valid, mask)

    n_rep = q.shape[2] // k_ctx.shape[2]
    k_r = repeat_kv(k_ctx, n_rep)
    v_r = repeat_kv(v_ctx, n_rep)
    scale = attn_scale(spec)
    logits = jnp.einsum("bthd,bshd->bhts", q, k_r).astype(jnp.float32) * scale
    if spec.attn_logit_softcap:
        logits = (
            jnp.tanh(logits / spec.attn_logit_softcap) * spec.attn_logit_softcap
        )
    if spec.alibi:
        slopes = jnp.asarray(alibi_slopes(spec.num_attention_heads))
        logits = logits + slopes[None, :, None, None] * key_pos[:, :, None, :].astype(jnp.float32)
    logits = jnp.where(mask[:, None, :, :], logits, NEG_INF)
    if attn_topk and attn_topk < s:
        kth = jax.lax.top_k(logits, attn_topk)[0][..., -1:]  # [B,H,T,1]
        own = (key_pos == q_pos)[:, None, :, :]  # the newest token survives
        logits = jnp.where((logits >= kth) | own, logits, NEG_INF)
    probs = jax.nn.softmax(logits, axis=-1).astype(q.dtype)
    return jnp.einsum("bhts,bshd->bthd", probs, v_r)


def layer_body(
    spec: ModelSpec,
    page_size: int,
    hidden: jax.Array,  # [B, T, D]
    params: dict,  # one layer's params
    k_slab: jax.Array,  # [S, Hkv, hd]: the flat arena (or a layer's slab);
    # stored folded (kv/arena.py): [S * Hkv, hd], same slots and pages
    v_slab: jax.Array,
    cos: jax.Array,
    sin: jax.Array,
    slots: jax.Array,  # ids INTO k_slab / v_slab (out of bounds = dropped)
    page_table: jax.Array,  # page ids INTO k_slab / v_slab
    q_positions: jax.Array,
    total_lens: jax.Array,
    tree_mask: jax.Array | None,
    window,  # traced scalar
    use_flash: bool = False,  # static: executor's shape heuristic said yes
    use_paged: bool = False,  # static: T=1 decode via the paged kernel
    lora: dict | None = None,  # this layer's per-request LoRA factors
    attn_topk: int = 0,  # sparse attention (executor disables the Pallas
    # kernels when this is on)
    t_real: int | None = None,  # real (unpadded) step tokens when T is a
    # padded bucket (the chunk kernel needs it to place query positions)
    ssm: tuple | None = None,  # (flat state arena, this layer's slots [B],
    # SsmRows) for a family with a state-space mixer; the layer then
    # returns the state arena as a fourth value
    rows: SsmRows | None = None,  # latent attention (spec.mla): whose the
    # flat rows are; k_slab / v_slab are then the latent and rotary-key slabs
):
    if spec.gdn is not None and "gdn_in_proj" in params:
        return _gdn_layer(spec, hidden, params, k_slab, v_slab, ssm, lora)
    if spec.one_sublayer and "q_proj" not in params:
        return _sublayer(spec, hidden, params, k_slab, v_slab, ssm, lora)
    if spec.mla is not None:
        return _mla_layer(
            spec, page_size, hidden, params, k_slab, v_slab, cos, sin, slots,
            page_table, q_positions, total_lens, rows, use_paged, lora,
        )
    b, t, d = hidden.shape
    h_heads, kv_heads, hd = (
        spec.num_attention_heads,
        spec.num_key_value_heads,
        spec.head_dim,
    )
    x = _norm(hidden, params, "input_layernorm", spec)
    mix, state = _mix(spec, params, x, ssm)
    with jax.named_scope("attn_proj"):
        xa = _attn_in(spec, x)
        q = _proj(xa, params, "q_proj", lora).reshape(b, t, h_heads, hd)
        k = _key_scale(spec, _proj(xa, params, "k_proj", lora)).reshape(
            b, t, kv_heads, hd
        )
        if spec.k_eq_v:
            # gemma-4 full-attention layers alias V to K (one shared
            # projection; reference gemma4/block.py attention_k_eq_v)
            v = k
        else:
            v = _proj(xa, params, "v_proj", lora).reshape(
                b, t, kv_heads, hd
            )
        gate = (
            _proj(xa, params, "q_gate_proj", lora) if spec.attn_gate else None
        )
        if spec.qk_norm:
            q, k = _qk_norm(spec, params, q, k)
        if not spec.alibi and spec.rope:
            q, k = apply_rotary(q, k, cos, sin)

    with jax.named_scope("arena_write"):
        k_slab, v_slab = arena_write(
            k_slab, v_slab, slots,
            k.reshape(b * t, kv_heads, hd), v.reshape(b * t, kv_heads, hd),
        )
    if use_paged:
        # the Pallas kernels stream K/V pages straight from the arena
        # (page table as scalar prefetch) — no gathered [B, S, Hkv, hd]
        # context buffer in HBM at all. T==1: decode kernel (int4 arenas
        # dequantize in-kernel); T>1: chunk kernel covering tree-verify
        # steps (tree mask applied in-kernel) and short multi-token
        # chunks. Eligibility (no alibi/softcap, T*H VMEM budget,
        # tree+window excluded) was checked host-side; sliding windows
        # ride in as a per-layer traced scalar.
        from bloombee_tpu.kv.quant import QuantSlab
        from bloombee_tpu.ops.pallas.paged_attention import (
            paged_chunk_attention,
            paged_decode_attention,
            paged_decode_attention_int4,
        )

        # the kernels compile for the device; interpret mode is only ever
        # the explicit test switch (read at trace time, like the spec)
        interpret = env.get("BBTPU_PAGED_INTERPRET")
        # (a folded slab goes in as its heads' view, a bitcast: kv/arena.py)
        k_pages = heads_view(k_slab, kv_heads)
        v_pages = heads_view(v_slab, kv_heads)
        with jax.named_scope("attention"):
            if t == 1:
                kernel = (
                    paged_decode_attention_int4
                    if isinstance(k_slab, QuantSlab)
                    else paged_decode_attention
                )
                attn = kernel(
                    q[:, 0], k_pages, v_pages, page_table, total_lens,
                    page_size=page_size, scale=attn_scale(spec),
                    interpret=interpret,
                    window=window,  # per-layer traced scalar (0 = full)
                )[:, None]  # [B, 1, H, hd]
            else:
                attn = paged_chunk_attention(
                    q, k_pages, v_pages, page_table, total_lens,
                    page_size=page_size, tree_mask=tree_mask,
                    scale=attn_scale(spec), interpret=interpret,
                    window=window, has_tree=tree_mask is not None,
                    t_real=t_real,
                )
        return _finish_layer(
            spec, params, hidden, x, _o_proj(spec, attn, params, lora, mix, gate),
            k_slab, v_slab, lora, state,
        )
    if use_flash and spec.flash_window:
        attn = _flash_by_window(
            spec, page_size, q, k_slab, v_slab, page_table, q_positions[:, 0],
            total_lens, window,
        )
    else:
        with jax.named_scope("arena_gather"):
            k_ctx = gather_pages(
                k_slab, page_table, page_size, kv_heads
            ).astype(hidden.dtype)
            v_ctx = gather_pages(
                v_slab, page_table, page_size, kv_heads
            ).astype(hidden.dtype)

        if use_flash:
            # long-context prefill: the Pallas kernel streams K/V tiles through
            # VMEM instead of materializing [B,H,T,S] logits in HBM. Eligibility
            # (no tree/alibi/softcap, T>=128, no window or the family's
            # `flash_window`) was checked host-side by the executor; per-row
            # starts/lens ride in as traced vectors, so
            # MIXED-length batches (multi-turn session prefill) engage flash
            # too, with the lens mask hiding each row's page-padded tail.
            from bloombee_tpu.ops.pallas.flash_attention import flash_attention

            with jax.named_scope("attention"):
                attn = flash_attention(
                    q, k_ctx, v_ctx, causal=True, scale=attn_scale(spec),
                    starts=q_positions[:, 0], lens=total_lens,
                    interpret=env.get("BBTPU_FLASH_INTERPRET"),
                )
        else:
            with jax.named_scope("attention"):
                attn = attend_paged(
                    spec, q, k_ctx, v_ctx, q_positions, total_lens, tree_mask,
                    window, attn_topk,
                )
    return _finish_layer(
        spec, params, hidden, x, _o_proj(spec, attn, params, lora, mix, gate),
        k_slab, v_slab, lora, state,
    )


def chunk_run_pages(w: int, window: int, page_size: int, max_pages: int):
    """How many pages a chunk of up to `w` rows gathers under a static
    `window`: the bucket's `max_pages` for window 0 or a run that would
    pass them; else the pages that span (start - window, start + w), a
    whole number of the flash kernel's widest K blocks, so that the widest
    divides the run (ops/pallas/flash_attention.py `flash_tiles`)."""
    n = -(-(window + w + page_size) // BLOCK_K) * BLOCK_K // page_size
    return max_pages if not window or n >= max_pages else n


def _chunk_pages(page_table_q, start, w: int, window: int, page_size: int):
    """The pages a chunk of up to `w` rows from position `start` attends
    under a static `window`: (pages [n], the first one's index). Every page
    for window 0; else the run of `chunk_run_pages` that spans
    (start - window, start + w)."""
    max_pages = page_table_q.shape[0]
    n = chunk_run_pages(w, window, page_size, max_pages)
    if n == max_pages:
        return page_table_q, jnp.int32(0)
    p0 = jnp.clip((start - window + 1) // page_size, 0, max_pages - n)
    return lax.dynamic_slice_in_dim(page_table_q, p0, n), p0


def _flash_by_window(spec, page_size, q, k_slab, v_slab, page_table, starts,
                     total_lens, window):
    """A prefill chunk's flash attention in a family whose window layers
    stand among full ones in ONE scan (`spec.flash_window`): q [B, T, H, hd]
    -> [B, T, H, hd]. The layer's `window` is a traced scalar there, the
    flash kernel's a static one, and what a layer gathers differs in SHAPE
    by kind, so both kinds are traced and `lax.cond` runs the layer's own: a
    window layer gathers only the pages its rows' windows span (`_chunk_pages`
    a sequence) and its kernel skips the K blocks below a query block's
    window; a full layer gathers every page. Each branch's ops carry its
    kind's scope (`window_attn` | `full_attn`) outside `arena_gather` and
    `attention`, so a device trace tells the two kinds' time apart."""
    from bloombee_tpu.ops.pallas.flash_attention import flash_attention

    t = q.shape[1]
    kv_heads, scale = spec.num_key_value_heads, attn_scale(spec)
    interpret = env.get("BBTPU_FLASH_INTERPRET")

    def attend(kind: str, static_window: int):
        def branch(q, k_slab, v_slab):
            with jax.named_scope(kind):
                pages, p0 = jax.vmap(
                    lambda pt, s: _chunk_pages(
                        pt, s, t, static_window, page_size)
                )(page_table, starts)
                with jax.named_scope("arena_gather"):
                    k_ctx = gather_pages(
                        k_slab, pages, page_size, kv_heads).astype(q.dtype)
                    v_ctx = gather_pages(
                        v_slab, pages, page_size, kv_heads).astype(q.dtype)
                shift = p0 * page_size
                with jax.named_scope("attention"):
                    return flash_attention(
                        q, k_ctx, v_ctx, causal=True, scale=scale,
                        starts=starts - shift, lens=total_lens - shift,
                        window=static_window, interpret=interpret,
                    )
        return branch

    return lax.cond(
        window > 0, attend("window_attn", spec.flash_window),
        attend("full_attn", 0), q, k_slab, v_slab,
    )


def _attn_in(spec, x):
    m = spec.attention_in_multiplier
    return x if m == 1.0 else x * m


def _key_scale(spec, k):
    m = spec.key_multiplier
    return k if m == 1.0 else k * m


def _qk_norm(spec, params, q, k):
    one_plus = spec.norm_type == "rms1p"
    return (
        rms_norm(q, params["q_norm"], spec.rms_norm_eps, one_plus),
        rms_norm(k, params["k_norm"], spec.rms_norm_eps, one_plus),
    )


def _o_proj(spec, attn, params, lora, mix=None, gate=None):
    """[..., T, H, hd] attention output -> [..., T, D] through o_proj, times
    the family's attention-output multiplier, plus the mixer's share.
    `gate` [..., T, H * hd]: a gated attention's output gate, applied as
    sigmoid(gate) before o_proj."""
    with jax.named_scope("attn_proj"):
        attn = attn.reshape(*attn.shape[:-2], -1)
        if gate is not None:
            attn = attn * jax.nn.sigmoid(gate).astype(attn.dtype)
        out = _proj(attn, params, "o_proj", lora)
        if spec.attention_out_multiplier != 1.0:
            out = out * spec.attention_out_multiplier
    return out if mix is None else out + mix


def attend_ragged(
    spec: ModelSpec,
    q: jax.Array,  # [R, H, hd] — ragged token rows across ALL members
    k_ctx: jax.Array,  # [B, S, Hkv, hd] — every member's gathered context
    v_ctx: jax.Array,
    q_pos: jax.Array,  # [R] context position per token
    q_seq: jax.Array,  # [R] owning sequence per token (>= B = padding)
    total_lens: jax.Array,  # [B]
    window,  # traced int32 scalar; 0 = full attention
    nt: jax.Array | None = None,  # [B] in-step token count per sequence
    tree_rows: jax.Array | None = None,  # [R, t_max] in-step visibility
) -> jax.Array:  # [R, H, hd]
    """Dense fallback for the ragged mixed-batch step: every token row
    attends the full [B, S] cross-session context and masks everything it
    doesn't own. Handles the kernel-ineligible configs (ALiBi, logit
    soft-cap, quantized arenas via gather_pages dequant) so those models
    still get the single fused dispatch. The x B masked logits columns are
    the fallback's price; padding rows (q_seq >= B) are fully masked and
    softmax to garbage that the executor slices away.

    (nt, tree_rows) switch the causal term into ragged TREE-verify
    semantics: sequence b's last nt[b] storage slots hold this step's
    speculative tree tokens, committed keys (storage pos < lens - nt) stay
    fully visible, and row i sees in-step slot m of its own sequence iff
    tree_rows[i, m]. Causality between in-step tokens is entirely encoded
    by tree_rows (ancestor-or-self), since depth positions repeat across
    sibling branches."""
    r, h, hd = q.shape
    b, s = k_ctx.shape[:2]
    key_pos = jnp.arange(s, dtype=jnp.int32)[None, None, :]  # [1, 1, S]
    seq_ids = jnp.arange(b, dtype=jnp.int32)[None, :, None]  # [1, B, 1]
    qp = q_pos[:, None, None]  # [R, 1, 1]
    own = (q_seq[:, None, None] == seq_ids) & (
        key_pos < total_lens[None, :, None]
    )
    if tree_rows is None:
        mask = own & (key_pos <= qp)
        mask &= (window <= 0) | (key_pos > (qp - window))
    else:
        t_max = tree_rows.shape[1]
        step_start = (total_lens - nt)[None, :, None]  # [1, B, 1]
        m = key_pos - step_start  # [1, B, S] in-step slot index (or < 0)
        mc = jnp.clip(m[0], 0, t_max - 1)  # [B, S]
        vis = tree_rows[:, mc] > 0  # [R, B, S]
        in_step = (m >= 0) & (key_pos < total_lens[None, :, None])
        mask = own & ((key_pos < step_start) | (in_step & vis))

    n_rep = h // k_ctx.shape[2]
    k_r = repeat_kv(k_ctx, n_rep)  # [B, S, H, hd]
    v_r = repeat_kv(v_ctx, n_rep)
    scale = attn_scale(spec)
    logits = jnp.einsum("rhd,bshd->rhbs", q, k_r).astype(jnp.float32) * scale
    if spec.attn_logit_softcap:
        logits = (
            jnp.tanh(logits / spec.attn_logit_softcap)
            * spec.attn_logit_softcap
        )
    if spec.alibi:
        slopes = jnp.asarray(alibi_slopes(spec.num_attention_heads))
        logits = logits + (
            slopes[None, :, None, None] * key_pos[None].astype(jnp.float32)
        )
    logits = jnp.where(mask[:, None, :, :], logits, NEG_INF)
    # softmax over the FLATTENED cross-session key axis: each row's mask
    # confines its probability mass to its own sequence's keys
    probs = jax.nn.softmax(
        logits.reshape(r, h, b * s), axis=-1
    ).astype(q.dtype)
    return jnp.einsum("rhs,shd->rhd", probs, v_r.reshape(b * s, h, hd))


def _attend_by_rows(spec, page_size, q, k_slab, v_slab, page_table,
                    total_lens, q_pos, rows: SsmRows):
    """Attention of a fused pack whose context is too long for the ragged
    kernel's one [R * H, hd] block and for dense scores over every member's
    pages (16 query heads x 1024 rows x 4 x 16384 keys in float32 is 4 GB):
    q [R, H, hd] -> [R, H, hd], sequence by sequence as `rows` tells them
    apart, the way latent attention and the mixers take a pack. A sequence
    with one row streams its pages through the paged decode kernel; THE one
    with more (a pack holds one: `one_chunk_a_pack`) runs the flash kernel
    over its own gathered pages. Kernels only: the caller keeps
    `attend_ragged` where none may run."""
    from bloombee_tpu.ops.pallas.flash_attention import flash_attention
    from bloombee_tpu.ops.pallas.paged_attention import paged_decode_attention

    r, h, hd = q.shape
    kv_heads = spec.num_key_value_heads
    scale = attn_scale(spec)
    out = jnp.zeros((r, h, hd), q.dtype)
    if rows.step_form:
        one = rows.nt == 1
        at = jnp.clip(rows.row0, 0, r - 1)
        with jax.named_scope("attention"):
            o = paged_decode_attention(
                q[at], heads_view(k_slab, kv_heads),
                heads_view(v_slab, kv_heads), page_table,
                jnp.where(one, total_lens, 0), page_size=page_size,
                scale=scale, interpret=env.get("BBTPU_PAGED_INTERPRET"),
            )
        out = out.at[jnp.where(one, rows.row0, r)].set(o, mode="drop")
    w = rows.window
    for i in range(rows.chunk_seqs.shape[0]):
        c = rows.chunk_seqs[i]
        r0, n_c = rows.row0[c], rows.nt[c]
        q_c = _window_rows(q, r0, w)
        with jax.named_scope("arena_gather"):
            k_ctx = gather_pages(
                k_slab, page_table[c][None], page_size, kv_heads)
            v_ctx = gather_pages(
                v_slab, page_table[c][None], page_size, kv_heads)
        start = q_pos[jnp.clip(r0, 0, r - 1)]
        with jax.named_scope("attention"):
            if flash_takes(w, k_ctx.shape[1]):
                o_c = flash_attention(
                    q_c[None], k_ctx.astype(q.dtype), v_ctx.astype(q.dtype),
                    causal=True, scale=scale, starts=start[None],
                    lens=total_lens[c][None],
                    interpret=env.get("BBTPU_FLASH_INTERPRET"),
                )[0]
            else:  # a bucket under the flash kernel's tile (tests)
                o_c = attend_paged(
                    spec, q_c[None], k_ctx.astype(q.dtype),
                    v_ctx.astype(q.dtype),
                    (start + jnp.arange(w, dtype=jnp.int32))[None],
                    total_lens[c][None], None, jnp.int32(0),
                )[0]
        real = (jnp.arange(w, dtype=jnp.int32) < n_c)[:, None, None]
        out = out + _place_rows(jnp.where(real, o_c, 0), r0, r)
    return out


def layer_body_ragged(
    spec: ModelSpec,
    page_size: int,
    hidden: jax.Array,  # [1, R, D] — every member's tokens, ragged-packed
    params: dict,  # one layer's params
    k_slab: jax.Array,  # [S, Hkv, hd]: the flat arena (or a layer's slab);
    # stored folded (kv/arena.py): [S * Hkv, hd], same slots and pages
    v_slab: jax.Array,
    cos: jax.Array,
    sin: jax.Array,
    slots: jax.Array,  # [R] ids into the slab (padding rows out of bounds)
    page_table: jax.Array,  # [B, NP] page ids into the slab
    q_positions: jax.Array,  # [1, R]
    total_lens: jax.Array,  # [B]
    q_seq: jax.Array,  # [R] owning sequence per token
    window,  # traced per-layer scalar
    use_kernel: bool = False,  # static: ragged Pallas kernel vs dense
    lora: dict | None = None,
    nt: jax.Array | None = None,  # [B] in-step token counts (tree groups)
    tree_rows: jax.Array | None = None,  # [R, t_max] in-step visibility
    ssm: tuple | None = None,  # as layer_body's
    rows: SsmRows | None = None,  # as layer_body's
):
    """layer_body for the ragged mixed-batch step: one [1, R, D] row-major
    pack of N decode tokens plus one prefill chunk's tokens — or, when
    (nt, tree_rows) are given, N sessions' speculative TREE rows verifying
    in one dispatch. Projections, rotary, and the arena scatter are
    position-wise, so they need no per-member structure — only attention
    does, and it gets it from (q_seq, q_positions) per row instead of
    layer_body's block-uniform (B, T)."""
    if spec.gdn is not None and "gdn_in_proj" in params:
        return _gdn_layer(spec, hidden, params, k_slab, v_slab, ssm, lora)
    if spec.one_sublayer and "q_proj" not in params:
        return _sublayer(spec, hidden, params, k_slab, v_slab, ssm, lora)
    if spec.mla is not None:
        return _mla_layer(
            spec, page_size, hidden, params, k_slab, v_slab, cos, sin, slots,
            page_table, q_positions, total_lens, rows, use_kernel, lora,
        )
    _, r, d = hidden.shape
    h_heads, kv_heads, hd = (
        spec.num_attention_heads,
        spec.num_key_value_heads,
        spec.head_dim,
    )
    x = _norm(hidden, params, "input_layernorm", spec)
    mix, state = _mix(spec, params, x, ssm)
    with jax.named_scope("attn_proj"):
        xa = _attn_in(spec, x)
        q = _proj(xa, params, "q_proj", lora).reshape(1, r, h_heads, hd)
        k = _key_scale(spec, _proj(xa, params, "k_proj", lora)).reshape(
            1, r, kv_heads, hd
        )
        if spec.k_eq_v:
            v = k
        else:
            v = _proj(xa, params, "v_proj", lora).reshape(
                1, r, kv_heads, hd
            )
        gate = (
            _proj(xa, params, "q_gate_proj", lora) if spec.attn_gate else None
        )
        if spec.qk_norm:
            q, k = _qk_norm(spec, params, q, k)
        if not spec.alibi and spec.rope:
            q, k = apply_rotary(q, k, cos, sin)

    with jax.named_scope("arena_write"):
        k_slab, v_slab = arena_write(
            k_slab, v_slab, slots,
            k.reshape(r, kv_heads, hd), v.reshape(r, kv_heads, hd),
        )
    from bloombee_tpu.kv.quant import QuantSlab

    if use_kernel and rows is not None:
        attn = _attend_by_rows(
            spec, page_size, q[0], k_slab, v_slab, page_table, total_lens,
            q_positions[0], rows,
        )[None]
    elif use_kernel and not isinstance(k_slab, QuantSlab):
        from bloombee_tpu.ops.pallas.paged_attention import (
            paged_ragged_attention,
        )

        with jax.named_scope("attention"):
            attn = paged_ragged_attention(
                q[0], heads_view(k_slab, kv_heads),
                heads_view(v_slab, kv_heads), page_table, total_lens,
                q_seq, q_positions[0],
                page_size=page_size, scale=attn_scale(spec),
                interpret=env.get("BBTPU_PAGED_INTERPRET"),
                window=window, nt=nt, tree_rows=tree_rows,
                has_tree=tree_rows is not None,
            )[None]
    else:
        with jax.named_scope("arena_gather"):
            k_ctx = gather_pages(
                k_slab, page_table, page_size, kv_heads
            ).astype(hidden.dtype)
            v_ctx = gather_pages(
                v_slab, page_table, page_size, kv_heads
            ).astype(hidden.dtype)
        with jax.named_scope("attention"):
            attn = attend_ragged(
                spec, q[0], k_ctx, v_ctx, q_positions[0], q_seq, total_lens,
                window, nt=nt, tree_rows=tree_rows,
            )[None]
    return _finish_layer(
        spec, params, hidden, x, _o_proj(spec, attn, params, lora, mix, gate),
        k_slab, v_slab, lora, state,
    )


def dense_unsupported(spec: ModelSpec) -> str | None:
    """Why a family can't run the cache-returning DENSE block forward
    (drafter path); None when it can. These are attend-injection limits:
    the caller supplies the attention fn, so position-bias (ALiBi),
    sliding windows, and logit soft-caps would silently drop."""
    if spec.alibi:
        return "ALiBi bias lives inside attention"
    if spec.layer_types and "sliding" in spec.layer_types:
        return "sliding-window masks live inside attention"
    if spec.attn_logit_softcap:
        return "attention logit soft-cap lives inside attention"
    if spec.heterogeneous:
        return "heterogeneous head_dim layers"
    if spec.one_sublayer:
        return "layers that are a mixer or an expert layer alone (recurrent state)"
    if spec.ssm is not None:
        return "a state-space mixer beside attention (recurrent state)"
    if spec.gdn is not None:
        return "linear-attention layers among attention layers (recurrent state)"
    if spec.mla is not None:
        return "latent attention (the cache holds latents, not K and V)"
    if spec.mamba is not None:
        return "Mamba layers among attention layers (recurrent state)"
    return None


def dense_block_forward(
    params: dict,
    spec: ModelSpec,
    hidden: jax.Array,  # [B, T, D]
    cos: jax.Array,
    sin: jax.Array,
    attend,  # (q, k, v) -> (attn_out [B, T, H, hd], aux)
):
    """Family-generic DENSE block forward with caller-supplied attention —
    the client-side analog of layer_body for code that manages its own KV
    (the speculative drafter; reference spec_decoding_drafter.py:67-110
    drives HF models the same way). Same spec switches as layer_body:
    norm types + biases, qk-norm, parallel-attn residual, sandwich norms,
    silu/gelu/MoE MLPs. Returns (hidden, (k, v))."""
    reason = dense_unsupported(spec)
    if reason is not None:
        raise NotImplementedError(
            f"dense block forward doesn't cover family {spec.family!r}: "
            f"{reason}"
        )
    b, t, d = hidden.shape
    h_heads, kv_heads, hd = (
        spec.num_attention_heads,
        spec.num_key_value_heads,
        spec.head_dim,
    )
    x = _norm(hidden, params, "input_layernorm", spec)
    q = _proj(x, params, "q_proj").reshape(b, t, h_heads, hd)
    k = _proj(x, params, "k_proj").reshape(b, t, kv_heads, hd)
    v = (
        k if spec.k_eq_v
        else _proj(x, params, "v_proj").reshape(b, t, kv_heads, hd)
    )
    if spec.qk_norm:
        q = rms_norm(q, params["q_norm"], spec.rms_norm_eps)
        k = rms_norm(k, params["k_norm"], spec.rms_norm_eps)
    q, k = apply_rotary(q, k, cos, sin)
    attn, _aux = attend(q, k, v)
    attn_out = _proj(attn.reshape(b, t, h_heads * hd), params, "o_proj")
    hidden, k, v = _finish_layer(spec, params, hidden, x, attn_out, k, v)
    return hidden, (k, v)


def _finish_layer(spec, params, hidden, x, attn_out, k_slab, v_slab,
                  lora=None, state=None):
    """Residual + MLP tail shared by the dense/flash/paged attention paths.
    A family with recurrent state gets its state arena back as a fourth
    value."""
    out = _residual_mlp(spec, params, hidden, x, attn_out, k_slab, v_slab,
                        lora)
    return out if state is None else (*out, state)


def _residual_mlp(spec, params, hidden, x, attn_out, k_slab, v_slab, lora):
    if spec.one_sublayer:
        # attention is the layer's only sublayer: no MLP follows it
        return hidden + attn_out, k_slab, v_slab
    if spec.parallel_attn:
        # falcon: parallel residual. 7b shares one input norm for attention
        # AND the MLP; 40b/180b new-arch uses two (ln_attn already fed the
        # projections above; ln_mlp feeds the MLP)
        if spec.num_ln_in_parallel_attn == 2:
            x_mlp = _norm(hidden, params, "mlp_layernorm", spec)
        else:
            x_mlp = x
        hidden = hidden + attn_out + _mlp(x_mlp, params, spec, lora)
        return hidden, k_slab, v_slab

    if spec.sandwich_norms:
        attn_out = _norm(attn_out, params, "post_attention_layernorm", spec)
        hidden = hidden + attn_out
        x2 = _norm(hidden, params, "pre_feedforward_layernorm", spec)
        mlp_out = _norm(
            _mlp(x2, params, spec, lora), params,
            "post_feedforward_layernorm", spec,
        )
        hidden = hidden + mlp_out
        return hidden, k_slab, v_slab

    hidden = hidden + attn_out
    x2 = _norm(hidden, params, "post_attention_layernorm", spec)
    hidden = hidden + _mlp(x2, params, spec, lora)
    return hidden, k_slab, v_slab
