"""Server-side multi-step greedy decode: N tokens per RPC, one jitted loop.

The TPU-first answer to the per-token host<->device round trip under
served single-session throughput (every per-step decode pays one dependent
h2d -> compute -> d2h trip plus a wire hop; how much that is on a directly
attached chip is not measured yet). When one server hosts the WHOLE model,
the client can hand it the last token id and let embed -> span -> norm+head
-> select run N times entirely on device (`lax.scan`), returning N token
ids per RPC — one round trip amortized over N tokens.

Reference analog to beat: `_fast_generate_greedy`
(/root/reference/src/bloombee/client/remote_generation.py:286-386), which
still round-trips hidden states once per token.

Exactness contract: on the same backend this loop is token-identical to the
client's per-step greedy path. The embed is computed in the table's dtype
then cast to the compute dtype (= the per-step path's fp32 host embed +
bf16 wire cast, which is exact for bf16/fp32 tables); the head consumes the
span output cast to fp32 (= the per-step path's wire fetch + np.float32
cast, exact because compute dtype == wire dtype); both use the SAME
embed/head math (models/head.py) and first-index argmax.
"""

from __future__ import annotations

import functools

import jax
import jax.numpy as jnp
from jax import lax

from bloombee_tpu.models.head import embed_impl, norm_head_impl
from bloombee_tpu.models.spec import ModelSpec
from bloombee_tpu.runtime.step import span_step_impl


def decode_loop_impl(
    client_params: dict,  # embed table + final norm + lm_head
    span_params: dict,  # stacked per-layer span params (leading dim L)
    arena_k: jax.Array,  # [L, S_tot, Hkv, hd] (donated)
    arena_v: jax.Array,  # [L, S_tot, Hkv, hd] (donated)
    ids0: jax.Array,  # [B] int32: the input token of the FIRST step
    finished0: jax.Array,  # [B] bool: rows already at EOS (forced to eos_id)
    plans: jax.Array,  # [N, plan_len] packed int32, one per step
    lora: dict | None = None,  # per-request LoRA factors, leading dim L
    *,
    spec: ModelSpec,
    page_size: int,
    max_pages: int,
    eos_id: int = -1,  # -1: no EOS clamping
    compute_dtype=jnp.bfloat16,
    windows: tuple | None = None,
    use_paged: bool = False,
    attn_topk: int = 0,
):
    """Returns (tokens [B, N], arena_k, arena_v).

    tokens[:, i] is the token selected AFTER step i (greedy argmax over the
    fp32 logits), with EOS rows clamped to eos_id exactly like the client's
    per-step `finished` masking (client/model.py generate). Steps whose plan
    carries out-of-bounds slots (bucket padding beyond the requested count)
    produce garbage tokens the caller slices away; their KV writes are
    dropped by the scatter's drop mode.
    """
    has_embed_norm = "embed_norm" in client_params

    def body(carry, plan):
        ids, finished, ak, av = carry
        h = embed_impl(
            client_params,
            ids[:, None],
            spec.embedding_multiplier,
            has_embed_norm,
            spec.rms_norm_eps,
        ).astype(compute_dtype)
        h, ak, av = span_step_impl(
            span_params, ak, av, h, plan, None, lora=lora,
            spec=spec, page_size=page_size, max_pages=max_pages,
            windows=windows, use_paged=use_paged, attn_topk=attn_topk,
        )
        logits = norm_head_impl(
            client_params,
            h[:, 0].astype(jnp.float32),
            spec.rms_norm_eps,
            spec.logits_soft_cap,
            spec.norm_type,
        )  # [B, V] fp32
        nxt = jnp.argmax(logits, axis=-1).astype(jnp.int32)
        if eos_id >= 0:
            nxt = jnp.where(finished, eos_id, nxt)
            finished = finished | (nxt == eos_id)
        return (nxt, finished, ak, av), nxt

    (_, _, arena_k, arena_v), toks = lax.scan(
        body, (ids0, finished0, arena_k, arena_v), plans
    )
    return toks.T, arena_k, arena_v  # [B, N]


decode_loop = functools.partial(
    jax.jit,
    static_argnames=(
        "spec", "page_size", "max_pages", "eos_id", "compute_dtype",
        "windows", "use_paged", "attn_topk",
    ),
    donate_argnames=("arena_k", "arena_v"),
)(decode_loop_impl)
