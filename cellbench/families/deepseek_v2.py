"""`model_type: deepseek_v2`: multi-head latent attention (MLA) and, after
`first_k_dense_replace` dense layers, DeepSeekMoE layers: routed experts
chosen by a group-limited softmax router beside shared experts. The
published `modeling_deepseek.py`, in the EXPANDED form and with no cache:

  per layer: h = rms(x)
      c_q = rms(h @ Wqa^T) ; q = c_q @ Wqb^T -> per head q_nope | q_pe
      [c_kv | k_pe] = h @ Wkva^T ; c_kv = rms(c_kv) ; k_pe ONE key for all heads
      rotary on q_pe, k_pe only (interleaved pairs, de-interleaved before the
      half-rotation; YaRN frequencies; cos/sin times mscale / mscale_all_dim's)
      [k_nope | v] = c_kv @ Wkvb^T per head
      score = (q_nope . k_nope + q_pe . k_pe) * qk_dim**-0.5 * mscale**2
      causal softmax in float32 ; x += concat_h(p @ v) @ Wo^T
  layer < first_k_dense_replace: x += silu_mlp(rms(x)) of intermediate_size
  else: g = rms(x) ; s = softmax(g @ Wg^T) over ALL routed experts ; a
      group's score is its largest s ; the topk_group best groups keep their
      s, every other s is 0 ; the top-k of what is left, weights
      s * routed_scaling_factor (no renormalisation) ;
      x += sum over the row's experts THAT THIS SHARE HOLDS of w_i E_i(g)
           + shared(g)
  logits = rms(x) @ head^T

One difference from the installed transformers port is known and kept: the
port leaves mscale**2 out of the softmax scale (`modeling_deepseek_v2.py`),
where the model's own published code multiplies it in. This file follows the
published code (tests/test_deepseek_v2.py divides the difference out).

A share of a deployment: the checkpoint holds `n_routed_experts` experts,
`experts_held` = [first, count) of the published numbering, and the router
scores all `router_experts` of them (two keys of the configuration's file
beside the source's own; the harness hands a family only the top level). A
pair whose expert lies on another chip adds nothing here, in the program and
in this reference alike. Without the two keys every expert is held.

Fills (`cellbench.assumed` records the readings they were set from): see
FILLS below.
"""

from __future__ import annotations

import math

from cellbench.checkpoint import ONES
from cellbench.reference import _rms, _rotate_half
from cellbench.roofline import BF16

F32 = 4
# The plan's fills, set from readings at the published widths (CPU float32
# counts, then the chip): the default "bits" (|w| in 2**-9..2**-6) everywhere
# made every row's residual one direction after layer 0, so a 512-row chunk
# reached 2-5 of the 20 held experts. What is set apart from "bits":
#   o_proj, down projections: a narrower range, so a layer's update stays
#     under the residual it is added to and rows keep their own direction;
#   the router: wider, so its logits follow the row and not the common part.
FILLS = {
    "o_proj": {"low": -0.006, "high": 0.006},
    "down_proj": {"low": -0.006, "high": 0.006},
    "router": {"low": -0.06, "high": 0.06},
}


def _router_width(config: dict) -> int:
    return config.get("router_experts", config["n_routed_experts"])


def _held(config: dict) -> tuple[int, int]:
    first, count = config.get("experts_held", (0, config["n_routed_experts"]))
    return int(first), int(count)


def _sparse(config: dict, layer: int) -> bool:
    return (config.get("n_routed_experts") is not None
            and layer >= config["first_k_dense_replace"]
            and layer % config.get("moe_layer_freq", 1) == 0)


def _shared_width(config: dict) -> int:
    return config["moe_intermediate_size"] * (config.get("n_shared_experts") or 0)


# ------------------------------------------------------- checkpoint plan
def _mlp_tensors(prefix: str, d: int, i: int) -> list[tuple]:
    return [
        (f"{prefix}.gate_proj.weight", (i, d)),
        (f"{prefix}.up_proj.weight", (i, d)),
        (f"{prefix}.down_proj.weight", (d, i), FILLS["down_proj"]),
    ]


def layer_tensors(config: dict, layer: int) -> list[tuple]:
    d, heads = config["hidden_size"], config["num_attention_heads"]
    nope, rope, vd = (config["qk_nope_head_dim"], config["qk_rope_head_dim"],
                      config["v_head_dim"])
    qr, kvr = config["q_lora_rank"], config["kv_lora_rank"]
    p = f"model.layers.{layer}"
    a = f"{p}.self_attn"
    tensors = [
        (f"{p}.input_layernorm.weight", (d,), ONES),
        (f"{p}.post_attention_layernorm.weight", (d,), ONES),
        (f"{a}.q_a_proj.weight", (qr, d)),
        (f"{a}.q_a_layernorm.weight", (qr,), ONES),
        (f"{a}.q_b_proj.weight", (heads * (nope + rope), qr)),
        (f"{a}.kv_a_proj_with_mqa.weight", (kvr + rope, d)),
        (f"{a}.kv_a_layernorm.weight", (kvr,), ONES),
        (f"{a}.kv_b_proj.weight", (heads * (nope + vd), kvr)),
        (f"{a}.o_proj.weight", (d, heads * vd), FILLS["o_proj"]),
    ]
    if not _sparse(config, layer):
        return tensors + _mlp_tensors(f"{p}.mlp", d, config["intermediate_size"])
    tensors.append(
        (f"{p}.mlp.gate.weight", (_router_width(config), d), FILLS["router"]))
    first, count = _held(config)
    for e in range(first, first + count):
        tensors += _mlp_tensors(
            f"{p}.mlp.experts.{e}", d, config["moe_intermediate_size"])
    if _shared_width(config):
        tensors += _mlp_tensors(
            f"{p}.mlp.shared_experts", d, _shared_width(config))
    return tensors


def client_tensors(config: dict) -> list[tuple]:
    v, d = config["vocab_size"], config["hidden_size"]
    return [
        ("model.embed_tokens.weight", (v, d)),
        ("model.norm.weight", (d,), ONES),
        ("lm_head.weight", (v, d)),
    ]


# ------------------------------------------------------------- reference
# the router stays as the checkpoint has it in the int8 control: a choice
# flipped by a rounded score is another expert, not a rounding
INT8_KEEPS = ("router",)


def layer_params(tensors: dict, config: dict, layer: int) -> dict:
    """One layer's tensors under short names, torch layout [out, in]; the
    held experts stacked [E_held, out, in]. Still bfloat16 (exact)."""
    import numpy as np

    p = f"model.layers.{layer}."
    a = p + "self_attn."
    out = {
        "ln1": tensors[p + "input_layernorm.weight"],
        "ln2": tensors[p + "post_attention_layernorm.weight"],
        "q_a": tensors[a + "q_a_proj.weight"],
        "q_a_norm": tensors[a + "q_a_layernorm.weight"],
        "q_b": tensors[a + "q_b_proj.weight"],
        "kv_a": tensors[a + "kv_a_proj_with_mqa.weight"],
        "kv_a_norm": tensors[a + "kv_a_layernorm.weight"],
        "kv_b": tensors[a + "kv_b_proj.weight"],
        "o": tensors[a + "o_proj.weight"],
    }
    if not _sparse(config, layer):
        for k in ("gate", "up", "down"):
            out[k] = tensors[p + f"mlp.{k}_proj.weight"]
        return out
    out["router"] = tensors[p + "mlp.gate.weight"]
    first, count = _held(config)
    for k in ("gate", "up", "down"):
        out[f"e_{k}"] = np.stack([
            tensors[p + f"mlp.experts.{e}.{k}_proj.weight"]
            for e in range(first, first + count)
        ])
        if _shared_width(config):
            out[f"s_{k}"] = tensors[p + f"mlp.shared_experts.{k}_proj.weight"]
    return out


def yarn_mscale(factor: float, mscale: float = 1.0) -> float:
    return 1.0 if factor <= 1 else 0.1 * mscale * math.log(factor) + 1.0


def rotary_frequencies(config: dict):
    """(inverse frequencies [rope / 2], the factor on cos and sin): plain
    rotary without `rope_scaling`, YaRN with it."""
    import numpy as np

    dim, base = config["qk_rope_head_dim"], config.get("rope_theta", 10000.0)
    pos = np.arange(0, dim, 2, dtype=np.float32) / dim
    extra = 1.0 / base ** pos
    rs = config.get("rope_scaling")
    if not rs:
        return extra, 1.0
    factor, orig = rs["factor"], rs["original_max_position_embeddings"]

    def correction(turns):
        return dim * math.log(orig / (turns * 2 * math.pi)) / (2 * math.log(base))

    low = max(math.floor(correction(rs.get("beta_fast", 32))), 0)
    high = min(math.ceil(correction(rs.get("beta_slow", 1))), dim - 1)
    ramp = np.clip((np.arange(dim // 2, dtype=np.float32) - low)
                   / max(high - low, 0.001), 0, 1)
    inter = extra / factor
    inv = inter * ramp + extra * (1 - ramp)
    return inv.astype(np.float32), (
        yarn_mscale(factor, rs.get("mscale", 1.0))
        / yarn_mscale(factor, rs.get("mscale_all_dim", 0.0)))


def softmax_scale(config: dict) -> float:
    scale = (config["qk_nope_head_dim"] + config["qk_rope_head_dim"]) ** -0.5
    rs = config.get("rope_scaling")
    if rs and rs.get("mscale_all_dim"):
        scale *= yarn_mscale(rs["factor"], rs["mscale_all_dim"]) ** 2
    return scale


def _rope(x, positions, config):
    """x [T, ..., rope] with interleaved pairs: de-interleave, then the
    half-rotation (the published `apply_rotary_pos_emb`)."""
    import jax.numpy as jnp

    inv, mscale = rotary_frequencies(config)
    ang = positions.astype(jnp.float32)[:, None] * jnp.asarray(inv)
    ang = jnp.concatenate([ang, ang], -1)
    ang = ang.reshape(ang.shape[0], *([1] * (x.ndim - 2)), ang.shape[-1])
    x = jnp.concatenate([x[..., 0::2], x[..., 1::2]], -1)
    return x * (jnp.cos(ang) * mscale) + _rotate_half(x) * (jnp.sin(ang) * mscale)


def mla_attention(p: dict, config: dict, h, positions, block: int = 256):
    """Latent attention on one sequence's normed rows h [T, D], expanded,
    a block of queries at a time: [T, heads * v_dim]."""
    import jax
    import jax.numpy as jnp

    t = h.shape[0]
    heads = config["num_attention_heads"]
    nope, rope, vd = (config["qk_nope_head_dim"], config["qk_rope_head_dim"],
                      config["v_head_dim"])
    kvr, eps = config["kv_lora_rank"], config["rms_norm_eps"]
    q = (_rms(h @ p["q_a"].T, p["q_a_norm"], eps) @ p["q_b"].T).reshape(
        t, heads, nope + rope)
    q_nope, q_pe = q[..., :nope], _rope(q[..., nope:], positions, config)
    ckv = h @ p["kv_a"].T
    c_kv = _rms(ckv[:, :kvr], p["kv_a_norm"], eps)
    k_pe = _rope(ckv[:, kvr:], positions, config)  # [T, rope]
    kv = (c_kv @ p["kv_b"].T).reshape(t, heads, nope + vd)
    k_nope, v = kv[..., :nope], kv[..., nope:]
    scale = softmax_scale(config)
    pad = -t % block
    qn = jnp.pad(q_nope, ((0, pad), (0, 0), (0, 0))).reshape(-1, block, heads, nope)
    qp = jnp.pad(q_pe, ((0, pad), (0, 0), (0, 0))).reshape(-1, block, heads, rope)
    pb = jnp.pad(positions, (0, pad), mode="edge").reshape(-1, block)

    def one(args):
        a, b, pp = args
        scores = (jnp.einsum("thn,shn->hts", a, k_nope)
                  + jnp.einsum("thr,sr->hts", b, k_pe)) * scale
        mask = pp[:, None] >= positions[None, :]
        scores = jnp.where(mask[None], scores, -jnp.inf)
        return jnp.einsum("hts,shv->thv", jax.nn.softmax(scores, -1), v)

    return jax.lax.map(one, (qn, qp, pb)).reshape(-1, heads * vd)[:t]


def _silu_mlp(x, gate, up, down):
    import jax

    return (jax.nn.silu(x @ gate.T) * (x @ up.T)) @ down.T


def route(scores, config: dict):
    """Group-limited greedy top-k on softmax scores [R, E]: (indices [R, k],
    weights [R, k] = score * routed_scaling_factor)."""
    import jax
    import jax.numpy as jnp

    r, e = scores.shape
    if config.get("topk_method", "greedy") == "group_limited_greedy":
        groups = config["n_group"]
        best = scores.reshape(r, groups, e // groups).max(-1)
        _, kept = jax.lax.top_k(best, config["topk_group"])
        keep = jnp.zeros((r, groups), bool).at[
            jnp.arange(r)[:, None], kept].set(True)
        scores = jnp.where(jnp.repeat(keep, e // groups, axis=1), scores, 0.0)
    top, idx = jax.lax.top_k(scores, config["num_experts_per_tok"])
    return idx, top * config.get("routed_scaling_factor", 1.0)


def moe(x, p: dict, config: dict, block: int = 512):
    """The sparse layer's MLP on normed rows [R, D]: the held experts'
    weighted sum plus the shared experts."""
    import jax
    import jax.numpy as jnp

    r, d = x.shape
    first, count = _held(config)
    pad = -r % block
    xb = jnp.pad(x, ((0, pad), (0, 0))).reshape(-1, block, d)

    def one(rows):
        idx, top = route(jax.nn.softmax(rows @ p["router"].T, -1), config)
        local = idx - first
        held = (local >= 0) & (local < count)
        w = jnp.zeros((block, count), jnp.float32).at[
            jnp.arange(block)[:, None], jnp.clip(local, 0, count - 1)
        ].add(jnp.where(held, top, 0.0))
        g = jnp.einsum("rd,eid->rei", rows, p["e_gate"])
        u = jnp.einsum("rd,eid->rei", rows, p["e_up"])
        hid = jax.nn.silu(g) * u * w[:, :, None]
        return jnp.einsum("rei,edi->rd", hid, p["e_down"])

    out = jax.lax.map(one, xb).reshape(-1, d)[:r]
    if "s_gate" in p:
        out = out + _silu_mlp(x, p["s_gate"], p["s_up"], p["s_down"])
    return out


def layer_forward(p: dict, config: dict, hidden, positions):
    """One sequence: hidden [T, D] float32, positions [T]."""
    import jax
    import jax.numpy as jnp

    p = jax.tree.map(lambda a: a.astype(jnp.float32), p)
    eps = config["rms_norm_eps"]
    attn = mla_attention(p, config, _rms(hidden, p["ln1"], eps), positions)
    hidden = hidden + attn @ p["o"].T
    x = _rms(hidden, p["ln2"], eps)
    if "router" in p:
        return hidden + moe(x, p, config)
    return hidden + _silu_mlp(x, p["gate"], p["up"], p["down"])


def embed(client: dict, config: dict, ids):
    import numpy as np

    return np.asarray(client["model.embed_tokens.weight"][ids], np.float32)


def logits_rows(client: dict, config: dict, hidden_rows):
    import jax.numpy as jnp

    norm, head = (jnp.asarray(client[name]).astype(jnp.float32)
                  for name in ("model.norm.weight", "lm_head.weight"))
    return _rms(hidden_rows, norm, config["rms_norm_eps"]) @ head.T


# -------------------------------------------------------- roofline needs
def _attention_weights(config: dict) -> int:
    d, heads = config["hidden_size"], config["num_attention_heads"]
    nope, rope, vd = (config["qk_nope_head_dim"], config["qk_rope_head_dim"],
                      config["v_head_dim"])
    qr, kvr = config["q_lora_rank"], config["kv_lora_rank"]
    return (d * qr + qr * heads * (nope + rope) + d * (kvr + rope)
            + kvr * heads * (nope + vd) + heads * vd * d)


def latent_row_bytes(config: dict) -> int:
    """One token's cached row in one layer: the latent and the rotary key."""
    return (config["kv_lora_rank"] + config["qk_rope_head_dim"]) * BF16


def _absorbed_flops_per_key(config: dict) -> int:
    """Per query row and attended key, all heads, absorbed: scores over
    latent + rotary dims, values over the latent."""
    kvr, rope = config["kv_lora_rank"], config["qk_rope_head_dim"]
    return 2 * config["num_attention_heads"] * ((kvr + rope) + kvr)


def mla_attention_needs(config: dict, rows: float, context: float,
                        kind: str = "chunk") -> dict:
    """What the attention core alone (`mla_attention` + `latent_io`) needs
    over the configuration's layers, in the ABSORBED form both paths run:
    "chunk": `rows` queries of ONE sequence with `context` tokens cached
    (the latent rows read once, the chunk's own written once, the causal
    lower triangle); "decode": `rows` sequences, each its own `context`
    latent rows once."""
    layers = config["num_hidden_layers"]
    row = latent_row_bytes(config)
    heads, kvr, rope = (config["num_attention_heads"], config["kv_lora_rank"],
                        config["qk_rope_head_dim"])
    q_io = rows * heads * (2 * kvr + rope) * BF16  # q_lat, q_pe in; o_lat out
    if kind == "decode":
        attended, kv = context + 1, rows * (context + 1) * row
    else:
        attended, kv = context + rows / 2, (context + 2 * rows) * row
    return {"bytes": layers * (kv + q_io),
            "flops": layers * rows * attended * _absorbed_flops_per_key(config)}


def _needs(config: dict, rows: float, attention: dict, held_pairs: float,
           distinct: float) -> dict:
    """Every layer's attention weights once; a dense layer's MLP; a sparse
    layer's router, shared experts and the `distinct` held experts the rows
    reach, each row computing its `held_pairs` pairs; the absorb products
    through W_kvb (already among the attention weights' FLOPs); the
    attention core as `attention` states; the rows' activations."""
    layers, d = config["num_hidden_layers"], config["hidden_size"]
    dense = sum(not _sparse(config, i) for i in range(layers))
    sparse = layers - dense
    attn = _attention_weights(config)
    expert = 3 * d * config["moe_intermediate_size"]
    shared = 3 * d * _shared_width(config)
    router = d * _router_width(config)
    mlp = 3 * d * config["intermediate_size"]
    weights = (layers * attn + dense * mlp
               + sparse * (router + shared + distinct * expert))
    flops = rows * 2 * (layers * attn + dense * mlp
                        + sparse * (router + shared + held_pairs * expert))
    return {"bytes": weights * BF16 + attention["bytes"] + 2 * rows * d * BF16,
            "flops": flops + attention["flops"],
            "weight_bytes": weights * BF16, "kv_bytes": attention["bytes"]}


def _expert_reach(config: dict, rows: float) -> tuple[float, float]:
    """(held pairs a row, distinct held experts `rows` rows reach), in
    expectation under routing that is uniform over experts."""
    first, count = _held(config)
    p = config["num_experts_per_tok"] / _router_width(config)
    return count * p, count * (1.0 - (1.0 - p) ** rows)


def decode_step_needs(config: dict, rows: float, context: float) -> dict:
    """One decode step of `rows` rows at mean context `context`: absorbed
    attention streaming every row's latent pages once; the held experts the
    rows reach."""
    pairs, distinct = _expert_reach(config, rows)
    return _needs(config, rows,
                  mla_attention_needs(config, rows, context, "decode"),
                  pairs, distinct)


def chunk_needs(config: dict, rows: float, context: float) -> dict:
    """One prefill chunk of `rows` tokens of ONE sequence with `context`
    tokens cached: absorbed attention over the cached latent rows and the
    chunk's own; the held experts the rows reach (all of them at 512)."""
    pairs, distinct = _expert_reach(config, rows)
    return _needs(config, rows,
                  mla_attention_needs(config, rows, context, "chunk"),
                  pairs, distinct)
