"""Plain reference for both configurations, independent of the program.

The published forward pass of a Mistral / Qwen3-MoE decoder stack in
straightforward jax.numpy and float32 (`highest` matmul precision, set by the
caller): no kernels, no cache, no batching tricks. Its inputs are the
checkpoint file the benchmark wrote from --seed (parsed here, not through the
program's loader) and token ids from the same seed. Departures from the
published description: none; the depth is the configuration's cut.

  hidden = embed[ids]
  per layer: x = rms(hidden) ; q,k,v = x@Wq^T.. ; per-head rms on q,k (Qwen3)
             rotary (HF rotate_half) ; causal softmax attention with GQA and
             the sliding window ; hidden += attn@Wo^T
             x = rms(hidden) ; hidden += mlp(x)
  mlp: silu(x@Wg^T) * (x@Wu^T) @ Wd^T, or the Qwen3-MoE block: softmax over
       all experts, top-k, renormalised (norm_topk_prob), experts' gated MLPs
  logits = rms(hidden) @ head^T
"""

from __future__ import annotations

import json
import pathlib
import time
from concurrent.futures import ThreadPoolExecutor

import numpy as np


def read_safetensors(path: pathlib.Path) -> dict[str, np.ndarray]:
    """name -> array; BF16 tensors come back as ml_dtypes.bfloat16 views."""
    import ml_dtypes

    raw = np.fromfile(path, dtype=np.uint8)
    n = int.from_bytes(raw[:8].tobytes(), "little")
    header = json.loads(raw[8: 8 + n].tobytes())
    body = raw[8 + n:]
    out = {}
    for name, meta in header.items():
        if name == "__metadata__":
            continue
        if meta["dtype"] != "BF16":
            raise ValueError(f"{path}: {name} is {meta['dtype']}, not BF16")
        a, b = meta["data_offsets"]
        out[name] = body[a:b].view(ml_dtypes.bfloat16).reshape(meta["shape"])
    return out


def layer_params(ckpt: pathlib.Path, config: dict, layer: int) -> dict:
    """One layer's tensors under short names, torch layout [out, in]; the
    experts stacked [E, out, in]. Still bfloat16 (exact); cast on use."""
    from cellbench.checkpoint import file_name

    t = read_safetensors(ckpt / file_name(f"layer{layer:03d}"))
    p = f"model.layers.{layer}."
    out = {
        "ln1": t[p + "input_layernorm.weight"],
        "ln2": t[p + "post_attention_layernorm.weight"],
        **{k: t[p + f"self_attn.{k}_proj.weight"] for k in "qkvo"},
    }
    if config["model_type"] == "qwen3_moe":
        out["q_norm"] = t[p + "self_attn.q_norm.weight"]
        out["k_norm"] = t[p + "self_attn.k_norm.weight"]
        out["router"] = t[p + "mlp.gate.weight"]
        for k in ("gate", "up", "down"):
            out[f"e_{k}"] = np.stack([
                t[p + f"mlp.experts.{e}.{k}_proj.weight"]
                for e in range(config["num_experts"])
            ])
    else:
        for k in ("gate", "up", "down"):
            out[k] = t[p + f"mlp.{k}_proj.weight"]
    return out


def client_params(ckpt: pathlib.Path) -> dict:
    from cellbench.checkpoint import CLIENT_SHARD, file_name

    t = read_safetensors(ckpt / file_name(CLIENT_SHARD))
    return {"embed": t["model.embed_tokens.weight"],
            "norm": t["model.norm.weight"], "head": t["lm_head.weight"]}


def _rms(x, w, eps):
    import jax.numpy as jnp

    var = jnp.mean(jnp.square(x), axis=-1, keepdims=True)
    return x * jnp.reciprocal(jnp.sqrt(var + eps)) * w


def _rotate_half(x):
    import jax.numpy as jnp

    half = x.shape[-1] // 2
    return jnp.concatenate([-x[..., half:], x[..., :half]], axis=-1)


def _moe(x, p, config, block: int = 512):
    """Qwen3MoeSparseMoeBlock on [R, D] rows, every expert computed for a
    block of rows at a time and weighted by the renormalised top-k router
    probabilities (zero off the top-k): the same sum the sparse form makes."""
    import jax
    import jax.numpy as jnp

    k = config["num_experts_per_tok"]
    r, d = x.shape
    pad = -r % block
    xb = jnp.pad(x, ((0, pad), (0, 0))).reshape(-1, block, d)

    def one(rows):
        probs = jax.nn.softmax(rows @ p["router"].T, axis=-1)
        top, idx = jax.lax.top_k(probs, k)
        if config.get("norm_topk_prob"):
            top = top / top.sum(-1, keepdims=True)
        w = jnp.zeros_like(probs).at[jnp.arange(block)[:, None], idx].set(top)
        g = jnp.einsum("rd,eid->rei", rows, p["e_gate"])
        u = jnp.einsum("rd,eid->rei", rows, p["e_up"])
        # the router weight goes in before the down projection (linear, so
        # the same sum) to keep the [R, E, D] intermediate out of memory
        h = jax.nn.silu(g) * u * w[:, :, None]
        return jnp.einsum("rei,edi->rd", h, p["e_down"])

    return jax.lax.map(one, xb).reshape(-1, d)[:r]


def _attention(q, k, v, positions, window, block: int = 512):
    """Causal (and windowed) softmax attention for one sequence, a block of
    queries at a time: q [T, H, hd], k and v [T, Hkv, hd]."""
    import jax
    import jax.numpy as jnp

    t, heads, hd = q.shape
    kvh = k.shape[1]
    pad = -t % block
    qb = jnp.pad(q, ((0, pad), (0, 0), (0, 0)))
    qb = qb.reshape(-1, block, kvh, heads // kvh, hd)
    pb = jnp.pad(positions, (0, pad), mode="edge").reshape(-1, block)

    def one(args):
        qq, pp = args
        scores = jnp.einsum("tgrh,sgh->grts", qq, k) / np.sqrt(hd)
        dist = pp[:, None] - positions[None, :]
        mask = dist >= 0
        if window:
            mask &= dist < window
        scores = jnp.where(mask[None, None], scores, -jnp.inf)
        return jnp.einsum("grts,sgh->tgrh", jax.nn.softmax(scores, -1), v)

    return jax.lax.map(one, (qb, pb)).reshape(-1, heads * hd)[:t]


def layer_forward(p: dict, config: dict, hidden, positions):
    """One sequence: hidden [T, D] float32, positions [T]; p's leaves may be
    bfloat16 (exact) and are cast to float32 here."""
    import jax
    import jax.numpy as jnp

    p = jax.tree.map(lambda a: a.astype(jnp.float32), p)
    t, d = hidden.shape
    heads, kvh = config["num_attention_heads"], config["num_key_value_heads"]
    hd = config.get("head_dim") or d // heads
    eps = config["rms_norm_eps"]
    x = _rms(hidden, p["ln1"], eps)
    q = (x @ p["q"].T).reshape(t, heads, hd)
    k = (x @ p["k"].T).reshape(t, kvh, hd)
    v = (x @ p["v"].T).reshape(t, kvh, hd)
    if "q_norm" in p:
        q, k = _rms(q, p["q_norm"], eps), _rms(k, p["k_norm"], eps)
    inv = 1.0 / (config["rope_theta"] ** (jnp.arange(0, hd, 2) / hd))
    ang = positions.astype(jnp.float32)[:, None] * inv
    ang = jnp.concatenate([ang, ang], -1)[:, None, :]
    q = q * jnp.cos(ang) + _rotate_half(q) * jnp.sin(ang)
    k = k * jnp.cos(ang) + _rotate_half(k) * jnp.sin(ang)
    attn = _attention(q, k, v, positions, config.get("sliding_window") or 0)
    hidden = hidden + attn @ p["o"].T
    x = _rms(hidden, p["ln2"], eps)
    if "router" in p:
        y = _moe(x, p, config)
    else:
        y = (jax.nn.silu(x @ p["gate"].T) * (x @ p["up"].T)) @ p["down"].T
    return hidden + y


def logits_rows(client: dict, config: dict, hidden_rows):
    import jax.numpy as jnp

    w = {k: jnp.asarray(v).astype(jnp.float32) for k, v in client.items()
         if k != "embed"}
    return _rms(hidden_rows, w["norm"], config["rms_norm_eps"]) @ w["head"].T


def int8_weights(p: dict) -> dict:
    """The control's weights: every matrix as symmetric int8 codes with one
    scale per output channel, dequantised (norm vectors untouched): the
    nearest precision below the bfloat16 the configurations state."""
    import jax.numpy as jnp

    def quant(w):
        if w.ndim < 2:
            return w
        w = w.astype(jnp.float32)
        scale = jnp.max(jnp.abs(w), axis=-1, keepdims=True) / 127.0
        return jnp.clip(jnp.round(w / scale), -127, 127) * scale

    return {name: quant(w) for name, w in p.items()}


def reference_logits(ckpt: pathlib.Path, config: dict, ids: np.ndarray,
                     rows: list[tuple[int, int]],
                     timing: dict | None = None) -> dict:
    """Float32 logits at (sequence, position) pairs `rows` for right-padded
    token ids [B, T] (causal attention keeps padding out of real positions):
    {"exact": the reference, "int8": the same forward pass with int8_weights,
    the control the comparison must tell apart from the reference}."""
    import jax
    import jax.numpy as jnp

    client = client_params(ckpt)
    positions = jnp.arange(ids.shape[1])
    hidden = jnp.asarray(np.asarray(client["embed"][ids], np.float32))

    def forward(p, hs):
        return jax.lax.map(
            lambda h: layer_forward(p, config, h, positions), hs)

    step = jax.jit(lambda p, hs, hq: (
        forward(p, hs), forward(int8_weights(p), hq)))
    exact = low = hidden
    timing = {} if timing is None else timing
    timing["forward_by_layer"] = []
    clock = time.perf_counter
    layers = range(config["num_hidden_layers"])
    with ThreadPoolExecutor(max_workers=3) as readers:  # read ahead
        ahead = {i: readers.submit(layer_params, ckpt, config, i)
                 for i in layers[:3]}
        for layer in layers:
            t0 = clock()
            host = ahead.pop(layer).result()
            if layer + 3 in layers:
                ahead[layer + 3] = readers.submit(
                    layer_params, ckpt, config, layer + 3)
            t1 = clock()
            p = jax.block_until_ready(jax.tree.map(jnp.asarray, host))
            t2 = clock()
            exact, low = jax.block_until_ready(step(p, exact, low))
            timing["forward_by_layer"].append(round(clock() - t2, 2))
            for key, dt in (("wait_for_read", t1 - t0),
                            ("to_device", t2 - t1), ("forward", clock() - t2)):
                timing[key] = timing.get(key, 0.0) + dt
    out = {}
    for name, hs in (("exact", exact), ("int8", low)):
        picked = jnp.stack([hs[s, t] for s, t in rows])
        out[name] = np.asarray(logits_rows(client, config, picked), np.float32)
    return out


def _centred(x: np.ndarray) -> np.ndarray:
    return x - x.mean(axis=-1, keepdims=True)


def compare(client_logits: np.ndarray, ref: dict) -> dict:
    """Two numbers per row, each steady from seed to seed because it averages
    over the whole vocabulary instead of following one token:

    err   rms of (client - reference) over the rms of the reference's
          centred logits: how far the served logits are from float32.
    int8  projection of (client - reference) on (int8 reference - reference),
          in units of the latter: 0 when the served weights are the
          checkpoint's, 1 when they are its int8 codes. bfloat16 activations
          alone put `err` within a factor of two of an int8-weight server's,
          so `err` cannot tell the two apart; this can.
    """
    exact = _centred(ref["exact"])
    d = _centred(client_logits) - exact
    q = _centred(ref["int8"]) - exact
    scale = np.sqrt(np.mean(exact * exact, -1))
    return {"err": np.sqrt(np.mean(d * d, -1)) / scale,
            "int8": np.sum(d * q, -1) / np.sum(q * q, -1),
            # the control's own `err`: the int8 reference in the program's place
            "control_err": np.sqrt(np.mean(q * q, -1)) / scale}
