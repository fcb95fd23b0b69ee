#!/usr/bin/env python3
"""What the fills of `cellbench/families/nemotron_h.py` give at the PUBLISHED
widths: one period (EMEMEM*, the cell's configuration cut to 7 layers) of a
seeded checkpoint through the family file's plain float32 layers, one
sequence, and a line of readings a layer:

  every layer  rms of the residual before it and of what it adds
  M            the rms of S_t C_t against the skip D x_t; the tokens a head
               remembers, 1 / (dt * A), at the 10th, 50th, 90th percentile
  E            rms of the routed sum (the held experts) and of the shared
               expert; the share of chosen pairs the bias moved; the gap
               between the 6th and the 7th best score against the bias's range
  *            std of the scores; the keys a query weighs (exp of the
               entropy of its softmax), median over heads and rows

    chiprun -- python3 scripts/nemotron_fill_readings.py [--tokens 2048]

It runs wherever JAX runs (the numbers are arithmetic, not times); at 2048
tokens an E layer's 64 experts take 2.6 GB in float32. `cellbench.assumed.
fills` of the configuration's file and PERF.md section 6 (PR 54) record what
it read.
"""

from __future__ import annotations

import argparse
import json
import pathlib
import shutil
import sys
import tempfile

import numpy as np

ROOT = pathlib.Path(__file__).resolve().parents[1]
sys.path.insert(0, str(ROOT))

from cellbench import checkpoint, families, reference  # noqa: E402
from cellbench.reference import _rms  # noqa: E402


def rms(x) -> float:
    return float(np.sqrt(np.mean(np.square(np.asarray(x, np.float64)))))


def main(argv=None) -> int:
    import jax
    import jax.numpy as jnp

    parser = argparse.ArgumentParser()
    parser.add_argument("--tokens", type=int, default=2048)
    parser.add_argument("--seed", type=int, default=5400000001)
    args = parser.parse_args(argv)
    config = json.loads((ROOT / "cellbench/configs/"
                         "nemotron3-nano-30b-ep2-span14.json").read_text())
    config.pop("cellbench")
    config.update(num_hidden_layers=7, hybrid_override_pattern="EMEMEM*")
    family = families.of(config)
    work = pathlib.Path(tempfile.mkdtemp(prefix="nemotron_fills_"))
    try:
        checkpoint.write_checkpoint(work, config, args.seed)
        client = reference.read_safetensors(
            work / checkpoint.file_name(checkpoint.CLIENT_SHARD))
        ids = np.random.default_rng(args.seed).integers(
            0, config["vocab_size"], (args.tokens,))
        h = jnp.asarray(family.embed(client, config, ids))
        pos = jnp.arange(args.tokens)
        eps = config["layer_norm_epsilon"]
        with jax.default_matmul_precision("highest"):
            for layer in range(config["num_hidden_layers"]):
                p = jax.tree.map(
                    lambda a: jnp.asarray(a).astype(jnp.float32),
                    reference.layer_params(work, config, layer))
                x = _rms(h, p["ln"], eps)
                out = {"layer": layer,
                       "kind": config["hybrid_override_pattern"][layer],
                       "residual_rms": rms(h)}
                if "in" in p:
                    update, _ = family.mixer_forward(p, config, x, True)
                    s = family._dims(config)
                    zxbcdt = x @ p["in"].T
                    dt = jax.nn.softplus(
                        zxbcdt[:, s["d_in"] + s["conv_dim"]:] + p["dt_bias"])
                    memory = 1.0 / np.asarray(dt * jnp.exp(p["a_log"]))
                    out["memory_tokens_p10_p50_p90"] = [
                        float(np.percentile(memory, q)) for q in (10, 50, 90)]
                    # what the state alone gives: the same mixer with D = 0
                    skipped = dict(p, d=jnp.zeros_like(p["d"]))
                    no_skip, _ = family.mixer_forward(skipped, config, x, True)
                    out["update_without_skip_rms"] = rms(no_skip)
                elif "router" in p:
                    update = family.moe(x, p, config)
                    shared = family.relu2_mlp(x, p["s_up"], p["s_down"])
                    out["routed_rms"] = rms(update - shared)
                    out["shared_rms"] = rms(shared)
                    logits = x @ p["router"].T
                    k = config["num_experts_per_tok"]
                    idx, _ = family.route(logits, p["expert_bias"], config)
                    plain = jax.lax.top_k(logits, k)[1]
                    moved = ~(idx[:, :, None] == plain[:, None, :]).any(-1)
                    out["bias_moved_share"] = 100 * float(moved.mean())
                    top = jax.lax.top_k(jax.nn.sigmoid(logits), k + 1)[0]
                    out["score_6th_minus_7th_p50"] = float(
                        jnp.median(top[:, k - 1] - top[:, k]))
                    out["top_scores_p50"] = [
                        float(jnp.median(top[:, j])) for j in (0, k - 1)]
                    first, count = family._held(config)
                    held = ((idx >= first) & (idx < first + count)).sum(-1)
                    out["held_pairs_a_row"] = float(held.mean())
                else:
                    update = family.attention(p, config, x, pos) @ p["o"].T
                    t, hd = x.shape[0], config["head_dim"]
                    rows = jnp.arange(t - 64, t)
                    q = (x[rows] @ p["q"].T).reshape(64, -1, hd)
                    kk = (x @ p["k"].T).reshape(t, -1, hd)
                    rep = q.shape[1] // kk.shape[1]
                    scores = jnp.einsum(
                        "rhd,shd->hrs", q, jnp.repeat(kk, rep, 1)) / hd ** 0.5
                    out["score_std"] = float(scores.std())
                    mask = pos[None, None, :] <= rows[None, :, None]
                    w = jax.nn.softmax(jnp.where(mask, scores, -jnp.inf), -1)
                    entropy = -(w * jnp.log(w + 1e-30)).sum(-1)
                    out["keys_weighed_p50"] = float(
                        jnp.median(jnp.exp(entropy)))
                out["update_rms"] = rms(update)
                h = h + update
                print(json.dumps(out), flush=True)
        print(json.dumps({"final_residual_rms": rms(h),
                          "tokens": args.tokens,
                          "platform": jax.devices()[0].platform}))
    finally:
        shutil.rmtree(work, ignore_errors=True)
    return 0


if __name__ == "__main__":
    sys.exit(main())
