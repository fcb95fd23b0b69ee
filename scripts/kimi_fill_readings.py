#!/usr/bin/env python3
"""The readings `kimi-linear-48b-ep4-span8`'s fills were set from (the
configuration file's `cellbench.assumed.readings`): the plain reference
(cellbench/families/kimi_linear.py, float32 at `highest`) over one seeded
sequence at the published widths, layer by layer, with what the fills decide
counted beside it:

  memory of a channel        1 / |g|, in tokens: p10, p50, p90 over rows,
                             heads and channels of a KDA layer
  decay inside a head        the ratio of the 90th to the 10th percentile of
                             |g| over a head's 128 channels, median over
                             rows and heads (near 1 the model IS Gated
                             DeltaNet), and how far |g| moves with the token
  row cosine by layer        mean cosine between distinct rows' residuals
  update / residual rms      each layer's two updates against what they add to
  latent layers' scores      standard deviation of a head's scaled scores
                             over the keys a late query sees, and the keys
                             that hold half of its softmax mass
  held pairs a row, rows with a held expert, held experts a 512-row chunk
  reaches                    by sparse layer (uniform routing: 2.0, 0.87, 64)
  bias moved                 pairs in the top-k only because of the bias
  routed sum / shared expert rms, on rows with a held pair

    python3 scripts/kimi_fill_readings.py [--tokens 2048] [--seed N] [--out F]

On the chip (chiprun) it takes a minute or two; it holds one layer's float32
weights (1.9 GB) at a time. One JSON object on the last line.
"""

from __future__ import annotations

import argparse
import json
import pathlib
import sys
import tempfile

ROOT = pathlib.Path(__file__).resolve().parents[1]
sys.path.insert(0, str(ROOT))


def main(argv=None) -> int:
    parser = argparse.ArgumentParser()
    parser.add_argument("--tokens", type=int, default=2048)
    parser.add_argument("--seed", type=int, default=5100000777)
    parser.add_argument("--config", default=str(
        ROOT / "cellbench/configs/kimi-linear-48b-ep4-span8.json"))
    parser.add_argument("--out", default=None)
    args = parser.parse_args(argv)

    import jax
    import jax.numpy as jnp
    import numpy as np

    from cellbench import checkpoint, families, reference

    config = json.loads(pathlib.Path(args.config).read_text())
    config.pop("cellbench", None)
    family = families.of(config)
    first, count = family._held(config)
    k = config["num_experts_per_token"]
    eps = config["rms_norm_eps"]
    nope = config["qk_nope_head_dim"]
    out = {"tokens": args.tokens, "seed": args.seed,
           "platform": jax.devices()[0].platform, "layers": []}
    (ROOT / ".cache").mkdir(exist_ok=True)  # 7 GB of checkpoint: not /tmp
    with tempfile.TemporaryDirectory(dir=ROOT / ".cache") as tmp, \
            jax.default_matmul_precision("highest"):
        ckpt = pathlib.Path(tmp)
        checkpoint.write_checkpoint(ckpt, config, args.seed)
        client = reference.read_safetensors(
            ckpt / checkpoint.file_name(checkpoint.CLIENT_SHARD))
        ids = np.random.default_rng(args.seed).integers(
            0, config["vocab_size"], args.tokens)
        h = jnp.asarray(family.embed(client, config, ids))
        pos = jnp.arange(args.tokens)

        def cosine(x):
            x = x / jnp.linalg.norm(x, axis=-1, keepdims=True)
            n = x.shape[0]
            return float((jnp.sum(x @ x.T) - n) / (n * (n - 1)))

        rms = lambda x: float(jnp.sqrt(jnp.mean(jnp.square(x))))  # noqa: E731
        pct = lambda x, q: [  # noqa: E731
            float(v) for v in np.percentile(np.asarray(x), q)]

        @jax.jit
        def layer(p, h):
            p = jax.tree.map(lambda a: a.astype(jnp.float32), p)
            x = reference._rms(h, p["ln1"], eps)
            extra = {}
            if "conv_q" in p:
                up_a = family.kimi_delta_attention(p, config, x)
                extra["g"] = family.kda_inputs(p, config, x)[3]
            else:
                up_a = family.mla_attention(p, config, x, pos)
                # the last 64 queries' scaled scores over all keys
                t = x.shape[0]
                heads = config["num_attention_heads"]
                rope = config["qk_rope_head_dim"]
                kvr = config["kv_lora_rank"]
                q = (x[-64:] @ p["q"].T).reshape(64, heads, nope + rope)
                ckv = x @ p["kv_a"].T
                c_kv = reference._rms(ckv[:, :kvr], p["kv_a_norm"], eps)
                kv = (c_kv @ p["kv_b"].T).reshape(t, heads, -1)
                extra["scores"] = (
                    jnp.einsum("thn,shn->hts", q[..., :nope], kv[..., :nope])
                    + jnp.einsum("thr,sr->hts", q[..., nope:], ckv[:, kvr:])
                ) * (nope + rope) ** -0.5
            mid = h + up_a
            x2 = reference._rms(mid, p["ln2"], eps)
            if "router" in p:
                up_m = family.moe(x2, p, config)
                extra["logits"] = x2 @ p["router"].T
                extra["shared"] = family._silu_mlp(
                    x2, p["s_gate"], p["s_up"], p["s_down"])
            else:
                up_m = family._silu_mlp(x2, p["gate"], p["up"], p["down"])
            return mid + up_m, up_a, up_m, extra

        for i in range(config["num_hidden_layers"]):
            host = reference.layer_params(ckpt, config, i)
            p = jax.tree.map(jnp.asarray, host)
            new, up_a, up_m, extra = layer(p, h)
            got = {"layer": i, "kind": "kda" if "g" in extra else "latent",
                   "residual_rms_in": rms(h), "mixer_update_rms": rms(up_a),
                   "mlp_update_rms": rms(up_m), "row_cosine_out": cosine(new)}
            if "g" in extra:
                g = np.abs(np.asarray(extra["g"]))  # [T, H, K]
                got["memory_tokens_p10_p50_p90"] = pct(1.0 / g, [10, 50, 90])
                inside = np.percentile(g, 90, axis=-1) / np.percentile(
                    g, 10, axis=-1)
                got["decay_p90_over_p10_inside_a_head_median"] = float(
                    np.median(inside))
                # the same channel over the rows: how far the token moves it
                over_rows = np.percentile(g, 90, axis=0) / np.percentile(
                    g, 10, axis=0)
                got["decay_p90_over_p10_over_tokens_median"] = float(
                    np.median(over_rows))
            if "scores" in extra:
                s = np.asarray(extra["scores"])[:, :, : args.tokens - 64]
                got["score_std_over_keys_mean"] = float(s.std(-1).mean())
                w = np.sort(np.asarray(jax.nn.softmax(jnp.asarray(s), -1)),
                            -1)[..., ::-1]
                half = (np.cumsum(w, -1) < 0.5).sum(-1) + 1
                got["keys_holding_half_the_mass_median"] = float(
                    np.median(half))
                got["keys_seen"] = int(s.shape[-1])
            if "logits" in extra:
                logits = extra["logits"]
                bias = jnp.asarray(host["expert_bias"]).astype(jnp.float32)
                scores = jax.nn.sigmoid(logits)
                _, idx = jax.lax.top_k(scores + bias, k)
                _, plain = jax.lax.top_k(scores, k)
                top, _ = jax.lax.top_k(scores, k + 1)
                moved = ~(idx[:, :, None] == plain[:, None, :]).any(-1)
                here = (idx >= first) & (idx < first + count)
                chunks = [
                    int(np.unique(np.asarray(idx[a:a + 512])[
                        np.asarray(here[a:a + 512])]).size)
                    for a in range(0, args.tokens - 511, 512)]
                load = np.bincount(
                    np.asarray(idx).ravel(), minlength=scores.shape[1])
                with_pair = np.asarray(here.any(-1))
                routed = np.asarray(up_m - extra["shared"])[with_pair]
                got.update(
                    held_pairs_a_row=float(here.sum() / here.shape[0]),
                    rows_with_a_held_expert=float(here.any(-1).mean()),
                    held_experts_reached_a_512_row_chunk=chunks,
                    bias_moved_pairs_share=float(moved.mean()),
                    rows_whose_choice_the_bias_changes=float(
                        moved.any(-1).mean()),
                    score_kth_and_next_mean=[
                        float(top[:, k - 1].mean()), float(top[:, k].mean())],
                    logit_std=float(logits.std()),
                    expert_load_min_max_of_mean=[
                        float(load.min() / load.mean()),
                        float(load.max() / load.mean())],
                    routed_sum_rms_on_rows_with_one=float(
                        np.sqrt(np.mean(np.square(routed)))),
                    shared_expert_rms=rms(extra["shared"]))
            out["layers"].append(got)
            h = new
        # what the rows far back decide: the first 256 ids changed
        out["final_residual_rms"] = rms(h)
    text = json.dumps(out)
    if args.out:
        pathlib.Path(args.out).parent.mkdir(parents=True, exist_ok=True)
        pathlib.Path(args.out).write_text(text)
    print(text)
    return 0


if __name__ == "__main__":
    sys.exit(main())
