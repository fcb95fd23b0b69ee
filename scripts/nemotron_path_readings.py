#!/usr/bin/env python3
"""Which of the served paths a row took, against the plain reference, at the
cell's own widths: the span's executor driven by hand (no server, no
client) over two sequences, bfloat16 as the cell serves it, each row's
hidden state after the span compared with the family file's float32 forward
of the same rows. One line a phase:

  chunk     prefill in 512-row chunks and a ragged tail (flash, tiled experts,
            the chunked scan), the last 8 rows of each sequence
  solo      decode steps of ONE sequence (paged decode, the expert list, one
            recurrence step)
  fused     a ragged pack: a decode row of one sequence beside a chunk of
            the other
  group     decode steps of BOTH sequences in one dispatch

    chiprun -- python3 scripts/nemotron_path_readings.py [--tokens 2200]

`err` is rms(got - want) / rms(want - input) a row: against what the span
ADDED to the row. `--tiny` runs a small preset on the CPU (a rehearsal of
the script, not a reading).
"""

from __future__ import annotations

import argparse
import asyncio
import json
import pathlib
import shutil
import sys
import tempfile

import numpy as np

ROOT = pathlib.Path(__file__).resolve().parents[1]
sys.path.insert(0, str(ROOT))

from cellbench import checkpoint, families, reference  # noqa: E402


def main(argv=None) -> int:
    import jax
    import jax.numpy as jnp

    from bloombee_tpu.kv.cache_manager import CacheManager
    from bloombee_tpu.models.checkpoint import load_span_params
    from bloombee_tpu.runtime.executor import SpanExecutor

    parser = argparse.ArgumentParser()
    parser.add_argument("--tokens", type=int, default=2200)
    parser.add_argument("--seed", type=int, default=5400000401)
    parser.add_argument("--tiny", action="store_true")
    parser.add_argument("--dtype", default="bfloat16")
    args = parser.parse_args(argv)
    config = json.loads((ROOT / "cellbench/configs/"
                         "nemotron3-nano-30b-ep2-span14.json").read_text())
    config.pop("cellbench")
    chunk = 512
    if args.tiny:
        sys.path.insert(0, str(ROOT / "tests"))
        from test_cellbench_nemotron_h import TINY_NEMOTRON_H

        config = dict(TINY_NEMOTRON_H, hybrid_override_pattern="EMEMEM*EMEMEM*",
                      num_hidden_layers=14)
        chunk = 32
    held = tuple(config["experts_held"])
    layers = config["num_hidden_layers"]
    family = families.of(config)
    dtype = jnp.dtype(args.dtype)
    work = pathlib.Path(tempfile.mkdtemp(prefix="nemotron_paths_"))
    try:
        checkpoint.write_checkpoint(work, config, args.seed)
        client = reference.read_safetensors(
            work / checkpoint.file_name(checkpoint.CLIENT_SHARD))
        rng = np.random.default_rng(args.seed)
        t_a, t_b = args.tokens, args.tokens - chunk - 37
        ids = rng.integers(0, config["vocab_size"], (2, t_a + 16))
        h = np.asarray(family.embed(client, config, ids), np.float32)
        params, spec = load_span_params(
            str(work), 0, layers, dtype=dtype, experts=held)
        pages = 2 * (-(-(t_a + 64) // 16)) + 64
        manager = CacheManager(
            layers, pages, 16, spec.num_key_value_heads, spec.head_dim,
            dtype=dtype, ssm=spec.recurrent, state_slots=4,
            arena_layers=spec.arena_layers(0, layers))
        ex = SpanExecutor(params, spec, manager, compute_dtype=dtype)
        got = [{}, {}]  # row -> (phase, hidden)

        def keep(seq, phase, start, out):
            for i, row in enumerate(np.asarray(out, np.float32)):
                got[seq][start + i] = (phase, row)

        async def run():
            async with manager.allocate(1, t_a + 64) as ha, \
                    manager.allocate(1, t_a + 64) as hb:
                at = [0, 0]
                for seq, handle, end in ((0, ha, t_a), (1, hb, t_b)):
                    while at[seq] < end:
                        n = min(chunk, end - at[seq])
                        x = h[seq:seq + 1, at[seq]:at[seq] + n]
                        keep(seq, "chunk", at[seq],
                             np.asarray(ex.prefill(handle, x))[0])
                        at[seq] += n
                for _ in range(3):  # A alone
                    x = h[:1, at[0]:at[0] + 1]
                    keep(0, "solo", at[0], np.asarray(ex.decode(ha, x))[0])
                    at[0] += 1
                # a pack: A's decode row beside a chunk of B
                n = chunk // 2 + 5
                out, both = ex.ragged_group(
                    [ha, hb], [h[:1, at[0]:at[0] + 1], h[1:, at[1]:at[1] + n]],
                    tree_masks=[None, None], depths_list=[None, None])
                manager.commit(both)
                out = np.asarray(out)
                keep(0, "fused", at[0], out[:1])
                keep(1, "fused_chunk", at[1], out[1:1 + n])
                at[0] += 1
                at[1] += n
                for _ in range(4):  # both in one dispatch
                    out, both = ex.decode_group(
                        [ha, hb], [h[:1, at[0]:at[0] + 1],
                                   h[1:, at[1]:at[1] + 1]])
                    manager.commit(both)
                    out = np.asarray(out)
                    keep(0, "group", at[0], out[0])
                    keep(1, "group", at[1], out[1])
                    at[0] += 1
                    at[1] += 1
                for _ in range(3):  # B alone
                    x = h[1:, at[1]:at[1] + 1]
                    keep(1, "solo", at[1], np.asarray(ex.decode(hb, x))[0])
                    at[1] += 1
                return at

        at = asyncio.run(run())
        ex.fetch(jnp.zeros(()))
        print(json.dumps({
            "attn_dispatches": ex.attn_dispatches,
            "moe_dispatches": ex.moe_dispatches,
            "kernel_fallbacks": ex.kernel_fallbacks,
            "kv_writes": ex.kv_writes, "rows": at}), flush=True)
        # the reference: every layer over both sequences' rows
        with jax.default_matmul_precision("highest"):
            want = []
            for seq in (0, 1):
                x = jnp.asarray(h[seq, :at[seq]])
                pos = jnp.arange(at[seq])
                for layer in range(layers):
                    p = jax.tree.map(
                        jnp.asarray,
                        reference.layer_params(work, config, layer))
                    x = jax.jit(family.layer_forward, static_argnums=1)(
                        p, _Frozen(config), x, pos)
                want.append(np.asarray(x))
        for seq in (0, 1):
            phases: dict[str, list] = {}
            for row, (phase, out) in sorted(got[seq].items()):
                if phase == "chunk" and row < at[seq] - 400 and row % 97:
                    continue  # a sample of the early rows
                added = want[seq][row] - h[seq, row]
                err = float(np.sqrt(np.mean((out - want[seq][row]) ** 2))
                            / np.sqrt(np.mean(added ** 2)))
                phases.setdefault(phase, []).append((row, round(err, 4)))
            for phase, rows in phases.items():
                errs = [e for _, e in rows]
                print(json.dumps({
                    "seq": "AB"[seq], "phase": phase, "rows": len(rows),
                    "err_median": float(np.median(errs)),
                    "err_max": max(errs), "last": rows[-12:]}), flush=True)
        print(json.dumps({"platform": jax.devices()[0].platform,
                          "dtype": str(dtype), "tokens": args.tokens}))
    finally:
        shutil.rmtree(work, ignore_errors=True)
    return 0


class _Frozen(dict):
    """A configuration as a static argument of `jax.jit`."""

    def __hash__(self):
        return hash(json.dumps(self, sort_keys=True))


if __name__ == "__main__":
    sys.exit(main())
