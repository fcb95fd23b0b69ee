#!/usr/bin/env python3
"""What the paged decode kernel takes at Phi-4-mini-flash's decode shape by
the pages a grid step streams, ON THE CHIP (`_pages_per_step`'s 1024-row cap
was held against these readings in PR 53; run by no cell; without a TPU the
script refuses, exit code 2: a time read anywhere else is no reading, and the
kernel's interpret-mode checks are tests/test_paged_attention.py's):

    python3 scripts/paged_pages_readings.py [--pages 1,2,4,8] [--rows 1,4]
        [--contexts 8200,12000] [--windows 0,512] [--sequential] [--out F]
        [--other PATH/paged_attention.py]

For each (rows, context, window) and each count of pages a grid step: 16
chained calls in one jit (a decode step of the cell's span makes 16), 40
float32 query halves against a bfloat16 slab of the cell's 5376 pages of 10
K/V pairs, stored FOLDED as the cell's arena is (`[tokens * 10, 128]`, so the
kernel's page view is a bitcast: until PR 56 the script handed over
`[tokens, 10, 128]`, and the slabs' re-tiling, 27.5 us a call, was read as the
kernel's in every number of PR 53), at the 1024-page bucket, the physical pages
shuffled
(`--sequential`: in the arena's order): microseconds a call, the turns (grid
steps) a call walks and those of them that hold a live page (`walk_bounds`;
the whole page bucket for a kernel file without it), the time its live pages'
bytes take at 819 GB/s and that time's share of the call, and the largest
difference from the one-page-a-step output (the running maximum is re-based a
step, not a page: a reassociation). The count the rule gives the shape is
marked `rule`. `--other` times another tree's kernel file too (the parent's:
`"kernel": "other"`) and gives this tree's largest difference from it at the
same pages a step (`diff_other`). One JSON object a line, on standard output
and, as it is read, in `--out`.
"""

from __future__ import annotations

import argparse
import contextlib
import importlib.util
import itertools
import json
import pathlib
import sys
import time

ROOT = pathlib.Path(__file__).resolve().parents[1]
sys.path.insert(0, str(ROOT))

HBM_BYTES_PER_S = 819e9  # one v5e chip (Google Cloud documentation, "TPU v5e")
PAGE, ARENA_PAGES, BUCKET = 16, 5376, 1024
HEADS, KV_HEADS, HEAD_DIM = 40, 10, 128
CALLS, REPEATS = 16, 8


def _ints(text):
    return [int(x) for x in text.split(",") if x]


def main(argv=None) -> int:
    parser = argparse.ArgumentParser()
    parser.add_argument("--pages", default="1,2,4,8")
    parser.add_argument("--rows", default="1,4")
    parser.add_argument("--contexts", default="8200,12000")
    parser.add_argument("--windows", default="0,512")
    parser.add_argument("--sequential", action="store_true")
    parser.add_argument("--out", default=None)
    parser.add_argument("--other", default=None)
    args = parser.parse_args(argv)

    import jax
    import jax.numpy as jnp
    import numpy as np

    if jax.devices()[0].platform != "tpu":
        print("paged_pages_readings.py reads times on a TPU only; this "
              f"process has {jax.devices()[0].platform}", file=sys.stderr)
        return 2

    from bloombee_tpu.ops.pallas import paged_attention as this

    kernels = [("this", this)]
    if args.other:
        spec = importlib.util.spec_from_file_location("other_pa", args.other)
        other = importlib.util.module_from_spec(spec)
        spec.loader.exec_module(other)
        kernels.append(("other", other))
    rule = this._pages_per_step
    page_rows = PAGE * KV_HEADS
    keys = jax.random.split(jax.random.PRNGKey(53), 3)
    slab_shape = (ARENA_PAGES * PAGE * KV_HEADS, HEAD_DIM)
    k_slab = jax.random.normal(keys[0], slab_shape, jnp.bfloat16)
    v_slab = jax.random.normal(keys[1], slab_shape, jnp.bfloat16)
    sinks = [sys.stdout]

    def emit(rec):
        for f in sinks:
            print(json.dumps(rec), file=f, flush=True)

    def timed(pa, pages, q, page_table, lens, window):
        """(one call's output, seconds a call) of the kernel file `pa` at
        `pages` a grid step."""
        kept = pa._pages_per_step
        pa._pages_per_step = lambda n_pages, rows: pages
        try:
            # the slabs go in as arguments: closed over, each program would
            # carry them as 440 MB of constants
            def call(q, k, v):
                return pa.paged_decode_attention.__wrapped__(
                    q, k.reshape(-1, KV_HEADS, HEAD_DIM),
                    v.reshape(-1, KV_HEADS, HEAD_DIM), page_table, lens,
                    page_size=PAGE, window=jnp.int32(window))

            def chain(q, k, v):
                def body(_, q):
                    for _ in range(CALLS):
                        q = q + 1e-3 * call(q, k, v)
                    return q
                return jax.lax.fori_loop(0, REPEATS, body, q)

            out = jax.block_until_ready(jax.jit(call)(q, k_slab, v_slab))
            many = jax.jit(chain)
            jax.block_until_ready(many(q, k_slab, v_slab))
            best = 1e9
            for _ in range(5):
                t0 = time.perf_counter()
                jax.block_until_ready(many(q, k_slab, v_slab))
                best = min(best, time.perf_counter() - t0)
        finally:
            pa._pages_per_step = kept
        return np.asarray(out), best / (CALLS * REPEATS)

    rng = np.random.default_rng(53)
    with contextlib.ExitStack() as stack:
        if args.out:
            pathlib.Path(args.out).parent.mkdir(parents=True, exist_ok=True)
            sinks.append(stack.enter_context(open(args.out, "w")))
        for rows, context in itertools.product(
                _ints(args.rows), _ints(args.contexts)):
            q = jax.random.normal(
                keys[2], (rows, HEADS, HEAD_DIM), jnp.float32)
            live = -(-context // PAGE)
            page_table = np.zeros((rows, BUCKET), np.int32)
            for i in range(rows):
                page_table[i, :live] = (
                    np.arange(i * BUCKET, i * BUCKET + live)
                    if args.sequential
                    else rng.permutation(ARENA_PAGES)[:live])
            page_table = jnp.asarray(page_table)
            lens = jnp.full((rows,), context, jnp.int32)
            for window in _ints(args.windows):
                first = max(context - window, 0) // PAGE if window else 0
                need = rows * (live - first) * page_rows * HEAD_DIM * 2 * 2
                bytes_us = need / HBM_BYTES_PER_S * 1e6
                base = None
                for pages, (name, pa) in itertools.product(
                        _ints(args.pages), kernels):
                    _, extent, live_turns = this.walk_bounds(
                        np.asarray(lens), window, PAGE, pages, np)
                    bounded = hasattr(pa, "walk_bounds")
                    rec = {"kernel": name, "rows": rows, "context": context,
                           "window": window, "sequential": args.sequential,
                           "pages": pages,
                           "rule": pages == rule(BUCKET, page_rows),
                           "turns": rows * (
                               int(extent) if bounded else BUCKET // pages),
                           "live_turns": int(live_turns),
                           "live_pages": rows * (live - first)}
                    try:
                        out, s = timed(pa, pages, q, page_table, lens, window)
                    except Exception as e:  # a block the compiler refuses
                        emit({**rec, "error": str(e)[:300]})
                        continue
                    if name == "this":
                        mine = out
                        base = out if base is None else base
                    emit({**rec, "us": round(s * 1e6, 2),
                          "bytes_us": round(bytes_us, 2),
                          "bytes_share": round(bytes_us / (s * 1e6), 4),
                          **({"diff_max": float(np.abs(out - base).max())}
                             if name == "this" else
                             {"diff_other": float(np.abs(out - mine).max())})})
    return 0


if __name__ == "__main__":
    sys.exit(main())
