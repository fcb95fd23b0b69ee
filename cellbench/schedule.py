"""The one general traffic generator. A traffic mix is a JSON file of
parameters under cellbench/traffic/; this module turns it into the fixed
replay every run of a cell makes. Nothing about lengths, order or timing is
drawn at run time: the seed changes token ids (and the weights) only.

Traffic file keys:
  loop          "closed" (a session's next request is due when its last ended)
  sessions      N concurrent session slots
  stagger_s     slot i's first request is due i * stagger_s after the start
  prompt_tokens fixed list; slot i takes entries i, i+N, i+2N, ... (cycling)
  new_tokens    fixed list of the same length (greedy, no EOS)
  judge         {"requests": J, "new_tokens": K}: J entries (a seeded choice)
                are served once more after the window, concurrently, K new
                tokens each, and their logits compared with the reference
  source        where the lengths come from

The harness's own constants (the same for every mix) are below, not in the
traffic files.
"""

from __future__ import annotations

import json
import pathlib

import numpy as np

PAGE_TOKENS = 16  # run_server --page-size default; bucket math below
WARM_SHOTS = 3  # rounds of the warm-up's bucket cover (loadgen._cover)
WARM_NEW_TOKENS = 4  # answers are cut to this in the warm-up's schedule pass
RAMP_S = 4.0  # the loop runs this long before the window opens (set-up)
DRAIN_S = 60.0  # longest wait for the slots' last requests after the window
HERE = pathlib.Path(__file__).resolve().parent


def load_traffic(name: str) -> dict:
    path = HERE / "traffic" / f"{name}.json"
    traffic = json.loads(path.read_text())
    n = len(traffic["prompt_tokens"])
    if traffic["loop"] != "closed":
        raise ValueError(f"{path}: only loop=closed is generated yet")
    if n != len(traffic["new_tokens"]) or n < traffic["sessions"]:
        raise ValueError(f"{path}: prompt_tokens / new_tokens mismatch")
    return traffic


def entry(traffic: dict, slot: int, turn: int) -> int:
    """Index of the schedule entry slot `slot` plays on its `turn`-th turn."""
    n = len(traffic["prompt_tokens"])
    return (slot + turn * traffic["sessions"]) % n


def token_ids(seed: int, index: int, length: int, vocab: int) -> list[int]:
    """Prompt ids of schedule entry `index`: from the seed, nothing else."""
    rng = np.random.default_rng([int(seed), 7919, int(index)])
    return rng.integers(0, vocab, size=length).tolist()


def page_bucket(context_tokens: int) -> int:
    pages = max(-(-context_tokens // PAGE_TOKENS), 1)
    b = 4
    while b < pages:
        b *= 2
    return b


def peak_live_tokens(traffic: dict) -> int:
    """Most tokens the sessions can hold at once: the N longest requests."""
    longest = sorted(
        (p + n for p, n in zip(traffic["prompt_tokens"], traffic["new_tokens"])),
        reverse=True,
    )
    return sum(longest[: traffic["sessions"]])


def _pow2(n: int) -> int:
    b = 1
    while b < n:
        b *= 2
    return b


def chunk_lengths(prompt: int, chunk: int) -> list[int]:
    """Lengths of the chunks a prompt is prefilled in."""
    return [chunk] * (prompt // chunk) + ([prompt % chunk] if prompt % chunk else [])


def cover_plan(traffic: dict, chunk: int) -> list[dict]:
    """What the warm-up has to drive so that every program the window can
    need is compiled before it: one entry per page bucket the schedule's
    contexts reach. A decode group's program is keyed by (rows bucket, page
    bucket), a solo chunk's by (chunk-length bucket, page bucket), a chunk
    fused with k decode rows by (bucket of all rows, bucket of 1 + k
    sequences, chunk-length bucket, page bucket); a group's page bucket is
    its longest member's. So N decoder sessions are opened at a context
    inside the bucket (`decoder_prompt`, the schedule's shortest prompt
    there) and stepped in teams of every width behind a `blocker` (a
    one-chunk prompt), alone and beside a fresh one-chunk prompt:
    `shots` lists one [k, length] for every DISTINCT key that a chunk of the
    schedule (full or tail, whatever its length) and k = 1..N-1 decode rows
    can make. `solo_tails` are the tail lengths, one per length bucket, that
    a decoder session steps alone: the last chunk of a prompt that ends in
    this page bucket, with nothing beside it."""
    n = traffic["sessions"]
    prompts = sorted(set(traffic["prompt_tokens"]))
    buckets = sorted({page_bucket(c) for p, new in zip(
        traffic["prompt_tokens"], traffic["new_tokens"]) for c in (p, p + new)})
    lengths = sorted({c for p in prompts for c in chunk_lengths(p, chunk)})
    shots: dict[tuple, list[int]] = {}
    for length in lengths:
        for k in range(1, n):
            key = (_pow2(length + k), _pow2(1 + k), _pow2(length))
            shots.setdefault(key, [k, length])
    plan = []
    for b in buckets:
        top = b * PAGE_TOKENS
        low = (b // 2) * PAGE_TOKENS if b > 4 else 0
        inside = [p for p in prompts if low < p <= top]
        tails: dict[int, int] = {}
        for p in inside:
            if p % chunk:
                tails.setdefault(_pow2(p % chunk), p % chunk)
        plan.append({"page_bucket": b, "top": top,
                     "decoder_prompt": inside[0] if inside else low + 1,
                     "blocker": min(chunk, top),
                     "shots": [v for v in shots.values() if v[1] <= top],
                     "solo_tails": sorted(tails.values())})
    return plan


def judged_entries(traffic: dict, seed: int) -> list[int]:
    n = len(traffic["prompt_tokens"])
    want = min(traffic["judge"]["requests"], n)
    rng = np.random.default_rng([int(seed), 104729])
    return sorted(rng.choice(n, size=want, replace=False).tolist())
