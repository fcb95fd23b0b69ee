#!/usr/bin/env python3
"""Plant a linear-attention or gated-attention fault in a COPY of the program,
to see that the benchmark's `correct` notices it (PERF.md section 2: the
controls of `qwen3-next-80b-ep4-span8`'s `logit_error_limit`).

    python3 scripts/plant_gdn_fault.py state_reset .try_fault_state
    cd .try_fault_state && python3 cellbench/run.py --workload \\
        qwen3next-longctx --seed 3900000403 --seconds 0 --trace 0
    # correct: false

The copy holds `bloombee_tpu/`, `cellbench/` and `BENCHMARK.json` (all
`cellbench/run.py` needs) with ONE line of the program changed
(runtime/layer_body.py):

  state_reset  a chunk with a padded tail (a prompt's LAST chunk) starts
               from an empty state S: everything the linear layers kept of
               the prompt before that chunk boundary is lost
  beta         beta left out of the update (taken as 1): every token
               overwrites what its key reads instead of blending into it
  attn_gate    the full-attention layers' output gate left out

The served tokens still come and no request fails; only the comparison with
the reference sees it. `.try*` is in `.gitignore`.
"""

from __future__ import annotations

import pathlib
import shutil
import sys

ROOT = pathlib.Path(__file__).resolve().parents[1]
BODY = pathlib.Path("bloombee_tpu") / "runtime" / "layer_body.py"
FAULTS = {
    "state_reset": (
        BODY,
        "            s0 = jnp.where(rows.fresh[c], 0.0, s0)  # gdn",
        "            s0 = jnp.where(rows.fresh[c] | (n_c < w), 0.0, s0)  # gdn",
    ),
    "beta": (
        BODY,
        "        beta = jax.nn.sigmoid(b)",
        "        beta = jnp.ones_like(b)",
    ),
    "attn_gate": (
        BODY,
        "            attn = attn * jax.nn.sigmoid(gate).astype(attn.dtype)",
        "            attn = attn * 1",
    ),
}


def plant(tree: pathlib.Path, kind: str) -> None:
    """Change the one line of `tree`'s program that `kind` names."""
    where, sound, broken = FAULTS[kind]
    path = tree / where
    text = path.read_text()
    if text.count(sound) != 1:
        raise SystemExit(f"{path}: expected the sound line once: {sound!r}")
    path.write_text(text.replace(sound, broken))


def main(argv: list[str]) -> int:
    kind, tree = argv[0], pathlib.Path(argv[1])
    shutil.rmtree(tree, ignore_errors=True)
    tree.mkdir(parents=True)
    junk = shutil.ignore_patterns("__pycache__")
    for name in ("bloombee_tpu", "cellbench"):
        shutil.copytree(ROOT / name, tree / name, ignore=junk)
    shutil.copy(ROOT / "BENCHMARK.json", tree / "BENCHMARK.json")
    plant(tree, kind)
    return 0


if __name__ == "__main__":
    sys.exit(main(sys.argv[1:]))
