"""Judge child: the plain reference against the client's logits.

Runs after the server child has exited, so it may hold the chip (one process
per chip): a 16-layer, 4096-token float32 forward is minutes on the CPU and
seconds there. In a rehearsal (JAX_PLATFORMS=cpu) it runs on the CPU.

    python cellbench/judge.py <plan.json>

The plan names the checkpoint the benchmark wrote from --seed, the model
config, and for each judged request its token ids (prompt + served tokens)
and the file of client logits, one row per position judged (the last prompt
position, then each decode step). Writes {"err_median",
"int8_projection_median", ...} and, for a traced run, the reduced trace.
"""

from __future__ import annotations

import hashlib
import json
import pathlib
import sys
import time

HERE = pathlib.Path(__file__).resolve().parent
ROOT = HERE.parent
sys.path.insert(0, str(ROOT))

import numpy as np  # noqa: E402

from cellbench import families  # noqa: E402


def reference_key(plan: dict, ids: list, rows: list) -> str:
    """Names the kept reference: configuration, seed, judged ids and rows,
    and the code that computes it (reference.py, checkpoint.py and the
    configuration's family file, by their bytes)."""
    code = (HERE / "reference.py", HERE / "checkpoint.py",
            families.path_of(plan["config"]))
    return hashlib.sha256(json.dumps([
        plan.get("uid"), plan.get("seed"), plan["config"], ids, rows,
        *(hashlib.sha256(f.read_bytes()).hexdigest() for f in code),
    ]).encode()).hexdigest()[:32]


def main(plan_path: str) -> int:
    plan = json.loads(pathlib.Path(plan_path).read_text())
    import jax

    from cellbench import reference

    # every program of the reference goes to the persistent cache, however
    # quickly it compiled (the directory comes from JAX_COMPILATION_CACHE_DIR)
    jax.config.update("jax_persistent_cache_min_compile_time_secs", 0.0)
    jax.config.update("jax_persistent_cache_min_entry_size_bytes", -1)

    t0 = time.time()
    config, ckpt = plan["config"], pathlib.Path(plan["ckpt"])
    reqs = plan["requests"]
    # one width per cell, whatever the seed chose to judge: one program,
    # found in the compile cache by every later run
    width = max(plan["width"], max(len(r["ids"]) for r in reqs))
    ids = np.zeros((len(reqs), width), np.int32)
    rows, got = [], []
    for s, r in enumerate(reqs):
        ids[s, : len(r["ids"])] = r["ids"]
        client = np.load(r["logits_file"])
        # row j of the client's logits predicted token prompt_len + j: it
        # was computed from the hidden state at position prompt_len - 1 + j
        rows += [(s, r["prompt_tokens"] - 1 + j) for j in range(len(client))]
        got.append(client)
    timing: dict = {}
    # The reference's own output, kept per (configuration, seed, judged ids,
    # reference code) inside the checkout: a later run of the same seed (the
    # second set of a check) reads it back instead of computing it again.
    key = reference_key(plan, ids.tolist(), rows)
    cache = (pathlib.Path(plan["reference_cache"]) / f"{key}.npz"
             if plan.get("reference_cache") else None)
    if cache is not None and cache.exists():
        with np.load(cache) as kept:
            ref = {"exact": kept["exact"], "int8": kept["int8"]}
        timing["from_cache"] = str(cache.name)
    else:
        with jax.default_matmul_precision("highest"):
            ref = reference.reference_logits(ckpt, config, ids, rows, timing)
        timing["platform"] = jax.devices()[0].platform
        if cache is not None:
            cache.parent.mkdir(parents=True, exist_ok=True)
            tmp = cache.with_suffix(".tmp.npz")
            np.savez(tmp, **ref)
            tmp.replace(cache)
    cmp = reference.compare(np.concatenate(got), ref)
    err, proj = cmp["err"], cmp["int8"]
    agree = float(np.mean(
        np.argmax(np.concatenate(got), -1) == np.argmax(ref["exact"], -1)
    ))
    trace = None
    if plan.get("trace_dir"):
        from cellbench import trace as tracing

        planes = tracing.load(
            tracing.find_xplane(pathlib.Path(plan["trace_dir"])))
        # a CPU rehearsal has no device plane: no device metric is made up
        trace = (tracing.reduce(planes, plan.get("traced_s"))
                 if planes else None)
    pathlib.Path(plan["out"]).write_text(json.dumps({
        "trace": trace,
        "rows": len(rows), "requests": len(reqs),
        "err_median": float(np.median(err)), "err_max": float(err.max()),
        "err_by_row": [round(float(e), 6) for e in err],
        "int8_projection_median": float(np.median(proj)),
        "int8_reference_err_median": float(np.median(cmp["control_err"])),
        "int8_projection_by_row": [round(float(e), 4) for e in proj],
        "greedy_token_agreement": agree,
        "reference": "cellbench/reference.py + families/"
                     f"{families.path_of(config).name}, float32, precision highest",
        "reference_platform": timing.get("platform", "cache"),
        "reference_seconds": round(time.time() - t0, 2),
        "reference_split_s": {
            k: round(v, 2) if isinstance(v, float) else v
            for k, v in timing.items()},
    }))
    return 0


if __name__ == "__main__":
    sys.exit(main(sys.argv[1]))
