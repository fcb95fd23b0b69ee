#!/usr/bin/env python3
"""Lower the eight cells' step programs (decode, chunk, tail, fused, at
the cells' shapes, as tests/test_chip_compile.py and tests/test_afmoe.py
build them; every chunk with the row scatter, `page_groups` off: what a
chunk that starts inside a page still runs) for a described
v5e in the tree this runs from (cwd), and print one sha256 of the StableHLO
text a program, the Pallas kernels' serialized bodies cut out (they embed the
source files' paths and line numbers: compare `git diff -- bloombee_tpu/ops/
pallas` beside it). Run it in two trees and compare: equal hashes say a
change left those programs as they were, at no chip time (PERF.md section 6,
PR 47).

    JAX_PLATFORMS=cpu python3 scripts/lowered_step_hashes.py > change.json
    (cd .try_parent && JAX_PLATFORMS=cpu python3 ../scripts/lowered_step_hashes.py) > parent.json
"""
import hashlib, json, os, sys
sys.path.insert(0, os.getcwd()); sys.path.insert(0, os.path.join(os.getcwd(), "tests"))
os.environ.setdefault("TPU_LOG_DIR", "disabled")
import jax
from jax.experimental import topologies
from jax.sharding import SingleDeviceSharding
import test_chip_compile as T
from bloombee_tpu.runtime.step import span_step_packed, span_step_ragged

one = SingleDeviceSharding(topologies.get_topology_desc(platform="tpu", topology_name="v5e:2x2").devices[0])
out = {}
def h(name, lowered):
    import re
    text = re.sub(r'\\22body\\22: \\22[^\\]*\\22', 'BODY', lowered.as_text())
    out[name] = hashlib.sha256(text.encode()).hexdigest()[:16] + f" bodies={text.count('BODY')}"

def windows(spec, n): return tuple(spec.window_for_layer(i) for i in range(n))

# mistral, qwen3moe, falconh1 (longdoc: 128-row chunks, 256-page bucket)
for cell, (config, b, _) in T._CELL_SPANS.items():
    spec, params, arena, state = T._cell_shapes(config, one)
    layers, pages = spec.num_hidden_layers, 256
    w = windows(spec, layers)
    flash = all(x == 0 for x in w)
    common = dict(spec=spec, page_size=T.PAGE, max_pages=pages, windows=w)
    extra = () if state is None else (state,)
    r, n_seqs = 256, 4
    plan_len = r + n_seqs * pages + r + n_seqs + r + layers + (0 if state is None else 3 * n_seqs + 1)
    h(f"{cell}.fused", span_step_ragged.lower(params, arena, arena, T._cell_payload(spec, r, plan_len, one), None, *extra, r=r, n_seqs=n_seqs, use_kernel=False, **common))
    for prog, (bb, t, case) in {"decode": (b, 1, dict(use_paged=True)), "chunk": (1, 128, dict(use_flash=flash, t_real=128))}.items():
        plan_len = bb * t + bb * pages + bb * t + bb + layers + (0 if state is None else bb)
        h(f"{cell}.{prog}", span_step_packed.lower(params, arena, arena, T._cell_payload(spec, bb * t, plan_len, one), None, None, *extra, b=bb, t=t, **case, **common))

# deepseek_v2
spec, params, latent, rotary = T._deepseek_shapes(one)
layers, pages = 5, 1024
common = dict(spec=spec, page_size=T.PAGE, max_pages=pages, windows=(0,) * layers)
r, n_seqs = 1024, 4
plan_len = r + n_seqs * pages + r + n_seqs + r + layers + 3 * n_seqs + 1
h("deepseekv2.fused", span_step_ragged.lower(params, latent, rotary, T._cell_payload(spec, r, plan_len, one), None, None, r=r, n_seqs=n_seqs, use_kernel=True, **common))
for prog, (b, t) in {"decode": (2, 1), "chunk": (1, 512)}.items():
    plan_len = b * t + b * pages + b * t + b + layers
    h(f"deepseekv2.{prog}", span_step_packed.lower(params, latent, rotary, T._cell_payload(spec, b * t, plan_len, one), None, None, None, b=b, t=t, use_paged=True, t_real=None if t == 1 else t, **common))

# qwen3_next
spec, params, arena, state = T._qwen3_next_shapes(one)
layers, pages = 8, 1024
common = dict(spec=spec, page_size=T.PAGE, max_pages=pages, windows=(0,) * layers)
plan_len = r + n_seqs * pages + r + n_seqs + r + layers + 3 * n_seqs + 1
h("qwen3next.fused", span_step_ragged.lower(params, arena, arena, T._cell_payload(spec, r, plan_len, one), None, state, r=r, n_seqs=n_seqs, use_kernel=True, **common))
for prog, (b, t, case) in {"decode": (4, 1, dict(use_paged=True)), "chunk": (1, 512, dict(use_flash=True, t_real=512, expert_kernels=True)), "tail": (1, 8, dict(use_paged=True, t_real=5))}.items():
    plan_len = b * t + b * pages + b * t + b + layers + b
    h(f"qwen3next.{prog}", span_step_packed.lower(params, arena, arena, T._cell_payload(spec, b * t, plan_len, one), None, None, state, b=b, t=t, **case, **common))

# phi4flash
spec, params, arena, state = T._phi4flash_shapes(one)
layers, pages = 32, 1024
common = dict(spec=spec, page_size=T.PAGE, max_pages=pages, windows=(0,) * layers)
plan_len = (r + n_seqs * pages + r + n_seqs + r + layers + 3 * n_seqs + 1 + r + 1)
h("phi4flash.fused", span_step_ragged.lower(params, arena, arena, T._cell_payload(spec, r, plan_len, one), None, state, r=r, n_seqs=n_seqs, use_kernel=True, **common))
for prog, (b, t, t_real) in {"decode": (4, 1, 1), "chunk": (1, 512, 512), "tail": (1, 8, 5)}.items():
    plan_len = b * t + b * pages + b * t + b + layers + b + (b * t + 1 if t > 1 else 0)
    h(f"phi4flash.{prog}", span_step_packed.lower(params, arena, arena, T._cell_payload(spec, b * t, plan_len, one), None, None, state, b=b, t=t, use_paged=True, t_real=t_real, **common))

# trinity (afmoe; PR 47's cell, added here in PR 49): 1024-page bucket
import test_afmoe as A
spec, params, arena = A._cell_shapes(one)
layers, pages = 5, 1024
common = dict(spec=spec, page_size=16, max_pages=pages, windows=windows(spec, layers))
for prog, (b, t, case) in {"decode": (4, 1, dict(use_paged=True)), "chunk": (1, 512, dict(use_flash=True, t_real=512, expert_kernels=True)), "tail": (1, 8, dict(use_paged=True, t_real=5))}.items():
    plan_len = b * t + b * pages + b * t + b + layers
    h(f"trinity.{prog}", span_step_packed.lower(params, arena, arena, T._cell_payload(spec, b * t, plan_len, one), None, None, None, b=b, t=t, **case, **common))
# kimi_linear (PR 51's cell, added here in PR 52): latent arena + state arena
spec, params, latent, shared_key, state = T._kimi_linear_shapes(one)
layers, pages = 8, 1024
common = dict(spec=spec, page_size=T.PAGE, max_pages=pages, windows=(0,) * layers)
plan_len = r + n_seqs * pages + r + n_seqs + r + layers + 3 * n_seqs + 1
h("kimilinear.fused", span_step_ragged.lower(params, latent, shared_key, T._cell_payload(spec, r, plan_len, one), None, state, r=r, n_seqs=n_seqs, use_kernel=True, **common))
for prog, (b, t, t_real) in {"decode": (4, 1, None), "chunk": (1, 512, 512), "tail": (1, 8, 5)}.items():
    plan_len = b * t + b * pages + b * t + b + layers + b
    h(f"kimilinear.{prog}", span_step_packed.lower(params, latent, shared_key, T._cell_payload(spec, b * t, plan_len, one), None, None, state, b=b, t=t, use_paged=True, t_real=t_real, **common))
print(json.dumps(out, indent=0))
