"""The chip's compiler, without the chip: AOT compiles for a DESCRIBED TPU v5e.

Interpret-mode tests cannot see what Mosaic refuses (an unsupported cast, a
load of a dtype the TPU has no vector type for, too much VMEM, an unaligned
slice). The TPU compiler is installed wherever libtpu is, and compiles for a
topology that is described, not attached — so every Pallas kernel on the
served path is compiled here at Llama-3-8B head geometry, plus whole span
steps over the 8-layer span chip_smoke.py serves, with the kernels on. A
compile that passes is not a chip run; it only says the chip's compiler
takes the program.

Steering: the traced code picks interpret mode from the BBTPU_*_INTERPRET
test switches alone, so the tests pin those off and compile the jitted
functions themselves (the executor's `jax.default_backend()` gate would
route a CPU process around the kernels). The persistent compile cache is off
around the file: an entry compiled for a described device cannot be read
back without one and only produces warnings.
"""

import os

os.environ.setdefault("TPU_LOG_DIR", "disabled")  # or libtpu logs to /tmp

import functools  # noqa: E402

import jax  # noqa: E402
import jax.numpy as jnp  # noqa: E402
import pytest  # noqa: E402
from jax.sharding import (  # noqa: E402
    Mesh,
    NamedSharding,
    PartitionSpec as P,
    SingleDeviceSharding,
)

from bloombee_tpu.kv.quant import QuantSlab  # noqa: E402
from bloombee_tpu.models.llama.block import init_block_params  # noqa: E402
from bloombee_tpu.models.spec import ModelSpec  # noqa: E402
from bloombee_tpu.ops.pallas.flash_attention import (  # noqa: E402
    flash_attention,
)
from bloombee_tpu.ops.pallas.grouped_experts import (  # noqa: E402
    grouped_experts,
    tiled_experts,
)
from bloombee_tpu.ops.pallas.paged_attention import (  # noqa: E402
    paged_chunk_attention,
    paged_decode_attention,
    paged_decode_attention_int4,
    paged_ragged_attention,
)
from bloombee_tpu.runtime.layer_body import chunk_run_pages  # noqa: E402
from bloombee_tpu.runtime.step import (  # noqa: E402
    span_step_packed,
    span_step_ragged,
)
from bloombee_tpu.utils.tree import stack_params  # noqa: E402

# Llama-3-8B head geometry and the arena chip_smoke.py serves with
H, HKV, HD, D = 32, 8, 128, 4096
PAGE, NUM_PAGES, LAYERS = 16, 1024, 8
S_TOT = PAGE * NUM_PAGES
B, NP = 8, 64  # 8 sequences, 64-page (1024-token) context bucket
SPEC = ModelSpec(
    family="llama", hidden_size=D, intermediate_size=14336,
    num_attention_heads=H, num_key_value_heads=HKV, head_dim=HD,
    num_hidden_layers=LAYERS, vocab_size=128256, rope_theta=500000.0,
)
i32, bf16 = jnp.int32, jnp.bfloat16


@pytest.fixture(scope="module")
def v5e():
    """The four devices of a described v5e 2x2 host (skip where libtpu
    cannot describe one), with the persistent compile cache switched off."""
    from jax.experimental import topologies
    from jax.experimental.compilation_cache import compilation_cache as cc

    try:
        devices = topologies.get_topology_desc(
            platform="tpu", topology_name="v5e:2x2"
        ).devices
    except Exception as e:  # noqa: BLE001 — no libtpu / unknown topology
        pytest.skip(f"cannot describe a TPU v5e here: {e!r}")
    was_on = jax.config.jax_enable_compilation_cache
    jax.config.update("jax_enable_compilation_cache", False)
    cc.reset_cache()
    yield devices
    jax.config.update("jax_enable_compilation_cache", was_on)
    cc.reset_cache()


@pytest.fixture(autouse=True)
def _compiled_not_interpreted(monkeypatch):
    for switch in ("BBTPU_PAGED_INTERPRET", "BBTPU_FLASH_INTERPRET"):
        monkeypatch.delenv(switch, raising=False)


def _compile(fn, *shapes):
    compiled = jax.jit(fn).lower(*shapes).compile()
    assert "tpu_custom_call" in compiled.as_text(), "no Mosaic kernel inside"
    return compiled


def _kernel_cases():
    """name -> (fn, shapes as (shape, dtype) tuples or QuantSlab of them)."""
    slab = ((S_TOT, HKV, HD), bf16)
    qslab = QuantSlab(
        ((S_TOT, HKV, HD // 2), jnp.uint8),
        ((S_TOT, HKV, HD // 32), jnp.float16),
        ((S_TOT, HKV, HD // 32), jnp.float16),
    )
    pt, lens = ((B, NP), i32), ((B,), i32)
    cases = {
        "paged_decode": (
            functools.partial(paged_decode_attention, page_size=PAGE),
            [((B, H, HD), bf16), slab, slab, pt, lens],
        ),
        "paged_decode_int4": (
            functools.partial(paged_decode_attention_int4, page_size=PAGE),
            [((B, H, HD), bf16), qslab, qslab, pt, lens],
        ),
        "flash_t128": (  # one --prefill-chunk 128 chunk
            lambda q, k, v, st, ln: flash_attention(
                q, k, v, causal=True, starts=st, lens=ln
            ),
            [((1, 128, H, HD), bf16), ((1, 256, HKV, HD), bf16),
             ((1, 256, HKV, HD), bf16), ((1,), i32), ((1,), i32)],
        ),
        "flash_t512": (  # max_chunk_tokens
            lambda q, k, v, st, ln: flash_attention(
                q, k, v, causal=True, starts=st, lens=ln
            ),
            [((1, 512, H, HD), bf16), ((1, 512, HKV, HD), bf16),
             ((1, 512, HKV, HD), bf16), ((1,), i32), ((1,), i32)],
        ),
    }
    # the cells' flash calls, one sequence's chunk each, at the tile
    # `flash_tiles` gives the shape and over the keys the serving path
    # gathers (`chunk_run_pages` at the cell's page bucket: 5,120 under
    # Trinity's window, 1,536 under phi4flash's, the bucket without one):
    # Trinity's window and full layers, Qwen3-Next's full layer at head_dim
    # 256, phi4flash's windowed call on query halves with the float32
    # output and its full layer, the two 128-row cells; and the two window
    # runs as they were gathered until PR 48 (37 and 9 K blocks of 128: the
    # only block that divides them)
    for name, (t, keys, heads, kv_heads, hd, window, out) in {
        "trinity_window": (512, 1024 * PAGE, 48, 8, 128, 4096, None),
        "trinity_full": (512, 1024 * PAGE, 48, 8, 128, 0, None),
        "qwen3next_full": (512, 1024 * PAGE, 16, 2, 256, 0, None),
        "phi4flash_window": (512, 1024 * PAGE, 40, 10, 128, 512, jnp.float32),
        "phi4flash_full": (512, 1024 * PAGE, 40, 10, 128, 0, jnp.float32),
        "falconh1_t128": (128, 256 * PAGE, 20, 4, 128, 0, None),
        "qwen3moe_t128": (128, 256 * PAGE, 32, 4, 128, 0, None),
        "trinity_window_4736": (512, 4736, 48, 8, 128, 4096, None),
        "phi4flash_window_1152": (512, 1152, 40, 10, 128, 512, jnp.float32),
    }.items():
        s = PAGE * chunk_run_pages(t, window, PAGE, keys // PAGE)
        cases[f"flash_{name}"] = (
            functools.partial(
                lambda q, k, v, st, ln, window, out: flash_attention(
                    q, k, v, causal=True, starts=st, lens=ln, window=window,
                    out_dtype=out,
                ), window=window, out=out),
            [((1, t, heads, hd), bf16), ((1, s, kv_heads, hd), bf16),
             ((1, s, kv_heads, hd), bf16), ((1,), i32), ((1,), i32)],
        )
    # the cells' decode steps: one to four rows at the 4096-token page
    # bucket of a 1280-page arena, 8 pages a grid step at 8 KV heads and
    # one at 4 (`_pages_per_step`)
    for name, (heads, kv_heads) in {
        "mistral": (32, 8), "falconh1": (20, 4), "qwen3": (32, 4),
    }.items():
        cell_slab = ((1280 * PAGE, kv_heads, HD), bf16)
        for rows in (1, 4):
            cases[f"paged_decode_{name}_b{rows}_p256"] = (
                functools.partial(paged_decode_attention, page_size=PAGE),
                [((rows, heads, HD), bf16), cell_slab, cell_slab,
                 ((rows, 256), i32), ((rows,), i32)],
            )
    # phi4flash's: the 1024-page bucket of the cell's 5376-page arena, 40
    # query halves of 128 in float32 (`_diff_attend` widens them) against 10
    # K/V pairs: four 160-row bfloat16 blocks a grid step
    # (the grid's extent is a traced scalar since PR 56, `walk_bounds`: by
    # the rows' lengths alone, and in the `_w512` cases by a window that is
    # an operand too, as a window layer's call hands it over)
    phi_slab = ((5376 * PAGE, 10, HD), bf16)
    for rows in (1, 4):
        phi_call = [((rows, 40, HD), jnp.float32), phi_slab, phi_slab,
                    ((rows, 1024), i32), ((rows,), i32)]
        cases[f"paged_decode_phi4flash_b{rows}_p1024"] = (
            functools.partial(paged_decode_attention, page_size=PAGE),
            phi_call,
        )
        cases[f"paged_decode_phi4flash_b{rows}_p1024_w512"] = (
            lambda q, k, v, p, ln, w: paged_decode_attention(
                q, k, v, p, ln, page_size=PAGE, window=w),
            phi_call + [((), i32)],
        )
    # T=64 and R=64 sit on the executor's `rows * H <= 2048` VMEM edge
    for t in (16, 64):
        cases[f"paged_chunk_t{t}"] = (
            functools.partial(paged_chunk_attention, page_size=PAGE),
            [((B, t, H, HD), bf16), slab, slab, pt, lens],
        )
        cases[f"paged_chunk_tree_t{t}"] = (
            lambda q, k, v, p, ln, tm: paged_chunk_attention(
                q, k, v, p, ln, page_size=PAGE, tree_mask=tm, has_tree=True
            ),
            [((B, t, H, HD), bf16), slab, slab, pt, lens,
             ((B, t, t), jnp.bool_)],
        )
    for r in (16, 64):
        rows = [((r, H, HD), bf16), slab, slab, pt, lens, ((r,), i32),
                ((r,), i32)]
        cases[f"paged_ragged_r{r}"] = (
            functools.partial(paged_ragged_attention, page_size=PAGE), rows,
        )
        cases[f"paged_ragged_tree_r{r}"] = (
            lambda q, k, v, p, ln, qs, qp, nt, tr: paged_ragged_attention(
                q, k, v, p, ln, qs, qp, page_size=PAGE, nt=nt, tree_rows=tr,
                has_tree=True,
            ),
            rows + [((B,), i32), ((r, 16), i32)],
        )
    # a decode group's experts by index: 2 rows x top-8 of Qwen3-30B-A3B's
    # 128 experts over a 4-layer stack (one [D, I] block an expert), and 3
    # rows x top-2 at Mixtral-8x7B's widths (the intermediate dim in tiles)
    for name, (layers_experts, d, i, rows, slots) in {
        "grouped_experts_qwen3": (4 * 128, 2048, 768, 2, 16),
        "grouped_experts_mixtral": (8, 4096, 14336, 3, 6),
    }.items():
        cases[name] = (
            grouped_experts,
            [((rows, d), bf16), ((slots,), i32), ((), i32),
             ((slots, rows), jnp.float32), ((layers_experts, d, i), bf16),
             ((layers_experts, d, i), bf16), ((layers_experts, i, d), bf16)],
        )
    # a 512-row chunk's chosen pairs in row tiles: top-10 over the 128 held
    # experts of Qwen3-Next (one [D, I] block an expert, 8 layers' stacks)
    # and top-6 over DeepSeek-V2's 20 held (the intermediate dim in tiles, 4
    # layers' stacks); a 1024-row fused pack at DeepSeek-V2's widths, where
    # the rows and their sums take the most VMEM
    for name, (layers_experts, d, i, rows, top_k, slots) in {
        "tiled_experts_qwen3next": (8 * 128, 2048, 512, 512, 10, 128),
        "tiled_experts_deepseekv2": (4 * 20, 5120, 1536, 512, 6, 20),
        "tiled_experts_deepseekv2_r1024": (4 * 20, 5120, 1536, 1024, 6, 20),
    }.items():
        cases[name] = (
            tiled_experts,
            [((rows, d), bf16), ((slots,), i32), ((), i32), ((slots,), i32),
             ((slots,), i32), ((rows * top_k,), i32),
             ((rows * top_k,), jnp.float32), ((layers_experts, d, i), bf16),
             ((layers_experts, d, i), bf16), ((layers_experts, i, d), bf16)],
        )
    return cases


_KERNELS = _kernel_cases()


@pytest.mark.parametrize("name", sorted(_KERNELS))
def test_kernel_compiles_for_v5e(v5e, name):
    one_chip = SingleDeviceSharding(v5e[0])

    def described(leaf):
        shape, dtype = leaf
        return jax.ShapeDtypeStruct(shape, dtype, sharding=one_chip)

    fn, shapes = _KERNELS[name]
    _compile(fn, *[
        QuantSlab(*map(described, s)) if isinstance(s, QuantSlab)
        else described(s)
        for s in shapes
    ])


def _span_shapes(sharding_for):
    """(params, arena) ShapeDtypeStructs for the 8-layer span at 8B widths;
    sharding_for(kind, name) places each leaf."""
    params = jax.eval_shape(
        lambda: stack_params([
            init_block_params(jax.random.PRNGKey(0), SPEC, dtype=bf16)
            for _ in range(LAYERS)
        ])
    )
    params = {
        k: jax.ShapeDtypeStruct(
            v.shape, v.dtype, sharding=sharding_for("param", k)
        )
        for k, v in params.items()
    }
    arena = jax.ShapeDtypeStruct(
        (LAYERS, S_TOT, HKV, HD), bf16, sharding=sharding_for("arena", "")
    )
    return params, arena


def _payload(hidden_rows: int, plan_len: int, sharding):
    # pack_step_payload: bf16 hidden + int32 plan as uint16 lanes
    return jax.ShapeDtypeStruct(
        (hidden_rows * D + 2 * plan_len,), jnp.uint16, sharding=sharding
    )


def _packed_plan_len(b: int, t: int, pages: int) -> int:
    return b * t + b * pages + b * t + b + LAYERS


_SPAN_STEPS = {
    # decode through the paged kernel, prefill chunk through flash, a short
    # chunk through the chunk kernel (what warm-up + chip_smoke dispatch)
    "decode_paged": dict(b=B, t=1, pages=NP, use_paged=True),
    # window layers among full ones in one stack (the Mistral / Trinity
    # shape): the layer scan hands the kernel each layer's window as a
    # traced scalar, and the walk's extent follows it (PR 56)
    "decode_paged_windows": dict(
        b=B, t=1, pages=NP, use_paged=True,
        windows=(512, 512, 512, 0) * (LAYERS // 4)),
    # (both chunks' rows come as page groups: written by page, PR 49)
    "prefill_flash": dict(
        b=1, t=128, pages=8, use_flash=True, page_groups=True),
    "chunk_paged": dict(
        b=1, t=64, pages=32, use_paged=True, t_real=44, page_groups=True),
}


@pytest.mark.parametrize("name", sorted(_SPAN_STEPS))
def test_span_step_compiles_for_v5e(v5e, name):
    one_chip = SingleDeviceSharding(v5e[0])
    case = dict(_SPAN_STEPS[name])
    b, t, pages = case.pop("b"), case.pop("t"), case.pop("pages")
    case.setdefault("windows", (0,) * LAYERS)
    params, arena = _span_shapes(lambda kind, key: one_chip)
    compiled = span_step_packed.lower(
        params, arena, arena,
        _payload(b * t, _packed_plan_len(b, t, pages), one_chip),
        None, None,
        spec=SPEC, b=b, t=t, page_size=PAGE, max_pages=pages, **case,
    ).compile()
    assert "tpu_custom_call" in compiled.as_text()


@pytest.mark.parametrize("b", [1, 2, 8])
def test_moe_decode_step_reads_the_expert_stacks_where_they_lie(v5e, b):
    """A decode group of a 4-layer span at Qwen3-30B-A3B's widths: the
    grouped kernel beside the paged one, and no temporary the size of a
    layer's expert stack (402 MB): the stacks do not ride the scan."""
    one_chip = SingleDeviceSharding(v5e[0])
    layers, d, e, i, heads = 4, 2048, 128, 768, 32
    spec = ModelSpec(
        family="qwen3_moe", hidden_size=d, intermediate_size=i,
        num_attention_heads=heads, num_key_value_heads=4, head_dim=128,
        num_hidden_layers=layers, vocab_size=151936, rope_theta=1e6,
        qk_norm=True, num_experts=e, num_experts_per_tok=8,
        moe_pre_softmax=True, moe_norm_topk=True,
    )
    s = lambda *shape: jax.ShapeDtypeStruct(  # noqa: E731
        (layers, *shape), bf16, sharding=one_chip)
    params = {
        "input_layernorm": s(d), "post_attention_layernorm": s(d),
        "q_proj": s(heads * 128, d), "k_proj": s(512, d),
        "v_proj": s(512, d), "o_proj": s(heads * 128, d),
        "q_norm": s(128), "k_norm": s(128), "router": s(d, e),
        "experts_gate": s(e, d, i), "experts_up": s(e, d, i),
        "experts_down": s(e, i, d),
    }
    arena = s(S_TOT, 4, 128)
    pages = 256
    plan_len = b + b * pages + b + b + layers
    payload = jax.ShapeDtypeStruct(
        (b * d + 2 * plan_len,), jnp.uint16, sharding=one_chip)
    compiled = span_step_packed.lower(
        params, arena, arena, payload, None, None,
        spec=spec, b=b, t=1, page_size=PAGE, max_pages=pages,
        windows=(0,) * layers, use_paged=True,
    ).compile()
    assert compiled.as_text().count("tpu_custom_call") == 2
    assert compiled.memory_analysis().temp_size_in_bytes < 200e6


@pytest.mark.parametrize("tree", [False, True], ids=["causal", "tree"])
def test_span_step_ragged_compiles_for_v5e(v5e, tree):
    """One fused decode + chunk dispatch (R=64: the widest row bucket the
    kernel gate admits at 32 heads), causal and tree-verify variants."""
    one_chip = SingleDeviceSharding(v5e[0])
    r, n_seqs, pages, t_max = 64, 2, 32, (16 if tree else 0)
    plan_len = r + n_seqs * pages + r + n_seqs + r + LAYERS
    if tree:
        plan_len += n_seqs + r * t_max
    params, arena = _span_shapes(lambda kind, key: one_chip)
    compiled = span_step_ragged.lower(
        params, arena, arena, _payload(r, plan_len, one_chip), None,
        spec=SPEC, r=r, n_seqs=n_seqs, page_size=PAGE, max_pages=pages,
        windows=(0,) * LAYERS, use_kernel=True, t_max=t_max,
    ).compile()
    assert "tpu_custom_call" in compiled.as_text()


def test_tp4_span_step_compiles_and_shards_for_v5e(v5e):
    """The --tp 4 serving step (GSPMD over the 2x2 host, dense attention:
    Pallas kernels stay off under a mesh) compiles for four chips, and each
    chip holds a quarter of the span's weights and arena."""
    from bloombee_tpu.parallel.serving import ARENA_SPEC, SERVING_PARAM_SPECS

    mesh = Mesh(v5e, ("tp",))
    replicated = NamedSharding(mesh, P())

    def sharding_for(kind, key):
        spec = ARENA_SPEC if kind == "arena" else SERVING_PARAM_SPECS[key]
        return NamedSharding(mesh, spec)

    params, arena = _span_shapes(sharding_for)
    compiled = span_step_packed.lower(
        params, arena, arena,
        _payload(B, _packed_plan_len(B, 1, NP), replicated), None, None,
        spec=SPEC, b=B, t=1, page_size=PAGE, max_pages=NP,
        windows=(0,) * LAYERS,
    ).compile()
    assert "all-reduce" in compiled.as_text()  # the Megatron psums
    total = sum(
        v.size * v.dtype.itemsize for v in [*params.values(), arena, arena]
    )
    per_chip = compiled.memory_analysis().argument_size_in_bytes
    assert per_chip < 0.3 * total, (per_chip, total)


# --------------------------------------------------------------- falcon_h1
# a state-space mixer beside attention: the span steps of the benchmark's
# cell (cellbench/configs/falcon-h1-34b-span8.json: published widths, 8
# layers, 1280 pages, 16 state slots) with the recurrent-state arena in the
# carry, the attention kernels on
def _cell_shapes(config_name, one_chip, pages=1280):
    """(spec, params, arena, state) ShapeDtypeStructs of a benchmark cell's
    span as the loaders store it (models/layout.py): q/k/v output-major,
    the mixer's in_proj padded to whole lanes."""
    import json
    import pathlib

    from bloombee_tpu.kv.cache_manager import state_slots_for
    from bloombee_tpu.models.auto import spec_from_config_dict
    from bloombee_tpu.models.layout import lane_padded

    config = json.loads((
        pathlib.Path(__file__).resolve().parents[1]
        / f"cellbench/configs/{config_name}.json").read_text())
    config.pop("cellbench")
    spec = spec_from_config_dict(config)
    ssm, layers = spec.ssm, spec.num_hidden_layers
    d, i, hd = spec.hidden_size, spec.intermediate_size, spec.head_dim
    h, kv = spec.num_attention_heads, spec.num_key_value_heads
    f32 = jnp.float32

    def s(shape, dtype=bf16):
        return jax.ShapeDtypeStruct((layers, *shape), dtype, sharding=one_chip)

    params = {
        "input_layernorm": s((d,)), "post_attention_layernorm": s((d,)),
        "q_proj": s((h * hd, d)), "k_proj": s((kv * hd, d)),
        "v_proj": s((kv * hd, d)), "o_proj": s((h * hd, d)),
    }
    if spec.qk_norm:
        params.update(q_norm=s((hd,)), k_norm=s((hd,)))
    if spec.num_experts:
        e = spec.num_experts
        params.update(
            router=s((d, e)), experts_gate=s((e, d, i)),
            experts_up=s((e, d, i)), experts_down=s((e, i, d)))
    else:
        params.update(
            gate_proj=s((d, i)), up_proj=s((d, i)), down_proj=s((i, d)))
    arena = s((pages * PAGE, kv, hd))
    if ssm is None:
        return spec, params, arena, None
    params.update({
        "ssm_in_proj": s((d, lane_padded(ssm.proj_dim))),
        "ssm_out_proj": s((ssm.d_ssm, d)),
        "ssm_conv_w": s((ssm.conv, ssm.conv_dim)),
        "ssm_conv_b": s((ssm.conv_dim,)), "ssm_norm": s((ssm.d_ssm,)),
        "ssm_a_log": s((ssm.heads,), f32), "ssm_d": s((ssm.heads,), f32),
        "ssm_dt_bias": s((ssm.heads,), f32),
    })
    slots = state_slots_for(spec, pages, PAGE, 8)
    state = {
        "ssm": s((slots, ssm.heads, ssm.head_dim, ssm.state), f32),
        "conv": s((slots, ssm.conv - 1, ssm.conv_dim)),
    }
    return spec, params, arena, state


def _cell_payload(spec, rows, plan_len, sharding):
    return jax.ShapeDtypeStruct(
        (rows * spec.hidden_size + 2 * plan_len,), jnp.uint16,
        sharding=sharding)


_FALCON_H1_STEPS = {
    # 4096-token page bucket, as the cell's contexts: a decode group through
    # the paged kernel (one recurrence step a row), a full chunk through
    # flash (the SSD chunk form), a tail through the chunk kernel
    "decode_paged": dict(b=4, t=1, pages=256, use_paged=True),
    # (both chunks' rows come as page groups: written by page, PR 49)
    "prefill_flash": dict(
        b=1, t=128, pages=256, use_flash=True, t_real=128, page_groups=True),
    "tail_paged": dict(
        b=1, t=64, pages=256, use_paged=True, t_real=44, page_groups=True),
}


@pytest.mark.parametrize("name", sorted(_FALCON_H1_STEPS))
def test_falcon_h1_span_step_compiles_for_v5e(v5e, name):
    one_chip = SingleDeviceSharding(v5e[0])
    spec, params, arena, state = _cell_shapes("falcon-h1-34b-span8", one_chip)
    case = dict(_FALCON_H1_STEPS[name])
    b, t, pages = case.pop("b"), case.pop("t"), case.pop("pages")
    layers = spec.num_hidden_layers
    plan_len = b * t + b * pages + b * t + b + layers + b  # + state slots
    compiled = span_step_packed.lower(
        params, arena, arena, _cell_payload(spec, b * t, plan_len, one_chip),
        None, None, state,
        spec=spec, b=b, t=t, page_size=PAGE, max_pages=pages,
        windows=(0,) * layers, **case,
    ).compile()
    assert "tpu_custom_call" in compiled.as_text()
    # the state arena comes back: donated in, aliased out
    assert len(compiled.output_shardings) == 4


@pytest.mark.parametrize("r,use_kernel", [(256, False), (64, True)],
                         ids=["chunk128+decodes", "tail+decodes-kernel"])
def test_falcon_h1_ragged_step_compiles_for_v5e(v5e, r, use_kernel):
    """A chunk fused with decode rows: 128 + k rows make the 256 bucket
    (20 heads x 256 rows is past the ragged kernel's gate: dense attention),
    a short tail + k rows the 64 bucket (the ragged kernel)."""
    one_chip = SingleDeviceSharding(v5e[0])
    spec, params, arena, state = _cell_shapes("falcon-h1-34b-span8", one_chip)
    n_seqs, pages, layers = 4, 256, spec.num_hidden_layers
    plan_len = r + n_seqs * pages + r + n_seqs + r + layers + 3 * n_seqs + 1
    compiled = span_step_ragged.lower(
        params, arena, arena, _cell_payload(spec, r, plan_len, one_chip),
        None, state,
        spec=spec, r=r, n_seqs=n_seqs, page_size=PAGE, max_pages=pages,
        windows=(0,) * layers, use_kernel=use_kernel,
    ).compile()
    assert ("tpu_custom_call" in compiled.as_text()) == use_kernel


# ------------------------------------------------- stored layouts (PR 34)
# A stacked weight lies on the device the way the step programs read it
# (models/layout.py), so no span-step program of a benchmark cell holds a
# copy of a parameter: stored [L, in, out], q/k/v were re-laid out before
# the layer scan in every run (Mistral decode: 806 MB of temporaries), and
# Falcon-H1's in_proj likewise at 9248 columns (758 MB).
_CELL_SPANS = {
    # cell -> (config, decode rows, temporaries' bound by program in MB;
    # a fused pack's dense attention scores are its temporaries: no bound)
    "mistral": ("mistral-7b-span16", 2, {"decode": 50, "chunk": 50}),
    "qwen3moe": ("qwen3-30b-a3b-span4", 2, {"decode": 20, "chunk": 20}),
    "falconh1": ("falcon-h1-34b-span8", 4, {"decode": 600, "chunk": 50}),
}


@pytest.mark.parametrize("program", ["decode", "chunk", "fused"])
@pytest.mark.parametrize("cell", sorted(_CELL_SPANS))
def test_cell_span_step_copies_no_parameter(v5e, cell, program):
    """The three cells' spans x (decode group at the 4096-token page bucket,
    128-row chunk through flash, 256-row fused pack): the compiled text
    holds no `copy` of a `stacked_params` parameter, and the temporaries
    stay under what the program's activations need."""
    import re

    one_chip = SingleDeviceSharding(v5e[0])
    config, b, bounds = _CELL_SPANS[cell]
    spec, params, arena, state = _cell_shapes(config, one_chip)
    layers, pages = spec.num_hidden_layers, 256
    common = dict(
        spec=spec, page_size=PAGE, max_pages=pages, windows=(0,) * layers)
    extra = () if state is None else (state,)
    if program == "fused":
        r, n_seqs = 256, 4
        plan_len = r + n_seqs * pages + r + n_seqs + r + layers
        plan_len += 0 if state is None else 3 * n_seqs + 1
        compiled = span_step_ragged.lower(
            params, arena, arena,
            _cell_payload(spec, r, plan_len, one_chip), None, *extra,
            r=r, n_seqs=n_seqs, use_kernel=False, **common,
        ).compile()
    else:
        b, t, case = (
            (b, 1, dict(use_paged=True)) if program == "decode"
            else (1, 128, dict(
                use_flash=True, t_real=128, page_groups=True))
        )
        plan_len = b * t + b * pages + b * t + b + layers
        plan_len += 0 if state is None else b
        compiled = span_step_packed.lower(
            params, arena, arena,
            _cell_payload(spec, b * t, plan_len, one_chip), None, None,
            *extra, b=b, t=t, **case, **common,
        ).compile()
    text = compiled.as_text()
    assert "%stacked_params__q_proj" in text  # the names the check reads
    copied = re.findall(r"copy\([^)\n]*%(stacked_params\w+)", text)
    assert not copied, copied
    if program != "decode":
        # 128 and 256 rows lie under the ridge or run no kernel: the dense
        # form, as before the tiled one existed (ops/moe.py `expert_form`)
        assert not re.search(r"jit\((tiled|grouped)_experts\)", text)
    if program in bounds:
        temp = compiled.memory_analysis().temp_size_in_bytes
        assert temp < bounds[program] * 1e6, temp


# ------------------------------------------------------------- deepseek_v2
# latent attention with a latent page in the arena, a dense layer before
# sparse ones in one step program, 20 of 160 experts held: the span steps of
# the benchmark's cell (cellbench/configs/deepseek-v2-ep8-span5.json:
# published widths, layer 0 and four sparse layers, 5376 pages) at the
# 1024-page bucket its contexts take
def _deepseek_shapes(one_chip, pages=5376):
    import dataclasses
    import json
    import pathlib

    from bloombee_tpu.models.auto import spec_from_config_dict
    from bloombee_tpu.models.layout import LEAD

    config = json.loads((
        pathlib.Path(__file__).resolve().parents[1]
        / "cellbench/configs/deepseek-v2-ep8-span5.json").read_text())
    held = tuple(config["experts_held"])
    spec = dataclasses.replace(
        spec_from_config_dict(config), num_experts=config["router_experts"],
        moe_held=held)
    d, h, m = spec.hidden_size, spec.num_attention_heads, spec.mla

    def s(n, *shape):
        return jax.ShapeDtypeStruct((n, *shape), bf16, sharding=one_chip)

    def attention(n):
        return {
            "input_layernorm": s(n, d), "post_attention_layernorm": s(n, d),
            "q_a_norm": s(n, m.q_rank), "kv_a_norm": s(n, m.kv_rank),
            "q_a_proj": s(n, m.q_rank, d),
            "q_b_nope": s(n, h * m.nope_dim, m.q_rank),
            "q_b_rope": s(n, h * m.rope_dim, m.q_rank),
            "kv_a_proj": s(n, m.kv_rank + m.rope_dim, d),
            "kv_b_k": s(n, h, m.nope_dim, m.kv_rank),
            "kv_b_v": s(n, h, m.v_dim, m.kv_rank),
            "o_proj": s(n, h * m.v_dim, d),
        }

    i, e, si = spec.moe_intermediate_size, held[1], spec.moe_shared_intermediate
    dense = {**attention(1), "gate_proj": s(1, d, spec.intermediate_size),
             "up_proj": s(1, d, spec.intermediate_size),
             "down_proj": s(1, spec.intermediate_size, d)}
    sparse = {**attention(4), "router_t": s(4, spec.num_experts, d),
              "experts_gate": s(4, e, d, i), "experts_up": s(4, e, d, i),
              "experts_down": s(4, e, i, d), "shared_gate": s(4, d, si),
              "shared_up": s(4, d, si), "shared_down": s(4, si, d)}
    params = {**{LEAD + k: v for k, v in dense.items()}, **sparse}
    latent, rotary = m.page_payload
    return (spec, params, s(5, pages * PAGE, *latent),
            s(5, pages * PAGE, *rotary))


@pytest.mark.parametrize("name", ["decode", "flash_t512", "flash_t8"])
def test_latent_attention_kernel_compiles_for_v5e(v5e, name):
    """The two kernels of ops/pallas/latent_attention.py at the published
    widths (128 heads, a 512-wide latent, the rotary key in whole lanes)."""
    from bloombee_tpu.ops.pallas.latent_attention import (
        latent_flash_attention,
        paged_decode_attention_latent,
    )

    def s(shape, dtype=bf16):
        return jax.ShapeDtypeStruct(
            shape, dtype, sharding=SingleDeviceSharding(v5e[0]))

    if name == "decode":
        fn = functools.partial(
            paged_decode_attention_latent, page_size=PAGE, scale=0.1147)
        shapes = [s((8, 128, 512)), s((8, 128, 128)), s((5 * 5376 * PAGE, 512)),
                  s((5 * 5376 * PAGE, 128)), s((8, 1024), i32), s((8,), i32)]
    else:
        t = int(name.removeprefix("flash_t"))
        fn = functools.partial(latent_flash_attention, scale=0.1147)
        shapes = [s((128, t, 512)), s((128, t, 128)), s((16384, 512)),
                  s((16384, 128)), s((), i32), s((), i32), s((), i32)]
    assert "tpu_custom_call" in jax.jit(fn).lower(
        *shapes).compile().as_text()


@pytest.mark.parametrize("program", ["decode", "chunk", "fused"])
def test_deepseek_v2_span_step_compiles_and_copies_no_parameter(v5e, program):
    """The cell's three step programs (a decode group, a solo 512-row chunk,
    a 1024-row fused pack; 1024-page bucket, kernels on): ONE program runs
    layer 0's dense MLP and the four sparse layers over one flat latent
    arena; the compiled text holds no `copy` of a `stacked_params` parameter
    and no slice or copy of a whole arena, and the temporaries stay what the
    rows' activations need."""
    import re

    one_chip = SingleDeviceSharding(v5e[0])
    spec, params, latent, rotary = _deepseek_shapes(one_chip)
    layers, pages = 5, 1024
    common = dict(
        spec=spec, page_size=PAGE, max_pages=pages, windows=(0,) * layers)
    if program == "fused":
        r, n_seqs = 1024, 4
        plan_len = r + n_seqs * pages + r + n_seqs + r + layers + 3 * n_seqs + 1
        compiled = span_step_ragged.lower(
            params, latent, rotary, _cell_payload(spec, r, plan_len, one_chip),
            None, None, r=r, n_seqs=n_seqs, use_kernel=True, **common,
        ).compile()
    else:
        b, t = (2, 1) if program == "decode" else (1, 512)
        plan_len = b * t + b * pages + b * t + b + layers
        compiled = span_step_packed.lower(
            params, latent, rotary,
            _cell_payload(spec, b * t, plan_len, one_chip), None, None, None,
            b=b, t=t, use_paged=True, t_real=None if t == 1 else t,
            page_groups=t > 1, **common,
        ).compile()
    text = compiled.as_text()
    assert "%stacked_params__lead_q_a_proj" in text  # the names read below
    assert "%stacked_params__router_t" in text
    copied = re.findall(r"copy\([^)\n]*%(stacked_params\w+)", text)
    assert not copied, copied
    assert not re.findall(r"copy\([^)\n]*%arena_[kv]", text)
    kernels = text.count("tpu_custom_call")
    # decode: the paged latent kernel in both runs + the grouped experts;
    # chunk: the flash form in both runs + the tiled experts (512 rows lie
    # above the ridge); fused: both kernels in both runs + the tiled experts
    assert kernels == {"decode": 3, "chunk": 3, "fused": 5}[program]
    assert ("jit(tiled_experts)" in text) == (program != "decode")
    assert ("jit(grouped_experts)" in text) == (program == "decode")
    # no temporary the size of a layer's held stack (944 MB)
    temp = compiled.memory_analysis().temp_size_in_bytes
    assert temp < {"decode": 20, "chunk": 300, "fused": 600}[program] * 1e6


# -------------------------------------------------------------- qwen3_next
# gated-DeltaNet layers with a gated full-attention layer every fourth, two
# cache kinds in one span, 128 of 512 experts held: the span steps of the
# benchmark's cell (cellbench/configs/qwen3-next-80b-ep4-span8.json:
# published widths, two periods, 5376 pages) at the 1024-page bucket its
# contexts take. The K/V arena has a row a FULL layer (2), the state arena a
# row a LINEAR one (6); head_dim 256 with 2 KV heads through every kernel.
def _qwen3_next_shapes(one_chip, pages=5376):
    import dataclasses
    import json
    import pathlib

    from bloombee_tpu.kv.cache_manager import state_slots_for
    from bloombee_tpu.models.auto import spec_from_config_dict
    from bloombee_tpu.models.layout import LANES, linear_prefix

    config = json.loads((
        pathlib.Path(__file__).resolve().parents[1]
        / "cellbench/configs/qwen3-next-80b-ep4-span8.json").read_text())
    held = tuple(config["experts_held"])
    spec = dataclasses.replace(
        spec_from_config_dict(config), num_experts=config["router_experts"],
        moe_held=held)
    d, h, kv, hd, g = (spec.hidden_size, spec.num_attention_heads,
                       spec.num_key_value_heads, spec.head_dim, spec.gdn)
    periods, m = spec.num_hidden_layers // 4, 3
    f32 = jnp.float32

    def s(lead, *shape, dtype=bf16):
        return jax.ShapeDtypeStruct((*lead, *shape), dtype, sharding=one_chip)

    def moe(lead):
        i, e, si = spec.moe_intermediate_size, held[1], spec.moe_shared_intermediate
        return {
            "input_layernorm": s(lead, d),
            "post_attention_layernorm": s(lead, d),
            "router": s(lead, d, spec.num_experts),
            "experts_gate": s(lead, e, d, i), "experts_up": s(lead, e, d, i),
            "experts_down": s(lead, e, i, d), "shared_gate": s(lead, d, si),
            "shared_up": s(lead, d, si), "shared_down": s(lead, si, d),
            "shared_gate_w": s(lead, d),
        }

    lin = (periods,)
    linear = {
        **moe(lin), "gdn_in_proj": s(lin, d, g.proj_dim),
        "gdn_ba_proj": s(lin, d, 2 * LANES),
        "gdn_conv_w": s(lin, g.conv, g.conv_dim),
        "gdn_a_log": s(lin, g.value_heads, dtype=f32),
        "gdn_dt_bias": s(lin, g.value_heads, dtype=f32),
        "gdn_norm": s(lin, g.value_dim),
        "gdn_out_proj": s(lin, g.d_value, d),
    }
    full = {
        **moe((periods,)), "q_proj": s((periods,), h * hd, d),
        "q_gate_proj": s((periods,), h * hd, d),
        "k_proj": s((periods,), kv * hd, d),
        "v_proj": s((periods,), kv * hd, d),
        "o_proj": s((periods,), h * hd, d),
        "q_norm": s((periods,), hd), "k_norm": s((periods,), hd),
    }
    params = {**full, **{
        linear_prefix(j) + k: v for j in range(m) for k, v in linear.items()
    }}
    kv_layers, state_layers = spec.arena_layers(0, spec.num_hidden_layers)
    slots = state_slots_for(spec, pages, PAGE, 8)
    state = {
        "ssm": s((state_layers, slots), *g.state_shape, dtype=f32),
        "conv": s((state_layers, slots), *g.tail_shape),
    }
    return spec, params, _arena_shape(s, kv_layers, pages, kv, hd), state


def _arena_shape(s, kv_layers, pages, kv, hd, dtype=bf16):
    """A cell's K/V arena as `make_arena` stores it: the rule's layout."""
    from bloombee_tpu.kv.arena import folds

    if folds(kv, hd, dtype):
        return s((kv_layers,), pages * PAGE * kv, hd)
    return s((kv_layers,), pages * PAGE, kv, hd)


def _slab_moves(text: str, elems: int) -> list[str]:
    """The compiled program's reshape / copy / transpose operations whose
    result holds at least `elems` elements: a re-lay-out of a whole slab (a
    reshape that is free reads `bitcast`)."""
    import math
    import re

    return [
        f"{m.group(3)} {m.group(1)}[{m.group(2)}]"
        for m in re.finditer(
            r"= (\w+)\[([\d,]+)\]\S* (reshape|copy|transpose)\(", text)
        if math.prod(int(x) for x in m.group(2).split(",")) >= elems
    ]


def _scatter_indices(text: str) -> list[int]:
    """How many indices each `scatter` of a compiled program takes (the
    leading dimension of its second operand): what the device pays for."""
    import re

    shapes = dict(re.findall(r"%([\w.-]+) = \w+\[([\d,]*)\]", text))
    shapes.update(re.findall(r"[(, ]([\w.-]+): \w+\[([\d,]*)\]", text))
    return [
        int(shapes[m.group(1)].split(",")[0] or 1)
        for m in re.finditer(r" scatter\(%[\w.-]+, %([\w.-]+),", text)
    ]


def _rule_solves(text: str) -> list[tuple[int, bool]]:
    """The delta rule's triangular solves in a compiled step program, as
    the chip runs them (`InvertDiagBlocksLowerTriangular` custom calls):
    (the batch of systems one call takes, whether it stands INSIDE the walk
    over a chunk's blocks, a `while` under the rule's scope)."""
    import re

    return [
        (int(m.group(1)), "_rule/while" in m.group(2))
        for m in re.finditer(
            r"= f32\[(\d+),1,\d+,\d+\]\S* custom-call\([^\n]*custom_call_target="
            r'"InvertDiagBlocksLowerTriangular"[^\n]*op_name="([^"]*)"', text)
    ]


@pytest.mark.parametrize("program", ["decode", "chunk", "tail", "fused"])
def test_qwen3_next_span_step_compiles_and_copies_no_parameter(v5e, program):
    """The cell's step programs (a decode group through the paged kernel and
    the experts' grouped form, a solo 512-row chunk through flash, an 8-row
    tail through the chunk kernel, a 1024-row fused pack attended sequence
    by sequence): ONE program scans two periods of three linear layers and
    a full one over a 2-row K/V arena and a 6-row state arena; the compiled
    text holds no `copy` of a `stacked_params` parameter and no copy of a
    whole arena, and the temporaries stay what the rows' activations need."""
    import re

    one_chip = SingleDeviceSharding(v5e[0])
    spec, params, arena, state = _qwen3_next_shapes(one_chip)
    assert (arena.shape[0], state["ssm"].shape[0]) == (2, 6)
    layers, pages = 8, 1024
    common = dict(
        spec=spec, page_size=PAGE, max_pages=pages, windows=(0,) * layers)
    if program == "fused":
        r, n_seqs = 1024, 4
        plan_len = r + n_seqs * pages + r + n_seqs + r + layers + 3 * n_seqs + 1
        compiled = span_step_ragged.lower(
            params, arena, arena, _cell_payload(spec, r, plan_len, one_chip),
            None, state, r=r, n_seqs=n_seqs, use_kernel=True, **common,
        ).compile()
    else:
        b, t, case = {
            "decode": (4, 1, dict(use_paged=True)),
            # (a chunk's rows come as page groups: kv/arena.py
            # `rows_fill_pages`; an 8-row tail is under one page)
            "chunk": (1, 512, dict(
                use_flash=True, t_real=512, expert_kernels=True,
                page_groups=True)),
            "tail": (1, 8, dict(use_paged=True, t_real=5)),
        }[program]
        plan_len = b * t + b * pages + b * t + b + layers + b
        compiled = span_step_packed.lower(
            params, arena, arena,
            _cell_payload(spec, b * t, plan_len, one_chip), None, None, state,
            b=b, t=t, **case, **common,
        ).compile()
    text = compiled.as_text()
    assert "%stacked_params__lin2_gdn_in_proj" in text  # the names read below
    assert "%stacked_params__q_gate_proj" in text
    copied = re.findall(r"copy\([^)\n]*%(stacked_params\w+)", text)
    assert not copied, copied
    # (the convolution tails' arena, 4.7 MB, is re-laid out for its 3-row
    # second-minor dimension, as Falcon-H1's: `gdn_state_move_share` reads it)
    assert not re.findall(r"copy\([^)\n]*%(arena_[kv]|state__ssm)", text)
    assert "tpu_custom_call" in text
    # the chunk and the pack lie above the ridge: their chosen pairs in row
    # tiles; a decode group and the 8-row tail list their chosen experts
    tiled = program in ("chunk", "fused")
    assert ("jit(tiled_experts)" in text) == tiled
    assert ("jit(grouped_experts)" in text) == (not tiled)
    # 2 KV heads x 256 are no whole tile: the arena is stored folded (the
    # rule, kv/arena.py `folds`), so the paged kernels' page view is a
    # bitcast and no program re-lays a slab out (88 MB; unfolded, a decode
    # step did it four times: `reshape` 1.08 s of a traced 5 s, ledger PR 45)
    assert arena.shape == (2, 5376 * PAGE * 2, 256)
    slab = arena.shape[1] * arena.shape[2]
    assert not _slab_moves(text, slab), _slab_moves(text, slab)
    # the chunk's K/V go in one index a PAGE (32 a slab); every other
    # program one a (token, head) row: 2 a token
    writes = _scatter_indices(text)
    if program == "chunk":
        assert writes.count(512 // PAGE) == 2 and 512 * 2 not in writes, writes
    else:
        rows = {"decode": 4, "tail": 8, "fused": 1024}[program]
        assert writes.count(rows * 2) == 2, writes
    # the rule's chunk form: ONE triangular solve a linear layer (three
    # bodies in the scanned period), over all of a chunk's 64-row blocks x
    # 32 value heads at once and IN FRONT of the walk over the blocks (until
    # PR 52 it stood inside the walk: batch 32, eight turns a layer); the
    # 8-row tail is one block, decode rows take the step form
    blocks = {"decode": 0, "chunk": 8, "tail": 1, "fused": 16}[program]
    assert _rule_solves(text) == [(blocks * 32, False)] * (3 * bool(blocks))
    temp = compiled.memory_analysis().temp_size_in_bytes
    # decode 12.2 MB and tail 11.6 MB (400 MB was the bound while they held
    # a slab-sized buffer), chunk 43.7 MB, fused 206 MB; ONE stack for the
    # three linear positions made the chunk program's 2,742 MB. The batched
    # pass raised the chunk by 0.6 MB and the pack by 1.4 (43.1 and 204.9
    # before PR 52): a layer's [8, 32, 64, 64] pair matrices and [8, 32, 64,
    # 256] right-hand sides are 25 MB, under the experts' peak
    assert temp < {"decode": 20, "chunk": 60, "tail": 20,
                   "fused": 300}[program] * 1e6, temp


def _phi4flash_shapes(one_chip, pages=5376):
    import json
    import pathlib

    from bloombee_tpu.kv.cache_manager import state_slots_for
    from bloombee_tpu.models.auto import spec_from_config_dict
    from bloombee_tpu.models.layout import lane_padded, sambay_prefix

    config = json.loads((
        pathlib.Path(__file__).resolve().parents[1]
        / "cellbench/configs/phi4-mini-flash-full32.json").read_text())
    spec = spec_from_config_dict(config)
    d, i, mb = spec.hidden_size, spec.intermediate_size, spec.mamba
    kv, hd, c = spec.num_key_value_heads, spec.head_dim, spec.mamba.d_inner
    f32 = jnp.float32

    def s(lead, *shape, dtype=bf16):
        return jax.ShapeDtypeStruct((*lead, *shape), dtype, sharding=one_chip)

    def common(n):
        return {
            "input_layernorm": s(n, d), "input_layernorm_bias": s(n, d),
            "post_attention_layernorm": s(n, d),
            "post_attention_layernorm_bias": s(n, d),
            "gate_proj": s(n, d, i), "up_proj": s(n, d, i),
            "down_proj": s(n, i, d),
        }

    def mamba(n):
        return {
            **common(n), "mamba_in_proj": s(n, d, 2 * c),
            "mamba_conv_w": s(n, mb.conv, c), "mamba_conv_b": s(n, c),
            "mamba_x_proj": s(n, c, lane_padded(mb.x_proj_dim)),
            "mamba_dt_proj": s(n, mb.dt_rank, c),
            "mamba_dt_bias": s(n, c, dtype=f32),
            "mamba_a_t": s(n, mb.state, c, dtype=f32),
            "mamba_d": s(n, c, dtype=f32), "mamba_out_proj": s(n, c, d),
        }

    def attention(n, cross=False):
        out = {
            **common(n), "q_proj": s(n, d, d), "q_bias": s(n, d),
            "o_proj": s(n, d, d), "o_bias": s(n, d),
            "attn_lambda": s(n, 2, dtype=f32), "attn_subln": s(n, hd),
        }
        if not cross:
            out.update(k_proj=s(n, kv * hd, d), k_bias=s(n, kv * hd),
                       v_proj=s(n, kv * hd, d), v_bias=s(n, kv * hd))
        return out

    def gmu(n):
        return {**common(n), "gmu_in_proj": s(n, d, c),
                "gmu_out_proj": s(n, c, d)}

    runs = {"a": (mamba((8,)), attention((8,))),
            "b": (mamba((1,)), attention((1,))),
            "c": (gmu((7,)), attention((7,), cross=True))}
    params = {
        sambay_prefix(run, j) + k: v
        for run, pair in runs.items() for j in (0, 1)
        for k, v in pair[j].items()
    }
    kv_layers, state_layers = spec.arena_layers(0, spec.num_hidden_layers)
    slots = state_slots_for(spec, pages, PAGE, 8)
    state = {
        "ssm": s((state_layers, slots), *mb.state_shape, dtype=f32),
        "conv": s((state_layers, slots), *mb.tail_shape),
    }
    return spec, params, _arena_shape(s, kv_layers, pages, kv, hd), state


@pytest.mark.parametrize("program", ["decode", "chunk", "tail", "fused"])
def test_phi4flash_span_step_compiles_and_copies_no_parameter(v5e, program):
    """The cell `phi4flash-longctx`'s step programs at the published widths,
    all 32 layers: a decode group (paged decode attention under the window,
    over the full layer's pages, and seven cross layers on the SAME pages;
    one scan step a row), a solo 512-row chunk (the selective-scan kernel,
    windowed flash over the pages its windows span, flash over the whole
    context at layer 17, and the three exits in ONE program), an 8-row tail
    and a 1024-row fused pack. Three runs of layer pairs over a 9-row K/V
    arena and a 9-row state arena; the compiled text holds no `copy` of a
    `stacked_params` parameter and no copy of a whole arena."""
    import re

    one_chip = SingleDeviceSharding(v5e[0])
    spec, params, arena, state = _phi4flash_shapes(one_chip)
    assert (arena.shape, state["ssm"].shape[:1]) == (
        (9, 5376 * PAGE * 10, 128), (9,))
    layers, pages = 32, 1024
    common = dict(spec=spec, page_size=PAGE, max_pages=pages,
                  windows=(0,) * layers)
    if program == "fused":
        r, n_seqs = 1024, 4
        plan_len = (r + n_seqs * pages + r + n_seqs + r + layers
                    + 3 * n_seqs + 1 + r + 1)
        compiled = span_step_ragged.lower(
            params, arena, arena, _cell_payload(spec, r, plan_len, one_chip),
            None, state, r=r, n_seqs=n_seqs, use_kernel=True, **common,
        ).compile()
    else:
        b, t, t_real = {"decode": (4, 1, 1), "chunk": (1, 512, 512),
                        "tail": (1, 8, 5)}[program]
        plan_len = b * t + b * pages + b * t + b + layers + b + (
            b * t + 1 if t > 1 else 0)
        compiled = span_step_packed.lower(
            params, arena, arena,
            _cell_payload(spec, b * t, plan_len, one_chip), None, None, state,
            b=b, t=t, use_paged=True, t_real=t_real,
            # (a chunk's rows come as page groups; a tail of 8 is no page)
            page_groups=program == "chunk", **common,
        ).compile()
    text = compiled.as_text()
    assert "%stacked_params__sa0_mamba_in_proj" in text  # the names read below
    assert "%stacked_params__sc1_q_proj" in text
    copied = re.findall(r"copy\([^)\n]*%(stacked_params\w+)", text)
    # (`attn_lambda` is two float32 a layer: a decode program re-lays its 64
    # bytes out)
    assert not [c for c in copied if "attn_lambda" not in c], copied
    assert not re.findall(r"copy\([^)\n]*%(arena_[kv]|state__ssm)", text)
    assert "tpu_custom_call" in text
    assert ("jit(selective_scan)" in text) == (program != "decode")
    # B and C reach the scan kernel as flat [T * N] scalars (PR 59): no
    # [T, N, 1] column array, a 128-lane row an element, in front of the call
    columns = re.findall(rf"f32\[\d+,{spec.mamba.state},1\]", text)
    assert not columns, set(columns)
    slab = arena.shape[1] * arena.shape[2]
    assert not _slab_moves(text, slab), _slab_moves(text, slab)
    temp = compiled.memory_analysis().temp_size_in_bytes
    assert temp < 1500e6, temp
    # the chunk's K/V go in one index a PAGE, 32 a slab where the row
    # scatter takes 5,120 (kv/arena.py `arena_write` on `PageSlots`; the
    # pair scan holds one write, layer 17 the other: 2 x K and V); every
    # other program one a (token, pair) row
    writes, pairs = _scatter_indices(text), spec.num_key_value_heads
    if program == "chunk":
        assert writes.count(512 // PAGE) == 4 and 512 * pairs not in writes, (
            writes)
    else:
        rows = {"decode": 4, "tail": 8, "fused": 1024}[program]
        assert writes.count(rows * pairs) == 4, writes


# ---------------------------------------------- the arena's layout rule (PR 46)
# kv/arena.py `folds`: [S_tot, kv_heads, head_dim] is stored folded,
# [S_tot * kv_heads, head_dim], exactly where the paged kernels' view of it
# as pages of [page_size * kv_heads, head_dim] rows is no bitcast on the
# device. The six cells' K/V shapes and two more; DeepSeek-V2's page is a
# latent and a rotary key, not heads: never folded, its own kernel.
_LAYOUT_CASES = {
    "mistral-8x128": (8, 128, False),
    "qwen3moe-4x128": (4, 128, False),
    "falconh1-8x128": (8, 128, False),
    "qwen3next-2x256": (2, 256, True),
    "phi4flash-10x128": (10, 128, True),
    "4x256": (4, 256, True),
    "16x256": (16, 256, False),
    "nemotronh-2x128": (2, 128, False),
}


def _arena_part_of_a_decode_step(kv, hd, folded: bool, pages=5376):
    """(fn, shapes): four rows' K and V written into one layer's slab and
    attended through the paged decode kernel, the slab in the given layout
    and addressed through the arena's helpers as `layer_body` does."""
    from bloombee_tpu.kv.arena import arena_write, heads_view

    def fn(q, k_slab, v_slab, slots, k_new, v_new, table, lens):
        k_slab, v_slab = arena_write(k_slab, v_slab, slots, k_new, v_new)
        out = paged_decode_attention(
            q, heads_view(k_slab, kv), heads_view(v_slab, kv), table, lens,
            page_size=PAGE)
        return out, k_slab, v_slab

    slab = (pages * PAGE * kv, hd) if folded else (pages * PAGE, kv, hd)
    h = max(4 * kv, 8)
    return fn, [
        ((4, h, hd), bf16), (slab, bf16), (slab, bf16), ((4,), i32),
        ((4, kv, hd), bf16), ((4, kv, hd), bf16), ((4, 1024), i32),
        ((4,), i32)]


def _page_write_moves_no_slab(compile_, slabs, token_rows, rows: int):
    """The chunk side (PR 49): 512 rows that come as page groups, written
    by page into donated slabs of these shapes (kv/arena.py `arena_write` on
    `PageSlots`, through the [pages, page_size * n_kv, lanes] view): 32
    indices a slab where the row scatter takes `rows`, no reshape / copy /
    transpose the size of a slab, a temporary under half a slab, the slabs
    updated in place. Through the split view [pages, page_size, n_kv, hd] a
    4 x 128 slab is re-laid out twice (my compile, PR 49): one view for
    every plain slab."""
    import math

    from bloombee_tpu.kv.arena import PageSlots, arena_write, page_view_free

    def write(k_slab, v_slab, slots, k_new, v_new):
        return arena_write(
            k_slab, v_slab, PageSlots(slots, PAGE), k_new, v_new)

    assert all(page_view_free(slab, bf16) for slab in slabs)
    shapes = [(slabs[0], bf16), (slabs[1], bf16), ((512,), i32),
              ((512, *token_rows[0]), bf16), ((512, *token_rows[1]), bf16)]
    compiled = compile_(write, shapes, donate=(0, 1))
    text = compiled.as_text()
    elems = min(math.prod(slab) for slab in slabs)
    assert not _slab_moves(text, elems), _slab_moves(text, elems)
    memory = compiled.memory_analysis()
    assert memory.temp_size_in_bytes < elems  # < half the smaller slab
    assert memory.alias_size_in_bytes == 2 * sum(
        math.prod(slab) for slab in slabs)
    assert _scatter_indices(text) == [512 // PAGE] * 2
    by_row = compile_(arena_write, shapes, donate=(0, 1))
    folded = len(slabs[0]) == 2 and len(token_rows[0]) == 2
    assert _scatter_indices(by_row.as_text()) == [
        rows if folded else 512] * 2


@pytest.mark.parametrize("case", sorted(_LAYOUT_CASES) + ["deepseekv2-latent"])
def test_chosen_layout_holds_no_slab_sized_move(v5e, case):
    """The layout the rule chooses compiles to a decode program with no
    reshape / copy / transpose the size of a slab and no slab-sized
    temporary; where it chooses to fold, the unfolded layout DOES hold one
    (so the rule folds nothing that did not need it: the other cases' chosen
    layout is the unfolded one). And the chunk side: a chunk's rows written
    by page into the chosen layout move no slab either."""
    import math

    from bloombee_tpu.kv.arena import folds

    one_chip = SingleDeviceSharding(v5e[0])

    def compile_(fn, shapes, donate=()):
        return jax.jit(fn, donate_argnums=donate).lower(*(
            jax.ShapeDtypeStruct(s, d, sharding=one_chip) for s, d in shapes
        )).compile()

    if case == "deepseekv2-latent":
        from bloombee_tpu.ops.pallas.latent_attention import (
            paged_decode_attention_latent,
        )

        elems = 5 * 5376 * PAGE * 512
        compiled = compile_(
            functools.partial(
                paged_decode_attention_latent, page_size=PAGE, scale=0.1147),
            [((8, 128, 512), bf16), ((8, 128, 128), bf16),
             ((5 * 5376 * PAGE, 512), bf16), ((5 * 5376 * PAGE, 128), bf16),
             ((8, 1024), i32), ((8,), i32)])
        assert not _slab_moves(compiled.as_text(), elems // 5)
        _page_write_moves_no_slab(
            compile_, [(5 * 5376 * PAGE, 512), (5 * 5376 * PAGE, 128)],
            [(512,), (128,)], rows=512)
        return
    kv, hd, folded = _LAYOUT_CASES[case]
    assert folds(kv, hd, bf16) is folded
    assert folds(kv, hd, jnp.float32) is folded
    elems = 5376 * PAGE * kv * hd
    fn, shapes = _arena_part_of_a_decode_step(kv, hd, folded)
    compiled = compile_(fn, shapes, donate=(1, 2))
    text = compiled.as_text()
    assert "tpu_custom_call" in text
    assert not _slab_moves(text, elems), _slab_moves(text, elems)
    assert compiled.memory_analysis().temp_size_in_bytes < elems  # < half a slab
    slab = shapes[1][0]
    _page_write_moves_no_slab(
        compile_, [slab, slab], [(kv, hd), (kv, hd)], rows=512 * kv)
    if folded:
        fn, shapes = _arena_part_of_a_decode_step(kv, hd, False)
        moved = _slab_moves(compile_(fn, shapes, donate=(1, 2)).as_text(), elems)
        assert len(moved) >= 2, moved  # K's slab and V's


# ------------------------------------------------------------- kimi_linear
# Kimi delta attention layers (a decay a key channel) with a position-free
# latent-attention layer every fourth, a LATENT arena beside a state arena,
# the leading dense layer inside the first period, 64 of 256 experts held:
# the span steps of the benchmark's cell (cellbench/configs/
# kimi-linear-48b-ep4-span8.json: published widths, two periods, 5376 pages)
# at the 1024-page bucket its contexts take. The two periods differ (layer
# 0's MLP is dense), so the span is two runs of one period each, the first
# under `lead.`.
def _kimi_linear_shapes(one_chip, pages=5376):
    import dataclasses
    import json
    import pathlib

    from bloombee_tpu.kv.cache_manager import state_slots_for
    from bloombee_tpu.models.auto import spec_from_config_dict
    from bloombee_tpu.models.layout import LANES, LEAD, linear_prefix

    config = json.loads((
        pathlib.Path(__file__).resolve().parents[1]
        / "cellbench/configs/kimi-linear-48b-ep4-span8.json").read_text())
    held = tuple(config["experts_held"])
    spec = dataclasses.replace(
        spec_from_config_dict(config), num_experts=config["router_experts"],
        moe_held=held)
    d, h, g, m = (spec.hidden_size, spec.num_attention_heads, spec.gdn,
                  spec.mla)
    f32 = jnp.float32

    def s(*shape, dtype=bf16):
        return jax.ShapeDtypeStruct((1, *shape), dtype, sharding=one_chip)

    i, e, si = spec.moe_intermediate_size, held[1], spec.moe_shared_intermediate
    norms = {"input_layernorm": s(d), "post_attention_layernorm": s(d)}
    sparse = {
        "router_t": s(spec.num_experts, d),
        "expert_bias": s(spec.num_experts, dtype=f32),
        "experts_gate": s(e, d, i), "experts_up": s(e, d, i),
        "experts_down": s(e, i, d), "shared_gate": s(d, si),
        "shared_up": s(d, si), "shared_down": s(si, d),
    }
    dense = {"gate_proj": s(d, spec.intermediate_size),
             "up_proj": s(d, spec.intermediate_size),
             "down_proj": s(spec.intermediate_size, d)}
    kda = {
        **norms, "gdn_in_proj": s(d, g.proj_dim),
        "gdn_low_proj": s(d, 3 * LANES),
        "gdn_f_b_proj": s(g.gate_rank, g.d_key),
        "gdn_g_b_proj": s(g.gate_rank, g.d_value),
        "gdn_conv_w": s(g.conv, g.conv_dim),
        "gdn_a_log": s(g.value_heads, dtype=f32),
        "gdn_dt_bias": s(g.d_key, dtype=f32),
        "gdn_norm": s(g.value_dim), "gdn_out_proj": s(g.d_value, d),
    }
    latent = {
        **norms, "kv_a_norm": s(m.kv_rank),
        "q_b_nope": s(h * m.nope_dim, d), "q_b_rope": s(h * m.rope_dim, d),
        "kv_a_proj": s(m.kv_rank + m.rope_dim, d),
        "kv_b_k": s(h, m.nope_dim, m.kv_rank),
        "kv_b_v": s(h, m.v_dim, m.kv_rank), "o_proj": s(h * m.v_dim, d),
    }
    params = {}
    for run, first_mlp in ((LEAD, dense), ("", sparse)):
        for j in range(3):
            mlp = first_mlp if j == 0 else sparse
            params.update({run + linear_prefix(j) + k: v
                           for k, v in {**kda, **mlp}.items()})
        params.update({run + k: v for k, v in {**latent, **sparse}.items()})
    assert spec.period_runs(0, 8) == (
        (("linear+dense", "linear", "linear", "full"), 1),
        (("linear", "linear", "linear", "full"), 1))
    kv_layers, state_layers = spec.arena_layers(0, spec.num_hidden_layers)
    slots = state_slots_for(spec, pages, PAGE, 8)

    def a(*shape, dtype=bf16):
        return jax.ShapeDtypeStruct(shape, dtype, sharding=one_chip)

    state = {
        "ssm": a(state_layers, slots, *g.state_shape, dtype=f32),
        "conv": a(state_layers, slots, *g.tail_shape),
    }
    c, pe = m.page_payload
    return (spec, params, a(kv_layers, pages * PAGE, *c),
            a(kv_layers, pages * PAGE, *pe), state)


@pytest.mark.parametrize("program", ["decode", "chunk", "tail", "fused"])
def test_kimi_linear_span_step_compiles_and_copies_no_parameter(v5e, program):
    """The cell's step programs (a decode group, a solo 512-row chunk, an
    8-row tail, a 1024-row fused pack; 1024-page bucket, kernels on): ONE
    program scans the period with the dense layer, then the other, over a
    2-row latent arena and a 6-row state arena; the compiled text holds no
    `copy` of a `stacked_params` parameter and no copy of a whole arena, the
    latent kernels and the experts' kernel forms are in it, and the
    temporaries stay what the rows' activations need."""
    import re

    one_chip = SingleDeviceSharding(v5e[0])
    spec, params, latent, shared_key, state = _kimi_linear_shapes(one_chip)
    assert (latent.shape, shared_key.shape[2], state["ssm"].shape[:2]) == (
        (2, 5376 * PAGE, 512), 128, (6, 16))
    layers, pages = 8, 1024
    common = dict(
        spec=spec, page_size=PAGE, max_pages=pages, windows=(0,) * layers)
    if program == "fused":
        r, n_seqs = 1024, 4
        plan_len = r + n_seqs * pages + r + n_seqs + r + layers + 3 * n_seqs + 1
        compiled = span_step_ragged.lower(
            params, latent, shared_key,
            _cell_payload(spec, r, plan_len, one_chip), None, state,
            r=r, n_seqs=n_seqs, use_kernel=True, **common,
        ).compile()
    else:
        b, t, t_real = {"decode": (4, 1, None), "chunk": (1, 512, 512),
                        "tail": (1, 8, 5)}[program]
        plan_len = b * t + b * pages + b * t + b + layers + b
        compiled = span_step_packed.lower(
            params, latent, shared_key,
            _cell_payload(spec, b * t, plan_len, one_chip), None, None, state,
            b=b, t=t, use_paged=True, t_real=t_real, page_groups=t == 512,
            **common,
        ).compile()
    text = compiled.as_text()
    assert "%stacked_params__lead_lin0_gdn_in_proj" in text  # the names read
    assert "%stacked_params__q_b_rope" in text
    copied = re.findall(r"copy\([^)\n]*%(stacked_params\w+)", text)
    assert not copied, copied
    assert not re.findall(r"copy\([^)\n]*%(arena_[kv]|state__ssm)", text)
    # decode: the paged latent kernel in both runs + the grouped experts in
    # the seven sparse layers' four distinct bodies... counted by name below
    assert "tpu_custom_call" in text
    tiled = program in ("chunk", "fused")
    assert ("jit(tiled_experts)" in text) == tiled
    assert ("jit(grouped_experts)" in text) == (not tiled)
    # ONE triangular solve a KDA layer, as Qwen3-Next's above (six bodies:
    # two runs of periods)
    blocks = {"decode": 0, "chunk": 8, "tail": 1, "fused": 16}[program]
    assert _rule_solves(text) == [(blocks * 32, False)] * (6 * bool(blocks))
    temp = compiled.memory_analysis().temp_size_in_bytes
    # the chunk reads 108 MB and the pack 409 (96 and 395 before PR 52: the
    # batched pass holds a layer's eight blocks at once, for the vector decay
    # the [8, 4, 64, 32, 128] factors up to each sub-block's reference, 34 MB,
    # and the exact [8, 4, 16, 16, 32, 128] diagonals, 17 MB, where a block at
    # a time held an eighth), tail 44 MB
    assert temp < {"decode": 60, "chunk": 150, "tail": 60,
                   "fused": 500}[program] * 1e6, temp


# -------------------------------------------------------------- nemotron_h
# Layers that are ONE sublayer each (a Mamba-2 mixer, an ungated relu²
# expert layer of 64 held two-matrix experts, position-free GQA attention
# alone): the span steps of the benchmark's cell (cellbench/configs/
# nemotron3-nano-30b-ep2-span14.json: published widths, EMEMEM*EMEMEM*, 5376
# pages) at the 1024-page bucket its contexts take. The span is ONE run, the
# period (moe, mamba, moe, mamba, moe, mamba, full) twice, one stack a
# position in it.
def _nemotron_h_shapes(one_chip, pages=5376):
    import dataclasses
    import json
    import pathlib

    from bloombee_tpu.kv.cache_manager import state_slots_for
    from bloombee_tpu.models.auto import spec_from_config_dict
    from bloombee_tpu.models.layout import lane_padded, unit_prefix

    config = json.loads((
        pathlib.Path(__file__).resolve().parents[1]
        / "cellbench/configs/nemotron3-nano-30b-ep2-span14.json").read_text())
    held = tuple(config["experts_held"])
    spec = dataclasses.replace(
        spec_from_config_dict(config), num_experts=config["router_experts"],
        moe_held=held)
    d, h, kv, hd, ssm = (spec.hidden_size, spec.num_attention_heads,
                         spec.num_key_value_heads, spec.head_dim, spec.ssm)
    f32 = jnp.float32
    i, si = lane_padded(spec.moe_intermediate_size), spec.moe_shared_intermediate
    assert (i, ssm.proj_dim, lane_padded(ssm.proj_dim)) == (1920, 10304, 10368)

    def stacks(n):
        def s(*shape, dtype=bf16):
            return jax.ShapeDtypeStruct((n, *shape), dtype, sharding=one_chip)

        norm = {"input_layernorm": s(d)}
        return {
            "moe": {
                **norm, "router_t": s(spec.num_experts, d),
                "expert_bias": s(spec.num_experts, dtype=f32),
                "experts_up": s(held[1], d, i),
                "experts_down": s(held[1], i, d),
                "shared_up": s(d, si), "shared_down": s(si, d)},
            "mamba": {
                **norm, "ssm_in_proj": s(d, lane_padded(ssm.proj_dim)),
                "ssm_out_proj": s(ssm.d_ssm, d),
                "ssm_conv_w": s(ssm.conv, ssm.conv_dim),
                "ssm_conv_b": s(ssm.conv_dim), "ssm_norm": s(ssm.d_ssm),
                "ssm_a_log": s(ssm.heads, dtype=f32),
                "ssm_d": s(ssm.heads, dtype=f32),
                "ssm_dt_bias": s(ssm.heads, dtype=f32)},
            "full": {
                **norm, "q_proj": s(h * hd, d), "k_proj": s(kv * hd, d),
                "v_proj": s(kv * hd, d), "o_proj": s(h * hd, d)},
        }

    runs = spec.period_runs(0, spec.num_hidden_layers)
    assert runs == ((("moe", "mamba") * 3 + ("full",), 2),)
    params = {}
    for r, (kinds, repeats) in enumerate(runs):
        for j, kind in enumerate(kinds):
            params.update({unit_prefix(r, j) + k: v
                           for k, v in stacks(repeats)[kind].items()})
    kv_layers, state_layers = spec.arena_layers(0, spec.num_hidden_layers)
    slots = state_slots_for(spec, pages, PAGE, 8)

    def a(*shape, dtype=bf16):
        return jax.ShapeDtypeStruct(shape, dtype, sharding=one_chip)

    state = {
        "ssm": a(state_layers, slots, *ssm.state_shape, dtype=f32),
        "conv": a(state_layers, slots, *ssm.tail_shape),
    }
    # 2 KV heads of 128 do not fold (kv/arena.py `folds`)
    return spec, params, a(kv_layers, pages * PAGE, kv, hd), state


@pytest.mark.parametrize("program", ["decode", "chunk", "tail", "fused"])
def test_nemotron_h_span_step_compiles_and_copies_no_parameter(v5e, program):
    """The cell's step programs (a decode group, a solo 512-row chunk, an
    8-row tail, a 1024-row fused pack; 1024-page bucket, kernels on): ONE
    program scans the period twice over a 2-row K/V arena and a 6-row
    state arena; the compiled text holds no `copy` of a `stacked_params`
    parameter and no copy of a whole arena, the ungated expert kernels are
    in it with weight blocks inside the budget, and the temporaries stay
    what the rows' activations need."""
    import re
    import time

    from bloombee_tpu.kv.arena import folds

    one_chip = SingleDeviceSharding(v5e[0])
    spec, params, arena, state = _nemotron_h_shapes(one_chip)
    assert not folds(2, 128, bf16)
    assert (arena.shape, state["ssm"].shape) == (
        (2, 5376 * PAGE, 2, 128), (6, 16, 64, 64, 128))
    slab = arena.shape[1] * arena.shape[2] * arena.shape[3]
    layers, pages = 14, 1024
    common = dict(
        spec=spec, page_size=PAGE, max_pages=pages, windows=(0,) * layers)
    t0 = time.time()
    if program == "fused":
        r, n_seqs = 1024, 4
        plan_len = r + n_seqs * pages + r + n_seqs + r + layers + 3 * n_seqs + 1
        compiled = span_step_ragged.lower(
            params, arena, arena,
            _cell_payload(spec, r, plan_len, one_chip), None, state,
            r=r, n_seqs=n_seqs, use_kernel=True, **common,
        ).compile()
    else:
        b, t, t_real = {"decode": (4, 1, None), "chunk": (1, 512, 512),
                        "tail": (1, 8, 5)}[program]
        plan_len = b * t + b * pages + b * t + b + layers + b
        compiled = span_step_packed.lower(
            params, arena, arena,
            _cell_payload(spec, b * t, plan_len, one_chip), None, None, state,
            b=b, t=t, use_paged=t < 512, use_flash=t == 512,
            expert_kernels=True, t_real=t_real, page_groups=t == 512,
            **common,
        ).compile()
    seconds = time.time() - t0
    text = compiled.as_text()
    assert "%stacked_params__r0p0_experts_up" in text  # the names read
    assert "%stacked_params__r0p6_q_proj" in text
    copied = re.findall(r"copy\([^)\n]*%(stacked_params\w+)", text)
    assert not copied, copied
    assert not re.findall(r"copy\([^)\n]*%(arena_[kv]|state__ssm)", text)
    assert "tpu_custom_call" in text
    tiled = program in ("chunk", "fused")
    assert ("jit(tiled_experts)" in text) == tiled
    assert ("jit(grouped_experts)" in text) == (not tiled)
    # no program re-lays a K/V slab out or copies one (while the two
    # attention layers stood in runs of ONE repeat each, whose scans the
    # compiler unrolls, a chunk's page write copied the whole 88 MB arena
    # four times: 0.126 s of a traced 5 s, my chip run, PR 54, call 1); the
    # chunk's K/V go in one index a PAGE
    moved = [m for m in _slab_moves(text, slab)
             if f"[{2 * arena.shape[1]},2,128]" in m]
    assert not moved, moved
    if program == "chunk":  # K and V of the period's one attention layer
        assert _scatter_indices(text).count(512 // PAGE) == 2
    temp = compiled.memory_analysis().temp_size_in_bytes
    assert temp < {"decode": 80, "chunk": 400, "tail": 80,
                   "fused": 800}[program] * 1e6, temp
    # far from the client's 120 s step_timeout, on this machine's CPU
    assert seconds < 100, seconds
