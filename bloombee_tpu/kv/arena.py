"""Device KV arena: the data plane of the paged cache.

Replaces the reference's torch slab + in-place writes
(/root/reference/src/bloombee/server/memory_cache_manager.py:1373 `_write_kvs`,
paged_kv.py:137-204 page-at-a-time writes) with functional jnp ops designed to
live *inside* the jitted span step: the arena is a donated carry, writes are
scatters, reads are page gathers. XLA turns the donated scatter into an
in-place HBM update — the slab-write-vs-cat win of the reference's arch reform
(tests/bench_arch_reform.py) is the default here.

Layout: per layer, a flat slot dimension of num_pages * page_size tokens:
    k, v: [L, num_pages * page_size, n_kv_heads, head_dim]
Slot ids come from the host-side PagedKVTable (page * page_size + offset).

Addressing inside a step: no layer ever gets its slab as an array of its own.
The step views the stored arena as ONE flat slab [L * S_tot, n_kv, hd]
(`flat_arena`, a bitcast), carries that whole through its layer scan, and
layer `l` reaches its part by offset — it writes at `layer_slots(slots, l)`
and reads through `layer_pages(page_table, l)`. `arena_write`, `gather_pages`
and the Pallas kernels only ever see a slab plus ids into it, so they serve a
layer slab and the flat arena alike. Why not slice a slab out of the stack
per layer: the slice and the write-back each copy the whole slab, which moves
the arena through HBM once a layer to write a handful of rows; the
benchmark's `scan_slab_move_share` reads such copies.

Recurrent state beside the pages: a family with a state-space mixer
(`ModelSpec.ssm`) or gated-DeltaNet layers (`ModelSpec.gdn`) keeps, per
sequence and layer that has such state, ONE fixed-size slot that is never
paged: `make_state_arena` -> {"ssm": [L, slots, *state_shape] float32, "conv":
[L, slots, K-1, C]}. It is addressed the same way: viewed flat over
(layer, slot), carried whole through the step's scan, layer `l` reads and
writes rows `layer_state_slots(slots, l)`. A slot can be kept or zeroed,
never cut to a position: everything that truncates pages refuses such a
family or resets the slot (kv/cache_manager.py).
"""

from __future__ import annotations

import jax
import jax.numpy as jnp


def make_arena(
    num_layers: int,
    num_pages: int,
    page_size: int,
    n_kv_heads: int,
    head_dim: int,
    dtype=jnp.bfloat16,
    quant: str | None = None,
    payload: tuple[tuple[int, ...], tuple[int, ...]] | None = None,
) -> dict:
    """quant="int4": store the slabs group-quantized (the reference's
    TorchCompressedDevice KV capacity lever, compression.py:22-210) — ~3.2x
    more tokens per HBM byte; writes quantize and reads dequantize inside
    the jitted span step.

    `payload`: what a token's row holds in each of the two slabs when it is
    not K and V head slabs; a latent-attention family declares it
    (models/spec.py `MlaSpec.page_payload`: the latent in "k", the rotary
    key in "v"). Addressing is unchanged: slots, pages, `flat_arena`,
    `layer_slots`, `layer_pages`, `arena_write`, `gather_pages` only ever
    see [L, S_tot, ...] and ids."""
    if payload is not None:
        if quant not in (None, "none"):
            raise ValueError(
                "a latent page (latent attention) has no int4 form: "
                "--kv-quant is unsupported for this family"
            )
        s_tot = num_pages * page_size
        return {
            "k": jnp.zeros((num_layers, s_tot, *payload[0]), dtype),
            "v": jnp.zeros((num_layers, s_tot, *payload[1]), dtype),
        }
    shape = (num_layers, num_pages * page_size, n_kv_heads, head_dim)
    if quant == "int4":
        from bloombee_tpu.kv.quant import make_quant_slab

        return {"k": make_quant_slab(shape), "v": make_quant_slab(shape)}
    if quant not in (None, "none"):
        raise ValueError(f"unknown KV quant mode {quant!r}")
    return {"k": jnp.zeros(shape, dtype), "v": jnp.zeros(shape, dtype)}


def make_state_arena(
    num_layers: int, num_slots: int, ssm, conv_dtype=jnp.bfloat16
) -> dict:
    """The recurrent-state arena of a family with recurrent state (`ssm`:
    a models.spec SsmSpec or GdnSpec, whichever `ModelSpec.recurrent`
    gives): the state in float32 (a sum over thousands of positions), the
    convolution's tail in the compute dtype (its rows are the projection's
    outputs, so nothing is rounded). `num_layers`: the layers that KEEP
    such state (`ModelSpec.arena_layers`)."""
    return {
        "ssm": jnp.zeros((num_layers, num_slots, *ssm.state_shape), jnp.float32),
        "conv": jnp.zeros((num_layers, num_slots, *ssm.tail_shape), conv_dtype),
    }


def state_slot_bytes(ssm, conv_itemsize: int = 2) -> int:
    """Bytes ONE sequence's recurrent state takes in ONE layer: the state in
    float32, the convolution's tail in the compute dtype."""
    import math

    return (
        math.prod(ssm.state_shape) * 4
        + math.prod(ssm.tail_shape) * conv_itemsize
    )


def layer_state_slots(slots: jax.Array, layer, num_slots: int, num_layers: int):
    """Layer `layer`'s state slots inside the flat state arena; a padding
    row's id (outside [0, num_slots)) maps past the END, so a scatter with
    `mode="drop"` discards it and a gather clamps to a row nobody keeps."""
    valid = (slots >= 0) & (slots < num_slots)
    return jnp.where(valid, slots + layer * num_slots, num_layers * num_slots)


def flat_arena(arena):
    """[L, S_tot, ...] -> [L * S_tot, ...], leaf by leaf (an int4 QuantSlab
    has three leaves). The two leading dimensions are contiguous, so this is
    a bitcast, and under `--tp` it merges two unsharded dimensions."""
    return jax.tree.map(
        lambda a: a.reshape(a.shape[0] * a.shape[1], *a.shape[2:]), arena
    )


def stacked_arena(flat, num_layers: int):
    """Inverse of flat_arena: back to the stored [L, S_tot, ...] layout."""
    return jax.tree.map(
        lambda a: a.reshape(num_layers, -1, *a.shape[1:]), flat
    )


def layer_slots(slots: jax.Array, layer, s_tot: int, num_layers: int):
    """Layer `layer`'s slot ids inside the flat arena: `slots + layer * S_tot`.
    A padding row's id (anything outside [0, S_tot)) maps past the END of the
    flat arena, so `mode="drop"` still discards it — offset naively, slot
    S_tot of layer l would be slot 0 of layer l + 1."""
    valid = (slots >= 0) & (slots < s_tot)
    return jnp.where(valid, slots + layer * s_tot, num_layers * s_tot)


def layer_pages(page_table: jax.Array, layer, num_pages: int):
    """Layer `layer`'s physical page ids inside the flat arena. Padding
    entries (page 0) become the layer's own page 0: read, then masked."""
    return page_table + layer * num_pages


def arena_write(
    k_layer: jax.Array,  # [S, n_kv, hd]: a layer's slab or the flat arena
    v_layer: jax.Array,
    slots: jax.Array,  # [N] int32 flat slot ids
    k_new: jax.Array,  # [N, n_kv, hd]
    v_new: jax.Array,
) -> tuple[jax.Array, jax.Array]:
    """Scatter new KV rows into a slab (functional; donate the slab).

    Out-of-bounds slot ids are dropped — the span step points padding rows at
    slot == num_slots to discard their writes (`layer_slots` keeps them out
    of bounds when the slab is the flat arena).
    """
    from bloombee_tpu.kv.quant import QuantSlab, quantize

    if isinstance(k_layer, QuantSlab):
        new_k, new_v = quantize(k_new), quantize(v_new)
        k_layer = QuantSlab(
            *(
                a.at[slots].set(b, mode="drop")
                for a, b in zip(k_layer, new_k)
            )
        )
        v_layer = QuantSlab(
            *(
                a.at[slots].set(b, mode="drop")
                for a, b in zip(v_layer, new_v)
            )
        )
        return k_layer, v_layer
    k_layer = k_layer.at[slots].set(k_new.astype(k_layer.dtype), mode="drop")
    v_layer = v_layer.at[slots].set(v_new.astype(v_layer.dtype), mode="drop")
    return k_layer, v_layer


def gather_pages(
    layer_slab: jax.Array,  # [S, n_kv, hd]: a layer's slab or the flat arena
    page_table: jax.Array,  # [B, max_pages] int32 page ids INTO that slab
    page_size: int,
) -> jax.Array:
    """Gather each sequence's pages: returns [B, max_pages*page_size, n_kv, hd].

    Invalid (padding) pages gather garbage rows; callers mask by context
    length — the clamped-read invariant lives in the attention mask, mirroring
    the reference's gather_prefix clamp (paged_kv.py:265-316).
    """
    from bloombee_tpu.kv.quant import QuantSlab, dequantize

    b, max_pages = page_table.shape
    slots = (
        page_table[:, :, None] * page_size
        + jnp.arange(page_size, dtype=page_table.dtype)[None, None, :]
    ).reshape(b, max_pages * page_size)
    if isinstance(layer_slab, QuantSlab):
        gathered = QuantSlab(*(leaf[slots] for leaf in layer_slab))
        return dequantize(gathered, jnp.float32)
    return layer_slab[slots]


def arena_reorder(
    k_layer: jax.Array,
    v_layer: jax.Array,
    src_slots: jax.Array,  # [N] gather sources (surviving speculative slots)
    dst_slots: jax.Array,  # [N] scatter destinations (compacted prefix slots)
) -> tuple[jax.Array, jax.Array]:
    """Compact surviving speculative KV rows onto the committed prefix.

    The reference does this with a background reorder thread
    (memory_cache_manager.py:2011-2160 update_cache_and_async_reorder); here it
    is a single on-device gather+scatter fused into the step that needs it —
    SURVEY.md section 7 'hard parts' #2 recommends exactly this.
    `src_slots == dst_slots` rows are no-ops (gather-before-scatter semantics:
    all reads happen from the pre-update slab).
    """
    k_rows = k_layer[src_slots]
    v_rows = v_layer[src_slots]
    return k_layer.at[dst_slots].set(k_rows), v_layer.at[dst_slots].set(v_rows)
