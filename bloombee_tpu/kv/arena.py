"""Device KV arena: the data plane of the paged cache.

Replaces the reference's torch slab + in-place writes
(/root/reference/src/bloombee/server/memory_cache_manager.py:1373 `_write_kvs`,
paged_kv.py:137-204 page-at-a-time writes) with functional jnp ops designed to
live *inside* the jitted span step: the arena is a donated carry, writes are
scatters, reads are page gathers. XLA turns the donated scatter into an
in-place HBM update — the slab-write-vs-cat win of the reference's arch reform
(tests/bench_arch_reform.py) is the default here.

What a scatter costs on the device is its INDICES, not its bytes (about 75 ns
an index: PERF.md section 6, PR 49), so `arena_write` has two forms of one
write. A decode row, tree rows, a chunk that starts inside a page and an int4
slab go row by row, one index a (token, head) row of a folded slab and one a
token otherwise. A dispatch whose rows come as PAGE GROUPS goes page by page,
one index a page, through the slab's [pages, page_size * n_kv, hd] view (the
paged kernels' own): the host sees it in the slots it already has
(`rows_fill_pages`: every page_size rows of the bucket are one page's leading
tokens from its first, or padding; a prompt chunk that starts on a page
boundary is that) and hands the slots down as `PageSlots`; a page the rows
fill only in part is read, its real rows replaced and written back, so the
slab afterwards is the row scatter's bit for bit. `page_view_free` says by the
slab's shape where that view is a bitcast on the device; a slab where it is
not keeps the row scatter.

Layout: per layer, a flat slot dimension of num_pages * page_size tokens:
    k, v: [L, num_pages * page_size, n_kv_heads, head_dim]
Slot ids come from the host-side PagedKVTable (page * page_size + offset).

The layout follows from the shape (`folds`). The paged kernels read a slab as
pages of [page_size * n_kv_heads, head_dim] rows. The device tiles the two
minor dimensions in (8, 128) tiles, so [S_tot, n_kv_heads, head_dim] holds
those rows byte for byte only where a token's heads fill whole tiles
(n_kv_heads a multiple of 8) or are one tile column's part (head_dim 128 and
n_kv_heads 1, 2, 4): Mistral 8 x 128, Qwen3-30B-A3B 4 x 128, Falcon-H1
8 x 128. Anywhere else (Qwen3-Next 2 x 256, phi4flash 10 x 128) the view is
a re-lay-out of the WHOLE slab in front of every kernel, so the arena is
stored FOLDED, a layer's slab [S_tot * n_kv_heads, head_dim], token-major
then head: exactly those rows. Slots and pages stay TOKEN slots and pages
everywhere; a slab of two dimensions IS a folded one, and `arena_write`,
`gather_pages`, `heads_view` and `slot_rows` see it in their input. An int4
arena, a latent page and an arena sharded over a mesh stay unfolded.

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

import dataclasses

import jax
import jax.numpy as jnp
import numpy as np


SUBLANES, LANES = 8, 128  # the device's tile of a 2- or 4-byte type


def folds(n_kv_heads: int, head_dim: int, dtype) -> bool:
    """Whether a K/V arena of this shape is stored folded: whether
    [S_tot, n_kv_heads, head_dim] and [S_tot * n_kv_heads, head_dim] are
    DIFFERENT bytes in the device's tiled layout (the module docstring's
    rule; tests/test_chip_compile.py compiles both layouts' decode programs
    for a described v5e and finds the slab-sized re-lay-out exactly where
    this says). A head_dim of no whole lanes is re-laid out by the kernels
    either way and an 8-bit type tiles otherwise: neither folds."""
    if head_dim % LANES or jnp.dtype(dtype).itemsize not in (2, 4):
        return False
    if n_kv_heads % SUBLANES == 0:
        return False
    return not (head_dim == LANES and SUBLANES % n_kv_heads == 0)


def make_arena(
    num_layers: int,
    num_pages: int,
    page_size: int,
    n_kv_heads: int,
    head_dim: int,
    dtype=jnp.bfloat16,
    quant: str | None = None,
    payload: tuple[tuple[int, ...], tuple[int, ...]] | None = None,
    sharded: bool = False,
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
    see [L, S_tot, ...] and ids.

    `sharded`: the arena will be committed to a mesh on its head axis
    (`--tp`), which a folded slab does not have; every dispatch is dense
    there, so no kernel views its pages. Otherwise plain K and V head slabs
    are stored folded, [L, S_tot * n_kv_heads, head_dim], wherever `folds`
    says the kernels' page view would re-lay them out."""
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
    if not sharded and folds(n_kv_heads, head_dim, dtype):
        shape = (num_layers, shape[1] * n_kv_heads, head_dim)
    return {"k": jnp.zeros(shape, dtype), "v": jnp.zeros(shape, dtype)}


def is_folded(slab) -> bool:
    """Whether a layer's slab (or the flat arena) of K/V heads is stored
    folded, [tokens * heads, head_dim]: it has two dimensions. (A latent
    page has two as well: its callers never ask.)"""
    return getattr(slab, "ndim", 3) == 2


def arena_tokens(arena_k, n_kv_heads: int | None) -> int:
    """S_tot of a stored arena [L, S_tot, ...]; `n_kv_heads`: the K/V heads
    a token's rows hold (None: a latent page), so that a folded arena's
    [L, S_tot * n_kv_heads, head_dim] counts its tokens and not its rows."""
    rows = arena_k.shape[1]
    folded = n_kv_heads is not None and getattr(arena_k, "ndim", 4) == 3
    return rows // n_kv_heads if folded else rows


def heads_view(slab, n_kv_heads: int):
    """A slab as a paged kernel takes it, [tokens, heads, head_dim]: itself,
    or a folded one reshaped. The kernel views that as pages of
    [page_size * heads, head_dim] rows at once, so the two reshapes are one
    bitcast of the folded slab."""
    if is_folded(slab):
        return slab.reshape(-1, n_kv_heads, slab.shape[-1])
    return slab


def slot_rows(slots, fold: int):
    """Token slots [N] -> the rows they take along a slab's token axis:
    themselves (`fold` 1), or in a folded slab the `fold` = n_kv_heads rows
    slot * fold + head, token-major [N * fold]. A slot out of range stays
    out of range. numpy in, numpy out; jax in, jax out."""
    if fold == 1:
        return slots
    xp = jnp if isinstance(slots, jax.Array) else np
    return (
        slots[:, None] * fold + xp.arange(fold, dtype=slots.dtype)[None, :]
    ).reshape(-1)


@jax.tree_util.register_dataclass
@dataclasses.dataclass(frozen=True)
class PageSlots:
    """A dispatch's token slots [N] that come as PAGE GROUPS, as
    `rows_fill_pages` says on the host: rows [g * page_size, (g + 1) *
    page_size) are the leading tokens of ONE page from its first, the rest of
    the group (and any whole group) out of range. `arena_write` writes such
    rows one index a page; everything else that takes slots (`layer_slots`)
    hands the type through."""

    slots: jax.Array
    page_size: int = dataclasses.field(metadata={"static": True})


def rows_fill_pages(slots: np.ndarray, page_size: int, oob: int) -> bool:
    """The rule of the page write, read on the host from a dispatch's padded
    slots (row order as the program gets them; `oob` and above: a padding
    row): every group of `page_size` rows is one page's leading tokens, in
    order from the page's first slot, then padding, or padding alone, and
    some group holds more than one token. A prompt chunk that starts on a
    page boundary reads so whatever its length; a decode row, tree rows, a
    chunk that starts inside a page and a pack with single rows before its
    chunk do not."""
    flat = np.ravel(slots)
    if flat.size < page_size or flat.size % page_size:
        return False
    groups = flat.reshape(-1, page_size)
    first = groups[:, :1]
    real = groups < oob
    runs_on = groups == first + np.arange(page_size, dtype=flat.dtype)
    return bool(
        np.all((first % page_size == 0) | ~real[:, :1])
        and np.all(runs_on | ~real)  # (so no row is real after a padding first)
        and real[:, 1:].any()
    )


def page_view_free(slab_shape: tuple, dtype) -> bool:
    """Whether a plain slab of this shape (a layer's, or the flat arena's:
    the leading dimension does not matter) can be viewed as pages, [pages,
    page_size * the rows a token takes, lanes], by a bitcast in the device's
    tiled layout, so that a write through the view moves nothing
    (tests/test_chip_compile.py compiles both sides for a described v5e).
    The minor dimension must be whole lanes of a 2- or 4-byte type; a folded
    slab and a latent page, [rows, lanes], then always are; a slab of K/V
    heads [S, n_kv, hd] is where `folds` leaves it unfolded BECAUSE its bytes
    are the folded slab's (a folding shape stored unfolded, as under a
    `--tp` mesh, is not, and keeps the row scatter)."""
    lanes = slab_shape[-1]
    if lanes % LANES or jnp.dtype(dtype).itemsize not in (2, 4):
        return False
    if len(slab_shape) == 3:
        return not folds(slab_shape[1], lanes, dtype)
    return len(slab_shape) == 2


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
    S_tot of layer l would be slot 0 of layer l + 1. `PageSlots` stay so
    (a layer's pages start at a multiple of page_size in the flat arena)."""
    if isinstance(slots, PageSlots):
        return dataclasses.replace(
            slots, slots=layer_slots(slots.slots, layer, s_tot, num_layers)
        )
    valid = (slots >= 0) & (slots < s_tot)
    return jnp.where(valid, slots + layer * s_tot, num_layers * s_tot)


def layer_pages(page_table: jax.Array, layer, num_pages: int):
    """Layer `layer`'s physical page ids inside the flat arena. Padding
    entries (page 0) become the layer's own page 0: read, then masked."""
    return page_table + layer * num_pages


def _write_pages(slab, slots: PageSlots, new):
    """`new` [N, ...] written into `slab` (its page view free:
    `page_view_free`) one index a PAGE. A page the rows fill in part (a
    chunk's tail) is read, its real rows replaced, and written back; a group
    of padding rows alone has its page id out of range and is dropped, like
    a padding row of the row scatter."""
    page_size = slots.page_size
    n = slots.slots.shape[0]
    lanes = slab.shape[-1]
    token_rows = new.size // (n * lanes)  # the K/V heads, or 1 (a latent)
    pages = slab.reshape(-1, page_size * token_rows, lanes)
    groups = slots.slots.reshape(n // page_size, page_size)
    page_ids = groups[:, 0] // page_size
    real = groups < pages.shape[0] * page_size
    real = jnp.repeat(real, token_rows, axis=1)[:, :, None]
    new = new.reshape(-1, *pages.shape[1:]).astype(slab.dtype)
    old = pages.at[page_ids].get(mode="clip")
    pages = pages.at[page_ids].set(jnp.where(real, new, old), mode="drop")
    return pages.reshape(slab.shape)


def arena_write(
    k_layer: jax.Array,  # [S, n_kv, hd]: a layer's slab or the flat arena
    v_layer: jax.Array,
    slots,  # [N] int32 flat slot ids, or PageSlots of them
    k_new: jax.Array,  # [N, n_kv, hd]
    v_new: jax.Array,
) -> tuple[jax.Array, jax.Array]:
    """Write new KV rows into a slab (functional; donate the slab).

    Out-of-bounds slot ids are dropped — the span step points padding rows at
    slot == num_slots to discard their writes (`layer_slots` keeps them out
    of bounds when the slab is the flat arena).

    A folded slab [S * n_kv, hd] takes the same TOKEN slots and rows: each
    token's heads go to rows slot * n_kv + head.

    `PageSlots` (the host saw that the rows come as page groups) are written
    one index a page wherever the slab's page view is free (`page_view_free`:
    every plain slab the cells hold); plain slots, an int4 slab and a slab
    whose page view would move it are scattered row by row. The slab
    afterwards is the same either way, bit for bit.
    """
    from bloombee_tpu.kv.quant import QuantSlab, quantize

    if isinstance(slots, PageSlots):
        if not isinstance(k_layer, QuantSlab) and all(
            page_view_free(slab.shape, slab.dtype)
            for slab in (k_layer, v_layer)
        ):
            return (
                _write_pages(k_layer, slots, k_new),
                _write_pages(v_layer, slots, v_new),
            )
        slots = slots.slots

    if is_folded(k_layer) and k_new.ndim == 3:
        # (the rows themselves are reshaped below, to the slab's own rows)
        slots = slot_rows(slots, k_new.shape[1])

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
    k_layer = k_layer.at[slots].set(
        k_new.reshape(-1, *k_layer.shape[1:]).astype(k_layer.dtype),
        mode="drop",
    )
    v_layer = v_layer.at[slots].set(
        v_new.reshape(-1, *v_layer.shape[1:]).astype(v_layer.dtype),
        mode="drop",
    )
    return k_layer, v_layer


def gather_pages(
    layer_slab: jax.Array,  # [S, n_kv, hd]: a layer's slab or the flat arena
    page_table: jax.Array,  # [B, max_pages] int32 page ids INTO that slab
    page_size: int,
    n_kv_heads: int | None = None,  # given by a caller whose slab holds K/V
    # heads and may be stored folded
) -> jax.Array:
    """Gather each sequence's pages: returns [B, max_pages*page_size, n_kv, hd].

    A folded slab [S * n_kv, hd] is gathered BY PAGE through its
    [pages, page_size * n_kv, hd] view (a bitcast): one index a page, not
    one a row.

    Invalid (padding) pages gather garbage rows; callers mask by context
    length — the clamped-read invariant lives in the attention mask, mirroring
    the reference's gather_prefix clamp (paged_kv.py:265-316).
    """
    from bloombee_tpu.kv.quant import QuantSlab, dequantize

    b, max_pages = page_table.shape
    if n_kv_heads is not None and is_folded(layer_slab):
        hd = layer_slab.shape[-1]
        pages = layer_slab.reshape(-1, page_size * n_kv_heads, hd)[page_table]
        return pages.reshape(b, max_pages * page_size, n_kv_heads, hd)
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
