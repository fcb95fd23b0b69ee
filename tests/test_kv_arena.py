"""Device arena ops: scatter-write/gather byte equivalence with a dense cache,
and reorder (spec-decode compaction) semantics.

Ports the intent of /root/reference/tests/test_phase0_cache_write_parity.py
(slab write == torch.cat) and test_paged_kv_spec_dec_routing.py.
"""

import numpy as np
import pytest

import jax
import jax.numpy as jnp

from bloombee_tpu.kv.arena import (
    PageSlots,
    arena_reorder,
    arena_write,
    flat_arena,
    gather_pages,
    layer_slots,
    make_arena,
    page_view_free,
    rows_fill_pages,
)
from bloombee_tpu.kv.paged import PagedKVTable


def test_write_then_gather_equals_dense():
    L, P, ps, kv, hd = 2, 8, 4, 2, 8
    arena = make_arena(L, P, ps, kv, hd, dtype=jnp.float32)
    t = PagedKVTable(P, ps)
    t.add_seq(0)
    t.add_seq(1)

    rng = np.random.default_rng(0)
    dense = {0: [], 1: []}
    # interleaved multi-step writes of uneven sizes
    for step, n in enumerate([3, 5, 1]):
        for sid in (0, 1):
            k_new = rng.normal(size=(n, kv, hd)).astype(np.float32)
            v_new = rng.normal(size=(n, kv, hd)).astype(np.float32)
            slots = jnp.asarray(t.assign_write_slots(sid, n))
            for layer in range(L):
                k_l, v_l = arena_write(
                    arena["k"][layer], arena["v"][layer], slots,
                    jnp.asarray(k_new) * (layer + 1), jnp.asarray(v_new),
                )
                arena["k"] = arena["k"].at[layer].set(k_l)
                arena["v"] = arena["v"].at[layer].set(v_l)
            dense[sid].append(k_new)

    pt = jnp.asarray(t.page_table([0, 1], max_pages=3))
    for layer in range(L):
        gathered = np.asarray(gather_pages(arena["k"][layer], pt, ps))
        for i, sid in enumerate((0, 1)):
            ref = np.concatenate(dense[sid], axis=0) * (layer + 1)
            np.testing.assert_array_equal(gathered[i, : len(ref)], ref)


def test_reorder_gathers_before_scatter():
    L, P, ps, kv, hd = 1, 4, 4, 1, 4
    arena = make_arena(L, P, ps, kv, hd, dtype=jnp.float32)
    rows = jnp.arange(P * ps, dtype=jnp.float32)[:, None, None] * jnp.ones(
        (1, kv, hd)
    )
    arena["k"] = arena["k"].at[0].set(rows)
    arena["v"] = arena["v"].at[0].set(rows * 10)

    # overlapping src/dst: move slots [5, 6, 2] onto [2, 3, 4]
    src = jnp.asarray([5, 6, 2])
    dst = jnp.asarray([2, 3, 4])
    k_l, v_l = arena_reorder(arena["k"][0], arena["v"][0], src, dst)
    got = np.asarray(k_l[:, 0, 0])
    # slot 4 must receive the OLD value of slot 2 (gather-before-scatter)
    assert got[2] == 5 and got[3] == 6 and got[4] == 2
    assert np.asarray(v_l[:, 0, 0])[4] == 20


# ------------------------------------------ the write by page (PR 49)
# `arena_write` on `PageSlots` (rows that come as page groups: one index a
# page, a page filled in part read and written back) against the row scatter
# of the same slots: the slab afterwards is the same, bit for bit.
PS, PAGES, LAYERS = 16, 12, 3
S_TOT = PS * PAGES
# layout -> (K slab's, V slab's shape a layer; a new row's K and V shape)
_LAYOUTS = {
    "folded": (((S_TOT * 2, 256),) * 2, ((2, 256),) * 2),
    "unfolded": (((S_TOT, 4, 128),) * 2, ((4, 128),) * 2),
    "latent": (((S_TOT, 256), (S_TOT, 128)), ((256,), (128,))),
}


def _chunk(page_ids, rows: int) -> np.ndarray:
    """A sequence's `rows` consecutive tokens from its pages' first slot."""
    pos = np.arange(rows)
    return (np.asarray(page_ids)[pos // PS] * PS + pos % PS).astype(np.int32)


def _padded(bucket: int, *chunks) -> np.ndarray:
    """[len(chunks), bucket] slots, each row a chunk then padding."""
    out = np.full((len(chunks), bucket), S_TOT, np.int32)
    for i, c in enumerate(chunks):
        out[i, : len(c)] = c
    return out


_CASES = {  # case -> padded slots [B, T]
    "whole": _padded(32, _chunk([5, 2], 32)),
    "tail": _padded(64, _chunk([7, 0, 9, 3], 55)),
    "padding": _padded(64, _chunk([11, 4], 19)),  # rows and whole groups
    "two_sequences": _padded(32, _chunk([1, 8], 32), _chunk([6, 10], 21)),
    "layer_offset": _padded(32, _chunk([3, 9], 27)),
}


def _slabs(layout, layers, seed):
    """(K slab, V slab) of random numbers, `layers` layers flat, and a
    function making `n` new rows (K, V)."""
    rng = np.random.default_rng(seed)
    shapes, new = _LAYOUTS[layout]

    def draw(shape):
        return jnp.asarray(rng.standard_normal(shape), jnp.bfloat16)

    slabs = tuple(draw((layers * s[0], *s[1:])) for s in shapes)
    return slabs, lambda n: tuple(draw((n, *r)) for r in new)


def _by_page(k, v, slots, k_new, v_new):
    return arena_write(k, v, PageSlots(slots, PS), k_new, v_new)


def _scatter_indices(fn, *args) -> list[int]:
    """How many indices each scatter of the traced function takes."""
    return [
        eqn.invars[1].aval.shape[0]
        for eqn in jax.make_jaxpr(fn)(*args).jaxpr.eqns
        if eqn.primitive.name == "scatter"
    ]


@pytest.mark.parametrize("case", sorted(_CASES))
@pytest.mark.parametrize("layout", sorted(_LAYOUTS))
def test_page_write_equals_row_scatter(layout, case):
    padded = _CASES[case]
    assert rows_fill_pages(padded, PS, S_TOT)
    layers = LAYERS if case == "layer_offset" else 1
    (k0, v0), new = _slabs(layout, layers, sorted(_CASES).index(case))
    assert page_view_free(k0.shape, k0.dtype)
    slots = jnp.asarray(padded.reshape(-1))
    if case == "layer_offset":
        # the flat arena, layer 1's rows: a padding slot maps past its END
        slots = layer_slots(slots, 1, S_TOT, layers)
        assert int(slots.max()) == layers * S_TOT
    k_new, v_new = new(slots.shape[0])
    want = arena_write(k0, v0, slots, k_new, v_new)
    got = jax.jit(_by_page)(k0, v0, slots, k_new, v_new)
    for g, w, before in zip(got, want, (k0, v0)):
        assert g.shape == w.shape and g.dtype == w.dtype
        np.testing.assert_array_equal(
            np.asarray(g, np.float32), np.asarray(w, np.float32))
        changed = np.any(
            np.asarray(g, np.float32).reshape(layers * S_TOT, -1)
            != np.asarray(before, np.float32).reshape(layers * S_TOT, -1),
            axis=1)
        real = np.asarray(slots)[np.asarray(slots) < layers * S_TOT]
        assert sorted(np.flatnonzero(changed)) == sorted(real)
    # one index a page group (padding groups included), not one a row
    n = slots.shape[0]
    assert _scatter_indices(_by_page, k0, v0, slots, k_new, v_new) == [
        n // PS] * 2
    fold = 2 if layout == "folded" else 1
    assert _scatter_indices(arena_write, k0, v0, slots, k_new, v_new) == [
        n * fold] * 2


def test_layer_slots_hand_page_slots_through():
    slots = PageSlots(jnp.asarray([16, 17, S_TOT, -1], jnp.int32), PS)
    got = layer_slots(slots, 2, S_TOT, LAYERS)
    assert isinstance(got, PageSlots) and got.page_size == PS
    assert got.slots.tolist() == [
        16 + 2 * S_TOT, 17 + 2 * S_TOT, LAYERS * S_TOT, LAYERS * S_TOT]


@pytest.mark.parametrize("rows,why", [
    (_padded(32, _chunk([5, 2], 32) + 3), "a chunk that starts inside a page"),
    (_padded(1, _chunk([5], 1)), "a decode row"),
    (_padded(1, _chunk([5], 1), _chunk([7], 1)), "a decode group"),
    (_padded(8, _chunk([5], 7)), "a bucket under one page"),
    (_padded(32, np.r_[_chunk([5], 16), _chunk([2], 16)[::-1]]),
     "rows of a page out of order"),
    (_padded(16, np.r_[S_TOT, _chunk([5], 15)]), "a page's rows after padding"),
    (_padded(32), "padding alone"),
    (_padded(16, _chunk([5], 1)), "one row, padded to a page"),
])
def test_rows_that_are_no_page_groups_keep_the_scatter(rows, why):
    assert not rows_fill_pages(rows, PS, S_TOT), why


@pytest.mark.parametrize("slab,why", [
    ((S_TOT, 2, 256), "a folding shape stored unfolded (a --tp mesh)"),
    ((S_TOT, 12, 128), "likewise"),
    ((S_TOT, 2, 64), "no whole lanes"),
    ((S_TOT, 576), "a latent of no whole lanes"),
    ("int4", "an int4 slab"),
])
def test_slabs_without_a_free_page_view_keep_the_scatter(slab, why):
    """`PageSlots` into a slab whose page view would move it, or into an
    int4 slab: the row scatter, index for index and bit for bit."""
    from bloombee_tpu.kv.quant import QuantSlab, make_quant_slab

    rng = np.random.default_rng(7)
    slots = jnp.asarray(_CASES["tail"].reshape(-1))
    n = slots.shape[0]
    if slab == "int4":
        k0 = v0 = make_quant_slab((S_TOT, 4, 128))
        row = (4, 128)
    else:
        assert not page_view_free(slab, jnp.bfloat16), why
        k0 = v0 = jnp.asarray(rng.standard_normal(slab), jnp.bfloat16)
        row = slab[1:]
    k_new, v_new = (
        jnp.asarray(rng.standard_normal((n, *row)), jnp.bfloat16)
        for _ in range(2))
    want = arena_write(k0, v0, slots, k_new, v_new)
    got = _by_page(k0, v0, slots, k_new, v_new)
    for g, w in zip(jax.tree.leaves(got), jax.tree.leaves(want)):
        np.testing.assert_array_equal(
            np.asarray(g, np.float32), np.asarray(w, np.float32))
    assert isinstance(got[0], QuantSlab) == (slab == "int4")
    by_row = _scatter_indices(arena_write, k0, v0, slots, k_new, v_new)
    assert _scatter_indices(_by_page, k0, v0, slots, k_new, v_new) == by_row
    assert set(by_row) == {n}
