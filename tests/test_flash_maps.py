"""The index maps of the streamed flash grids, walked on the CPU with no
kernel run (PR 46): a causal grid's streamed side — K/V in the forward and
dq, the q side in dk/dv — stands still on the steps the kernel bodies skip,
so the pipeline, which copies a block when its index changes, fetches no
block a step is not going to read.

The builders' own ``pallas_call`` arguments are captured (the call itself
is replaced), once with the maps as they are and once with the parent's
(``parent_k`` / ``parent_q`` below: the identity, or the window's span
clamped at the array's end), and every operand's block index is evaluated
over the whole grid in the grid's order."""

import jax
import jax.numpy as jnp
import numpy as np
import pytest

from tony_tpu import profiler
from tony_tpu.ops import attention as A


def parent_k(nq, nk, bq, bk, causal, window, xp=jnp):
    """``_windowed_k`` as it stood before PR 46."""
    if window is None:
        return nk, lambda i, kb: kb
    kspan, _ = A._window_spans(nq, nk, bq, bk, window)
    return kspan, lambda i, kb: jnp.minimum(
        A._first_kb(i * bq, bk, window) + kb, nk - 1)


def parent_q(nq, nk, bq, bk, causal, window, xp=jnp):
    """``_windowed_q`` as it stood before PR 46."""
    if window is None:
        return nq, lambda j, x: x
    _, qspan = A._window_spans(nq, nk, bq, bk, window)
    return qspan, lambda j, x: jnp.minimum((j * bk) // bq + x, nq - 1)


def sds(*shape, dtype=jnp.bfloat16):
    return jax.ShapeDtypeStruct(shape, dtype)


# family -> (packed, heads, kv heads, variant, window in blocks of bk or None)
FAMILIES = {
    "mha_classic": (False, 2, 2, "_PLAIN", None),
    "mha_packed": (True, 2, 2, "_PLAIN", None),
    "gqa_classic": (False, 4, 2, "_PLAIN", None),
    "gqa_packed": (True, 4, 2, "_PLAIN", None),
    "sel": (True, 4, 2, "_SEL", None),
    "mla": (True, 2, 2, "_MLA", None),
    "window_mha": (True, 2, 2, "_PLAIN", 1.5),
    "window_gqa": (False, 4, 2, "_PLAIN", 2.6),
}
# (t, block_q, block_k): 1, 2, 4 and 32 blocks a side, and unequal blocks
SIZES = [(128, 128, 128), (256, 128, 128), (512, 128, 128),
         (1024, 256, 128), (1024, 128, 256), (4096, 128, 128)]
D, B = 128, 2


def grid_of(family, t, tk, bq, bk, causal=True):
    packed, h, hkv, var, window = FAMILIES[family]
    if window is not None:
        window = int(window * bk)
        window = None if window >= tk else window
    lay = A._Layout(packed, B, h, hkv, t, tk, D)
    g = A._Grid(lay, getattr(A, var), causal, 1.0,
                A.Blocks(*[(bq, bk)] * 3), True, None, window)
    extras = {"_PLAIN": (),
              "_SEL": (sds(B, -(-tk // A.SEL_SPAN), t, A.SEL_LANES,
                           dtype=jnp.int32),),
              "_MLA": (sds(B, h, t, 64), sds(B, tk, 64))}[var]
    return g, extras


def captured(monkeypatch, g, extras, maps=None):
    """``{kernel: (grid, in_specs)}`` of the three streamed calls of ``g``,
    under the module's maps or under ``maps = (k-side, q-side)``."""
    calls = []

    def pallas_call(kernel, *, grid, in_specs, out_shape, **kw):
        calls.append((tuple(grid), list(in_specs)))
        return lambda *operands: out_shape

    with monkeypatch.context() as m:
        m.setattr(A.pl, "pallas_call", pallas_call)
        if maps:
            m.setattr(A, "_visible_k", maps[0])
            m.setattr(A, "_visible_q", maps[1])
        lay = g.lay
        shape = ((B, lay.t, lay.h * D), (B, lay.tk, lay.hkv * D)) \
            if lay.packed else ((B * lay.h, lay.t, D), (B * lay.hkv, lay.tk, D))
        q, k = sds(*shape[0]), sds(*shape[1])
        lse = lay.per_head_f32()
        A._streamed_forward(g, q, k, k, *extras)
        A._streamed_backward(g, q, k, k, q, q, lse, *extras)
    return dict(zip(("fwd", "dq", "dkv"), calls))


def walk(grid, spec):
    """``[steps, len(block index)]``: the spec's block index at every grid
    step, in the grid's order."""
    ids = np.indices(grid).reshape(len(grid), -1)
    index = spec.index_map(*ids)
    return np.stack([np.broadcast_to(np.asarray(x), ids.shape[1:])
                     for x in index], axis=1)


def contributes(kernel, g, grid):
    """The kernel bodies' ``contributes`` at every grid step, from the
    bodies' own arithmetic (``_flash_kernel`` ... ``_flash_bwd_dkv_kernel``)."""
    lay, window = g.lay, g.window
    bq, bk = getattr(g.blocks, kernel)
    ids = np.indices(grid).reshape(len(grid), -1)
    if kernel != "dkv":
        qi, kb = ids[lay.axis], ids[lay.axis + 1]
        if window is not None:
            kb = kb + np.maximum(qi * bq - (window - 1), 0) // bk
        return kb * bk < (qi + 1) * bq if g.causal else kb >= 0
    kj, qx = ids[lay.axis], ids[lay.axis + 1]
    nq = -(-lay.t // bq)
    qb = qx % (grid[lay.axis + 1] // lay.reps)
    if window is None:
        return (qb + 1) * bq > kj * bk if g.causal else qb >= 0
    qb = qb + (kj * bk) // bq
    return (qb <= ((kj + 1) * bk + window - 2) // bq) & (qb < nq)


def copies(index, cell):
    """Block copies the pipeline issues for one operand over the steps of
    ``cell``: the first block and every change of block index."""
    own = index[cell]
    return 1 + int(np.count_nonzero(np.any(own[1:] != own[:-1], axis=1)))


def first_cell(g, grid):
    ids = np.indices(grid).reshape(len(grid), -1)
    return np.all(ids[:g.lay.axis] == 0, axis=0)


@pytest.mark.parametrize("t,bq,bk", SIZES,
                         ids=lambda v: str(v))
@pytest.mark.parametrize("family", sorted(FAMILIES))
def test_a_causal_streamed_side_stands_still_where_the_body_skips(
        family, t, bq, bk, monkeypatch):
    g, extras = grid_of(family, t, t, bq, bk)
    if g.var is A._SEL and A.SEL_SPAN % bk:
        pytest.skip("a selection word spans whole k-blocks")
    ours = captured(monkeypatch, g, extras)
    theirs = captured(monkeypatch, g, extras, (parent_k, parent_q))
    visited, total = A.kv_blocks(t, t, bq, bk, True, g.window)
    fetched = A.streamed_fetches(t, t, bq, bk, True, g.window)
    nq = t // bq
    for kernel in ("fwd", "dq", "dkv"):
        grid, specs = ours[kernel]
        assert grid == theirs[kernel][0]            # no grid changes
        runs = contributes(kernel, g, grid)
        cell = first_cell(g, grid)
        for spec, old in zip(specs, theirs[kernel][1]):
            # (a) where the body computes, every operand is the parent's
            np.testing.assert_array_equal(walk(grid, spec)[runs],
                                          walk(grid, old)[runs])
        if kernel == "dkv":
            # the q side (q, do, o, lse): a copy for at most every step
            # that computes and one a (k-block, rep) besides
            q_side = walk(grid, specs[0])
            assert copies(q_side, cell) <= int(runs[cell].sum()) + \
                (t // bk) * g.lay.reps
            before = copies(walk(grid, theirs[kernel][1][0]), cell)
            assert copies(q_side, cell) <= before
            if g.window is None and not runs[cell].all():
                # (under a window the parent's sweep began on the diagonal
                # and stood still at the array's end already)
                assert copies(q_side, cell) < before
            for same in (3, 4):             # do and o are blocked as q
                np.testing.assert_array_equal(walk(grid, specs[same]),
                                              q_side)
            lse = walk(grid, specs[5])      # the same (head, rows)
            np.testing.assert_array_equal(
                lse[:, [2, 1]] if g.lay.packed else lse[:, :2],
                q_side[:, [1, 2]] if g.lay.packed else q_side[:, :2])
            continue
        k_side = walk(grid, specs[1])
        np.testing.assert_array_equal(k_side, walk(grid, specs[2]))   # v
        # (b) the K/V index changes exactly as often as the counter says
        assert copies(k_side, cell) == fetched
        # (c) and never more than the visited blocks and one a row
        assert fetched <= visited + nq
        # what the parent fetched without a window: every scheduled block
        before = copies(walk(grid, theirs[kernel][1][1]), cell)
        steps = grid[g.lay.axis + 1]
        assert fetched <= before
        if g.window is None:
            assert before == (nq * steps if steps > 1 else 1)
    assert fetched <= visited <= total


@pytest.mark.parametrize("t,tk,bq,bk", [
    (512, 512, 128, 128), (256, 1024, 128, 256), (1024, 512, 128, 128)],
    ids=lambda v: str(v))
@pytest.mark.parametrize("family", ["mha_classic", "mha_packed",
                                    "gqa_classic", "gqa_packed"])
def test_a_call_that_is_not_causal_keeps_the_identity_maps(
        family, t, tk, bq, bk, monkeypatch):
    """(d) No step is skipped, so nothing stands still: the maps are the
    parent's, operand for operand and equation for equation (a jaxpr of
    such a call does not change)."""
    g, extras = grid_of(family, t, tk, bq, bk, causal=False)
    ours = captured(monkeypatch, g, extras)
    theirs = captured(monkeypatch, g, extras, (parent_k, parent_q))
    for kernel in ("fwd", "dq", "dkv"):
        grid, specs = ours[kernel]
        ids = [jax.ShapeDtypeStruct((), jnp.int32)] * len(grid)
        for spec, old in zip(specs, theirs[kernel][1]):
            np.testing.assert_array_equal(walk(grid, spec), walk(grid, old))
            assert str(jax.make_jaxpr(spec.index_map)(*ids)) == str(
                jax.make_jaxpr(old.index_map)(*ids))
        steps = np.indices(grid).reshape(len(grid), -1)[g.lay.axis + 1]
        side = walk(grid, specs[0 if kernel == "dkv" else 1])
        row = side[:, 1] if g.lay.packed else side[:, -2]
        np.testing.assert_array_equal(
            row, steps % (grid[g.lay.axis + 1] // g.lay.reps)
            if kernel == "dkv" else steps)


def test_a_causal_cross_call_stays_inside_its_arrays(monkeypatch):
    """Causal over other lengths (the mask is top-left aligned): k-blocks
    no query reaches, or q-blocks past every key, are skipped whole, and
    the maps stand on a block that exists."""
    for t, tk in ((512, 1024), (1024, 512)):
        g, extras = grid_of("gqa_packed", t, tk, 128, 128)
        ours = captured(monkeypatch, g, extras)
        for kernel, (grid, specs) in ours.items():
            for spec, shape in zip(specs[:3], (t, tk, tk)):
                rows = walk(grid, spec)[:, 1]
                assert rows.min() >= 0 and rows.max() < shape // 128


# the seven cells' forward grids: (t, head size, window) -> facts
CELLS = {
    "mistral7b.train": ((2048, 128, None), (10, 16, 1, 512)),
    "phi4flash.train-8k swa": ((8192, 128, 512), (31, 256, 16, 512)),
    "phi4flash.train-8k full": ((8192, 128, None), (36, 64, 35, 1024)),
    "keyevl2.train-16k": ((16384, 128, None), (136, 256, 135, 1024)),
    "zaya1.train-32k": ((32768, 128, None), (528, 1024, 527, 1024)),
    "kimilinear.train-32k": ((32768, 128, None), (528, 1024, 527, 1024)),
    "olmohybrid.train-16k": ((16384, 128, None), (136, 256, 135, 1024)),
    "glm47flash.train-16k": ((16384, 256, None), (528, 1024, 527, 512)),
}


@pytest.mark.parametrize("cell", sorted(CELLS))
def test_blocks_fetched_at_the_cells_shapes(cell):
    """``kv_blocks_fetched`` beside the two older counts: with K/V resident
    the one whole block (Mistral's cell); streamed, the visited blocks less
    the row starts that find their block in place — the parent's grids
    fetched every scheduled block (``total``)."""
    (t, d, window), (visited, total, fetched, side) = CELLS[cell]
    facts = A.block_facts(t, t, causal=True, window=window, head_dim=d)
    assert (facts["kv_blocks_visited"], facts["kv_blocks_total"],
            facts["kv_blocks_fetched"], facts["block_q.fwd"]) == (
        visited, total, fetched, side)
    assert facts["kv_blocks_fetched"] <= visited + t // side
    if fetched > 1:
        assert A.streamed_fetches(t, t, side, side, False) == total


def test_selected_attention_records_what_it_fetches():
    """``Attention._selected``'s trace-time facts (the Keye cell's length):
    every tile at or below the diagonal visited, no K/V block above it
    fetched."""
    from tony_tpu.models.transformer import _count_selection

    profiler.reset_timeline()
    _count_selection(16384, 2048, 128, 2)
    c = profiler.counters()
    profiler.reset_timeline()
    assert (c["attn:kv_blocks_visited.sel"], c["attn:kv_blocks_total.sel"],
            c["attn:kv_blocks_fetched.sel"]) == (136, 256, 135)
    assert c["attn:block_q.fwd.sel"] == c["attn:block_k.dkv.sel"] == 1024
    assert c["attn:selected_pairs"] < c["attn:causal_pairs"]
