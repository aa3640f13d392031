"""Flash attention: fused online-softmax attention as Pallas TPU kernels.

The score matrix never leaves VMEM: each (batch·head, q-block) grid cell
streams K/V blocks through the online-softmax recurrence (running max m,
normalizer l, accumulator acc — same math as
:mod:`tony_tpu.parallel.ring_attention`, which runs the recurrence *across
chips* while these kernels run it *within* one), so HBM traffic is O(T·D)
instead of O(T²) and the matmuls hit the MXU in bf16/f32 with f32
accumulation. Causal runs skip entire k-blocks above the diagonal — the
dominant win for long sequences — and a window those left of it: neither
computed nor copied from HBM. The backward is two more kernels (dq; dk/dv)
that recompute p from the saved log-sum-exp rows (no T×T residuals).

Entries (Pallas on a TPU or under ``interpret=True``, a pure-JAX reference
elsewhere): :func:`flash_attention` over ``[B, H, T, D]`` (any head size,
ragged and cross lengths; :func:`flash_attention_sharded` maps it over a
mesh) and :func:`flash_attention_packed` over the projections' own ``[B,
T, H·D]`` (heads as lane blocks, no transpose), both with zero-copy GQA
and an optional causal window; :func:`flash_attention_selected` (packed,
over the keys a learned selection names); :func:`flash_attention_mla`
(packed, query/key wider than the values, one key part shared by the
heads); :func:`flash_decode`, the serving plane's forward over a cache.

The training entries share what lies below them: three tile expressions,
six kernel bodies (forward, dq, dk/dv; K/V streamed by the grid or
resident in VMEM) and ONE description of a grid — a layout, a variant,
the call's statics — from which a streamed and a resident builder make
every ``pallas_call``. What the entries decide they decide from the
shapes: blocks (:func:`_pick_blocks`), padding (:func:`_plan_dispatch`),
residency (:func:`_resident_fits`); and from ``causal`` and ``window`` the
span of blocks a row can see, outside which a streamed grid's map stands
still and fetches nothing (:func:`_visible_k`, :func:`_visible_q`).
"""

from __future__ import annotations

import functools
import itertools
import math
from typing import Callable, NamedTuple, Optional

import numpy as np

from tony_tpu import profiler

# The first module of tony_tpu.ops: pallas is imported here before any
# other kernel file asks (set-up spans tony:import; its TPU half is 0.02 s).
with profiler.importing("jax"):
    import jax
    import jax.numpy as jnp
with profiler.importing("jax.experimental.pallas"):
    from jax.experimental import pallas as pl
    from jax.experimental.pallas import tpu as pltpu

from tony_tpu import remat

_NEG_INF = -1e30

# The per-row log-sum-exp is carried as [rows, _LSE_LANES] with the value
# replicated across lanes: a (block_q,) 1-D block has its second-to-minor
# dim squeezed, which the Mosaic TPU lowering rejects — blocks need a
# (sublane, lane) shape whose dims divide the (8, 128) f32 tile or equal
# the array dims. Lane-replicating is the same layout the reference JAX
# TPU flash kernel uses for its l/m residuals.
_LSE_LANES = 8


class Blocks(NamedTuple):
    """``(block_q, block_k)`` of the forward, the dq and the dk/dv call
    of one attention (static: a ``nondiff`` argument of the custom VJPs)."""
    fwd: tuple
    dq: tuple
    dkv: tuple


def reference_attention(q: jax.Array, k: jax.Array, v: jax.Array,
                        causal: bool = True,
                        scale: Optional[float] = None,
                        window: Optional[int] = None) -> jax.Array:
    """Plain attention over [B, H, T, D], f32 softmax accumulation.
    K/V may carry fewer heads (GQA); they are repeated up to H here —
    this is the semantic spec the zero-copy kernels are tested against.
    ``window`` (causal only): query t sees keys t-window+1 .. t."""
    d = q.shape[-1]
    scale = d ** -0.5 if scale is None else scale
    if k.shape[1] != q.shape[1]:
        reps = q.shape[1] // k.shape[1]
        k = jnp.repeat(k, reps, axis=1)
        v = jnp.repeat(v, reps, axis=1)
    s = jnp.einsum("bhqd,bhkd->bhqk", q.astype(jnp.float32),
                   k.astype(jnp.float32)) * scale
    if causal:
        t_q, t_k = q.shape[2], k.shape[2]
        gap = (jax.lax.broadcasted_iota(jnp.int32, (t_q, t_k), 0)
               - jax.lax.broadcasted_iota(jnp.int32, (t_q, t_k), 1))
        mask = gap >= 0 if window is None else (gap >= 0) & (gap < window)
        s = jnp.where(mask[None, None], s, _NEG_INF)
    p = jax.nn.softmax(s, axis=-1)
    return jnp.einsum("bhqk,bhkd->bhqd", p,
                      v.astype(jnp.float32)).astype(q.dtype)


def _scope(name: str, window) -> str:
    """Device scope of a kernel call: windowed calls carry ``_win`` (their
    custom calls are then named ``%attn_fwd_win.N`` ...), so a trace tells
    them from the full causal ones, whose work differs at equal shapes."""
    return name if window is None else f"{name}_win"


def _first_kb(q0, block_k, window, xp=jnp):
    """First k-block seen by a run of queries starting at position ``q0``
    (may be traced) under a causal window of ``window`` keys (query t
    sees keys t-window+1 .. t): the block holding the earliest key of
    the first query."""
    return xp.maximum(q0 - (window - 1), 0) // block_k


def _last_qb(kj, bq, block_k, window):
    """Last q-block (exclusive bound is the caller's) that sees k-block
    ``kj`` under the window: the block of the latest query that still
    sees the block's last key."""
    return ((kj + 1) * block_k + window - 2) // bq


def _window_spans(nq, nk, bq, block_k, window):
    """Static grid extents of a windowed call: the most k-blocks any
    q-block visits, and the most q-blocks any k-block is visited by."""
    kspan = max(min(nk - 1, ((qi + 1) * bq - 1) // block_k)
                - max(qi * bq - window + 1, 0) // block_k + 1
                for qi in range(nq))
    qspan = max(min(nq - 1, ((kj + 1) * block_k + window - 2) // bq)
                - (kj * block_k) // bq + 1 for kj in range(nk))
    return kspan, qspan


def kv_blocks(t: int, tk: int, block_q: Optional[int] = None,
              block_k: Optional[int] = None, causal: bool = True,
              window: Optional[int] = None, head_dim: int = 128,
              itemsize: int = 2) -> tuple:
    """(visited, total) K/V blocks of one head's forward grid at the
    blocks :func:`flash_attention` would pick for ``t`` x ``tk``: what
    the causal triangle and the window leave of the ``nq * nk`` square.
    Pure arithmetic (the tests read it); the kernels compute exactly these
    blocks and fetch no other (:func:`streamed_fetches`)."""
    facts = block_facts(t, tk, block_q, block_k, causal, window, head_dim,
                        itemsize)
    return facts["kv_blocks_visited"], facts["kv_blocks_total"]


def block_facts(t: int, tk: int, block_q: Optional[int] = None,
                block_k: Optional[int] = None, causal: bool = True,
                window: Optional[int] = None, head_dim: int = 128,
                itemsize: int = 2) -> dict:
    """What a call of these shapes runs, as the counters a model records
    once at trace time (``attn:<key>.<kind>``): the blocks each of the
    three kernels gets (``block_q.fwd`` ... ``block_k.dkv``),
    :func:`kv_blocks`' two counts and ``kv_blocks_fetched`` (the forward's
    K/V copies a head: :func:`streamed_fetches`; resident, the one whole
    block). Pure arithmetic: the same plan the entry points make."""
    _, blocks, extra = _plan_dispatch(t, tk, block_q, block_k, causal,
                                      window, head_dim, itemsize)
    bq, bk = blocks.fwd
    if isinstance(extra, tuple):
        t, tk = extra[0], extra[1]
    elif extra:
        t = tk = extra
    nq, nk = -(-t // bq), -(-tk // bk)
    if window is not None and window >= tk:
        window = None
    visited = 0
    for qi in range(nq):
        hi = min(nk - 1, ((qi + 1) * bq - 1) // bk) if causal else nk - 1
        lo = max(qi * bq - window + 1, 0) // bk if window else 0
        visited += hi - lo + 1
    fetched = 1 if _resident_fits(tk, head_dim, itemsize) else \
        streamed_fetches(t, tk, bq, bk, causal, window)
    facts = {"kv_blocks_visited": visited, "kv_blocks_total": nq * nk,
             "kv_blocks_fetched": fetched}
    for kernel, (kq, kk) in blocks._asdict().items():
        facts[f"block_q.{kernel}"] = kq
        facts[f"block_k.{kernel}"] = kk
    return facts


def streamed_fetches(t: int, tk: int, block_q: int, block_k: int,
                     causal: bool = True,
                     window: Optional[int] = None) -> int:
    """K/V block copies one head's STREAMED forward grid issues: the
    pipeline copies a block when its index changes, so the first block
    plus the changes of the builder's own map (:func:`_visible_k`) along
    the grid's order. The identity would give the ``nq * nk`` square."""
    nq, nk = -(-t // block_q), -(-tk // block_k)
    steps, kblk = _visible_k(nq, nk, block_q, block_k, causal, window, np)
    walk = np.broadcast_to(
        kblk(np.arange(nq)[:, None], np.arange(steps)[None, :]), (nq, steps))
    return 1 + int(np.count_nonzero(np.diff(walk.ravel())))


def _mask_s(s, q0, k0, causal, kv_len, window=None):
    """Score masking shared by every kernel body, for a tile whose first
    query sits at position ``q0`` and first key at ``k0`` (either may be
    traced): the causal triangle and/or the key-length mask for
    end-padded K/V (``kv_len`` = the REAL key count, a static int —
    ``None`` means no padded keys to hide), and/or the sliding window
    (``window`` keys back from the query, the query's own included; causal
    calls only). All are resolved at trace time, so the unmasked paths
    compile to exactly the pre-mask kernels. Padded keys never fully mask
    a k-block (padding rounds up to the block size, so the last block
    keeps >= 1 real key) — the online-softmax max can't get stuck at
    -inf. Under a window a row may see nothing of the first block its
    q-block visits; what it accumulates there at the running max's floor
    is multiplied by exp(floor - real max) = 0 when its first real block
    arrives (blocks are visited in ascending order and the diagonal is
    always real)."""
    if not causal and kv_len is None:
        return s
    k_pos = k0 + jax.lax.broadcasted_iota(jnp.int32, s.shape, 1)
    if causal:
        q_pos = q0 + jax.lax.broadcasted_iota(jnp.int32, s.shape, 0)
        keep = q_pos >= k_pos        # no subtraction where no window asks
        if window is not None:
            keep &= q_pos - k_pos < window
        s = jnp.where(keep, s, _NEG_INF)
    if kv_len is not None:
        s = jnp.where(k_pos < kv_len, s, _NEG_INF)
    return s


def _mask_at(q0, k0, causal, kv_len, window):
    """``_mask_s`` with a tile's position and the call's statics bound."""
    return functools.partial(_mask_s, q0=q0, k0=k0, causal=causal,
                             kv_len=kv_len, window=window)


# A learned selection (one per query, shared by the heads) reaches the
# kernels as bits: ``sel[b, w, t, lane]`` (int32) holds, in bit ``j``,
# whether query t sees key ``(32 w + j) * 128 + lane`` — 128-key blocks
# along the bits, so a tile's mask is a scalar shift of one [rows, 128]
# word block and one word block serves ``SEL_SPAN`` consecutive keys
# (1/8 byte a pair; :func:`pack_selection` writes the layout).
SEL_LANES = 128
SEL_SPAN = 32 * SEL_LANES


def _sel_keep(words, kb, block_k: int):
    """bool [rows, block_k]: the selection's bits for the tile whose
    k-block index (in blocks of ``block_k`` keys; may be traced) is ``kb``,
    from the [rows, 128] word block covering it."""
    chunks = block_k // SEL_LANES
    bit0 = (kb * chunks) % 32
    keep = [jax.lax.shift_right_logical(
        words, jnp.full(words.shape, bit0 + c, words.dtype)) & 1
        for c in range(chunks)]
    # packsite: region-local — lane tiles of one kernel tile's mask, inside
    # the Pallas body (VMEM values, no sharded array in sight).
    return (keep[0] if chunks == 1 else jnp.concatenate(keep, axis=1)) != 0


def _mask_sel(s, words, kb):
    """``_mask_s``'s counterpart for a selection. The selection is causal
    by construction, so no position is compared."""
    return jnp.where(_sel_keep(words, kb, s.shape[1]), s, _NEG_INF)


def _sel_word(block_k: int):
    """k-block index -> index of the selection word block holding it."""
    per = SEL_SPAN // block_k
    return lambda kb: kb // per


def _dot(a, b, dims):
    return jax.lax.dot_general(a, b, (dims, ((), ())),
                               preferred_element_type=jnp.float32)


_NT = ((1,), (1,))      # a @ b.T
_NN = ((1,), (0,))      # a @ b
_TN = ((0,), (0,))      # a.T @ b

# The three tile expressions below are the whole arithmetic of the six
# kernel bodies: a streamed body and its resident twin differ only in
# where the blocks come from (the grid, or a loop over VMEM) and where
# the running state lives (scratch, or the loop's carry). ``mask`` is
# ``_mask_s`` with the tile's position bound. Matmul inputs stay in their
# storage dtype (bf16): bf16 x bf16 products are exact in the MXU's f32
# accumulator, so this loses nothing over upcast-then-dot — and doesn't
# rely on Mosaic folding converts back out of an f32 matmul. The softmax
# runs in f32; ``scale`` multiplies the f32 scores, never a bf16 operand.


def _scores(q, k_blk, shared):
    """``q . k^T`` of one tile; with ``shared = (qs, ks_blk)`` plus the
    part of the score every head takes against one shared key part (MLA:
    ``[q_h, qs_h] . [k_h, ks]`` without a 192-wide operand)."""
    if shared is None:
        return _dot(q, k_blk, _NT)
    return _dot(q, k_blk, _NT) + _dot(shared[0], shared[1], _NT)


def _fwd_tile(q, k_blk, v_blk, carry, mask, scale, shared=None):
    """One online-softmax step: the ``[Bq, Bk]`` score tile folded into
    the running (max m, normalizer l, accumulator acc); p casts back to
    the storage dtype for p.v."""
    m, l, acc = carry
    s = mask(_scores(q, k_blk, shared) * scale)
    m_new = jnp.maximum(m, jnp.max(s, axis=-1, keepdims=True))
    p = jnp.exp(s - m_new)
    alpha = jnp.exp(m - m_new)
    l_new = l * alpha + jnp.sum(p, axis=-1, keepdims=True)
    acc_new = acc * alpha + _dot(p.astype(v_blk.dtype), v_blk, _NN)
    return m_new, l_new, acc_new


def _fwd_out(m, l, acc, o_ref, lse_ref):
    l_safe = jnp.where(l > 0, l, 1.0)
    o_ref[:] = (acc / l_safe).astype(o_ref.dtype)
    lse_ref[:] = jnp.broadcast_to(m + jnp.log(l_safe), lse_ref.shape)


def _row_dot(do, o):
    """D = rowsum(do * o), the softmax backward's per-row term, [Bq, 1]."""
    return jnp.sum(do.astype(jnp.float32) * o.astype(jnp.float32),
                   axis=-1, keepdims=True)


def _dq_tile(q, do, lse, D, k_blk, v_blk, mask, scale, shared=None):
    """dq's share of one tile: p recomputed exactly from (q, k, lse),
    ds = p * (dp - D), returns ds . k * scale in f32 (with ``shared``:
    and ds . ks * scale, the shared part's query gradient)."""
    p = jnp.exp(mask(_scores(q, k_blk, shared) * scale) - lse)
    dp = _dot(do, v_blk, _NT)
    ds = (p * (dp - D)).astype(k_blk.dtype)
    if shared is None:
        return _dot(ds, k_blk, _NN) * scale
    return _dot(ds, k_blk, _NN) * scale, _dot(ds, shared[1], _NN) * scale


def _dkv_tile(q, do, o, lse, k_blk, v_blk, mask, scale, shared=None):
    """(dk, dv) shares of one tile, f32: dv = p^T . do, dk = ds^T . q *
    scale, with p recomputed exactly from (q, k, lse) (with ``shared``:
    and ds^T . qs * scale, this head's share of the shared key part's
    gradient)."""
    p = jnp.exp(mask(_scores(q, k_blk, shared) * scale) - lse)
    dv = _dot(p.astype(do.dtype), do, _TN)
    dp = _dot(do, v_blk, _NT)
    ds = (p * (dp - _row_dot(do, o))).astype(q.dtype)
    if shared is None:
        return _dot(ds, q, _TN) * scale, dv
    return (_dot(ds, q, _TN) * scale, dv, _dot(ds, shared[0], _TN) * scale)


def _flash_kernel(q_ref, k_ref, v_ref, o_ref, lse_ref, m_scr, l_scr,
                  acc_scr, *, causal: bool, scale: float, qi_axis: int = 1,
                  kv_len: Optional[int] = None,
                  window: Optional[int] = None, sel_ref=None, shared=None):
    """Streamed-KV flash forward: grid ``(..., qi, kb)`` with the k-block
    axis INNERMOST, so K/V arrive one ``[Bk, D]`` block at a time (VMEM
    stays O(block), any context length fits) while the online-softmax
    state (running max m, normalizer l, accumulator acc) carries across
    k-steps in VMEM scratch. The q/o/lse blocks keep a constant index over
    the k axis, so they stay resident and o/lse flush once, written at the
    last k-step. Causal q-blocks skip the compute of k-blocks above the
    diagonal via predication: the steps stay scheduled, and copy nothing
    (the caller's k-side map stands still: ``_visible_k``). Also writes the
    log-sum-exp rows the backward kernels reconstruct p from.
    ``qi_axis`` is which grid axis carries the q-block index (the k axis
    is ``qi_axis + 1``): the layout's ``axis``. Under a ``window`` the k
    axis holds only the blocks a q-block can see (``_window_spans``): step
    0 is the q-block's first visible block, and blocks left of the window
    are neither fetched nor computed. ``sel_ref`` (the ``_SEL`` variant): the
    word block of a learned selection, which then is the mask. ``shared``
    (the ``_MLA`` variant): ``(qs_ref, ks_ref)``, this head's query part
    against the key part all heads share (:func:`_scores`)."""
    bq, d = q_ref.shape
    bk = k_ref.shape[0]
    qi = pl.program_id(qi_axis)
    kb = step = pl.program_id(qi_axis + 1)
    nkb = pl.num_programs(qi_axis + 1)
    if window is not None:
        kb = step + _first_kb(qi * bq, bk, window)

    @pl.when(step == 0)
    def _init():
        m_scr[:] = jnp.full_like(m_scr, _NEG_INF)
        l_scr[:] = jnp.zeros_like(l_scr)
        acc_scr[:] = jnp.zeros_like(acc_scr)

    contributes = (kb * bk < (qi + 1) * bq) if causal else (kb >= 0)

    @pl.when(contributes)
    def _step():
        mask = _mask_at(qi * bq, kb * bk, causal, kv_len, window) \
            if sel_ref is None else functools.partial(
                _mask_sel, words=sel_ref[:], kb=kb)
        if shared is None:
            m, l, acc = _fwd_tile(
                q_ref[:], k_ref[:], v_ref[:],
                (m_scr[:, 0:1], l_scr[:, 0:1], acc_scr[:]), mask, scale)
        else:
            m, l, acc = _fwd_tile(
                q_ref[:], k_ref[:], v_ref[:],
                (m_scr[:, 0:1], l_scr[:, 0:1], acc_scr[:]), mask, scale,
                (shared[0][:], shared[1][:]))
        acc_scr[:] = acc
        m_scr[:] = jnp.broadcast_to(m, m_scr.shape)
        l_scr[:] = jnp.broadcast_to(l, l_scr.shape)

    @pl.when(step == nkb - 1)
    def _finalize():
        _fwd_out(m_scr[:, 0:1], l_scr[:, 0:1], acc_scr[:], o_ref, lse_ref)


def _flash_bwd_dq_kernel(q_ref, k_ref, v_ref, do_ref, o_ref, lse_ref, dq_ref,
                         dq_scr, *, causal: bool, scale: float,
                         qi_axis: int = 1, kv_len: Optional[int] = None,
                         window: Optional[int] = None, sel_ref=None,
                         shared=None):
    """dq, streamed like the forward (grid ``(..., qi, kb)``, k innermost,
    dq accumulated in VMEM scratch): recompute p from (q, k, lse) per
    k-block — ds = p·(dpᵀ−D); dq += ds·k·scale. No T×T buffer and no
    full-length K/V ever materialize. ``shared``: ``(qs_ref, ks_ref,
    dqs_ref, dqs_scr)`` (MLA)."""
    bq, d = q_ref.shape
    bk = k_ref.shape[0]
    qi = pl.program_id(qi_axis)
    kb = step = pl.program_id(qi_axis + 1)
    nkb = pl.num_programs(qi_axis + 1)
    if window is not None:
        kb = step + _first_kb(qi * bq, bk, window)

    @pl.when(step == 0)
    def _init():
        dq_scr[:] = jnp.zeros_like(dq_scr)
        if shared is not None:
            shared[3][:] = jnp.zeros_like(shared[3])

    contributes = (kb * bk < (qi + 1) * bq) if causal else (kb >= 0)

    @pl.when(contributes)
    def _step():
        mask = _mask_at(qi * bq, kb * bk, causal, kv_len, window) \
            if sel_ref is None else functools.partial(
                _mask_sel, words=sel_ref[:], kb=kb)
        do = do_ref[:]
        if shared is None:
            dq_scr[:] = dq_scr[:] + _dq_tile(
                q_ref[:], do, lse_ref[:, 0:1], _row_dot(do, o_ref[:]),
                k_ref[:], v_ref[:], mask, scale)
        else:
            dq, dqs = _dq_tile(
                q_ref[:], do, lse_ref[:, 0:1], _row_dot(do, o_ref[:]),
                k_ref[:], v_ref[:], mask, scale,
                (shared[0][:], shared[1][:]))
            dq_scr[:] = dq_scr[:] + dq
            shared[3][:] = shared[3][:] + dqs

    @pl.when(step == nkb - 1)
    def _finalize():
        dq_ref[:] = dq_scr[:].astype(dq_ref.dtype)
        if shared is not None:
            shared[2][:] = shared[3][:].astype(shared[2].dtype)


def _flash_bwd_dkv_kernel(q_ref, k_ref, v_ref, do_ref, o_ref, lse_ref,
                          dk_ref, dv_ref, dk_scr, dv_scr, *, causal: bool,
                          scale: float, qi_axis: int = 1, nqb: int = 0,
                          kv_len: Optional[int] = None,
                          window: Optional[int] = None, nq: int = 0,
                          sel_ref=None, shared=None):
    """dk/dv, streamed: grid ``(..., kj, qx)`` with the q-side axis
    INNERMOST — q/do/o/lse arrive one block at a time while this k-block's
    dk/dv accumulate in VMEM scratch (dv += pᵀ·do; dk += dsᵀ·q·scale).
    Causal k-blocks skip q-blocks strictly above the diagonal, a sweep's
    first steps (the q-side map waits on the diagonal: ``_visible_q``).

    GQA: one kv head serves ``reps`` query heads, so the innermost axis is
    the FLATTENED (rep, q-block) index of size reps·nqb — the callers'
    q-side index maps decode it — and dk/dv accumulate across the whole
    sweep. ``nqb`` is the per-head q-block count (0 ⇒ no grouping: the
    axis is plain q-blocks). Under a ``window`` the per-head sweep
    holds only the q-blocks that can see this k-block (``nqb`` is then
    that span, ``nq`` the real q-block count): it starts at the diagonal
    and ends where the window does. ``shared``: ``(qs_ref, ks_ref,
    dks_ref, dks_scr)`` (MLA; this head's share of the shared part's
    gradient, summed over heads by the caller)."""
    bk, d = k_ref.shape
    bq = q_ref.shape[0]
    kj = pl.program_id(qi_axis)
    qx = pl.program_id(qi_axis + 1)
    nqx = pl.num_programs(qi_axis + 1)
    qb = qx % nqb if nqb else qx
    if window is not None:
        qb = qb + (kj * bk) // bq

    @pl.when(qx == 0)
    def _init():
        dk_scr[:] = jnp.zeros_like(dk_scr)
        dv_scr[:] = jnp.zeros_like(dv_scr)
        if shared is not None:
            shared[3][:] = jnp.zeros_like(shared[3])

    contributes = ((qb + 1) * bq > kj * bk) if causal else (qb >= 0)
    if window is not None:
        contributes = (qb <= _last_qb(kj, bq, bk, window)) & (qb < nq)

    @pl.when(contributes)
    def _step():
        mask = _mask_at(qb * bq, kj * bk, causal, kv_len, window) \
            if sel_ref is None else functools.partial(
                _mask_sel, words=sel_ref[:], kb=kj)
        if shared is None:
            dk, dv = _dkv_tile(q_ref[:], do_ref[:], o_ref[:],
                               lse_ref[:, 0:1], k_ref[:], v_ref[:], mask,
                               scale)
        else:
            dk, dv, dks = _dkv_tile(q_ref[:], do_ref[:], o_ref[:],
                                    lse_ref[:, 0:1], k_ref[:], v_ref[:],
                                    mask, scale,
                                    (shared[0][:], shared[1][:]))
            shared[3][:] = shared[3][:] + dks
        dv_scr[:] = dv_scr[:] + dv
        dk_scr[:] = dk_scr[:] + dk

    @pl.when(qx == nqx - 1)
    def _finalize():
        dk_ref[:] = dk_scr[:].astype(dk_ref.dtype)
        dv_ref[:] = dv_scr[:].astype(dv_ref.dtype)
        if shared is not None:
            shared[2][:] = shared[3][:].astype(shared[2].dtype)


def _visible_k(nq, nk, bq, bk, causal, window, xp=jnp):
    """(extent of the k axis, (q-block, step) -> k-block) of a q-major
    streamed grid (forward, dq). A causal q-block sees the k-blocks from
    the first its window reaches (block 0 without one) to its diagonal's;
    the axis holds the longest such span, counted from a row's first
    block. Past the span, on the steps the bodies skip, the map stands
    still on the NEXT row's first block: the pipeline copies a block only
    when its index changes, so that block arrives under the diagonal
    tile's compute and a skipped step fetches nothing. Not causal: the
    identity. ``xp``: numpy walks the map untraced (``streamed_fetches``)."""
    if not causal:
        return nk, lambda i, kb: kb
    seen = lambda i, kb: kb * bk < (i + 1) * bq     # the bodies' contributes
    if window is None:
        return nk, lambda i, kb: xp.where(seen(i, kb), kb, 0)
    first = lambda i: _first_kb(i * bq, bk, window, xp)

    def kblk(i, step):
        kb = first(i) + step
        return xp.where(seen(i, kb), kb, xp.minimum(first(i + 1), nk - 1))
    return _window_spans(nq, nk, bq, bk, window)[0], kblk


def _visible_q(nq, nk, bq, bk, causal, window, xp=jnp):
    """(per-head extent of the q sweep, (k-block, position) -> q-block) of
    the k-major dk/dv grid: a causal k-block is seen from the q-block on
    its diagonal to the last its window reaches (the array's last without
    one). Without a window the sweep holds every q-block and its skipped
    steps come FIRST: the map stands on the diagonal block until the sweep
    reaches it. With one the sweep starts there and stands on its last."""
    if not causal:
        return nq, lambda j, x: x
    first = lambda j: xp.minimum((j * bk) // bq, nq - 1)
    if window is None:
        return nq, lambda j, x: xp.maximum(x, first(j))
    last = lambda j: xp.minimum(_last_qb(j, bq, bk, window), nq - 1)
    return (_window_spans(nq, nk, bq, bk, window)[1],
            lambda j, x: xp.minimum(first(j) + x, last(j)))


def _lane_of(reps: int):
    """Packed-layout head -> kv-lane-block map; identity when reps == 1 so
    the MHA path keeps div-free index maps."""
    if reps == 1:
        return lambda h: h
    return lambda h: h // reps


# --------------------------------------------------------------------
# Resident-KV variants: the whole K/V for one (batch, head) lives in
# VMEM and the kernel loops k-blocks internally, letting causal grids
# skip above-diagonal blocks from the SCHEDULE (not just the compute)
# — measured ~7% faster than the streamed kernels at bench shapes.
# Only legal while K/V fit VMEM: ``_resident_fits`` gates the dispatch.
# --------------------------------------------------------------------

def _flash_kernel_resident(q_ref, k_ref, v_ref, o_ref, lse_ref, *, block_k: int,
                  causal: bool, scale: float, qi_axis: int = 1,
                  kv_len: Optional[int] = None,
                  window: Optional[int] = None):
    """One grid cell: q-block [Bq, D] against the full K/V [T, D] in VMEM,
    streamed in block_k chunks through the online-softmax recurrence. Also
    writes the log-sum-exp rows the backward kernels reconstruct p from.
    ``qi_axis``: the grid axis of the q-block index, the layout's ``axis``."""
    bq, d = q_ref.shape
    t = k_ref.shape[0]
    qi = pl.program_id(qi_axis)
    q = q_ref[:]
    # Only k-blocks touching or below the diagonal contribute.
    num_kb = pl.cdiv((qi + 1) * bq if causal else t, block_k)

    def body(kb, carry):
        mask = _mask_at(qi * bq, kb * block_k, causal, kv_len, window)
        return _fwd_tile(q, k_ref[pl.ds(kb * block_k, block_k), :],
                         v_ref[pl.ds(kb * block_k, block_k), :], carry,
                         mask, scale)

    kb0 = 0 if window is None else _first_kb(qi * bq, block_k, window)
    m, l, acc = jax.lax.fori_loop(kb0, num_kb, body, (
        jnp.full((bq, 1), _NEG_INF, jnp.float32),
        jnp.zeros((bq, 1), jnp.float32), jnp.zeros((bq, d), jnp.float32)))
    _fwd_out(m, l, acc, o_ref, lse_ref)


def _flash_bwd_dq_kernel_resident(q_ref, k_ref, v_ref, do_ref, o_ref, lse_ref, dq_ref,
                         *, block_k: int, causal: bool, scale: float,
                         qi_axis: int = 1, kv_len: Optional[int] = None,
                         window: Optional[int] = None):
    """dq for one q-block: recompute p from (q, k, lse) per k-block —
    ds = p·(dpᵀ−D); dq += ds·k·scale. No T×T buffer ever materializes."""
    bq, d = q_ref.shape
    t = k_ref.shape[0]
    qi = pl.program_id(qi_axis)
    q = q_ref[:]
    do = do_ref[:]
    D = _row_dot(do, o_ref[:])
    lse = lse_ref[:, 0:1]                                # [Bq, 1]
    num_kb = pl.cdiv((qi + 1) * bq if causal else t, block_k)

    def body(kb, dq):
        mask = _mask_at(qi * bq, kb * block_k, causal, kv_len, window)
        return dq + _dq_tile(q, do, lse, D,
                             k_ref[pl.ds(kb * block_k, block_k), :],
                             v_ref[pl.ds(kb * block_k, block_k), :],
                             mask, scale)

    kb0 = 0 if window is None else _first_kb(qi * bq, block_k, window)
    dq = jax.lax.fori_loop(kb0, num_kb, body,
                           jnp.zeros((bq, d), jnp.float32))
    dq_ref[:] = dq.astype(dq_ref.dtype)


def _flash_bwd_dkv_kernel_resident(q_ref, k_ref, v_ref, do_ref, o_ref, lse_ref,
                          dk_ref, dv_ref, *scratch, block_q: int,
                          causal: bool, scale: float, qi_axis: int = 1,
                          kv_len: Optional[int] = None,
                          window: Optional[int] = None):
    """dk/dv for one k-block: iterate q-blocks (from the diagonal down when
    causal): dv += pᵀ·do; dk += dsᵀ·q·scale.

    GQA: the grid carries a ``rep`` axis INSIDE the k-block axis (size 1
    without grouping); each rep step streams in one of the query heads this
    kv head serves, and dk/dv accumulate across the sweep in ``scratch``
    (two float32 k-blocks), flushing on the last rep. Without grouping the
    call passes no scratch: the one-rep path never touches it, and this is
    the variant whose dispatch is gated on VMEM fit."""
    bk, d = k_ref.shape
    t = q_ref.shape[0]
    kj = pl.program_id(qi_axis)
    rep = pl.program_id(qi_axis + 1)
    nreps = pl.num_programs(qi_axis + 1)   # static (grid is static)

    if nreps > 1:
        dk_scr, dv_scr = scratch

        @pl.when(rep == 0)
        def _init():
            dk_scr[:] = jnp.zeros_like(dk_scr)
            dv_scr[:] = jnp.zeros_like(dv_scr)
    k_blk = k_ref[:]
    v_blk = v_ref[:]
    num_qb = pl.cdiv(t, block_q)
    qb0 = (kj * bk) // block_q if causal else 0
    if window is not None:
        num_qb = jnp.minimum(num_qb, _last_qb(kj, block_q, bk, window) + 1)

    def body(qb, carry):
        rows = pl.ds(qb * block_q, block_q)
        mask = _mask_at(qb * block_q, kj * bk, causal, kv_len, window)
        dk, dv = _dkv_tile(q_ref[rows, :], do_ref[rows, :], o_ref[rows, :],
                           lse_ref[rows, 0:1], k_blk, v_blk, mask, scale)
        return carry[0] + dk, carry[1] + dv

    if nreps == 1:
        # MHA / reps==1 fast path: register accumulation, one flush — no
        # scratch round-trips (measured ~4 MFU points of a Llama train
        # step when the grouped path ran unconditionally).
        zeros = jnp.zeros((bk, d), jnp.float32)
        dk, dv = jax.lax.fori_loop(qb0, num_qb, body, (zeros, zeros))
        dk_ref[:] = dk.astype(dk_ref.dtype)
        dv_ref[:] = dv.astype(dv_ref.dtype)
        return

    dk, dv = jax.lax.fori_loop(qb0, num_qb, body,
                               (dk_scr[:], dv_scr[:]))
    dk_scr[:] = dk
    dv_scr[:] = dv

    @pl.when(rep == nreps - 1)
    def _finalize():
        dk_ref[:] = dk_scr[:].astype(dk_ref.dtype)
        dv_ref[:] = dv_scr[:].astype(dv_ref.dtype)


# One (batch, head)'s K/V must fit VMEM for the resident variants. The
# budget is in BYTES, not sequence length: VMEM use scales with
# tk·d·itemsize, so a gate on the length alone would OOM below it for
# head_dim>128 or f32 inputs. Calibrated on v5e at the measured boundary —
# t=4096·d=128·bf16 (1 MiB per tensor) fits with headroom, t=8192 OOMs.
_RESIDENT_KV_BYTES = 4096 * 128 * 2


def _resident_fits(tk: int, d: int, itemsize: int) -> bool:
    return tk * d * itemsize <= _RESIDENT_KV_BYTES


# --------------------------------------------------------------------
# One description of a flash grid, two builders. Everything between an
# entry and ``pallas_call`` — block specs, index maps, grids, scratch, out
# shapes, the GQA sweep of dk/dv, a window's spans — is written once,
# against a LAYOUT, a VARIANT and the call's statics (``_Grid``). The
# STREAMED builder walks the k-side (forward, dq) or the q-side (dk/dv)
# with the grid's last axis; the RESIDENT one holds it whole in VMEM.
# --------------------------------------------------------------------


def _zero(*ids):
    return 0


class _Layout(NamedTuple):
    """How the grids address a ``(rows, head)`` block of their operands.

    classic — ``[B, H, T, D]`` viewed ``[B·H, T, D]``: a head is a leading
    index, and a grid has ONE axis of ``B·heads`` cells before its block
    axes. packed — ``[B, T, H·D]``: a head is a lane block (its number the
    block index of the minor dimension, so ``D % 128 == 0``) and a grid
    starts ``(b, head)``; no ``[B, H, T, D]`` transpose ever materializes.
    A rows-per-head operand (the log-sum-exp, MLA's ``qs``) is ``[B·H, T,
    lanes]`` / ``[B, H, T, lanes]``.

    GQA is zero-copy in both: K/V carry ``hkv = h / reps`` heads; a q-major
    cell stands on a query head and reads :meth:`kv_head` of it, a k-major
    cell stands on a kv head and sweeps the query heads :meth:`q_head`
    names, so no repeated K/V ever materializes in HBM. The spec makers
    take ``head`` and ``row`` as functions of a cell's grid ids and call
    them in the order the block index lists them."""
    packed: bool
    b: int
    h: int
    hkv: int
    t: int
    tk: int
    d: int

    @property
    def reps(self) -> int:
        return self.h // self.hkv

    @property
    def axis(self) -> int:
        """The first block axis of a grid, after the (batch, head) cells:
        the kernels' ``qi_axis``."""
        return 2 if self.packed else 1

    def cells(self, heads: int) -> tuple:
        """Leading grid extents: one cell a (batch, head)."""
        return (self.b, heads) if self.packed else (self.b * heads,)

    def own(self, *ids):
        """The head a grid cell stands on (classic: over the batch)."""
        return ids[self.axis - 1]

    def kv_head(self, *ids):
        """The kv head that serves a q-major cell's query head; without
        grouping the identity, so MHA keeps div-free index maps."""
        g = self.own(*ids)
        if self.packed or self.reps == 1:
            return _lane_of(self.reps)(g)
        return (g // self.h) * self.hkv + (g % self.h) // self.reps

    def q_head(self, rep):
        """``ids -> `` the query head number ``rep(*ids)`` of those a
        k-major cell's kv head serves."""
        def head(*ids):
            g = self.own(*ids)
            if self.packed:
                return g * self.reps + rep(*ids)
            return ((g // self.hkv) * self.h + (g % self.hkv) * self.reps
                    + rep(*ids))
        return head

    def rows(self, n: int, head, row, width: Optional[int] = None):
        """Spec of ``n`` rows of one head of a q- or kv-like operand
        (``width`` lanes a head, ``d`` unless named; ``head=_zero`` with a
        ``width``: an operand all heads share, ``[B, T, width]``)."""
        block = (None, n, self.d if width is None else width)
        if self.packed:
            return pl.BlockSpec(
                block, lambda *ids: (ids[0], row(*ids), head(*ids)))
        return pl.BlockSpec(block, lambda *ids: (head(*ids), row(*ids), 0))

    def per_head(self, n: int, head, row, lanes: int = _LSE_LANES):
        """Spec of ``n`` rows of one head of a rows-per-head operand. The
        log-sum-exp is the default: ``_LSE_LANES`` equal lanes a row."""
        if self.packed:
            return pl.BlockSpec(
                (None, None, n, lanes),
                lambda *ids: (ids[0], head(*ids), row(*ids), 0))
        return pl.BlockSpec((None, n, lanes),
                            lambda *ids: (head(*ids), row(*ids), 0))

    def per_head_f32(self, lanes: int = _LSE_LANES):
        """A float32 rows-per-head result over the queries (the lse)."""
        shape = (self.b, self.h) if self.packed else (self.b * self.h,)
        return jax.ShapeDtypeStruct((*shape, self.t, lanes), jnp.float32)


class _Sides(NamedTuple):
    """A streamed grid as a variant's extra operands see it: the blocks,
    and the query head, q-block and k-block a grid cell works on (functions
    of its ids)."""
    bq: int
    bk: int
    qhead: Callable
    qrow: Callable
    krow: Callable


_NONE = ([], [], [])


class _Variant(NamedTuple):
    """What a family adds to the three streamed calls, as data. Its extra
    operands follow the base ones into every call, an extra result of dq
    or dk/dv follows the base results and brings one float32 scratch.

    ``tag`` ends the scope names; ``take`` turns a call's extra refs (ins,
    outs, scratch) into the keywords the kernel bodies take them by;
    ``specs(lay, sides, *extras)`` gives, under either grid, the extra
    operands' specs and the extra results of dq and of dk/dv, each as
    ``(specs, shapes, scratch)``. ``cost``: whether the forward carries a
    cost estimate — the selected and MLA calls never did (ROADMAP D18),
    and the estimate is part of the program a cell traces."""
    tag: str = ""
    take: Optional[Callable] = None
    specs: Callable = lambda lay, sides: ([], _NONE, _NONE)
    cost: bool = False


_PLAIN = _Variant(cost=True)


def _extras_kernel(body, take, n_in, n_x, n_out, n_dx, *refs, **kw):
    """The one adapter between a variant's call and a kernel body. The call
    lists base ins, extra ins, base outs, extra outs, base scratch, one
    scratch an extra out; the body takes the base refs by position and the
    extras by ``take``'s keywords."""
    cuts = [0, *itertools.accumulate((n_in, n_x, n_out, n_dx)),
            len(refs) - n_dx, len(refs)]
    ins, xs, outs, dxs, scr, xscr = (
        refs[a:b] for a, b in zip(cuts, cuts[1:]))
    body(*ins, *outs, *scr, **take(*xs, *dxs, *xscr), **kw)


class _Grid(NamedTuple):
    """One attention as the builders see it: layout, variant, and the
    statics of the call."""
    lay: _Layout
    var: _Variant
    causal: bool
    scale: float
    blocks: Blocks
    interpret: bool
    kv_len: Optional[int] = None
    window: Optional[int] = None

    def scope(self, name: str) -> str:
        return _scope(name, self.window) + self.var.tag

    def kernel(self, body, n_in: int = 0, n_out: int = 0, extras=(),
               n_dx: int = 0, **kw):
        """``body`` with the call's statics bound — where the variant brings
        extra refs, behind the adapter, told how many refs of each kind."""
        kw.update(causal=self.causal, scale=self.scale,
                  qi_axis=self.lay.axis, kv_len=self.kv_len,
                  window=self.window)
        if self.var.take is None:
            return functools.partial(body, **kw)
        return functools.partial(_extras_kernel, body, self.var.take, n_in,
                                 len(extras), n_out, n_dx, **kw)

    def cost(self, q, k, v):
        if not self.var.cost:
            return None
        pairs = self.lay.b * self.lay.h * self.lay.t * self.lay.tk
        return pl.CostEstimate(
            flops=4 * pairs * self.lay.d // (2 if self.causal else 1),
            bytes_accessed=(q.size + k.size + v.size) * q.dtype.itemsize,
            transcendentals=pairs)


def _like(x):
    return jax.ShapeDtypeStruct(x.shape, x.dtype)


def _q_major(g: _Grid, bq: int, bk: Optional[int] = None):
    """The q-major grid (forward, dq), each cell a pinned q-block of one
    query head: ``(grid, q-side spec, k-side spec, lse spec, sides)``.
    Streamed, a last axis walks the k-blocks a q-block sees and stands
    still on the steps it skips (``_visible_k``). Resident (``bk=None``)
    there is no such axis: the kv head's whole K/V is one block."""
    lay = g.lay
    nq = pl.cdiv(lay.t, bq)

    def qrow(*ids):
        return ids[lay.axis]

    if bk is None:
        inner, krows, krow = (), lay.tk, _zero
    else:
        nkw, kblk = _visible_k(nq, pl.cdiv(lay.tk, bk), bq, bk, g.causal,
                               g.window)
        inner, krows = (nkw,), bk

        def krow(*ids):
            return kblk(ids[lay.axis], ids[lay.axis + 1])

    return ((*lay.cells(lay.h), nq, *inner), lay.rows(bq, lay.own, qrow),
            lay.rows(krows, lay.kv_head, krow),
            lay.per_head(bq, lay.own, qrow),
            _Sides(bq, bk, lay.own, qrow, krow))


def _streamed_forward(g: _Grid, q, k, v, *extras):
    """``(out, lse)`` of the streamed forward."""
    lay = g.lay
    bq, bk = g.blocks.fwd
    grid, q_pin, k_str, lse_pin, sides = _q_major(g, bq, bk)
    with jax.named_scope(g.scope("attn_fwd")):
        return pl.pallas_call(
            g.kernel(_flash_kernel, 3, 2, extras),
            grid=grid,
            in_specs=[q_pin, k_str, k_str,
                      *g.var.specs(lay, sides, *extras)[0]],
            out_specs=(q_pin, lse_pin),
            out_shape=(_like(q), lay.per_head_f32()),
            scratch_shapes=[
                pltpu.VMEM((bq, _LSE_LANES), jnp.float32),   # m
                pltpu.VMEM((bq, _LSE_LANES), jnp.float32),   # l
                pltpu.VMEM((bq, lay.d), jnp.float32)],       # acc
            interpret=g.interpret,
            cost_estimate=g.cost(q, k, v),
        )(q, k, v, *extras)


def _streamed_backward(g: _Grid, q, k, v, do, o, lse, *extras):
    """``(dq, dk, dv, extra results of dq, extra results of dk/dv)``,
    streamed."""
    lay, var = g.lay, g.var
    operands = (q, k, v, do, o, lse, *extras)
    bq, bk = g.blocks.dq
    grid, q_pin, k_str, lse_pin, sides = _q_major(g, bq, bk)
    xins, (xspecs, xshapes, xscratch), _ = var.specs(lay, sides, *extras)
    with jax.named_scope(g.scope("attn_bwd_dq")):
        dq, *dxq = pl.pallas_call(
            g.kernel(_flash_bwd_dq_kernel, 6, 1, extras, len(xspecs)),
            grid=grid,
            in_specs=[q_pin, k_str, k_str, q_pin, q_pin, lse_pin, *xins],
            out_specs=[q_pin, *xspecs],
            out_shape=[_like(q), *xshapes],
            scratch_shapes=[pltpu.VMEM((bq, lay.d), jnp.float32), *xscratch],
            interpret=g.interpret,
        )(*operands)

    # dk/dv grid: (cells of kv heads, kj, qx) — the q-side walked
    # innermost. qx is the flattened (rep, q-block) sweep over every query
    # head the kv head serves, each rep's part mapped to the q-blocks that
    # see the k-block (``_visible_q``); dk/dv accumulate across all of it.
    # Without grouping or window the maps stay free of div and mod.
    bq, bk = g.blocks.dkv
    nq, nk = pl.cdiv(lay.t, bq), pl.cdiv(lay.tk, bk)
    nqb, qblk = _visible_q(nq, nk, bq, bk, g.causal, g.window)

    def krow(*ids):
        return ids[lay.axis]

    def qx(*ids):
        return ids[lay.axis + 1]

    plain_sweep = lay.reps == 1 and g.window is None
    if plain_sweep:
        qhead, at = lay.own, qx
    else:
        qhead = lay.q_head(lambda *ids: qx(*ids) // nqb)
        at = lambda *ids: qx(*ids) % nqb

    def qrow(*ids):
        return qblk(krow(*ids), at(*ids))

    sides = _Sides(bq, bk, qhead, qrow, krow)
    q_str = lay.rows(bq, qhead, qrow)
    k_pin = lay.rows(bk, lay.own, krow)
    xins, _, (xspecs, xshapes, xscratch) = var.specs(lay, sides, *extras)
    with jax.named_scope(g.scope("attn_bwd_dkv")):
        dk, dv, *dxk = pl.pallas_call(
            g.kernel(_flash_bwd_dkv_kernel, 6, 2, extras, len(xspecs),
                     nqb=0 if plain_sweep else nqb, nq=nq),
            grid=(*lay.cells(lay.hkv), nk, lay.reps * nqb),
            in_specs=[q_str, k_pin, k_pin, q_str, q_str,
                      lay.per_head(bq, qhead, qrow), *xins],
            out_specs=[k_pin, k_pin, *xspecs],
            out_shape=[_like(k), _like(v), *xshapes],
            scratch_shapes=[pltpu.VMEM((bk, lay.d), jnp.float32),
                            pltpu.VMEM((bk, lay.d), jnp.float32), *xscratch],
            interpret=g.interpret,
        )(*operands)
    return dq, dk, dv, dxq, dxk


def _resident_forward(g: _Grid, q, k, v):
    """``(out, lse)`` of the resident forward."""
    bq, bk = g.blocks.fwd
    grid, q_blk, kv_all, lse_blk, _ = _q_major(g, bq)
    with jax.named_scope(g.scope("attn_fwd")):
        return pl.pallas_call(
            g.kernel(_flash_kernel_resident, block_k=bk),
            grid=grid,
            in_specs=[q_blk, kv_all, kv_all],
            out_specs=(q_blk, lse_blk),
            out_shape=(_like(q), g.lay.per_head_f32()),
            interpret=g.interpret,
            cost_estimate=g.cost(q, k, v),
        )(q, k, v)


def _resident_backward(g: _Grid, q, k, v, do, o, lse):
    """``(dq, dk, dv)``, resident."""
    lay = g.lay
    bq, bk = g.blocks.dq
    grid, q_blk, kv_all, lse_blk, _ = _q_major(g, bq)
    with jax.named_scope(g.scope("attn_bwd_dq")):
        dq = pl.pallas_call(
            g.kernel(_flash_bwd_dq_kernel_resident, block_k=bk),
            grid=grid,
            in_specs=[q_blk, kv_all, kv_all, q_blk, q_blk, lse_blk],
            out_specs=q_blk,
            out_shape=_like(q),
            interpret=g.interpret,
        )(q, k, v, do, o, lse)

    # dk/dv grid: (cells of kv heads, kj, rep) — rep brings in, one at a
    # time and whole, the query heads the kv head serves; dk/dv accumulate
    # in scratch across them (none without grouping: see the kernel).
    bq, bk = g.blocks.dkv
    qhead = lay.q_head(lambda *ids: ids[lay.axis + 1])
    q_all = lay.rows(lay.t, qhead, _zero)
    k_blk = lay.rows(bk, lay.own, lambda *ids: ids[lay.axis])
    with jax.named_scope(g.scope("attn_bwd_dkv")):
        dk, dv = pl.pallas_call(
            g.kernel(_flash_bwd_dkv_kernel_resident, block_q=bq),
            grid=(*lay.cells(lay.hkv), pl.cdiv(lay.tk, bk), lay.reps),
            in_specs=[q_all, k_blk, k_blk, q_all, q_all,
                      lay.per_head(lay.t, qhead, _zero)],
            out_specs=(k_blk, k_blk),
            out_shape=(_like(k), _like(v)),
            scratch_shapes=[pltpu.VMEM((bk, lay.d), jnp.float32)] * 2
            if lay.reps > 1 else [],
            interpret=g.interpret,
        )(q, k, v, do, o, lse)
    return dq, dk, dv


def _forward(g: _Grid, q, k, v):
    if _resident_fits(g.lay.tk, g.lay.d, k.dtype.itemsize):
        return _resident_forward(g, q, k, v)
    return _streamed_forward(g, q, k, v)


def _backward(g: _Grid, q, k, v, do, o, lse):
    if _resident_fits(g.lay.tk, g.lay.d, k.dtype.itemsize):
        return _resident_backward(g, q, k, v, do, o, lse)
    return _streamed_backward(g, q, k, v, do, o, lse)[:3]


def _named(out, lse):
    """The two residuals of every flash forward under the names the
    backward's keep-rule knows (:mod:`tony_tpu.remat`)."""
    return remat.name(out, "flash_out"), remat.name(lse, "flash_lse")


def _classic(q, k) -> _Layout:
    b, h, t, d = q.shape
    return _Layout(False, b, h, k.shape[1], t, k.shape[2], d)


def _rows_of(x):
    """``[B, H, T, D]`` as the classic grids read it: ``[B·H, T, D]``."""
    return x.reshape(-1, *x.shape[2:])


@functools.partial(jax.custom_vjp, nondiff_argnums=(3, 4, 5, 6, 7, 8))
def _flash(q, k, v, causal, scale, blocks, interpret, kv_len=None,
           window=None):
    return _flash_fwd(q, k, v, causal, scale, blocks, interpret, kv_len,
                      window)[0]


def _flash_fwd(q, k, v, causal, scale, blocks, interpret, kv_len=None,
               window=None):
    g = _Grid(_classic(q, k), _PLAIN, causal, scale, blocks, interpret,
              kv_len, window)
    out, lse = _forward(g, *map(_rows_of, (q, k, v)))
    out, lse = _named(out.reshape(q.shape), lse)   # lse: [b·h, t, lanes]
    return out, (q, k, v, out, lse)


def _flash_bwd(causal, scale, blocks, interpret, kv_len, window, residuals,
               do):
    q, k, v, out, lse = residuals
    g = _Grid(_classic(q, k), _PLAIN, causal, scale, blocks, interpret,
              kv_len, window)
    grads = _backward(g, *map(_rows_of, (q, k, v, do, out)), lse)
    return tuple(dx.reshape(x.shape) for dx, x in zip(grads, (q, k, v)))


_flash.defvjp(_flash_fwd, _flash_bwd)


def _packed(q, k, heads: int) -> _Layout:
    b, t, hd = q.shape
    d = hd // heads
    return _Layout(True, b, heads, k.shape[2] // d, t, k.shape[1], d)


@functools.partial(jax.custom_vjp, nondiff_argnums=(3, 4, 5, 6, 7, 8))
def _flash_packed(q, k, v, heads, causal, scale, blocks, interpret,
                  window=None):
    return _flash_packed_fwd(q, k, v, heads, causal, scale, blocks,
                             interpret, window)[0]


def _flash_packed_fwd(q, k, v, heads, causal, scale, blocks, interpret,
                      window=None):
    g = _Grid(_packed(q, k, heads), _PLAIN, causal, scale, blocks,
              interpret, None, window)
    out, lse = _named(*_forward(g, q, k, v))
    return out, (q, k, v, out, lse)


def _flash_packed_bwd(heads, causal, scale, blocks, interpret, window,
                      residuals, do):
    q, k, v, out, lse = residuals
    g = _Grid(_packed(q, k, heads), _PLAIN, causal, scale, blocks,
              interpret, None, window)
    return _backward(g, q, k, v, do, out, lse)


_flash_packed.defvjp(_flash_packed_fwd, _flash_packed_bwd)


# --------------------------------------------------------------------
# Attention over a learned selection: every query sees the keys one
# selection names (shared by its heads), packed as ``_mask_sel`` reads
# them. The three streamed packed grids with the selection's word block as
# one more operand; tiles above the diagonal are skipped as under the
# causal mask, tiles below it are all visited (whether a tile no query
# selects can be skipped is not settled here: ``attn:kv_blocks_visited.sel``
# counts what is visited).
# --------------------------------------------------------------------


def pack_selection(keep: jax.Array) -> jax.Array:
    """bool ``[B, T, Tk]`` -> the selection's words, int32
    ``[B, ceil(Tk / SEL_SPAN), T, 128]``."""
    b, t, tk = keep.shape
    w = -(-tk // SEL_SPAN)
    bits = jnp.pad(keep, ((0, 0), (0, 0), (0, w * SEL_SPAN - tk))).reshape(
        b, t, w, 32, SEL_LANES).astype(jnp.uint32)
    words = jnp.sum(bits << jnp.arange(32, dtype=jnp.uint32)[:, None],
                    axis=3, dtype=jnp.uint32)
    return jax.lax.bitcast_convert_type(words, jnp.int32).transpose(
        0, 2, 1, 3)


def unpack_selection(sel: jax.Array, tk: int) -> jax.Array:
    """The inverse of :func:`pack_selection`: bool ``[B, T, tk]``."""
    b, w, t, _ = sel.shape
    words = jax.lax.bitcast_convert_type(
        sel.transpose(0, 2, 1, 3), jnp.uint32)[:, :, :, None, :]
    bits = (words >> jnp.arange(32, dtype=jnp.uint32)[:, None]) & 1
    return bits.reshape(b, t, w * SEL_SPAN)[:, :, :tk] != 0


def selection_blocks(t: int, d: int = 128, itemsize: int = 2) -> Blocks:
    """The blocks of the three selected-attention kernels at length ``t``:
    :func:`_pick_blocks`' side, fitted, in whole 128-key lane blocks that
    divide a selection word's span."""
    if t % SEL_LANES:
        raise ValueError(f"selected attention needs a length that is a "
                         f"multiple of {SEL_LANES}, got {t}")
    side = _pick_blocks(t, None, d, itemsize).fwd[0]
    while t % side:
        side //= 2
    return Blocks(*[(side, side)] * 3)


def _sel_specs(lay, s, sel):
    """The word block holding a tile's selection: the q-block's rows of
    the word that covers the k-block. No gradient."""
    word = _sel_word(s.bk)
    return ([lay.per_head(s.bq, lambda *ids: word(s.krow(*ids)), s.qrow,
                          SEL_LANES)], _NONE, _NONE)


_SEL = _Variant("_sel", lambda sel_ref: dict(sel_ref=sel_ref), _sel_specs)


@functools.partial(jax.custom_vjp, nondiff_argnums=(4, 5, 6, 7))
def _flash_selected(q, k, v, sel, heads, scale, blocks, interpret):
    return _flash_selected_fwd(q, k, v, sel, heads, scale, blocks,
                               interpret)[0]


def _flash_selected_fwd(q, k, v, sel, heads, scale, blocks, interpret):
    g = _Grid(_packed(q, k, heads), _SEL, True, scale, blocks, interpret)
    out, lse = _named(*_streamed_forward(g, q, k, v, sel))
    return (out, lse), (q, k, v, sel, out, lse)


def _flash_selected_bwd(heads, scale, blocks, interpret, residuals, do):
    q, k, v, sel, out, lse = residuals
    g = _Grid(_packed(q, k, heads), _SEL, True, scale, blocks, interpret)
    dq, dk, dv, _, _ = _streamed_backward(g, q, k, v, do[0], out, lse, sel)
    return dq, dk, dv, None


_flash_selected.defvjp(_flash_selected_fwd, _flash_selected_bwd)


def selected_attention_reference(q, k, v, keep, heads,
                                 scale: Optional[float] = None):
    """The semantic spec of :func:`flash_attention_selected` over the
    packed layout, with the membership as a bool ``[B, T, T]``: returns
    ``(out [B, T, H·D], lse [B, H, T])``."""
    b, t, hd = q.shape
    d = hd // heads
    hkv = k.shape[2] // d
    scale = d ** -0.5 if scale is None else scale
    to4 = lambda x, n: x.reshape(b, t, n, d).transpose(0, 2, 1, 3).astype(
        jnp.float32)
    q4 = to4(q, heads)
    k4 = jnp.repeat(to4(k, hkv), heads // hkv, axis=1)
    v4 = jnp.repeat(to4(v, hkv), heads // hkv, axis=1)
    s = jnp.einsum("bhqd,bhkd->bhqk", q4, k4) * scale
    s = jnp.where(keep[:, None], s, _NEG_INF)
    lse = jax.nn.logsumexp(s, axis=-1)
    out = jnp.einsum("bhqk,bhkd->bhqd", jnp.exp(s - lse[..., None]), v4)
    return (out.transpose(0, 2, 1, 3).reshape(b, t, hd).astype(q.dtype),
            lse)


def flash_attention_selected(q: jax.Array, k: jax.Array, v: jax.Array,
                             sel: jax.Array, heads: int,
                             scale: Optional[float] = None,
                             interpret: Optional[bool] = None):
    """Causal self-attention over the packed ``[B, T, H·D]`` layout in
    which query t sees the keys the selection ``sel`` names
    (:func:`pack_selection`; one selection a query, shared by the heads,
    every selected key at or before the query). Returns ``(out, lse)``:
    ``lse`` is ``[B, H, T]`` float32, the log-sum-exp of each row's
    selected scores, for :func:`selected_head_probs` (it carries no
    gradient; neither does ``sel``). Pallas kernels on a TPU or with
    ``interpret=True``, :func:`selected_attention_reference` elsewhere."""
    b, t, hd = q.shape
    d = hd // heads
    scale = d ** -0.5 if scale is None else scale
    if interpret is None:
        if jax.default_backend() != "tpu":
            return selected_attention_reference(
                q, k, v, unpack_selection(sel, t), heads, scale)
        interpret = False
    if d % 128:
        raise ValueError(f"selected attention reads heads as lane blocks: "
                         f"head_dim {d} is not a multiple of 128")
    out, lse = _flash_selected(q, k, v, sel, heads, scale,
                               selection_blocks(t, d, k.dtype.itemsize),
                               interpret)
    return out, lse[..., 0]


# --------------------------------------------------------------------
# Latent attention expanded for training (MLA without rotation): head h
# scores ``[q_h, qs_h] . [k_h, ks]`` — a ``d``-wide part of its own and a
# narrower part against ONE key part all heads share — and sums values of
# width ``d``. The q/k width (192) is not the value width (128) and is no
# multiple of the lane tile, so the score is taken as two products
# (:func:`_scores`) in the three streamed packed grids: nothing is padded
# to 256 and the shared part is read once a tile, not once a head in HBM.
# ``qs`` travels as [B, H, T, ds] (a 64-wide head is no lane block of a
# packed array), ``ks`` as [B, T, ds].
# --------------------------------------------------------------------


def _mla_specs(lay, s, qs, ks):
    """``qs`` a head's rows beside q, ``ks`` the shared rows beside k. dq
    also returns dqs, blocked as ``qs``; dk/dv each head's share of the
    shared part's gradient, float32 ``[B, H, T, ds]``: the caller sums."""
    width = ks.shape[2]
    qs_blk = lay.per_head(s.bq, s.qhead, s.qrow, width)
    scratch = lambda rows: [pltpu.VMEM((rows, width), jnp.float32)]
    return ([qs_blk, lay.rows(s.bk, _zero, s.krow, width)],
            ([qs_blk], [_like(qs)], scratch(s.bq)),
            ([lay.per_head(s.bk, lay.own, s.krow, width)],
             [lay.per_head_f32(width)], scratch(s.bk)))


_MLA = _Variant("_mla", lambda *refs: dict(shared=refs), _mla_specs)


@functools.partial(jax.custom_vjp, nondiff_argnums=(5, 6, 7, 8))
def _flash_mla(q, qs, k, ks, v, heads, scale, blocks, interpret):
    return _flash_mla_fwd(q, qs, k, ks, v, heads, scale, blocks,
                          interpret)[0]


def _flash_mla_fwd(q, qs, k, ks, v, heads, scale, blocks, interpret):
    g = _Grid(_packed(q, k, heads), _MLA, True, scale, blocks, interpret)
    out, lse = _named(*_streamed_forward(g, q, k, v, qs, ks))
    return out, (q, qs, k, ks, v, out, lse)


def _flash_mla_bwd(heads, scale, blocks, interpret, residuals, do):
    q, qs, k, ks, v, out, lse = residuals
    g = _Grid(_packed(q, k, heads), _MLA, True, scale, blocks, interpret)
    dq, dk, dv, (dqs,), (dks,) = _streamed_backward(
        g, q, k, v, do, out, lse, qs, ks)
    return dq, dqs, dk, dks.sum(axis=1).astype(ks.dtype), dv


_flash_mla.defvjp(_flash_mla_fwd, _flash_mla_bwd)


def mla_attention_reference(q, qs, k, ks, v, heads,
                            scale: Optional[float] = None):
    """The semantic spec of :func:`flash_attention_mla`:
    :func:`reference_attention` over the concatenated ``[q_h, qs_h]`` and
    ``[k_h, ks]``."""
    b, t, hd = q.shape
    d, ds = hd // heads, ks.shape[2]
    scale = (d + ds) ** -0.5 if scale is None else scale
    to4 = lambda x: x.reshape(b, t, heads, -1).transpose(0, 2, 1, 3)
    # packsite: region-local — each head's own part beside the shared part
    # of one unsharded activation (the model refuses a mesh).
    q4 = jnp.concatenate([to4(q), qs], axis=-1)
    # packsite: region-local — as above.
    k4 = jnp.concatenate(
        [to4(k), jnp.broadcast_to(ks[:, None], (b, heads, t, ds))], axis=-1)
    out = reference_attention(q4, k4, to4(v), True, scale)
    return out.transpose(0, 2, 1, 3).reshape(b, t, -1)


def flash_attention_mla(q: jax.Array, qs: jax.Array, k: jax.Array,
                        ks: jax.Array, v: jax.Array, heads: int,
                        scale: Optional[float] = None,
                        interpret: Optional[bool] = None) -> jax.Array:
    """Causal self-attention whose query/key width is not its value width
    (latent attention expanded for training): ``q``, ``k`` ``[B, T, H·d]``
    packed (each head's own part), ``qs`` ``[B, H, T, ds]`` (each head's
    part against the shared key part), ``ks`` ``[B, T, ds]`` (the key part
    all heads share), ``v`` ``[B, T, H·d]``; head h's scores are ``(q_h .
    k_h + qs_h . ks) * scale`` (``scale`` defaults to ``(d + ds)^-1/2``).
    Returns ``[B, T, H·d]``. Pallas kernels (the streamed packed grids; a
    length off the blocks is zero-padded at the end, which the causal mask
    hides) on a TPU or with ``interpret=True``,
    :func:`mla_attention_reference` elsewhere."""
    b, t, hd = q.shape
    d, ds = hd // heads, ks.shape[2]
    if not (k.shape == v.shape == q.shape and qs.shape == (b, heads, t, ds)
            and ks.shape == (b, t, ds)):
        raise ValueError(f"mla shapes: q {q.shape} qs {qs.shape} k {k.shape} "
                         f"ks {ks.shape} v {v.shape}, heads={heads}")
    scale = (d + ds) ** -0.5 if scale is None else scale
    if interpret is None:
        if jax.default_backend() != "tpu":
            return mla_attention_reference(q, qs, k, ks, v, heads, scale)
        interpret = False
    if d % 128:
        raise ValueError(f"mla attention reads heads as lane blocks: "
                         f"head part {d} is not a multiple of 128")
    plan, blocks, t_pad = _plan_dispatch(t, t, None, None, True, None, d,
                                         k.dtype.itemsize)
    if plan == "kernel":
        return _flash_mla(q, qs, k, ks, v, heads, scale, blocks, interpret)
    rows = lambda x, axis: jnp.pad(
        x, [(0, t_pad - t if a == axis else 0) for a in range(x.ndim)])
    out = _flash_mla(rows(q, 1), rows(qs, 2), rows(k, 1), rows(ks, 1),
                     rows(v, 1), heads, scale, blocks, interpret)
    return out[:, :t]


# Query rows of a probabilities tile; its keys are twice as many.
_PROBS_ROWS = 512


def _head_probs_kernel(q_ref, k_ref, lse_ref, sel_ref, p_ref, *, scale,
                       row0: int):
    """Grid ``(b, qi, kb, h)``, heads innermost: the output tile stays in
    VMEM while every head adds its probabilities ``exp(q.k scale - lse)``
    to it, and the selection masks the sum at the last head."""
    bq, bk = p_ref.shape
    qi, kb, h = pl.program_id(1), pl.program_id(2), pl.program_id(3)
    below = kb * bk <= row0 + (qi + 1) * bq - 1

    @pl.when(h == 0)
    def _init():
        p_ref[:] = jnp.zeros_like(p_ref)

    @pl.when(below)
    def _step():
        s = _dot(q_ref[:], k_ref[:], _NT) * scale
        p_ref[:] = p_ref[:] + jnp.exp(s - lse_ref[:, 0:1])

    @pl.when(below & (h == pl.num_programs(3) - 1))
    def _finalize():
        p_ref[:] = jnp.where(_sel_keep(sel_ref[:], kb, bk), p_ref[:], 0.0)


def selected_head_probs(q: jax.Array, k: jax.Array, lse: jax.Array,
                        sel: jax.Array, heads: int, row0: int = 0,
                        scale: Optional[float] = None,
                        interpret: Optional[bool] = None) -> jax.Array:
    """The main attention's probabilities summed over the heads, on the
    selected keys and zero elsewhere: float32 ``[B, R, Tk]`` for the ``R``
    query rows ``q [B, R, H·D]`` that start at position ``row0`` (with
    their ``lse [B, H, R]`` and ``sel [B, W, R, 128]``) against the keys
    ``k [B, Tk, Hkv·D]``, ``Tk >= row0 + R`` not required: keys past a
    row's position are never selected. Nothing here is differentiated."""
    b, r, hd = q.shape
    tk = k.shape[1]
    d = hd // heads
    scale = d ** -0.5 if scale is None else scale
    if interpret is None:
        if jax.default_backend() != "tpu":
            keep = unpack_selection(sel, tk)
            hkv = k.shape[2] // d
            q4 = q.reshape(b, r, heads, d).astype(jnp.float32)
            k4 = jnp.repeat(k.reshape(b, tk, hkv, d), heads // hkv,
                            axis=2).astype(jnp.float32)
            s = jnp.einsum("bqhd,bkhd->bhqk", q4, k4) * scale
            p = jnp.exp(s - lse[..., None]).sum(axis=1)
            return jnp.where(keep, p, 0.0)
        interpret = False
    bq = _fit_block(_PROBS_ROWS, r)
    bk = _fit_lane_block(2 * _PROBS_ROWS, tk)
    while bk > SEL_LANES and SEL_SPAN % bk:
        bk = _fit_lane_block(bk - SEL_LANES, tk)
    if not bq or bk % SEL_LANES or SEL_SPAN % bk:
        raise ValueError(f"no legal blocks for {r} rows x {tk} keys")
    lane = _lane_of(hd // k.shape[2])
    word = _sel_word(bk)
    lse8 = jnp.broadcast_to(lse[..., None], (*lse.shape, _LSE_LANES))
    with jax.named_scope("attn_probs_sel"):
        return pl.pallas_call(
            functools.partial(_head_probs_kernel, scale=scale, row0=row0),
            grid=(b, r // bq, tk // bk, heads),
            in_specs=[
                pl.BlockSpec((None, bq, d), lambda bi, i, kb, h: (bi, i, h)),
                pl.BlockSpec((None, bk, d),
                             lambda bi, i, kb, h: (bi, kb, lane(h))),
                pl.BlockSpec((None, None, bq, _LSE_LANES),
                             lambda bi, i, kb, h: (bi, h, i, 0)),
                pl.BlockSpec((None, None, bq, SEL_LANES),
                             lambda bi, i, kb, h: (bi, word(kb), i, 0))],
            out_specs=pl.BlockSpec((None, bq, bk),
                                   lambda bi, i, kb, h: (bi, i, kb)),
            out_shape=jax.ShapeDtypeStruct((b, r, tk), jnp.float32),
            interpret=interpret,
        )(q, k, lse8, sel)


def _fit_block(limit: int, t: int) -> int:
    """Largest block ≤ limit that divides ``t`` and is a multiple of the
    16-row sublane tile; 0 if none exists (ragged ``t``)."""
    b = min(limit, t)
    b -= b % 16
    while b >= 16 and t % b:
        b -= 16
    return b if b >= 16 else 0


def _fit_lane_block(limit: int, t: int) -> int:
    """Largest block ≤ limit that divides ``t`` in whole 128-lane tiles
    (a block along an array's minor dimension); ``t`` itself where it is
    shorter than one tile; 0 if none exists."""
    if t <= SEL_LANES:
        return t
    b = min(limit, t) // SEL_LANES * SEL_LANES
    while b and t % b:
        b -= SEL_LANES
    return b


def _pick_blocks(tk, window, d, itemsize) -> Blocks:
    """Upper bounds of the three kernels' blocks when the caller names
    none, from what the call shows (PERF.md §6 PR 28 has both tables).

    A score tile costs fewer cycles the larger it is — per-row work
    (running max, rescale, the accumulator's trip through VMEM) and the
    per-step overhead are spread over more keys — until the tile's
    temporaries no longer fit VMEM beside what the kernel holds: with K/V
    resident (``_resident_fits``) that is a side of 512 (the resident
    dk/dv kernel, which also holds a head's whole q, do and o, stops
    compiling at 1024), streamed it is 1024 (2048 does not compile).
    Against that stands the masked work a larger block drags along: one
    causal block a side pays the whole square ((n+1)/n of the triangle
    for n blocks a side), and under a window a block wider than the
    window is mostly masked. Measured on the v5e, the larger tile wins
    down to one block a side (t = 512: 1.06 / 1.28 / 1.65 ms at 512
    against 1.46 / 1.59 / 2.45 at 256, forward / dq / dk-dv, 16 x 32
    heads) but not beyond the window (8192 under a window of 512:
    7.1 ms at 512 against 8.9 at 1024), so a window caps the side at
    itself, never under 512. The three kernels' optima coincide within
    3% at every measured shape, so they get the same side today; the
    choice stays per kernel. Rows wider than 128 x bf16 halve the side
    until a block is no larger in bytes than the calibrated one."""
    side = 512 if _resident_fits(tk, d, itemsize) else 1024
    while side > 128 and side * d * itemsize > 1024 * 128 * 2:
        side //= 2
    if window is not None:
        side = min(side, max(512, 1 << (window - 1).bit_length()))
    return Blocks(*[(side, side)] * 3)


def _pad_len(t: int) -> int:
    """Length a ragged sequence is zero-padded to when the rule picks the
    blocks: the next lane multiple (128), so that blocks of whole lane
    tiles divide it; a sequence under one lane tile, the next sublane
    multiple (16), and is one block."""
    return t + (-t) % (128 if t > 128 else 16)


def _plan_dispatch(t, tk, block_q, block_k, causal, window=None, d=128,
                   itemsize=2):
    """Shared kernel-dispatch policy for both layouts; the second item is
    the :class:`Blocks` of the three kernels:
    ``("kernel", blocks, None)`` — tile-legal dividing blocks exist;
    ``("pad", blocks, t_pad)`` — causal self-attention, zero-pad the seq
    (end-padded keys sit above every real query's diagonal, so the causal
    mask hides them for free);
    ``("pad_masked", blocks, (t_pad, tk_pad, kv_len))`` — any other
    ragged lengths (non-causal, or cross q/k): q and K/V zero-pad
    independently to tile-legal block multiples and the kernels mask the
    padded keys via the static ``kv_len`` (after the chip's compiler
    refused the first kernel's non-tile-aligned block shape, these
    shapes were sent to the reference fallback — the T×T score
    materialization — instead).

    ``block_q`` / ``block_k`` of ``None``: :func:`_pick_blocks` chooses
    each kernel's blocks from the shapes, and a ragged length pads to
    :func:`_pad_len` first, so the rule sees the length the kernels run.
    Integers are one upper bound for all three kernels, as before the
    rule existed.
    """
    if (block_q is None) != (block_k is None):
        raise ValueError("block_q and block_k: name both or neither, got "
                         f"{block_q} and {block_k}")

    def fit(t, tk):
        if block_q is None:
            limits = _pick_blocks(tk, window, d, itemsize)
        else:
            limits = Blocks(*[(block_q, block_k)] * 3)
        return Blocks(*[(_fit_block(lq, t), _fit_block(lk, tk))
                        for lq, lk in limits])

    blocks = fit(t, tk)
    if all(bq and bk for bq, bk in blocks):
        return ("kernel", blocks, None)
    self_attn = causal and t == tk
    if block_q is None:
        t_pad = _pad_len(t)
        tk_pad = t_pad if self_attn else _pad_len(tk)
        blocks = fit(t_pad, tk_pad)
    else:
        bq = min(max(16, block_q - block_q % 16), t + ((-t) % 16))
        bk = min(max(16, block_k - block_k % 16), tk + ((-tk) % 16))
        blocks = Blocks(*[(bq, bk)] * 3)
        if self_attn:
            t_pad = tk_pad = t + ((-t) % math.lcm(bq, bk))
        else:
            t_pad, tk_pad = t + ((-t) % bq), tk + ((-tk) % bk)
    if self_attn:
        return ("pad", blocks, t_pad)
    return ("pad_masked", blocks, (t_pad, tk_pad, tk))


class KernelFallbackWarning(UserWarning):
    """A TPU run left the Pallas kernel for its XLA twin."""


def _warn_fallback(reason: str) -> None:
    """One warning per distinct reason when a TPU run leaves the kernel
    path — the reference fallback materializes the T×T score matrix, an
    OOM/perf cliff on long sequences that should never be silent. The
    message starts with a fixed colon-free prefix so a run that must not
    fall back (``chip_smoke.py``) makes it fatal through the standard
    ``PYTHONWARNINGS="error:kernel fallback"`` in every process it
    starts."""
    import warnings

    if reason not in _warned:
        _warned.add(reason)
        warnings.warn(
            f"kernel fallback to the XLA twin ({reason}); the full "
            f"score matrix may materialize",
            KernelFallbackWarning, stacklevel=3)


_warned: set = set()


def _check_window(window, causal, t, tk):
    """A window is a causal self-attention mask; one that reaches the
    first key is no window (``None``: today's kernels)."""
    if window is None:
        return None
    if not causal or t != tk or window < 1:
        raise ValueError(
            f"window={window} needs causal self-attention (causal="
            f"{causal}, t={t}, tk={tk}) and window >= 1")
    return None if window >= tk else int(window)


def flash_attention(q: jax.Array, k: jax.Array, v: jax.Array,
                    causal: bool = True, scale: Optional[float] = None,
                    block_q: Optional[int] = None,
                    block_k: Optional[int] = None,
                    interpret: Optional[bool] = None,
                    window: Optional[int] = None) -> jax.Array:
    """Fused attention over ``[batch, heads, seq, head_dim]``.

    Dispatch: the pallas kernel on TPU backends (or when ``interpret=True``
    forces the pallas interpreter — how CPU tests cover the kernel), the
    pure-JAX reference elsewhere. Odd shapes stay on the kernel path:
    causal self-attention with a sequence length that doesn't divide the
    block size is zero-padded up to the next block boundary (end-padded
    keys sit above the diagonal for every real query, so the causal mask
    already excludes them); other ragged seq lengths zero-pad q and K/V
    independently with the padded keys masked in-kernel (static
    ``kv_len``); a head_dim off the 8-row sublane tile zero-pads the
    feature dim (zero k-dims add nothing to scores, zero v-columns are
    sliced off). The reference only runs on non-TPU backends.

    ``block_q`` / ``block_k`` of ``None`` (the default): the blocks of the
    forward, the dq and the dk/dv call are chosen from the call's shapes
    by :func:`_pick_blocks` — a side of 512 with K/V resident in VMEM,
    1024 streamed, never wider than a window (or 512), halved for rows
    wider than 128 x bf16 — and fitted to the lengths (the largest
    tile-legal divisor; a ragged length pads to the next lane multiple
    first). The larger tile wins down to ONE block a side: at t = 512 a
    512 block pays the whole causal square and is still 1.3-1.5x faster
    than four 256 blocks paying three quarters of it, because per-row work
    and the store slot, not the matmuls, bound a 256-key tile (v5e, PR 28:
    the compiler's static schedule, then the chip; tables in PERF.md §6).
    Integers are honoured as one upper bound for all three kernels.

    GQA is zero-copy: K/V may carry ``heads // reps`` heads — the kernels'
    index maps route query head h to kv head h·hkv/h, and the dk/dv grids
    group by kv head, so no repeated K/V ever materializes in HBM.

    ``window`` (static, causal self-attention only): query t attends keys
    t-window+1 .. t. K/V blocks wholly left of a q-block's window are
    skipped like those above the diagonal — in the forward, dq and dk/dv
    grids, streamed (the grid's inner axis shrinks to the visible span,
    so they are not fetched either) and resident (the in-kernel loops
    start and stop at the window) alike; :func:`kv_blocks` counts them.
    ``window=None`` compiles to exactly the kernels without the argument.
    """
    d = q.shape[-1]
    scale = d ** -0.5 if scale is None else scale
    t, tk = q.shape[2], k.shape[2]
    window = _check_window(window, causal, t, tk)
    if q.shape[1] % k.shape[1]:
        raise ValueError(
            f"query heads {q.shape[1]} not a multiple of kv heads "
            f"{k.shape[1]}")
    if interpret is None:
        on_tpu = jax.default_backend() == "tpu"
        if not on_tpu:
            return reference_attention(q, k, v, causal, scale, window)
        interpret = False
    if d % 8:
        # Head dim off the 8-row sublane tile: zero-pad the feature dim
        # (extra k dims add 0 to every score; extra v dims emit zero
        # output columns, sliced off — scale was already computed from
        # the REAL d above) and stay on the kernel path.
        widths = ((0, 0), (0, 0), (0, 0), (0, (-d) % 8))
        return flash_attention(
            jnp.pad(q, widths), jnp.pad(k, widths), jnp.pad(v, widths),
            causal=causal, scale=scale, block_q=block_q, block_k=block_k,
            interpret=interpret, window=window)[..., :d]
    # Blocks must divide the seq dims AND be sublane-tile-legal: the
    # in-kernel pl.ds(kb*block, block) K/V slices need block to be a
    # multiple of the sublane tile (8 for f32, 16 for bf16 — 16 covers
    # both), else Mosaic rejects the unaligned slice even when the block
    # equals the array dim. _plan_dispatch shrinks to the largest dividing
    # tile-legal block before resorting to padding, so e.g. t=384 runs
    # the kernel unpadded in one block of 384 rather than padding to 512;
    # the pad paths bound blocks by the padded length so short sequences
    # don't pay for a full-sized block (t=8 pads to 16, not 128).
    plan, blocks, extra = _plan_dispatch(t, tk, block_q, block_k, causal,
                                         window, d, k.dtype.itemsize)
    if plan == "kernel":
        return _flash(q, k, v, causal, scale, blocks, interpret, None,
                      window)
    if plan == "pad":
        widths = ((0, 0), (0, 0), (0, extra - t), (0, 0))
        qp, kp, vp = (jnp.pad(x, widths) for x in (q, k, v))
        out = _flash(qp, kp, vp, causal, scale, blocks, interpret, None,
                     window)
        return out[:, :, :t, :]
    t_pad, tk_pad, kv_len = extra
    qp = jnp.pad(q, ((0, 0), (0, 0), (0, t_pad - t), (0, 0)))
    kvw = ((0, 0), (0, 0), (0, tk_pad - tk), (0, 0))
    out = _flash(qp, jnp.pad(k, kvw), jnp.pad(v, kvw), causal, scale,
                 blocks, interpret, kv_len if tk_pad != tk else None, None)
    return out[:, :, :t, :]


def flash_attention_packed(q: jax.Array, k: jax.Array, v: jax.Array,
                           heads: int, causal: bool = True,
                           scale: Optional[float] = None,
                           block_q: Optional[int] = None,
                           block_k: Optional[int] = None,
                           interpret: Optional[bool] = None,
                           window: Optional[int] = None) -> jax.Array:
    """Fused attention over the packed ``[batch, seq, heads·head_dim]``
    layout — the projection output's natural shape. The kernel reads each
    head as a lane offset (grid ``(b, h, i)``), so the ``[B, H, T, D]``
    transpose+copy the classic layout forces never materializes; the
    profiled win on the Llama bench is ~5% of step time. Requires
    ``head_dim`` to be a multiple of 128 (lane-tile alignment for the
    per-head slices); otherwise use :func:`flash_attention`. GQA is
    zero-copy here too: K/V may be packed ``[B, T, Hkv·D]`` with
    ``heads % Hkv == 0`` — query head h reads kv lane-block h·Hkv/heads.
    ``window`` as in :func:`flash_attention`."""
    b, t, hd = q.shape
    tk = k.shape[1]
    window = _check_window(window, causal, t, tk)
    if hd % heads:
        raise ValueError(
            f"packed dim {hd} is not divisible by heads={heads}")
    d = hd // heads
    if k.shape[2] % d or heads % (k.shape[2] // d):
        raise ValueError(
            f"packed kv dim {k.shape[2]} is not a head-multiple of "
            f"head_dim {d} dividing heads={heads}")
    if k.shape != v.shape:
        # reps is derived from k; a mixed narrow-k/wide-v call (the
        # pre-GQA convention) would silently read wrong v lane blocks.
        raise ValueError(f"k {k.shape} and v {v.shape} must match")
    scale = d ** -0.5 if scale is None else scale

    def unpacked_fallback():
        def to4(x):
            return x.reshape(b, -1, x.shape[2] // d, d).transpose(0, 2, 1, 3)
        out = flash_attention(to4(q), to4(k), to4(v), causal=causal,
                              scale=scale, block_q=block_q, block_k=block_k,
                              interpret=interpret, window=window)
        return out.transpose(0, 2, 1, 3).reshape(b, t, hd)

    if interpret is None:
        if jax.default_backend() != "tpu":
            return unpacked_fallback()
        interpret = False
    if d % 128:
        _warn_fallback(
            f"packed layout needs head_dim % 128 == 0, got {d}")
        return unpacked_fallback()
    plan, blocks, extra = _plan_dispatch(t, tk, block_q, block_k, causal,
                                         window, d, k.dtype.itemsize)
    if plan == "kernel":
        return _flash_packed(q, k, v, heads, causal, scale, blocks,
                             interpret, window)
    if plan == "pad_masked":
        # Ragged non-causal / cross lengths: route through the classic
        # layout, whose pad+mask path keeps the pallas kernel (the packed
        # kernels don't carry the kv mask — one transpose beats a T×T
        # reference materialization).
        return unpacked_fallback()
    widths = ((0, 0), (0, extra - t), (0, 0))
    qp, kp, vp = (jnp.pad(x, widths) for x in (q, k, v))
    out = _flash_packed(qp, kp, vp, heads, causal, scale, blocks,
                        interpret, window)
    return out[:, :t, :]


# --------------------------------------------------------------------
# Flash decoding: the serving plane's attention (tony_tpu.serve). One
# small q-block (the engine's fixed row block — a sublane tile of new
# tokens) attends over a long cached K/V buffer, streamed in k-blocks
# through the same online-softmax recurrence as the training kernels.
# Forward-only (no vjp: serving never differentiates), masked by ABSOLUTE
# positions (each row carries its own position — continuous batching puts
# rows of different sequences, at different depths, in one launch).
#
# Numerics contract (the serve plane's decode-vs-prefill bit pin rides on
# it): the pallas kernel and the pure-XLA fallback share one mask/update
# expression (`_decode_mask_update`) and issue the same f32 dots in the
# same per-block order, so they are bit-identical; and every op is
# row-independent, so a row computes the same bits whether it rides a
# prefill block, a decode block, or a differently-joined batch (the
# engine keeps all row counts at sublane-tile multiples — single-row
# GEMV paths are the one place XLA CPU breaks row invariance).
#
# The speculative lane (tony_tpu.serve.spec) leans on the same contract
# from a third direction: its one-launch k-token verification is a
# decode-shaped call whose q-block carries k+1 REAL rows at consecutive
# positions p0..p0+k (the engine scatters all k+1 candidate KV rows into
# the buffer first, so row j attends the draft rows below it). Because
# each row's mask is its own absolute position and every op is
# row-independent, verify row j is bit-identical to the plain decode row
# at position p0+j — which is exactly what makes greedy accept/reject
# reproduce sequential greedy decode bit for bit, with rejected rows
# never read (they sit above every surviving row's position).
# --------------------------------------------------------------------


def _decode_mask_update(s, q_pos, k_pos, m, l):
    """One online-softmax block step, shared verbatim by the pallas
    kernel and the XLA fallback: mask scores by absolute position
    (``k_pos <= q_pos`` — causal over the cache, which also hides
    unwritten/garbage buffer tail positions), then fold the block into
    the running (m, l) state. All f32; broadcasting carries the leading
    batch dims of whichever caller."""
    s = jnp.where(k_pos <= q_pos, s, _NEG_INF)
    m_new = jnp.maximum(m, jnp.max(s, axis=-1, keepdims=True))
    p = jnp.exp(s - m_new)
    alpha = jnp.exp(m - m_new)
    l_new = l * alpha + jnp.sum(p, axis=-1, keepdims=True)
    return p, alpha, m_new, l_new


def _decode_xla(q, k, v, q_positions, scale, block_k):
    """Pure-XLA flash-decode fallback: fori_loop over k-blocks of the
    cache, grouped [b, hkv, g·t, d] so GQA query heads batch onto their
    kv head exactly like the kernel's head map."""
    b, h, t, d = q.shape
    hkv, ctx = k.shape[1], k.shape[2]
    g = h // hkv
    qf = q.astype(jnp.float32).reshape(b, hkv, g * t, d)
    kf = k.astype(jnp.float32)
    vf = v.astype(jnp.float32)
    # [b, 1, g·t, 1] absolute position per row (the g query heads of one
    # kv head share their rows' positions).
    q_pos = jnp.broadcast_to(
        q_positions.astype(jnp.int32)[:, None, None, :],
        (b, hkv, g, t)).reshape(b, hkv, g * t, 1)
    nkb = ctx // block_k
    m0 = jnp.full((b, hkv, g * t, 1), _NEG_INF, jnp.float32)
    l0 = jnp.zeros((b, hkv, g * t, 1), jnp.float32)
    a0 = jnp.zeros((b, hkv, g * t, d), jnp.float32)

    def body(kb, carry):
        m, l, acc = carry
        k_blk = jax.lax.dynamic_slice_in_dim(kf, kb * block_k, block_k, 2)
        v_blk = jax.lax.dynamic_slice_in_dim(vf, kb * block_k, block_k, 2)
        s = jax.lax.dot_general(
            qf, k_blk, (((3,), (3,)), ((0, 1), (0, 1))),
            preferred_element_type=jnp.float32) * scale
        k_pos = kb * block_k + jax.lax.broadcasted_iota(
            jnp.int32, s.shape, 3)
        p, alpha, m_new, l_new = _decode_mask_update(s, q_pos, k_pos, m, l)
        acc_new = acc * alpha + jax.lax.dot_general(
            p, v_blk, (((3,), (2,)), ((0, 1), (0, 1))),
            preferred_element_type=jnp.float32)
        return m_new, l_new, acc_new

    m, l, acc = jax.lax.fori_loop(0, nkb, body, (m0, l0, a0))
    out = acc / jnp.where(l > 0, l, 1.0)
    return out.reshape(b, hkv, g, t, d).reshape(b, h, t, d).astype(q.dtype)


def _decode_kernel(q_ref, k_ref, v_ref, pos_ref, o_ref, *, block_k: int,
                   scale: float):
    """One (batch, query-head) cell: q-block [t, d] against this kv
    head's full cached [ctx, d] in VMEM, k-blocks streamed through the
    shared online recurrence. Positions ride lane-replicated int32 (the
    lse layout trick — a 1-D block would squeeze illegally on Mosaic)."""
    t, d = q_ref.shape
    ctx = k_ref.shape[0]
    q = q_ref[:].astype(jnp.float32)
    q_pos = pos_ref[:, 0:1]

    m0 = jnp.full((t, 1), _NEG_INF, jnp.float32)
    l0 = jnp.zeros((t, 1), jnp.float32)
    a0 = jnp.zeros((t, d), jnp.float32)

    def body(kb, carry):
        m, l, acc = carry
        k_blk = k_ref[pl.ds(kb * block_k, block_k), :].astype(jnp.float32)
        v_blk = v_ref[pl.ds(kb * block_k, block_k), :].astype(jnp.float32)
        s = jax.lax.dot_general(
            q, k_blk, (((1,), (1,)), ((), ())),
            preferred_element_type=jnp.float32) * scale
        k_pos = kb * block_k + jax.lax.broadcasted_iota(
            jnp.int32, s.shape, 1)
        p, alpha, m_new, l_new = _decode_mask_update(s, q_pos, k_pos, m, l)
        acc_new = acc * alpha + jax.lax.dot_general(
            p, v_blk, (((1,), (0,)), ((), ())),
            preferred_element_type=jnp.float32)
        return m_new, l_new, acc_new

    m, l, acc = jax.lax.fori_loop(0, ctx // block_k, body, (m0, l0, a0))
    o_ref[:] = (acc / jnp.where(l > 0, l, 1.0)).astype(o_ref.dtype)


def _decode_pallas(q, k, v, q_positions, scale, block_k, interpret):
    b, h, t, d = q.shape
    hkv, ctx = k.shape[1], k.shape[2]
    reps = h // hkv
    pos = jnp.broadcast_to(
        q_positions.astype(jnp.int32)[:, :, None], (b, t, _LSE_LANES))
    return pl.pallas_call(
        functools.partial(_decode_kernel, block_k=block_k, scale=scale),
        grid=(b, h),
        in_specs=[
            pl.BlockSpec((None, None, t, d), lambda bi, hi: (bi, hi, 0, 0)),
            pl.BlockSpec((None, None, ctx, d),
                         lambda bi, hi: (bi, hi // reps, 0, 0)),
            pl.BlockSpec((None, None, ctx, d),
                         lambda bi, hi: (bi, hi // reps, 0, 0)),
            pl.BlockSpec((None, t, _LSE_LANES), lambda bi, hi: (bi, 0, 0)),
        ],
        out_specs=pl.BlockSpec((None, None, t, d),
                               lambda bi, hi: (bi, hi, 0, 0)),
        out_shape=jax.ShapeDtypeStruct((b, h, t, d), q.dtype),
        interpret=interpret,
        cost_estimate=pl.CostEstimate(
            flops=4 * b * h * t * ctx * d,
            bytes_accessed=(k.size + v.size) * k.dtype.itemsize
            + q.size * q.dtype.itemsize,
            transcendentals=b * h * t * ctx),
    )(q, k, v, pos)


def flash_decode(q: jax.Array, k: jax.Array, v: jax.Array,
                 q_positions: jax.Array, *, scale: Optional[float] = None,
                 block_k: int = 128,
                 interpret: Optional[bool] = None) -> jax.Array:
    """Flash-decoding attention for the serving plane: a small q-block
    ``[b, h, t, d]`` (t = the engine's row block) against a cached K/V
    buffer ``[b, hkv, ctx, d]``, masked by each row's ABSOLUTE position
    (``q_positions`` int32 ``[b, t]``: key j participates in row i iff
    ``j <= q_positions[i]`` — causal over the cache, and unwritten buffer
    tail positions are excluded for free because they sit above every
    live row's position).

    Dispatch mirrors :func:`flash_attention`: the pallas kernel on TPU
    (``interpret=True`` for CPU test coverage), the pure-XLA fallback
    elsewhere — the two are bit-identical (shared
    :func:`_decode_mask_update`, same f32 dots in the same k-block
    order), which the serve tests pin. GQA is zero-copy (query head h
    reads kv head ``h·hkv/h``). Forward-only: serving never
    differentiates through the cache.
    """
    if q.ndim != 4 or k.ndim != 4:
        raise ValueError(f"flash_decode wants [b, h, t, d] q and "
                         f"[b, hkv, ctx, d] k/v, got {q.shape}/{k.shape}")
    b, h, t, d = q.shape
    hkv, ctx = k.shape[1], k.shape[2]
    if h % hkv:
        raise ValueError(f"query heads {h} not a multiple of kv heads "
                         f"{hkv}")
    if k.shape != v.shape:
        raise ValueError(f"k {k.shape} and v {v.shape} must match")
    if q_positions.shape != (b, t):
        raise ValueError(f"q_positions must be [b, t]={b, t}, got "
                         f"{q_positions.shape}")
    scale = d ** -0.5 if scale is None else scale
    bk = _fit_block(block_k, ctx)
    if interpret is None:
        if jax.default_backend() != "tpu":
            return _decode_xla(q, k, v, q_positions, scale, bk or ctx)
        interpret = False
    if not bk or t % 8 or d % 8 \
            or not _resident_fits(ctx, d, k.dtype.itemsize):
        # Off-tile shapes / oversized caches leave the kernel path; the
        # fallback is the same math (and bit-identical where both run).
        _warn_fallback(
            f"flash_decode shapes t={t} d={d} ctx={ctx} off the kernel "
            f"tiles (or cache exceeds the VMEM budget)")
        return _decode_xla(q, k, v, q_positions, scale, bk or ctx)
    return _decode_pallas(q, k, v, q_positions, scale, bk, interpret)


def flash_attention_sharded(q: jax.Array, k: jax.Array, v: jax.Array,
                            mesh, causal: bool = True,
                            scale: Optional[float] = None,
                            block_q: Optional[int] = None,
                            block_k: Optional[int] = None,
                            model_axis: str = "model",
                            interpret: Optional[bool] = None) -> jax.Array:
    """Global-array entry point: shard_map the flash kernel over the mesh —
    batch over the data axes, heads over the tensor-parallel axis, sequence
    unsharded (intra-chip fusion is this kernel's job; a sharded sequence
    axis is :func:`tony_tpu.parallel.ring_attention_sharded`'s).

    GSPMD cannot partition a custom pallas call from sharding annotations
    alone — an unmapped kernel inside a tp>1 jit gets its operands
    all-gathered per device, defeating tensor parallelism — so models must
    route through this wrapper whenever a mesh is active.
    """
    from jax.sharding import PartitionSpec as P

    b, h = q.shape[0], q.shape[1]
    from tony_tpu.parallel.overlap import sync_axes  # call-time: no cycle

    dp_axes = sync_axes(mesh)
    dp_size = 1
    for a in dp_axes:
        dp_size *= mesh.shape[a]
    tp = model_axis if model_axis in mesh.axis_names else None
    tp_size = mesh.shape[tp] if tp else 1
    if b % dp_size or h % tp_size or k.shape[1] % tp_size:
        # shard_map needs exact divisibility (GQA: kv heads shard over the
        # same tp axis, so they must divide too); rather than hard-fail a
        # config the plain GSPMD path would run (slowly), fall back.
        _warn_fallback(
            f"batch {b} % dp {dp_size} or heads {h}/kv {k.shape[1]} % tp "
            f"{tp_size} != 0; flash kernel will run unmapped under GSPMD")
        return flash_attention(q, k, v, causal=causal, scale=scale,
                               block_q=block_q, block_k=block_k,
                               interpret=interpret)
    spec = P(dp_axes or None, tp, None, None)
    fn = functools.partial(flash_attention, causal=causal, scale=scale,
                           block_q=block_q, block_k=block_k,
                           interpret=interpret)
    from tony_tpu.compat import shard_map as _shard_map
    return _shard_map(fn, mesh, in_specs=(spec, spec, spec),
                      out_specs=spec)(q, k, v)
