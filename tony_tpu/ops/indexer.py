"""A learned sparse-attention indexer: which keys each query attends.

Every query scores every earlier key with a few small heads,

    I[t, s] = sum_j w[t, j] * relu(qI[t, j] . kI[s])          (s <= t)

and attends the ``topk`` keys of largest score (all of them while
``t < topk``; ties go to the lower position). One selection a query, shared
by the main attention's heads. The indexer is trained by its own loss,

    L_I = mean_t KL(p_t || softmax_{s in S_t} I[t, s])

with ``p_t`` the main attention's probabilities summed over the heads on
the selected set ``S_t`` and L1-normalised, detached: nothing but the
indexer's three projections gets a gradient from it.

Nothing of size ``[T, T]`` outlives a block of :data:`ROW_BLOCK` query
rows: scores are made a row block at a time (a Pallas tile kernel on the TPU, a scan
over the index heads elsewhere), the row-wise ``topk``-th largest score is
found **exactly** by bisection on the scores' order-preserving integer
image (32 counting passes, no ``approx_max_k``), and the selection leaves
as bits (:func:`tony_tpu.ops.attention.pack_selection`). The loss walks the
same row blocks: scores again, the probabilities pass
(:func:`tony_tpu.ops.attention.selected_head_probs`), the KL and its
backward into ``qI``, ``kI`` and ``w``.

The loss runs **once a layer**. Its gradient is taken with its value, in
the forward, and pulled back through whatever made ``qI``, ``w`` and ``kI``
(``through``: the model's three projections, scale and rotation) to the
parameters behind them; those gradients — three small matrices a layer, not
three ``[T, ...]`` activations — are the loss's only residuals, named
``index_grad`` (:func:`tony_tpu.remat.name`), and the backward scales them
by the cotangent. A block under :func:`tony_tpu.remat.block` keeps that
name whatever else its step keeps, so remat's second forward holds none of
the loss's work. Everything else the loss reads (``sel``, the main
attention's ``q``, ``k``, ``lse``, the projections' input) is a constant to
it. Trace-time counter: ``index:grad_in_forward`` (1 once that pass was
traced).

Device scopes: ``attn_index`` (scores), ``attn_select`` (threshold, ties,
packing), ``attn_index_loss`` (probabilities, KL and its backward).
"""

from __future__ import annotations

import functools
from typing import Optional

import jax
import jax.numpy as jnp
from jax.experimental import pallas as pl

from tony_tpu import profiler, remat
from tony_tpu.ops import attention as att


# ---------------------------------------------------------------- scores

def index_scores_reference(qi, w, ki):
    """``I`` for query rows ``qi [B, R, J, E]``, ``w [B, R, J]`` against
    keys ``ki [B, K, E]``: float32 ``[B, R, K]``, no causal mask. One head
    at a time, so ``[R, J, K]`` is never held."""
    def head(acc, xs):
        q_j, w_j = xs                                   # [B,R,E], [B,R]
        z = jnp.einsum("bre,bke->brk", q_j, ki,
                       preferred_element_type=jnp.float32)
        return acc + w_j[..., None].astype(jnp.float32) * jax.nn.relu(z), None

    acc0 = jnp.zeros((*qi.shape[:2], ki.shape[1]), jnp.float32)
    out, _ = jax.lax.scan(
        head, acc0, (jnp.moveaxis(qi, 2, 0), jnp.moveaxis(w, 2, 0)))
    return out


def _scores_kernel(q_ref, w_ref, k_ref, o_ref, *, row0: int):
    """One ``[bq, bk]`` score tile: the index heads one after the other
    through the MXU, relu and weight in float32. Tiles wholly above the
    diagonal are written as zeros."""
    heads, bq, _ = q_ref.shape
    bk = k_ref.shape[0]
    below = pl.program_id(2) * bk <= row0 + (pl.program_id(1) + 1) * bq - 1

    @pl.when(below)
    def _tile():
        k = k_ref[:]
        acc = jnp.zeros((bq, bk), jnp.float32)
        for j in range(heads):
            z = jax.lax.dot_general(q_ref[j], k, (((1,), (1,)), ((), ())),
                                    preferred_element_type=jnp.float32)
            acc = acc + w_ref[:, j:j + 1] * jnp.maximum(z, 0.0)
        o_ref[:] = acc

    @pl.when(jnp.logical_not(below))
    def _skip():
        o_ref[:] = jnp.zeros_like(o_ref)


def _scores_pallas(qi, w, ki, row0, interpret):
    b, r, heads, e = qi.shape
    k = ki.shape[1]
    bq, bk = att._fit_block(512, r), att._fit_lane_block(1024, k)
    if not bq or not bk:
        raise ValueError(f"no legal blocks for {r} rows x {k} keys")
    with jax.named_scope("attn_index_scores"):       # the custom call's name
        return pl.pallas_call(
            functools.partial(_scores_kernel, row0=row0),
            grid=(b, r // bq, k // bk),
            in_specs=[
                pl.BlockSpec((None, heads, bq, e),
                             lambda bi, i, kb: (bi, 0, i, 0)),
                pl.BlockSpec((None, bq, heads), lambda bi, i, kb: (bi, i, 0)),
                pl.BlockSpec((None, bk, e), lambda bi, i, kb: (bi, kb, 0))],
            out_specs=pl.BlockSpec((None, bq, bk),
                                   lambda bi, i, kb: (bi, i, kb)),
            out_shape=jax.ShapeDtypeStruct((b, r, k), jnp.float32),
            interpret=interpret,
        )(jnp.moveaxis(qi, 2, 1), w.astype(jnp.float32), ki)


def index_scores(qi, w, ki, row0: int = 0,
                 interpret: Optional[bool] = None):
    """``I [B, R, K]`` (float32) of the query rows that start at position
    ``row0``; entries above the diagonal are unspecified (the kernel
    writes zeros there, the reference the scores): callers mask by
    position. Not differentiated: :func:`index_loss` has its own
    backward."""
    if interpret is None:
        if jax.default_backend() != "tpu":
            return index_scores_reference(qi, w, ki)
        interpret = False
    return _scores_pallas(qi, w, ki, row0, interpret)


def index_scores_backward(qi, w, ki, g):
    """The cotangents ``(d qI, d w, d kI)`` (float32) of ``g = dL/dI``
    ``[B, R, K]``, one head at a time."""
    def head(dk, xs):
        q_j, w_j = xs
        z = jnp.einsum("bre,bke->brk", q_j, ki,
                       preferred_element_type=jnp.float32)
        dw_j = jnp.sum(g * jax.nn.relu(z), axis=-1)
        gz = jnp.where(z > 0, g * w_j[..., None].astype(jnp.float32),
                       0.0).astype(ki.dtype)
        dq_j = jnp.einsum("brk,bke->bre", gz, ki,
                          preferred_element_type=jnp.float32)
        dk = dk + jnp.einsum("brk,bre->bke", gz, q_j,
                             preferred_element_type=jnp.float32)
        return dk, (dq_j, dw_j)

    dk0 = jnp.zeros(ki.shape, jnp.float32)
    dk, (dq, dw) = jax.lax.scan(
        head, dk0, (jnp.moveaxis(qi, 2, 0), jnp.moveaxis(w, 2, 0)))
    return jnp.moveaxis(dq, 0, 2), jnp.moveaxis(dw, 0, 2), dk


# ------------------------------------------------------------- selection

def _ordered(x):
    """float32 -> uint32 in ``jax.lax.top_k``'s order (the total order:
    -0.0 below +0.0)."""
    bits = jax.lax.bitcast_convert_type(x, jnp.int32)
    key = bits ^ ((bits >> 31) & jnp.int32(0x7FFFFFFF))
    return jax.lax.bitcast_convert_type(key, jnp.uint32) ^ jnp.uint32(1 << 31)


def select_topk(scores, row0: int, topk: int):
    """bool ``[B, R, K]``: for the query at position ``row0 + r``, the
    ``topk`` keys of largest score among positions ``<= row0 + r`` (all of
    them where there are no more than ``topk``); equal scores go to the
    lower position. Exact: the ``topk``-th largest value of each row by
    bisection over its order-preserving integer image."""
    b, r, k = scores.shape
    pos_q = row0 + jax.lax.broadcasted_iota(jnp.int32, (r, k), 0)
    valid = jax.lax.broadcasted_iota(jnp.int32, (r, k), 1) <= pos_q
    if row0 + r <= topk:
        return jnp.broadcast_to(valid, scores.shape)
    key = jnp.where(valid, _ordered(scores), jnp.uint32(0))

    def bit(i, thr):
        cand = thr | (jnp.uint32(1) << (31 - i).astype(jnp.uint32))
        enough = jnp.sum(key >= cand[..., None], axis=-1,
                         dtype=jnp.int32) >= topk
        return jnp.where(enough, cand, thr)

    # The largest threshold that still leaves topk keys at or above it:
    # the topk-th largest key (0 for a row with fewer valid keys).
    thr = jax.lax.fori_loop(0, 32, bit, jnp.zeros((b, r), jnp.uint32))
    above = (key > thr[..., None]) & valid
    equal = (key == thr[..., None]) & valid
    room = topk - jnp.sum(above, axis=-1, dtype=jnp.int32)
    n_equal = jnp.sum(equal, axis=-1, dtype=jnp.int32)

    def some_tie(_):
        rank = jnp.cumsum(equal, axis=-1, dtype=jnp.int32)
        return above | (equal & (rank <= room[..., None]))

    # Equal scores that do not all fit are rare (an exact zero at the
    # threshold): the running count is made only where a row has them.
    return jax.lax.cond(jnp.any(n_equal > room), some_tie,
                        lambda _: above | equal, None)


# Query rows scored, thresholded and scored again for the loss at a time
# (all of them where a sequence is shorter). A block's temporaries are
# ``[rows, keys]`` float32 — 64 MiB each at 1024 rows of 16384 keys, a
# handful alive at once: what the keye-vl-2.0-30b-a3b step has room for,
# and the one value that has run on the chip (PERF.md §5).
ROW_BLOCK = 1024


def _row_blocks(t: int):
    """(row0, rows, keys) of each block of query rows; a block's keys end
    where its last row does."""
    block = min(ROW_BLOCK, t)
    if t % block:
        raise ValueError(f"length {t} is not a multiple of the indexer's "
                         f"row block {block}")
    return [(r0, block, r0 + block) for r0 in range(0, t, block)]


def select(qi, w, ki, topk: int, interpret: Optional[bool] = None):
    """The selection of every query, packed
    (:func:`tony_tpu.ops.attention.pack_selection`): int32
    ``[B, ceil(T / 4096), T, 128]``. ``qi [B, T, J, E]``, ``w [B, T, J]``,
    ``ki [B, T, E]``. Not differentiated."""
    qi, w, ki = jax.lax.stop_gradient((qi, w, ki))
    t = qi.shape[1]
    words = -(-t // att.SEL_SPAN)
    out = []
    for r0, rows, keys in _row_blocks(t):
        with jax.named_scope("attn_index"):
            scores = index_scores(qi[:, r0:r0 + rows], w[:, r0:r0 + rows],
                                  ki[:, :keys], r0, interpret)
        with jax.named_scope("attn_select"):
            sel = att.pack_selection(select_topk(scores, r0, topk))
            out.append(jnp.pad(sel, ((0, 0), (0, words - sel.shape[1]),
                                     (0, 0), (0, 0))))
    with jax.named_scope("attn_select"):
        # packsite: region-local — row blocks of one selection along the
        # query axis; every operand is unsharded.
        return out[0] if len(out) == 1 else jnp.concatenate(out, axis=2)


# ------------------------------------------------------------------ loss

def _block_loss(qi, w, ki, sel, q, k, lse, heads, scale, r0, total_rows,
                interpret, with_grads):
    """One row block's share of ``L_I`` (a sum over its rows divided by
    ``total_rows``) and, where asked, of its gradient."""
    keys = ki.shape[1]
    keep = att.unpack_selection(sel, keys)
    scores = index_scores(qi, w, ki, r0, interpret)
    probs = att.selected_head_probs(q, k, lse, sel, heads, r0, scale,
                                    interpret)
    p = probs / jnp.maximum(probs.sum(axis=-1, keepdims=True), 1e-30)
    logq = jax.nn.log_softmax(jnp.where(keep, scores, att._NEG_INF), axis=-1)
    kl = jnp.sum(jnp.where(p > 0, p * (jnp.log(jnp.where(p > 0, p, 1.0))
                                       - logq), 0.0))
    if not with_grads:
        return kl / total_rows, None
    g = jnp.where(keep, jnp.exp(logq) - p, 0.0) / total_rows
    return kl / total_rows, index_scores_backward(qi, w, ki, g)


def _loss_blocks(qi, w, ki, sel, q, k, lse, heads, scale, interpret,
                 with_grads):
    b, t = qi.shape[:2]
    loss = jnp.float32(0.0)
    dq, dw, dk = [], [], jnp.zeros(ki.shape, jnp.float32)
    for r0, rows, keys in _row_blocks(t):
        rs = slice(r0, r0 + rows)
        words = -(-keys // att.SEL_SPAN)
        # One row block after the other: a block's operands are held back
        # until the block before it is done, so that two blocks' [rows,
        # keys] temporaries never share the chip.
        (loss, dk), operands = jax.lax.optimization_barrier((
            (loss, dk), (qi[:, rs], w[:, rs], ki[:, :keys],
                         sel[:, :words, rs], q[:, rs], k[:, :keys],
                         lse[:, :, rs])))
        part, grads = _block_loss(*operands, heads, scale, r0, b * t,
                                  interpret, with_grads)
        loss = loss + part
        if with_grads:
            dq.append(grads[0])
            dw.append(grads[1])
            dk = dk.at[:, :keys].add(grads[2])
    if not with_grads:
        return loss, None
    # packsite: region-local — row blocks of one gradient along the query
    # axis; every operand is unsharded.
    return loss, (jnp.concatenate(dq, axis=1), jnp.concatenate(dw, axis=1),
                  dk)


@functools.partial(jax.custom_vjp, nondiff_argnums=(0, 8, 9, 10))
def _index_loss(project, params, inputs, projected, sel, q, k, lse, heads,
                scale, interpret):
    """``L_I`` of ``projected = project(params, *inputs)``, differentiated
    with respect to ``params`` alone."""
    return _loss_blocks(*projected, sel, q, k, lse, heads, scale, interpret,
                        False)[0]


def _index_loss_fwd(project, params, inputs, projected, sel, q, k, lse,
                    heads, scale, interpret):
    profiler.count_once("index:grad_in_forward", 1)
    # The gradient is made with the value: the probabilities and the
    # scores of a row block are then computed once for both.
    loss, grads = _loss_blocks(*projected, sel, q, k, lse, heads, scale,
                               interpret, True)
    # Only the transpose of ``project`` is run: its value is ``projected``,
    # which the caller made (and selected with), and is dead here.
    _, pull = jax.vjp(lambda p: project(p, *inputs), params)
    (grads,) = pull(tuple(g.astype(a.dtype)
                          for g, a in zip(grads, projected)))
    return loss, jax.tree.map(lambda g: remat.name(g, "index_grad"), grads)


def _index_loss_bwd(project, heads, scale, interpret, grads, g):
    return (jax.tree.map(lambda a: (g * a).astype(a.dtype), grads),
            *[None] * 6)


_index_loss.defvjp(_index_loss_fwd, _index_loss_bwd)


def index_loss(qi, w, ki, sel, q, k, lse, heads: int, through,
               scale: Optional[float] = None,
               interpret: Optional[bool] = None):
    """``L_I``: the mean over queries of ``KL(p_t || softmax_{S_t} I_t)``.
    ``q [B, T, H·D]``, ``k [B, T, Hkv·D]`` and ``lse [B, H, T]`` are the
    main attention's (after rotary), read as constants, as is ``sel``.

    ``through = (project, params, *inputs)`` says where ``qi, w, ki`` come
    from: they are ``project(params, *inputs)``, made by the caller (who
    selected with them), and a constant here too. The gradient reaches
    ``params`` alone (a pytree; ``project`` closes over no array), and is
    the loss's only residual (``index_grad``)."""
    d = q.shape[-1] // heads
    scale = d ** -0.5 if scale is None else scale
    project, params, *inputs = through
    constants = jax.lax.stop_gradient(
        (tuple(inputs), (qi, w, ki), sel, q, k, lse))
    with jax.named_scope("attn_index_loss"):
        return _index_loss(project, params, *constants, heads, scale,
                           interpret)
