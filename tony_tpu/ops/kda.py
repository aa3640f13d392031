"""Gated delta-rule linear attention with a per-channel decay (KDA, the
Kimi Linear report, arXiv:2510.26692): a matrix state a head.

The recurrence, per head, ``k_t, q_t, g_t`` in ``R^dk`` (``g_t <= 0`` the
log-decay of each key channel), ``v_t`` in ``R^dv``, ``beta_t`` in (0, 1)::

    S_t = (I - beta_t k_t k_t^T) Diag(exp(g_t)) S_{t-1} + beta_t k_t v_t^T
    o_t = S_t^T q_t                                            S_{-1} = 0

Token by token that is ``T`` rank-one updates of a ``dk x dv`` matrix;
:func:`kda` is **chunked** with a ``custom_vjp``. Within a chunk of ``C``
tokens with incoming state ``S`` and ``G_t = g_0 + ... + g_t``::

    A[t, i]   = beta_t sum_c k_t[c] k_i[c] exp(G_t[c] - G_i[c])      i < t
    Aqk[t, i] =        sum_c q_t[c] k_i[c] exp(G_t[c] - G_i[c])      i <= t
    U = (I + A)^-1 (beta * (V - (K * exp(G)) S))
    O = (Q * exp(G)) S + Aqk U
    S' = Diag(exp(G_last)) S + (K * exp(G_last - G))^T U

``exp(G_t - G_i)`` is a per-channel factor, so ``A`` is a matmul only once
it is split as ``exp(G_t - r) exp(r - G_i)`` about a reference ``r`` — and
``exp(r - G_i)`` overflows under a strong decay unless ``r`` lies between
the two tokens. So the pairs ``(t, i)`` are taken **by level**: at level
``n`` (1, 2, 4, ... C/2) the chunk is cut in blocks of ``n``, a pair
belongs to the level where ``t`` and ``i`` fall in sibling blocks, and the
reference is the first token of ``t``'s block: both exponents are sums of
``g`` over tokens between the two and never positive, every level is one
``[C, dk] x [dk, C]`` matmul under a 0/1 mask, and nothing can overflow.
The partial sums of ``g`` are one matmul with a constant 0/1 table
(:func:`tables`), ``g`` split in bfloat16 parts so that the product is
exact to float32. ``(I + A)^-1`` is Neumann products: exact for a
nilpotent matrix, 16 x 16 diagonal blocks first so that no power grows.

The forward keeps the state at the start of every ``keep``-th chunk
(``[T / (keep C), dv, dk]`` float32 a head); the backward walks those steps
in reverse, rebuilds the ``keep`` states of one step, and runs each
chunk's hand-written backward (:func:`chunk_bwd`) with the state's adjoint
carried across. Decays, state, the inverse and every accumulation are
float32; the large matmuls take operands in the inputs' dtype.

Dispatch follows :mod:`tony_tpu.ops.ssm`: Pallas kernels
(``kda_chunk_fwd``, ``kda_chunk_bwd``; grid (batch, head, step) with the
step axis sequential and the state in VMEM scratch) on a TPU, the same
chunk functions under ``interpret=True`` for CPU tests, and an XLA twin
(``lax.scan`` over steps of the same functions) elsewhere.
"""

from __future__ import annotations

import functools
from typing import Optional

import jax
import jax.numpy as jnp
import numpy as np
from jax.experimental import pallas as pl
from jax.experimental.pallas import tpu as pltpu

from tony_tpu.ops.attention import _warn_fallback

CHUNK = 64      # tokens a chunk
KEEP = 4        # chunks between two kept states
_SUB = 16       # side of the diagonal blocks inverted first

_NT = ((1,), (1,))      # a @ b.T
_NN = ((1,), (0,))      # a @ b
_TN = ((0,), (0,))      # a.T @ b
_HIGHEST = jax.lax.Precision.HIGHEST


def n_chunks(t: int, chunk: int = CHUNK) -> int:
    """Chunks a call over ``t`` tokens runs (the counter ``kda:chunks``)."""
    return -(-t // chunk)


def states_kept(t: int, chunk: int = CHUNK, keep: int = KEEP) -> int:
    """States the forward keeps for the backward (``kda:states_kept``)."""
    return -(-t // (chunk * keep))


@functools.lru_cache(maxsize=None)
def tables(chunk: int):
    """``(sums, masks)`` of a chunk length (a power of two), as numpy.

    ``sums`` ``[(2 L + 2) C, C]`` 0/1: stacked blocks that, times ``g
    [C, dk]``, give for each level ``n = 2^l`` the row exponent ``sum of g
    over (start of t's block, t]`` and the column exponent ``sum over (i,
    start of the block after i's]``, then ``G`` (sum over ``[0, t]``) and
    ``G_last - G`` (sum over ``(t, C)``). ``masks`` ``[L C, C]`` 0/1: the
    pairs ``(t, i)`` of each level."""
    levels = [1 << i for i in range(chunk.bit_length() - 1)]
    if not levels or levels[-1] * 2 != chunk:
        raise ValueError(f"kda chunk {chunk}: a power of two, at least 2")
    t = np.arange(chunk)[:, None]
    j = np.arange(chunk)[None, :]
    sums, masks = [], []
    for n in levels:
        sums.append((j > t // n * n) & (j <= t))
        sums.append((j > t) & (j <= (t // n + 1) * n))
        masks.append((t // n == j // n + 1) & (t // n % 2 == 1))
    sums += [j <= t, j > t]
    return (np.concatenate(sums).astype(np.float32),
            np.concatenate(masks).astype(np.float32))


def _mm(a, b, dims, cd):
    """``a`` x ``b`` into float32, operands in the compute dtype ``cd``
    (float32: at the highest precision)."""
    if jnp.dtype(cd) == jnp.float32:
        return jax.lax.dot_general(a, b, (dims, ((), ())),
                                   precision=_HIGHEST,
                                   preferred_element_type=jnp.float32)
    return jax.lax.dot_general(a.astype(cd), b.astype(cd),
                               (dims, ((), ())),
                               preferred_element_type=jnp.float32)


def _mm_table(table, x, dims, cd):
    """A 0/1 ``table`` (bfloat16, exact) x float32 ``x``, exact to float32
    in bfloat16 passes: ``x`` is split into parts that each fit bfloat16
    (two where the model computes in bfloat16: 2^-16, three in float32).
    Off the TPU one float32 product (XLA's CPU runtime has no bfloat16
    product inside a loop)."""
    if jax.default_backend() != "tpu":
        return _mm32(table.astype(jnp.float32), x, dims)
    return _mm_split(table, x, dims, cd)


def _mm_split(table, x, dims, cd):
    out, rest = None, x
    for _ in range(3 if jnp.dtype(cd) == jnp.float32 else 2):
        part = rest.astype(jnp.bfloat16)
        rest = rest - part.astype(jnp.float32)
        term = jax.lax.dot_general(table, part, (dims, ((), ())),
                                   preferred_element_type=jnp.float32)
        out = term if out is None else out + term
    return out


def _mm32(a, b, dims=_NN):
    return _mm(a, b, dims, jnp.float32)


def _eye(c):
    return (jax.lax.broadcasted_iota(jnp.int32, (c, c), 0)
            == jax.lax.broadcasted_iota(jnp.int32, (c, c), 1))


def _neumann(x, power, eye):
    """``(I + x)^-1`` for ``x^power = 0`` (``power`` a power of two):
    ``(I - x)(I + x^2)(I + x^4)...``, float32."""
    out, p = eye - x, x
    for _ in range(power.bit_length() - 2):
        p = _mm32(p, p)
        out = out + _mm32(out, p)
    return out


def _inverse(a):
    """``(I + a)^-1`` of a strictly lower triangular ``a [C, C]``: the
    ``_SUB``-wide diagonal blocks ``d`` first, ``td = (I + d)^-1``; then
    ``I + a = (I + d)(I + td (a - d))`` and ``td (a - d)`` is strictly
    BLOCK lower triangular, nilpotent in ``C / _SUB`` steps."""
    c = a.shape[0]
    eye = _eye(c).astype(jnp.float32)
    sub = min(_SUB, c)
    if sub == c:
        return _neumann(a, c, eye)
    blk = lambda axis: jax.lax.broadcasted_iota(
        jnp.int32, (c, c), axis) // sub
    d = jnp.where(blk(0) == blk(1), a, 0.0)
    td = _neumann(d, sub, eye)
    return _mm32(_neumann(_mm32(td, a - d), c // sub, eye), td)


def _levels(q, k, g, sums, masks, cd):
    """What a chunk's forward and backward both start from: the exponent
    blocks, the two pair matrices and each level's decayed operands."""
    c = q.shape[0]
    e = _mm_table(sums, g, _NN, cd)
    block = lambda i: e[i * c:(i + 1) * c]
    n_levels = masks.shape[0] // c
    akk = aqk = None
    per_level = []
    for l in range(n_levels):
        er, ec = jnp.exp(block(2 * l)), jnp.exp(block(2 * l + 1))
        kr, qr, kc = k * er, q * er, k * ec
        m = masks[l * c:(l + 1) * c]
        pk, pq = m * _mm(kr, kc, _NT, cd), m * _mm(qr, kc, _NT, cd)
        akk = pk if akk is None else akk + pk
        aqk = pq if aqk is None else aqk + pq
        per_level.append((er, ec, kr, qr, kc, m))
    eg, el = jnp.exp(block(2 * n_levels)), jnp.exp(block(2 * n_levels + 1))
    aqk = aqk + jnp.where(_eye(c), jnp.sum(q * k, axis=1, keepdims=True), 0.0)
    return akk, aqk, eg, el, per_level


def chunk_fwd(st, q, k, v, g, beta, sums, masks, cd):
    """One chunk of one head: ``st [dv, dk]`` float32 (the state,
    transposed: a decay scales its lanes), ``q, k, g [C, dk]``, ``v
    [C, dv]``, ``beta [C, 1]`` -> ``(o [C, dv], state after)``, float32."""
    f32 = lambda x: x.astype(jnp.float32)
    q, k, v, g, beta = f32(q), f32(k), f32(v), f32(g), f32(beta)
    c = q.shape[0]
    akk, aqk, eg, el, _ = _levels(q, k, g, sums, masks, cd)
    t = _inverse(beta * akk)
    u = _mm(t, beta * (v - _mm(k * eg, st, _NT, cd)), _NN, cd)
    o = _mm(q * eg, st, _NT, cd) + _mm(aqk, u, _NN, cd)
    return o, st * eg[c - 1:c] + _mm(u, k * el, _TN, cd)


def chunk_bwd(st, q, k, v, g, beta, do, dst1, sums, masks, cd):
    """The chunk's backward, by hand: ``(dq, dk, dv, dg, dbeta, dst)`` from
    the output's and the outgoing state's adjoints. ``d(I + A)^-1`` is
    closed (``dA = -(T^T dU) U^T``), so the inverse's products are not
    walked back."""
    f32 = lambda x: x.astype(jnp.float32)
    q, k, v, g, beta, do = f32(q), f32(k), f32(v), f32(g), f32(beta), f32(do)
    c = q.shape[0]
    akk, aqk, eg, el, per_level = _levels(q, k, g, sums, masks, cd)
    t = _inverse(beta * akk)
    kg, qg, kl = k * eg, q * eg, k * el
    resid = v - _mm(kg, st, _NT, cd)
    u = _mm(t, beta * resid, _NN, cd)
    # state after = st * eg_last + u^T kl
    e_last = eg[c - 1:c]
    du = _mm(kl, dst1, _NT, cd)
    dkl = _mm(u, dst1, _NN, cd)
    dst = dst1 * e_last
    d_last = e_last * jnp.sum(st * dst1, axis=0, keepdims=True)
    # o = qg st^T + aqk u
    dqg = _mm(do, st, _NN, cd)
    dst = dst + _mm(do, qg, _TN, cd)
    daqk = _mm(do, u, _NT, cd)
    du = du + _mm(aqk, do, _TN, cd)
    # u = t (beta * resid)
    drhs = _mm32(t, du, _TN)
    da = -_mm(drhs, u, _NT, cd)
    dbeta = jnp.sum(drhs * resid, axis=1, keepdims=True) \
        + jnp.sum(da * akk, axis=1, keepdims=True)
    bd = beta * drhs
    dv = bd
    dkg = -_mm(bd, st, _NN, cd)
    dst = dst - _mm(bd, kg, _TN, cd)
    dakk = beta * da
    diag = jnp.sum(jnp.where(_eye(c), daqk, 0.0), axis=1, keepdims=True)
    dq = dqg * eg + diag * k
    dk = dkg * eg + dkl * el + diag * q
    row = jax.lax.broadcasted_iota(jnp.int32, eg.shape, 0)
    de = []
    for er, ec, kr, qr, kc, m in per_level:
        dpk, dpq = m * dakk, m * daqk
        dkr, dqr = _mm(dpk, kc, _NN, cd), _mm(dpq, kc, _NN, cd)
        dkc = _mm(dpk, kr, _TN, cd) + _mm(dpq, qr, _TN, cd)
        dq = dq + dqr * er
        dk = dk + dkr * er + dkc * ec
        de += [dkr * kr + dqr * qr, dkc * kc]
    de += [dkg * kg + dqg * qg + jnp.where(row == c - 1, d_last, 0.0),
           dkl * kl]
    # packsite: region-local — the exponent blocks' adjoints stacked as
    # the table stacks the blocks, one head's chunk (VMEM values).
    dg = _mm_table(sums, jnp.concatenate(de, axis=0), _TN, cd)
    return dq, dk, dv, dg, dbeta, dst


# --------------------------------------------------------------------
# XLA twin: lax.scan over steps of ``keep`` chunks, the chunk functions
# vmapped over batch and heads. The CPU's path.
# --------------------------------------------------------------------

def _steps(x, chunk, keep):
    """[B, T, H, D] -> [steps, keep, B, H, chunk, D]."""
    b, t, h, d = x.shape
    x = x.reshape(b, t // (chunk * keep), keep, chunk, h, d)
    return x.transpose(1, 2, 0, 4, 3, 5)


def _unsteps(x):
    """The inverse of :func:`_steps`."""
    s, keep, b, h, chunk, d = x.shape
    return x.transpose(2, 0, 1, 4, 3, 5).reshape(b, s * keep * chunk, h, d)


def _consts(chunk):
    sums, masks = tables(chunk)
    return jnp.asarray(sums, jnp.bfloat16), jnp.asarray(masks)


def _fwd_xla(q, k, v, g, beta, chunk, keep):
    b, t, h, dk = q.shape
    sums, masks = _consts(chunk)
    one = jax.vmap(jax.vmap(functools.partial(
        chunk_fwd, sums=sums, masks=masks, cd=q.dtype)))

    def step(st, xs):
        def chunk_of(st, x):
            o, st1 = one(st, *x)
            return st1, o
        st1, o = jax.lax.scan(chunk_of, st, xs)
        return st1, (o, st)
    st0 = jnp.zeros((b, h, v.shape[3], dk), jnp.float32)
    xs = tuple(_steps(x, chunk, keep) for x in (q, k, v, g, beta[..., None]))
    _, (o, kept) = jax.lax.scan(step, st0, xs)
    return _unsteps(o).astype(q.dtype), jnp.moveaxis(kept, 0, 2)


def _bwd_xla(q, k, v, g, beta, kept, do, chunk, keep):
    sums, masks = _consts(chunk)
    kw = dict(sums=sums, masks=masks, cd=q.dtype)
    fwd = jax.vmap(jax.vmap(functools.partial(chunk_fwd, **kw)))
    bwd = jax.vmap(jax.vmap(functools.partial(chunk_bwd, **kw)))

    def step(dst, xs):
        st, x, do = xs

        def rebuild(st, x):
            return fwd(st, *x)[1], st
        _, states = jax.lax.scan(rebuild, st, x)

        def chunk_of(dst, args):
            st, x, do = args
            *grads, dst0 = bwd(st, *x, do, dst)
            return dst0, tuple(grads)
        return jax.lax.scan(chunk_of, dst, (states, x, do), reverse=True)
    x = tuple(_steps(a, chunk, keep) for a in (q, k, v, g, beta[..., None]))
    dst = jnp.zeros_like(kept[:, :, 0])
    _, grads = jax.lax.scan(
        step, dst, (jnp.moveaxis(kept, 2, 0), x, _steps(do, chunk, keep)),
        reverse=True)
    dq, dk, dv, dg, dbeta = (_unsteps(a) for a in grads)
    return (dq.astype(q.dtype), dk.astype(k.dtype), dv.astype(v.dtype), dg,
            dbeta[..., 0])


# --------------------------------------------------------------------
# Pallas kernels. Packed layout: q, k, g [B, T, H dk], v, o [B, T, H dv],
# a head a lane block; beta [B, H, steps, keep, chunk] (rows).
# --------------------------------------------------------------------

def kda_chunk_fwd(q_ref, k_ref, v_ref, g_ref, b_ref, sums_ref, masks_ref,
                  o_ref, kept_ref, st_scr, *, chunk: int, keep: int):
    """One (batch, head, step) cell: ``keep`` chunks. Writes o and the
    state the step STARTED from."""
    @pl.when(pl.program_id(2) == 0)
    def _init():
        st_scr[...] = jnp.zeros_like(st_scr)

    kept_ref[...] = st_scr[...]
    sums, masks = sums_ref[...], masks_ref[...]

    def body(j, st):
        at = pl.ds(pl.multiple_of(j * chunk, chunk), chunk)
        o, st1 = chunk_fwd(st, q_ref[at, :], k_ref[at, :], v_ref[at, :],
                           g_ref[at, :], _column(b_ref[pl.ds(j, 1), :]),
                           sums, masks, q_ref.dtype)
        o_ref[at, :] = o.astype(o_ref.dtype)
        return st1

    st_scr[...] = jax.lax.fori_loop(0, keep, body, st_scr[...])


def kda_chunk_bwd(q_ref, k_ref, v_ref, g_ref, b_ref, sums_ref, masks_ref,
                  kept_ref, do_ref, dq_ref, dk_ref, dv_ref, dg_ref, db_ref,
                  states_scr, dst_scr, *, chunk: int, keep: int):
    """The same cell in reverse (the index maps walk the steps last to
    first): rebuild the step's ``keep`` incoming states, then each chunk's
    backward from the last to the first, the state's adjoint in scratch
    across steps."""
    @pl.when(pl.program_id(2) == 0)
    def _init():
        dst_scr[...] = jnp.zeros_like(dst_scr)

    sums, masks = sums_ref[...], masks_ref[...]
    cd = q_ref.dtype

    def inputs(j):
        at = pl.ds(pl.multiple_of(j * chunk, chunk), chunk)
        return at, (q_ref[at, :], k_ref[at, :], v_ref[at, :], g_ref[at, :],
                    _column(b_ref[pl.ds(j, 1), :]))

    def rebuild(j, st):
        states_scr[j] = st
        return chunk_fwd(st, *inputs(j)[1], sums, masks, cd)[1]

    states_scr[keep - 1] = jax.lax.fori_loop(0, keep - 1, rebuild,
                                             kept_ref[...])

    def body(i, dst):
        j = keep - 1 - i
        at, x = inputs(j)
        dq, dk, dv, dg, db, dst0 = chunk_bwd(
            states_scr[j], *x, do_ref[at, :], dst, sums, masks, cd)
        dq_ref[at, :] = dq.astype(dq_ref.dtype)
        dk_ref[at, :] = dk.astype(dk_ref.dtype)
        dv_ref[at, :] = dv.astype(dv_ref.dtype)
        dg_ref[at, :] = dg
        db_ref[pl.ds(j, 1), :] = jnp.sum(
            jnp.where(_eye(chunk), db, 0.0), axis=0, keepdims=True)
        return dst0

    dst_scr[...] = jax.lax.fori_loop(0, keep, body, dst_scr[...])


def _column(row):
    """``[1, C]`` -> ``[C, 1]`` through the diagonal (beta travels as rows:
    a ``[T, 1]`` array pads every element to a lane tile in HBM)."""
    c = row.shape[1]
    return jnp.sum(jnp.where(_eye(c), row, 0.0), axis=1, keepdims=True)


def _beta_rows(beta, chunk, keep):
    """[B, T, H] -> [B, H, steps, keep, chunk]."""
    b, t, h = beta.shape
    return beta.transpose(0, 2, 1).reshape(b, h, -1, keep, chunk)


_SEQ = pltpu.CompilerParams(
    dimension_semantics=("parallel", "parallel", "arbitrary"))


def _specs(rows, keep, dk, dv, order):
    """Block specs of a step's operands; ``order`` maps the grid's step
    index to the step (the backward's runs in reverse)."""
    seq = lambda d: pl.BlockSpec((None, rows, d),
                                 lambda bi, hi, si: (bi, order(si), hi))
    beta = pl.BlockSpec((None, None, None, keep, rows // keep),
                        lambda bi, hi, si: (bi, hi, order(si), 0, 0))
    kept = pl.BlockSpec((None, None, None, dv, dk),
                        lambda bi, hi, si: (bi, hi, order(si), 0, 0))
    whole = lambda a: pl.BlockSpec(a.shape, lambda bi, hi, si: (0, 0))
    return seq, beta, kept, whole


def _fwd_pallas(q, k, v, g, beta, chunk, keep, interpret):
    b, t, h, dk = q.shape
    dv = v.shape[3]
    rows = chunk * keep
    steps = t // rows
    sums, masks = _consts(chunk)
    seq, beta_spec, kept, whole = _specs(rows, keep, dk, dv, lambda si: si)
    o, states = pl.pallas_call(
        functools.partial(kda_chunk_fwd, chunk=chunk, keep=keep),
        grid=(b, h, steps),
        in_specs=[seq(dk), seq(dk), seq(dv), seq(dk), beta_spec,
                  whole(sums), whole(masks)],
        out_specs=(seq(dv), kept),
        out_shape=(jax.ShapeDtypeStruct((b, t, h * dv), q.dtype),
                   jax.ShapeDtypeStruct((b, h, steps, dv, dk), jnp.float32)),
        scratch_shapes=[pltpu.VMEM((dv, dk), jnp.float32)],
        compiler_params=_SEQ, interpret=interpret, name="kda_chunk_fwd",
    )(q.reshape(b, t, h * dk), k.reshape(b, t, h * dk),
      v.reshape(b, t, h * dv), g.reshape(b, t, h * dk),
      _beta_rows(beta, chunk, keep), sums, masks)
    return o.reshape(b, t, h, dv), states


def _bwd_pallas(q, k, v, g, beta, states, do, chunk, keep, interpret):
    b, t, h, dk = q.shape
    dv = v.shape[3]
    rows = chunk * keep
    steps = t // rows
    sums, masks = _consts(chunk)
    seq, beta_spec, kept, whole = _specs(rows, keep, dk, dv,
                                         lambda si: steps - 1 - si)
    packed = lambda d, dtype: jax.ShapeDtypeStruct((b, t, h * d), dtype)
    dq, dk_, dv_, dg, db = pl.pallas_call(
        functools.partial(kda_chunk_bwd, chunk=chunk, keep=keep),
        grid=(b, h, steps),
        in_specs=[seq(dk), seq(dk), seq(dv), seq(dk), beta_spec,
                  whole(sums), whole(masks), kept, seq(dv)],
        out_specs=(seq(dk), seq(dk), seq(dv), seq(dk), beta_spec),
        out_shape=(packed(dk, q.dtype), packed(dk, k.dtype),
                   packed(dv, v.dtype), packed(dk, jnp.float32),
                   jax.ShapeDtypeStruct((b, h, steps, keep, chunk),
                                        jnp.float32)),
        scratch_shapes=[pltpu.VMEM((keep, dv, dk), jnp.float32),
                        pltpu.VMEM((dv, dk), jnp.float32)],
        compiler_params=_SEQ, interpret=interpret, name="kda_chunk_bwd",
    )(q.reshape(b, t, h * dk), k.reshape(b, t, h * dk),
      v.reshape(b, t, h * dv), g.reshape(b, t, h * dk),
      _beta_rows(beta, chunk, keep), sums, masks, states,
      do.reshape(b, t, h * dv))
    return (dq.reshape(q.shape), dk_.reshape(k.shape), dv_.reshape(v.shape),
            dg.reshape(g.shape), db.reshape(b, h, t).transpose(0, 2, 1))


# --------------------------------------------------------------------
# The differentiable entry.
# --------------------------------------------------------------------

@functools.partial(jax.custom_vjp, nondiff_argnums=(5, 6, 7))
def _kda(q, k, v, g, beta, chunk, keep, interpret):
    return _kda_fwd(q, k, v, g, beta, chunk, keep, interpret)[0]


def _kda_fwd(q, k, v, g, beta, chunk, keep, interpret):
    with jax.named_scope("kda_chunk_fwd"):
        if interpret is None:
            o, states = _fwd_xla(q, k, v, g, beta, chunk, keep)
        else:
            o, states = _fwd_pallas(q, k, v, g, beta, chunk, keep, interpret)
    return o, (q, k, v, g, beta, states)


def _kda_bwd(chunk, keep, interpret, res, do):
    q, k, v, g, beta, states = res
    with jax.named_scope("kda_chunk_bwd"):
        if interpret is None:
            return _bwd_xla(q, k, v, g, beta, states, do, chunk, keep)
        return _bwd_pallas(q, k, v, g, beta, states, do, chunk, keep,
                           interpret)


_kda.defvjp(_kda_fwd, _kda_bwd)


def kda_reference(q, k, v, g, beta):
    """The recurrence of the module docstring token by token, float32:
    the specification (tests; O(T) sequential steps)."""
    f32 = lambda x: x.astype(jnp.float32)

    def step(s, x):
        q_t, k_t, v_t, g_t, b_t = x                 # [B, H, D] / [B, H]
        s = s * jnp.exp(g_t)[..., None]
        u = b_t[..., None] * (v_t - jnp.einsum("bhkv,bhk->bhv", s, k_t))
        s = s + k_t[..., None] * u[..., None, :]
        return s, jnp.einsum("bhkv,bhk->bhv", s, q_t)
    b, t, h, dk = q.shape
    xs = tuple(jnp.moveaxis(f32(x), 1, 0) for x in (q, k, v, g, beta))
    _, o = jax.lax.scan(step, jnp.zeros((b, h, dk, v.shape[3]), jnp.float32),
                        xs)
    return jnp.moveaxis(o, 0, 1)


def kda(q: jax.Array, k: jax.Array, v: jax.Array, g: jax.Array,
        beta: jax.Array, *, chunk: int = CHUNK, keep: int = KEEP,
        interpret: Optional[bool] = None) -> jax.Array:
    """The recurrence of the module docstring over ``q, k, g``
    ``[B, T, H, dk]`` (``g`` the log-decay, ``<= 0``), ``v``
    ``[B, T, H, dv]``, ``beta`` ``[B, T, H]``; returns ``o``
    ``[B, T, H, dv]`` in ``q``'s dtype. ``g`` and ``beta`` are taken in
    float32 whatever their dtype; ``q``'s dtype is the large matmuls'.

    ``chunk`` tokens are solved together and every ``keep``-th chunk's
    incoming state is kept for the backward. A ``T`` off ``chunk * keep``
    is zero-padded at the end (``k = 0``, ``beta = 0``, ``g = 0`` leave the
    state as it is). ``interpret=None`` picks the Pallas kernels on a TPU
    and the XLA twin elsewhere; ``True`` runs the kernel bodies in the
    Pallas interpreter."""
    if not (q.shape == k.shape == g.shape and v.shape[:3] == q.shape[:3]
            and beta.shape == q.shape[:3]):
        raise ValueError(f"kda shapes: q {q.shape} k {k.shape} v {v.shape} "
                         f"g {g.shape} beta {beta.shape}")
    t = q.shape[1]
    k, v = k.astype(q.dtype), v.astype(q.dtype)
    g, beta = g.astype(jnp.float32), beta.astype(jnp.float32)
    while keep > 1 and chunk * (keep // 2) >= t:
        keep //= 2
    pad = (-t) % (chunk * keep)
    if pad:
        q, k, v, g = (jnp.pad(x, ((0, 0), (0, pad), (0, 0), (0, 0)))
                      for x in (q, k, v, g))
        beta = jnp.pad(beta, ((0, 0), (0, pad), (0, 0)))
    if interpret is None and jax.default_backend() == "tpu":
        interpret = False
    if interpret is not None and (q.shape[3] % 128 or v.shape[3] % 128):
        _warn_fallback(f"kda reads heads as lane blocks: head sizes "
                       f"{q.shape[3]}, {v.shape[3]} off 128")
        interpret = None
    o = _kda(q, k, v, g, beta, chunk, keep, interpret)
    return o[:, :t] if pad else o
