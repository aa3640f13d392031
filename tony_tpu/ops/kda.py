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
Level 1 pairs ``t`` with ``t - 1``: its one exponent is ``g_t`` itself. The
other levels' partial sums of ``g`` are ONE matmul with a constant 0/1
table (:func:`tables`, 768 rows at C = 64), exact to float32
(:func:`_mm_table`): ``g`` is split in bfloat16 parts (two in a bfloat16
model: 2^-16; three in float32), the parts are stacked under each other
and meet as many copies of the table side by side, so one native bfloat16
product with a full contraction (K = 128) makes every block and the MXU's
float32 accumulator adds the parts. Each block is a sum of at most 64
nonpositive numbers, never a difference of prefix sums: nothing cancels.
``(I + A)^-1`` is Neumann products: exact for a nilpotent matrix, 16 x 16
diagonal blocks first so that no power grows.

A chunk has two halves. What the incoming state does not touch — the decay
factors, the pair matrices ``A`` / ``Aqk``, the inverse — is the
**state-free half** (:func:`chunk_half`); ``U``, ``O`` and ``S'`` start
from the state (:func:`chunk_state`). Each kernel call builds a chunk's
state-free half ONCE. The forward keeps the state at the start of every
``keep``-th chunk (``[T / (keep C), dv, dk]`` float32 a head); the backward
walks those steps in reverse: one loop forward over a step's ``keep`` chunks
builds each chunk's half, leaves it in VMEM scratch beside the state the
chunk starts from (the factors ``[keep, 13 C, dk]`` and ``A``, ``Aqk``,
the inverse ``[keep, 3, C, C]``, float32: 1.9 MB at 4 x 64 x 128, inside
the default 16 MiB) and advances the state with the state's products
alone; then each chunk's hand-written backward (:func:`chunk_bwd`) reads
the scratch, the state's adjoint carried across. Decays, state, the
inverse and every accumulation are float32; the large matmuls take
operands in the inputs' dtype.

Dispatch follows :mod:`tony_tpu.ops.ssm`: Pallas kernels
(``kda_chunk_fwd``, ``kda_chunk_bwd``; grid (batch, head, step) with the
step axis sequential and the state in VMEM scratch) on a TPU, the same
chunk functions under ``interpret=True`` for CPU tests, and an XLA twin
(``lax.scan`` over steps of the same functions) elsewhere. Only the
kernels compiled for the chip take the split product: the twin and the
interpreter multiply a float32 table at the highest precision — chosen by
the table :func:`_consts` hands over, not by the backend's name, so a
compile for a DESCRIBED chip (``tests/test_tpu_compile_hybrid.py``,
``tests/test_kernel_schedule.py``) builds the program the chip runs.
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
def halves_built(kernel: str, chunk: int = CHUNK, keep: int = KEEP) -> int:
    """State-free halves (:func:`chunk_half`) a (head, step) cell of the
    kernel ``"fwd"`` / ``"bwd"`` builds for its ``keep`` chunks, read off
    the kernel's own jaxpr (the counters ``kda:halves_built.fwd`` /
    ``.bwd``): a half takes the one ``exp`` of a chunk's stacked exponents
    (``[(2 L + 1) C, dk]``, no other value has those rows), counted once
    for every trip of the loops around it."""
    d = 128
    x = jax.ShapeDtypeStruct((1, chunk * keep, 1, d), jnp.bfloat16)
    g = jax.ShapeDtypeStruct(x.shape, jnp.float32)
    args = (x, x, x, g, jax.ShapeDtypeStruct(x.shape[:3], jnp.float32))
    if kernel == "fwd":
        call = _fwd_pallas
    else:
        call = _bwd_pallas
        args += (jax.ShapeDtypeStruct((1, 1, 1, d, d), jnp.float32), x)
    rows = chunk + table_rows(chunk)

    def count(jaxpr):
        n = 0
        for eqn in jaxpr.eqns:
            n += (eqn.primitive.name == "exp"
                  and eqn.outvars[0].aval.shape[:1] == (rows,))
            n += eqn.params.get("length", 1) * sum(
                map(count, jax.core.jaxprs_in_params(eqn.params)))
        return n
    return count(jax.make_jaxpr(functools.partial(
        call, chunk=chunk, keep=keep, interpret=False))(*args).jaxpr)


def table_rows(chunk: int = CHUNK) -> int:
    """Rows of 0/1 table a chunk's exponents are multiplied by (the
    counter ``kda:table_rows``)."""
    return tables(chunk)[0].shape[0]


@functools.lru_cache(maxsize=None)
def tables(chunk: int):
    """``(sums, masks)`` of a chunk length (a power of two), as numpy.

    ``sums`` ``[2 L C, C]`` 0/1: stacked blocks that, times ``g [C, dk]``,
    give for each level ``n = 2^l`` ABOVE THE FIRST the row exponent ``sum
    of g over (start of t's block, t]`` and the column exponent ``sum over
    (i, start of the block after i's]``, then ``G`` (sum over ``[0, t]``)
    and ``G_last - G`` (sum over ``(t, C)``). Level 1 pairs ``t`` with ``t
    - 1``: its row exponent is ``g_t`` itself and its column has none, so
    it takes no rows. ``masks`` ``[L C, C]`` 0/1: the pairs ``(t, i)`` of
    each level, the first included."""
    levels = [1 << i for i in range(chunk.bit_length() - 1)]
    if not levels or levels[-1] * 2 != chunk:
        raise ValueError(f"kda chunk {chunk}: a power of two, at least 2")
    t = np.arange(chunk)[:, None]
    j = np.arange(chunk)[None, :]
    sums, masks = [], []
    for n in levels:
        if n > 1:
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


def _mm32(a, b, dims=_NN):
    return _mm(a, b, dims, jnp.float32)


def _parts(cd) -> int:
    """bfloat16 parts a float32 operand of a 0/1 table is split into: two
    where the model computes in bfloat16 (2^-16), three in float32."""
    return 3 if jnp.dtype(cd) == jnp.float32 else 2


def _mm_table(table, x, dims, cd):
    """A 0/1 ``table`` x float32 ``x``, exact to float32. A float32 table
    (the twin's, the interpreter's: XLA's CPU runtime has no bfloat16
    product inside a loop) is one float32 product. A bfloat16 table (the
    compiled kernels': :func:`_consts`) is ``_parts(cd)`` copies of the
    table side by side, and ``x`` is split into as many parts that each fit
    bfloat16: table x ``x`` (``_NN``) meets the parts stacked under each
    other in ONE native product whose contraction the copies fill (C = 64
    alone half-fills a bfloat16 tile's lanes) and whose float32
    accumulator adds the parts; table^T x ``x`` (``_TN``) contracts over
    the rows and runs a product a part over the first copy."""
    if table.dtype == jnp.float32:
        return _mm32(table[...], x, dims)
    parts, rest = [], x
    for _ in range(_parts(cd)):
        parts.append(rest.astype(jnp.bfloat16))
        rest = rest - parts[-1].astype(jnp.float32)
    dot = lambda a, b: jax.lax.dot_general(
        a, b, (dims, ((), ())), preferred_element_type=jnp.float32)
    if dims == _NN:
        # packsite: region-local — one head's chunk (VMEM values).
        return dot(table[...], jnp.concatenate(parts, axis=0))
    first = table[:, :table.shape[1] // len(parts)]
    return functools.reduce(jnp.add, (dot(first, p) for p in parts))


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


def _f32(*xs):
    return tuple(x.astype(jnp.float32) for x in xs)


def _exponents(g, sums, cd):
    """The exponent blocks of a chunk stacked ``[(2 L + 1) C, dk]``: level
    1's row exponent, ``g`` itself, then the table's blocks
    (:func:`tables`) — each further level's row and column exponents,
    ``G``, ``G_last - G``. Sums of ``g``, each exact to float32; none is
    positive."""
    # packsite: region-local — one head's chunk (VMEM values).
    return jnp.concatenate([g, _mm_table(sums, g, _NN, cd)], axis=0)


def _operands(q, k, ex, masks):
    """Each level's decay factors (blocks of ``ex``, the ``exp`` of
    :func:`_exponents`), decayed operands and pair mask, ``(er, ec, kr, qr,
    kc, m)``; ``ec`` is None at level 1, whose column operand is ``k``."""
    c = q.shape[0]
    block = lambda i: ex[i * c:(i + 1) * c]
    for l in range(masks.shape[0] // c):
        er, ec = block(max(2 * l - 1, 0)), block(2 * l) if l else None
        yield (er, ec, k * er, q * er, k * ec if l else k,
               masks[l * c:(l + 1) * c])


def chunk_half(q, k, g, beta, sums, masks, cd):
    """The half of a chunk that its incoming state does not touch, made
    once a chunk by each kernel call: ``q, k, g [C, dk]``, ``beta [C, 1]``
    -> ``(ex, akk, aqk, t)`` float32 — the decay factors (``exp`` of
    :func:`_exponents`), the two pair matrices ``[C, C]`` and ``t = (I +
    beta akk)^-1``."""
    q, k, g, beta = _f32(q, k, g, beta)
    ex = jnp.exp(_exponents(g, sums, cd))
    akk = aqk = None
    for _, _, kr, qr, kc, m in _operands(q, k, ex, masks):
        pk, pq = m * _mm(kr, kc, _NT, cd), m * _mm(qr, kc, _NT, cd)
        akk = pk if akk is None else akk + pk
        aqk = pq if aqk is None else aqk + pq
    aqk = aqk + jnp.where(_eye(q.shape[0]),
                          jnp.sum(q * k, axis=1, keepdims=True), 0.0)
    return ex, akk, aqk, _inverse(beta * akk)


def chunk_state(st, half, q, k, v, beta, cd):
    """The half of a chunk's forward that starts from the state: ``st
    [dv, dk]`` float32 (the state, transposed: a decay scales its lanes),
    ``half`` from :func:`chunk_half`, ``v [C, dv]`` -> ``(o [C, dv], state
    after)``, float32."""
    q, k, v, beta = _f32(q, k, v, beta)
    c = q.shape[0]
    ex, _, aqk, t = half
    eg, el = ex[-2 * c:-c], ex[-c:]
    u = _mm(t, beta * (v - _mm(k * eg, st, _NT, cd)), _NN, cd)
    o = _mm(q * eg, st, _NT, cd) + _mm(aqk, u, _NN, cd)
    return o, st * eg[c - 1:c] + _mm(u, k * el, _TN, cd)


def chunk_fwd(st, q, k, v, g, beta, sums, masks, cd):
    """One chunk of one head, both halves: ``(o, state after)``."""
    half = chunk_half(q, k, g, beta, sums, masks, cd)
    return chunk_state(st, half, q, k, v, beta, cd)


def chunk_bwd(st, half, q, k, v, beta, do, dst1, sums, masks, cd):
    """The chunk's backward, by hand, from the state it started from and
    its state-free half: ``(dq, dk, dv, dg, dbeta, dst)`` from the
    output's and the outgoing state's adjoints. ``d(I + A)^-1`` is closed
    (``dA = -(T^T dU) U^T``), so the inverse's products are not walked
    back."""
    q, k, v, beta, do = _f32(q, k, v, beta, do)
    c = q.shape[0]
    ex, akk, aqk, t = half
    eg, el = ex[-2 * c:-c], ex[-c:]
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
    for er, ec, kr, qr, kc, m in _operands(q, k, ex, masks):
        dpk, dpq = m * dakk, m * daqk
        dkr, dqr = _mm(dpk, kc, _NN, cd), _mm(dpq, kc, _NN, cd)
        dkc = _mm(dpk, kr, _TN, cd) + _mm(dpq, qr, _TN, cd)
        dq = dq + dqr * er
        dk = dk + dkr * er + (dkc if ec is None else dkc * ec)
        de += [dkr * kr + dqr * qr] + ([] if ec is None else [dkc * kc])
    de += [dkg * kg + dqg * qg + jnp.where(row == c - 1, d_last, 0.0),
           dkl * kl]
    # packsite: region-local — the exponent blocks' adjoints stacked as
    # the table stacks the blocks, one head's chunk (VMEM values); level
    # 1's exponent is g itself.
    dg = de[0] + _mm_table(sums, jnp.concatenate(de[1:], axis=0), _TN, cd)
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


def _consts(chunk, cd=None):
    """The tables as the chunk functions take them: float32 for the twin
    and the interpreter; for the kernels compiled for the chip (``cd``
    their compute dtype) the sums in bfloat16 (0/1: exact), ``_parts(cd)``
    copies side by side (:func:`_mm_table`)."""
    sums, masks = tables(chunk)
    if cd is None:
        return jnp.asarray(sums), jnp.asarray(masks)
    return (jnp.asarray(np.tile(sums, (1, _parts(cd))), jnp.bfloat16),
            jnp.asarray(masks))


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
    over = lambda f, **consts: jax.vmap(jax.vmap(
        functools.partial(f, **consts)))
    half_of = over(chunk_half, **kw)
    state, bwd = over(chunk_state, cd=q.dtype), over(chunk_bwd, **kw)

    def step(dst, xs):
        st, x, do = xs

        def rebuild(st, x):
            q, k, v, g, beta = x
            half = half_of(q, k, g, beta)
            return state(st, half, q, k, v, beta)[1], (st, half)
        _, (states, halves) = jax.lax.scan(rebuild, st, x)

        def chunk_of(dst, args):
            st, half, (q, k, v, _, beta), do = args
            *grads, dst0 = bwd(st, half, q, k, v, beta, do, dst)
            return dst0, tuple(grads)
        return jax.lax.scan(chunk_of, dst, (states, halves, x, do),
                            reverse=True)
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
    state the step STARTED from. The loop runs unrolled: a chunk's
    state-free half does not wait for the chunk before it, and in one block
    the scheduler starts it under that chunk's tail (on the chip 45.2 ms a
    call for 50.3 at the Kimi Linear cell's size: PERF.md §6 PR 39). The
    tables go down as refs (:func:`kda_chunk_bwd` says why)."""
    @pl.when(pl.program_id(2) == 0)
    def _init():
        st_scr[...] = jnp.zeros_like(st_scr)

    kept_ref[...] = st_scr[...]

    def body(j, st):
        at = pl.ds(pl.multiple_of(j * chunk, chunk), chunk)
        o, st1 = chunk_fwd(st, q_ref[at, :], k_ref[at, :], v_ref[at, :],
                           g_ref[at, :], _column(b_ref[pl.ds(j, 1), :]),
                           sums_ref, masks_ref, q_ref.dtype)
        o_ref[at, :] = o.astype(o_ref.dtype)
        return st1

    st_scr[...] = jax.lax.fori_loop(0, keep, body, st_scr[...], unroll=True)


def kda_chunk_bwd(q_ref, k_ref, v_ref, g_ref, b_ref, sums_ref, masks_ref,
                  kept_ref, do_ref, dq_ref, dk_ref, dv_ref, dg_ref, db_ref,
                  states_scr, dst_scr, ex_scr, pairs_scr, *, chunk: int,
                  keep: int):
    """The same cell in reverse (the index maps walk the steps last to
    first). One loop forward over the step's chunks builds each chunk's
    state-free half, leaves it in scratch (``ex_scr``: the decay factors;
    ``pairs_scr``: akk, aqk, t) beside the state the chunk starts from,
    and advances the state with the state's products alone (unrolled as
    the forward's loop); then each chunk's backward from the last to the
    first reads the scratch, the state's adjoint in scratch across steps
    (a loop: each chunk waits for the adjoint of the one after it, and
    unrolled it ran no faster)."""
    @pl.when(pl.program_id(2) == 0)
    def _init():
        dst_scr[...] = jnp.zeros_like(dst_scr)

    # the tables go down as refs, read where a product takes them: read
    # whole up here they spill, ~100 vregs, before the loops start
    sums, masks, cd = sums_ref, masks_ref, q_ref.dtype

    def inputs(j):
        at = pl.ds(pl.multiple_of(j * chunk, chunk), chunk)
        return at, (q_ref[at, :], k_ref[at, :], v_ref[at, :],
                    _column(b_ref[pl.ds(j, 1), :]))

    def rebuild(j, st):
        at, (q, k, v, beta) = inputs(j)
        half = chunk_half(q, k, g_ref[at, :], beta, sums, masks, cd)
        states_scr[j] = st
        ex_scr[j] = half[0]
        for n, pair in enumerate(half[1:]):
            pairs_scr[j, n] = pair
        return chunk_state(st, half, q, k, v, beta, cd)[1]

    jax.lax.fori_loop(0, keep, rebuild, kept_ref[...], unroll=True)

    def body(i, dst):
        j = keep - 1 - i
        at, x = inputs(j)
        half = (ex_scr[j], *(pairs_scr[j, n] for n in range(3)))
        dq, dk, dv, dg, db, dst0 = chunk_bwd(
            states_scr[j], half, *x, do_ref[at, :], dst, sums, masks, cd)
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
    sums, masks = _consts(chunk, None if interpret else q.dtype)
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
    sums, masks = _consts(chunk, None if interpret else q.dtype)
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
        # the step's incoming states, the state's adjoint, and each
        # chunk's state-free half: (keep, 13 C, dk) + (keep, 3, C, C)
        # float32 = 1.9 MB at the cell's 4 x 64 x 128
        scratch_shapes=[
            pltpu.VMEM((keep, dv, dk), jnp.float32),
            pltpu.VMEM((dv, dk), jnp.float32),
            pltpu.VMEM((keep, chunk + table_rows(chunk), dk), jnp.float32),
            pltpu.VMEM((keep, 3, chunk, chunk), jnp.float32)],
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
