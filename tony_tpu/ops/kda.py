"""Gated delta-rule linear attention: a matrix state a head, under a
per-channel decay (KDA, the Kimi Linear report, arXiv:2510.26692) or ONE
decay a head (Gated DeltaNet, arXiv:2412.06464).

The recurrence, per head, ``k_t, q_t, g_t`` in ``R^dk`` (``g_t <= 0`` the
log-decay of each key channel; a scalar decay is the same number on every
channel), ``v_t`` in ``R^dv`` (``dv`` need not be ``dk``), ``beta_t`` in
(0, 2) (above 1 a step's ``I - beta k k^T`` has a negative eigenvalue)::

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
diagonal blocks first so that no power grows (they grow with ``beta |k_t .
k_i|`` before they cancel: float32 holds beta up to 2 over keys that point
alike, the looser the more alike — ``tests/test_kda.py``).

**One decay a head** (``g [B, T, H]``): ``exp(G_t - G_i)`` is a ``[C, C]``
factor, a sum of ``g`` over ``(i, t]`` that is never positive under the
causal mask (above the diagonal the exponent is ``-inf``: nothing
overflows), so ``A`` and ``Aqk`` are ONE product ``[k; q] k^T`` times it —
no levels, no table (:func:`_half_scalar`, :func:`_bwd_scalar`). Everything
after the pair matrices — the inverse, the state's half, the head of the
backward — is the per-channel route's code; the decay and its gradient
travel as rows beside beta, one float a head and token.

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
(``kda_chunk_fwd``, ``kda_chunk_bwd`` — called ``gdn_chunk_fwd`` /
``gdn_chunk_bwd`` under a scalar decay, the same two bodies without their
table operands; grid (batch, head, step) with the step axis sequential and
the state in VMEM scratch; a head a lane block, head sizes off 128 behind
zero lanes: :func:`kda`) on a TPU, the same
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


def _grid(c):
    """Row and column indices of a ``[c, c]`` matrix."""
    return (jax.lax.broadcasted_iota(jnp.int32, (c, c), a) for a in (0, 1))


def _row(column):
    """``[C, 1]`` -> ``[1, C]`` through the diagonal."""
    return jnp.sum(jnp.where(_eye(column.shape[0]), column, 0.0), axis=0,
                   keepdims=True)


def _half_scalar(q, k, g, beta, cd):
    """:func:`chunk_half` under ONE decay a head, ``g [C, 1]``: ``exp(G_t -
    G_i)`` is a ``[C, C]`` factor, so both pair matrices are one product
    (``[k; q] k^T``) times it under the causal mask — no levels, no table.
    ``G_t - G_i`` is a sum of ``g`` over ``(i, t]``, never positive where
    the mask keeps it; above the diagonal the exponent is ``-inf``. Returns
    ``(ex, akk, aqk, t, decay)``: ``ex [2 C, dk]`` stacks ``exp(G)`` and
    ``exp(G_last - G)`` over the key lanes, as the per-channel route's last
    two blocks, so :func:`chunk_state` and the head of :func:`chunk_bwd`
    read both routes alike (a ``[C, 1]`` value fills as many vector
    registers, and Mosaic broadcasts along one axis at a time: the state's
    ``[1, 1]`` decay would not lower); ``decay`` is the factor, kept for
    the backward."""
    c = q.shape[0]
    row, col = _grid(c)
    cum = jnp.sum(jnp.where(row >= col, _row(g), 0.0), axis=1, keepdims=True)
    decay = jnp.exp(jnp.where(row >= col, cum - _row(cum), -jnp.inf))
    # packsite: region-local — one head's chunk (VMEM values).
    ex = jnp.broadcast_to(jnp.exp(jnp.concatenate(
        [cum, cum[c - 1:c] - cum], axis=0)), (2 * c, q.shape[1]))
    # packsite: region-local — as above: k and q share the product with k^T.
    pairs = _mm(jnp.concatenate([k, q], axis=0), k, _NT, cd)
    akk = jnp.where(row > col, pairs[:c] * decay, 0.0)
    return ex, akk, pairs[c:] * decay, _inverse(beta * akk), decay


def chunk_half(q, k, g, beta, sums, masks, cd):
    """The half of a chunk that its incoming state does not touch, made
    once a chunk by each kernel call: ``q, k, g [C, dk]``, ``beta [C, 1]``
    -> ``(ex, akk, aqk, t)`` float32 — the decay factors (``exp`` of
    :func:`_exponents`), the two pair matrices ``[C, C]`` and ``t = (I +
    beta akk)^-1``. Without tables (``sums`` None) the decay is one a head,
    ``g [C, 1]``: :func:`_half_scalar`."""
    q, k, g, beta = _f32(q, k, g, beta)
    if sums is None:
        return _half_scalar(q, k, g, beta, cd)
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
    ex, _, aqk, t = half[:4]
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
    ex, akk, aqk, t = half[:4]
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
    if sums is None:
        dq, dk, dg = _bwd_scalar(q, k, half[4], akk, aqk, dakk, daqk,
                                 dqg * eg, dkg * eg + dkl * el,
                                 dkg * kg + dqg * qg, dkl * kl, d_last, cd)
        return dq, dk, dv, dg, dbeta, dst
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


def _bwd_scalar(q, k, decay, akk, aqk, dakk, daqk, dq, dk, deg, del_, d_last,
                cd):
    """The tail of :func:`chunk_bwd` under one decay a head: the pair
    matrices' adjoints back to ``q``, ``k`` (two products: ``[dQK; dKK] k``
    and ``[dKK; dQK]^T [k; q]``, the second with a full contraction) and to
    the decay ``g [C, 1]``. ``dq``, ``dk`` arrive holding the state's part;
    ``deg`` / ``del_`` ``[C, dk]`` are the adjoints of ``exp(G)`` / ``exp(
    G_last - G)`` times their values, channel by channel, ``d_last`` ``[1,
    dk]`` the state's decay's."""
    c = q.shape[0]
    row, col = _grid(c)
    dkk, dqk = jnp.where(row > col, dakk, 0.0) * decay, daqk * decay
    # packsite: region-local — one head's chunk (VMEM values), as below.
    left = _mm(jnp.concatenate([dqk, dkk], axis=0), k, _NN, cd)
    # packsite: region-local
    pairs = jnp.concatenate([dkk, dqk], axis=0)
    # packsite: region-local
    dk = dk + left[c:] + _mm(pairs, jnp.concatenate([k, q], axis=0), _TN, cd)
    # G_t - G_i: + to G_t along a row, - to G_i along a column
    dd = dakk * akk + daqk * aqk
    dcum = jnp.sum(dd, axis=1, keepdims=True) \
        - _column(jnp.sum(dd, axis=0, keepdims=True))
    deg, del_ = (jnp.sum(x, axis=1, keepdims=True) for x in (deg, del_))
    last = jax.lax.broadcasted_iota(jnp.int32, (c, 1), 0) == c - 1
    dcum = dcum + deg - del_ + jnp.where(
        last, jnp.sum(d_last, axis=1, keepdims=True)
        + jnp.sum(del_, axis=0, keepdims=True), 0.0)
    # G = cumsum g: g_j reaches every G_t with t >= j
    dg = jnp.sum(jnp.where(col >= row, _row(dcum), 0.0), axis=1,
                 keepdims=True)
    return dq + left[:c], dk, dg


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


def _tables(g, chunk, cd=None):
    """The tables a call over the decay ``g`` hands down: :func:`_consts`
    for a per-channel decay, none for a scalar one."""
    return () if g.ndim == 3 else _consts(chunk, cd)


def _columns(g):
    """A scalar decay ``[B, T, H]`` as ``[B, T, H, 1]`` (as beta travels
    through the twin); a per-channel one as it is."""
    return g[..., None] if g.ndim == 3 else g


def _fwd_xla(q, k, v, g, beta, chunk, keep):
    b, t, h, dk = q.shape
    sums, masks = _tables(g, chunk) or (None, None)
    one = jax.vmap(jax.vmap(functools.partial(
        chunk_fwd, sums=sums, masks=masks, cd=q.dtype)))

    def step(st, xs):
        def chunk_of(st, x):
            o, st1 = one(st, *x)
            return st1, o
        st1, o = jax.lax.scan(chunk_of, st, xs)
        return st1, (o, st)
    st0 = jnp.zeros((b, h, v.shape[3], dk), jnp.float32)
    xs = tuple(_steps(x, chunk, keep)
               for x in (q, k, v, _columns(g), beta[..., None]))
    _, (o, kept) = jax.lax.scan(step, st0, xs)
    return _unsteps(o).astype(q.dtype), jnp.moveaxis(kept, 0, 2)


def _bwd_xla(q, k, v, g, beta, kept, do, chunk, keep):
    sums, masks = _tables(g, chunk) or (None, None)
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
    x = tuple(_steps(a, chunk, keep)
              for a in (q, k, v, _columns(g), beta[..., None]))
    dst = jnp.zeros_like(kept[:, :, 0])
    _, grads = jax.lax.scan(
        step, dst, (jnp.moveaxis(kept, 2, 0), x, _steps(do, chunk, keep)),
        reverse=True)
    dq, dk, dv, dg, dbeta = (_unsteps(a) for a in grads)
    return (dq.astype(q.dtype), dk.astype(k.dtype), dv.astype(v.dtype),
            dg[..., 0] if g.ndim == 3 else dg, dbeta[..., 0])


# --------------------------------------------------------------------
# Pallas kernels. Packed layout: q, k, g [B, T, H dk], v, o [B, T, H dv],
# a head a lane block; beta — and a scalar decay g, and its dg —
# [B, H, steps, keep, chunk] (rows). One body a direction serves both
# routes: the per-channel decay's call hands it the two tables
# (``tables``), the scalar decay's none.
# --------------------------------------------------------------------

def _decay(g_ref, tables, j, at):
    """Chunk ``j``'s decay: its ``[C, dk]`` block, or a scalar decay's row
    stood up as ``[C, 1]``."""
    return g_ref[at, :] if tables else _column(g_ref[pl.ds(j, 1), :])


def kda_chunk_fwd(q_ref, k_ref, v_ref, g_ref, b_ref, *rest, chunk: int,
                  keep: int):
    """One (batch, head, step) cell: ``keep`` chunks. Writes o and the
    state the step STARTED from. The loop runs unrolled: a chunk's
    state-free half does not wait for the chunk before it, and in one block
    the scheduler starts it under that chunk's tail (on the chip 45.2 ms a
    call for 50.3 at the Kimi Linear cell's size: PERF.md §6 PR 39). The
    tables go down as refs (:func:`kda_chunk_bwd` says why)."""
    *tables, o_ref, kept_ref, st_scr = rest

    @pl.when(pl.program_id(2) == 0)
    def _init():
        st_scr[...] = jnp.zeros_like(st_scr)

    kept_ref[...] = st_scr[...]

    def body(j, st):
        at = pl.ds(pl.multiple_of(j * chunk, chunk), chunk)
        o, st1 = chunk_fwd(st, q_ref[at, :], k_ref[at, :], v_ref[at, :],
                           _decay(g_ref, tables, j, at),
                           _column(b_ref[pl.ds(j, 1), :]),
                           *(tables or (None, None)), q_ref.dtype)
        o_ref[at, :] = o.astype(o_ref.dtype)
        return st1

    st_scr[...] = jax.lax.fori_loop(0, keep, body, st_scr[...], unroll=True)


def kda_chunk_bwd(q_ref, k_ref, v_ref, g_ref, b_ref, *rest, chunk: int,
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
    (*tables, kept_ref, do_ref, dq_ref, dk_ref, dv_ref, dg_ref, db_ref,
     states_scr, dst_scr, ex_scr, pairs_scr) = rest

    @pl.when(pl.program_id(2) == 0)
    def _init():
        dst_scr[...] = jnp.zeros_like(dst_scr)

    # the tables go down as refs, read where a product takes them: read
    # whole up here they spill, ~100 vregs, before the loops start
    (sums, masks), cd = tables or (None, None), q_ref.dtype

    def inputs(j):
        at = pl.ds(pl.multiple_of(j * chunk, chunk), chunk)
        return at, (q_ref[at, :], k_ref[at, :], v_ref[at, :],
                    _column(b_ref[pl.ds(j, 1), :]))

    def rebuild(j, st):
        at, (q, k, v, beta) = inputs(j)
        half = chunk_half(q, k, _decay(g_ref, tables, j, at), beta, sums,
                          masks, cd)
        states_scr[j] = st
        ex_scr[j] = half[0]
        for n, pair in enumerate(half[1:]):
            pairs_scr[j, n] = pair
        return chunk_state(st, half, q, k, v, beta, cd)[1]

    jax.lax.fori_loop(0, keep, rebuild, kept_ref[...], unroll=True)

    def body(i, dst):
        j = keep - 1 - i
        at, x = inputs(j)
        half = (ex_scr[j],
                *(pairs_scr[j, n] for n in range(pairs_scr.shape[1])))
        dq, dk, dv, dg, db, dst0 = chunk_bwd(
            states_scr[j], half, *x, do_ref[at, :], dst, sums, masks, cd)
        dq_ref[at, :] = dq.astype(dq_ref.dtype)
        dk_ref[at, :] = dk.astype(dk_ref.dtype)
        dv_ref[at, :] = dv.astype(dv_ref.dtype)
        if tables:
            dg_ref[at, :] = dg
        else:
            dg_ref[pl.ds(j, 1), :] = _row(dg)
        db_ref[pl.ds(j, 1), :] = _row(db)
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


def _packed_decay(g, chunk, keep):
    """The decay as the kernels read it: a per-channel one beside q and k
    (a head a lane block), a scalar one as beta's rows."""
    if g.ndim == 3:
        return _beta_rows(g, chunk, keep)
    b, t, h, dk = g.shape
    return g.reshape(b, t, h * dk)


def _name(g, kernel):
    """The kernel call's name (the custom call's in a trace, the device
    scope around it): ``kda_chunk_*`` under a per-channel decay,
    ``gdn_chunk_*`` under a scalar one."""
    return f"{'gdn' if g.ndim == 3 else 'kda'}_chunk_{kernel}"


def _fwd_pallas(q, k, v, g, beta, chunk, keep, interpret):
    b, t, h, dk = q.shape
    dv = v.shape[3]
    rows = chunk * keep
    steps = t // rows
    seq, beta_spec, kept, whole = _specs(rows, keep, dk, dv, lambda si: si)
    tables = _tables(g, chunk, None if interpret else q.dtype)
    g_spec = seq(dk) if tables else beta_spec
    o, states = pl.pallas_call(
        functools.partial(kda_chunk_fwd, chunk=chunk, keep=keep),
        grid=(b, h, steps),
        in_specs=[seq(dk), seq(dk), seq(dv), g_spec, beta_spec,
                  *map(whole, tables)],
        out_specs=(seq(dv), kept),
        out_shape=(jax.ShapeDtypeStruct((b, t, h * dv), q.dtype),
                   jax.ShapeDtypeStruct((b, h, steps, dv, dk), jnp.float32)),
        scratch_shapes=[pltpu.VMEM((dv, dk), jnp.float32)],
        compiler_params=_SEQ, interpret=interpret, name=_name(g, "fwd"),
    )(q.reshape(b, t, h * dk), k.reshape(b, t, h * dk),
      v.reshape(b, t, h * dv), _packed_decay(g, chunk, keep),
      _beta_rows(beta, chunk, keep), *tables)
    return o.reshape(b, t, h, dv), states


def _bwd_pallas(q, k, v, g, beta, states, do, chunk, keep, interpret):
    b, t, h, dk = q.shape
    dv = v.shape[3]
    rows = chunk * keep
    steps = t // rows
    seq, beta_spec, kept, whole = _specs(rows, keep, dk, dv,
                                         lambda si: steps - 1 - si)
    packed = lambda d, dtype: jax.ShapeDtypeStruct((b, t, h * d), dtype)
    row_shape = jax.ShapeDtypeStruct((b, h, steps, keep, chunk), jnp.float32)
    tables = _tables(g, chunk, None if interpret else q.dtype)
    if tables:
        g_spec, dg_shape = seq(dk), packed(dk, jnp.float32)
        half_rows, pairs = chunk + table_rows(chunk), 3
    else:       # the factors over the key lanes; akk, aqk, t and the decay
        g_spec, dg_shape, half_rows, pairs = beta_spec, row_shape, \
            2 * chunk, 4
    dq, dk_, dv_, dg, db = pl.pallas_call(
        functools.partial(kda_chunk_bwd, chunk=chunk, keep=keep),
        grid=(b, h, steps),
        in_specs=[seq(dk), seq(dk), seq(dv), g_spec, beta_spec,
                  *map(whole, tables), kept, seq(dv)],
        out_specs=(seq(dk), seq(dk), seq(dv), g_spec, beta_spec),
        out_shape=(packed(dk, q.dtype), packed(dk, k.dtype),
                   packed(dv, v.dtype), dg_shape, row_shape),
        # the step's incoming states, the state's adjoint, and each
        # chunk's state-free half: (keep, 13 C, dk) + (keep, 3, C, C)
        # float32 = 1.9 MB at the cell's 4 x 64 x 128 (a scalar decay's:
        # (keep, 2 C, dk) and four pair matrices)
        scratch_shapes=[
            pltpu.VMEM((keep, dv, dk), jnp.float32),
            pltpu.VMEM((dv, dk), jnp.float32),
            pltpu.VMEM((keep, half_rows, dk), jnp.float32),
            pltpu.VMEM((keep, pairs, chunk, chunk), jnp.float32)],
        compiler_params=_SEQ, interpret=interpret, name=_name(g, "bwd"),
    )(q.reshape(b, t, h * dk), k.reshape(b, t, h * dk),
      v.reshape(b, t, h * dv), _packed_decay(g, chunk, keep),
      _beta_rows(beta, chunk, keep), *tables, states,
      do.reshape(b, t, h * dv))
    unrow = lambda x: x.reshape(b, h, t).transpose(0, 2, 1)
    return (dq.reshape(q.shape), dk_.reshape(k.shape), dv_.reshape(v.shape),
            dg.reshape(g.shape) if tables else unrow(dg), unrow(db))


# --------------------------------------------------------------------
# The differentiable entry.
# --------------------------------------------------------------------

@functools.partial(jax.custom_vjp, nondiff_argnums=(5, 6, 7))
def _kda(q, k, v, g, beta, chunk, keep, interpret):
    return _kda_fwd(q, k, v, g, beta, chunk, keep, interpret)[0]


def _kda_fwd(q, k, v, g, beta, chunk, keep, interpret):
    with jax.named_scope(_name(g, "fwd")):
        if interpret is None:
            o, states = _fwd_xla(q, k, v, g, beta, chunk, keep)
        else:
            o, states = _fwd_pallas(q, k, v, g, beta, chunk, keep, interpret)
    return o, (q, k, v, g, beta, states)


def _kda_bwd(chunk, keep, interpret, res, do):
    q, k, v, g, beta, states = res
    with jax.named_scope(_name(g, "bwd")):
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


def _lanes(x):
    """The last axis zero-padded to a multiple of 128: the kernels read a
    head as a lane block."""
    pad = (-x.shape[-1]) % 128
    return jnp.pad(x, ((0, 0),) * (x.ndim - 1) + ((0, pad),)) if pad else x


def kda(q: jax.Array, k: jax.Array, v: jax.Array, g: jax.Array,
        beta: jax.Array, *, chunk: int = CHUNK, keep: int = KEEP,
        interpret: Optional[bool] = None) -> jax.Array:
    """The recurrence of the module docstring over ``q, k`` ``[B, T, H,
    dk]``, ``v`` ``[B, T, H, dv]``, ``beta`` ``[B, T, H]`` and the
    log-decay ``g <= 0``: ``[B, T, H, dk]``, a decay a key channel (KDA),
    or ``[B, T, H]``, ONE a head (Gated DeltaNet: its ``dg`` leaves at that
    shape too). Returns ``o`` ``[B, T, H, dv]`` in ``q``'s dtype. ``g`` and
    ``beta`` are taken in float32 whatever their dtype; ``q``'s dtype is the
    large matmuls'.

    ``chunk`` tokens are solved together and every ``keep``-th chunk's
    incoming state is kept for the backward. A ``T`` off ``chunk * keep``
    is zero-padded at the end (``k = 0``, ``beta = 0``, ``g = 0`` leave the
    state as it is). ``interpret=None`` picks the Pallas kernels on a TPU
    and the XLA twin elsewhere; ``True`` runs the kernel bodies in the
    Pallas interpreter. The kernels read a head as a lane block: head sizes
    off 128 (96 and 192, say) go through them behind **zero lanes** — zero
    key channels and zero value columns change nothing, their state rows
    and output columns stay zero — at the cost of q, k, v, o and their
    gradients ``ceil(d / 128) 128 / d`` times as wide in HBM (4/3 at 96 and
    192); the MXU's passes and the vector registers are 128 lanes wide
    either way."""
    scalar = g.ndim == 3
    if not (q.shape == k.shape and v.shape[:3] == q.shape[:3]
            and beta.shape == q.shape[:3]
            and g.shape == (beta.shape if scalar else q.shape)):
        raise ValueError(f"kda shapes: q {q.shape} k {k.shape} v {v.shape} "
                         f"g {g.shape} beta {beta.shape}")
    t, dv = q.shape[1], v.shape[3]
    k, v = k.astype(q.dtype), v.astype(q.dtype)
    g, beta = g.astype(jnp.float32), beta.astype(jnp.float32)
    while keep > 1 and chunk * (keep // 2) >= t:
        keep //= 2
    pad = (-t) % (chunk * keep)
    if pad:
        steps = lambda x: jnp.pad(
            x, ((0, 0), (0, pad)) + ((0, 0),) * (x.ndim - 2))
        q, k, v, g, beta = map(steps, (q, k, v, g, beta))
    if interpret is None and jax.default_backend() == "tpu":
        interpret = False
    if interpret is not None:
        q, k, v = map(_lanes, (q, k, v))
        g = g if scalar else _lanes(g)
    o = _kda(q, k, v, g, beta, chunk, keep, interpret)
    return o[:, :t, :, :dv] if pad or o.shape[3] != dv else o
