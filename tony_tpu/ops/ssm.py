"""Selective state-space scan (Mamba-1) and its causal depthwise
convolution.

The recurrence, per channel e of E and state n of N::

    h_t = exp(dt_t[e] * A[e, n]) * h_{t-1} + (dt_t[e] * x_t[e]) * B_t[n]
    y_t[e] = sum_n h_t[e, n] * C_t[n] + D[e] * x_t[e]            h_0 = 0

Written as ``lax.scan`` or ``associative_scan`` over ``[T, E, N]`` it holds
2.7 GB of float32 state a layer at T = 8192, E = 5120, N = 16, so
:func:`selective_scan` is **chunked** with a ``custom_vjp``: the forward
keeps the state only at chunk boundaries (``[T / chunk, N, E]``), the
backward walks the chunks in reverse, recomputes the states of one chunk
(in VMEM on the chip) and carries the state's adjoint across them.

:func:`conv_silu_unit` (PR 47) is a delta-rule mixer's operand chain —
that convolution, SiLU and each head's normalisation — as one kernel each
way (``delta_conv_fwd``, ``delta_conv_bwd``): bfloat16 in, float32 in
VMEM, bfloat16 out.

Dispatch follows :mod:`tony_tpu.ops.attention`: the Pallas kernels
(``ssm_scan_fwd``, ``ssm_scan_bwd``) on a TPU, the same bodies under
``interpret=True`` for CPU tests, the XLA twin elsewhere — and leaving the
kernel on a TPU warns with the ``kernel fallback`` prefix.

Kernel layout: 1024 channels are one ``[8, 128]`` float32 register tile,
so a time step's update of one state index n is a handful of full-width
vector operations; the grid is (batch, E / 1024, T / chunk) with the time
axis innermost and sequential, the state living in VMEM scratch across
it. ``B_t[n]`` and ``C_t[n]`` are scalars read from SMEM (a scalar
broadcasts into a vector operation for free), and the backward's ``dB``
and ``dC`` are full reductions of one tile to a scalar, written to SMEM
per channel block and summed over the blocks outside. The state, ``dt``
and every accumulation are float32; ``state_dtype=bfloat16`` rounds the
carried state and ``dt`` to bfloat16 (the benchmark's lower-precision
control — never a faster path).
"""

from __future__ import annotations

import functools
from typing import Optional

import jax
import jax.numpy as jnp
from jax.experimental import pallas as pl
from jax.experimental.pallas import tpu as pltpu

from tony_tpu.ops.attention import _warn_fallback

_SUB, _LANES = 8, 128
_EB = _SUB * _LANES          # channels per kernel block: one f32 tile
_MAX_STATE = 32              # the kernels unroll over the state index


def causal_conv1d(x: jax.Array, w: jax.Array,
                  bias: Optional[jax.Array] = None) -> jax.Array:
    """Causal depthwise convolution over time: ``x`` [B, T, E], ``w``
    [K, E] -> ``out[t] = sum_j w[j] * x[t - (K-1) + j]`` (``w[K-1]``
    multiplies the current step; steps before 0 are zeros). K shifted
    multiply-adds that XLA fuses with what follows (device scope
    ``ssm_conv``). Its callers: the Mamba layers of ``models/hybrid.py``
    (1.2 ms a step in ``phi4flash.train-8k``) and ``ops/cca.py``, whose
    ``[d, d]`` matrix a tap is another kernel's matter (ROADMAP G20). The
    delta-rule mixers called it until PR 47: there the trace showed XLA's
    backward of convolution + SiLU + head normalisation keeping float32
    ``[T, E]`` intermediates in HBM, 23 passes a tensor a layer-step and
    11.6% of ``kimilinear.train-32k``'s step — they run
    :func:`conv_silu_unit` now, and this function is its specification."""
    k = w.shape[0]
    with jax.named_scope("ssm_conv"):
        xp = jnp.pad(x, ((0, 0), (k - 1, 0), (0, 0)))
        t = x.shape[1]
        out = sum(xp[:, j:j + t] * w[j].astype(x.dtype) for j in range(k))
        if bias is not None:
            out = out + bias.astype(x.dtype)
        return out


# --------------------------------------------------------------------
# A delta-rule mixer's operand chain (``models/hybrid.py`` ``_delta_qkv``):
# convolution, SiLU and the head's normalisation in one pass each way.
# --------------------------------------------------------------------

_HALO = 16          # rows read of the block before: one bfloat16 tile
_STRIP_ELEMS = 1 << 17      # of a strip: the rows a kernel's loop takes
_BLOCK_BYTES = 1 << 21      # of one compute-dtype block at most
_UNIT_EPS = 1e-6


def conv_silu_unit_xla(x, w, heads: int, unit: bool, scale: float = 1.0):
    """The chain in plain jax.numpy, float32 from the cast of ``x`` to the
    cast of the result: the CPU's path, the path of a shape the kernels
    do not take, and the specification the kernels are tested against."""
    b, t, e = x.shape
    y = jax.nn.silu(causal_conv1d(x.astype(jnp.float32), w))
    if unit:
        y = y.reshape(b, t, heads, e // heads)
        y = (y * jax.lax.rsqrt(jnp.sum(jnp.square(y), -1, keepdims=True)
                               + _UNIT_EPS)).reshape(b, t, e)
    if scale != 1.0:
        y = y * scale
    return y.astype(x.dtype)


def conv_lanes(e: int, heads: int, unit: bool = True) -> Optional[int]:
    """Channels a block of the fused kernels holds, from what the code can
    see — the row's width and the head's. Under ``unit`` whole heads, at
    most a lane tile's worth of them (their sums are one ``[rows, 128]``
    array), in whole lane tiles where the row is made of them (512
    channels: four heads of 128) and else the whole row (1440 = 15 x 96);
    ``None`` where no such block exists: the caller keeps the XLA chain.
    Without ``unit`` the channels stand alone: four lane tiles where they
    divide the row, else the row."""
    if e % heads:
        return None
    tiles = 4 * _LANES
    if not unit:
        return tiles if e % tiles == 0 else e
    d = e // heads
    if e % _LANES == 0:
        for eb in range(max(d + (-d) % _LANES, tiles), e + 1, _LANES):
            if e % eb == 0 and eb % d == 0 and eb // d <= _LANES:
                return eb
    return e if heads <= _LANES else None


def conv_strip(eb: int, unit: bool = True) -> int:
    """Rows of a block the kernels' loop takes at a time, a power of two
    of at least a bfloat16 tile. Under ``unit`` up to 128 float32
    registers' worth of channels x rows, 128 rows at most (128 x 512, 64
    x 1440): a table's load into the MXU is shared by all of them, and the
    products' latency hidden behind their elementwise work. Without it 16
    registers' worth (32 x 512): a longer strip of a chain with nothing to
    wait for only spills (static schedules for a described v5e, PERF.md
    section 6 PR 47)."""
    rows = (_STRIP_ELEMS if unit else _STRIP_ELEMS >> 3) // eb
    return max(_HALO, min(128, 1 << max(rows, 1).bit_length() - 1))


def conv_block(t: int, eb: int, unit: bool = True, itemsize: int = 2) -> int:
    """Time steps a block of the fused kernels holds (the counters
    ``kda:conv_block`` / ``gdn:conv_block``): a power of two of strips,
    ``_BLOCK_BYTES`` of the compute dtype at most and 1024 steps at most
    (x, dy and dx, each twice, stay under 12 MB of VMEM), no more than the
    sequence rounded up to a strip."""
    tb = 1024
    strip = conv_strip(eb, unit)
    while tb > strip and tb * eb * itemsize > _BLOCK_BYTES:
        tb //= 2
    while tb > strip and tb // 2 >= t:
        tb //= 2
    return tb


def _segments(eb: int, d: int, dtype):
    """The 0/1 tables a block's head sums are products with. A head of
    whole lane tiles (``d % 128 == 0``): ONE ``[128, 128]`` tile of ones —
    a lane tile times it is the tile's row sums, in every lane. Any other
    head size: ``[eb, 128]`` (channel c is of head c // d; columns past
    the block's heads are zero) and its transpose, which spreads a head's
    factor back over its channels. bfloat16 tables (the compiled kernels')
    come three times over, stacked along the contraction: a float32
    operand meets them split into three bfloat16 parts side by side, one
    native product whose float32 accumulator adds the parts (as
    ``ops.kda._mm_table``)."""
    if d % _LANES == 0:
        tables = (jnp.ones((_LANES, _LANES), dtype),)
    else:
        seg = (jnp.arange(eb)[:, None] // d
               == jnp.arange(_LANES)[None, :]).astype(dtype)
        tables = (seg, seg.T)
    if jnp.dtype(dtype) == jnp.float32:
        return tables
    # packsite: region-local — constant tables of one kernel call.
    return tuple(jnp.concatenate([t] * 3, 0) for t in tables)


def _mm_seg(v, table_ref):
    """float32 ``v`` x a 0/1 table, exact to float32."""
    if table_ref.dtype == jnp.float32:
        return jnp.dot(v, table_ref[...], precision=jax.lax.Precision.HIGHEST,
                       preferred_element_type=jnp.float32)
    parts, rest = [], v
    for _ in range(3):
        parts.append(rest.astype(jnp.bfloat16))
        rest = rest - parts[-1].astype(jnp.float32)
    # packsite: region-local — one strip's parts (VMEM values).
    return jnp.dot(jnp.concatenate(parts, axis=1), table_ref[...],
                   preferred_element_type=jnp.float32)


def _head_sums(v, d, tables):
    """Each head's sum over its channels of ``v [rows, eb]``: ``[rows,
    128]``, a head a column — or, for heads of whole lane tiles, already
    ``[rows, eb]`` with a head's sum in each of its channels (the heads'
    tiles stacked under each other meet the tile of ones in one
    product)."""
    if len(tables) == 2:
        return _mm_seg(v, tables[0])
    rows, eb = v.shape
    m = d // _LANES
    tile = lambda c: v[:, c * _LANES:(c + 1) * _LANES]
    heads = [functools.reduce(jnp.add, map(tile, range(h * m, (h + 1) * m)))
             for h in range(eb // d)]
    # packsite: region-local — a strip's heads under each other.
    sums = _mm_seg(jnp.concatenate(heads, axis=0), tables[0])
    # packsite: region-local — and side by side again.
    return jnp.concatenate([sums[h * rows:(h + 1) * rows]
                            for h in range(eb // d) for _ in range(m)], 1)


def _head_spread(f, tables):
    """A factor a head, as :func:`_head_sums` leaves it, over the head's
    channels: ``[rows, eb]``."""
    return _mm_seg(f, tables[1]) if len(tables) == 2 else f


def _sigmoid(p, estimate: bool):
    """``1 / (1 + exp(-p))`` in float32. ``estimate`` (the compiled
    kernels): the reciprocal unit's estimate and one Newton step, which is
    what float32's own division compiles to, less its handling of zeros,
    infinities and NaNs — ``exp`` is held under the overflow that would
    make the step ``inf x 0``. The interpreter's estimate is a bfloat16
    one, so there the division is written out."""
    d = 1.0 + jnp.exp(jnp.minimum(-p, 80.0))
    if not estimate:
        return 1.0 / d
    r = pl.reciprocal(d, approx=True)
    return r * (2.0 - d * r)


def _conv(prev, cur, w_ref):
    """(the pre-activation of a strip, its taps ``[x[t], x[t - 1], ...,
    x[t - (k - 1)]]``), in float32: ``cur`` the strip, ``prev`` the
    ``_HALO`` rows before it."""
    k = w_ref.shape[0]
    f32 = lambda v: v.astype(jnp.float32)
    # packsite: region-local — a strip under its halo (VMEM values).
    xf = jnp.concatenate([f32(prev)[_HALO - _SUB:], f32(cur)], axis=0)
    taps = [xf[_SUB:]] + [pltpu.roll(xf, s, 0)[_SUB:] for s in range(1, k)]
    # summed as ``causal_conv1d`` sums: the oldest tap first
    return functools.reduce(jnp.add, (
        w_ref[pl.ds(j, 1), :] * taps[k - 1 - j] for j in range(k))), taps


def _strips(x_ref, halo_ref, first, strip: int, one, carry, reverse=False):
    """``carry = one(prev, start, carry)`` over a block's strips, ``prev``
    the ``_HALO`` rows of ``x`` before the strip at ``start``: the block's
    own, or — the first strip's — ``halo_ref``'s, zeros where the block is
    the sequence's first. ``reverse`` walks the strips last to first."""
    n = x_ref.shape[0] // strip
    halo = halo_ref[...]

    def head(carry):
        return one(jnp.where(first, jnp.zeros_like(halo), halo), 0, carry)

    def body(r, carry):
        start = pl.multiple_of((n - r if reverse else r) * strip, strip)
        return one(x_ref[pl.ds(start - _HALO, _HALO), :], start, carry)
    if reverse:
        return head(jax.lax.fori_loop(1, n, body, carry))
    return jax.lax.fori_loop(1, n, body, head(carry))


def delta_conv_fwd(x_ref, halo_ref, w_ref, *rest, strip: int, d: int,
                   unit: bool, scale: float, estimate: bool):
    """One (batch, channel block, time block) cell of the forward: strip
    by strip ``silu(conv(x))`` and, with ``unit``, each head's channels
    over the root of their sum of squares, times ``scale``. The ``k - 1``
    rows before the block are the last of ``halo_ref``, the 16 rows of
    ``x`` before it (zeros before step 0)."""
    *tables, o_ref = rest

    def one(prev, start, carry):
        p, _ = _conv(prev, x_ref[pl.ds(start, strip), :], w_ref)
        y = p * _sigmoid(p, estimate)
        if unit:
            y = y * _head_spread(jax.lax.rsqrt(
                _head_sums(y * y, d, tables) + _UNIT_EPS) * scale, tables)
        elif scale != 1.0:
            y = y * scale
        o_ref[pl.ds(start, strip), :] = y.astype(o_ref.dtype)
        return carry
    _strips(x_ref, halo_ref, pl.program_id(2) == 0, strip, one, 0)


def delta_conv_bwd(x_ref, halo_ref, w_ref, dy_ref, *rest, strip: int, d: int,
                   unit: bool, scale: float, estimate: bool):
    """The same cell in reverse (the index maps walk the time blocks last
    to first, the loop a block's strips): the pre-activation again from
    ``x``, ``dy`` through the normalisation's and SiLU's derivatives to
    the pre-activation's cotangent ``dp``; ``dx[t] = sum_s w[k-1-s]
    dp[t + s]`` reads the first rows of the strip after, carried in
    registers across strips and in ``nxt_scr`` across blocks; ``dw``
    gathers in its resident block, eight partial rows a tap (summed
    outside)."""
    *tables, dx_ref, dw_ref, nxt_scr = rest
    k = w_ref.shape[0]
    i = pl.program_id(2)

    @pl.when(i == 0)
    def _init():
        nxt_scr[...] = jnp.zeros_like(nxt_scr)
        dw_ref[...] = jnp.zeros_like(dw_ref)

    def one(prev, start, nxt):
        p, taps = _conv(prev, x_ref[pl.ds(start, strip), :], w_ref)
        sig = _sigmoid(p, estimate)
        y = p * sig
        dy = dy_ref[pl.ds(start, strip), :].astype(jnp.float32)
        if unit:
            # n = scale y r, r = (sum y^2 + eps)^-1/2 a head:
            # dy = scale r (dn - y r^2 sum(dn y))
            r = jax.lax.rsqrt(_head_sums(y * y, d, tables) + _UNIT_EPS)
            c = r * r * _head_sums(dy * y, d, tables)
            dy = _head_spread(r * scale, tables) * (
                dy - y * _head_spread(c, tables))
        elif scale != 1.0:
            dy = dy * scale
        dp = dy * (sig + y * (1.0 - sig))        # silu' = s + p s (1 - s)
        for s in range(k):
            rows = pl.ds((k - 1 - s) * _SUB, _SUB)
            dw_ref[rows, :] += (dp * taps[s]).reshape(
                strip // _SUB, _SUB, -1).sum(0)
        # packsite: region-local — a strip over the rows after it.
        dpf = jnp.concatenate([dp, nxt], axis=0)
        dx = functools.reduce(jnp.add, (
            w_ref[pl.ds(k - 1 - s, 1), :] * (
                dp if s == 0
                else pltpu.roll(dpf, strip + _SUB - s, 0)[:strip])
            for s in range(k)))
        dx_ref[pl.ds(start, strip), :] = dx.astype(dx_ref.dtype)
        return dp[:_SUB]
    nxt_scr[...] = _strips(x_ref, halo_ref, i == pl.num_programs(2) - 1,
                           strip, one, nxt_scr[...], reverse=True)


def _conv_specs(eb, tb, order):
    """Block specs over ``[B, T, E]`` for the grid (batch, channel block,
    time block): a block of ``x``, the ``_HALO`` rows before it, a
    ``[rows, eb]`` parameter, a table. ``order`` maps the grid's time
    index to the block's."""
    seq = pl.BlockSpec((None, tb, eb),
                       lambda bi, ji, ti: (bi, order(ti), ji))
    halo = pl.BlockSpec((None, _HALO, eb), lambda bi, ji, ti: (
        bi, jnp.maximum(order(ti) * (tb // _HALO) - 1, 0), ji))
    rows = lambda n: pl.BlockSpec((n, eb), lambda bi, ji, ti: (0, ji))
    whole = lambda a: pl.BlockSpec(a.shape, lambda bi, ji, ti: (0, 0))
    return seq, halo, rows, whole


def _conv_call(kernel, d, unit, scale, eb, tb, interpret):
    """What the two calls share: the kernel closed over its statics, and
    the tables."""
    tables = _segments(eb, d, jnp.float32 if interpret else jnp.bfloat16) \
        if unit else ()
    return functools.partial(
        kernel, strip=min(conv_strip(eb, unit), tb), d=d, unit=unit,
        scale=scale, estimate=not interpret), tables


def _conv_fwd_pallas(x, w, d, unit, scale, eb, tb, interpret):
    b, t, e = x.shape
    seq, halo, rows, whole = _conv_specs(eb, tb, lambda ti: ti)
    kernel, tables = _conv_call(delta_conv_fwd, d, unit, scale, eb, tb,
                                interpret)
    with jax.named_scope("delta_conv_fwd"):
        return pl.pallas_call(
            kernel, grid=(b, e // eb, t // tb),
            in_specs=[seq, halo, rows(w.shape[0]), *map(whole, tables)],
            out_specs=seq, out_shape=jax.ShapeDtypeStruct(x.shape, x.dtype),
            compiler_params=_SEQ, interpret=interpret, name="delta_conv_fwd",
        )(x, x, w, *tables)


def _conv_bwd_pallas(x, w, dy, d, unit, scale, eb, tb, interpret):
    b, t, e = x.shape
    k = w.shape[0]
    nt = t // tb
    seq, halo, rows, whole = _conv_specs(eb, tb, lambda ti: nt - 1 - ti)
    kernel, tables = _conv_call(delta_conv_bwd, d, unit, scale, eb, tb,
                                interpret)
    with jax.named_scope("delta_conv_bwd"):
        dx, dw = pl.pallas_call(
            kernel, grid=(b, e // eb, nt),
            in_specs=[seq, halo, rows(k), seq, *map(whole, tables)],
            out_specs=(seq, pl.BlockSpec((None, k * _SUB, eb),
                                         lambda bi, ji, ti: (bi, 0, ji))),
            out_shape=(jax.ShapeDtypeStruct(x.shape, x.dtype),
                       jax.ShapeDtypeStruct((b, k * _SUB, e), jnp.float32)),
            scratch_shapes=[pltpu.VMEM((_SUB, eb), jnp.float32)],
            compiler_params=_SEQ, interpret=interpret, name="delta_conv_bwd",
        )(x, x, w, dy, *tables)
        return dx, dw.reshape(b, k, _SUB, e).sum((0, 2))


@functools.partial(jax.custom_vjp, nondiff_argnums=(2, 3, 4, 5, 6, 7))
def _conv_unit(x, w, d, unit, scale, eb, tb, interpret):
    return _conv_fwd_pallas(x, w, d, unit, scale, eb, tb, interpret)


def _conv_unit_fwd(x, w, d, unit, scale, eb, tb, interpret):
    return _conv_fwd_pallas(x, w, d, unit, scale, eb, tb, interpret), (x, w)


def _conv_unit_bwd(d, unit, scale, eb, tb, interpret, res, dy):
    x, w = res
    return _conv_bwd_pallas(x, w, dy, d, unit, scale, eb, tb, interpret)


_conv_unit.defvjp(_conv_unit_fwd, _conv_unit_bwd)


def conv_silu_unit(x: jax.Array, w: jax.Array, *, heads: int, unit: bool,
                   scale: float = 1.0, block: Optional[int] = None,
                   interpret: Optional[bool] = None) -> jax.Array:
    """``silu(causal_conv1d(x, w))`` over ``x`` ``[B, T, E]`` in the
    compute dtype and ``w`` ``[K, E]`` float32 and, with ``unit``, each of
    the ``heads``' ``E / heads`` channels over ``sqrt(their sum of squares
    + 1e-6)``; times ``scale``; in ``x``'s dtype. Everything between the
    two casts is float32.

    On a TPU (``interpret=None``) and under the interpreter (``True``) one
    kernel each way (``delta_conv_fwd``, ``delta_conv_bwd``; residuals
    ``x`` and ``w``): ``x`` is read once and the result written once, no
    float32 ``[T, E]`` array reaches HBM, ``dw`` gathers in VMEM over the
    time axis. A head's sum is a product with a 0/1 table — whatever the
    head's size — so the block is whole heads wide (:func:`conv_lanes`)
    and ``block`` steps long (:func:`conv_block`); a ``T`` off the block is
    zero-padded at the end. Elsewhere, and for a shape with no such block
    (then with the ``kernel fallback`` warning on a TPU), the chain in
    plain jax.numpy (:func:`conv_silu_unit_xla`)."""
    b, t, e = x.shape
    if w.ndim != 2 or w.shape[1] != e or e % heads:
        raise ValueError(f"conv_silu_unit shapes: x {x.shape} w {w.shape} "
                         f"heads {heads}")
    plan = conv_plan(t, e, heads, unit, w.shape[0], x.dtype.itemsize,
                     interpret)
    if plan is None:
        return conv_silu_unit_xla(x, w, heads, unit, scale)
    interpret, eb, tb = plan
    tb = block or tb
    if tb % min(conv_strip(eb, unit), tb) or tb % _HALO:
        raise ValueError(f"conv_silu_unit block {tb}: whole strips of "
                         f"{conv_strip(eb, unit)} steps, or one bfloat16 "
                         f"tile's multiple under that")
    pad = (-t) % tb
    if pad:
        x = jnp.pad(x, ((0, 0), (0, pad), (0, 0)))
    y = _conv_unit(x, w.astype(jnp.float32), e // heads, unit, float(scale),
                   eb, tb, interpret)
    return y[:, :t] if pad else y


def conv_plan(t: int, e: int, heads: int, unit: bool, taps: int,
              itemsize: int = 2, interpret: Optional[bool] = None):
    """``(interpret, channels a block, steps a block)`` where
    :func:`conv_silu_unit` runs its kernels over ``[B, t, e]``, ``None``
    where it runs the XLA chain: off a TPU without ``interpret``, and for
    a shape with no block of whole heads (warned on a TPU)."""
    if interpret is None and jax.default_backend() == "tpu":
        interpret = False
    if interpret is None:
        return None
    eb = conv_lanes(e, heads, unit)
    if eb is None or taps - 1 > _SUB:
        _warn_fallback(f"conv_silu_unit has no block of whole heads for "
                       f"E={e}, heads={heads}, K={taps}")
        return None
    return interpret, eb, conv_block(t, eb, unit, itemsize)


def _round_state(h, state_dtype):
    """``h`` (f32) rounded to ``state_dtype`` and back. bfloat16 by integer
    arithmetic on the float32 bits (round to nearest even), so that the
    kernel needs no half-tile bfloat16 vector."""
    if jnp.dtype(state_dtype) == jnp.float32:
        return h
    if jnp.dtype(state_dtype) != jnp.bfloat16:
        raise ValueError(f"state_dtype {state_dtype}: float32 or bfloat16")
    u = jax.lax.bitcast_convert_type(h, jnp.int32)
    u = (u + 0x7FFF + ((u >> 16) & 1)) & jnp.int32(-65536)
    # Straight through for autodiff (the XLA twin's backward), as the
    # kernel's hand-written backward passes it.
    return h + jax.lax.stop_gradient(
        jax.lax.bitcast_convert_type(u, jnp.float32) - h)


def n_chunks(t: int, chunk: int) -> int:
    """Chunks a scan over ``t`` steps runs (the counter ``ssm:chunks``)."""
    return -(-t // chunk)


# --------------------------------------------------------------------
# XLA twin: the same chunked algorithm in plain jax (lax.scan inside a
# chunk, lax.scan over chunks). The CPU's path, and the specification the
# kernels are tested against.
# --------------------------------------------------------------------

def _chunk_xla(h0, x, dt, bm, cm, a, d, state_dtype):
    """One chunk of one sequence: ``h0`` [E, N]; ``x``, ``dt`` [L, E];
    ``bm``, ``cm`` [L, N] -> (y [L, E], state after the chunk)."""
    def step(h, inp):
        x_t, dt_t, b_t, c_t = inp
        h = jnp.exp(dt_t[:, None] * a) * h \
            + (dt_t * x_t)[:, None] * b_t[None, :]
        h = _round_state(h, state_dtype)
        return h, h @ c_t + d * x_t
    h, y = jax.lax.scan(step, h0, (x, dt, bm, cm))
    return y, h


def _split(x, nc, chunk):
    """[B, T, ...] -> [nc, B, chunk, ...] (T already a multiple)."""
    b = x.shape[0]
    return jnp.moveaxis(x.reshape(b, nc, chunk, *x.shape[2:]), 1, 0)


def _fwd_xla(x, dt, a, bm, cm, d, chunk, state_dtype):
    b, t, e = x.shape
    nc = t // chunk
    one = jax.vmap(functools.partial(_chunk_xla, state_dtype=state_dtype),
                   in_axes=(0, 0, 0, 0, 0, None, None))

    def body(h, inp):
        y, h_next = one(h, *inp, a, d)
        return h_next, (y, h)
    h0 = jnp.zeros((b, e, a.shape[1]), jnp.float32)
    _, (y, hb) = jax.lax.scan(
        body, h0, tuple(_split(v, nc, chunk) for v in (x, dt, bm, cm)))
    return jnp.moveaxis(y, 0, 1).reshape(b, t, e), hb   # hb [nc, B, E, N]


def _bwd_xla(x, dt, a, bm, cm, d, hb, dy, chunk, state_dtype):
    b, t, e = x.shape
    nc = t // chunk
    one = jax.vmap(functools.partial(_chunk_xla, state_dtype=state_dtype),
                   in_axes=(0, 0, 0, 0, 0, None, None))

    def body(carry, inp):
        g_h, da, dd = carry
        h0, xc, dtc, bc, cc, dyc = inp
        _, vjp = jax.vjp(one, h0, xc, dtc, bc, cc, a, d)
        g_h0, gx, gdt, gb, gc, ga, gd = vjp((dyc, g_h))
        return (g_h0, da + ga, dd + gd), (gx, gdt, gb, gc)
    init = (jnp.zeros_like(hb[0]), jnp.zeros_like(a), jnp.zeros_like(d))
    (_, da, dd), outs = jax.lax.scan(
        body, init,
        (hb, *(_split(v, nc, chunk) for v in (x, dt, bm, cm, dy))),
        reverse=True)
    gx, gdt, gb, gc = (jnp.moveaxis(o, 0, 1).reshape(b, t, -1)
                       for o in outs)
    return gx, gdt, da, gb, gc, dd


# --------------------------------------------------------------------
# Pallas kernels.
# --------------------------------------------------------------------

def ssm_scan_fwd(x_ref, dt_ref, b_ref, c_ref, a_ref, d_ref, y_ref, hb_ref,
                 h_scr, *, chunk: int, n_state: int, state_dtype):
    """One (batch, channel block, chunk) cell: ``chunk`` steps of the
    recurrence for 1024 channels. Writes y and the state the chunk
    STARTED from (what the backward recomputes the chunk from)."""
    @pl.when(pl.program_id(2) == 0)
    def _init():
        h_scr[...] = jnp.zeros_like(h_scr)

    hb_ref[...] = h_scr[...]
    dsk = d_ref[...]

    def body(t, h):
        dt, x = dt_ref[t], x_ref[t]
        dx = dt * x
        y = dsk * x
        out = []
        for n in range(n_state):
            hn = jnp.exp(dt * a_ref[n]) * h[n] + dx * b_ref[t, n]
            hn = _round_state(hn, state_dtype)
            y = y + hn * c_ref[t, n]
            out.append(hn)
        y_ref[t] = y
        return tuple(out)

    h = jax.lax.fori_loop(0, chunk, body,
                          tuple(h_scr[n] for n in range(n_state)))
    for n in range(n_state):
        h_scr[n] = h[n]


def ssm_scan_bwd(x_ref, dt_ref, b_ref, c_ref, a_ref, d_ref, hb_ref, dy_ref,
                 dx_ref, ddt_ref, db_ref, dc_ref, da_ref, dd_ref,
                 hs_scr, g_scr, da_scr, dd_scr, *, chunk: int, n_state: int,
                 state_dtype):
    """The same cell in reverse (the index maps walk the chunks last to
    first): recompute the chunk's states into VMEM (``hs_scr[t + 1]`` =
    h_t, ``hs_scr[0]`` = the saved boundary), then run the adjoint
    recurrence ``G_t = C_t dy_t + a_{t+1} G_{t+1}`` backwards. ``dA`` and
    ``dD`` accumulate across the whole time axis in scratch and are
    written by the last cell; the rounding of a bfloat16 state is passed
    straight through."""
    c = pl.program_id(2)
    last = pl.num_programs(2) - 1

    @pl.when(c == 0)
    def _init():
        g_scr[...] = jnp.zeros_like(g_scr)
        da_scr[...] = jnp.zeros_like(da_scr)
        dd_scr[...] = jnp.zeros_like(dd_scr)

    hs_scr[0] = hb_ref[...]

    def recompute(t, h):
        dt, x = dt_ref[t], x_ref[t]
        dx = dt * x
        out = []
        for n in range(n_state):
            hn = jnp.exp(dt * a_ref[n]) * h[n] + dx * b_ref[t, n]
            hn = _round_state(hn, state_dtype)
            hs_scr[t + 1, n] = hn
            out.append(hn)
        return tuple(out)

    jax.lax.fori_loop(0, chunk, recompute,
                      tuple(hb_ref[n] for n in range(n_state)))
    dsk = d_ref[...]

    def body(i, g):
        t = chunk - 1 - i
        dt, x, dy = dt_ref[t], x_ref[t], dy_ref[t]
        dx = dt * x
        ddx = jnp.zeros_like(x)
        ddt = jnp.zeros_like(x)
        out = []
        for n in range(n_state):
            a_n = a_ref[n]
            grad_h = g[n] + dy * c_ref[t, n]            # dL/dh_t
            dc_ref[t, n] = jnp.sum(dy * hs_scr[t + 1, n])
            decay = jnp.exp(dt * a_n)
            darg = grad_h * hs_scr[t, n] * decay        # dL/d(dt * A)
            ddt = ddt + darg * a_n
            da_scr[n] = da_scr[n] + darg * dt
            ddx = ddx + grad_h * b_ref[t, n]
            db_ref[t, n] = jnp.sum(grad_h * dx)
            out.append(decay * grad_h)
        dx_ref[t] = ddx * dt + dy * dsk
        ddt_ref[t] = ddt + ddx * x
        dd_scr[...] = dd_scr[...] + dy * x
        return tuple(out)

    g = jax.lax.fori_loop(0, chunk, body,
                          tuple(g_scr[n] for n in range(n_state)))
    for n in range(n_state):
        g_scr[n] = g[n]

    @pl.when(c == last)
    def _flush():
        da_ref[...] = da_scr[...]
        dd_ref[...] = dd_scr[...]


def _blocks(x, e_pad):
    """[B, T, E] -> [B, T, J, 8, 128] (channels zero-padded to J x 1024:
    a padded channel has dt = 0 and x = 0, so it stays 0 everywhere)."""
    b, t, e = x.shape
    if e_pad != e:
        x = jnp.pad(x, ((0, 0), (0, 0), (0, e_pad - e)))
    return x.reshape(b, t, e_pad // _EB, _SUB, _LANES)


def _param_blocks(a, d, e_pad):
    """A [E, N] -> [N, J, 8, 128]; D [E] -> [J, 8, 128]."""
    e, n = a.shape
    a = jnp.pad(a, ((0, e_pad - e), (0, 0))).T
    d = jnp.pad(d, (0, e_pad - e))
    j = e_pad // _EB
    return a.reshape(n, j, _SUB, _LANES), d.reshape(j, _SUB, _LANES)


_SEQ = pltpu.CompilerParams(
    dimension_semantics=("parallel", "parallel", "arbitrary"),
    vmem_limit_bytes=48 * 1024 * 1024)


def _fwd_pallas(x, dt, a, bm, cm, d, chunk, state_dtype, interpret):
    b, t, e = x.shape
    n = a.shape[1]
    e_pad = e + (-e) % _EB
    j, nc = e_pad // _EB, t // chunk
    a5, d5 = _param_blocks(a, d, e_pad)
    seq = pl.BlockSpec((None, chunk, None, _SUB, _LANES),
                       lambda bi, ji, ci: (bi, ci, ji, 0, 0))
    scal = pl.BlockSpec((chunk, n), lambda bi, ji, ci: (bi * nc + ci, 0),
                        memory_space=pltpu.SMEM)
    y5, hb = pl.pallas_call(
        functools.partial(ssm_scan_fwd, chunk=chunk, n_state=n,
                          state_dtype=state_dtype),
        grid=(b, j, nc),
        in_specs=[
            seq, seq, scal, scal,
            pl.BlockSpec((n, None, _SUB, _LANES),
                         lambda bi, ji, ci: (0, ji, 0, 0)),
            pl.BlockSpec((None, _SUB, _LANES),
                         lambda bi, ji, ci: (ji, 0, 0)),
        ],
        out_specs=(
            seq,
            pl.BlockSpec((None, None, None, n, _SUB, _LANES),
                         lambda bi, ji, ci: (bi, ci, ji, 0, 0, 0)),
        ),
        out_shape=(
            jax.ShapeDtypeStruct((b, t, j, _SUB, _LANES), jnp.float32),
            jax.ShapeDtypeStruct((b, nc, j, n, _SUB, _LANES), jnp.float32),
        ),
        scratch_shapes=[pltpu.VMEM((n, _SUB, _LANES), jnp.float32)],
        compiler_params=_SEQ, interpret=interpret, name="ssm_scan_fwd",
    )(_blocks(x, e_pad), _blocks(dt, e_pad), bm.reshape(b * t, n),
      cm.reshape(b * t, n), a5, d5)
    return y5.reshape(b, t, e_pad)[..., :e], hb


def _bwd_pallas(x, dt, a, bm, cm, d, hb, dy, chunk, state_dtype, interpret):
    b, t, e = x.shape
    n = a.shape[1]
    e_pad = e + (-e) % _EB
    j, nc = e_pad // _EB, t // chunk
    a5, d5 = _param_blocks(a, d, e_pad)
    rev = lambda ci: nc - 1 - ci
    seq = pl.BlockSpec((None, chunk, None, _SUB, _LANES),
                       lambda bi, ji, ci: (bi, rev(ci), ji, 0, 0))
    scal = pl.BlockSpec((chunk, n),
                        lambda bi, ji, ci: (bi * nc + rev(ci), 0),
                        memory_space=pltpu.SMEM)
    scal_out = pl.BlockSpec(
        (chunk, n), lambda bi, ji, ci: ((bi * j + ji) * nc + rev(ci), 0),
        memory_space=pltpu.SMEM)
    a_spec = pl.BlockSpec((n, None, _SUB, _LANES),
                          lambda bi, ji, ci: (0, ji, 0, 0))
    d_spec = pl.BlockSpec((None, _SUB, _LANES),
                          lambda bi, ji, ci: (ji, 0, 0))
    dx5, ddt5, db, dc, da5, dd5 = pl.pallas_call(
        functools.partial(ssm_scan_bwd, chunk=chunk, n_state=n,
                          state_dtype=state_dtype),
        grid=(b, j, nc),
        in_specs=[
            seq, seq, scal, scal, a_spec, d_spec,
            pl.BlockSpec((None, None, None, n, _SUB, _LANES),
                         lambda bi, ji, ci: (bi, rev(ci), ji, 0, 0, 0)),
            seq,
        ],
        out_specs=(
            seq, seq, scal_out, scal_out,
            pl.BlockSpec((None, n, None, _SUB, _LANES),
                         lambda bi, ji, ci: (bi, 0, ji, 0, 0)),
            pl.BlockSpec((None, None, _SUB, _LANES),
                         lambda bi, ji, ci: (bi, ji, 0, 0)),
        ),
        out_shape=(
            jax.ShapeDtypeStruct((b, t, j, _SUB, _LANES), jnp.float32),
            jax.ShapeDtypeStruct((b, t, j, _SUB, _LANES), jnp.float32),
            jax.ShapeDtypeStruct((b * j * t, n), jnp.float32),
            jax.ShapeDtypeStruct((b * j * t, n), jnp.float32),
            jax.ShapeDtypeStruct((b, n, j, _SUB, _LANES), jnp.float32),
            jax.ShapeDtypeStruct((b, j, _SUB, _LANES), jnp.float32),
        ),
        scratch_shapes=[
            pltpu.VMEM((chunk + 1, n, _SUB, _LANES), jnp.float32),   # h_t
            pltpu.VMEM((n, _SUB, _LANES), jnp.float32),        # adjoint
            pltpu.VMEM((n, _SUB, _LANES), jnp.float32),        # dA
            pltpu.VMEM((_SUB, _LANES), jnp.float32),           # dD
        ],
        compiler_params=_SEQ, interpret=interpret, name="ssm_scan_bwd",
    )(_blocks(x, e_pad), _blocks(dt, e_pad), bm.reshape(b * t, n),
      cm.reshape(b * t, n), a5, d5, hb, _blocks(dy, e_pad))
    unblock = lambda v: v.reshape(b, t, e_pad)[..., :e]
    da = da5.sum(0).reshape(n, e_pad).T[:e]
    dd = dd5.sum(0).reshape(e_pad)[:e]
    return (unblock(dx5), unblock(ddt5), da,
            db.reshape(b, j, t, n).sum(1), dc.reshape(b, j, t, n).sum(1), dd)


# --------------------------------------------------------------------
# The differentiable entry.
# --------------------------------------------------------------------

@functools.partial(jax.custom_vjp, nondiff_argnums=(6, 7, 8))
def _scan(x, dt, a, bm, cm, d, chunk, state_dtype, interpret):
    return _scan_fwd(x, dt, a, bm, cm, d, chunk, state_dtype, interpret)[0]


def _scan_fwd(x, dt, a, bm, cm, d, chunk, state_dtype, interpret):
    with jax.named_scope("ssm_scan_fwd"):
        if interpret is None:
            y, hb = _fwd_xla(x, dt, a, bm, cm, d, chunk, state_dtype)
        else:
            y, hb = _fwd_pallas(x, dt, a, bm, cm, d, chunk, state_dtype,
                                interpret)
    return y, (x, dt, a, bm, cm, d, hb)


def _scan_bwd(chunk, state_dtype, interpret, res, dy):
    x, dt, a, bm, cm, d, hb = res
    with jax.named_scope("ssm_scan_bwd"):
        if interpret is None:
            return _bwd_xla(x, dt, a, bm, cm, d, hb, dy, chunk, state_dtype)
        return _bwd_pallas(x, dt, a, bm, cm, d, hb, dy, chunk, state_dtype,
                           interpret)


_scan.defvjp(_scan_fwd, _scan_bwd)


def selective_scan(x: jax.Array, dt: jax.Array, a: jax.Array, bm: jax.Array,
                   cm: jax.Array, d: jax.Array, *, chunk: int = 64,
                   state_dtype=jnp.float32,
                   interpret: Optional[bool] = None) -> jax.Array:
    """The recurrence of the module docstring over ``x``, ``dt`` [B, T, E],
    ``a`` [E, N] (negative), ``bm``, ``cm`` [B, T, N], ``d`` [E]; returns
    ``y`` [B, T, E] in float32. Everything is computed in float32 whatever
    the inputs' dtype.

    ``chunk`` steps share one saved state: memory for the backward is
    ``T / chunk`` states instead of ``T``, and one chunk's states are
    recomputed at a time. A ``T`` off the chunk is zero-padded at the end
    (``dt = 0`` leaves the state as it is). ``interpret=None`` picks the
    Pallas kernels on a TPU and the XLA twin elsewhere; ``True`` runs the
    kernel bodies in the Pallas interpreter."""
    if x.shape != dt.shape or bm.shape != cm.shape \
            or a.shape != (x.shape[2], bm.shape[2]):
        raise ValueError(
            f"selective_scan shapes: x {x.shape} dt {dt.shape} a {a.shape} "
            f"b {bm.shape} c {cm.shape}")
    f32 = lambda v: v.astype(jnp.float32)
    x, dt, a, bm, cm, d = (f32(v) for v in (x, dt, a, bm, cm, d))
    dt = _round_state(dt, state_dtype)
    t = x.shape[1]
    chunk = min(chunk, t + (-t) % _SUB)
    pad = (-t) % chunk
    if pad:
        x, dt, bm, cm = (jnp.pad(v, ((0, 0), (0, pad), (0, 0)))
                         for v in (x, dt, bm, cm))
    if interpret is None and jax.default_backend() == "tpu":
        interpret = False
    if interpret is not None and a.shape[1] > _MAX_STATE:
        _warn_fallback(f"selective_scan unrolls over the state index; "
                       f"N={a.shape[1]} > {_MAX_STATE}")
        interpret = None
    y = _scan(x, dt, a, bm, cm, d, chunk, jnp.dtype(state_dtype), interpret)
    return y[:, :t] if pad else y
