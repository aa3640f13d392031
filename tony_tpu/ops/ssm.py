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
    multiply-adds that XLA fuses with what follows: there is nothing for
    a kernel to win on an elementwise chain (device scope ``ssm_conv``)."""
    k = w.shape[0]
    with jax.named_scope("ssm_conv"):
        xp = jnp.pad(x, ((0, 0), (k - 1, 0), (0, 0)))
        t = x.shape[1]
        out = sum(xp[:, j:j + t] * w[j].astype(x.dtype) for j in range(k))
        if bias is not None:
            out = out + bias.astype(x.dtype)
        return out


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
