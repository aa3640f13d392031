"""A grouped matmul over ragged row groups: rows sorted by group, group
``g`` taking ``sizes[g]`` consecutive rows of ``lhs [M, K]`` through its own
``rhs[g] [K, N]``. What a dropless expert layer runs its experts as
(``models.moe.DroplessMoE``).

On the TPU three Pallas kernels that **walk the groups**: the grid is one
step a (group, row tile) pair, found from ``sizes`` on the device and
handed to the kernel as prefetched scalars, so a call costs the rows that
are there and one read of each group's matrix — not the row buffer's worst
case, which is what ``jax.lax.ragged_dot`` costs on the v5e (PERF.md §5).
A group of no rows is visited once all the same (its matrix is read and
nothing kept; the weight gradient has to write its zeros anyway): at the
dropless layer's sizes a call is bound by reading the matrices, so a call
costs the same whether an expert got a few rows or none, and a step's time
does not follow which experts the routing lets starve. Rows past the last
group are **not written** (forward and backward): callers name them zero,
as with XLA's kernel.

* ``%moe_gmm``    ``out[rows of g] = lhs[rows of g] @ rhs[g]``
* ``%moe_gmm_t``  the same against ``rhs[g]`` transposed (the backward
  into ``lhs``)
* ``%moe_tgmm``   ``out[g] = lhs[rows of g]^T @ dout[rows of g]`` (the
  backward into ``rhs``)

Elsewhere (the CPU tests' default) the call is ``jax.lax.ragged_dot``;
``interpret=True`` runs the kernels' own bodies.
"""

from __future__ import annotations

import functools
import warnings
from typing import Optional

import jax
import jax.numpy as jnp
from jax.experimental import pallas as pl
from jax.experimental.pallas import tpu as pltpu

from tony_tpu.ops.attention import KernelFallbackWarning

# Rows a grid step takes. A tile that straddles two groups is computed once
# for each, so smaller tiles waste less where groups are small; 128 fills
# the v5e's MXU rows.
ROW_TILE = 128
# Widest output block (columns); the contracted dimension is taken whole.
COL_TILE = 1024


def _row_tile(m: int) -> int:
    for tm in (ROW_TILE, 64, 32, 16, 8):
        if m % tm == 0:
            return tm
    return 0


def _col_tile(n: int) -> int:
    if n <= COL_TILE:
        return n
    for tn in range(COL_TILE, 0, -128):
        if n % tn == 0:
            return tn
    return n


def group_visits(sizes, m: int, tm: int):
    """The grid's steps for ``sizes [G]`` over ``m`` rows in tiles of
    ``tm``: ``(starts [G], ends [G], group [V], tile [V], n)`` — step ``i <
    n`` works on row tile ``tile[i]`` for group ``group[i]``, groups in
    order and a group's tiles in order, so a tile two groups share is
    visited by one right after the other; a group of no rows gets one
    step, on the tile where it would start. ``V = m / tm + G - 1`` bounds
    ``n``."""
    g = sizes.shape[0]
    tiles_m = m // tm
    ends = jnp.cumsum(sizes).astype(jnp.int32)
    starts = ends - sizes
    first = jnp.minimum(starts // tm, tiles_m - 1)
    last = jnp.maximum(ends - 1, 0) // tm
    count = jnp.where(sizes > 0, last - first + 1, 1)
    total = tiles_m + g - 1
    group = jnp.repeat(jnp.arange(g, dtype=jnp.int32), count,
                       total_repeat_length=total)
    begin = jnp.cumsum(count) - count
    tile = first[group] + jnp.arange(total, dtype=jnp.int32) - begin[group]
    return (starts, ends, group,
            jnp.clip(tile, 0, tiles_m - 1).astype(jnp.int32),
            count.sum().astype(jnp.int32))


def _rows_of_group(starts, ends, group, tile, step, tm):
    """[tm, 1] mask: which rows of this step's tile are its group's."""
    g = group[step]
    row = tile[step] * tm + jax.lax.broadcasted_iota(jnp.int32, (tm, 1), 0)
    return (row >= starts[g]) & (row < ends[g])


def _gmm_kernel(starts, ends, group, tile, lhs_ref, rhs_ref, out_ref, *,
                tm: int, transposed: bool):
    """One (group, row tile) step of one column block: the whole tile
    through the group's matrix, its own rows kept (none, for a group of no
    rows); the tile's other rows keep what the groups before left there."""
    mine = _rows_of_group(starts, ends, group, tile, pl.program_id(1), tm)
    dims = (((1,), (1 if transposed else 0,)), ((), ()))
    acc = jax.lax.dot_general(lhs_ref[...], rhs_ref[...], dims,
                              preferred_element_type=jnp.float32)
    out_ref[...] = jnp.where(mine, acc, out_ref[...].astype(jnp.float32)
                             ).astype(out_ref.dtype)


def _gmm_pallas(lhs, rhs, sizes, transposed: bool, interpret: bool):
    m, kc = lhs.shape
    n = rhs.shape[1] if transposed else rhs.shape[2]
    tm, tn = _row_tile(m), _col_tile(n)
    starts, ends, group, tile, steps = group_visits(sizes, m, tm)
    if transposed:
        rhs_spec = pl.BlockSpec((None, tn, kc),
                                lambda j, i, s, e, g, t: (g[i], j, 0))
    else:
        rhs_spec = pl.BlockSpec((None, kc, tn),
                                lambda j, i, s, e, g, t: (g[i], 0, j))
    with jax.named_scope("moe_gmm_t" if transposed else "moe_gmm"):
        return pl.pallas_call(
            functools.partial(_gmm_kernel, tm=tm, transposed=transposed),
            grid_spec=pltpu.PrefetchScalarGridSpec(
                num_scalar_prefetch=4,
                grid=(n // tn, steps),
                in_specs=[
                    pl.BlockSpec((tm, kc),
                                 lambda j, i, s, e, g, t: (t[i], 0)),
                    rhs_spec],
                out_specs=pl.BlockSpec(
                    (tm, tn), lambda j, i, s, e, g, t: (t[i], j))),
            out_shape=jax.ShapeDtypeStruct((m, n), lhs.dtype),
            compiler_params=pltpu.CompilerParams(
                dimension_semantics=("parallel", "arbitrary")),
            interpret=interpret,
        )(starts, ends, group, tile, lhs, rhs)


def _tgmm_kernel(starts, ends, group, tile, lhs_ref, dout_ref, out_ref,
                 acc_ref, *, tm: int):
    """One (group, row tile) step of one output block: the group's rows of
    the tile, transposed, times the same rows of ``dout``, added up over
    the group's steps. The other rows are **selected** away on both sides
    (they may hold anything), never multiplied by zero."""
    step, steps = pl.program_id(2), pl.num_programs(2)
    g = group[step]

    @pl.when((step == 0) | (group[jnp.maximum(step - 1, 0)] != g))
    def _first():
        acc_ref[...] = jnp.zeros_like(acc_ref)

    mine = _rows_of_group(starts, ends, group, tile, step, tm)
    lhs = jnp.where(mine, lhs_ref[...], 0)
    dout = jnp.where(mine, dout_ref[...], 0)
    acc_ref[...] += jax.lax.dot_general(
        lhs, dout, (((0,), (0,)), ((), ())),
        preferred_element_type=jnp.float32)

    @pl.when((step == steps - 1)
             | (group[jnp.minimum(step + 1, steps - 1)] != g))
    def _last():
        out_ref[...] = acc_ref[...].astype(out_ref.dtype)


def _tgmm_pallas(lhs, dout, sizes, out_dtype, interpret: bool):
    m, k = lhs.shape
    n = dout.shape[1]
    tm = _row_tile(m)
    tk, tn = _col_tile(k), _col_tile(n)
    starts, ends, group, tile, steps = group_visits(sizes, m, tm)
    with jax.named_scope("moe_tgmm"):
        return pl.pallas_call(
            functools.partial(_tgmm_kernel, tm=tm),
            grid_spec=pltpu.PrefetchScalarGridSpec(
                num_scalar_prefetch=4,
                grid=(k // tk, n // tn, steps),
                in_specs=[
                    pl.BlockSpec((tm, tk),
                                 lambda a, b, i, s, e, g, t: (t[i], a)),
                    pl.BlockSpec((tm, tn),
                                 lambda a, b, i, s, e, g, t: (t[i], b))],
                out_specs=pl.BlockSpec(
                    (None, tk, tn),
                    lambda a, b, i, s, e, g, t: (g[i], a, b)),
                scratch_shapes=[pltpu.VMEM((tk, tn), jnp.float32)]),
            out_shape=jax.ShapeDtypeStruct((sizes.shape[0], k, n),
                                           out_dtype),
            compiler_params=pltpu.CompilerParams(
                dimension_semantics=("parallel", "parallel", "arbitrary")),
            interpret=interpret,
        )(starts, ends, group, tile, lhs, dout)


@functools.partial(jax.custom_vjp, nondiff_argnums=(3,))
def _grouped(lhs, rhs, sizes, interpret):
    return _gmm_pallas(lhs, rhs, sizes, False, interpret)


def _grouped_fwd(lhs, rhs, sizes, interpret):
    return _grouped(lhs, rhs, sizes, interpret), (lhs, rhs, sizes)


def _grouped_bwd(interpret, saved, g):
    lhs, rhs, sizes = saved
    g = g.astype(lhs.dtype)
    return (_gmm_pallas(g, rhs, sizes, True, interpret),
            _tgmm_pallas(lhs, g, sizes, rhs.dtype, interpret), None)


_grouped.defvjp(_grouped_fwd, _grouped_bwd)


def grouped_matmul(lhs, rhs, sizes, interpret: Optional[bool] = None):
    """``out [M, N]`` with ``out[rows of g] = lhs[rows of g] @ rhs[g]`` for
    ``lhs [M, K]``, ``rhs [G, K, N]`` and int32 ``sizes [G]`` (their sum at
    most ``M``). Rows past the last group are unspecified, in the result
    and in ``lhs``'s cotangent; ``sizes`` gets none."""
    if interpret is None:
        if jax.default_backend() != "tpu":
            return jax.lax.ragged_dot(lhs, rhs, sizes)
        interpret = False
    if not _row_tile(lhs.shape[0]):
        warnings.warn(f"grouped_matmul: {lhs.shape[0]} rows are no whole "
                      "tiles; XLA's grouped kernel instead",
                      KernelFallbackWarning, stacklevel=2)
        return jax.lax.ragged_dot(lhs, rhs, sizes)
    return _grouped(lhs, rhs, sizes.astype(jnp.int32), interpret)
