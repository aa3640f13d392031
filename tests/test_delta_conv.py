"""``tony_tpu.ops.ssm.conv_silu_unit``: a delta-rule mixer's operand chain
— causal depthwise convolution, SiLU, each head's channels over the root
of their sum of squares — as one Pallas kernel each way, the bodies under
``interpret=True`` against the chain in plain jax.numpy
(``conv_silu_unit_xla``: ``silu(causal_conv1d(x.astype(f32), w))`` and the
normalisation ``models/hybrid.py`` ran as XLA fusions before PR 47).

The head shapes are the cells': 32 x 128 (``kimilinear.train-32k``: a head
is a lane tile, its sum a product with a tile of ones), 15 x 96 and 15 x
192 (``olmohybrid.train-16k``: rows of 1440 and 2880 channels, no multiple
of 128; a head's sum a product with a 0/1 ``[E, 128]`` table). Time blocks
of 32 steps, so that every block boundary falls inside the convolution's
reach, with ``T`` a multiple of the block and not.

Tolerances: the output within ONE bfloat16 ulp of the chain's (both round
the same float32 value up to the order of a few additions); the gradients
as ``tests/test_kda.py`` holds a bfloat16 route (1e-2 of the largest
entry) and, in float32, 1e-5. The compiled kernels take ``1 / (1 +
exp(-p))`` as the reciprocal unit's estimate and one Newton step (float32's
own division on the chip); the interpreter's estimate is a bfloat16 one,
so under it the bodies write the division out (``estimate=False``) —
the one line of the bodies these tests do not run; a chip run holds it
(PERF.md section 6, PR 47)."""

import warnings

import jax
import jax.numpy as jnp
import numpy as np
import pytest

from tony_tpu.ops import causal_conv1d, ssm
from tony_tpu.ops.attention import KernelFallbackWarning

BLOCK, TAPS = 32, 4
HEADS = {"32x128": (32, 128), "15x96": (15, 96), "15x192": (15, 192)}
# (batch, T): a multiple of the block; off it, two sequences
TIMES = {"b1_t64": (1, 64), "b2_t72": (2, 72)}


def inputs(b, t, heads, d, dtype=jnp.bfloat16, seed=0):
    ks = jax.random.split(jax.random.PRNGKey(seed), 3)
    e = heads * d
    return (jax.random.normal(ks[0], (b, t, e)).astype(dtype),
            jax.random.normal(ks[1], (TAPS, e)) * 0.5,
            jax.random.normal(ks[2], (b, t, e)).astype(dtype))


def chain(x, w, heads, unit, scale):
    """The specification, written out: the convolution in float32, SiLU,
    the head's normalisation, the scale, the cast."""
    b, t, e = x.shape
    y = jax.nn.silu(causal_conv1d(x.astype(jnp.float32), w))
    if unit:
        y = y.reshape(b, t, heads, e // heads)
        y = y * jax.lax.rsqrt(
            jnp.sum(jnp.square(y), -1, keepdims=True) + 1e-6)
    return (y.reshape(b, t, e) * scale).astype(x.dtype)


def kernel(x, w, heads, unit, scale, block=BLOCK):
    return ssm.conv_silu_unit(x, w, heads=heads, unit=unit, scale=scale,
                              block=block, interpret=True)


def f32(a):
    return np.asarray(a.astype(jnp.float32))


def ulps(got, want):
    """The largest distance in bfloat16 ulps of ``want``. An entry under
    1e-4 of the largest is a sum that cancelled (one in ~500,000 here):
    there the order of float32's additions shows, and it is held to the
    ulp of that floor."""
    got, want = f32(got), f32(want)
    size = np.maximum(np.abs(want), 1e-4 * np.abs(want).max())
    ulp = 2.0 ** (np.floor(np.log2(size)) - 7)
    return float(np.max(np.abs(got - want) / ulp))


def rel(got, want):
    got, want = f32(got), f32(want)
    return float(np.abs(got - want).max() / (np.abs(want).max() + 1e-30))


@pytest.mark.parametrize("unit", [True, False], ids=["unit", "plain"])
@pytest.mark.parametrize("times", sorted(TIMES))
@pytest.mark.parametrize("shape", sorted(HEADS))
def test_forward_is_the_chain_to_one_bfloat16_ulp(shape, times, unit):
    (heads, d), (b, t) = HEADS[shape], TIMES[times]
    x, w, _ = inputs(b, t, heads, d)
    scale = d ** -0.5 if unit else 1.0
    got = kernel(x, w, heads, unit, scale)
    want = chain(x, w, heads, unit, scale)
    assert got.shape == want.shape and got.dtype == jnp.bfloat16
    assert ulps(got, want) <= 1.0
    assert ulps(got, ssm.conv_silu_unit_xla(x, w, heads, unit, scale)) <= 1.0


@pytest.mark.parametrize("unit", [True, False], ids=["unit", "plain"])
@pytest.mark.parametrize("times", sorted(TIMES))
@pytest.mark.parametrize("shape", sorted(HEADS))
def test_gradients_are_autodiffs_of_the_chain(shape, times, unit):
    (heads, d), (b, t) = HEADS[shape], TIMES[times]
    x, w, dy = inputs(b, t, heads, d)
    scale = d ** -0.5 if unit else 1.0
    _, vjp = jax.vjp(lambda x, w: kernel(x, w, heads, unit, scale), x, w)
    _, want = jax.vjp(lambda x, w: chain(x, w, heads, unit, scale), x, w)
    (dx, dw), (dx0, dw0) = vjp(dy), want(dy)
    assert dx.dtype == jnp.bfloat16 and dw.dtype == jnp.float32
    assert dw.shape == (TAPS, heads * d)
    assert rel(dx, dx0) < 1e-2
    assert rel(dw, dw0) < 1e-4      # gathered in float32, both sides


@pytest.mark.parametrize("shape", sorted(HEADS))
def test_float32_in_is_float32_throughout(shape):
    """Nothing on the path rounds to bfloat16 when the compute dtype is
    float32: output and both gradients to 1e-5, where a bfloat16 anywhere
    would read 4e-3."""
    heads, d = HEADS[shape]
    x, w, dy = inputs(1, 64, heads, d, jnp.float32)
    y, vjp = jax.vjp(lambda x, w: kernel(x, w, heads, True, 0.5), x, w)
    y0, want = jax.vjp(lambda x, w: chain(x, w, heads, True, 0.5), x, w)
    assert y.dtype == jnp.float32 and rel(y, y0) < 1e-5
    for got, ref in zip(vjp(dy), want(dy)):
        assert rel(got, ref) < 1e-5


def test_steps_before_zero_are_zeros():
    """The first three rows see fewer taps: ``out[0] = silu(w[3] x[0])``,
    ``out[1] = silu(w[3] x[1] + w[2] x[0])``, ... — nothing of the halo
    block the first grid step is handed (the same rows, clamped)."""
    heads, d = 4, 128
    x, w, _ = inputs(1, 64, heads, d)
    got = f32(kernel(x, w, heads, False, 1.0))[0]
    xf, wf = f32(x)[0], np.asarray(w)
    for row in range(TAPS - 1):
        pre = sum(wf[TAPS - 1 - s] * xf[row - s] for s in range(row + 1))
        want = jnp.asarray(pre / (1 + np.exp(-pre))).astype(jnp.bfloat16)
        assert ulps(jnp.asarray(got[row]), want) <= 1.0, row


@pytest.mark.parametrize("block", [16, 32, 64])
def test_a_block_boundary_inside_the_convolutions_reach(block):
    """Rows ``block .. block + 2`` read the block before through the halo;
    in the backward ``dx`` of rows ``block - 3 .. block - 1`` reads the
    block after through the carried rows. Any block gives the one-block
    answer."""
    heads, d = 4, 128
    x, w, dy = inputs(1, 128, heads, d, seed=3)
    one, vjp1 = jax.vjp(
        lambda x, w: kernel(x, w, heads, True, 1.0, block=128), x, w)
    got, vjp = jax.vjp(
        lambda x, w: kernel(x, w, heads, True, 1.0, block=block), x, w)
    np.testing.assert_array_equal(f32(got), f32(one))
    (dx, dw), (dx1, dw1) = vjp(dy), vjp1(dy)
    np.testing.assert_array_equal(f32(dx), f32(dx1))
    assert rel(dw, dw1) < 1e-6          # another order of the partial sums


def test_the_blocks_are_chosen_from_the_shapes():
    # (E, heads, unit) -> channels a block; the cells' six chains first
    lanes = {(4096, 32, True): 512, (4096, 32, False): 512,
             (1440, 15, True): 1440, (2880, 15, False): 2880,
             (2880, 15, True): 2880, (3072, 32, True): 768,
             (256, 4, True): 256,
             # more heads than a table has columns, rows off the lane tile
             (200 * 24, 200, True): None}
    for (e, heads, unit), want in lanes.items():
        assert ssm.conv_lanes(e, heads, unit) == want, (e, heads, unit)
    # the strip: 128 registers' worth under unit, 16 without; the block: a
    # power of two of strips, 2 MB of bfloat16 at most
    assert (ssm.conv_strip(512), ssm.conv_strip(1440),
            ssm.conv_strip(512, False), ssm.conv_strip(2880, False)) == (
        128, 64, 32, 16)
    assert (ssm.conv_block(32768, 512), ssm.conv_block(16384, 1440),
            ssm.conv_block(16384, 2880, False), ssm.conv_block(40, 512)) == (
        1024, 512, 256, 128)
    for e, unit in ((512, True), (1440, True), (2880, False)):
        assert ssm.conv_block(16384, e, unit) % ssm.conv_strip(e, unit) == 0


def test_no_kernel_off_a_tpu_and_a_warning_where_no_block_exists(monkeypatch):
    assert ssm.conv_plan(64, 4096, 32, True, TAPS) is None        # the CPU
    assert ssm.conv_plan(64, 4096, 32, True, TAPS, interpret=True) == (
        True, 512, 128)
    monkeypatch.setattr(jax, "default_backend", lambda: "tpu")
    assert ssm.conv_plan(32768, 4096, 32, True, TAPS) == (False, 512, 1024)
    x, w, _ = inputs(1, 32, 200, 24)
    with pytest.warns(KernelFallbackWarning, match="whole heads"):
        got = ssm.conv_silu_unit(x, w, heads=200, unit=True, interpret=True)
    np.testing.assert_array_equal(f32(got), f32(chain(x, w, 200, True, 1.0)))
    with warnings.catch_warnings():
        warnings.simplefilter("error", KernelFallbackWarning)
        with pytest.raises(ValueError, match="whole strips"):
            kernel(*inputs(1, 64, 4, 128)[:2], 4, True, 1.0, block=24)
