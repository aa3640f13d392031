"""``tony_tpu.ops.kda`` at small sizes on the CPU: the chunked gated delta
rule against the plain token-by-token recurrence — the output and the
gradient of each of ``q, k, v, g, beta`` — over several chunk counts, kept
states and a padded length, under a decay strong enough to overflow
``exp(-cumsum g)``; the kernel bodies under the Pallas interpreter against
the XLA twin; and the pieces: the level tables, the inverse, the exact 0/1
product, the hand-written chunk backward against autodiff."""

import jax
import jax.numpy as jnp
import numpy as np
import pytest

from tony_tpu.ops import kda as K

ARGS = ("q", "k", "v", "g", "beta")


def _data(t, h=2, d=16, decay=0.3, seed=0, dtype=jnp.float32):
    ks = jax.random.split(jax.random.PRNGKey(seed), 5)
    unit = lambda x: x / jnp.linalg.norm(x, axis=-1, keepdims=True)
    q = unit(jax.random.normal(ks[0], (1, t, h, d))) * d ** -0.5
    k = unit(jax.random.normal(ks[1], (1, t, h, d)))
    v = jax.random.normal(ks[2], (1, t, h, d))
    g = -jax.nn.softplus(jax.random.normal(ks[3], (1, t, h, d))) * decay
    beta = jax.nn.sigmoid(jax.random.normal(ks[4], (1, t, h)))
    return (q.astype(dtype), k.astype(dtype), v.astype(dtype), g, beta)


def _rel(a, b):
    a, b = np.asarray(a, np.float32), np.asarray(b, np.float32)
    return float(np.linalg.norm(a - b) / (np.linalg.norm(b) + 1e-30))


def _both(t, chunk, keep, decay=0.3, interpret=None, d=16):
    args = _data(t, d=d, decay=decay, seed=t)
    w = jax.random.normal(jax.random.PRNGKey(9), args[2].shape)
    mine = lambda *a: (K.kda(*a, chunk=chunk, keep=keep,
                             interpret=interpret).astype(jnp.float32)
                       * w).sum()
    plain = lambda *a: (K.kda_reference(*a) * w).sum()
    return (K.kda(*args, chunk=chunk, keep=keep, interpret=interpret),
            K.kda_reference(*args),
            jax.grad(mine, (0, 1, 2, 3, 4))(*args),
            jax.grad(plain, (0, 1, 2, 3, 4))(*args))


# (tokens, chunk, keep): one chunk; several chunks in one step; several
# steps; a length off the chunk (zero-padded); the cell's chunk of 64.
SHAPES = [(16, 16, 1), (64, 16, 4), (128, 16, 2), (72, 8, 2), (50, 16, 2),
          (128, 64, 2)]


@pytest.fixture(scope="module", params=SHAPES, ids=lambda s: "t%d_c%d_k%d" % s)
def chunked(request):
    return _both(*request.param)


def test_output_matches_the_recurrence(chunked):
    o, want, _, _ = chunked
    assert _rel(o, want) < 2e-5


@pytest.mark.parametrize("arg", range(5), ids=ARGS)
def test_gradient_matches_the_recurrence(chunked, arg):
    _, _, g, want = chunked
    assert _rel(g[arg], want[arg]) < 5e-5, ARGS[arg]


@pytest.fixture(scope="module", params=[4.0, 12.0], ids=["decay4", "decay12"])
def strong(request):
    """A decay of ~-3 (-8) a step and channel: over a chunk of 32
    ``exp(-cumsum g)`` is e^96 (e^256), beyond float32."""
    return _both(96, 32, 2, decay=request.param)


def test_a_strong_decay_overflows_nothing(strong):
    o, want, g, _ = strong
    assert np.isfinite(np.asarray(o)).all()
    assert all(np.isfinite(np.asarray(x)).all() for x in g)
    assert _rel(o, want) < 2e-5


@pytest.mark.parametrize("arg", range(5), ids=ARGS)
def test_gradient_under_a_strong_decay(strong, arg):
    _, _, g, want = strong
    assert _rel(g[arg], want[arg]) < 5e-5, ARGS[arg]


@pytest.fixture(scope="module", params=[(256, 64, 2, 0.3), (128, 64, 1, 6.0)],
                ids=["steps2", "strong"])
def interpreted(request):
    """The Pallas bodies (head size 128: a lane block) and the XLA twin."""
    t, chunk, keep, decay = request.param
    return (_both(t, chunk, keep, decay, interpret=True, d=128),
            _both(t, chunk, keep, decay, interpret=None, d=128))


def test_kernel_bodies_equal_the_twin(interpreted):
    (o, want, g, gw), (o2, _, g2, _) = interpreted
    assert _rel(o, o2) < 1e-6 and _rel(o, want) < 2e-5
    for a, b, c in zip(g, g2, gw):
        assert _rel(a, b) < 1e-6 and _rel(a, c) < 5e-5


def test_bfloat16_operands_stay_close():
    args = _data(128, d=32, seed=3, dtype=jnp.bfloat16)
    exact = tuple(a.astype(jnp.float32) for a in args)
    assert _rel(K.kda(*args, chunk=32, keep=2),
                K.kda_reference(*exact)) < 1e-2


@pytest.mark.parametrize("chunk", [2, 8, 64])
def test_levels_cover_every_pair_once(chunk):
    sums, masks = K.tables(chunk)
    levels = masks.reshape(-1, chunk, chunk)
    t, i = np.indices((chunk, chunk))
    assert (levels.sum(0) == (i < t)).all()
    g = -np.random.default_rng(chunk).random((chunk, 3))
    e = (sums @ g).reshape(-1, chunk, 3)
    cum = np.cumsum(g, axis=0)
    for l, m in enumerate(levels):
        for a, b in zip(*np.nonzero(m)):
            np.testing.assert_allclose(e[2 * l, a] + e[2 * l + 1, b],
                                       cum[a] - cum[b], atol=1e-12)
    assert (e <= 0).all()               # no exponent can overflow
    np.testing.assert_allclose(e[-2], cum, atol=1e-12)
    np.testing.assert_allclose(e[-1], cum[-1] - cum, atol=1e-12)


def test_tables_refuse_a_chunk_off_a_power_of_two():
    with pytest.raises(ValueError, match="power of two"):
        K.tables(48)


@pytest.mark.parametrize("c", [8, 16, 64])
def test_inverse_of_a_strictly_lower_matrix(c):
    a = np.tril(np.random.default_rng(c).normal(size=(c, c)), -1) * 0.5
    got = K._inverse(jnp.asarray(a, jnp.float32))
    np.testing.assert_allclose(got, np.linalg.inv(np.eye(c) + a),
                               rtol=2e-4, atol=2e-4)


@pytest.mark.parametrize("cd, tol", [(jnp.bfloat16, 3e-5), (jnp.float32, 2e-7)])
def test_split_product_is_exact_to_float32(cd, tol):
    """The TPU's path for the 0/1 tables: bfloat16 passes over a split
    operand (two parts in a bfloat16 model, three in float32)."""
    sums, _ = K.tables(16)
    x = jax.random.normal(jax.random.PRNGKey(1), (16, 128)) * 7
    got = K._mm_split(jnp.asarray(sums, jnp.bfloat16), x, K._NN, cd)
    want = sums.astype(np.float64) @ np.asarray(x, np.float64)
    assert np.abs(np.asarray(got) - want).max() < tol * np.abs(want).max()


def test_chunk_backward_is_the_forward_s_vjp():
    c, d = 16, 8
    q, k, v, g, beta = (a[0, :, 0] for a in _data(c, h=1, d=d, seed=5))
    st = jax.random.normal(jax.random.PRNGKey(2), (d, d))
    sums, masks = K._consts(c)
    kw = dict(sums=sums, masks=masks, cd=jnp.float32)
    fwd = lambda st, q, k, v, g, b: K.chunk_fwd(st, q, k, v, g, b, **kw)
    (o, st1), vjp = jax.vjp(fwd, st, q, k, v, g, beta[:, None])
    do, dst1 = jnp.ones_like(o) * 0.3, jnp.ones_like(st1) * 0.1
    want = vjp((do, dst1))
    dq, dk, dv, dg, db, dst = K.chunk_bwd(st, q, k, v, g, beta[:, None], do,
                                          dst1, **kw)
    for a, b in zip((dst, dq, dk, dv, dg, db), want):
        assert _rel(a, b) < 1e-5


def test_shapes_are_checked_and_facts_counted():
    q, k, v, g, beta = _data(16)
    with pytest.raises(ValueError, match="kda shapes"):
        K.kda(q, k, v, g, beta[:, :8])
    assert K.n_chunks(32768, 64) == 512
    assert K.states_kept(32768, 64, 4) == 128
