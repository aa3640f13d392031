"""``tony_tpu.ops.kda`` at small sizes on the CPU: the chunked gated delta
rule against the plain token-by-token recurrence — the output and the
gradient of each of ``q, k, v, g, beta`` — over several chunk counts, kept
states and a padded length, under a decay strong enough to overflow
``exp(-cumsum g)``; the kernel bodies under the Pallas interpreter against
the XLA twin; and the pieces: the level tables, the inverse, the exact 0/1
product and the exponent blocks it makes, the hand-written chunk backward
(from a chunk's state-free half, made once) against autodiff of the two
halves composed, and the halves a kernel call builds. The same under ONE
decay a head (Gated DeltaNet: ``g [B, T, H]``), key and value sizes that
differ and lie off the lane width (the kernels' zero lanes), beta up to
2."""

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


def _exponents64(g, chunk):
    """Every level's (row, column) exponent blocks, then ``G`` and ``G_last
    - G``, float64: level 1's row block is ``g`` itself and its column
    block nothing; the rest is ``tables()`` x ``g``."""
    g = np.asarray(g, np.float64)
    e = (K.tables(chunk)[0].astype(np.float64) @ g).reshape(-1, *g.shape)
    return np.concatenate([g[None], np.zeros_like(g)[None], e])


@pytest.mark.parametrize("chunk", [2, 8, 64])
def test_levels_cover_every_pair_once(chunk):
    sums, masks = K.tables(chunk)
    assert sums.shape == (K.table_rows(chunk), chunk)
    assert K.table_rows(chunk) == 2 * (chunk.bit_length() - 1) * chunk
    levels = masks.reshape(-1, chunk, chunk)
    t, i = np.indices((chunk, chunk))
    assert (levels.sum(0) == (i < t)).all()
    g = -np.random.default_rng(chunk).random((chunk, 3))
    e = _exponents64(g, chunk)
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


SPLIT = [(jnp.bfloat16, 3e-5), (jnp.float32, 2e-7)]


@pytest.mark.parametrize("dims", [K._NN, K._TN], ids=["table_x", "tableT_x"])
@pytest.mark.parametrize("cd, tol", SPLIT)
def test_split_product_is_exact_to_float32(cd, tol, dims):
    """The compiled kernels' path for the 0/1 tables: a float32 operand
    split into bfloat16 parts (two in a bfloat16 model, three in float32)
    against as many copies of the table side by side — one product whose
    contraction the copies fill, or (transposed) a product a part."""
    sums, _ = K.tables(16)
    wide, _ = K._consts(16, cd)
    assert wide.dtype == jnp.bfloat16
    assert wide.shape == (sums.shape[0], 16 * K._parts(cd))
    rows = 16 if dims == K._NN else sums.shape[0]
    x = jax.random.normal(jax.random.PRNGKey(1), (rows, 128)) * 7
    got = K._mm_table(wide, x, dims, cd)
    table = sums.astype(np.float64)
    want = (table if dims == K._NN else table.T) @ np.asarray(x, np.float64)
    assert np.abs(np.asarray(got) - want).max() < tol * np.abs(want).max()


@pytest.mark.parametrize("decay", [0.3, 4.0, 12.0])
@pytest.mark.parametrize("cd, tol", SPLIT)
def test_exponent_blocks_are_exact_to_float32(cd, tol, decay):
    """The exponent blocks by the compiled kernels' route (level 1's from
    ``g`` itself, the rest one split product) against ``tables()`` x ``g``
    in float64, under the strong decays too: sums of ``g``, never
    differences of prefix sums, so nothing cancels."""
    chunk = 64
    g = np.asarray(_data(chunk, h=1, d=128, decay=decay, seed=7)[3][0, :, 0])
    got = np.asarray(K._exponents(jnp.asarray(g), K._consts(chunk, cd)[0],
                                  cd)).reshape(-1, *g.shape)
    want = np.delete(_exponents64(g, chunk), 1, axis=0)
    assert got.shape == want.shape and (got <= 0).all()
    assert np.abs(got - want).max() < tol * np.abs(want).max()
    # the float32 table (twin, interpreter): one float32 sum of 64 terms
    twin = np.asarray(K._exponents(jnp.asarray(g), K._consts(chunk)[0], cd))
    assert np.abs(twin.reshape(want.shape) - want).max() \
        < 1e-6 * np.abs(want).max()


@pytest.fixture(scope="module", params=[
    (16, jnp.float32, 0.3), (64, jnp.float32, 0.3), (16, jnp.bfloat16, 0.3),
    (64, jnp.bfloat16, 0.3), (64, jnp.float32, 6.0)],
    ids=lambda p: f"c{p[0]}_{jnp.dtype(p[1]).name}_decay{p[2]}")
def chunk_vjp(request):
    """One chunk's adjoints by hand (``chunk_half`` once, handed to
    ``chunk_bwd``) and by autodiff of ``chunk_fwd`` (the two halves
    composed), under a strong decay too, with the tolerance the compute
    dtype allows: autodiff rounds a bfloat16 operand's cotangent to
    bfloat16, the hand-written backward keeps every product's float32."""
    c, cd, decay = request.param
    d = 32
    q, k, v, g, beta = (a[0, :, 0] for a in
                        _data(c, h=1, d=d, decay=decay, seed=c, dtype=cd))
    beta = beta[:, None]
    st = jax.random.normal(jax.random.PRNGKey(2), (d, d))
    do = jax.random.normal(jax.random.PRNGKey(4), (c, d)) * 0.3
    dst1 = jax.random.normal(jax.random.PRNGKey(5), (d, d)) * 0.1
    sums, masks = K._consts(c)
    kw = dict(sums=sums, masks=masks, cd=cd)
    fwd = lambda st, q, k, v, g, b: K.chunk_fwd(st, q, k, v, g, b, **kw)
    _, vjp = jax.vjp(fwd, st, q, k, v, g, beta)
    half = K.chunk_half(q, k, g, beta, **kw)
    *grads, dst = K.chunk_bwd(st, half, q, k, v, beta, do, dst1, **kw)
    # reads at most 1.6e-7 and 4.5e-3
    tol = 1e-6 if cd == jnp.float32 else 1e-2
    return (dst, *grads), vjp((do, dst1)), tol


@pytest.mark.parametrize("out", range(6), ids=("state",) + ARGS)
def test_chunk_backward_is_the_forward_s_vjp(chunk_vjp, out):
    got, want, tol = chunk_vjp
    assert got[out].shape == want[out].shape
    assert _rel(got[out], want[out]) < tol


@pytest.mark.parametrize("arg", range(5), ids=ARGS)
def test_bfloat16_gradients_stay_close(arg):
    """``g``'s and ``beta``'s too: they reach the parameters (a decay rate
    a head, ``a_log``) only through the chunk backward's ``dg``."""
    args = _data(128, d=32, seed=3, dtype=jnp.bfloat16)
    exact = tuple(a.astype(jnp.float32) for a in args)
    w = jax.random.normal(jax.random.PRNGKey(9), args[2].shape)
    grad = lambda f, a: jax.grad(
        lambda *a: (f(*a).astype(jnp.float32) * w).sum(), arg)(*a)
    got = grad(lambda *a: K.kda(*a, chunk=32, keep=2), args)
    # reads 2.8e-3 to 4.0e-3
    assert _rel(got, grad(K.kda_reference, exact)) < 1e-2, ARGS[arg]


@pytest.mark.parametrize("keep", [2, 4, 8])
def test_a_kernel_call_builds_one_half_a_chunk(monkeypatch, keep):
    """Each kernel body has ONE loop that calls ``chunk_half``, traced once
    and run ``keep`` times: the backward builds no half in its reverse
    loop and none twice. ``halves_built`` reads the same off the kernels'
    jaxprs — and hears a second half, where a kernel builds one."""
    calls = []
    real = K.chunk_half

    def counted(*args, **kw):
        calls.append(1)
        return real(*args, **kw)
    monkeypatch.setattr(K, "chunk_half", counted)
    q, k, v, g, beta = _data(64 * keep, h=1, d=128, seed=keep)
    o, res = jax.eval_shape(lambda *a: K._kda_fwd(*a, 64, keep, True),
                            q, k, v, g, beta)
    assert len(calls) == 1
    del calls[:]
    jax.eval_shape(lambda res, do: K._kda_bwd(64, keep, True, res, do),
                   res, o)
    assert len(calls) == 1
    assert K.halves_built("fwd", 64, keep) == keep
    assert K.halves_built("bwd", 64, keep) == keep
    # PR 38's backward: every chunk's half again where the state's adjoint
    # comes back (g is not handed down there: k stands in)
    bwd = K.chunk_bwd
    monkeypatch.setattr(K, "chunk_bwd", lambda st, half, q, k, v, beta, *a: bwd(
        st, real(q, k, k, beta, *a[2:]), q, k, v, beta, *a))
    K.halves_built.cache_clear()
    try:
        assert K.halves_built("bwd", 64, keep) == 2 * keep
    finally:
        K.halves_built.cache_clear()


def test_shapes_are_checked_and_facts_counted():
    q, k, v, g, beta = _data(16)
    with pytest.raises(ValueError, match="kda shapes"):
        K.kda(q, k, v, g, beta[:, :8])
    assert K.n_chunks(32768, 64) == 512
    assert K.states_kept(32768, 64, 4) == 128
    # PR 38's kernels: 4 (forward) and 7 (backward) halves a step of four
    # chunks, 896 rows
    assert K.halves_built("fwd") == 4 == K.halves_built("bwd")
    assert K.table_rows(64) == 768


# -- one decay a head (Gated DeltaNet) ---------------------------------------

def _scalar_data(t, h=2, dk=24, dv=40, decay=0.3, seed=0, dtype=jnp.float32,
                 alike=0.0):
    """``dk != dv``, both off the lane width; ``g [1, t, h]``; beta in (0,
    2). ``alike`` mixes one direction into every key (neighbouring tokens'
    keys of a trained model point alike)."""
    ks = jax.random.split(jax.random.PRNGKey(seed), 6)
    unit = lambda x: x / jnp.linalg.norm(x, axis=-1, keepdims=True)
    q = unit(jax.random.normal(ks[0], (1, t, h, dk))) * dk ** -0.5
    k = unit(jax.random.normal(ks[1], (1, t, h, dk))
             + alike * dk ** 0.5 * unit(jax.random.normal(ks[5], (1, 1, h, dk))))
    v = jax.random.normal(ks[2], (1, t, h, dv))
    g = -jax.nn.softplus(jax.random.normal(ks[3], (1, t, h))) * decay
    beta = 2 * jax.nn.sigmoid(jax.random.normal(ks[4], (1, t, h)))
    return (q.astype(dtype), k.astype(dtype), v.astype(dtype), g, beta)


def _plain(q, k, v, g, beta):
    """The token-by-token recurrence with the scalar decay on every key
    channel."""
    return K.kda_reference(q, k, v, jnp.broadcast_to(g[..., None], q.shape),
                           beta)


def _both_scalar(t, chunk, keep, decay=0.3, interpret=None, **sizes):
    args = _scalar_data(t, decay=decay, seed=t, **sizes)
    w = jax.random.normal(jax.random.PRNGKey(9), args[2].shape)
    mine = lambda *a: (K.kda(*a, chunk=chunk, keep=keep,
                             interpret=interpret).astype(jnp.float32)
                       * w).sum()
    plain = lambda *a: (_plain(*a) * w).sum()
    return (K.kda(*args, chunk=chunk, keep=keep, interpret=interpret),
            _plain(*args), jax.grad(mine, (0, 1, 2, 3, 4))(*args),
            jax.grad(plain, (0, 1, 2, 3, 4))(*args))


@pytest.fixture(scope="module", params=SHAPES, ids=lambda s: "t%d_c%d_k%d" % s)
def scalar(request):
    return _both_scalar(*request.param)


def test_scalar_decay_output_matches_the_recurrence(scalar):
    o, want, g, _ = scalar
    assert o.shape == want.shape and o.shape[-1] == 40
    assert g[3].shape == g[4].shape == o.shape[:3]     # dg leaves [B, T, H]
    assert _rel(o, want) < 2e-5


@pytest.mark.parametrize("arg", range(5), ids=ARGS)
def test_scalar_decay_gradient_matches_the_recurrence(scalar, arg):
    _, _, g, want = scalar
    assert _rel(g[arg], want[arg]) < 5e-5, ARGS[arg]


@pytest.fixture(scope="module", params=[4.0, 12.0], ids=["decay4", "decay12"])
def scalar_strong(request):
    """``exp(G_t - G_i)`` is taken under the causal mask: above the
    diagonal the difference is positive and e^256 is beyond float32."""
    return _both_scalar(96, 32, 2, decay=request.param)


@pytest.mark.parametrize("arg", range(6), ids=("o",) + ARGS)
def test_scalar_decay_strong_overflows_nothing(scalar_strong, arg):
    o, want, g, gw = scalar_strong
    got, want = ((o,) + tuple(g))[arg], ((want,) + tuple(gw))[arg]
    assert np.isfinite(np.asarray(got)).all()
    assert _rel(got, want) < 5e-5


@pytest.fixture(scope="module", params=[(256, 64, 2, 0.3), (128, 64, 1, 6.0)],
                ids=["steps2", "strong"])
def scalar_interpreted(request):
    """The Pallas bodies at the Olmo Hybrid cell's head sizes, 96 and 192,
    behind zero lanes (128 and 256), and the XLA twin at 96 and 192."""
    t, chunk, keep, decay = request.param
    sizes = dict(dk=96, dv=192)
    return (_both_scalar(t, chunk, keep, decay, interpret=True, **sizes),
            _both_scalar(t, chunk, keep, decay, interpret=None, **sizes))


@pytest.mark.parametrize("arg", range(6), ids=("o",) + ARGS)
def test_scalar_decay_kernel_bodies_equal_the_twin(scalar_interpreted, arg):
    (o, want, g, gw), (o2, _, g2, _) = scalar_interpreted
    a, b, c = (((x,) + tuple(y))[arg] for x, y in ((o, g), (o2, g2),
                                                   (want, gw)))
    assert a.shape == b.shape == c.shape
    assert _rel(a, b) < 2e-6 and _rel(a, c) < 5e-5


def test_zero_lanes_serve_the_per_channel_route_too():
    """Head size 24 through the kernel bodies: q, k, g padded to 128 key
    lanes, v to 128 value lanes, o cut back."""
    args = _data(64, d=24, seed=5)
    o = K.kda(*args, chunk=16, keep=2, interpret=True)
    assert o.shape == args[2].shape
    assert _rel(o, K.kda_reference(*args)) < 2e-5
    assert _rel(o, K.kda(*args, chunk=16, keep=2)) < 1e-6


@pytest.mark.parametrize("arg", range(6), ids=("o",) + ARGS)
def test_scalar_decay_bfloat16_stays_close(arg):
    args = _scalar_data(128, seed=3, dtype=jnp.bfloat16)
    exact = tuple(a.astype(jnp.float32) for a in args)
    if arg == 0:
        assert _rel(K.kda(*args, chunk=32, keep=2), _plain(*exact)) < 1e-2
        return
    w = jax.random.normal(jax.random.PRNGKey(9), args[2].shape)
    grad = lambda f, a: jax.grad(
        lambda *a: (f(*a).astype(jnp.float32) * w).sum(), arg - 1)(*a)
    got = grad(lambda *a: K.kda(*a, chunk=32, keep=2), args)
    # reads 2.5e-3 to 4.5e-3
    assert _rel(got, grad(_plain, exact)) < 1e-2, ARGS[arg - 1]


@pytest.mark.parametrize("alike, tol", [(0.0, 2e-5), (0.5, 2e-5), (2.0, 5e-4)])
def test_beta_up_to_two_over_keys_that_point_alike(alike, tol):
    """``(I + A)^-1`` by Neumann products is exact for any nilpotent ``A``,
    but its powers grow with ``beta |k_t . k_i|`` before they cancel: with
    beta in (0, 2) and keys sharing a direction (cosine ~0.2 at 0.5, ~0.8
    at 2) float32 still holds the recurrence, the looser the more alike."""
    args = _scalar_data(128, seed=11, alike=alike)
    cos = jnp.einsum("bthd,bshd->bhts", args[1], args[1])
    assert (alike == 0) or float(jnp.mean(cos)) > 0.15 * alike
    assert float(args[4].max()) > 1.8
    assert _rel(K.kda(*args, chunk=64, keep=2), _plain(*args)) < tol


@pytest.fixture(scope="module", params=[
    (16, jnp.float32, 0.3), (64, jnp.float32, 0.3), (16, jnp.bfloat16, 0.3),
    (64, jnp.bfloat16, 0.3), (64, jnp.float32, 6.0)],
    ids=lambda p: f"c{p[0]}_{jnp.dtype(p[1]).name}_decay{p[2]}")
def scalar_chunk_vjp(request):
    """``chunk_vjp`` without tables: one decay a head, ``g [C, 1]``, a
    ``dk x dv`` state."""
    c, cd, decay = request.param
    dk, dv = 24, 40
    q, k, v, g, beta = (a[0, :, 0] for a in _scalar_data(
        c, h=1, dk=dk, dv=dv, decay=decay, seed=c, dtype=cd))
    g, beta = g[:, None], beta[:, None]
    st = jax.random.normal(jax.random.PRNGKey(2), (dv, dk))
    do = jax.random.normal(jax.random.PRNGKey(4), (c, dv)) * 0.3
    dst1 = jax.random.normal(jax.random.PRNGKey(5), (dv, dk)) * 0.1
    kw = dict(sums=None, masks=None, cd=cd)
    fwd = lambda st, q, k, v, g, b: K.chunk_fwd(st, q, k, v, g, b, **kw)
    _, vjp = jax.vjp(fwd, st, q, k, v, g, beta)
    half = K.chunk_half(q, k, g, beta, **kw)
    assert len(half) == 5 and half[0].shape == (2 * c, dk)
    *grads, dst = K.chunk_bwd(st, half, q, k, v, beta, do, dst1, **kw)
    # reads at most 1.3e-6 (dg under decay 6: row and column sums of both
    # signs) and 4.7e-3
    tol = 3e-6 if cd == jnp.float32 else 1e-2
    return (dst, *grads), vjp((do, dst1)), tol


@pytest.mark.parametrize("out", range(6), ids=("state",) + ARGS)
def test_scalar_chunk_backward_is_the_forward_s_vjp(scalar_chunk_vjp, out):
    got, want, tol = scalar_chunk_vjp
    assert got[out].shape == want[out].shape
    assert _rel(got[out], want[out]) < tol


def test_scalar_decay_takes_no_table_and_names_its_kernels():
    """The scalar route's kernels get five operands (no 0/1 tables) and
    their own names; the per-channel route's keep theirs."""
    def calls(args):
        text = str(jax.make_jaxpr(lambda *a: K.kda(
            *a, chunk=64, keep=2, interpret=False))(*args))
        return [n for n in ("kda_chunk_fwd", "gdn_chunk_fwd")
                if f"name={n}" in text or f"{n} for" in text]
    assert calls(_scalar_data(128, dk=96, dv=192)) == ["gdn_chunk_fwd"]
    assert calls(_data(128, d=128)) == ["kda_chunk_fwd"]
    assert K._tables(_scalar_data(16)[3], 64) == ()
    with pytest.raises(ValueError, match="kda shapes"):
        q, k, v, g, beta = _scalar_data(16)
        K.kda(q, k, v, g[..., :1], beta)
