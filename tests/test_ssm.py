"""``tony_tpu.ops.ssm``: the chunked selective scan and its ``custom_vjp``
against a plain ``lax.scan`` over time — the XLA twin and the Pallas
bodies under ``interpret=True``, several chunk sizes, T off the chunk —
and the causal depthwise convolution against its definition.

Tolerance 2e-5 of each array's largest entry: both sides are float32 and
differ only in the order of a few dozen additions (the sum over the state
index; the reductions of dB and dC over 1024 channels at a time)."""

import jax
import jax.numpy as jnp
import numpy as np
import pytest

from tony_tpu.ops import causal_conv1d, selective_scan
from tony_tpu.ops import ssm

TOL = 2e-5


def plain_scan(x, dt, a, bm, cm, d):
    def one(x, dt, bm, cm):
        def step(h, inp):
            x_t, dt_t, b_t, c_t = inp
            h = jnp.exp(dt_t[:, None] * a) * h \
                + (dt_t * x_t)[:, None] * b_t[None]
            return h, h @ c_t + d * x_t
        return jax.lax.scan(step, jnp.zeros(a.shape), (x, dt, bm, cm))[1]
    return jax.vmap(one)(x, dt, bm, cm)


def inputs(b, t, e, n, seed=0):
    ks = jax.random.split(jax.random.PRNGKey(seed), 7)
    return (jax.random.normal(ks[0], (b, t, e)),
            jax.nn.softplus(jax.random.normal(ks[1], (b, t, e)) - 3),
            -jnp.exp(jax.random.normal(ks[2], (e, n)) * 0.5),
            jax.random.normal(ks[3], (b, t, n)),
            jax.random.normal(ks[4], (b, t, n)),
            jax.random.normal(ks[5], (e,))), \
        jax.random.normal(ks[6], (b, t, e))


def close(got, want):
    scale = float(jnp.max(jnp.abs(want))) + 1e-30
    return float(jnp.max(jnp.abs(got - want))) / scale


CASES = {
    # name: (batch, T, E, N, chunk, interpret)
    "xla_chunk8_t37": (2, 37, 160, 4, 8, None),
    "xla_chunk16_t64": (1, 64, 96, 16, 16, None),
    "xla_chunk64_t24": (1, 24, 96, 16, 64, None),
    "pallas_chunk8_t37": (2, 37, 160, 4, 8, True),
    "pallas_chunk16_t64_two_blocks": (1, 64, 1100, 16, 16, True),
    "pallas_chunk64_t24": (1, 24, 96, 16, 64, True),
}


@pytest.mark.parametrize("case", sorted(CASES))
def test_forward_matches_plain_scan(case):
    b, t, e, n, chunk, interpret = CASES[case]
    args, _ = inputs(b, t, e, n)
    y = selective_scan(*args, chunk=chunk, interpret=interpret)
    assert y.shape == (b, t, e) and y.dtype == jnp.float32
    assert close(y, plain_scan(*args)) < TOL


@pytest.mark.parametrize("case", sorted(CASES))
def test_custom_vjp_matches_plain_scan(case):
    b, t, e, n, chunk, interpret = CASES[case]
    args, w = inputs(b, t, e, n, seed=1)
    got = jax.grad(lambda *a: (selective_scan(
        *a, chunk=chunk, interpret=interpret) * w).sum(), range(6))(*args)
    want = jax.grad(lambda *a: (plain_scan(*a) * w).sum(), range(6))(*args)
    for name, g, r in zip(("x", "dt", "a", "b", "c", "d"), got, want):
        assert g.shape == r.shape
        assert close(g, r) < TOL, name


def test_forward_keeps_state_only_at_chunk_boundaries():
    """The residuals of the vjp hold T / chunk states, never T."""
    args, _ = inputs(1, 64, 128, 4)
    _, res = ssm._scan_fwd(*args, 16, jnp.dtype(jnp.float32), None)
    assert res[-1].shape == (4, 1, 128, 4)          # [chunks, B, E, N]
    assert ssm.n_chunks(64, 16) == 4 and ssm.n_chunks(37, 8) == 5


def test_bfloat16_state_is_a_different_result():
    """The lower-precision control rounds the state and dt: far outside
    the float32 tolerance, on both implementations alike."""
    args, _ = inputs(1, 64, 128, 4)
    want = plain_scan(*args)
    for interpret in (None, True):
        y = selective_scan(*args, chunk=16, state_dtype=jnp.bfloat16,
                           interpret=interpret)
        assert close(y, want) > 50 * TOL
    a, b = (selective_scan(*args, chunk=16, state_dtype=jnp.bfloat16,
                           interpret=i) for i in (None, True))
    assert close(a, b) < TOL


def test_shape_mismatch_is_an_error():
    args, _ = inputs(1, 16, 32, 4)
    with pytest.raises(ValueError, match="selective_scan shapes"):
        selective_scan(args[0], args[1][:, :8], *args[2:])


@pytest.mark.parametrize("k", [1, 4])
def test_causal_conv1d_is_its_definition(k):
    x = jax.random.normal(jax.random.PRNGKey(0), (2, 9, 5))
    w = jax.random.normal(jax.random.PRNGKey(1), (k, 5))
    bias = jax.random.normal(jax.random.PRNGKey(2), (5,))
    want = np.zeros((2, 9, 5), np.float32)
    for t in range(9):
        for j in range(k):
            if t - (k - 1) + j >= 0:
                want[:, t] += np.asarray(w[j]) * np.asarray(
                    x[:, t - (k - 1) + j])
    np.testing.assert_allclose(causal_conv1d(x, w, bias),
                               want + np.asarray(bias), atol=1e-5)
    # causal: a later input never moves an earlier output
    x2 = x.at[:, 5:].set(0.0)
    np.testing.assert_array_equal(causal_conv1d(x, w)[:, :5],
                                  causal_conv1d(x2, w)[:, :5])
