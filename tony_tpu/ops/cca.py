"""The q/k mixing of compressed convolutional attention (Zyphra, arXiv:
2510.04476): attention runs in a latent narrower than the model, and what
the narrow projections lose is given back by mixing q and k over time and
with each other before they meet.

For the raw projections ``q~ [B, T, H, d]`` and ``k~ [B, T, G, d]`` (``H``
query heads over ``G`` key heads, ``g(h) = h // (H / G)``), over the
``H + G`` heads side by side:

* ``c1_t = a0 * u_t + a1 * u_{t-1} + b1``: a depthwise causal convolution
  (:func:`tony_tpu.ops.ssm.causal_conv1d`, as the state-space layers');
* ``c2_t[j] = A0[j] c1_t[j] + A1[j] c1_{t-1}[j] + b2[j]``: a causal
  convolution whose taps are a ``[d, d]`` matrix a head (:func:`head_conv`);
* ``q[h] = c2_q[h] + (q~[h] + k~[g(h)]) / 2`` and ``k[g] = c2_k[g] +
  (k~[g] + mean_{h in g} q~[h]) / 2``: each takes the other's mean;
* both L2-normalised to length ``sqrt(d)``, k times a learned temperature
  a key head.

Plain XLA: shifted multiply-adds, two batched ``[d, d]`` matmuls a tap, and
a row norm — an elementwise chain around one small matmul, 0.3% of a
layer's FLOPs at the published widths (device scope ``cca_mix``, which the
caller opens and closes with the rotation). Steps before 0 are zeros.
"""

from __future__ import annotations

import jax
import jax.numpy as jnp

from tony_tpu.ops.ssm import causal_conv1d


def shift1(x: jax.Array) -> jax.Array:
    """``x [B, T, ...]`` one step later: ``out[t] = x[t - 1]``, zeros at
    step 0."""
    pad = ((0, 0), (1, 0)) + ((0, 0),) * (x.ndim - 2)
    return jnp.pad(x, pad)[:, :-1]


def head_conv(c: jax.Array, w: jax.Array, bias: jax.Array) -> jax.Array:
    """Causal convolution over time with a matrix a head and tap: ``c``
    [B, T, H, d], ``w`` [K, H, d, d] (``w[K-1]`` multiplies the current
    step, as :func:`causal_conv1d`'s), ``bias`` [H, d], all float32 ->
    float32 [B, T, H, d]. The products are float32 matmuls at the default
    precision: on the TPU one bfloat16 pass that adds up in float32."""
    k, t = w.shape[0], c.shape[1]
    cp = jnp.pad(c, ((0, 0), (k - 1, 0), (0, 0), (0, 0)))
    return bias + sum(jnp.einsum("bthd,hde->bthe", cp[:, j:j + t], w[j])
                      for j in range(k))


def mix(q_raw: jax.Array, k_raw: jax.Array, conv0_w: jax.Array,
        conv0_b: jax.Array, conv1_w: jax.Array, conv1_b: jax.Array,
        tau: jax.Array, *, n_heads: int, n_kv_heads: int,
        eps: float) -> tuple:
    """``(q [B, T, H, d], k [B, T, G, d])`` float32, mixed and normalised
    (not yet rotated), from the packed projections ``q_raw [B, T, H d]``
    and ``k_raw [B, T, G d]``. ``conv0_w [K0, (H + G) d]``, ``conv0_b
    [(H + G) d]``, ``conv1_w [K1, H + G, d, d]``, ``conv1_b [(H + G) d]``
    with the query heads first; ``tau [G]``. Everything is float32."""
    b, t, nq = q_raw.shape
    h, g = n_heads, n_kv_heads
    d = nq // h
    q32, k32 = q_raw.astype(jnp.float32), k_raw.astype(jnp.float32)

    def convs(u, lo, heads):
        cols = slice(lo * d, (lo + heads) * d)
        c1 = causal_conv1d(u, conv0_w[:, cols], conv0_b[cols])
        return head_conv(c1.reshape(b, t, heads, d),
                         conv1_w[:, lo:lo + heads],
                         conv1_b[cols].reshape(heads, d))

    q4, k4 = q32.reshape(b, t, h, d), k32.reshape(b, t, g, d)
    q = convs(q32, 0, h) + 0.5 * (q4 + jnp.repeat(k4, h // g, axis=2))
    k = convs(k32, h, g) + 0.5 * (
        k4 + q4.reshape(b, t, g, h // g, d).mean(axis=3))
    unit = lambda x: x * jax.lax.rsqrt(
        jnp.mean(x * x, axis=-1, keepdims=True) + eps)
    return unit(q), unit(k) * tau[:, None]
