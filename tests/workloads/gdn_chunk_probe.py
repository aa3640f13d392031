"""The scalar-decay chunk kernels alone on the chip at the Olmo Hybrid
cell's size (1 x 16384 x 15 heads of 96 x 192, bfloat16): the output and
gradients against the token-by-token recurrence at a short length (float32
and bfloat16), then the time of a forward call and of forward + backward.
``chiprun -- python tests/workloads/gdn_chunk_probe.py``."""

import sys
import time
from pathlib import Path

sys.path.insert(0, str(Path(__file__).resolve().parents[2]))

import jax
import jax.numpy as jnp
import numpy as np

from tony_tpu.ops import kda as K

H, DK, DV = 15, 96, 192


def data(t, dtype, seed=0):
    ks = jax.random.split(jax.random.PRNGKey(seed), 5)
    unit = lambda x: x / jnp.linalg.norm(x, axis=-1, keepdims=True)
    q = unit(jax.random.normal(ks[0], (1, t, H, DK))) * DK ** -0.5
    k = unit(jax.random.normal(ks[1], (1, t, H, DK)))
    v = jax.random.normal(ks[2], (1, t, H, DV))
    g = -jax.nn.softplus(jax.random.normal(ks[3], (1, t, H))) * 0.5
    beta = 2 * jax.nn.sigmoid(jax.random.normal(ks[4], (1, t, H)))
    return q.astype(dtype), k.astype(dtype), v.astype(dtype), g, beta


def rel(a, b):
    a, b = np.asarray(a, np.float32), np.asarray(b, np.float32)
    return float(np.linalg.norm(a - b) / (np.linalg.norm(b) + 1e-30))


def plain(q, k, v, g, beta):
    return K.kda_reference(q, k, v, jnp.broadcast_to(g[..., None], q.shape),
                           beta)


print(jax.devices(), flush=True)
for dtype in (jnp.float32, jnp.bfloat16):
    args = data(1024, dtype)
    exact = tuple(a.astype(jnp.float32) for a in args)
    w = jax.random.normal(jax.random.PRNGKey(9), args[2].shape)
    loss = lambda f: lambda *a: (f(*a).astype(jnp.float32) * w).sum()
    with jax.default_matmul_precision("highest"):
        want = jax.jit(plain)(*exact)
        gw = jax.jit(jax.grad(loss(plain), range(5)))(*exact)
    o = jax.jit(K.kda)(*args)
    g = jax.jit(jax.grad(loss(K.kda), range(5)))(*args)
    print(jnp.dtype(dtype).name, "o", rel(o, want),
          [round(rel(a, b), 7) for a, b in zip(g, gw)], flush=True)

args = data(16384, jnp.bfloat16, 1)
fwd = jax.jit(K.kda)
both = jax.jit(jax.grad(lambda *a: K.kda(*a).astype(jnp.float32).sum(),
                        range(5)))
for name, fn in (("fwd", fwd), ("fwd+bwd", both)):
    jax.block_until_ready(fn(*args))
    t0 = time.perf_counter()
    for _ in range(10):
        out = fn(*args)
    jax.block_until_ready(out)
    print(name, "ms a call", (time.perf_counter() - t0) * 100, flush=True)
