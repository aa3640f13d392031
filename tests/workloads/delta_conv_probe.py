"""The fused convolution / SiLU / head-normalisation kernels alone on the
chip (``ops.ssm.conv_silu_unit``) against the XLA chain they replace
(``conv_silu_unit_xla``), at the Kimi Linear cell's shapes (``[1, 32768,
4096]``, 32 heads of 128: q with its scale, and v without the norm) and
the Olmo Hybrid cell's (``[1, 16384, 1440]``, 15 heads of 96; ``[1, 16384,
2880]`` without the norm): first the compiled bodies' arithmetic — the one
part the interpreter's tests cannot run is the reciprocal's estimate and
Newton step — in float32 and bfloat16 at a short length, then the time of
a forward call and of forward + backward, both routes.
``chiprun -- python tests/workloads/delta_conv_probe.py [block ...]``: with
blocks given, the kernels are timed at each of them too."""

import sys
import time
from pathlib import Path

sys.path.insert(0, str(Path(__file__).resolve().parents[2]))

import jax
import jax.numpy as jnp
import numpy as np

from tony_tpu.ops import ssm

SHAPES = {"kimi q": (32768, 32, 128, True), "kimi v": (32768, 32, 128, False),
          "olmo q": (16384, 15, 96, True), "olmo v": (16384, 15, 192, False)}


def data(t, heads, d, dtype, seed=0):
    ks = jax.random.split(jax.random.PRNGKey(seed), 3)
    e = heads * d
    return (jax.random.normal(ks[0], (1, t, e)).astype(dtype),
            jax.random.normal(ks[1], (4, e)) * 0.5,
            jax.random.normal(ks[2], (1, t, e)).astype(dtype))


def rel(a, b):
    a, b = np.asarray(a, np.float32), np.asarray(b, np.float32)
    return float(np.abs(a - b).max() / (np.abs(b).max() + 1e-30))


def routes(heads, d, unit, block=None):
    scale = d ** -0.5 if unit else 1.0
    return {
        "kernel": lambda x, w: ssm.conv_silu_unit(
            x, w, heads=heads, unit=unit, scale=scale, block=block),
        "xla": lambda x, w: ssm.conv_silu_unit_xla(x, w, heads, unit, scale)}


def timed(fn, *args, calls=10):
    jax.block_until_ready(fn(*args))
    t0 = time.perf_counter()
    for _ in range(calls):
        out = fn(*args)
    jax.block_until_ready(out)
    return (time.perf_counter() - t0) / calls * 1e3


print(jax.devices(), flush=True)
for name, (_, heads, d, unit) in SHAPES.items():
    for dtype in (jnp.float32, jnp.bfloat16):
        x, w, dy = data(2048, heads, d, dtype)
        got, want = ({k: jax.jit(lambda x, w, f=f: jax.vjp(f, x, w)[1](dy)
                                 + (f(x, w),))(x, w)
                      for k, f in routes(heads, d, unit).items()}[k]
                     for k in ("kernel", "xla"))
        print(name, jnp.dtype(dtype).name, "dx dw y",
              [round(rel(a, b), 8) for a, b in zip(got, want)], flush=True)

blocks = [None] + [int(a) for a in sys.argv[1:]]
for name, (t, heads, d, unit) in SHAPES.items():
    x, w, dy = data(t, heads, d, jnp.bfloat16, 1)
    for block in blocks:
        # x, dy and dx, each twice, within the kernels' 48 MB of VMEM
        if block and block * ssm.conv_lanes(heads * d, heads, unit) > 2 << 20:
            continue
        for route, f in routes(heads, d, unit, block).items():
            if block and route == "xla":
                continue
            fwd = jax.jit(f)

            def both(x, w, f=f):
                y, vjp = jax.vjp(f, x, w)
                return (y, *vjp(dy))
            both = jax.jit(both)
            print(name, route, "block", block or (
                ssm.conv_plan(t, heads * d, heads, unit, 4) or (0, 0, 0))[2],
                "fwd ms", round(timed(fwd, x, w), 3),
                "fwd+bwd ms", round(timed(both, x, w), 3), flush=True)
