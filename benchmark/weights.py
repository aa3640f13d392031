"""Seeded weights, made on the device in one jitted call, in the
benchmark's own flat layout — and the one place that knows how that layout
maps onto the program's parameter tree.

The program and the reference are both given these values (the reference
makes its own copy from the seed; it takes nothing the program has made).
"""

from __future__ import annotations

import math

import jax
import jax.numpy as jnp


def leaf_specs(cfg: dict) -> dict:
    """name -> (shape, std); std None marks a norm scale (1 + 0.1 N(0,1))."""
    d, f, v, n = cfg["hidden"], cfg["ffn"], cfg["vocab"], cfg["layers"]
    q, kv = cfg["heads"] * cfg["head_dim"], cfg["kv_heads"] * cfg["head_dim"]
    lecun = lambda fan_in: 1.0 / math.sqrt(fan_in)
    specs = {
        "embed": ((v, d), 0.02), "final_norm": ((d,), None),
        "lm_head": ((d, v), lecun(d)),
        "attn_norm": ((n, d), None), "mlp_norm": ((n, d), None),
        "wq": ((n, d, q), lecun(d)), "wk": ((n, d, kv), lecun(d)),
        "wv": ((n, d, kv), lecun(d)), "wo": ((n, q, d), lecun(q)),
        "w_gate": ((n, d, f), lecun(d)), "w_up": ((n, d, f), lecun(d)),
        "w_down": ((n, f, d), lecun(f)),
    }
    return specs


def seed_key(seed: int) -> jax.Array:
    """A key for any whole-number seed (the driver's pass 2**31). The
    "rbg" generator: billions of normals in a fraction of the time the
    default threefry takes on a TPU."""
    return jax.random.fold_in(
        jax.random.key(seed % (2 ** 31), impl="rbg"), seed // (2 ** 31))


def make_weights(cfg: dict, seed: int, dtype, shardings: dict | None = None):
    """All leaves from ``seed`` in one jitted call, as ``dtype``;
    ``shardings`` (name -> sharding) places them as they are made."""
    specs = leaf_specs(cfg)

    def gen(key):
        out = {}
        for i, (name, (shape, std)) in enumerate(sorted(specs.items())):
            z = jax.random.normal(jax.random.fold_in(key, i), shape,
                                  jnp.float32)
            out[name] = (1.0 + 0.1 * z if std is None else std * z
                         ).astype(dtype)
        return out

    return jax.jit(gen, out_shardings=shardings)(seed_key(seed))


def to_program_tree(w: dict) -> dict:
    """The flat layout as the program's ``params`` tree (Transformer with
    scan_layers: every per-layer leaf stacked on a leading layer axis)."""
    k = lambda name: {"kernel": w[name]}
    block = {
        "attn_norm": {"scale": w["attn_norm"]},
        "mlp_norm": {"scale": w["mlp_norm"]},
        "attn": {"wq": k("wq"), "wk": k("wk"), "wv": k("wv"), "wo": k("wo")},
        "mlp": {n: k(n) for n in ("w_gate", "w_up", "w_down")},
    }
    return {"embedding": w["embed"], "final_norm": {"scale": w["final_norm"]},
            "lm_head": k("lm_head"), "layers": {"block": block}}


def from_program_tree(tree: dict) -> dict:
    """Inverse of :func:`to_program_tree`, for anything shaped like the
    program's params (its optimizer moments, its shardings)."""
    block = tree["layers"]["block"]
    un = lambda node: node["kernel"]
    return {"embed": tree["embedding"],
            "final_norm": tree["final_norm"]["scale"],
            "lm_head": un(tree["lm_head"]),
            "attn_norm": block["attn_norm"]["scale"],
            "mlp_norm": block["mlp_norm"]["scale"],
            **{n: un(block["attn"][n]) for n in ("wq", "wk", "wv", "wo")},
            **{n: un(block["mlp"][n]) for n in ("w_gate", "w_up", "w_down")}}
