"""Seeded weights of the ``keye-vl-2.0-30b-a3b`` configuration, made on
the device in one jitted call, in the benchmark's own flat layout (every
per-layer leaf stacked on a leading layer axis), and the one place that
knows how that layout maps onto the program's parameter tree (``Transformer``
with scanned layers, ``DroplessMoE``). The program and the reference are
both given these values; the reference makes its own copy from the seed.

Every leaf is a seeded normal: std 1/sqrt(fan_in), norm scales 1 + 0.1
N(0,1), and the embedding at std 1 — unit-variance token vectors. At the
0.02 of the other configurations a seeded model's residual stream is its
layers' common component: every token then routes to the same experts and
scores the same keys (a held expert took 15,898 of a layer's 16,384 tokens
or none), the 8th expert and the 2048-th key of every token sit on a tie
that any rounding flips, and a step's time follows the seed (my chip runs,
PR 31). Token vectors that differ, as a trained model's do, spread the
router and the indexer.
"""

from __future__ import annotations

import math

import jax
import jax.numpy as jnp

from benchmark.weights import seed_key

ATTN = ("wq", "wk", "wv", "wo", "index_wq", "index_wk", "index_w")
NORMS = ("q_norm", "k_norm")
EXPERTS = ("w_router", "w_gate", "w_up", "w_down")
# The indexer's three leaves: their gradient comes from L_I alone.
INDEX_LEAVES = ("index_wq", "index_wk", "index_w")
# Leaves the chip's check leaves out of its widest-leaf numbers: none. (At
# an embedding of std 0.02 the router's and the indexer's gradients rode on
# choices that flipped on rounding — 0.0096..0.041 and 0.0025..0.013 over
# four sound seeds — and had to be left out; with token vectors that differ
# they read 0.0001..0.0013 like every other leaf; my chip runs, PR 31.)
NOISE_LEAVES = ()


def leaf_specs(cfg: dict) -> dict:
    """name -> (shape, std); std None marks a norm scale (1 + 0.1 N(0,1))."""
    d, f, v, n = cfg["hidden"], cfg["ffn"], cfg["vocab"], cfg["layers"]
    hd = cfg["head_dim"]
    q, kv = cfg["heads"] * hd, cfg["kv_heads"] * hd
    j, e, held = cfg["index_heads"], cfg["index_dim"], cfg["experts_held"]
    lecun = lambda fan_in: 1.0 / math.sqrt(fan_in)
    return {
        "embed": ((v, d), 1.0), "final_norm": ((d,), None),
        "lm_head": ((d, v), lecun(d)),
        "attn_norm": ((n, d), None), "mlp_norm": ((n, d), None),
        "q_norm": ((n, hd), None), "k_norm": ((n, hd), None),
        "wq": ((n, d, q), lecun(d)), "wk": ((n, d, kv), lecun(d)),
        "wv": ((n, d, kv), lecun(d)), "wo": ((n, q, d), lecun(q)),
        "index_wq": ((n, d, j * e), lecun(d)),
        "index_wk": ((n, d, e), lecun(d)), "index_w": ((n, d, j), lecun(d)),
        "w_router": ((n, d, cfg["experts"]), lecun(d)),
        "w_gate": ((n, held, d, f), lecun(d)),
        "w_up": ((n, held, d, f), lecun(d)),
        "w_down": ((n, held, f, d), lecun(f)),
    }


def make_weights(cfg: dict, seed: int, dtype=jnp.float32) -> dict:
    """All leaves from ``seed`` in one jitted call, as ``dtype``. The
    router is drawn at its published width whichever experts are held, so
    every share of a layer routes alike."""
    specs = leaf_specs(cfg)

    def gen(key):
        out = {}
        for i, (name, (shape, std)) in enumerate(sorted(specs.items())):
            z = jax.random.normal(jax.random.fold_in(key, i), shape,
                                  jnp.float32)
            out[name] = (1.0 + 0.1 * z if std is None else std * z
                         ).astype(dtype)
        return out

    return jax.jit(gen)(seed_key(seed))


def to_program_tree(w: dict) -> dict:
    k = lambda name: {"kernel": w[name]}
    block = {
        "attn_norm": {"scale": w["attn_norm"]},
        "mlp_norm": {"scale": w["mlp_norm"]},
        "attn": {**{n: k(n) for n in ATTN},
                 **{n: {"scale": w[n]} for n in NORMS}},
        "moe_mlp": {n: w[n] for n in EXPERTS},
    }
    return {"embedding": w["embed"], "final_norm": {"scale": w["final_norm"]},
            "lm_head_kernel": w["lm_head"], "layers": {"block": block}}


def from_program_tree(tree: dict) -> dict:
    """Inverse of :func:`to_program_tree`, for anything shaped like the
    program's params (its optimizer moments)."""
    block = tree["layers"]["block"]
    return {"embed": tree["embedding"],
            "final_norm": tree["final_norm"]["scale"],
            "lm_head": tree["lm_head_kernel"],
            "attn_norm": block["attn_norm"]["scale"],
            "mlp_norm": block["mlp_norm"]["scale"],
            **{n: block["attn"][n]["kernel"] for n in ATTN},
            **{n: block["attn"][n]["scale"] for n in NORMS},
            **{n: block["moe_mlp"][n] for n in EXPERTS}}
