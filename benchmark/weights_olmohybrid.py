"""Seeded weights of the ``olmo-hybrid-7b`` configuration, made on the device
in one jitted call, in the benchmark's own flat layout (``L<i>.<leaf>`` a
layer, the layers being of two kinds), and the one place that knows how that
layout maps onto the program's parameter tree (``HybridDecoder`` with
``gdn`` / ``attn`` mixers over the held heads and a ``SwiGLU``). The program
and the reference are both given these values; the reference makes its own
copy from the seed.

Every leaf is seeded: matrices normal at std 1/sqrt(fan_in) (a
convolution's fan-in is its taps; ``W_o``'s the held width), norm scales at
1 + 0.1 N(0,1). What keeps a seeded model from degenerating:

* the embedding at std 1, unit-variance token vectors, as the Keye, ZAYA1
  and Kimi Linear configurations': the sublayers read the residual stream
  itself (the norm comes after them), and each adds a unit-scale row to it;
  the head is untied and lecun, so logits are of unit scale and the first
  loss ~ln 12544 + 0.5;
* ``A_log`` = log(8 exp(0.5 z)) clipped to [log 1, log 16] and ``dt_bias``
  the inverse softplus of exp(U(log 0.001, log 0.1)) (Mamba's draw), one a
  head: the per-step log-decay is ~-0.05 to -5 at the gate's unit-scale
  input, so the state neither freezes nor is wiped every step;
* ``w_b`` at lecun: beta = 2 sigmoid(N(0, ~1..4)) fills (0, 2), a third of
  the steps above 1.4 — the range ``linear_allow_neg_eigval`` opens.
"""

from __future__ import annotations

import math

import jax
import jax.numpy as jnp

from benchmark.weights import seed_key

EMBED_STD = 1.0
# Leaves held as {"kernel": ...} in the program's tree, by mixer / ffn.
KERNELS = {"gdn": ("wq", "wk", "wv", "wa", "wb", "wz", "wo"),
           "attn": ("wq", "wk", "wv", "wo"),
           "ffn": ("w_gate", "w_up", "w_down")}
BARE = {"gdn": ("conv_q", "conv_k", "conv_v", "a_log", "dt_bias"),
        "attn": ()}
SCALES = {"gdn": ("o_norm",), "attn": ("q_norm", "k_norm")}
# Leaves the chip's check leaves out of its widest-leaf numbers
# (``grad_norm_gap``, ``param_change_gap``): every mixer's ``wq`` and
# ``wk``. q and k go through a normalisation a head (gdn: L2) or over the
# width (attn: RMSNorm) before they meet anything, so their gradient has no
# part along them and what is left is small; the bfloat16 rounding of the
# projection's output and of its cotangent is a large share of it, on some
# seeds: over twelve sound seeds the widest leaf of the first gradient was
# a gdn layer's wq or wk every time, 0.0010-0.0225, heavy-tailed (one leaf
# 0.0225 where every other leaf of that seed reads <= 0.0013), against
# 0.017-0.030 under int8 — a maximum that measures two leaves' noise and is
# blind to the other 58. With the delta rule taken in float32 the same
# leaves read the same (a CPU run at 256 wide: 0.035 on one seed of three),
# so it is the configuration's bfloat16 projections, not the chunk kernels
# (my chip runs, PR 40: calls 122, 128, 129; PERF.md section 6). The median
# over EVERY leaf (grad_median_gap) still counts them, and the CPU tests
# compare them in float32, where they agree to 1e-5.
NOISE_LEAVES = ("wq", "wk")


def layer_specs(kind: str, cfg: dict) -> dict:
    """name -> (shape, how): a float std, None (a scale, 1 + 0.1 z),
    "a_log" or "dt_bias"."""
    d, f = cfg["hidden"], cfg["ffn"]
    lecun = lambda fan_in: 1.0 / math.sqrt(fan_in)
    specs = {"norm1": ((d,), None), "norm2": ((d,), None),
             "w_gate": ((d, f), lecun(d)), "w_up": ((d, f), lecun(d)),
             "w_down": ((f, d), lecun(f))}
    if kind == "gdn":
        h, dk, dv = cfg["gdn_heads"], cfg["dk"], cfg["dv"]
        specs.update({
            "wq": ((d, h * dk), lecun(d)), "wk": ((d, h * dk), lecun(d)),
            "wv": ((d, h * dv), lecun(d)), "wz": ((d, h * dv), lecun(d)),
            **{f"conv_{n}": ((cfg["conv"], h * w), lecun(cfg["conv"]))
               for n, w in (("q", dk), ("k", dk), ("v", dv))},
            "a_log": ((h,), "a_log"), "dt_bias": ((h,), "dt_bias"),
            "wa": ((d, h), lecun(d)), "wb": ((d, h), lecun(d)),
            "o_norm": ((dv,), None), "wo": ((h * dv, d), lecun(h * dv))})
    else:
        e = cfg["heads"] * cfg["head_dim"]
        specs.update({
            "wq": ((d, e), lecun(d)), "wk": ((d, e), lecun(d)),
            "wv": ((d, e), lecun(d)), "q_norm": ((e,), None),
            "k_norm": ((e,), None), "wo": ((e, d), lecun(e))})
    return specs


def leaf_specs(cfg: dict) -> dict:
    d, v = cfg["hidden"], cfg["vocab"]
    specs = {"embed": ((v, d), EMBED_STD), "final_norm": ((d,), None),
             "lm_head": ((d, v), 1.0 / math.sqrt(d))}
    for i, kind in enumerate(cfg["kinds"]):
        specs.update({f"L{i}.{n}": s
                      for n, s in layer_specs(kind, cfg).items()})
    return specs


def make_weights(cfg: dict, seed: int, dtype=jnp.float32) -> dict:
    """All leaves from ``seed`` in one jitted call, as ``dtype``."""
    specs = leaf_specs(cfg)

    def gen(key):
        out = {}
        for i, (name, (shape, how)) in enumerate(sorted(specs.items())):
            k = jax.random.fold_in(key, i)
            z = jax.random.normal(k, shape, jnp.float32)
            if isinstance(how, float):
                leaf = how * z
            elif how is None:
                leaf = 1.0 + 0.1 * z
            elif how == "a_log":
                leaf = jnp.clip(math.log(8.0) + 0.5 * z, 0.0, math.log(16.0))
            else:
                u = jax.random.uniform(jax.random.fold_in(k, 1), shape)
                dt = jnp.exp(u * (math.log(0.1) - math.log(0.001))
                             + math.log(0.001))
                leaf = dt + jnp.log(-jnp.expm1(-dt))
            out[name] = leaf.astype(dtype)
        return out

    return jax.jit(gen)(seed_key(seed))


def layer_leaves(w: dict, i: int) -> dict:
    return {n[len(f"L{i}."):]: a for n, a in w.items()
            if n.startswith(f"L{i}.")}


def mixer_tree(kind: str, mine: dict) -> dict:
    """A mixer's leaves as the program's module holds them."""
    tree = {n: {"kernel": mine[n]} for n in KERNELS[kind]}
    tree.update({n: {"scale": mine[n]} for n in SCALES[kind]})
    tree.update({n: mine[n] for n in BARE[kind]})
    return tree


def to_program_tree(w: dict, cfg: dict) -> dict:
    """The flat layout as the program's ``params`` tree."""
    tree = {"embedding": w["embed"], "lm_head_kernel": w["lm_head"],
            "final_norm": {"scale": w["final_norm"]}}
    for i, kind in enumerate(cfg["kinds"]):
        mine = layer_leaves(w, i)
        tree[f"layer_{i}"] = {
            "norm1": {"scale": mine["norm1"]},
            "norm2": {"scale": mine["norm2"]},
            kind: mixer_tree(kind, mine),
            "mlp": {n: {"kernel": mine[n]} for n in KERNELS["ffn"]}}
    return tree


def from_program_tree(tree: dict, cfg: dict) -> dict:
    """Inverse of :func:`to_program_tree`, for anything shaped like the
    program's params (its optimizer moments)."""
    w = {"embed": tree["embedding"], "lm_head": tree["lm_head_kernel"],
         "final_norm": tree["final_norm"]["scale"]}
    for i, kind in enumerate(cfg["kinds"]):
        layer = tree[f"layer_{i}"]
        mix = layer[kind]
        flat = {"norm1": layer["norm1"]["scale"],
                "norm2": layer["norm2"]["scale"],
                **{n: mix[n]["kernel"] for n in KERNELS[kind]},
                **{n: mix[n]["scale"] for n in SCALES[kind]},
                **{n: mix[n] for n in BARE[kind]},
                **{n: layer["mlp"][n]["kernel"] for n in KERNELS["ffn"]}}
        w.update({f"L{i}.{n}": a for n, a in flat.items()})
    return w
