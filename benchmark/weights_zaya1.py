"""Seeded weights of the ``zaya1-8b`` configuration, made on the device in
one jitted call, in the benchmark's own flat layout (every per-layer leaf
stacked on a leading layer axis), and the one place that knows how that
layout maps onto the program's parameter tree (``Transformer`` with scanned
layers, latent attention, ``DroplessMoE`` with an ``MLPRouter``). The
program and the reference are both given these values; the reference makes
its own copy from the seed.

Every leaf is a seeded normal: matrices at std 1/sqrt(fan_in) (a
convolution's fan-in is its taps x its input width), norm scales, the key
temperature ``tau`` and the router's depth decay ``gamma`` at 1 + 0.1
N(0,1), the convolutions' biases at ``BIAS_STD``. Three choices keep a
seeded model from degenerating (each read on the CPU at the published
widths before the first chip run, PERF.md section 6, PR 33):

* the tied table at std 1, unit-variance token vectors, as the Keye
  configuration's: at 0.1 the attention output, a near-uniform mean of the
  values at these lengths and so nearly the same at every position, is 23%
  of a layer-0 row and 93% of a layer-3 row, every token routes alike (a
  layer's held half took 7% to 89% of its tokens) and a step's time follows
  the seed;
* the final norm's scale at ``HEAD_SCALE`` (1 + 0.1 N(0,1)): a row of the
  table is a token's vector AND its logit direction, so at a scale of 1 a
  token's own logit is ~hidden = 2048 and the loss as large; at 0.005 it is
  ~10 beside logits of std 0.23 and the loss ~12 (ln 131136 = 11.8);
* the router MLP's three matrices at ``ROUTER_STD`` 0.02 and its biases at
  0: at 1/sqrt(256) a gelu's mean (0.28 at unit input) is a logit offset
  half as large as the logits' spread over tokens and an expert takes 15% to
  27% of a layer's tokens; at 0.02 the hidden layers work in the gelu's
  near-linear range, the offsets are an eighth of the spread and the fullest
  expert takes 11% to 17% (6.25% is even).
"""

from __future__ import annotations

import math

import jax
import jax.numpy as jnp

from benchmark.weights import seed_key

EMBED_STD = 1.0
HEAD_SCALE = 0.005
ROUTER_STD = 0.02
BIAS_STD = 0.02
ATTN = ("wq", "wk", "wv", "wo")
MIX = ("conv0_w", "conv0_b", "conv1_w", "conv1_b", "tau")
ROUTER = {"r_down": "w_down", "r_bdown": "b_down", "r_gamma": "gamma",
          "r_norm": "norm", "r_w1": "w1", "r_b1": "b1", "r_w2": "w2",
          "r_b2": "b2", "r_w3": "w3"}
EXPERTS = ("w_gate", "w_up", "w_down")
# Leaves the chip's check leaves out of its widest-leaf numbers.
NOISE_LEAVES = ()


def leaf_specs(cfg: dict) -> dict:
    """name -> (shape, std); std None marks a scale (1 + 0.1 N(0,1)), a
    1-tuple that scale times its entry."""
    d, f, v, n = cfg["hidden"], cfg["ffn"], cfg["vocab"], cfg["layers"]
    hd, r, held = cfg["head_dim"], cfg["router_hidden"], cfg["experts_held"]
    h, g = cfg["heads"], cfg["kv_heads"]
    q, kv, (k0, k1) = h * hd, g * hd, cfg["taps"]
    lecun = lambda fan_in: 1.0 / math.sqrt(fan_in)
    return {
        "embed": ((v, d), EMBED_STD), "final_norm": ((d,), (HEAD_SCALE,)),
        "attn_norm": ((n, d), None), "mlp_norm": ((n, d), None),
        "wq": ((n, d, q), lecun(d)), "wk": ((n, d, kv), lecun(d)),
        "wv": ((n, d, kv), lecun(d)), "wo": ((n, q, d), lecun(q)),
        # tap K-1 multiplies the current step, tap K-2 the one before
        "conv0_w": ((n, k0, q + kv), lecun(k0)),
        "conv0_b": ((n, q + kv), BIAS_STD),
        "conv1_w": ((n, k1, h + g, hd, hd), lecun(k1 * hd)),
        "conv1_b": ((n, q + kv), BIAS_STD),
        "tau": ((n, g), None),
        "r_down": ((n, d, r), lecun(d)), "r_bdown": ((n, r), 0.0),
        "r_gamma": ((n, r), None), "r_norm": ((n, r), None),
        "r_w1": ((n, r, r), ROUTER_STD), "r_b1": ((n, r), 0.0),
        "r_w2": ((n, r, r), ROUTER_STD), "r_b2": ((n, r), 0.0),
        "r_w3": ((n, r, cfg["experts"]), ROUTER_STD),
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
            if isinstance(std, float):
                leaf = std * z
            else:
                leaf = (1.0 if std is None else std[0]) * (1.0 + 0.1 * z)
            out[name] = leaf.astype(dtype)
        return out

    return jax.jit(gen)(seed_key(seed))


def to_program_tree(w: dict) -> dict:
    block = {
        "attn_norm": {"scale": w["attn_norm"]},
        "mlp_norm": {"scale": w["mlp_norm"]},
        "attn": {**{n: {"kernel": w[n]} for n in ATTN},
                 **{n: w[n] for n in MIX}},
        "moe_mlp": {**{n: w[n] for n in EXPERTS},
                    "router": {p: w[n] for n, p in ROUTER.items()}},
    }
    return {"embedding": w["embed"], "final_norm": {"scale": w["final_norm"]},
            "layers": {"block": block}}


def from_program_tree(tree: dict) -> dict:
    """Inverse of :func:`to_program_tree`, for anything shaped like the
    program's params (its optimizer moments)."""
    block = tree["layers"]["block"]
    return {"embed": tree["embedding"],
            "final_norm": tree["final_norm"]["scale"],
            "attn_norm": block["attn_norm"]["scale"],
            "mlp_norm": block["mlp_norm"]["scale"],
            **{n: block["attn"][n]["kernel"] for n in ATTN},
            **{n: block["attn"][n] for n in MIX},
            **{n: block["moe_mlp"][n] for n in EXPERTS},
            **{n: block["moe_mlp"]["router"][p] for n, p in ROUTER.items()}}
