"""Seeded weights of the ``kimi-linear-48b-a3b`` configuration, made on the
device in one jitted call, in the benchmark's own flat layout
(``L<i>.<leaf>`` a layer, the layers being of different kinds), and the one
place that knows how that layout maps onto the program's parameter tree
(``HybridDecoder`` with ``kda`` / ``attn_mla`` mixers, a ``SwiGLU`` or a
``DroplessMoE`` with a sigmoid router and a shared expert). The program and
the reference are both given these values; the reference makes its own copy
from the seed.

Every leaf is seeded: matrices normal at std 1/sqrt(fan_in) (a
convolution's fan-in is its taps), norm scales at 1 + 0.1 N(0,1). What
keeps a seeded model from degenerating:

* the embedding at std 1, unit-variance token vectors, as the Keye and
  ZAYA1 configurations': at 0.02 the mixers' output (unit-scale after the
  head norm and ``W_o``) would be all of a row, every token would route
  alike and a step's time would follow the seed; the head is untied and
  lecun, so logits are of unit scale and the first loss ~ln 20480 + 0.5;
* ``A_log`` = log(8 exp(0.5 z)) clipped to [log 1, log 16] and ``dt_bias``
  the inverse softplus of exp(U(log 0.001, log 0.1)) (Mamba's draw): the
  per-step log-decay is ~-0.05 to -2 at the gate's unit-scale input, so the
  state neither freezes nor is wiped every step;
* the output gate's bias at ``BIAS_STD``, the router's selection bias at
  ``SELECT_STD``: small against the sigmoid scores' spread (~0.2), enough
  to move the choice of the tokens at the edge.
"""

from __future__ import annotations

import math

import jax
import jax.numpy as jnp

from benchmark.weights import seed_key

EMBED_STD = 1.0
BIAS_STD = 0.02
SELECT_STD = 0.01
SCOPES = {"kda": "kda", "mla": "attn_mla"}
# Leaves held as {"kernel": ...} in the program's tree, by mixer / ffn.
KERNELS = {"kda": ("wq", "wk", "wv", "wf1", "wf2", "wb", "wg1", "wg2", "wo"),
           "mla": ("wq", "wkv_a", "wkv_b", "wo"),
           "dense": ("w_gate", "w_up", "w_down")}
EXPERT_LEAVES = ("w_router", "router_bias", "w_gate", "w_up", "w_down",
                 "shared_gate", "shared_up", "shared_down")
# Leaves the chip's check leaves out of its widest-leaf numbers.
NOISE_LEAVES = ()


def layer_specs(kind: str, ffn: str, cfg: dict) -> dict:
    """name -> (shape, how): a float std, None (a scale, 1 + 0.1 z),
    "a_log" or "dt_bias"."""
    d = cfg["hidden"]
    lecun = lambda fan_in: 1.0 / math.sqrt(fan_in)
    specs = {"norm1": ((d,), None), "norm2": ((d,), None)}
    if kind == "kda":
        h, hd = cfg["kda_heads"], cfg["kda_head_dim"]
        e = h * hd
        specs.update({
            "wq": ((d, e), lecun(d)), "wk": ((d, e), lecun(d)),
            "wv": ((d, e), lecun(d)),
            **{f"conv_{n}": ((cfg["conv"], e), lecun(cfg["conv"]))
               for n in "qkv"},
            "a_log": ((h,), "a_log"), "dt_bias": ((e,), "dt_bias"),
            "wf1": ((d, hd), lecun(d)), "wf2": ((hd, e), lecun(hd)),
            "wb": ((d, h), lecun(d)),
            "wg1": ((d, hd), lecun(d)), "wg2": ((hd, e), lecun(hd)),
            "bg2": ((e,), BIAS_STD), "o_norm": ((hd,), None),
            "wo": ((e, d), lecun(e))})
    else:
        h, r = cfg["mla_heads"], cfg["kv_rank"]
        specs.update({
            "wq": ((d, h * (cfg["nope"] + cfg["rope"])), lecun(d)),
            "wkv_a": ((d, r + cfg["rope"]), lecun(d)),
            "kv_norm": ((r,), None),
            "wkv_b": ((r, h * (cfg["nope"] + cfg["v_dim"])), lecun(r)),
            "wo": ((h * cfg["v_dim"], d), lecun(h * cfg["v_dim"]))})
    if ffn == "dense":
        f = cfg["dense_ffn"]
        specs.update({"w_gate": ((d, f), lecun(d)), "w_up": ((d, f), lecun(d)),
                      "w_down": ((f, d), lecun(f))})
    else:
        f, held, s = cfg["ffn"], cfg["experts_held"], cfg["shared"] * cfg["ffn"]
        specs.update({
            "w_router": ((d, cfg["experts"]), lecun(d)),
            "router_bias": ((cfg["experts"],), SELECT_STD),
            "w_gate": ((held, d, f), lecun(d)),
            "w_up": ((held, d, f), lecun(d)),
            "w_down": ((held, f, d), lecun(f)),
            "shared_gate": ((d, s), lecun(d)), "shared_up": ((d, s), lecun(d)),
            "shared_down": ((s, d), lecun(s))})
    return specs


def leaf_specs(cfg: dict) -> dict:
    d, v = cfg["hidden"], cfg["vocab"]
    specs = {"embed": ((v, d), EMBED_STD), "final_norm": ((d,), None),
             "lm_head": ((d, v), 1.0 / math.sqrt(d))}
    for i, (kind, ffn) in enumerate(zip(cfg["kinds"], cfg["ffns"])):
        specs.update({f"L{i}.{n}": s
                      for n, s in layer_specs(kind, ffn, cfg).items()})
    return specs


def make_weights(cfg: dict, seed: int, dtype=jnp.float32) -> dict:
    """All leaves from ``seed`` in one jitted call, as ``dtype``. The
    router and its bias are drawn at their published width whichever
    experts are held, so every share of a layer routes alike."""
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


def _layer_tree(kind: str, ffn: str, mine: dict) -> dict:
    k = lambda n: {"kernel": mine[n]}
    mixer = {n: k(n) for n in KERNELS[kind]}
    if kind == "kda":
        mixer["wg2"]["bias"] = mine["bg2"]
        mixer["o_norm"] = {"scale": mine["o_norm"]}
        mixer.update({n: mine[n] for n in ("conv_q", "conv_k", "conv_v",
                                            "a_log", "dt_bias")})
    else:
        mixer["kv_norm"] = {"scale": mine["kv_norm"]}
    tree = {"norm1": {"scale": mine["norm1"]},
            "norm2": {"scale": mine["norm2"]}, SCOPES[kind]: mixer}
    if ffn == "dense":
        tree["mlp"] = {n: k(n) for n in KERNELS["dense"]}
    else:
        tree["moe_mlp"] = {n: mine[n] for n in EXPERT_LEAVES}
    return tree


def to_program_tree(w: dict, cfg: dict) -> dict:
    """The flat layout as the program's ``params`` tree."""
    tree = {"embedding": w["embed"], "lm_head_kernel": w["lm_head"],
            "final_norm": {"scale": w["final_norm"]}}
    for i, (kind, ffn) in enumerate(zip(cfg["kinds"], cfg["ffns"])):
        mine = {name[len(f"L{i}."):]: a for name, a in w.items()
                if name.startswith(f"L{i}.")}
        tree[f"layer_{i}"] = _layer_tree(kind, ffn, mine)
    return tree


def from_program_tree(tree: dict, cfg: dict) -> dict:
    """Inverse of :func:`to_program_tree`, for anything shaped like the
    program's params (its optimizer moments)."""
    w = {"embed": tree["embedding"], "lm_head": tree["lm_head_kernel"],
         "final_norm": tree["final_norm"]["scale"]}
    for i, (kind, ffn) in enumerate(zip(cfg["kinds"], cfg["ffns"])):
        layer = tree[f"layer_{i}"]
        mix = layer[SCOPES[kind]]
        flat = {"norm1": layer["norm1"]["scale"],
                "norm2": layer["norm2"]["scale"],
                **{n: mix[n]["kernel"] for n in KERNELS[kind]}}
        if kind == "kda":
            flat.update(bg2=mix["wg2"]["bias"], o_norm=mix["o_norm"]["scale"],
                        **{n: mix[n] for n in ("conv_q", "conv_k", "conv_v",
                                               "a_log", "dt_bias")})
        else:
            flat["kv_norm"] = mix["kv_norm"]["scale"]
        if ffn == "dense":
            flat.update({n: layer["mlp"][n]["kernel"]
                         for n in KERNELS["dense"]})
        else:
            flat.update({n: layer["moe_mlp"][n] for n in EXPERT_LEAVES})
        w.update({f"L{i}.{n}": a for n, a in flat.items()})
    return w
