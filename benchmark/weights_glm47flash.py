"""Seeded weights of the ``glm-4.7-flash`` configuration, made on the
device in one jitted call, in the benchmark's own flat layout (``L<i>.<leaf>``
a layer; ``M.<leaf>`` the multi-token-prediction module: its two norms,
``w_eh``, its block's leaves under the layer's names, ``final_norm``), and
the one place that knows how that layout maps onto the program's parameter
tree (``HybridDecoder`` with ``attn_mla`` mixers under a low-rank query, a
``SwiGLU`` or a ``DroplessMoE`` with a sigmoid router and a shared expert,
an ``MTP`` module). The program and the reference are both given these
values; the reference makes its own copy from the seed.

Every leaf is seeded: matrices normal at std 1/sqrt(fan_in), norm scales
at 1 + 0.1 N(0,1). What keeps a seeded model from degenerating:

* the embedding at std 1, unit-variance token vectors, as the Keye, ZAYA1
  and Kimi Linear configurations': at 0.02 the mixers' output (unit-scale
  after ``W_o``) would be all of a row, every token would route alike and a
  step's time would follow the seed; the head is untied and lecun, so logits
  are of unit scale and the first loss ~ln 19360 + 0.5;
* the router's selection bias at ``SELECT_STD``: small against the sigmoid
  scores' spread (~0.2), enough to move the choice of the tokens at the
  edge.
"""

from __future__ import annotations

import math

import jax
import jax.numpy as jnp

from benchmark.weights import seed_key

EMBED_STD = 1.0
SELECT_STD = 0.01
# Leaves held as {"kernel": ...} in the program's tree.
MLA_KERNELS = ("wq_a", "wq_b", "wkv_a", "wkv_b", "wo")
MLA_NORMS = ("q_norm", "kv_norm")
DENSE_KERNELS = ("w_gate", "w_up", "w_down")
EXPERT_LEAVES = ("w_router", "router_bias", "w_gate", "w_up", "w_down",
                 "shared_gate", "shared_up", "shared_down")
MTP_NORMS = ("e_norm", "h_norm", "final_norm")
# Leaves the chip's check leaves out of its widest-leaf numbers.
NOISE_LEAVES = ()


def layer_specs(ffn: str, cfg: dict) -> dict:
    """name -> (shape, how): a float std or None (a scale, 1 + 0.1 z)."""
    d, h, r, qr = cfg["hidden"], cfg["heads"], cfg["kv_rank"], cfg["q_rank"]
    lecun = lambda fan_in: 1.0 / math.sqrt(fan_in)
    specs = {
        "norm1": ((d,), None), "norm2": ((d,), None),
        "wq_a": ((d, qr), lecun(d)), "q_norm": ((qr,), None),
        "wq_b": ((qr, h * (cfg["nope"] + cfg["rope"])), lecun(qr)),
        "wkv_a": ((d, r + cfg["rope"]), lecun(d)), "kv_norm": ((r,), None),
        "wkv_b": ((r, h * (cfg["nope"] + cfg["v_dim"])), lecun(r)),
        "wo": ((h * cfg["v_dim"], d), lecun(h * cfg["v_dim"]))}
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


def blocks(cfg: dict) -> list:
    """(prefix, feed-forward) of every block: the stack's, then the
    multi-token-prediction module's (the last layer's kind)."""
    return [(f"L{i}", f) for i, f in enumerate(cfg["ffns"])] \
        + [("M", cfg["ffns"][-1])] * cfg["mtp"]


def leaf_specs(cfg: dict) -> dict:
    d, v = cfg["hidden"], cfg["vocab"]
    specs = {"embed": ((v, d), EMBED_STD), "final_norm": ((d,), None),
             "lm_head": ((d, v), 1.0 / math.sqrt(d))}
    for prefix, ffn in blocks(cfg):
        specs.update({f"{prefix}.{n}": s
                      for n, s in layer_specs(ffn, cfg).items()})
    if cfg["mtp"]:
        specs.update({f"M.{n}": ((d,), None) for n in MTP_NORMS})
        specs["M.w_eh"] = ((2 * d, d), 1.0 / math.sqrt(2 * d))
    return specs


def make_weights(cfg: dict, seed: int, dtype=jnp.float32) -> dict:
    """All leaves from ``seed`` in one jitted call, as ``dtype``. The
    router and its bias are drawn at their published width whichever
    experts are held, so every share of a layer routes alike."""
    specs = leaf_specs(cfg)

    def gen(key):
        out = {}
        for i, (name, (shape, how)) in enumerate(sorted(specs.items())):
            z = jax.random.normal(jax.random.fold_in(key, i), shape,
                                  jnp.float32)
            out[name] = (1.0 + 0.1 * z if how is None
                         else how * z).astype(dtype)
        return out

    return jax.jit(gen)(seed_key(seed))


def _layer_tree(ffn: str, mine: dict) -> dict:
    k = lambda n: {"kernel": mine[n]}
    mixer = {n: k(n) for n in MLA_KERNELS}
    mixer.update({n: {"scale": mine[n]} for n in MLA_NORMS})
    tree = {"norm1": {"scale": mine["norm1"]},
            "norm2": {"scale": mine["norm2"]}, "attn_mla": mixer}
    if ffn == "dense":
        tree["mlp"] = {n: k(n) for n in DENSE_KERNELS}
    else:
        tree["moe_mlp"] = {n: mine[n] for n in EXPERT_LEAVES}
    return tree


def _layer_flat(ffn: str, layer: dict) -> dict:
    mix = layer["attn_mla"]
    flat = {"norm1": layer["norm1"]["scale"], "norm2": layer["norm2"]["scale"],
            **{n: mix[n]["kernel"] for n in MLA_KERNELS},
            **{n: mix[n]["scale"] for n in MLA_NORMS}}
    if ffn == "dense":
        flat.update({n: layer["mlp"][n]["kernel"] for n in DENSE_KERNELS})
    else:
        flat.update({n: layer["moe_mlp"][n] for n in EXPERT_LEAVES})
    return flat


def _under(w: dict, prefix: str) -> dict:
    return {name[len(prefix) + 1:]: a for name, a in w.items()
            if name.startswith(prefix + ".")}


def to_program_tree(w: dict, cfg: dict) -> dict:
    """The flat layout as the program's ``params`` tree."""
    tree = {"embedding": w["embed"], "lm_head_kernel": w["lm_head"],
            "final_norm": {"scale": w["final_norm"]}}
    for i, ffn in enumerate(cfg["ffns"]):
        tree[f"layer_{i}"] = _layer_tree(ffn, _under(w, f"L{i}"))
    if cfg["mtp"]:
        mine = _under(w, "M")
        tree["mtp"] = {"layer": _layer_tree(cfg["ffns"][-1], mine),
                       "w_eh": {"kernel": mine["w_eh"]},
                       **{n: {"scale": mine[n]} for n in MTP_NORMS}}
    return tree


def from_program_tree(tree: dict, cfg: dict) -> dict:
    """Inverse of :func:`to_program_tree`, for anything shaped like the
    program's params (its optimizer moments)."""
    w = {"embed": tree["embedding"], "lm_head": tree["lm_head_kernel"],
         "final_norm": tree["final_norm"]["scale"]}
    for i, ffn in enumerate(cfg["ffns"]):
        w.update({f"L{i}.{n}": a
                  for n, a in _layer_flat(ffn, tree[f"layer_{i}"]).items()})
    if cfg["mtp"]:
        mtp = tree["mtp"]
        flat = {**_layer_flat(cfg["ffns"][-1], mtp["layer"]),
                "w_eh": mtp["w_eh"]["kernel"],
                **{n: mtp[n]["scale"] for n in MTP_NORMS}}
        w.update({f"M.{n}": a for n, a in flat.items()})
    return w
