"""``configs/kimi-linear-48b-a3b.json`` as the sizes the benchmark computes
with, and the keyword arguments that make the registry's
``kimi-linear-48b-a3b`` that configuration. jax-free. (``modelcfg.py`` knows
the dense decoder's keys only.)"""

from __future__ import annotations

import json
from pathlib import Path

HERE = Path(__file__).resolve().parent


def load(name: str) -> dict:
    raw = json.loads((HERE / "configs" / f"{name}.json").read_text())
    lin, n = raw["linear_attn_config"], raw["num_hidden_layers"]
    # The published lists count layers from 1; the first n are the cut's.
    kinds = ["kda" if i in lin["kda_layers"] else "mla"
             for i in range(1, n + 1)]
    assert all((i in lin["full_attn_layers"]) == (k == "mla")
               for i, k in zip(range(1, n + 1), kinds))
    return {
        "name": name, "hidden": raw["hidden_size"], "layers": n,
        "kinds": kinds,
        "ffns": ["dense" if i < raw["first_k_dense_replace"] else "experts"
                 for i in range(n)],
        "dense_ffn": raw["intermediate_size"],
        "ffn": raw["moe_intermediate_size"],
        "experts": raw["held"]["router_outputs"],    # the router's width
        "experts_held": raw["num_experts"],          # held here (reduced)
        "expert_offset": raw["held"]["expert_offset"],
        "top_k": raw["num_experts_per_token"],
        "shared": raw["num_shared_experts"],
        "route_scale": raw["routed_scaling_factor"],
        "kda_heads": lin["num_heads"], "kda_head_dim": lin["head_dim"],
        "conv": lin["short_conv_kernel_size"],
        "mla_heads": raw["num_attention_heads"],
        "kv_rank": raw["kv_lora_rank"], "nope": raw["qk_nope_head_dim"],
        "rope": raw["qk_rope_head_dim"], "v_dim": raw["v_head_dim"],
        "vocab": raw["vocab_size"], "eps": raw["rms_norm_eps"],
        "program": raw["program"],
    }


def program_kwargs(cfg: dict, seq: int) -> dict:
    prog = cfg["program"]
    return dict(
        vocab=cfg["vocab"], dim=cfg["hidden"], layers=cfg["kinds"],
        ffns=[["dense", cfg["dense_ffn"]] if f == "dense" else f
              for f in cfg["ffns"]],
        norm="rmsnorm", tie_embeddings=False, norm_eps=cfg["eps"],
        kda_heads=cfg["kda_heads"], kda_head_dim=cfg["kda_head_dim"],
        kda_conv=cfg["conv"], kda_chunk=prog["kda_chunk"],
        kda_keep=prog["kda_keep"], mla_heads=cfg["mla_heads"],
        mla_kv_rank=cfg["kv_rank"], mla_nope_dim=cfg["nope"],
        mla_rope_dim=cfg["rope"], mla_v_dim=cfg["v_dim"],
        moe_experts=cfg["experts"], moe_top_k=cfg["top_k"],
        moe_experts_held=cfg["experts_held"],
        moe_expert_offset=cfg["expert_offset"], moe_ffn=cfg["ffn"],
        moe_shared=cfg["shared"], moe_route_scale=cfg["route_scale"],
        xent_chunk=prog["xent_chunk"])


def tiny(cfg: dict) -> dict:
    """The same layers at a size the CPU holds: rehearsals and tests only,
    never a result. 4 of 16 experts held, from the third on, 2 a token."""
    return dict(cfg, hidden=64, dense_ffn=128, ffn=32, experts=16,
                experts_held=4, expert_offset=2, top_k=2, kda_heads=2,
                kda_head_dim=16, mla_heads=2, kv_rank=32, nope=16, rope=8,
                v_dim=16, vocab=256,
                program=dict(cfg["program"], xent_chunk=32, kda_chunk=8,
                             kda_keep=2))


def param_count(cfg: dict) -> dict:
    """Parameters by part (the table of ISSUE 38) and the total."""
    d = cfg["hidden"]
    e, hd = cfg["kda_heads"] * cfg["kda_head_dim"], cfg["kda_head_dim"]
    kda = 3 * d * e + 3 * cfg["conv"] * e + (d * hd + hd * e) \
        + d * cfg["kda_heads"] + (d * hd + hd * e + e) \
        + cfg["kda_heads"] + e + hd + e * d
    h, r = cfg["mla_heads"], cfg["kv_rank"]
    mla = d * h * (cfg["nope"] + cfg["rope"]) + d * (r + cfg["rope"]) + r \
        + r * h * (cfg["nope"] + cfg["v_dim"]) + h * cfg["v_dim"] * d
    expert = 3 * d * cfg["ffn"]
    out = {"kda_mixer": kda, "mla_mixer": mla, "one_expert": expert,
           "expert_layer_held": (cfg["experts_held"] + cfg["shared"]) * expert
           + d * cfg["experts"] + cfg["experts"],
           "dense_mlp": 3 * d * cfg["dense_ffn"]}
    total = 0
    for kind, ffn in zip(cfg["kinds"], cfg["ffns"]):
        total += out[f"{kind}_mixer"] + 2 * d + (
            out["dense_mlp"] if ffn == "dense" else out["expert_layer_held"])
    out["embedding_head_final_norm"] = 2 * cfg["vocab"] * d + d
    out["total"] = total + out["embedding_head_final_norm"]
    return out
