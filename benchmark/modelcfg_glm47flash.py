"""``configs/glm-4.7-flash.json`` as the sizes the benchmark computes with,
and the keyword arguments that make the registry's ``glm-4.7-flash`` that
configuration. jax-free. (``modelcfg.py`` knows the dense decoder's keys
only.)"""

from __future__ import annotations

import json
from pathlib import Path

HERE = Path(__file__).resolve().parent


def load(name: str) -> dict:
    raw = json.loads((HERE / "configs" / f"{name}.json").read_text())
    n = raw["num_hidden_layers"]
    return {
        "name": name, "hidden": raw["hidden_size"], "layers": n,
        "kinds": ["mla"] * n,
        "ffns": ["dense" if i < raw["first_k_dense_replace"] else "experts"
                 for i in range(n)],
        "dense_ffn": raw["intermediate_size"],
        "ffn": raw["moe_intermediate_size"],
        "experts": raw["held"]["router_outputs"],    # the router's width
        "experts_held": raw["n_routed_experts"],     # held here (reduced)
        "expert_offset": raw["held"]["expert_offset"],
        "top_k": raw["num_experts_per_tok"],
        "shared": raw["n_shared_experts"],
        "route_scale": raw["routed_scaling_factor"],
        "heads": raw["num_attention_heads"],
        "q_rank": raw["q_lora_rank"], "kv_rank": raw["kv_lora_rank"],
        "nope": raw["qk_nope_head_dim"], "rope": raw["qk_rope_head_dim"],
        "v_dim": raw["v_head_dim"], "theta": float(raw["rope_theta"]),
        "vocab": raw["vocab_size"], "eps": raw["rms_norm_eps"],
        "mtp": raw["num_nextn_predict_layers"],
        "mtp_weight": raw["program"]["mtp_weight"],
        "program": raw["program"],
    }


def program_kwargs(cfg: dict, seq: int) -> dict:
    prog = cfg["program"]
    return dict(
        vocab=cfg["vocab"], dim=cfg["hidden"], layers=cfg["kinds"],
        ffns=[["dense", cfg["dense_ffn"]] if f == "dense" else f
              for f in cfg["ffns"]],
        norm="rmsnorm", tie_embeddings=False, norm_eps=cfg["eps"],
        mla_heads=cfg["heads"], mla_q_rank=cfg["q_rank"],
        mla_kv_rank=cfg["kv_rank"], mla_nope_dim=cfg["nope"],
        mla_rope_dim=cfg["rope"], mla_v_dim=cfg["v_dim"],
        mla_rope_theta=cfg["theta"],
        moe_experts=cfg["experts"], moe_top_k=cfg["top_k"],
        moe_experts_held=cfg["experts_held"],
        moe_expert_offset=cfg["expert_offset"], moe_ffn=cfg["ffn"],
        moe_shared=cfg["shared"], moe_route_scale=cfg["route_scale"],
        mtp_layers=cfg["mtp"], mtp_weight=cfg["mtp_weight"],
        xent_chunk=prog["xent_chunk"])


def tiny(cfg: dict) -> dict:
    """The same layers at a size the CPU holds: rehearsals and tests only,
    never a result. Two layers (the dense one and an expert one) and the
    MTP module; 4 of 16 experts held, from the third on, 2 a token; head
    parts 24 + 8 under values of 32."""
    return dict(cfg, hidden=64, layers=2, kinds=["mla"] * 2,
                ffns=["dense", "experts"], dense_ffn=128, ffn=32,
                experts=16, experts_held=4, expert_offset=2, top_k=2,
                heads=2, q_rank=24, kv_rank=32, nope=24, rope=8, v_dim=32,
                vocab=256, program=dict(cfg["program"], xent_chunk=32))


def param_count(cfg: dict) -> dict:
    """Parameters by part (the lines of ISSUE 45) and the total."""
    d, h, r, qr = cfg["hidden"], cfg["heads"], cfg["kv_rank"], cfg["q_rank"]
    qk = cfg["nope"] + cfg["rope"]
    mla = d * qr + qr + qr * h * qk + d * (r + cfg["rope"]) + r \
        + r * h * (cfg["nope"] + cfg["v_dim"]) + h * cfg["v_dim"] * d
    expert = 3 * d * cfg["ffn"]
    out = {"mla_mixer": mla, "one_expert": expert,
           "expert_ffn_held": (cfg["experts_held"] + cfg["shared"]) * expert
           + d * cfg["experts"] + cfg["experts"],
           "dense_ffn": 3 * d * cfg["dense_ffn"]}
    out["expert_block"] = mla + out["expert_ffn_held"] + 2 * d
    out["dense_block"] = mla + out["dense_ffn"] + 2 * d
    out["embedding_head_final_norm"] = 2 * cfg["vocab"] * d + d
    # Two norms, W_eh, a block of the last layer's kind, the head's norm.
    last = "dense_block" if cfg["ffns"][-1] == "dense" else "expert_block"
    out["mtp_module"] = cfg["mtp"] * (2 * d + 2 * d * d + out[last] + d)
    out["total"] = out["embedding_head_final_norm"] + out["mtp_module"] + sum(
        out["dense_block" if f == "dense" else "expert_block"]
        for f in cfg["ffns"])
    return out
