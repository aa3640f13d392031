"""``configs/zaya1-8b.json`` as the sizes the benchmark computes with, and
the keyword arguments that make the registry's ``zaya1-8b`` that
configuration. jax-free. (``modelcfg.py`` knows the dense decoder's keys
only.)"""

from __future__ import annotations

import json
from pathlib import Path

HERE = Path(__file__).resolve().parent


def load(name: str) -> dict:
    raw = json.loads((HERE / "configs" / f"{name}.json").read_text())
    return {
        "name": name, "hidden": raw["hidden_size"],
        "heads": raw["num_attention_heads"],
        "kv_heads": raw["num_key_value_heads"], "head_dim": raw["head_dim"],
        "taps": [raw["cca_time0"], raw["cca_time1"]],
        "rope_fraction": raw["partial_rotary_factor"],
        "rope_theta": float(raw["rope_parameters"]["hybrid"]["rope_theta"]),
        "ffn": raw["moe_intermediate_size"],
        "experts": raw["held"]["router_outputs"],    # the router's width
        "experts_held": raw["num_experts"],          # held here (reduced)
        "expert_offset": raw["held"]["expert_offset"],
        "top_k": raw["num_experts_per_tok"],
        "router_hidden": raw["router_hidden_size"],
        "vocab": raw["vocab_size"], "layers": raw["num_hidden_layers"],
        "eps": raw["rms_norm_eps"], "program": raw["program"],
    }


def program_kwargs(cfg: dict, seq: int) -> dict:
    return dict(vocab=cfg["vocab"], dim=cfg["hidden"], n_layers=cfg["layers"],
                n_heads=cfg["heads"], n_kv_heads=cfg["kv_heads"],
                attn_head_dim=cfg["head_dim"], cca_taps=cfg["taps"],
                rope_fraction=cfg["rope_fraction"],
                rope_theta=cfg["rope_theta"], ffn_hidden=cfg["ffn"],
                max_seq=seq, norm_eps=cfg["eps"], moe_experts=cfg["experts"],
                moe_top_k=cfg["top_k"], moe_experts_held=cfg["experts_held"],
                moe_expert_offset=cfg["expert_offset"],
                router_hidden=cfg["router_hidden"],
                xent_chunk=cfg["program"]["xent_chunk"])


def tiny(cfg: dict) -> dict:
    """The same layer at a size the CPU holds: rehearsals and tests only,
    never a result. 4 of 8 experts held, from the third on."""
    return dict(cfg, hidden=64, heads=4, kv_heads=2, head_dim=8, ffn=32,
                experts=8, experts_held=4, expert_offset=2, router_hidden=16,
                vocab=256, layers=2,
                program=dict(cfg["program"], xent_chunk=32))


def param_count(cfg: dict) -> dict:
    """Parameters of one layer by part, the one table and the total."""
    d, hd, r = cfg["hidden"], cfg["head_dim"], cfg["router_hidden"]
    q, kv = cfg["heads"] * hd, cfg["kv_heads"] * hd
    lanes, (k0, k1) = q + kv, cfg["taps"]
    out = {"attention": d * (q + 2 * kv) + q * d,
           "cca_mix": k0 * lanes + lanes + k1 * (lanes // hd) * hd * hd
           + lanes + cfg["kv_heads"],
           "norms": 2 * d,
           "router": d * r + r + 2 * (r * r + r) + r * cfg["experts"] + 2 * r,
           "experts_held": cfg["experts_held"] * 3 * d * cfg["ffn"]}
    out["layer"] = sum(out.values())
    out["embedding"] = cfg["vocab"] * d
    out["total"] = cfg["layers"] * out["layer"] + out["embedding"] + d
    return out
