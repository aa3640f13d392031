"""``configs/keye-vl-2.0-30b-a3b.json`` as the sizes the benchmark computes
with, and the keyword arguments that make the registry's
``keye-vl-2.0-30b-a3b`` that configuration. jax-free. (``modelcfg.py``
knows the dense decoder's keys only.)"""

from __future__ import annotations

import json
from pathlib import Path

HERE = Path(__file__).resolve().parent


def load(name: str) -> dict:
    raw = json.loads((HERE / "configs" / f"{name}.json").read_text())
    sa = raw["sa_config"]
    return {
        "name": name, "hidden": raw["hidden_size"],
        "heads": raw["num_attention_heads"],
        "kv_heads": raw["num_key_value_heads"], "head_dim": raw["head_dim"],
        "ffn": raw["moe_intermediate_size"],
        "experts": raw["num_local_experts"],         # the router's width
        "experts_held": raw["num_experts"],          # held here (reduced)
        "expert_offset": raw["held"]["expert_offset"],
        "top_k": raw["num_experts_per_tok"],
        "index_heads": sa["indexer_num_heads"],
        "index_dim": sa["indexer_head_dim"], "index_topk": sa["topk"],
        "vocab": raw["vocab_size"], "layers": raw["num_hidden_layers"],
        "rope_theta": float(raw["rope_theta"]), "eps": raw["rms_norm_eps"],
        "program": raw["program"],
    }


def program_kwargs(cfg: dict, seq: int) -> dict:
    p = cfg["program"]
    return dict(vocab=cfg["vocab"], dim=cfg["hidden"], n_layers=cfg["layers"],
                n_heads=cfg["heads"], n_kv_heads=cfg["kv_heads"],
                attn_head_dim=cfg["head_dim"], ffn_hidden=cfg["ffn"],
                max_seq=seq, rope_theta=cfg["rope_theta"],
                norm_eps=cfg["eps"], moe_experts=cfg["experts"],
                moe_top_k=cfg["top_k"], moe_experts_held=cfg["experts_held"],
                moe_expert_offset=cfg["expert_offset"],
                index_heads=cfg["index_heads"], index_dim=cfg["index_dim"],
                index_topk=cfg["index_topk"], xent_chunk=p["xent_chunk"])


def tiny(cfg: dict) -> dict:
    """The same layer at a size the CPU holds: rehearsals and tests only,
    never a result. A selection of 16 keys of 64, 2 of 8 experts held."""
    return dict(cfg, hidden=64, heads=4, kv_heads=2, head_dim=32, ffn=32,
                experts=8, experts_held=2, expert_offset=2, top_k=2,
                index_heads=2, index_dim=16, index_topk=16, vocab=256,
                layers=2, program=dict(cfg["program"], xent_chunk=32))


def param_count(cfg: dict) -> dict:
    """Parameters of one layer by part, the two tables and the total."""
    d, hd = cfg["hidden"], cfg["head_dim"]
    q, kv = cfg["heads"] * hd, cfg["kv_heads"] * hd
    j, e = cfg["index_heads"], cfg["index_dim"]
    out = {"attention": d * (q + 2 * kv) + q * d, "qk_norm": 2 * hd,
           "indexer": d * (j * e + e + j), "norms": 2 * d,
           "router": d * cfg["experts"],
           "experts_held": cfg["experts_held"] * 3 * d * cfg["ffn"]}
    out["layer"] = sum(out.values())
    out["embedding"] = out["lm_head"] = cfg["vocab"] * d
    out["total"] = cfg["layers"] * out["layer"] + 2 * cfg["vocab"] * d + d
    return out
