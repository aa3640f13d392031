"""``configs/olmo-hybrid-7b.json`` as the sizes the benchmark computes with,
and the keyword arguments that make the registry's ``olmo-hybrid-7b`` that
configuration. jax-free. (``modelcfg.py`` knows the dense decoder's keys
only.)"""

from __future__ import annotations

import json
from pathlib import Path

HERE = Path(__file__).resolve().parent
KINDS = {"linear_attention": "gdn", "full_attention": "attn"}


def load(name: str) -> dict:
    raw = json.loads((HERE / "configs" / f"{name}.json").read_text())
    n, held = raw["num_hidden_layers"], raw["held"]
    assert raw["linear_num_key_heads"] == raw["linear_num_value_heads"] \
        == raw["num_attention_heads"] == raw["num_key_value_heads"]
    assert raw["rope_parameters"]["rope_theta"] is None     # no rotation
    return {
        "name": name, "hidden": raw["hidden_size"], "layers": n,
        # the published list's first n entries are the cut's layers
        "kinds": [KINDS[t] for t in raw["layer_types"][:n]],
        "ffn": raw["intermediate_size"],
        # HELD here (reduced); the published counts beside them
        "heads": raw["num_attention_heads"],
        "kv_heads": raw["num_key_value_heads"],
        "heads_total": held["heads_total"],
        "head_dim": raw["hidden_size"] // held["heads_total"],
        "gdn_heads": raw["linear_num_key_heads"],
        "gdn_heads_total": held["linear_heads_total"],
        "dk": raw["linear_key_head_dim"], "dv": raw["linear_value_head_dim"],
        "conv": raw["linear_conv_kernel_dim"],
        "neg_eigval": raw["linear_allow_neg_eigval"],
        "vocab": raw["vocab_size"], "eps": raw["rms_norm_eps"],
        "program": raw["program"],
    }


def program_kwargs(cfg: dict) -> dict:
    prog = cfg["program"]
    return dict(
        vocab=cfg["vocab"], dim=cfg["hidden"], layers=cfg["kinds"],
        ffn_hidden=cfg["ffn"], norm_eps=cfg["eps"],
        n_heads=cfg["heads_total"], n_kv_heads=cfg["heads_total"],
        gdn_heads=cfg["gdn_heads_total"], heads_held=cfg["heads"],
        gdn_key_dim=cfg["dk"], gdn_value_dim=cfg["dv"],
        gdn_conv=cfg["conv"], gdn_neg_eigval=cfg["neg_eigval"],
        kda_chunk=prog["kda_chunk"], kda_keep=prog["kda_keep"],
        xent_chunk=prog["xent_chunk"])


def tiny(cfg: dict) -> dict:
    """The same layers at a size the CPU holds: rehearsals and tests only,
    never a result. 2 of 4 heads held, head sizes off the lane width."""
    return dict(cfg, hidden=64, ffn=128, heads=2, kv_heads=2, heads_total=4,
                head_dim=16, gdn_heads=2, gdn_heads_total=4, dk=12, dv=24,
                vocab=256,
                program=dict(cfg["program"], xent_chunk=32, kda_chunk=8,
                             kda_keep=2))


def param_count(cfg: dict) -> dict:
    """Parameters by part (the table of ISSUE 40) and the total, at the
    heads held."""
    d, h, gh = cfg["hidden"], cfg["heads"], cfg["gdn_heads"]
    k, v = gh * cfg["dk"], gh * cfg["dv"]
    out = {
        # q, k, v, z, o; a, b; the three convolutions; A_log, dt_bias, the
        # head norm
        "gdn_mixer": 2 * d * k + 2 * d * v + v * d + 2 * d * gh
        + cfg["conv"] * (2 * k + v) + 2 * gh + cfg["dv"],
        # q, k, v, o; the q- and k-norm's scales
        "attn_mixer": 4 * d * h * cfg["head_dim"] + 2 * h * cfg["head_dim"],
        "ffn": 3 * d * cfg["ffn"]}
    for kind in ("gdn", "attn"):
        out[f"{kind}_layer"] = out[f"{kind}_mixer"] + out["ffn"] + 2 * d
    out["embedding_head_final_norm"] = 2 * cfg["vocab"] * d + d
    out["total"] = sum(out[f"{kind}_layer"] for kind in cfg["kinds"]) \
        + out["embedding_head_final_norm"]
    return out
