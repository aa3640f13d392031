"""``configs/phi-4-mini-flash-reasoning.json`` as the sizes the benchmark
computes with, and the keyword arguments that make the registry's
``hybrid-decoder`` that configuration. jax-free. (``modelcfg.py`` knows the
dense decoder's keys only.)"""

from __future__ import annotations

import json
import math
from pathlib import Path

HERE = Path(__file__).resolve().parent


def load(name: str) -> dict:
    raw = json.loads((HERE / "configs" / f"{name}.json").read_text())
    a = raw["assumed"]
    d = raw["hidden_size"]
    return {
        "name": name, "hidden": d, "ffn": raw["intermediate_size"],
        "heads": raw["num_attention_heads"],
        "kv_heads": raw["num_key_value_heads"],
        "head_dim": d // raw["num_attention_heads"],
        "vocab": raw["vocab_size"], "layers": raw["num_hidden_layers"],
        "kinds": list(raw["layer_kinds"]), "window": raw["sliding_window"],
        "eps": raw["layer_norm_eps"],
        "ssm_state": a["mamba_d_state"]["value"],
        "ssm_conv": a["mamba_d_conv"]["value"],
        "ssm_expand": a["mamba_expand"]["value"],
        "dt_rank": a["mamba_dt_rank"]["value"],
        "program": raw["program"],
    }


def d_inner(cfg: dict) -> int:
    return cfg["ssm_expand"] * cfg["hidden"]


def program_kwargs(cfg: dict) -> dict:
    p = cfg["program"]
    return dict(vocab=cfg["vocab"], dim=cfg["hidden"], ffn_hidden=cfg["ffn"],
                n_heads=cfg["heads"], n_kv_heads=cfg["kv_heads"],
                layers=cfg["kinds"], window=cfg["window"],
                ssm_state=cfg["ssm_state"], ssm_conv=cfg["ssm_conv"],
                ssm_expand=cfg["ssm_expand"], ssm_dt_rank=cfg["dt_rank"],
                norm_eps=cfg["eps"], scan_chunk=p["scan_chunk"],
                xent_chunk=p["xent_chunk"])


def tiny(cfg: dict) -> dict:
    """The same six kinds at a size the CPU holds: rehearsals and tests
    only, never a result."""
    return dict(cfg, hidden=64, ffn=128, heads=4, kv_heads=2, head_dim=16,
                vocab=256, window=16, ssm_state=4, dt_rank=math.ceil(64 / 16),
                program=dict(cfg["program"], scan_chunk=8, xent_chunk=32))


def param_count(cfg: dict) -> dict:
    """Parameters by layer kind (mixer + MLP + the two norms), the tied
    table and the total."""
    d, f, e = cfg["hidden"], cfg["ffn"], d_inner(cfg)
    n, r, k = cfg["ssm_state"], cfg["dt_rank"], cfg["ssm_conv"]
    q, kv = cfg["heads"] * cfg["head_dim"], cfg["kv_heads"] * cfg["head_dim"]
    hd = cfg["head_dim"]
    lam = 4 * hd + 2 * hd
    mixer = {
        "mamba": d * 2 * e + k * e + e + e * (r + 2 * n) + r * e + e
        + e * n + e + e * d,
        "gmu": 2 * d * e,
        "swa": d * (q + 2 * kv) + q + 2 * kv + lam + q * d + d,
        "cross": d * q + q + lam + q * d + d,
    }
    mixer["full"] = mixer["swa"]
    rest = 3 * d * f + 4 * d
    out = {kind: mixer[kind] + rest for kind in mixer}
    out["embed"] = cfg["vocab"] * d
    out["total"] = sum(out[kind] for kind in cfg["kinds"]) + out["embed"] \
        + 2 * d
    return out
