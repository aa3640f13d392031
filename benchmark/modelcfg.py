"""A configuration file (benchmark/configs/<name>.json) as the sizes the
benchmark computes with. jax-free."""

from __future__ import annotations

import json
from pathlib import Path

HERE = Path(__file__).resolve().parent


def load(name: str) -> dict:
    """Sizes of configuration ``name``: the source's keys under the names
    the benchmark's own code uses."""
    raw = json.loads((HERE / "configs" / f"{name}.json").read_text())
    return {
        "name": name,
        "hidden": raw["hidden_size"], "ffn": raw["intermediate_size"],
        "heads": raw["num_attention_heads"],
        "kv_heads": raw["num_key_value_heads"],
        "head_dim": raw["hidden_size"] // raw["num_attention_heads"],
        "vocab": raw["vocab_size"], "layers": raw["num_hidden_layers"],
        "rope_theta": raw["rope_theta"], "eps": raw["rms_norm_eps"],
        "program": raw["program"],
    }


def program_kwargs(cfg: dict, max_seq: int) -> dict:
    """Keyword arguments that make the registry's generic decoder this
    configuration (``get_model(cfg["program"]["model"], **kwargs)``)."""
    return dict(vocab=cfg["vocab"], dim=cfg["hidden"], n_layers=cfg["layers"],
                n_heads=cfg["heads"], n_kv_heads=cfg["kv_heads"],
                ffn_hidden=cfg["ffn"], max_seq=max_seq,
                rope_theta=cfg["rope_theta"], norm_eps=cfg["eps"])


def tiny(cfg: dict) -> dict:
    """The same architecture at a size the CPU holds: rehearsals and the
    tests only, never a result."""
    return dict(cfg, hidden=64, ffn=128, heads=4, kv_heads=2, head_dim=16,
                vocab=256, layers=min(cfg["layers"], 2))
