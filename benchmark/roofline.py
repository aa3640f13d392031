"""The yardstick's arithmetic: peaks by ``device_kind``, the operations and
bytes a kernel's algorithm needs for a call (from its shapes alone), and
the model FLOPs a trained token requires. jax-free.

Counted as the algorithm needs them, not as any implementation spends
them: attention causally (half the square), nothing recomputed, and the
embedding lookup as the gather it is (no matmul FLOPs).
"""

from __future__ import annotations

import json
from pathlib import Path

HERE = Path(__file__).resolve().parent


def peaks(kind: str) -> dict:
    table = json.loads((HERE / "peaks.json").read_text())
    if kind not in table or kind == "source":
        raise KeyError(f"device kind {kind!r} is not in peaks.json")
    return table[kind]


def matmul_params(cfg: dict) -> int:
    """Parameters a token's forward pass multiplies by: projections, the
    FFN, the head. The embedding table is looked up, not multiplied."""
    d, f = cfg["hidden"], cfg["ffn"]
    q, kv = cfg["heads"] * cfg["head_dim"], cfg["kv_heads"] * cfg["head_dim"]
    return cfg["layers"] * (d * (q + 2 * kv) + q * d + 3 * d * f) \
        + d * cfg["vocab"]


def train_flops_per_token(cfg: dict, seq: int) -> int:
    """Forward + backward: 6 per multiplied parameter, plus causal
    attention — per layer and token 2 matmuls x 2 FLOPs x heads x head_dim
    x (seq+1)/2 keys forward, twice that backward."""
    attention = 6 * cfg["layers"] * cfg["heads"] * cfg["head_dim"] * (seq + 1)
    return 6 * matmul_params(cfg) + attention


def flash_fwd(b: int, heads: int, kv_heads: int, s: int, hd: int,
              bytes_per: int = 2) -> tuple:
    """(flops, bytes) of one causal flash forward over [b, s]: QK^T and PV
    over half the square; q, k, v read and o written once, plus the f32
    log-sum-exp row."""
    flops = 4 * b * heads * s * s * hd // 2
    nbytes = b * s * hd * (2 * heads + 2 * kv_heads) * bytes_per \
        + b * heads * s * 4
    return flops, nbytes


def flash_bwd(b: int, heads: int, kv_heads: int, s: int, hd: int,
              bytes_per: int = 2) -> tuple:
    """(flops, bytes) of the causal backward: five matmuls where the
    forward has two (scores again, dV, dP, dQ, dK); q, k, v, o, do and the
    log-sum-exp read, dq, dk, dv written."""
    flops = 10 * b * heads * s * s * hd // 2
    nbytes = b * s * hd * (4 * heads + 4 * kv_heads) * bytes_per \
        + b * heads * s * 4
    return flops, nbytes


def least_seconds(flops: float, nbytes: float, peak: dict) -> tuple:
    """The least time the chip could take and which peak bounds it."""
    compute = flops / peak["bf16_flops"]
    memory = nbytes / peak["hbm_bytes_per_s"]
    return (compute, "compute") if compute >= memory else (memory, "memory")
