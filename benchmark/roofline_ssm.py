"""The arithmetic of what the ``phi-4-mini-flash-reasoning`` configuration
adds: operations and bytes of a selective-scan call and of a differential
attention call (window-aware), and the model FLOPs a trained token
requires. jax-free; peaks and ``least_seconds`` are ``roofline.py``'s.

Counted as the algorithm needs them, not as the implementation spends
them: nothing recomputed (the backward's scores are counted once, the
scan's states never leave the chip's fast memory, so they cost no bytes),
attention over the keys a query can see — ``min(t + 1, window)`` under a
window, ``t + 1`` causally — with q and k at the published head size 64
(the kernel multiplies a zero-extended 128) and the pair's 128-wide V.
"""

from __future__ import annotations

from benchmark import modelcfg_phi4flash as mc

SCAN_FLOPS_STATE = 7     # per (t, e, n): dt*A, exp, *h, dx*B, +, h*C, +
SCAN_FLOPS_CHANNEL = 3   # per (t, e): dt*x, D*x, +


def ssm_scan_fwd(b: int, t: int, e: int, n: int, act_bytes: int = 2) -> tuple:
    """(flops, bytes) of one forward scan: xc (compute dtype), dt (f32),
    B and C read, y (f32) written once; nothing for the state."""
    flops = b * t * e * (SCAN_FLOPS_STATE * n + SCAN_FLOPS_CHANNEL)
    nbytes = b * t * (e * (act_bytes + 4 + 4) + 2 * n * act_bytes)
    return flops, nbytes


def ssm_scan_bwd(b: int, t: int, e: int, n: int, act_bytes: int = 2) -> tuple:
    """(flops, bytes) of the backward: twice the forward's operations (the
    adjoint recurrence and the four products that feed the gradients);
    the forward's inputs and dy (f32) read, d xc, d dt, dB, dC (f32)
    written; dA and dD are E x N and E."""
    flops = 2 * b * t * e * (SCAN_FLOPS_STATE * n + SCAN_FLOPS_CHANNEL)
    nbytes = b * t * (e * (act_bytes + 4 + 4 + 4 + 4)
                      + 2 * n * act_bytes + 2 * n * 4) + 4 * e * (n + 1)
    return flops, nbytes


def visited_keys(t: int, window: int | None) -> int:
    """Sum over the t queries of the keys each sees."""
    if window is None or window >= t:
        return t * (t + 1) // 2
    return window * (window + 1) // 2 + (t - window) * window


def diff_attn_fwd(b: int, t: int, heads: int, kv_heads: int, hd: int,
                  window: int | None = None, bytes_per: int = 2) -> tuple:
    """(flops, bytes) of one differential attention forward: ``heads``
    score maps (q_j k_j^T over hd) each times the pair's 2 hd-wide V; q,
    k, v read, the two outputs of each pair and the f32 log-sum-exp rows
    written."""
    keys = visited_keys(t, window)
    flops = b * heads * keys * (2 * hd + 2 * 2 * hd)
    nbytes = b * t * hd * (heads + 2 * kv_heads + 2 * heads) * bytes_per \
        + b * heads * t * 4
    return flops, nbytes


def diff_attn_bwd(b: int, t: int, heads: int, kv_heads: int, hd: int,
                  window: int | None = None, bytes_per: int = 2) -> tuple:
    """(flops, bytes) of the backward: dP and dV over 2 hd, dQ and dK over
    hd (the scores it rebuilds are the forward's, counted there); q, k, v,
    o, do and the log-sum-exp read, dq, dk, dv written."""
    keys = visited_keys(t, window)
    flops = b * heads * keys * (2 * 2 * 2 * hd + 2 * 2 * hd)
    nbytes = b * t * hd * (2 * heads + 4 * kv_heads + 4 * heads) * bytes_per \
        + b * heads * t * 4
    return flops, nbytes


def matmul_params(cfg: dict) -> int:
    """Parameters a token's forward pass multiplies by, over the layers'
    kinds, plus the tied head (the table is looked up at the bottom)."""
    d, f, e = cfg["hidden"], cfg["ffn"], mc.d_inner(cfg)
    n, r = cfg["ssm_state"], cfg["dt_rank"]
    q, kv = cfg["heads"] * cfg["head_dim"], cfg["kv_heads"] * cfg["head_dim"]
    mixer = {"mamba": d * 2 * e + e * (r + 2 * n) + r * e + e * d,
             "gmu": 2 * d * e,
             "swa": d * (q + 2 * kv) + q * d,
             "cross": 2 * d * q}
    mixer["full"] = mixer["swa"]
    return sum(mixer[k] + 3 * d * f for k in cfg["kinds"]) \
        + d * cfg["vocab"]


def train_flops_per_token(cfg: dict, seq: int) -> float:
    """Forward + backward of one token: 6 per multiplied parameter; each
    attention layer's forward and backward over the keys its queries see;
    three times the forward scan and convolution of each state-space
    layer."""
    hd, heads, kvh = cfg["head_dim"], cfg["heads"], cfg["kv_heads"]
    e, n = mc.d_inner(cfg), cfg["ssm_state"]
    total = 6.0 * matmul_params(cfg)
    for kind in cfg["kinds"]:
        if kind in ("swa", "full", "cross"):
            w = cfg["window"] if kind == "swa" else None
            total += (diff_attn_fwd(1, seq, heads, kvh, hd, w)[0]
                      + diff_attn_bwd(1, seq, heads, kvh, hd, w)[0]) / seq
        elif kind == "mamba":
            total += 3 * (e * (SCAN_FLOPS_STATE * n + SCAN_FLOPS_CHANNEL)
                          + 2 * cfg["ssm_conv"] * e)
    return total
