"""The yardstick's arithmetic for the ``glm-4.7-flash`` configuration: the
model FLOPs a trained token requires and the work of its two kinds of
kernel call. jax-free.

Counted as the algorithm needs them at the **published** shape, not as any
implementation spends them: latent attention over the causal half with
q.k over ``nope + rope`` (256) and p.v over ``v_dim`` (256), the backward
at twice the forward (dV, dP, dQ, dK: the scores a flash backward takes
again are a recomputation, and no work the model owes) and charged once
however many kernels share it, nothing padded; the bytes of a head's own
key part and of the ONE shared rotated part (a program that writes the
shared part out beside every head reads more than this, and is not
credited for it); the held experts at the rows they were sent where those
were counted (else at what an even routing sends them), the shared expert
at every token; the rotation, norms and gates as the elementwise work they
are (no matmul FLOPs); the embedding looked up (a gather, twice); the
untied head multiplied twice (the stack's rows and the multi-token-
prediction module's), ``W_eh`` and that module's block once each.
"""

from __future__ import annotations

from benchmark.roofline_keyevl2 import grouped_matmul  # noqa: F401 (reader)


def mla_fwd(b: int, heads: int, s: int, qk: int, v: int, shared: int,
            bytes_per: int = 2) -> tuple:
    """(flops, bytes) of one causal forward: q.k over ``qk`` and p.v over
    ``v`` on half the square; q, each head's own key part and v, and the
    ``shared`` key part once, read, o and the float32 log-sum-exp
    written."""
    flops = 2 * b * heads * (qk + v) * s * s // 2
    nbytes = b * s * (heads * (qk + (qk - shared) + 2 * v) + shared) \
        * bytes_per + b * heads * s * 4
    return flops, nbytes


def mla_bwd(b: int, heads: int, s: int, qk: int, v: int, shared: int,
            bytes_per: int = 2) -> tuple:
    """(flops, bytes) of the causal backward, twice the forward: dQ and dK
    over ``qk``, dP and dV over ``v``; the forward's operands, o and do
    read, the gradients of q, of each head's key part, of the shared part
    and of v written."""
    flops = 2 * mla_fwd(b, heads, s, qk, v, shared)[0]
    nbytes = 2 * b * s * (heads * (qk + (qk - shared) + 2 * v) + shared) \
        * bytes_per + b * heads * s * 4
    return flops, nbytes


def block_params(cfg: dict, ffn: str, held_rows: float) -> float:
    """Parameters a token's forward pass multiplies by in one block: the
    query's two products, the key/value latent's two, ``W_o``, and the
    dense SwiGLU or the router, the shared expert and ``held_rows`` held
    experts."""
    d, h = cfg["hidden"], cfg["heads"]
    qk = cfg["nope"] + cfg["rope"]
    mla = d * cfg["q_rank"] + cfg["q_rank"] * h * qk \
        + d * (cfg["kv_rank"] + cfg["rope"]) \
        + cfg["kv_rank"] * h * (cfg["nope"] + cfg["v_dim"]) \
        + h * cfg["v_dim"] * d
    if ffn == "dense":
        return mla + 3 * d * cfg["dense_ffn"]
    return mla + d * cfg["experts"] + 3 * d * cfg["ffn"] * (
        cfg["shared"] + held_rows)


def matmul_params(cfg: dict, held_rows: float = None) -> float:
    """Parameters a token's forward pass multiplies by: every block of
    the stack, the multi-token-prediction module (``W_eh`` and one more
    block of the last layer's kind) and the head once for each loss.
    ``held_rows``: experts held here that a token is sent to, a layer —
    counted, else what an even routing sends (``top_k x held /
    experts``)."""
    if held_rows is None:
        held_rows = cfg["top_k"] * cfg["experts_held"] / cfg["experts"]
    d = cfg["hidden"]
    ffns = list(cfg["ffns"]) + cfg["ffns"][-1:] * cfg["mtp"]
    return sum(block_params(cfg, f, held_rows) for f in ffns) \
        + cfg["mtp"] * 2 * d * d + (1 + cfg["mtp"]) * d * cfg["vocab"]


def mixer_flops_per_token(cfg: dict, seq: int) -> float:
    """Forward + backward of every block's attention products, a token:
    the causal half, the backward at twice the forward."""
    dims = (1, cfg["heads"], seq, cfg["nope"] + cfg["rope"], cfg["v_dim"],
            cfg["rope"])
    return (len(cfg["ffns"]) + cfg["mtp"]) * 3 * mla_fwd(*dims)[0] / seq


def train_flops_per_token(cfg: dict, seq: int,
                          held_rows: float = None) -> float:
    """Forward + backward of one token: 6 per multiplied parameter and
    each block's attention (``held_rows``: :func:`matmul_params`)."""
    return 6.0 * matmul_params(cfg, held_rows) \
        + mixer_flops_per_token(cfg, seq)
