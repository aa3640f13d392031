"""The yardstick's arithmetic for the ``kimi-linear-48b-a3b`` configuration:
the model FLOPs a trained token requires and the work of its three kinds of
kernel call. jax-free.

Counted as the algorithm needs them, not as any implementation spends
them: latent attention over the causal half with q.k over ``nope + rope``
and p.v over ``v_dim``, the linear attention as **the recurrence's own
work** (a function of tokens, heads and the ``d x d`` state: never of a
chunk length or of how a kernel solves a chunk), the held experts at the
rows they were sent where those were counted (else at what an even
routing sends them), the shared expert at every token,
convolutions, norms and gates as the elementwise work they are (no matmul
FLOPs), nothing recomputed, the embedding looked up (a gather) and the
untied head multiplied once.
"""

from __future__ import annotations

from benchmark.roofline_keyevl2 import grouped_matmul  # noqa: F401 (reader)


def kda_recurrence(tokens: int, heads: int, dk: int, dv: int,
                   backward: bool = False, bytes_per: int = 2) -> tuple:
    """(flops, bytes) of the gated delta rule over ``tokens`` steps of
    ``heads`` heads, forward or backward. A step of a head, forward: the
    decay of the state (``dk dv`` multiplies), ``S^T k`` (2 dk dv), the
    rank-one update (2 dk dv) and ``S^T q`` (2 dk dv): 7 dk dv; backward:
    the adjoint of each and the state's own recurrence, twice that.
    Bytes: q, k, v (and o) in the compute dtype, g in float32 and beta,
    read once and o written once; backward: those and do read, dq, dk, dv,
    dg and dbeta written."""
    step = 7 * dk * dv
    fwd_bytes = (2 * dk + 2 * dv) * bytes_per + 4 * dk + 4
    if not backward:
        return tokens * heads * step, tokens * heads * fwd_bytes
    return (2 * tokens * heads * step,
            tokens * heads * (2 * fwd_bytes + dv * bytes_per))


def mla_fwd(b: int, heads: int, s: int, qk: int, v: int, shared: int,
            bytes_per: int = 2) -> tuple:
    """(flops, bytes) of one causal forward: q.k over ``qk`` and p.v over
    ``v`` on half the square; q, each head's own key part and v, and the
    ``shared`` key part once, read, o and the float32 log-sum-exp written."""
    flops = 2 * b * heads * (qk + v) * s * s // 2
    nbytes = b * s * (heads * (qk + (qk - shared) + 2 * v) + shared) \
        * bytes_per + b * heads * s * 4
    return flops, nbytes


def mla_bwd(b: int, heads: int, s: int, qk: int, v: int, shared: int,
            bytes_per: int = 2) -> tuple:
    """(flops, bytes) of the causal backward: the scores again, dQ and dK
    over ``qk``, dP and dV over ``v``; the forward's operands, o and do
    read, the four gradients written."""
    flops = 2 * b * heads * (3 * qk + 2 * v) * s * s // 2
    nbytes = 2 * b * s * (heads * (qk + (qk - shared) + 2 * v) + shared) \
        * bytes_per + b * heads * s * 4
    return flops, nbytes


def matmul_params(cfg: dict, held_rows: float = None) -> float:
    """Parameters a token's forward pass multiplies by: each mixer's
    projections, the dense MLP, the router, the shared expert, the experts
    a token is sent to **that are held here** — ``held_rows`` of them a
    token a layer where the rows were counted, else what an even routing
    sends (``top_k x held / experts``) — and the head."""
    d = cfg["hidden"]
    if held_rows is None:
        held_rows = cfg["top_k"] * cfg["experts_held"] / cfg["experts"]
    e, hd = cfg["kda_heads"] * cfg["kda_head_dim"], cfg["kda_head_dim"]
    kda = 3 * d * e + 2 * (d * hd + hd * e) + d * cfg["kda_heads"] + e * d
    h, r = cfg["mla_heads"], cfg["kv_rank"]
    mla = d * h * (cfg["nope"] + cfg["rope"]) + d * (r + cfg["rope"]) \
        + r * h * (cfg["nope"] + cfg["v_dim"]) + h * cfg["v_dim"] * d
    experts = d * cfg["experts"] + 3 * d * cfg["ffn"] * (
        cfg["shared"] + held_rows)
    total = d * cfg["vocab"]
    for kind, ffn in zip(cfg["kinds"], cfg["ffns"]):
        total += (kda if kind == "kda" else mla) + (
            3 * d * cfg["dense_ffn"] if ffn == "dense" else experts)
    return total


def mixer_flops_per_token(cfg: dict, seq: int) -> float:
    """Forward + backward of every layer's sequence mixing, a token: the
    recurrence for a KDA layer; for an MLA layer the causal half, the
    backward at twice the forward (dV, dP, dQ, dK: the scores that
    :func:`mla_bwd` counts for the flash kernels are taken again there, and
    a recomputation is no model FLOP)."""
    kda = sum(kda_recurrence(1, cfg["kda_heads"], cfg["kda_head_dim"],
                             cfg["kda_head_dim"], backward)[0]
              for backward in (False, True))
    dims = (1, cfg["mla_heads"], seq, cfg["nope"] + cfg["rope"], cfg["v_dim"],
            cfg["rope"])
    mla = 3 * mla_fwd(*dims)[0] / seq
    return sum(kda if kind == "kda" else mla for kind in cfg["kinds"])


def train_flops_per_token(cfg: dict, seq: int,
                          held_rows: float = None) -> float:
    """Forward + backward of one token: 6 per multiplied parameter and
    each layer's mixing (``held_rows``: :func:`matmul_params`)."""
    return 6.0 * matmul_params(cfg, held_rows) \
        + mixer_flops_per_token(cfg, seq)
