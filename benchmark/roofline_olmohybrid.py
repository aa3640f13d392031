"""The yardstick's arithmetic for the ``olmo-hybrid-7b`` configuration: the
model FLOPs a trained token requires and the work of a chunk-kernel call.
jax-free.

Counted as the algorithm needs them, not as any implementation spends them,
at the heads HELD here: full attention over the causal half at the head
size (``roofline.flash_fwd`` / ``flash_bwd`` have its kernels' work), the
linear attention as **the recurrence's own work** at the published ``dk x
dv`` state under ONE decay a head (a function of tokens, heads and the
state: never of a chunk length, of how a kernel solves a chunk, or of the
lanes a kernel pads a head to), convolutions, norms and gates as the
elementwise work they are (no matmul FLOPs), nothing recomputed, the
embedding looked up (a gather) and the untied head multiplied once.
"""

from __future__ import annotations


def gdn_recurrence(tokens: int, heads: int, dk: int, dv: int,
                   backward: bool = False, bytes_per: int = 2) -> tuple:
    """(flops, bytes) of the gated delta rule under a scalar decay over
    ``tokens`` steps of ``heads`` heads, forward or backward. A step of a
    head, forward: the decay of the state (``dk dv`` multiplies), ``S^T k``
    (2 dk dv), the rank-one update (2 dk dv) and ``S^T q`` (2 dk dv): 7 dk
    dv; backward: the adjoint of each and the state's own recurrence, twice
    that. Bytes: q, k (``dk``), v and o (``dv``) in the compute dtype, g
    and beta one float32 each, read once and o written once; backward:
    those and do read, dq, dk, dv, dg and dbeta written."""
    step = 7 * dk * dv
    fwd_bytes = (2 * dk + 2 * dv) * bytes_per + 4 + 4
    if not backward:
        return tokens * heads * step, tokens * heads * fwd_bytes
    return (2 * tokens * heads * step,
            tokens * heads * (2 * fwd_bytes + dv * bytes_per))


def matmul_params(cfg: dict) -> int:
    """Parameters a token's forward pass multiplies by, at the heads held:
    each mixer's projections (gdn: q, k, v, the output gate z, the decay's
    and beta's, o; attn: q, k, v, o), the FFN's three, the head."""
    d = cfg["hidden"]
    k, v = cfg["gdn_heads"] * cfg["dk"], cfg["gdn_heads"] * cfg["dv"]
    per = {"gdn": 2 * d * k + 2 * d * v + 2 * d * cfg["gdn_heads"] + v * d,
           "attn": 4 * d * cfg["heads"] * cfg["head_dim"]}
    return sum(per[kind] + 3 * d * cfg["ffn"] for kind in cfg["kinds"]) \
        + d * cfg["vocab"]


def mixer_flops_per_token(cfg: dict, seq: int) -> float:
    """Forward + backward of every layer's sequence mixing, a token: the
    recurrence for a gdn layer; for an attn layer two products over the
    causal half forward, four backward (the scores a flash backward takes
    again are a recomputation, and no model FLOP)."""
    gdn = sum(gdn_recurrence(1, cfg["gdn_heads"], cfg["dk"], cfg["dv"],
                             backward)[0] for backward in (False, True))
    attn = 6 * cfg["heads"] * cfg["head_dim"] * (seq + 1)
    return sum(gdn if kind == "gdn" else attn for kind in cfg["kinds"])


def train_flops_per_token(cfg: dict, seq: int) -> float:
    """Forward + backward of one token: 6 per multiplied parameter and each
    layer's mixing."""
    return 6.0 * matmul_params(cfg) + mixer_flops_per_token(cfg, seq)
