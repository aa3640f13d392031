"""The yardstick's arithmetic for the ``keye-vl-2.0-30b-a3b`` configuration:
the operations and bytes each of its kernels' algorithms needs for a call
(from its shapes and the configuration alone), and the model FLOPs a
trained token requires. jax-free.

Counted as the algorithm needs them, not as any implementation spends
them: attention over the **selected** pairs only (a query at position t
sees min(t + 1, topk) keys), so a pass over the whole causal triangle reads
under 25% at 16384 tokens and nothing can read over 100%; the indexer's
scores over the causal half; the held experts at the rows an even routing
sends them; nothing recomputed; the embedding as the gather it is.
"""

from __future__ import annotations


def selected_pairs(t: int, topk: int, row0: int = 0, rows: int = None) -> int:
    """(query, key) pairs the selection keeps, for the queries at positions
    ``row0 .. row0 + rows`` (all ``t`` by default)."""
    rows = t - row0 if rows is None else rows
    full = lambda n: (lambda k: k * (k + 1) // 2 + (n - k) * topk)(
        min(n, topk))                 # pairs of positions 0 .. n-1
    return full(row0 + rows) - full(row0)


def causal_pairs(t: int, row0: int = 0, rows: int = None) -> int:
    rows = t - row0 if rows is None else rows
    return rows * row0 + rows * (rows + 1) // 2


def sel_attn_fwd(b: int, t: int, heads: int, kv_heads: int, hd: int,
                 topk: int, bytes_per: int = 2) -> tuple:
    """(flops, bytes) of one selected-attention forward: q.k and p.v over
    ``hd`` on the selected pairs; q, k, v read and o written once, the f32
    log-sum-exp row, and the selection at one bit a causal pair."""
    flops = 4 * b * heads * hd * selected_pairs(t, topk)
    nbytes = b * t * hd * (2 * heads + 2 * kv_heads) * bytes_per \
        + b * heads * t * 4 + b * causal_pairs(t) // 8
    return flops, nbytes


def sel_attn_bwd(b: int, t: int, heads: int, kv_heads: int, hd: int,
                 topk: int, bytes_per: int = 2) -> tuple:
    """(flops, bytes) of the backward (dq and dk/dv together): five
    matmuls where the forward has two; q, k, v, o, do and the log-sum-exp
    read, dq, dk, dv written, the selection read by both kernels."""
    flops = 10 * b * heads * hd * selected_pairs(t, topk)
    nbytes = b * t * hd * (4 * heads + 4 * kv_heads) * bytes_per \
        + b * heads * t * 4 + 2 * b * causal_pairs(t) // 8
    return flops, nbytes


def index_scores(b: int, rows: int, keys: int, index_heads: int,
                 index_dim: int, bytes_per: int = 2) -> tuple:
    """(flops, bytes) of the indexer's scores for the last ``rows`` queries
    of ``keys`` positions: per causal pair and index head one dot over
    ``index_dim``, the relu and the weighted sum; qI, kI and w read, the
    float32 scores of the causal pairs written."""
    pairs = causal_pairs(keys, keys - rows, rows)
    flops = b * pairs * index_heads * (2 * index_dim + 2)
    nbytes = b * (rows * index_heads * index_dim + keys * index_dim) \
        * bytes_per + b * rows * index_heads * 4 + b * pairs * 4
    return flops, nbytes


def head_probs(b: int, rows: int, keys: int, heads: int, kv_heads: int,
               hd: int, topk: int, bytes_per: int = 2) -> tuple:
    """(flops, bytes) of the head-summed probabilities of the last
    ``rows`` queries of ``keys`` positions: q.k over ``hd`` for every head
    on the selected pairs; q, k and the log-sum-exp read, the float32 sum
    written on the causal pairs."""
    pairs = selected_pairs(keys, topk, keys - rows, rows)
    flops = 2 * b * heads * hd * pairs
    nbytes = b * (rows * heads + keys * kv_heads) * hd * bytes_per \
        + b * heads * rows * 4 + b * causal_pairs(keys, keys - rows, rows) * 4
    return flops, nbytes


def grouped_matmul(rows: float, chunks: int, experts_held: int, hidden: int,
                   ffn: int, bytes_per: int = 2) -> tuple:
    """(flops, bytes) of one grouped-matmul call of the dropless expert
    layer (into the expert width, back out of it, either one transposed,
    or the weight gradient: all multiply ``rows`` x ``hidden`` x ``ffn``):
    ``rows`` are the rows **actually routed** to the held experts in the
    call, so the worst-case buffer counts for nothing; the rows read and
    written once, and the held experts' matrices once a layer's pass, a
    call being one of its ``chunks`` (that a chunk reads them again is
    the implementation's)."""
    flops = 2 * rows * hidden * ffn
    nbytes = rows * (hidden + ffn) * bytes_per \
        + experts_held * hidden * ffn * bytes_per / chunks
    return flops, nbytes


def matmul_params(cfg: dict) -> float:
    """Parameters a token's forward pass multiplies by: the four attention
    projections, the indexer's three, the router, the experts a token is
    sent to **that are held here** under an even routing (top_k x held /
    experts of them), and the head. The embedding is looked up."""
    d = cfg["hidden"]
    q, kv = cfg["heads"] * cfg["head_dim"], cfg["kv_heads"] * cfg["head_dim"]
    index = cfg["index_heads"] * cfg["index_dim"] + cfg["index_dim"] \
        + cfg["index_heads"]
    experts = cfg["top_k"] * cfg["experts_held"] / cfg["experts"] \
        * 3 * d * cfg["ffn"]
    return cfg["layers"] * (d * (q + 2 * kv) + q * d + d * index
                            + d * cfg["experts"] + experts) \
        + d * cfg["vocab"]


def train_flops_per_token(cfg: dict, seq: int) -> float:
    """Forward + backward of one token: 6 per multiplied parameter; each
    layer's attention forward and backward over the selected pairs; the
    indexer's scores forward (one dot a pair and head) and backward (two).
    The probabilities the indexer's loss reads are the attention's own and
    are not counted again."""
    heads, kvh, hd = cfg["heads"], cfg["kv_heads"], cfg["head_dim"]
    attn = (sel_attn_fwd(1, seq, heads, kvh, hd, cfg["index_topk"])[0]
            + sel_attn_bwd(1, seq, heads, kvh, hd, cfg["index_topk"])[0])
    index = 3 * index_scores(1, seq, seq, cfg["index_heads"],
                             cfg["index_dim"])[0]
    return 6.0 * matmul_params(cfg) + cfg["layers"] * (attn + index) / seq
