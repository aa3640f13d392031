"""The yardstick's arithmetic for the ``zaya1-8b`` configuration: the model
FLOPs a trained token requires and the work of its grouped expert calls.
jax-free.

Counted as the algorithm needs them, not as any implementation spends
them: attention over the causal half in the 8 x 128 latent
(``roofline.flash_fwd`` / ``flash_bwd``, the kernels' own count), the held
experts at the rows an even routing sends them (1 of 16 experts a token, 8
held: half the tokens), the depthwise convolution, norms and rotation as
the elementwise work they are (no matmul FLOPs), nothing recomputed, the
tied table multiplied once (the head) and looked up once (a gather).
"""

from __future__ import annotations

from benchmark import roofline
from benchmark.roofline_keyevl2 import grouped_matmul  # noqa: F401 (reader)


def matmul_params(cfg: dict) -> float:
    """Parameters a token's forward pass multiplies by: the four attention
    projections in the latent, the per-head convolution's taps, the
    router's four matrices, the one expert a token is sent to **if it is
    held here** under an even routing (top_k x held / experts of one), and
    the head."""
    d, hd, r = cfg["hidden"], cfg["head_dim"], cfg["router_hidden"]
    q, kv = cfg["heads"] * hd, cfg["kv_heads"] * hd
    mix = cfg["taps"][1] * (cfg["heads"] + cfg["kv_heads"]) * hd * hd
    router = d * r + 2 * r * r + r * cfg["experts"]
    experts = cfg["top_k"] * cfg["experts_held"] / cfg["experts"] \
        * 3 * d * cfg["ffn"]
    return cfg["layers"] * (d * (q + 2 * kv) + q * d + mix + router
                            + experts) + d * cfg["vocab"]


def attention_flops_per_token(cfg: dict, seq: int) -> float:
    """Forward + backward of one layer's attention over the causal half,
    a token."""
    dims = (1, cfg["heads"], cfg["kv_heads"], seq, cfg["head_dim"])
    return (roofline.flash_fwd(*dims)[0] + roofline.flash_bwd(*dims)[0]) / seq


def train_flops_per_token(cfg: dict, seq: int) -> float:
    """Forward + backward of one token: 6 per multiplied parameter and
    each layer's attention."""
    return 6.0 * matmul_params(cfg) \
        + cfg["layers"] * attention_flops_per_token(cfg, seq)
