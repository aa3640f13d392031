"""A decoder built from a tuple of layer kinds — state-space, windowed and
full differential attention, gated memory units, cross-attention to one
shared K/V (the SambaY decoder-hybrid-decoder of arXiv:2507.06607, as
Phi-4-mini-flash-reasoning runs it); gated delta-rule linear attention and
latent attention without positions over dense or expert feed-forwards
(Kimi Linear, arXiv:2510.26692); Gated DeltaNet (arXiv:2412.06464: the
delta rule under one decay a head) beside plain causal attention without
rotation, the norms after the sublayers (Olmo-Hybrid-7B); rotated latent
attention under a low-rank query in every layer and a multi-token-
prediction module behind the stack (GLM-4.7-Flash; DeepSeek-V3,
arXiv:2412.19437).

:class:`~tony_tpu.models.transformer.Transformer` folds ONE block kind with
``nn.scan``; here the kinds differ and two streams cross layers, so each
layer is its own module (its own ``nn.remat``, through ``remat.block``),
and every mixer declares what it ``emits`` for later layers and what it
``consumes``:

================  =========================  ===================  =========
kind (scope)      mixer                      consumes             emits
================  =========================  ===================  =========
``mamba`` (ssm)   Mamba-1 selective scan     —                    ``m``
``swa``           differential attention,    —                    —
(attn_swa)        causal, ``window`` keys
``full``          differential attention,    —                    ``k``,
(attn_full)       causal                                          ``v``
``gmu`` (gmu)     gated memory unit          ``m``                —
``cross``         differential attention of  ``k``, ``v``         —
(attn_cross)      its own q over k, v
``kda`` (kda)     gated delta rule, a        —                    —
                  128 x 128 state a head
``mla``           latent attention expanded  —                    —
(attn_mla)        for training, rotated or
                  not
``gdn`` (gdn)     gated delta rule, one      —                    —
                  decay a head, a key x
                  value state a head
``attn`` (attn)   causal softmax attention,  —                    —
                  no rotation, optional
                  q/k-norm
================  =========================  ===================  =========

A later emitter replaces an earlier one's stream (the last ``mamba``
before a ``gmu`` is the one it reads). Layer i, pre-norm:
``x += mixer_i(LN(x)); x += MLP(LN(x))`` with LayerNorm (scale and bias)
and ``MLP(u) = (up * silu(gate)) W2, [gate, up] = u W1``. No positional
encoding. The embedding table is tied: one parameter, looked up at the
bottom and multiplied at the top (``vocab`` may be a slice of the
published table — ids, logits and loss are then over the slice). The
configuration may change three of these: ``norm="rmsnorm"`` (scale only),
``tie_embeddings=False`` (an untied head ``lm_head_kernel``),
``norm_placement="post"`` (the Olmo family's reordered norm: ``x +=
LN(mixer_i(x)); x += LN(MLP(x))``), and ``ffns``,
a feed-forward a layer — ``("dense", width)``, a SwiGLU with separate
``w_gate`` / ``w_up`` / ``w_down`` under the scope ``mlp``, or
``"experts"``, :class:`~tony_tpu.models.moe.DroplessMoE` with a sigmoid
router and shared experts.

``kda`` (:class:`KDA`; :mod:`tony_tpu.ops.kda` has the recurrence)::

    q, k, v = silu(conv4(u W_{q,k,v}))      depthwise, causal, no bias
    q, k <- q / |q|, k / |k| a head;  q <- q * head^-1/2
    g = -exp(A_log_h) * softplus(W_f2 (W_f1 u) + dt_bias)     [heads x head]
    beta = sigmoid(u W_b)                                     [heads]
    y = W_o (RMSNorm_head(kda(q, k, v, g, beta)) * sigmoid(W_g2 (W_g1 u) + b_g))

``gdn`` (:class:`GDN`; the same op under a scalar decay, ``dk`` key and
``dv`` value channels a head)::

    q, k, v = silu(conv4(u W_{q,k,v}))      as ``kda``; q, k normalised alike
    g = -exp(A_log_h) * softplus(u w_a + dt_bias)             [heads]
    beta = 2 sigmoid(u w_b)        (1 sigmoid without ``gdn_neg_eigval``)
    y = W_o (RMSNorm_dv(kda(q, k, v, g, beta)) * silu(u W_z))

``attn`` (:class:`Attn`): ``q, k, v = u W_{q,k,v}`` over heads of
``head_dim``; with ``attn_qk_norm`` an RMSNorm over q's and k's whole
width; no rotation; causal softmax of ``q . k / sqrt(head_dim)``; ``W_o``.

**A share of the heads** (``heads_held``): ``gdn`` and ``attn`` build only
the held heads' columns of ``W_q .. W_z`` and rows of ``W_o`` — what one of
the chips that divide a layer's mixers by heads holds; the feed-forward and
the norms are whole on each. A mixer's shares add up to the uncut mixer
(the all-reduce that adds them on real chips is not run on one, and nothing
stands in for it); the q/k-norm's mean square is over the held width, what
a chip has without exchanging one scalar a token.

``mla`` (:class:`MLA`): ``q_h = u W_q`` (nope + rope wide) — with
``mla_q_rank`` ``RMSNorm(u W_qa) W_qb``, the query through a latent of its
own —, ``[c, k_s] = u W_kva``, ``[k_h, v_h] = RMSNorm(c) W_kvb``; head h's
key is ``[k_h, k_s]`` with ``k_s`` shared by the heads; with
``mla_rope_theta`` (values as wide as the whole key only) the last
``rope`` columns of every ``q_h`` and ``k_s`` are rotated by their position
(:func:`tony_tpu.models.transformer.rope`, neighbouring pairs), without it
**no rotation** on any part; causal
softmax of ``q_h . key_h / sqrt(nope + rope)``; ``W_o`` over the values.
Expanded for training, two shapes: values as wide as a key's own part
(128 + 64 over 128) go through
:func:`tony_tpu.ops.attention.flash_attention_mla`, which reads the shared
part beside each head's; values as wide as the whole key (192 + 64 over
256: a head's own part is no lane block) have each head's key joined and
go through the packed flash kernels at that head size.

**A second token** (``mtp_layers`` 1; DeepSeek-V3 section 2.2): behind the
stack, ``h' = [RMSNorm(Emb(t_{i+1})); RMSNorm(h_i)] W_eh`` over the last
layer's output ``h`` **before** the final norm, one more layer of the last
layer's kind and feed-forward (its own weights, the stack's positions), a
norm of its own, and the model's head a second time against ``t_{i+2}``:
one table and one head, two uses and two gradients each. With ``targets``
the model returns ``L_LM`` and sows ``mtp_weight * L_MTP`` into ``losses``
(a train step adds it: its ``aux_loss``), each a mean over its own rows;
without, it returns the first logits and sows the second into
``intermediates``. The module runs over all ``T`` rows — the last embeds
the row's first token in place of the one it lacks, sees no later row and
carries no loss.

Differential attention (heads 2p, 2p+1 are pair p; two query pairs share
one K/V pair; ``v = [v_1, v_2]`` is 128 wide)::

    a = softmax(q_1 k_1^T / 8 + M) v - lam * softmax(q_2 k_2^T / 8 + M) v
    lam = exp(lq1 . lk1) - exp(lq2 . lk2) + lam0,  lam0 = 0.8 - 0.6 exp(-0.3 i)
    a <- RMSNorm_128(a) * (1 - lam0)

runs as ONE flash call on the packed layout: the pair's K is already the
128-wide ``[k_1, k_2]`` of adjacent heads, so ``q_1`` is zero-extended to
``[q_1, 0]`` and ``q_2`` to ``[0, q_2]`` (``[q_1, 0] . [k_1, k_2] = q_1 .
k_1``). The kernel then sees 2 x pairs query heads of 128 over pairs / 2
K/V heads of 128: head size 128 is the packed kernels' lane tile, so there
is no ``[B, H, T, D]`` copy, V is read once per score map instead of once
per half, and the zero half costs the MXU nothing (a 64-deep contraction
fills half of its 128 rows anyway).

Precision: parameters float32, matmuls and activations ``dtype``
(bfloat16); LayerNorm / RMSNorm statistics, softmax statistics, ``dt``,
the scan's state and its output float32.
"""

from __future__ import annotations

import dataclasses
import math
from typing import Any, Callable, NamedTuple, Optional, Tuple

import flax.linen as nn
import jax
import jax.numpy as jnp

from tony_tpu import profiler, remat
from tony_tpu.models import register
from tony_tpu.models.transformer import RMSNorm, rope
from tony_tpu.ops import attention as attn_ops
from tony_tpu.ops import ssm

KINDS = ("mamba", "swa", "full", "gmu", "cross", "kda", "mla", "gdn", "attn")
DIFFERENTIAL = ("swa", "full", "cross")
# Kimi Linear's layers 1-5: the leading dense layer, then one period.
KIMI_LINEAR_CUT = ("kda", "kda", "kda", "mla", "kda")
PHI4_FLASH_CUT = ("mamba", "swa", "mamba", "full", "gmu", "cross")
# Olmo-Hybrid's period: three linear-attention layers, then a full one.
OLMO_HYBRID_CUT = ("gdn", "gdn", "gdn", "attn")


@dataclasses.dataclass(frozen=True)
class HybridConfig:
    vocab: int = 200064
    dim: int = 2560
    ffn_hidden: int = 10240
    n_heads: int = 40
    n_kv_heads: int = 20
    layers: Tuple[str, ...] = PHI4_FLASH_CUT
    window: int = 512
    ssm_state: int = 16
    ssm_conv: int = 4
    ssm_expand: int = 2
    ssm_dt_rank: int = 0            # 0 = ceil(dim / 16)
    scan_chunk: int = 64
    # float32; bfloat16 rounds the scan's state and dt (the benchmark's
    # lower-precision control — never faster).
    scan_dtype: Any = jnp.float32
    norm_eps: float = 1e-5
    dtype: Any = jnp.bfloat16
    # True: every projection (mixers and MLP) on the int8 lane of
    # tony_tpu.ops.quant, same parameter paths.
    quant: bool = False
    remat: bool = True
    # >0: fused tied head + row-chunked cross entropy; __call__ then takes
    # targets and returns the scalar loss (see Transformer.xent_chunk).
    xent_chunk: int = 0
    # None: Pallas kernels on a TPU, XLA twins elsewhere; True: the kernel
    # bodies in the Pallas interpreter (CPU tests).
    interpret: Optional[bool] = None
    mesh: Optional[Any] = None      # not supported: one chip
    norm: str = "layernorm"         # | "rmsnorm"
    tie_embeddings: bool = True
    # () = every layer the fused GatedMLP of ``ffn_hidden``; else one entry
    # a layer: ("dense", width) or "experts".
    ffns: Tuple[Any, ...] = ()
    kda_heads: int = 32
    kda_head_dim: int = 128
    kda_conv: int = 4
    kda_chunk: int = 64             # the delta rule's, ``gdn`` too
    kda_keep: int = 4
    gdn_heads: int = 30
    gdn_key_dim: int = 96
    gdn_value_dim: int = 192
    gdn_conv: int = 4
    gdn_neg_eigval: bool = True     # beta in (0, 2)
    attn_qk_norm: bool = False      # ``attn``: RMSNorm over q's, k's width
    # >0: ``gdn`` and ``attn`` build this many of their ``gdn_heads`` /
    # ``n_heads`` heads (module docstring, a share of the heads).
    heads_held: int = 0
    norm_placement: str = "pre"     # | "post": x += LN(sublayer(x))
    mla_heads: int = 32
    mla_kv_rank: int = 512
    mla_nope_dim: int = 128
    mla_rope_dim: int = 64          # the key part the heads share
    mla_v_dim: int = 128
    mla_q_rank: int = 0             # >0: the query through a latent
    mla_rope_theta: float = 0.0     # >0: the rope parts are rotated
    # 1: a multi-token-prediction module behind the stack (module
    # docstring), its loss weighed ``mtp_weight`` beside L_LM.
    mtp_layers: int = 0
    mtp_weight: float = 0.3
    moe_experts: int = 256
    moe_top_k: int = 8
    moe_experts_held: int = 0       # 0 = all of them
    moe_expert_offset: int = 0
    moe_ffn: int = 1024
    moe_shared: int = 1
    moe_route_scale: float = 1.0

    def __post_init__(self):
        unknown = set(self.layers) - set(KINDS)
        if unknown:
            raise ValueError(f"unknown layer kind(s) {sorted(unknown)}; "
                             f"choose from {KINDS}")
        have: set = set()
        for i, kind in enumerate(self.layers):
            missing = set(MIXERS[kind].consumes) - have
            if missing:
                raise ValueError(
                    f"layer {i} ({kind}) consumes {sorted(missing)}, which "
                    f"no earlier layer emits")
            have |= set(MIXERS[kind].emits)
        if set(self.layers) & set(DIFFERENTIAL) and (
                self.n_heads % 2 or self.n_kv_heads % 2
                or (self.n_heads // 2) % (self.n_kv_heads // 2)):
            raise ValueError("differential attention pairs heads: n_heads "
                             "and n_kv_heads even, query pairs a multiple "
                             "of K/V pairs")
        if self.norm not in ("layernorm", "rmsnorm"):
            raise ValueError(f"norm {self.norm!r}: 'layernorm' or 'rmsnorm'")
        if self.norm_placement not in ("pre", "post"):
            raise ValueError(f"norm_placement {self.norm_placement!r}: "
                             f"'pre' or 'post'")
        if self.heads_held and (
                set(self.layers) - {"gdn", "attn"} or not 0 < self.heads_held
                <= min(self.gdn_heads, self.n_heads)):
            raise ValueError(
                f"heads_held={self.heads_held}: only gdn and attn mixers "
                f"build a share of their {self.gdn_heads} / {self.n_heads} "
                f"heads")
        if self.ffns and len(self.ffns) != len(self.layers):
            raise ValueError(f"ffns names {len(self.ffns)} feed-forwards "
                             f"for {len(self.layers)} layers")
        for ffn in self.ffns:
            if ffn != "experts" and not (
                    isinstance(ffn, tuple) and len(ffn) == 2
                    and ffn[0] == "dense"):
                raise ValueError(f"feed-forward {ffn!r}: ('dense', width) "
                                 f"or 'experts'")
        if "mla" in self.layers and self.mla_v_dim not in (
                self.mla_nope_dim, self.mla_nope_dim + self.mla_rope_dim):
            raise ValueError("mla: values as wide as a key's own part "
                             "(nope_dim == v_dim) or as the whole key "
                             "(nope_dim + rope_dim == v_dim)")
        if "mla" in self.layers and self.mla_rope_theta \
                and self.mla_v_dim == self.mla_nope_dim:
            raise ValueError("mla: the rotation is written for values as "
                             "wide as the whole key (joined keys)")
        if self.mtp_layers not in (0, 1):
            raise ValueError(f"mtp_layers={self.mtp_layers}: one module "
                             f"predicts one more token")
        if self.mesh is not None:
            raise ValueError("the hybrid decoder runs on one chip; no "
                             "sharding rules are written for it yet")

    @property
    def head_dim(self) -> int:
        return self.dim // self.n_heads

    @property
    def d_inner(self) -> int:
        return self.ssm_expand * self.dim

    @property
    def dt_rank(self) -> int:
        return self.ssm_dt_rank or math.ceil(self.dim / 16)

    def held(self, heads: int) -> int:
        """Of a ``gdn`` / ``attn`` mixer's ``heads``, those built here."""
        return self.heads_held or heads

    def flops_per_token(self, seq: int) -> float:
        """~6 FLOPs a multiplied parameter (forward + backward) plus each
        layer's sequence mixing, a trained token of a ``seq``-long row —
        for a stack of ``kda`` / ``mla`` / ``gdn`` / ``attn`` layers, the
        last two at the heads held
        (Transformer.flops_per_token's accounting: the embedding is a
        gather, of a held range of experts the share an even routing sends
        here, the shared experts whole; MLA over the causal half with q.k
        over nope + rope and p.v over v, forward 2 and backward 4 products
        (the scores a flash backward takes again are a recomputation), a
        low-rank query as its two products; a multi-token-prediction
        module as its join, one more layer and the head a second time;
        plain attention alike over ``head_dim``; the delta rule as the
        recurrence's own 7 multiply-adds a state element a step, twice that
        backward)."""
        if set(self.layers) - {"kda", "mla", "gdn", "attn"}:
            raise NotImplementedError(
                "flops_per_token counts kda, mla, gdn and attn mixers; the "
                "other kinds' work is benchmark/roofline_ssm.py's")
        d, hd = self.dim, self.kda_head_dim
        e = self.kda_heads * hd
        qk = self.mla_nope_dim + self.mla_rope_dim
        gh, ah = self.held(self.gdn_heads), self.held(self.n_heads)
        gk, gv = gh * self.gdn_key_dim, gh * self.gdn_value_dim
        per = {
            "gdn": (2 * d * gk + 2 * d * gv + 2 * d * gh + gv * d,
                    3 * 7 * gh * self.gdn_key_dim * self.gdn_value_dim),
            "attn": (4 * d * ah * self.head_dim,
                     3 * ah * (seq + 1) * 2 * self.head_dim),
            "kda": (3 * d * e + 2 * (d * hd + hd * e) + d * self.kda_heads
                    + e * d, 3 * 7 * self.kda_heads * hd * hd),
            "mla": (
                ((d + self.mla_heads * qk) * self.mla_q_rank
                 if self.mla_q_rank else d * self.mla_heads * qk)
                + d * (self.mla_kv_rank + self.mla_rope_dim)
                + self.mla_kv_rank * self.mla_heads
                * (self.mla_nope_dim + self.mla_v_dim)
                + self.mla_heads * self.mla_v_dim * d,
                3 * self.mla_heads * seq * (qk + self.mla_v_dim)),
        }
        share = (self.moe_experts_held or self.moe_experts) / self.moe_experts
        experts = d * self.moe_experts + 3 * d * self.moe_ffn * (
            self.moe_shared + self.moe_top_k * share)
        params, mixing = d * self.vocab, 0.0
        stack = list(zip(self.layers, self.ffns or (
            ("dense", self.ffn_hidden),) * len(self.layers)))
        for kind, ffn in stack + stack[-1:] * self.mtp_layers:
            params += per[kind][0] + (experts if ffn == "experts"
                                      else 3 * d * ffn[1])
            mixing += per[kind][1]
        params += self.mtp_layers * (2 * d * d + d * self.vocab)
        return 6.0 * params + mixing


def _dense(cfg, feats, name, bias=False):
    if cfg.quant:
        from tony_tpu.ops.quant import QuantDense
        return QuantDense(feats, use_bias=bias, dtype=cfg.dtype,
                          param_dtype=jnp.float32, name=name)
    return nn.Dense(feats, use_bias=bias, dtype=cfg.dtype,
                    param_dtype=jnp.float32, name=name,
                    kernel_init=nn.initializers.lecun_normal())


class LayerNorm(nn.Module):
    """LayerNorm with scale and bias; statistics in float32."""
    eps: float = 1e-5

    @nn.compact
    def __call__(self, x):
        d = x.shape[-1]
        scale = self.param("scale", nn.initializers.ones, (d,), jnp.float32)
        bias = self.param("bias", nn.initializers.zeros, (d,), jnp.float32)
        x32 = x.astype(jnp.float32)
        mean = jnp.mean(x32, axis=-1, keepdims=True)
        var = jnp.mean(jnp.square(x32 - mean), axis=-1, keepdims=True)
        y = (x32 - mean) * jax.lax.rsqrt(var + self.eps)
        return (y * scale + bias).astype(x.dtype)


def _norm(cfg, name):
    """The configuration's norm over the model width."""
    if cfg.norm == "rmsnorm":
        return RMSNorm(cfg.norm_eps, name=name)
    return LayerNorm(cfg.norm_eps, name=name)


class SwiGLU(nn.Module):
    """``(silu(u W_gate) * u W_up) W_down``: the dense decoder's MLP (its
    parameter names, and ``gate`` / ``up`` named for the residual ladder).
    Every operand of its three weight-gradient products is fenced
    (:func:`remat.fence`): the input, ``silu(gate) * up`` and, through
    their cotangents, ``dgate``, ``dup`` and the output's are arrays made
    once, where an unrolled layer's backward would make each again inside
    every product that reads it (25.6 ms a product for 11.0 at 16384 x
    3840 x 11008: PERF.md section 6, PR 42)."""
    cfg: HybridConfig
    width: int

    @nn.compact
    def __call__(self, u):
        cfg = self.cfg
        u = remat.fence(u)
        gate = remat.fence(
            remat.name(_dense(cfg, self.width, "w_gate")(u), "gate"))
        up = remat.fence(remat.name(_dense(cfg, self.width, "w_up")(u), "up"))
        return remat.fence(_dense(cfg, cfg.dim, "w_down")(
            remat.fence(nn.silu(gate) * up)))


class GatedMLP(nn.Module):
    """``(up * silu(gate)) W2`` with ``[gate, up] = u W1`` (one fused
    projection, gate first)."""
    cfg: HybridConfig

    @nn.compact
    def __call__(self, u):
        cfg = self.cfg
        gate, up = jnp.split(_dense(cfg, 2 * cfg.ffn_hidden, "w1")(u), 2,
                             axis=-1)
        return _dense(cfg, cfg.dim, "w2")(up * nn.silu(gate))


class Mamba(nn.Module):
    """Mamba-1: in-projection, causal depthwise convolution, input-dependent
    (dt, B, C), the selective scan, gate, out-projection. Emits the scan's
    output before the gate as the memory ``m``."""
    cfg: HybridConfig

    @nn.compact
    def __call__(self, u):
        cfg = self.cfg
        e, n, r = cfg.d_inner, cfg.ssm_state, cfg.dt_rank
        xs, z = jnp.split(_dense(cfg, 2 * e, "in_proj")(u), 2, axis=-1)
        conv_w = self.param("conv_w", _conv_init, (cfg.ssm_conv, e),
                            jnp.float32)
        conv_b = self.param("conv_b", nn.initializers.zeros, (e,),
                            jnp.float32)
        xc = nn.silu(ssm.causal_conv1d(xs, conv_w, conv_b))
        rbc = _dense(cfg, r + 2 * n, "x_proj")(xc)
        low, bm, cm = rbc[..., :r], rbc[..., r:r + n], rbc[..., r + n:]
        w_dt = self.param("dt_w", nn.initializers.lecun_normal(), (r, e),
                          jnp.float32)
        b_dt = self.param("dt_b", _dt_bias_init, (e,), jnp.float32)
        dt = jax.nn.softplus(jnp.dot(
            low, w_dt.astype(cfg.dtype),
            preferred_element_type=jnp.float32) + b_dt)
        a_log = self.param(
            "a_log", lambda _k, shape, dtype: jnp.broadcast_to(jnp.log(
                jnp.arange(1, n + 1, dtype=dtype)), shape), (e, n),
            jnp.float32)
        d_skip = self.param("d_skip", nn.initializers.ones, (e,),
                            jnp.float32)
        profiler.count_once("ssm:chunks",
                            ssm.n_chunks(u.shape[1], cfg.scan_chunk))
        y = ssm.selective_scan(
            xc, dt, -jnp.exp(a_log), bm, cm, d_skip, chunk=cfg.scan_chunk,
            state_dtype=cfg.scan_dtype, interpret=cfg.interpret
        ).astype(cfg.dtype)
        return _dense(cfg, cfg.dim, "out_proj")(y * nn.silu(z)), (y,)


def _conv_init(key, shape, dtype):
    """U(-1/sqrt(K), 1/sqrt(K)) over the K taps (torch's Conv1d default
    for a depthwise kernel of K taps)."""
    bound = 1 / math.sqrt(shape[0])
    return jax.random.uniform(key, shape, dtype, -bound, bound)


def _dt_bias_init(key, shape, dtype, lo=1e-3, hi=1e-1):
    """Inverse softplus of dt drawn log-uniformly in [lo, hi] (Mamba-1):
    the seeded recurrence neither dies nor diverges over a long sequence."""
    dt = jnp.exp(jax.random.uniform(key, shape, dtype)
                 * (math.log(hi) - math.log(lo)) + math.log(lo))
    return dt + jnp.log(-jnp.expm1(-dt))


class GMU(nn.Module):
    """Gated memory unit: ``(m * silu(u W_g)) W_o`` over the memory the
    last state-space layer emitted, at the same token."""
    cfg: HybridConfig

    @nn.compact
    def __call__(self, u, m):
        cfg = self.cfg
        gate = nn.silu(_dense(cfg, cfg.d_inner, "w_gate")(u))
        return _dense(cfg, cfg.dim, "w_out")(m * gate), ()


class DiffAttention(nn.Module):
    """Differential attention (module docstring). ``kind``: ``swa``
    (window), ``full`` (emits its K, V) or ``cross`` (projects q only and
    consumes K, V)."""
    cfg: HybridConfig
    index: int
    kind: str = "full"

    @nn.compact
    def __call__(self, u, k=None, v=None):
        cfg = self.cfg
        b, t, _ = u.shape
        hd, nh, nkv = cfg.head_dim, cfg.n_heads, cfg.n_kv_heads
        pairs = nh // 2
        if self.kind == "cross":
            q = _dense(cfg, nh * hd, "wq", bias=True)(u)
        else:
            qkv = _dense(cfg, (nh + 2 * nkv) * hd, "wqkv", bias=True)(u)
            q, k, v = jnp.split(qkv, (nh * hd, (nh + nkv) * hd), axis=-1)
        window = cfg.window if self.kind == "swa" else None
        # Blocks are the kernels' own rule's (``ops.attention._pick_blocks``:
        # at the cell's 8192, streamed, 1024 for the causal square and 512
        # under the 512-key window; 512 with K/V resident): recorded here
        # so that a timeline says which tile shape the run used.
        for name, n in attn_ops.block_facts(
                t, t, causal=True, window=window, head_dim=2 * hd,
                itemsize=k.dtype.itemsize).items():
            profiler.count_once(f"attn:{name}.{self.kind}", n)
        # [q_1, 0] and [0, q_2]: each half-head against the pair's 128-wide
        # [k_1, k_2].
        q4 = q.reshape(b, t, pairs, 2, hd)
        zero = jnp.zeros_like(q4[..., 0, :])
        # packsite: region-local — a zero half beside each half-head of one
        # unsharded activation (the model refuses a mesh).
        first = jnp.concatenate([q4[..., 0, :], zero], -1)
        # packsite: region-local — as above.
        second = jnp.concatenate([zero, q4[..., 1, :]], -1)
        # packsite: region-local — the pair's two heads on a NEW axis.
        qp = jnp.stack([first, second], axis=3)
        out = attn_ops.flash_attention_packed(
            qp.reshape(b, t, nh * 2 * hd), k, v, nh, causal=True,
            scale=hd ** -0.5, window=window, interpret=cfg.interpret)
        out = out.reshape(b, t, pairs, 2, 2 * hd)
        lam0 = 0.8 - 0.6 * math.exp(-0.3 * self.index)
        vec = lambda name: self.param(
            name, nn.initializers.normal(0.1), (hd,), jnp.float32)
        lam = jnp.exp(jnp.sum(vec("lq1") * vec("lk1"))) \
            - jnp.exp(jnp.sum(vec("lq2") * vec("lk2"))) + lam0
        a = out[..., 0, :].astype(jnp.float32) \
            - lam * out[..., 1, :].astype(jnp.float32)
        a = RMSNorm(cfg.norm_eps, name="subln")(a) * (1.0 - lam0)
        y = _dense(cfg, cfg.dim, "wo", bias=True)(
            a.astype(cfg.dtype).reshape(b, t, nh * hd))
        return y, ((k, v) if self.kind == "full" else ())


def _a_log_init(key, shape, dtype):
    """log of U(1, 16) a head (the decay's rate, as Mamba-2 draws it)."""
    return jnp.log(jax.random.uniform(key, shape, dtype, 1.0, 16.0))


def _delta_qkv(mod, cfg, u, scope, heads, dk, dv, taps):
    """A delta-rule mixer's operands, under the device scopes ``<scope>_
    proj`` and ``<scope>_conv``: ``q, k, v = silu(conv(u W))`` over
    ``heads`` of ``dk`` / ``dk`` / ``dv`` channels (the projections named
    for the residual ladder), ``q`` and ``k`` normalised a head, ``q``
    times ``dk^-1/2``; ``[B, T, heads, d]`` in the compute dtype.
    Counters ``<scope>:conv_fused`` (how many of the three chains run
    ``ops.ssm``'s fused kernels) and ``<scope>:conv_block`` (the steps a
    block of q's holds; absent where none does)."""
    b, t, _ = u.shape
    chains = (("q", dk, True, dk ** -0.5), ("k", dk, True, 1.0),
              ("v", dv, False, 1.0))
    with jax.named_scope(scope + "_proj"):
        q, k, v = (remat.name(_dense(cfg, heads * d, "w" + n)(u), n)
                   for n, d, _, _ in chains)
    plans = [ssm.conv_plan(t, heads * d, heads, unit, taps,
                           jnp.dtype(cfg.dtype).itemsize, cfg.interpret)
             for _, d, unit, _ in chains]
    profiler.count_once(scope + ":conv_fused",
                        sum(p is not None for p in plans))
    if plans[0]:
        profiler.count_once(scope + ":conv_block", plans[0][2])
    with jax.named_scope(scope + "_conv"):
        # One pass each way from the projections' outputs to the chunk
        # kernel's operands: float32 between the two casts, in VMEM on the
        # chip (``delta_conv_fwd`` / ``delta_conv_bwd``) — as XLA's fusions
        # the chain was 23 float32 passes a tensor a layer-step, 11.6% of
        # the Kimi Linear cell's step (PERF.md section 6, PR 47).
        return tuple(ssm.conv_silu_unit(
            x, mod.param(f"conv_{n}", _conv_init, (taps, heads * d),
                         jnp.float32),
            heads=heads, unit=unit, scale=scale,
            interpret=cfg.interpret).reshape(b, t, heads, d)
            for x, (n, d, unit, scale) in zip((q, k, v), chains))


def _delta_out(cfg, o, gate):
    """``W_o (RMSNorm_head(o) * gate)``: ``o [B, T, heads, dv]``, ``gate``
    ``[B, T, heads dv]`` float32; ``wo`` named for the residual ladder,
    its two operands in the backward (the gated norm's output and the
    cotangent of its own) fenced as :class:`SwiGLU`'s are."""
    b, t, h, dv = o.shape
    o = RMSNorm(cfg.norm_eps, name="o_norm")(o).astype(jnp.float32)
    y = remat.fence((o.reshape(b, t, h * dv) * gate).astype(cfg.dtype))
    return remat.fence(remat.name(_dense(cfg, cfg.dim, "wo")(y), "wo"))


class KDA(nn.Module):
    """Gated delta-rule linear attention (module docstring): device scopes
    ``kda`` > ``kda_proj``, ``kda_conv``, ``kda_gate``, ``kda_out`` and the
    two chunk kernels. No positions enter."""
    cfg: HybridConfig

    @nn.compact
    def __call__(self, u):
        from tony_tpu.ops import kda as kda_ops

        cfg = self.cfg
        b, t, _ = u.shape
        h, d = cfg.kda_heads, cfg.kda_head_dim
        e = h * d
        q, k, v = _delta_qkv(self, cfg, u, "kda", h, d, d, cfg.kda_conv)
        with jax.named_scope("kda_gate"):
            a_log = self.param("a_log", _a_log_init, (h,), jnp.float32)
            dt_bias = self.param("dt_bias", _dt_bias_init, (e,), jnp.float32)
            f = _dense(cfg, e, "wf2")(_dense(cfg, d, "wf1")(u))
            g = (-jnp.exp(a_log)[:, None] * jax.nn.softplus(
                f.astype(jnp.float32) + dt_bias).reshape(b, t, h, d))
            beta = jax.nn.sigmoid(
                _dense(cfg, h, "wb")(u).astype(jnp.float32))
        for name, fact in (
                ("heads", h), ("chunk", cfg.kda_chunk),
                ("chunks", kda_ops.n_chunks(t, cfg.kda_chunk)),
                ("states_kept", kda_ops.states_kept(t, cfg.kda_chunk,
                                                    cfg.kda_keep)),
                *((f"halves_built.{kernel}", kda_ops.halves_built(
                    kernel, cfg.kda_chunk, cfg.kda_keep))
                  for kernel in ("fwd", "bwd")),
                ("table_rows", kda_ops.table_rows(cfg.kda_chunk))):
            profiler.count_once("kda:" + name, fact)
        o = kda_ops.kda(q, k, v, g, beta, chunk=cfg.kda_chunk,
                        keep=cfg.kda_keep, interpret=cfg.interpret)
        with jax.named_scope("kda_out"):
            gate = jax.nn.sigmoid(_dense(cfg, e, "wg2", bias=True)(
                _dense(cfg, d, "wg1")(u)).astype(jnp.float32))
            return _delta_out(cfg, o, gate), ()


class GDN(nn.Module):
    """Gated DeltaNet (module docstring): :class:`KDA`'s operands and
    output form at ``gdn_key_dim`` / ``gdn_value_dim`` a head, ONE decay a
    head, beta up to 2, a full-rank SiLU output gate; the held heads only.
    Device scopes ``gdn`` > ``gdn_proj``, ``gdn_conv``, ``gdn_gate``,
    ``gdn_out`` and the chunk kernels ``gdn_chunk_fwd`` /
    ``gdn_chunk_bwd``."""
    cfg: HybridConfig

    @nn.compact
    def __call__(self, u):
        from tony_tpu.ops import kda as kda_ops

        cfg = self.cfg
        t = u.shape[1]
        h, dk, dv = cfg.held(cfg.gdn_heads), cfg.gdn_key_dim, \
            cfg.gdn_value_dim
        q, k, v = _delta_qkv(self, cfg, u, "gdn", h, dk, dv, cfg.gdn_conv)
        with jax.named_scope("gdn_gate"):
            a_log = self.param("a_log", _a_log_init, (h,), jnp.float32)
            dt_bias = self.param("dt_bias", _dt_bias_init, (h,), jnp.float32)
            g = -jnp.exp(a_log) * jax.nn.softplus(
                _dense(cfg, h, "wa")(u).astype(jnp.float32) + dt_bias)
            beta = (2.0 if cfg.gdn_neg_eigval else 1.0) * jax.nn.sigmoid(
                _dense(cfg, h, "wb")(u).astype(jnp.float32))
        for name, fact in (
                ("heads", h), ("key_dim", dk), ("value_dim", dv),
                ("chunk", cfg.kda_chunk),
                ("chunks", kda_ops.n_chunks(t, cfg.kda_chunk)),
                ("states_kept", kda_ops.states_kept(t, cfg.kda_chunk,
                                                    cfg.kda_keep)),
                ("decay", 1)):                  # decays a head: scalar
            profiler.count_once("gdn:" + name, fact)
        o = kda_ops.kda(q, k, v, g, beta, chunk=cfg.kda_chunk,
                        keep=cfg.kda_keep, interpret=cfg.interpret)
        with jax.named_scope("gdn_out"):
            gate = nn.silu(_dense(cfg, h * dv, "wz")(u).astype(jnp.float32))
            return _delta_out(cfg, o, gate), ()


class Attn(nn.Module):
    """Plain causal softmax attention over the held heads of ``head_dim``
    (module docstring): no rotation, an optional RMSNorm over q's and k's
    whole held width; the packed flash kernels the dense decoder runs.
    Device scope ``attn`` and the three ``attn_*`` flash calls."""
    cfg: HybridConfig

    @nn.compact
    def __call__(self, u):
        cfg = self.cfg
        t = u.shape[1]
        h, hd = cfg.held(cfg.n_heads), cfg.head_dim
        q, k, v = (remat.name(_dense(cfg, h * hd, "w" + n)(u), n)
                   for n in "qkv")
        if cfg.attn_qk_norm:
            q = RMSNorm(cfg.norm_eps, name="q_norm")(q)
            k = RMSNorm(cfg.norm_eps, name="k_norm")(k)
        for name, n in attn_ops.block_facts(
                t, t, causal=True, head_dim=hd,
                itemsize=k.dtype.itemsize).items():
            profiler.count_once(f"attn:{name}.attn", n)
        out = attn_ops.flash_attention_packed(
            q, k, v, h, causal=True, scale=hd ** -0.5,
            interpret=cfg.interpret)
        return remat.name(_dense(cfg, cfg.dim, "wo")(out), "wo"), ()


class MLA(nn.Module):
    """Latent attention expanded for training (module docstring): device
    scopes ``attn_mla`` > ``mla_proj`` (the projections and their norms),
    ``mla_rope`` (the rotation and, where the values are as wide as the
    whole key, each head's joined key) and the three flash calls —
    ``*_mla`` where the shared key part is read beside each head's own,
    the packed kernels' own names where the keys are joined."""
    cfg: HybridConfig

    @nn.compact
    def __call__(self, u):
        cfg = self.cfg
        b, t, _ = u.shape
        h, r = cfg.mla_heads, cfg.mla_kv_rank
        dn, ds, dv = cfg.mla_nope_dim, cfg.mla_rope_dim, cfg.mla_v_dim
        joined = dv != dn           # values as wide as the whole key
        theta = cfg.mla_rope_theta
        with jax.named_scope("mla_proj"):
            if cfg.mla_q_rank:
                q = _dense(cfg, h * (dn + ds), "wq_b")(
                    RMSNorm(cfg.norm_eps, name="q_norm")(
                        _dense(cfg, cfg.mla_q_rank, "wq_a")(u)))
            else:
                q = _dense(cfg, h * (dn + ds), "wq")(u)
            if not joined:
                q = remat.name(q, "q")
            q = q.reshape(b, t, h, dn + ds)
            kva = _dense(cfg, r + ds, "wkv_a")(u)
            c, ks = kva[..., :r], kva[..., r:]
            kv = _dense(cfg, h * (dn + dv), "wkv_b")(
                RMSNorm(cfg.norm_eps, name="kv_norm")(c))
            kv = kv.reshape(b, t, h, dn + dv)
            if not joined:
                k = remat.name(kv[..., :dn].reshape(b, t, h * dn), "k")
            v = remat.name(kv[..., dn:].reshape(b, t, h * dv), "v")
            if not joined:
                qn = q[..., :dn].reshape(b, t, h * dn)
                qs = q[..., dn:].transpose(0, 2, 1, 3)
        for name, n in attn_ops.block_facts(
                t, t, causal=True, head_dim=dv,
                itemsize=v.dtype.itemsize).items():
            profiler.count_once(f"attn:{name}.mla", n)
        for name, fact in (("kv_rank", r), ("qk_dim", dn + ds),
                           ("v_dim", dv), ("q_rank", cfg.mla_q_rank),
                           ("rope_dim", ds if theta else 0)):
            if fact:
                profiler.count_once("mla:" + name, fact)
        if not joined:
            out = attn_ops.flash_attention_mla(qn, qs, k, ks, v, h,
                                               interpret=cfg.interpret)
        else:
            with jax.named_scope("mla_rope"):
                qs, ks = q[..., dn:], ks[:, :, None]
                if theta:
                    qs = rope(qs, jnp.arange(t), theta, seq_axis=1)
                    ks = rope(ks, jnp.arange(t), theta, seq_axis=1)
                # packsite: region-local — each head's own part beside its
                # rotated part, one unsharded activation (no mesh).
                q = remat.name(jnp.concatenate(
                    [q[..., :dn], qs], -1).reshape(b, t, -1), "q")
                # packsite: region-local — each head's own part beside the
                # part all heads share.
                k = remat.name(jnp.concatenate(
                    [kv[..., :dn], jnp.broadcast_to(ks, (b, t, h, ds))],
                    -1).reshape(b, t, -1), "k")
            out = attn_ops.flash_attention_packed(
                q, k, v, h, causal=True, scale=(dn + ds) ** -0.5,
                interpret=cfg.interpret)
        return remat.name(_dense(cfg, cfg.dim, "wo")(out), "wo"), ()


class Mixer(NamedTuple):
    """A layer kind: the mixer module's name (its device scope), the
    streams it reads from earlier layers and writes for later ones, and
    how to build it for layer ``index``."""
    scope: str
    consumes: Tuple[str, ...]
    emits: Tuple[str, ...]
    build: Callable[..., nn.Module]


def _attention(kind: str):
    return lambda cfg, index, name: DiffAttention(cfg, index, kind, name=name)


MIXERS = {
    "mamba": Mixer("ssm", (), ("m",),
                   lambda cfg, index, name: Mamba(cfg, name=name)),
    "gmu": Mixer("gmu", ("m",), (),
                 lambda cfg, index, name: GMU(cfg, name=name)),
    "swa": Mixer("attn_swa", (), (), _attention("swa")),
    "full": Mixer("attn_full", (), ("k", "v"), _attention("full")),
    "cross": Mixer("attn_cross", ("k", "v"), (), _attention("cross")),
    "kda": Mixer("kda", (), (), lambda cfg, index, name: KDA(cfg, name=name)),
    "mla": Mixer("attn_mla", (), (),
                 lambda cfg, index, name: MLA(cfg, name=name)),
    "gdn": Mixer("gdn", (), (), lambda cfg, index, name: GDN(cfg, name=name)),
    "attn": Mixer("attn", (), (),
                  lambda cfg, index, name: Attn(cfg, name=name)),
}


class HybridLayer(nn.Module):
    """``x += mixer(LN(x)); x += MLP(LN(x))`` — under ``norm_placement=
    "post"`` ``x += LN(mixer(x)); x += LN(MLP(x))``; returns the new ``x``
    and what the mixer emits. The mixer's module name is its device
    scope."""
    cfg: HybridConfig
    kind: str
    index: int

    @nn.compact
    def __call__(self, x, *consumed):
        cfg = self.cfg
        mixer = MIXERS[self.kind]
        norm1, norm2 = _norm(cfg, "norm1"), _norm(cfg, "norm2")
        post = cfg.norm_placement == "post"
        out, emitted = mixer.build(cfg, self.index, mixer.scope)(
            x if post else norm1(x), *consumed)
        x = x + (norm1(out) if post else out)
        ffn = cfg.ffns[self.index] if cfg.ffns else None
        if ffn is None:
            mlp = GatedMLP(cfg, name="mlp")
        elif ffn == "experts":
            from tony_tpu.models.moe import DroplessMoE
            mlp = DroplessMoE(cfg.dim, cfg.moe_ffn, cfg.moe_experts,
                              top_k=cfg.moe_top_k,
                              experts_held=cfg.moe_experts_held,
                              expert_offset=cfg.moe_expert_offset,
                              dtype=cfg.dtype, quant=cfg.quant,
                              router="sigmoid",
                              route_scale=cfg.moe_route_scale,
                              shared=cfg.moe_shared, name="moe_mlp")
        else:
            mlp = SwiGLU(cfg, ffn[1], name="mlp")
        out = mlp(x if post else norm2(x))
        return x + (norm2(out) if post else out), emitted


class MTP(nn.Module):
    """The multi-token-prediction module (module docstring): ``h`` the
    stack's output before the final norm, ``e`` the next token's embedding
    -> what the head reads to predict the token after it. Device scopes
    ``mtp`` > ``mtp_proj`` (the two norms, the join, ``W_eh``), the layer's
    own scopes, and the head's second pass (the decoder's)."""
    cfg: HybridConfig

    @nn.compact
    def __call__(self, h, e):
        cfg = self.cfg
        with jax.named_scope("mtp_proj"):
            # packsite: region-local — the embedding's half beside the
            # stack's, one unsharded activation (no mesh).
            x = _dense(cfg, cfg.dim, "w_eh")(jnp.concatenate(
                [_norm(cfg, "e_norm")(e), _norm(cfg, "h_norm")(h)], -1))
        layer_cls = remat.block(HybridLayer) if cfg.remat else HybridLayer
        # One more of the last layer: its kind, its feed-forward.
        x, _ = layer_cls(cfg, cfg.layers[-1], len(cfg.layers) - 1,
                         name="layer")(x)
        return _norm(cfg, "final_norm")(x)


class HybridDecoder(nn.Module):
    cfg: HybridConfig

    @nn.compact
    def __call__(self, tokens, targets=None):
        cfg = self.cfg
        embed = self.param("embedding", nn.initializers.normal(0.02),
                           (cfg.vocab, cfg.dim), jnp.float32)
        with jax.named_scope("embed"):
            x = jnp.take(embed, tokens, axis=0).astype(cfg.dtype)
        layer_cls = remat.block(HybridLayer) if cfg.remat else HybridLayer
        streams: dict = {}
        for i, kind in enumerate(cfg.layers):
            mixer = MIXERS[kind]
            x, emitted = layer_cls(cfg, kind, i, name=f"layer_{i}")(
                x, *(streams[s] for s in mixer.consumes))
            streams.update(zip(mixer.emits, emitted))
        for kind in KINDS:
            profiler.count_once(f"model:layers.{kind}",
                                cfg.layers.count(kind))
        # Expert feed-forwards the model runs: the stack's, and the
        # multi-token-prediction module's (the last layer's kind).
        profiler.count_once("model:layers.experts", (
            cfg.ffns + cfg.ffns[-1:] * cfg.mtp_layers).count("experts"))
        if cfg.heads_held:
            profiler.count_once("model:heads_held", cfg.heads_held)
            profiler.count_once("model:heads_total", cfg.n_heads)
        second = None
        if cfg.mtp_layers:
            for name, fact in (("model:layers.mtp", cfg.mtp_layers),
                               ("mtp:weight", cfg.mtp_weight),
                               ("head:calls", 1 + cfg.mtp_layers)):
                profiler.count_once(name, fact)
            with jax.named_scope("embed"):
                ahead = jnp.take(embed, jnp.roll(tokens, -1, axis=1),
                                 axis=0).astype(cfg.dtype)
            second = MTP(cfg, name="mtp")(x, ahead)
        x = _norm(cfg, "final_norm")(x)
        # Tied head: the same table, transposed.
        head = embed.T if cfg.tie_embeddings else self.param(
            "lm_head_kernel", nn.initializers.lecun_normal(),
            (cfg.dim, cfg.vocab), jnp.float32)
        if cfg.xent_chunk and targets is not None:
            from tony_tpu.train import chunked_next_token_xent
            if second is not None:
                with jax.named_scope("mtp"):
                    self.sow("losses", "mtp_loss", cfg.mtp_weight
                             * chunked_next_token_xent(
                                 second, head, targets, cfg.xent_chunk,
                                 cfg.dtype, shift=2))
            return chunked_next_token_xent(x, head, targets,
                                           cfg.xent_chunk, cfg.dtype)
        with jax.named_scope("lm_head"):
            logits = lambda x: jnp.dot(x, head.astype(cfg.dtype),
                                       preferred_element_type=jnp.float32)
            if second is not None:
                self.sow("intermediates", "mtp_logits", logits(second))
            return logits(x)


@register("hybrid-decoder")
def hybrid_decoder(**kw) -> HybridDecoder:
    """The layer-kind decoder at Phi-4-mini-flash-reasoning's widths and
    its six-layer cut by default; every size is a keyword."""
    if "layers" in kw:
        kw["layers"] = tuple(kw["layers"])
    if "ffns" in kw:
        kw["ffns"] = tuple(f if isinstance(f, str) else tuple(f)
                           for f in kw["ffns"])
    if "scan_dtype" in kw:
        kw["scan_dtype"] = jnp.dtype(kw["scan_dtype"])
    return HybridDecoder(HybridConfig(**kw))


@register("hybrid-tiny")
def hybrid_tiny(**kw) -> HybridDecoder:
    """Test scale: the same code path and all five kinds at toy widths."""
    defaults = dict(vocab=256, dim=64, ffn_hidden=128, n_heads=4,
                    n_kv_heads=2, window=8, ssm_state=4, scan_chunk=8,
                    remat=False)
    defaults.update(kw)
    return hybrid_decoder(**defaults)


@register("kimi-linear-48b-a3b")
def kimi_linear(**kw) -> HybridDecoder:
    """Kimi-Linear-48B-A3B's widths over its layers 1-5 by default (the
    leading dense KDA layer, then KDA, KDA, MLA, KDA over experts): hidden
    2304, 32 x 128 KDA heads, MLA of 32 heads at 128 + 64 over a 512-wide
    latent, a dense SwiGLU of 9216, 8 of 256 sigmoid-routed experts of 1024
    and a shared one, gates scaled by 2.446; RMSNorm, an untied head. A
    deployment holds a range of the experts (``moe_experts_held``) and a
    slice of the vocabulary."""
    defaults = dict(
        vocab=163840, dim=2304, layers=KIMI_LINEAR_CUT,
        ffns=(("dense", 9216),) + ("experts",) * 4, norm="rmsnorm",
        tie_embeddings=False, moe_route_scale=2.446)
    defaults.update(kw)
    if "ffns" not in kw and "layers" in kw:
        defaults["ffns"] = (("dense", 9216),) \
            + ("experts",) * (len(tuple(kw["layers"])) - 1)
    return hybrid_decoder(**defaults)


@register("kimi-linear-tiny")
def kimi_linear_tiny(**kw) -> HybridDecoder:
    """Test scale: the same code path (both new mixers, the dense and the
    expert feed-forward) at toy widths."""
    defaults = dict(
        vocab=256, dim=64, layers=("kda", "kda", "mla"),
        ffns=(("dense", 128), "experts", "experts"), kda_heads=2,
        kda_head_dim=16, kda_chunk=8, kda_keep=2, mla_heads=2,
        mla_kv_rank=32, mla_nope_dim=16, mla_rope_dim=8, mla_v_dim=16,
        moe_experts=8, moe_top_k=2, moe_ffn=32, remat=False)
    defaults.update(kw)
    return kimi_linear(**defaults)


@register("olmo-hybrid-7b")
def olmo_hybrid(**kw) -> HybridDecoder:
    """Olmo-Hybrid-7B's widths over one period of its layers by default
    (Gated DeltaNet x 3, then full attention): hidden 3840, 30 heads — of
    96 key and 192 value channels in the linear layers, of 128 in the full
    one, q/k-normed, no rotation — a dense SwiGLU of 11008 a layer, RMSNorm
    after each sublayer, an untied head. A deployment holds a share of the
    heads (``heads_held``) and a slice of the vocabulary."""
    defaults = dict(
        vocab=100352, dim=3840, ffn_hidden=11008, n_heads=30, n_kv_heads=30,
        layers=OLMO_HYBRID_CUT, norm="rmsnorm", norm_eps=1e-6,
        norm_placement="post", tie_embeddings=False, attn_qk_norm=True)
    defaults.update(kw)
    defaults.setdefault("ffns", (("dense", defaults["ffn_hidden"]),)
                        * len(tuple(defaults["layers"])))
    return hybrid_decoder(**defaults)


@register("olmo-hybrid-tiny")
def olmo_hybrid_tiny(**kw) -> HybridDecoder:
    """Test scale: the same code path (both mixers, the reordered norm, a
    share of the heads) at toy widths off the lane width, ``dk != dv``."""
    defaults = dict(
        vocab=256, dim=64, ffn_hidden=128, n_heads=4, n_kv_heads=4,
        layers=("gdn", "attn"), gdn_heads=4, gdn_key_dim=12,
        gdn_value_dim=24, kda_chunk=8, kda_keep=2, remat=False)
    defaults.update(kw)
    return olmo_hybrid(**defaults)


# GLM-4.7-Flash's layers 0-4: the leading dense layer, then four of the
# period of one.
GLM_FLASH_CUT = ("mla",) * 5


@register("glm-4.7-flash")
def glm_flash(**kw) -> HybridDecoder:
    """GLM-4.7-Flash's widths over its layers 0-4 by default (the leading
    dense layer, then four expert layers) and its multi-token-prediction
    module: hidden 2048, rotated MLA of 20 heads at 192 + 64 over 256-wide
    values, the query through a 768-wide and key/value through a 512-wide
    latent, theta 1e6; a dense SwiGLU of 10240, 4 of 64 sigmoid-routed
    experts of 1536 and a shared one, gates scaled by 1.8; RMSNorm, an
    untied head, ``L_LM + 0.3 L_MTP``. A deployment holds a range of the
    experts (``moe_experts_held``) and a slice of the vocabulary."""
    defaults = dict(
        vocab=154880, dim=2048, layers=GLM_FLASH_CUT, norm="rmsnorm",
        tie_embeddings=False, mla_heads=20, mla_kv_rank=512, mla_q_rank=768,
        mla_nope_dim=192, mla_rope_dim=64, mla_v_dim=256,
        mla_rope_theta=1e6, moe_experts=64, moe_top_k=4, moe_ffn=1536,
        moe_route_scale=1.8, mtp_layers=1, mtp_weight=0.3)
    defaults.update(kw)
    defaults.setdefault("ffns", (("dense", 10240),) + ("experts",) * (
        len(tuple(defaults["layers"])) - 1))
    return hybrid_decoder(**defaults)


@register("glm-4.7-flash-tiny")
def glm_flash_tiny(**kw) -> HybridDecoder:
    """Test scale: the same code path (the low-rank query, the rotation,
    joined keys under values as wide, the dense and the expert
    feed-forward, the second token's module) at toy widths."""
    defaults = dict(
        vocab=256, dim=64, layers=("mla", "mla"),
        ffns=(("dense", 128), "experts"), mla_heads=2, mla_kv_rank=32,
        mla_q_rank=24, mla_nope_dim=24, mla_rope_dim=8, mla_v_dim=32,
        moe_experts=8, moe_top_k=2, moe_ffn=32, remat=False)
    defaults.update(kw)
    return glm_flash(**defaults)
