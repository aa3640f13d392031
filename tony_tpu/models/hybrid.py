"""A decoder built from a tuple of layer kinds — state-space, windowed and
full differential attention, gated memory units, cross-attention to one
shared K/V (the SambaY decoder-hybrid-decoder of arXiv:2507.06607, as
Phi-4-mini-flash-reasoning runs it).

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
================  =========================  ===================  =========

A later emitter replaces an earlier one's stream (the last ``mamba``
before a ``gmu`` is the one it reads). Layer i, pre-norm:
``x += mixer_i(LN(x)); x += MLP(LN(x))`` with LayerNorm (scale and bias)
and ``MLP(u) = (up * silu(gate)) W2, [gate, up] = u W1``. No positional
encoding. The embedding table is tied: one parameter, looked up at the
bottom and multiplied at the top (``vocab`` may be a slice of the
published table — ids, logits and loss are then over the slice).

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
from tony_tpu.models.transformer import RMSNorm
from tony_tpu.ops import attention as attn_ops
from tony_tpu.ops import ssm

KINDS = ("mamba", "swa", "full", "gmu", "cross")
PHI4_FLASH_CUT = ("mamba", "swa", "mamba", "full", "gmu", "cross")


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
        if self.n_heads % 2 or self.n_kv_heads % 2 \
                or (self.n_heads // 2) % (self.n_kv_heads // 2):
            raise ValueError("differential attention pairs heads: n_heads "
                             "and n_kv_heads even, query pairs a multiple "
                             "of K/V pairs")
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
}


class HybridLayer(nn.Module):
    """``x += mixer(LN(x)); x += MLP(LN(x))``; returns the new ``x`` and
    what the mixer emits. The mixer's module name is its device scope."""
    cfg: HybridConfig
    kind: str
    index: int

    @nn.compact
    def __call__(self, x, *consumed):
        cfg = self.cfg
        mixer = MIXERS[self.kind]
        out, emitted = mixer.build(cfg, self.index, mixer.scope)(
            LayerNorm(cfg.norm_eps, name="norm1")(x), *consumed)
        x = x + out
        x = x + GatedMLP(cfg, name="mlp")(
            LayerNorm(cfg.norm_eps, name="norm2")(x))
        return x, emitted


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
        x = LayerNorm(cfg.norm_eps, name="final_norm")(x)
        # Tied head: the same table, transposed.
        if cfg.xent_chunk and targets is not None:
            from tony_tpu.train import chunked_next_token_xent
            return chunked_next_token_xent(x, embed.T, targets,
                                           cfg.xent_chunk, cfg.dtype)
        with jax.named_scope("lm_head"):
            return jnp.dot(x, embed.T.astype(cfg.dtype),
                           preferred_element_type=jnp.float32)


@register("hybrid-decoder")
def hybrid_decoder(**kw) -> HybridDecoder:
    """The layer-kind decoder at Phi-4-mini-flash-reasoning's widths and
    its six-layer cut by default; every size is a keyword."""
    if "layers" in kw:
        kw["layers"] = tuple(kw["layers"])
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
