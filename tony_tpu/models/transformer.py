"""Llama-style decoder for the GSPMD graduation config (SURVEY.md §6
config ⑤: ``pjit``/GSPMD Llama-2-7B on a pod slice).

TPU-first design:

* bf16 compute / f32 params, RMSNorm in f32 (numerics), rotary embeddings,
  grouped-query attention, SwiGLU MLP — matmul shapes stay MXU-friendly
  multiples of 128 in the real configs;
* every parameter carries flax *logical* axis names
  (``nn.with_logical_partitioning``); :data:`tony_tpu.parallel.RULES` maps
  them to the dp/fsdp/tp mesh so GSPMD inserts the tensor-parallel
  collectives — no hand-written allreduce;
* attention dispatches by what the layer is given: without a mesh and at a
  head size that is whole lane tiles,
  :func:`tony_tpu.ops.flash_attention_packed` over the projections' own
  ``[B, T, H·D]`` (no transpose); with q/k-norm
  or an indexer, ``flash_attention_selected``; in a latent, the packed
  kernels over its narrower heads; under a mesh,
  ``flash_attention_sharded`` (``[B, H, T, D]``, heads on the tp axis), or
  :func:`tony_tpu.parallel.ring_attention_sharded` when the sequence axis
  is sharded (long context, SURVEY.md §5.7); serving, ``flash_decode``
  over the cache; off the TPU, the pure-JAX reference;
* ``scan_layers`` folds the layer stack into one ``nn.scan`` (one trace +
  one compile of a single block) and ``remat`` wraps blocks in
  ``jax.checkpoint`` to trade FLOPs for HBM (:func:`tony_tpu.remat.block`:
  the train step says which named residuals the chip has room to keep).
"""

from __future__ import annotations

import dataclasses
from typing import Any, Optional, Tuple

import flax.linen as nn
import jax
import jax.numpy as jnp

from tony_tpu import remat
from tony_tpu.models import register
from tony_tpu.ops import flash_attention, reference_attention


@dataclasses.dataclass(frozen=True)
class TransformerConfig:
    vocab: int = 32000
    dim: int = 4096
    n_layers: int = 32
    n_heads: int = 32
    n_kv_heads: int = 32
    ffn_hidden: int = 11008
    max_seq: int = 4096
    rope_theta: float = 10000.0
    norm_eps: float = 1e-5
    dtype: Any = jnp.bfloat16
    attention: str = "flash"        # flash | ring | reference
    scan_layers: bool = True
    # True: every block's backward recomputes it from its input, but for
    # the named residuals the train step found room for (tony_tpu.remat).
    # False keeps everything (the tests' and the rehearsal's setting).
    remat: bool = True
    mesh: Optional[Any] = None      # required for attention="ring"
    # MoE (SURVEY.md §2.3 expert parallelism): >0 swaps the dense MLP for
    # an expert-parallel MoEMLP in every block.
    moe_experts: int = 0
    moe_top_k: int = 2
    moe_capacity_factor: float = 1.25
    moe_aux_coef: float = 0.01
    # >0 fuses the LM head with a row-chunked cross entropy: the [B,T,V]
    # logits tensor (f32: 4 GB at b64·s512·v32k) never materializes —
    # per-chunk logits are consumed immediately and rematerialized in the
    # backward. __call__ then takes targets and returns the scalar loss.
    xent_chunk: int = 0
    # Quantized compute lane (tony_tpu.ops.quant): which projection
    # groups run int8×int8→int32 matmuls with f32 rescale. True =
    # ("qkv", "o", "mlp"); a tuple selects explicitly ("lm_head" opts
    # the unembed in). "qkv" takes an indexer's three projections along;
    # under "mlp" dropless experts multiply their operands' int8 image. Embedding and norms stay bf16/f32 by policy. The
    # lane is loss-pin gated: tests/test_quant.py holds the quantized
    # tiny-transformer curve against bf16 within a committed tolerance.
    quant: Any = None
    # Width of one attention head where it is not dim / n_heads (0).
    attn_head_dim: int = 0
    # RMSNorm over each q and k head before the rotation.
    qk_norm: bool = False
    # Learned sparse attention (tony_tpu.ops.indexer): >0 gives every
    # block an indexer of this many heads of index_dim with one key head;
    # each query then attends the index_topk keys it scores highest (one
    # selection a query, shared by the heads), and the indexer's KL loss
    # is sown into ``losses``.
    index_heads: int = 0
    index_dim: int = 64
    index_topk: int = 2048
    # Dropless experts (models.moe.DroplessMoE) instead of the capacity
    # path: routes over moe_experts, holds the contiguous range
    # [moe_expert_offset, moe_expert_offset + moe_experts_held) of them
    # (0 held: all) and returns their part of the result.
    moe_dropless: bool = False
    moe_experts_held: int = 0
    moe_expert_offset: int = 0
    # Attention in a compressed latent with convolutional q/k mixing
    # (tony_tpu.ops.cca; arXiv:2510.04476): q, k, v are n_heads /
    # n_kv_heads x attn_head_dim wide (less than dim), the second half of
    # v reads the token before, q and k pass a depthwise causal
    # convolution of cca_taps[0] taps and a per-head one of cca_taps[1],
    # take each other's mean, are L2-normalised (k times a learned
    # temperature a head) and rotated.
    attn_latent: bool = False
    cca_taps: Tuple[int, int] = (2, 2)
    # Share of each head's width that is rotated (the leading dims).
    rope_fraction: float = 1.0
    # >0: the dropless experts' router is an MLP of this width
    # (models.moe.MLPRouter) whose state runs from layer to layer: the
    # layer stack carries (x, router_state); the gate is the softmax
    # probability itself.
    router_hidden: int = 0
    # One table: the head reads the embedding transposed.
    tie_embeddings: bool = False

    @property
    def head_dim(self) -> int:
        return self.attn_head_dim or self.dim // self.n_heads

    def quant_lanes(self) -> frozenset:
        """The validated set of quantized projection groups."""
        if not self.quant:
            return frozenset()
        lanes = ("qkv", "o", "mlp") if self.quant is True else (
            (self.quant,) if isinstance(self.quant, str)
            else tuple(self.quant))
        unknown = set(lanes) - {"qkv", "o", "mlp", "lm_head"}
        if unknown:
            raise ValueError(
                f"unknown quant lane(s) {sorted(unknown)} — choose from "
                f"('qkv', 'o', 'mlp', 'lm_head')")
        if "lm_head" in lanes and self.xent_chunk:
            raise ValueError(
                "quant lane 'lm_head' is not supported with xent_chunk "
                "(the fused head+loss consumes the kernel row-chunked; "
                "quantize it separately or drop the lane)")
        return frozenset(lanes)

    def flops_per_token(self) -> int:
        """≈6·N_matmul FLOPs per trained token (fwd+bwd), plus attention's
        12·L·heads·head_dim·seq term — matmul-FLOPs-only MFU accounting.
        The input embedding is a gather (backward: scatter-add) and
        contributes zero matmul FLOPs, so only the unembed projection
        counts toward the vocab term. For MoE, only the top-k experts' FFN
        params are active per token — of a held range, the share of them
        that an even routing sends here; a router MLP counts its four
        matrices, latent attention its per-head convolution (the depthwise
        one is no matmul). A tied head is still one multiplied table. With
        an indexer: its three
        projections, its scores over the causal half (forward and
        backward), and attention over the selected pairs only (a token
        sees min(position + 1, index_topk) keys)."""
        ffn_active = 3 * self.dim * self.ffn_hidden
        if self.moe_experts > 0:
            share = (self.moe_experts_held or self.moe_experts) \
                / self.moe_experts if self.moe_dropless else 1.0
            h = self.router_hidden
            router = self.dim * h + 2 * h * h + h * self.moe_experts \
                if h else self.dim * self.moe_experts
            ffn_active = int(self.moe_top_k * share * ffn_active + router)
        qdim = self.n_heads * self.head_dim
        mix = self.cca_taps[1] * self.head_dim ** 2 * (
            self.n_heads + self.n_kv_heads) if self.attn_latent else 0
        n_params = (
            self.vocab * self.dim  # unembed only; embed gather = 0 matmul FLOPs
            + self.n_layers * (
                self.dim * self.head_dim
                * (self.n_heads + 2 * self.n_kv_heads)   # wq, wk, wv
                + qdim * self.dim                          # wo
                + mix + ffn_active))
        t = self.max_seq
        if not self.index_heads:
            return 6 * n_params + 12 * self.n_layers * qdim * t
        k = min(self.index_topk, t)
        keys_seen = (k * (k + 1) // 2 + (t - k) * k) / t   # mean a token
        index = self.index_heads * self.index_dim
        n_params += self.n_layers * self.dim * (
            index + self.index_dim + self.index_heads)
        return int(6 * n_params + self.n_layers * (
            12 * qdim * keys_seen + 6 * index * (t + 1) / 2))


def rope(x: jax.Array, positions: jax.Array, theta: float,
         seq_axis: int = 2, fraction: float = 1.0) -> jax.Array:
    """Rotary embedding with positions [T] (shared across the batch) or
    [B, T] (per-sequence absolute positions — the serving plane's decode
    rows sit at different depths per sequence); the sequence dim sits at
    ``seq_axis`` (2 for [B, H, T, D], 1 for the packed [B, T, H, D]).
    ``fraction`` < 1 rotates only that leading share of the last dim (its
    frequencies span the rotated width) and passes the rest through."""
    if fraction < 1.0:
        rot = int(x.shape[-1] * fraction)
        # packsite: region-local — the rotated and the passed share of one
        # head's own lanes; no sharded dim is joined.
        return jnp.concatenate(
            [rope(x[..., :rot], positions, theta, seq_axis), x[..., rot:]],
            axis=-1)
    d = x.shape[-1]
    freqs = theta ** (-jnp.arange(0, d, 2, dtype=jnp.float32) / d)
    if positions.ndim == 2:
        angles = positions[..., None].astype(jnp.float32) * freqs  # [B,T,D/2]
        shape = [1] * x.ndim
        shape[0] = angles.shape[0]
        shape[seq_axis] = angles.shape[1]
        shape[-1] = d // 2
    else:
        angles = positions[:, None].astype(jnp.float32) * freqs[None, :]  # [T, D/2]
        shape = [1] * x.ndim
        shape[seq_axis] = angles.shape[0]
        shape[-1] = d // 2
    cos = jnp.cos(angles).reshape(shape)
    sin = jnp.sin(angles).reshape(shape)
    x1, x2 = x[..., ::2], x[..., 1::2]
    y1 = x1 * cos - x2 * sin
    y2 = x1 * sin + x2 * cos
    # packsite: region-local — elementwise RoPE recombination along a
    # NEW trailing axis; operands share one sharding, no shard-dim concat.
    return jnp.stack([y1, y2], axis=-1).reshape(x.shape).astype(x.dtype)


def _proj_dense(cfg: TransformerConfig, lane: str, feats: int,
                logical: Tuple[str, ...], name: str):
    """One projection on either compute lane: ``nn.Dense`` (bf16 MXU) or
    its quantized twin (int8 MXU, f32 rescale) when ``lane`` is in the
    config's quant set — identical param tree paths either way, so a
    checkpoint moves freely between the lanes."""
    init = nn.with_logical_partitioning(
        nn.initializers.lecun_normal(), logical)
    if lane in cfg.quant_lanes():
        from tony_tpu.ops.quant import QuantDense
        return QuantDense(feats, dtype=cfg.dtype,
                          param_dtype=jnp.float32, name=name,
                          kernel_init=init)
    return nn.Dense(feats, use_bias=False, dtype=cfg.dtype,
                    param_dtype=jnp.float32, name=name, kernel_init=init)


class RMSNorm(nn.Module):
    eps: float = 1e-5

    @nn.compact
    def __call__(self, x):
        scale = self.param("scale", nn.with_logical_partitioning(
            nn.initializers.ones, ("norm",)), (x.shape[-1],), jnp.float32)
        x32 = x.astype(jnp.float32)
        y = x32 * jax.lax.rsqrt(
            jnp.mean(x32 * x32, axis=-1, keepdims=True) + self.eps)
        return (y * scale).astype(x.dtype)


def _count_blocks(t: int, head_dim: int, itemsize: int) -> None:
    """The flash calls' trace-time facts on the task's timeline, once:
    ``attn:block_q.<fwd|dq|dkv>.dense``, ``attn:block_k.<...>.dense`` (the
    tile shape each kernel of this run gets from the kernels' rule) and
    ``attn:kv_blocks_visited.dense`` / ``attn:kv_blocks_total.dense`` /
    ``attn:kv_blocks_fetched.dense``."""
    from tony_tpu import profiler
    from tony_tpu.ops.attention import block_facts

    for name, n in block_facts(t, t, causal=True, head_dim=head_dim,
                               itemsize=itemsize).items():
        profiler.count_once(f"attn:{name}.dense", n)


def _count_selection(t: int, topk: int, head_dim: int, itemsize: int) -> None:
    """The selected-attention calls' trace-time facts, once:
    ``attn:index_topk``, ``attn:selected_pairs`` / ``attn:causal_pairs``
    (pairs of one sequence: what the selection leaves of the triangle),
    ``attn:block_q.<fwd|dq|dkv>.sel`` / ``attn:block_k.<...>.sel`` and
    ``attn:kv_blocks_visited.sel`` / ``attn:kv_blocks_total.sel`` /
    ``attn:kv_blocks_fetched.sel`` (the kernels visit every tile at or
    below the diagonal: none is skipped for holding no selected key; they
    fetch no K/V block of a tile above it)."""
    from tony_tpu import profiler
    from tony_tpu.ops.attention import selection_blocks, streamed_fetches

    k = min(topk, t)
    profiler.count_once("attn:index_topk", topk)
    profiler.count_once("attn:selected_pairs", k * (k + 1) // 2 + (t - k) * k)
    profiler.count_once("attn:causal_pairs", t * (t + 1) // 2)
    blocks = selection_blocks(t, head_dim, itemsize)
    for kernel, (bq, bk) in blocks._asdict().items():
        profiler.count_once(f"attn:block_q.{kernel}.sel", bq)
        profiler.count_once(f"attn:block_k.{kernel}.sel", bk)
    bq, bk = blocks.fwd
    profiler.count_once("attn:kv_blocks_visited.sel", sum(
        ((qi + 1) * bq - 1) // bk + 1 for qi in range(t // bq)))
    profiler.count_once("attn:kv_blocks_total.sel", (t // bq) * (t // bk))
    profiler.count_once("attn:kv_blocks_fetched.sel",
                        streamed_fetches(t, t, bq, bk))


class Attention(nn.Module):
    cfg: TransformerConfig

    @nn.compact
    def __call__(self, x, positions, kv=None):
        cfg = self.cfg
        if cfg.attn_latent:
            if kv is not None or cfg.mesh is not None:
                raise ValueError("latent attention has no serve mode and "
                                 "no sharded path")
            return self._latent(x, positions)
        b, t, _ = x.shape
        hd, nh, nkv = cfg.head_dim, cfg.n_heads, cfg.n_kv_heads
        # Fused-head projections with rank-2 kernels: (embed, heads·hd)
        # sharded ('fsdp', 'model') — the megatron TP layout. (DenseGeneral's
        # multi-dim features initialize flat then reshape, which breaks
        # logical-metadata unboxing under an active mesh.)
        dense = lambda feats, logical, name, lane: _proj_dense(
            cfg, lane, feats, logical, name)
        # The residuals a train step may keep (tony_tpu.remat) are named
        # where they are made; to any other trace a name is nothing.
        q = remat.name(dense(nh * hd, ("embed", "heads"), "wq", "qkv")(x),
                       "q")
        k = remat.name(
            dense(nkv * hd, ("embed", "kv_heads"), "wk", "qkv")(x), "k")
        v = remat.name(
            dense(nkv * hd, ("embed", "kv_heads"), "wv", "qkv")(x), "v")
        wo = lambda out: remat.name(
            dense(cfg.dim, ("heads", "embed"), "wo", "o")(out), "wo")
        if kv is not None:
            # Serve-mode forward (tony_tpu.serve): the t rows are NEW
            # tokens at per-sequence absolute ``positions`` [b, t]; the
            # context lives in the gathered KV buffer [b, ctx, nkv·hd].
            # The rows' post-rope k/v scatter into the buffer (so a row
            # attends itself and everything the cache holds below its
            # position), attention runs through the flash-decoding
            # kernel, and the raw rows are returned for the engine to
            # commit into the paged pool. Projections are the SAME
            # denses as training — the quant= lanes ride along — so a
            # training checkpoint serves without any param surgery.
            # Prefill, decode, AND speculative k+1-row verification
            # (serve.spec) are all this one branch at different real-row
            # counts: the scatter-before-attend order is what lets a
            # verify row attend the draft rows below it in the same
            # launch, and the in-buffer overwrite of positions >= each
            # row's own block start is what makes rolled-back (stale)
            # pool rows unreadable by construction.
            if cfg.qk_norm or cfg.index_heads:
                raise ValueError("serve mode has no q/k-norm and no "
                                 "selection inside paged decode")
            k_buf, v_buf = kv
            pos = positions.astype(jnp.int32)
            q4 = rope(q.reshape(b, t, nh, hd), pos, cfg.rope_theta,
                      seq_axis=1)
            k4 = rope(k.reshape(b, t, nkv, hd), pos, cfg.rope_theta,
                      seq_axis=1)
            k_rows = k4.reshape(b, t, nkv * hd).astype(k_buf.dtype)
            v_rows = v.astype(v_buf.dtype)
            bidx = jnp.arange(b)[:, None]
            # mode="drop": rows whose position falls off the buffer end
            # (the trailing padding rows of a decode block near ctx_max)
            # simply don't write.
            with jax.named_scope("attn_kv_scatter"):
                k_buf = k_buf.at[bidx, pos].set(k_rows, mode="drop")
                v_buf = v_buf.at[bidx, pos].set(v_rows, mode="drop")
            ctx = k_buf.shape[1]
            from tony_tpu.ops import flash_decode
            with jax.named_scope("attn_decode"):
                out = flash_decode(
                    q4.transpose(0, 2, 1, 3),
                    k_buf.reshape(b, ctx, nkv, hd).transpose(0, 2, 1, 3),
                    v_buf.reshape(b, ctx, nkv, hd).transpose(0, 2, 1, 3),
                    pos)
            out = out.transpose(0, 2, 1, 3).reshape(b, t, nh * hd)
            return wo(out), (k_rows, v_rows)
        if cfg.qk_norm or cfg.index_heads:
            if cfg.mesh is not None:
                raise ValueError("q/k-norm and the indexer have no sharded "
                                 "attention path")
            return wo(self._selected(x, q, k, v, positions))
        if (cfg.attention == "flash" and cfg.mesh is None
                and hd % 128 == 0):
            # Packed layout: the kernel reads heads as lane offsets from
            # the projections' natural [B, T, H·D] shape — the [B, H, T, D]
            # transpose copies (profiled ~5% of the Llama step) never
            # materialize.
            from tony_tpu.ops import flash_attention_packed
            q4 = rope(q.reshape(b, t, nh, hd), positions, cfg.rope_theta,
                      seq_axis=1)
            k4 = rope(k.reshape(b, t, nkv, hd), positions, cfg.rope_theta,
                      seq_axis=1)
            # GQA is zero-copy through the packed kernels: K/V stay at
            # [B, T, nkv·hd]; the kernel's index maps route query head h
            # to kv lane-block h·nkv/nh: no jnp.repeat, and no HBM spent
            # on repeated heads.
            _count_blocks(t, hd, v.dtype.itemsize)
            out = flash_attention_packed(
                q4.reshape(b, t, nh * hd), k4.reshape(b, t, nkv * hd), v,
                nh, causal=True)
            return wo(out)
        # [B, T, H·D] → [B, H, T, D]
        q = q.reshape(b, t, nh, hd).transpose(0, 2, 1, 3)
        k = k.reshape(b, t, nkv, hd).transpose(0, 2, 1, 3)
        v = v.reshape(b, t, nkv, hd).transpose(0, 2, 1, 3)
        q = rope(q, positions, cfg.rope_theta)
        k = rope(k, positions, cfg.rope_theta)
        # No GQA repeat on ANY path: the flash kernels, ring attention, and
        # reference_attention are all GQA-native — ring even ships
        # the narrow K/V around the ICI ring, dividing rotate traffic by
        # the group size.
        if cfg.attention == "ring":
            from tony_tpu.parallel import ring_attention_sharded
            assert cfg.mesh is not None, "attention='ring' needs cfg.mesh"
            out = ring_attention_sharded(q, k, v, cfg.mesh, causal=True)
        elif cfg.attention == "flash":
            if cfg.mesh is not None and cfg.mesh.shape.get("seq", 1) > 1:
                # A sharded sequence axis means per-device flash would be
                # wrong (causal attention needs global K/V) — ring
                # attention owns that layout.
                from tony_tpu.parallel import ring_attention_sharded
                out = ring_attention_sharded(q, k, v, cfg.mesh, causal=True)
            elif cfg.mesh is not None:
                # GSPMD can't partition a pallas call from annotations
                # alone — explicitly map it (heads on the tp axis).
                from tony_tpu.ops import flash_attention_sharded
                _count_blocks(t, hd, v.dtype.itemsize)
                out = flash_attention_sharded(q, k, v, cfg.mesh, causal=True)
            else:
                _count_blocks(t, hd, v.dtype.itemsize)
                out = flash_attention(q, k, v, causal=True)
        else:
            out = reference_attention(q, k, v, causal=True)
        out = out.transpose(0, 2, 1, 3).reshape(b, t, nh * hd)
        return wo(out)

    def _latent(self, x, positions):
        """Training-mode attention in a compressed latent
        (tony_tpu.ops.cca): the packed flash kernel over ``H·d`` query and
        ``G·d`` key/value columns, fewer than ``dim``. Device scopes
        ``cca_proj`` (the three projections and v's shifted half) and
        ``cca_mix`` (both convolutions, the means, norms, rotation)."""
        from tony_tpu import profiler
        from tony_tpu.ops import cca, flash_attention_packed

        cfg = self.cfg
        b, t, _ = x.shape
        hd, nh, nkv = cfg.head_dim, cfg.n_heads, cfg.n_kv_heads
        dense = lambda feats, logical, name, lane: _proj_dense(
            cfg, lane, feats, logical, name)
        with jax.named_scope("cca_proj"):
            q_raw = dense(nh * hd, ("embed", "heads"), "wq", "qkv")(x)
            k_raw = dense(nkv * hd, ("embed", "kv_heads"), "wk", "qkv")(x)
            v = dense(nkv * hd, ("embed", "kv_heads"), "wv", "qkv")(x)
            # The second half of v's heads reads the token before:
            # x_{t-1} W = (x W)_{t-1}.
            late = jnp.arange(nkv * hd) >= (nkv - nkv // 2) * hd
            v = remat.name(jnp.where(late, cca.shift1(v), v), "v")
        taps0, taps1 = cfg.cca_taps
        param = lambda name, init, shape: self.param(
            name, nn.with_logical_partitioning(init, (None,) * len(shape)),
            shape, jnp.float32)
        normal = lambda fan_in: nn.initializers.normal(fan_in ** -0.5)
        mix_params = (
            param("conv0_w", normal(taps0), (taps0, (nh + nkv) * hd)),
            param("conv0_b", nn.initializers.zeros, ((nh + nkv) * hd,)),
            param("conv1_w", normal(taps1 * hd), (taps1, nh + nkv, hd, hd)),
            param("conv1_b", nn.initializers.zeros, ((nh + nkv) * hd,)),
            param("tau", nn.initializers.ones, (nkv,)))

        # Recomputed in its own backward, whatever the block keeps: a
        # dozen float32 [T, H d] links of an elementwise chain that XLA
        # fuses away when it meets them together, and holds (1.3 GiB at
        # 32k tokens) when the forward hands them to the backward.
        @jax.checkpoint
        @jax.named_scope("cca_mix")
        def mixed(q_raw, k_raw, mix_params):
            q4, k4 = cca.mix(q_raw, k_raw, *mix_params, n_heads=nh,
                             n_kv_heads=nkv, eps=cfg.norm_eps)
            return tuple(
                rope(a, positions, cfg.rope_theta, seq_axis=1,
                     fraction=cfg.rope_fraction).astype(cfg.dtype).reshape(
                         b, t, -1) for a in (q4, k4))

        q3, k3 = mixed(q_raw, k_raw, mix_params)
        for name, n in (("heads", nh), ("kv_heads", nkv), ("taps", taps0)):
            profiler.count_once("cca:" + name, n)
        if hd % 128 == 0:
            _count_blocks(t, hd, v.dtype.itemsize)
        out = flash_attention_packed(remat.name(q3, "q"),
                                     remat.name(k3, "k"), v, nh, causal=True)
        return remat.name(
            dense(cfg.dim, ("heads", "embed"), "wo", "o")(out), "wo")

    def _selected(self, x, q, k, v, positions):
        """Training-mode attention with q/k-norm and/or a learned
        selection, over the packed layout: returns the ``[B, T, H·D]``
        output before ``wo`` and sows the indexer's loss. The indexer's
        ``qI, w, kI`` are made once, select, and are handed to the loss
        with the three kernels they came through: the loss runs once a
        layer and leaves the kernels' gradients as its residuals
        (:func:`tony_tpu.ops.indexer.index_loss`)."""
        from tony_tpu.ops import attention as att
        from tony_tpu.ops import indexer

        cfg = self.cfg
        b, t, _ = x.shape
        hd, nh, nkv = cfg.head_dim, cfg.n_heads, cfg.n_kv_heads
        q4, k4 = q.reshape(b, t, nh, hd), k.reshape(b, t, nkv, hd)
        if cfg.qk_norm:
            q4 = RMSNorm(cfg.norm_eps, name="q_norm")(q4)
            k4 = RMSNorm(cfg.norm_eps, name="k_norm")(k4)
        q3 = rope(q4, positions, cfg.rope_theta, seq_axis=1).reshape(
            b, t, nh * hd)
        k3 = rope(k4, positions, cfg.rope_theta, seq_axis=1).reshape(
            b, t, nkv * hd)
        if not cfg.index_heads:
            return att.flash_attention_packed(q3, k3, v, nh, causal=True)
        # The indexer reads the block's input and gives it no gradient:
        # its three projections learn from their own loss alone.
        xi = jax.lax.stop_gradient(x)
        nj, ne = cfg.index_heads, cfg.index_dim
        denses = {name: _proj_dense(cfg, "qkv", feats, ("embed", None), name)
                  for name, feats in (("index_wq", nj * ne), ("index_wk", ne),
                                      ("index_w", nj))}

        def project(dense, positions):
            """``qI, w, kI`` of the three projections ``dense(name)``."""
            with jax.named_scope("attn_index"):
                qi = rope(dense("index_wq").reshape(b, t, nj, ne), positions,
                          cfg.rope_theta, seq_axis=1)
                ki = rope(dense("index_wk").reshape(b, t, 1, ne), positions,
                          cfg.rope_theta, seq_axis=1).reshape(b, t, ne)
                wi = dense("index_w").astype(jnp.float32) * (nj * ne) ** -0.5
            return qi, wi, ki

        def of_kernels(kernels, xi, positions):
            """:func:`project` as a function of the kernels: what the
            loss transposes to hand them its gradient."""
            return project(lambda name: denses[name].apply(
                {"params": {"kernel": kernels[name]}}, xi), positions)

        qi, wi, ki = project(lambda name: denses[name](xi), positions)
        kernels = {name: nn.unbox(dense.variables["params"]["kernel"])
                   for name, dense in denses.items()}
        # Kernels on a TPU, their jax.numpy twins elsewhere (the ops
        # decide by backend, as flash_attention does).
        sel = remat.name(indexer.select(qi, wi, ki, cfg.index_topk), "sel")
        if t % 128 == 0:
            _count_selection(t, cfg.index_topk, hd, v.dtype.itemsize)
        out, lse = att.flash_attention_selected(q3, k3, v, sel, nh)
        # One pass a layer: the loss takes its gradient with its value and
        # keeps it as the three kernels' (remat.block keeps "index_grad").
        loss = indexer.index_loss(qi, wi, ki, sel, q3, k3, lse, nh,
                                  through=(of_kernels, kernels, xi, positions))
        self.sow("losses", "index_kl", loss,
                 reduce_fn=lambda a, c: a + c,
                 init_fn=lambda: jnp.float32(0.0))
        return out


class MLP(nn.Module):
    cfg: TransformerConfig

    @nn.compact
    def __call__(self, x):
        cfg = self.cfg
        dense = lambda feats, logical, name: _proj_dense(
            cfg, "mlp", feats, logical, name)
        gate = remat.name(
            dense(cfg.ffn_hidden, ("embed", "ffn"), "w_gate")(x), "gate")
        up = remat.name(
            dense(cfg.ffn_hidden, ("embed", "ffn"), "w_up")(x), "up")
        y = nn.silu(gate) * up
        return dense(cfg.dim, ("ffn", "embed"), "w_down")(y)


class Block(nn.Module):
    cfg: TransformerConfig

    @nn.compact
    def __call__(self, x, positions, kv=None):
        """``x`` is the residual stream, or with ``cfg.router_hidden`` the
        pair ``(x, router_state)`` the layer stack carries; the same comes
        back."""
        cfg = self.cfg
        if cfg.router_hidden:
            if not (cfg.moe_experts > 0 and cfg.moe_dropless):
                raise ValueError("router_hidden is the dropless experts' "
                                 "router: set moe_experts and moe_dropless")
            x, router_state = x
        attn_out = Attention(cfg, name="attn")(
            RMSNorm(cfg.norm_eps, name="attn_norm")(x), positions, kv=kv)
        new_kv = None
        if kv is not None:
            attn_out, new_kv = attn_out
        x = x + attn_out
        if cfg.moe_experts > 0 and cfg.moe_dropless:
            from tony_tpu.models.moe import DroplessMoE
            mlp = DroplessMoE(cfg.dim, cfg.ffn_hidden, cfg.moe_experts,
                              top_k=cfg.moe_top_k,
                              experts_held=cfg.moe_experts_held,
                              expert_offset=cfg.moe_expert_offset,
                              dtype=cfg.dtype,
                              quant="mlp" in cfg.quant_lanes(),
                              router_hidden=cfg.router_hidden,
                              norm_eps=cfg.norm_eps,
                              name="moe_mlp")
        elif cfg.moe_experts > 0:
            from tony_tpu.models.moe import MoEMLP
            mlp = MoEMLP(cfg.dim, cfg.ffn_hidden, cfg.moe_experts,
                         top_k=cfg.moe_top_k,
                         capacity_factor=cfg.moe_capacity_factor,
                         aux_coef=cfg.moe_aux_coef, dtype=cfg.dtype,
                         name="moe_mlp")
        else:
            mlp = MLP(cfg, name="mlp")
        if cfg.router_hidden:
            y, router_state = mlp(
                RMSNorm(cfg.norm_eps, name="mlp_norm")(x), router_state)
            return x + y, router_state
        x = x + mlp(RMSNorm(cfg.norm_eps, name="mlp_norm")(x))
        if kv is not None:
            return x, new_kv
        return x


class ScannedBlock(nn.Module):
    """Carry-signature wrapper so the layer stack folds into one
    ``nn.scan`` (single-block trace/compile, stacked params on a leading
    ``stage`` axis). In serve mode the per-layer KV buffer arrives as a
    scanned input and the freshly-written rows leave as the scan's
    stacked ys."""
    cfg: TransformerConfig

    @nn.compact
    def __call__(self, x, positions, kv=None):
        if kv is not None:
            return Block(self.cfg, name="block")(x, positions, kv=kv)
        return Block(self.cfg, name="block")(x, positions), None


class Transformer(nn.Module):
    cfg: TransformerConfig

    @nn.compact
    def __call__(self, tokens, targets=None, *, positions=None, kv=None):
        cfg = self.cfg
        _b, t = tokens.shape
        if kv is not None:
            # Serve-mode forward (tony_tpu.serve.engine): tokens are a
            # row block of NEW positions per sequence, context comes from
            # the gathered KV buffers (one [b, ctx, nkv·hd] pair per
            # layer, stacked on a leading layer axis), and the return is
            # ``(logits, (k_rows, v_rows))`` for the engine to commit
            # into its paged pool. Training traces are untouched: this
            # branch only exists when the engine passes kv.
            if targets is not None:
                raise ValueError("serve-mode forward takes no targets")
            if positions is None:
                raise ValueError("serve-mode forward needs positions "
                                 "[b, t] (per-sequence absolute)")
            if cfg.moe_experts > 0:
                raise ValueError("serve mode does not support MoE blocks")
        if cfg.tie_embeddings and not cfg.xent_chunk:
            raise ValueError("tie_embeddings needs the fused head + loss "
                             "(xent_chunk)")
        embed = self.param("embedding", nn.with_logical_partitioning(
            nn.initializers.normal(0.02), ("vocab", "embed")),
            (cfg.vocab, cfg.dim), jnp.float32)
        from flax.linen.spmd import get_logical_axis_rules

        def _sharded_training() -> bool:
            # True only when the rules context can actually shard the
            # table: a live axis-rules context AND a >1-device mesh in
            # scope (the train harness enters jax.set_mesh(mesh) around
            # its jit). jax.device_count() is NOT the right signal — a
            # single-device mesh on a multi-device host (or the CPU test
            # env's 8 virtual devices with an unsharded harness) must
            # keep the gather.
            if not get_logical_axis_rules():
                return False
            ambient = jax.sharding.get_abstract_mesh()
            return not ambient.empty and ambient.size > 1

        with jax.named_scope("embed"):
            if _sharded_training():
                # Sharded multi-device training only — on one device the
                # one-hot is uncounted work every step (the train harness
                # applies the rules context even unsharded): look up via
                # one-hot matmul, not gather. The table is
                # (vocab→model, embed→fsdp)-sharded while activations want
                # batch over (data, fsdp) — GSPMD reshard s dots cleanly
                # (psum over the contracted vocab axis + reduce-scatter)
                # but a gather's embed-fsdp→batch-fsdp transition is an
                # "involuntary full rematerialization": replicate-then-
                # slice EVERY step, fwd and transpose. The one-hot term is
                # 2·vocab·dim FLOPs/token ≈ 0.6% of a 7B step, and it
                # rides the MXU.
                x = jax.nn.one_hot(tokens, cfg.vocab, dtype=cfg.dtype) \
                    @ embed.astype(cfg.dtype)
            else:
                x = jnp.take(embed, tokens, axis=0).astype(cfg.dtype)
        x = nn.with_logical_constraint(x, ("batch", "act_seq", "act_embed"))
        if positions is None:
            positions = jnp.arange(t)

        if cfg.router_hidden:
            # The router's state of the layer before; none before the
            # first: zeros, whatever the first layer's decay.
            x = (x, jnp.zeros((*tokens.shape, cfg.router_hidden),
                              jnp.float32))
        # A model with an indexer keeps its loss's residuals on every rung.
        block_cls = remat.block(
            ScannedBlock, always=("index_grad",) if cfg.index_heads else ()
        ) if cfg.remat else ScannedBlock
        new_kv = None
        if cfg.scan_layers:
            if kv is not None:
                # The per-layer KV buffers ride the scan as a sliced
                # input (in_axes 0 on the layer axis); the fresh rows
                # come back as the stacked ys — no explicit jnp.stack,
                # so no pack site.
                x, new_kv = nn.scan(
                    block_cls,
                    variable_axes={"params": 0, "losses": 0, "stats": 0},
                    split_rngs={"params": True},
                    in_axes=(nn.broadcast, 0),
                    length=cfg.n_layers,
                    metadata_params={nn.PARTITION_NAME: "stage"},
                )(cfg, name="layers")(x, positions, kv)
            else:
                x, _ = nn.scan(
                    block_cls,
                    variable_axes={"params": 0, "losses": 0, "stats": 0},
                    split_rngs={"params": True},
                    in_axes=nn.broadcast,
                    length=cfg.n_layers,
                    metadata_params={nn.PARTITION_NAME: "stage"},
                )(cfg, name="layers")(x, positions)
        else:
            if kv is not None:
                ks, vs = [], []
                for i in range(cfg.n_layers):
                    x, (kr, vr) = block_cls(cfg, name=f"layer_{i}")(
                        x, positions, jax.tree.map(lambda a: a[i], kv))
                    ks.append(kr)
                    vs.append(vr)
                # packsite: region-local — stacking per-layer rows of one
                # replica's serve forward along a NEW layer axis; all
                # operands share one (replicated) sharding.
                new_kv = (jnp.stack(ks), jnp.stack(vs))
            else:
                for i in range(cfg.n_layers):
                    x, _ = block_cls(cfg, name=f"layer_{i}")(x, positions)
        if cfg.router_hidden:
            x, _ = x
        x = RMSNorm(cfg.norm_eps, name="final_norm")(x)
        if cfg.xent_chunk:
            # Fused head+loss: the kernel is hoisted to this scope (param
            # path "lm_head_kernel" instead of "lm_head/kernel") and the
            # row-chunked CE never materializes full logits. Without
            # targets (init / inference) it degrades to a plain head.
            from tony_tpu.train import chunked_next_token_xent
            # Tied: the table the ids were looked up in, transposed; its
            # gradient is the lookup's plus the head's.
            w = embed.T if cfg.tie_embeddings else self.param(
                "lm_head_kernel", nn.with_logical_partitioning(
                    nn.initializers.lecun_normal(), ("embed", "vocab")),
                (cfg.dim, cfg.vocab), jnp.float32)
            if targets is not None:
                return chunked_next_token_xent(x, w, targets,
                                               cfg.xent_chunk, cfg.dtype)
            with jax.named_scope("lm_head"):
                logits = (x @ w.astype(cfg.dtype)).astype(jnp.float32)
            if kv is not None:
                return logits, new_kv
            return logits
        # lm_head matmul in bf16 (an f32 matmul runs at a fraction of MXU
        # bf16 peak and this is ~2·dim·vocab FLOPs/token) — or int8 when
        # the "lm_head" quant lane is on; logits cast to f32 afterwards
        # for a stable softmax in the loss.
        logits = _proj_dense(cfg, "lm_head", cfg.vocab,
                             ("embed", "vocab"), "lm_head")(x)
        if kv is not None:
            return logits.astype(jnp.float32), new_kv
        return logits.astype(jnp.float32)


@register("llama2-7b")
def llama2_7b(**kw) -> Transformer:
    return Transformer(TransformerConfig(**kw))


@register("llama-tiny")
def llama_tiny(**kw) -> Transformer:
    """Test-scale config: same code path as 7B at toy shapes."""
    defaults = dict(vocab=256, dim=64, n_layers=2, n_heads=4, n_kv_heads=2,
                    ffn_hidden=128, max_seq=64, attention="reference",
                    scan_layers=True, remat=False)
    defaults.update(kw)
    return Transformer(TransformerConfig(**defaults))


@register("mixtral-8x7b")
def mixtral_8x7b(**kw) -> Transformer:
    """Mixtral-style sparse MoE: 8 experts, top-2 routing, GQA."""
    defaults = dict(vocab=32000, dim=4096, n_layers=32, n_heads=32,
                    n_kv_heads=8, ffn_hidden=14336, max_seq=4096,
                    moe_experts=8, moe_top_k=2)
    defaults.update(kw)
    return Transformer(TransformerConfig(**defaults))


@register("llama-moe-tiny")
def llama_moe_tiny(**kw) -> Transformer:
    """Test-scale MoE config: the mixtral code path at toy shapes."""
    defaults = dict(vocab=256, dim=64, n_layers=2, n_heads=4, n_kv_heads=2,
                    ffn_hidden=128, max_seq=64, attention="reference",
                    scan_layers=True, remat=False, moe_experts=4,
                    moe_top_k=2)
    defaults.update(kw)
    return Transformer(TransformerConfig(**defaults))



@register("keye-vl-2.0-30b-a3b")
def keye_vl2_30b_a3b(**kw) -> Transformer:
    """Keye-VL-2.0-30B-A3B's language model (the text path; no input
    tower): GQA 32 x 128 over 4 KV heads with q/k-norm, a 16 x 64 indexer
    that selects 2048 keys a query, 128 experts of width 768, 8 a token,
    dropless. ``moe_experts_held`` / ``moe_expert_offset`` say which
    experts live here."""
    defaults = dict(vocab=151936, dim=2048, n_layers=48, n_heads=32,
                    n_kv_heads=4, attn_head_dim=128, ffn_hidden=768,
                    max_seq=16384, rope_theta=1e7, norm_eps=1e-6,
                    qk_norm=True, index_heads=16, index_dim=64,
                    index_topk=2048, moe_experts=128, moe_top_k=8,
                    moe_dropless=True, xent_chunk=1024)
    defaults.update(kw)
    return Transformer(TransformerConfig(**defaults))


@register("keye-tiny")
def keye_tiny(**kw) -> Transformer:
    """Test-scale twin of ``keye-vl-2.0-30b-a3b``: the same code path at
    toy shapes (the kernels' jax.numpy twins on the CPU)."""
    defaults = dict(vocab=256, dim=64, n_layers=2, n_heads=4, n_kv_heads=2,
                    attn_head_dim=32, ffn_hidden=32, max_seq=64,
                    rope_theta=1e7, norm_eps=1e-6, qk_norm=True,
                    index_heads=2, index_dim=16, index_topk=16,
                    moe_experts=8, moe_top_k=2,
                    moe_dropless=True, attention="reference",
                    remat=False, xent_chunk=32)
    defaults.update(kw)
    return Transformer(TransformerConfig(**defaults))


@register("zaya1-8b")
def zaya1_8b(**kw) -> Transformer:
    """ZAYA1-8B: attention in a 1024-wide latent (8 query heads over 2
    key/value heads of 128) with convolutional q/k mixing, a shifted value
    half and half of each head rotated; 16 experts of width 2048, one a
    token, chosen by an MLP router of width 256 whose state runs down the
    stack; one tied 262,272-row table. ``moe_experts_held`` /
    ``moe_expert_offset`` say which experts live here."""
    defaults = dict(vocab=262272, dim=2048, n_layers=40, n_heads=8,
                    n_kv_heads=2, attn_head_dim=128, ffn_hidden=2048,
                    max_seq=32768, rope_theta=5e6, norm_eps=1e-5,
                    attn_latent=True, cca_taps=(2, 2), rope_fraction=0.5,
                    moe_experts=16, moe_top_k=1, moe_dropless=True,
                    router_hidden=256, tie_embeddings=True, xent_chunk=1024)
    defaults.update(kw)
    defaults["cca_taps"] = tuple(defaults["cca_taps"])   # a JSON list
    return Transformer(TransformerConfig(**defaults))


@register("zaya-tiny")
def zaya_tiny(**kw) -> Transformer:
    """Test-scale twin of ``zaya1-8b``: the same code path at toy shapes."""
    defaults = dict(vocab=256, dim=64, n_layers=2, n_heads=4, n_kv_heads=2,
                    attn_head_dim=8, ffn_hidden=32, max_seq=64,
                    rope_theta=5e6, norm_eps=1e-5, attn_latent=True,
                    cca_taps=(2, 2), rope_fraction=0.5, moe_experts=8,
                    moe_top_k=1, moe_dropless=True, router_hidden=16,
                    tie_embeddings=True, attention="reference",
                    remat=False, xent_chunk=32)
    defaults.update(kw)
    return Transformer(TransformerConfig(**defaults))
