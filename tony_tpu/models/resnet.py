"""ResNet v1.5 for the ImageNet data-parallel north star (SURVEY.md §6:
ResNet-50 DP ≥55% MFU on a pod slice via ``tony submit``).

TPU-first choices: NHWC layout (XLA's native conv layout on TPU), bf16
compute with f32 params and f32 batch-norm statistics, and no
data-dependent control flow — the whole forward is one traced graph. Under
``jit`` over a dp/fsdp mesh the batch dim is sharded by
:func:`tony_tpu.parallel.batch_sharding`; BatchNorm's batch-mean then spans
the *global* batch because arrays are logically global (GSPMD inserts the
cross-device mean), matching synchronized-BN semantics without any NCCL-style
explicit allreduce.
"""

from __future__ import annotations

from functools import partial
from typing import Any, Optional, Sequence, Tuple

import flax.linen as nn
import jax
import jax.numpy as jnp

from tony_tpu.models import register

ModuleDef = Any


class FusedBNAct(nn.Module):
    """BatchNorm(+residual-add)(+ReLU) on the fused pallas kernels
    (:mod:`tony_tpu.ops.batchnorm` — VERDICT r3 #1: the BN reductions are
    51.3% of the ResNet step; this folds the whole epilogue into minimal
    HBM passes). Param/stat names match ``nn.BatchNorm`` (scale/bias,
    batch_stats mean/var). Falls back to plain XLA math when the shape
    has no clean tiling, and for eval (running stats: one elementwise
    pass XLA already fuses well)."""
    relu: bool = True
    use_running_average: bool = False
    momentum: float = 0.9
    epsilon: float = 1e-5
    scale_init: Any = nn.initializers.ones
    dtype: Any = jnp.bfloat16   # compute dtype for the non-kernel paths
    interpret: bool = False     # CPU tests run the kernels interpreted

    @nn.compact
    def __call__(self, x, residual: Optional[jax.Array] = None):
        from tony_tpu.ops.batchnorm import fused_bn_act

        c = x.shape[-1]
        gamma = self.param("scale", self.scale_init, (c,), jnp.float32)
        beta = self.param("bias", nn.initializers.zeros, (c,), jnp.float32)
        ra_mean = self.variable("batch_stats", "mean",
                                lambda *_: jnp.zeros((c,), jnp.float32))
        ra_var = self.variable("batch_stats", "var",
                               lambda *_: jnp.ones((c,), jnp.float32))
        fused = None
        if not self.use_running_average:
            fused = fused_bn_act(x, gamma, beta, residual,
                                 eps=self.epsilon, relu=self.relu,
                                 interpret=self.interpret)
        if fused is not None:
            out, mean, var = fused
        else:
            if self.use_running_average:
                mean, var = ra_mean.value, ra_var.value
            else:  # XLA fallback for un-tileable shapes
                xf = x.astype(jnp.float32)
                axes = tuple(range(x.ndim - 1))
                mean = jnp.mean(xf, axis=axes)
                var = jnp.maximum(
                    jnp.mean(xf * xf, axis=axes) - mean * mean, 0.0)
            # Elementwise math in the compute dtype (like nn.BatchNorm
            # with dtype=bf16): an f32 path would bounce every activation
            # bf16→f32→bf16 — doubled HBM traffic on a bandwidth-bound
            # model. Only the [C]-vector prep stays f32.
            ct = self.dtype
            inv = (jax.lax.rsqrt(var + self.epsilon) * gamma)
            out = (x.astype(ct) - mean.astype(ct)) * inv.astype(ct) \
                + beta.astype(ct)
            if residual is not None:
                out = out + residual.astype(ct)
            if self.relu:
                out = jnp.maximum(out, 0.0)
            out = out.astype(x.dtype)
        if not self.use_running_average and not self.is_initializing() \
                and self.is_mutable_collection("batch_stats"):
            mom = self.momentum
            ra_mean.value = (mom * ra_mean.value
                             + (1 - mom) * jax.lax.stop_gradient(mean))
            ra_var.value = (mom * ra_var.value
                            + (1 - mom) * jax.lax.stop_gradient(var))
        return out


class Bottleneck(nn.Module):
    """1x1 → 3x3 → 1x1 bottleneck with projection shortcut (v1.5: the
    stride sits on the 3x3, not the 1x1)."""
    filters: int
    strides: Tuple[int, int]
    conv: ModuleDef
    norm: ModuleDef

    @nn.compact
    def __call__(self, x):
        residual = x
        y = self.conv(self.filters, (1, 1))(x)
        y = nn.relu(self.norm()(y))
        y = self.conv(self.filters, (3, 3), self.strides)(y)
        y = nn.relu(self.norm()(y))
        y = self.conv(self.filters * 4, (1, 1))(y)
        y = self.norm(scale_init=nn.initializers.zeros)(y)
        if residual.shape != y.shape:
            residual = self.conv(self.filters * 4, (1, 1),
                                 self.strides, name="proj")(residual)
            residual = self.norm(name="proj_bn")(residual)
        return nn.relu(y + residual)


class FusedBottleneck(nn.Module):
    """Bottleneck over the fused BN kernels: BN+ReLU epilogues are single
    kernels, and the block exit (zeros-init BN + residual add + ReLU) is
    ONE fused pass instead of three XLA fusions."""
    filters: int
    strides: Tuple[int, int]
    conv: ModuleDef
    norm: ModuleDef    # partial(FusedBNAct, ...)

    @nn.compact
    def __call__(self, x):
        residual = x
        y = self.conv(self.filters, (1, 1))(x)
        y = self.norm()(y)
        y = self.conv(self.filters, (3, 3), self.strides)(y)
        y = self.norm()(y)
        y = self.conv(self.filters * 4, (1, 1))(y)
        if residual.shape[-1] != self.filters * 4 \
                or self.strides != (1, 1):
            residual = self.conv(self.filters * 4, (1, 1),
                                 self.strides, name="proj")(residual)
            residual = self.norm(relu=False, name="proj_bn")(residual)
        return self.norm(scale_init=nn.initializers.zeros)(
            y, residual=residual)


class ResNet(nn.Module):
    stage_sizes: Sequence[int]
    num_classes: int = 1000
    width: int = 64
    dtype: Any = jnp.bfloat16      # compute dtype; params stay f32
    fused_bn: bool = False         # pallas BN+add+ReLU epilogues
    bn_interpret: bool = False     # interpret pallas kernels (CPU tests)
    # MLPerf-standard space-to-depth stem: the 7x7/s2 conv on 224²x3
    # becomes the mathematically equivalent 4x4/s1 conv on the s2d-packed
    # 112²x12 input (kernel zero-padded 7→8 taps; see s2d_stem_kernel and
    # tests/test_models.py::test_s2d_stem_equivalence). Input channels 3
    # pay a physically padded layout on TPU; 12 is no better per element
    # but touches the big tensor with 4x fewer rows — measured ~0.5 ms/step
    # (exp/s2d_results.txt).
    s2d_stem: bool = False

    @nn.compact
    def __call__(self, x, train: bool = True):
        conv = partial(nn.Conv, use_bias=False, dtype=self.dtype,
                       param_dtype=jnp.float32)
        # bf16 compute dtype: activations stay 2-byte through the norm
        # (f32 norms would bounce every activation bf16->f32->bf16, doubling
        # HBM traffic on a bandwidth-bound model); running stats and
        # scale/bias params remain f32 via param_dtype.
        if self.fused_bn:
            norm = partial(FusedBNAct, use_running_average=not train,
                           momentum=0.9, epsilon=1e-5, dtype=self.dtype,
                           interpret=self.bn_interpret)
            block_cls = FusedBottleneck
        else:
            norm = partial(nn.BatchNorm, use_running_average=not train,
                           momentum=0.9, epsilon=1e-5, dtype=self.dtype,
                           param_dtype=jnp.float32)
            block_cls = Bottleneck
        x = x.astype(self.dtype)
        if self.s2d_stem:
            n, h, w, c = x.shape
            x = x.reshape(n, h // 2, 2, w // 2, 2, c)
            x = x.transpose(0, 1, 3, 2, 4, 5).reshape(n, h // 2, w // 2,
                                                      4 * c)
            # Output position i consumes original rows 2i-3..2i+3 = packed
            # block rows i-2..i+1 → 4 taps, pad (2,1). Exact 7x7/s2
            # equivalence: the zero tap (original offset -4) multiplies
            # rows the 7x7 never read.
            x = conv(self.width, (4, 4), (1, 1),
                     padding=[(2, 1), (2, 1)], name="stem")(x)
        else:
            x = conv(self.width, (7, 7), (2, 2), padding=[(3, 3), (3, 3)],
                     name="stem")(x)
        if self.fused_bn:
            x = norm(name="stem_bn")(x)
        else:
            x = nn.relu(norm(name="stem_bn")(x))
        x = nn.max_pool(x, (3, 3), strides=(2, 2), padding=((1, 1), (1, 1)))
        for stage, size in enumerate(self.stage_sizes):
            for block in range(size):
                strides = (2, 2) if stage > 0 and block == 0 else (1, 1)
                x = block_cls(self.width * 2 ** stage, strides,
                              conv=conv, norm=norm)(x)
        x = jnp.mean(x, axis=(1, 2))
        x = nn.Dense(self.num_classes, dtype=jnp.float32,
                     param_dtype=jnp.float32)(x)
        return x


@register("resnet50")
def resnet50(**kw) -> ResNet:
    return ResNet(stage_sizes=(3, 4, 6, 3), **kw)


@register("resnet18-thin")
def resnet18_thin(**kw) -> ResNet:
    """Small variant for tests: same code path, toy width/depth."""
    kw.setdefault("width", 8)
    kw.setdefault("num_classes", 10)
    return ResNet(stage_sizes=(1, 1), **kw)


def s2d_stem_kernel(k7: jax.Array) -> jax.Array:
    """Transport a [7,7,Cin,Cout] stem kernel to the equivalent [4,4,4*Cin,
    Cout] space-to-depth kernel: packed tap (p,q,dr,dc) reads original tap
    (2p-1+dr, 2q-1+dc); the out-of-range taps (p=0,dr=0 → row -1) are the
    zero padding that makes 7→8 taps exact. Proof of equivalence:
    tests/test_models.py::test_s2d_stem_equivalence."""
    cin, cout = k7.shape[2], k7.shape[3]
    k8 = jnp.zeros((8, 8, cin, cout), k7.dtype).at[1:, 1:].set(k7)
    # (a, b) = (2p-1+dr, 2q-1+dc) → k8 index (a+1, b+1) = (2p+dr, 2q+dc).
    k4 = k8.reshape(4, 2, 4, 2, cin, cout)          # (p, dr, q, dc, ...)
    k4 = k4.transpose(0, 2, 1, 3, 4, 5)             # (p, q, dr, dc, ...)
    return k4.reshape(4, 4, 4 * cin, cout)


def resnet50_flops(batch: int, image: int = 224) -> int:
    """Analytic forward FLOPs (≈4.1 GFLOP @224²); training ≈3× forward."""
    # Standard figure: 4.089e9 MACs*2 fwd for 224x224.
    per_image = 8.2e9 * (image / 224) ** 2
    return int(per_image * batch)
