"""Quantized compute lane: int8 matmuls with f32 accumulation/rescale.

Int8 on the MXU doubles peak throughput over bf16 (v5e: 197 → 394 TOPS)
and halves every weight byte a collective ships — the one step-time lever
the kernel-level MFU push still had open after PR 7. Following
TF-Replicator's lesson (arXiv:1902.00465) the framework owns the whole
precision lane — scales, dtype policy, checkpoint semantics, static
verification — instead of leaving each user to rebuild it badly:

* :func:`quant_dot` / :func:`quant_dot_general` — symmetric int8
  quantization (per-tensor activations, per-channel or per-tensor
  weights) feeding an int8×int8→int32 matmul with an f32 rescale. Two
  execution paths share ONE rescale expression (:func:`_rescale`) and an
  exact integer accumulation, so they are bit-identical by construction:
  a pallas TPU kernel (``interpret=True`` is how CPU tests cover it, like
  ``ops/attention.py`` / ``ops/fused_optim.py``) and a pure-XLA
  ``lax.dot_general(preferred_element_type=int32)`` fallback. Gradients
  are straight-through (custom_vjp): the backward matmuls run in f32 on
  the dequantized operands — standard QAT semantics.
* :class:`QuantDense` — the drop-in ``nn.Dense`` twin the model lanes
  use (``models/transformer.py`` ``quant=`` projections, the mnist MLP's
  ``quant=True``): dynamic (current-tensor) scales, kernel logical
  partitioning preserved, param tree paths identical to ``nn.Dense``.
* Quantize-on-gather — the ZeRO-3 forward param gathers
  (:class:`tony_tpu.parallel.sched.GatherPlan`) optionally ship int8
  bytes: each even scatter bucket's local shard chunk is quantized with
  a bucket scale shared by every shard, gathered as int8 (4× fewer bytes
  than f32), and dequantized on arrival. Because the scale is shared,
  quantize∘gather ≡ gather∘quantize BIT-exact — packing int8 adds no
  error beyond quantization itself. Scales come from **delayed scaling**:
  a per-bucket amax history (:class:`QuantConfig.window` entries) updated
  inside the accum region like PR 7's opt slots — the region measures the
  current bucket amax (local max + ``pmax`` over fsdp), rolls it into the
  history, and NEXT step's scale is ``max(history) / 127``. The history
  rides :class:`QuantTrainState` and commits/restores through the PR 3
  manifest via a ``register_portable_codec`` entry whose portable form is
  per-LEAF (topology-independent — an fsdp=4 history restores onto fsdp=2
  re-bucketed, conservative max per bucket).

The whole lane is loss-pin gated (``tests/test_quant.py``): quantized
mnist-mlp / tiny-transformer training curves must track bf16 within the
committed tolerance, and the pallas kernel must match the XLA fallback
bit-exactly.
"""

from __future__ import annotations

import functools
from dataclasses import dataclass
from typing import Any, Callable, Dict, List, Optional, Sequence, Tuple

from tony_tpu import profiler

with profiler.importing("flax.linen"):  # set-up span tony:import
    import flax.linen as nn
    from flax import struct
    from flax.training.train_state import TrainState
import jax
import jax.numpy as jnp
import numpy as np
from jax.experimental import pallas as pl
from jax.experimental.pallas import tpu as pltpu
from jax.sharding import Mesh, NamedSharding, PartitionSpec as P

# Trace-time side channel into the profiler's plan registry.
_record = functools.partial(profiler.record, "quant")

# Symmetric int8: values in [-127, 127] (the -128 code is unused so the
# range is symmetric and negation is exact).
QMAX = 127.0

# Scales divide; an all-zero tensor must quantize to zeros, not NaNs.
AMAX_FLOOR = 1e-12


# ---------------------------------------------------------------------------
# Quantization math (one definition; every lane — kernel, fallback,
# gather — goes through these, so the numerics story has one source)
# ---------------------------------------------------------------------------

def scale_of(amax: jax.Array) -> jax.Array:
    """Symmetric scale from an amax statistic (elementwise over per-
    channel vectors): ``max(amax, floor) / 127``."""
    return jnp.maximum(jnp.asarray(amax, jnp.float32), AMAX_FLOOR) / QMAX


def quantize(x: jax.Array, scale: jax.Array) -> jax.Array:
    """Symmetric int8 quantization: ``clip(round(x / scale), ±127)``.
    ``scale`` broadcasts (scalar = per-tensor, trailing vector = per-
    channel). Round-to-nearest-even (``jnp.round``), everywhere."""
    return jnp.clip(jnp.round(x.astype(jnp.float32) / scale),
                    -QMAX, QMAX).astype(jnp.int8)


def dequantize(q: jax.Array, scale: jax.Array,
               dtype: Any = jnp.float32) -> jax.Array:
    return (q.astype(jnp.float32) * scale).astype(dtype)


def _rescale(acc: jax.Array, sx: jax.Array, sw: jax.Array) -> jax.Array:
    """THE f32 rescale of an int32 accumulator, shared VERBATIM by the
    pallas kernel body and the XLA fallback — with the integer matmul
    exact by construction, this one expression is why the two paths are
    bit-identical. ``sx`` is the scalar lhs scale, ``sw`` the [N] rhs
    scale vector (per-tensor rhs broadcasts the scalar into it)."""
    return acc.astype(jnp.float32) * (sx * sw)


def _resolve_impl(impl: Optional[str], interpret: bool) -> str:
    """Impl-dispatch policy, same as ops/attention.py / ops/fused_optim:
    explicit wins; else pallas on TPU or under the interpreter, the XLA
    fallback elsewhere."""
    if impl is not None:
        return impl
    return "pallas" if (interpret
                        or jax.default_backend() == "tpu") else "xla"


def _round_up(n: int, m: int) -> int:
    return n + ((-n) % m)


# ---------------------------------------------------------------------------
# The int8 matmul core: int8×int8 → int32 accumulate → f32 rescale
# ---------------------------------------------------------------------------

def _dot_kernel(sx_ref, x_ref, w_ref, sw_ref, o_ref):
    """One (bm, bn) output tile: whole-K int8 dot on the MXU with an
    int32 accumulator (exact — integer addition is associative, so the
    grid layout cannot perturb numerics), rescaled through the shared
    :func:`_rescale`."""
    acc = jax.lax.dot_general(
        x_ref[:], w_ref[:], (((1,), (0,)), ((), ())),
        preferred_element_type=jnp.int32)
    o_ref[:] = _rescale(acc, sx_ref[0], sw_ref[0]).astype(o_ref.dtype)


# Output-tile targets: int8 operand tiles are (32, 128); 256×256 keeps
# the x/w/out VMEM blocks of one grid step under ~0.5 MiB combined.
_BM, _BN = 256, 256


def _int8_matmul(xq: jax.Array, wq: jax.Array, sx: jax.Array,
                 sw: jax.Array, *, impl: Optional[str],
                 interpret: bool, out_dtype: Any = jnp.float32) -> jax.Array:
    """``[M, K] int8 @ [K, N] int8 → [M, N]`` with f32 rescale — the
    dispatch point of the two bit-identical paths. ``sw`` is the [N]
    per-channel scale vector. ``out_dtype``: what the rescaled f32 tile
    is stored as (a cast of the same f32 value on both paths; a bf16
    caller then never holds the f32 ``[M, N]``)."""
    impl = _resolve_impl(impl, interpret)
    if impl == "xla":
        acc = jax.lax.dot_general(
            xq, wq, (((1,), (0,)), ((), ())),
            preferred_element_type=jnp.int32)
        return _rescale(acc, sx, sw).astype(out_dtype)
    if impl != "pallas":
        raise ValueError(f"unknown impl {impl!r} (pallas|xla)")
    m, k = xq.shape
    n = wq.shape[1]
    # int8 tiles are (32, 128): sublane dims pad to 32, lane dims to 128.
    # Zero pads are inert through an integer dot; padded output rows/cols
    # are sliced back off.
    bm = min(_BM, _round_up(m, 32))
    bn = min(_BN, _round_up(n, 128))
    mp, np_, kp = _round_up(m, bm), _round_up(n, bn), _round_up(k, 128)
    xq = jnp.pad(xq, ((0, mp - m), (0, kp - k)))
    wq = jnp.pad(wq, ((0, kp - k), (0, np_ - n)))
    sw2 = jnp.pad(sw, (0, np_ - n)).reshape(1, np_)
    out = pl.pallas_call(
        _dot_kernel,
        grid=(mp // bm, np_ // bn),
        in_specs=[
            pl.BlockSpec(memory_space=pltpu.SMEM),
            pl.BlockSpec((bm, kp), lambda i, j: (i, 0)),
            pl.BlockSpec((kp, bn), lambda i, j: (0, j)),
            pl.BlockSpec((1, bn), lambda i, j: (0, j)),
        ],
        out_specs=pl.BlockSpec((bm, bn), lambda i, j: (i, j)),
        out_shape=jax.ShapeDtypeStruct((mp, np_), out_dtype),
        interpret=interpret,
        cost_estimate=pl.CostEstimate(
            flops=2 * m * k * n,
            bytes_accessed=m * k + k * n
            + jnp.dtype(out_dtype).itemsize * m * n + 4 * n,
            transcendentals=0),
    )(sx.reshape(1), xq, wq, sw2)
    return out[:m, :n]


def _qdot_impl(x: jax.Array, w: jax.Array, per_channel: bool,
               impl: Optional[str], interpret: bool, out_dtype: Any):
    """Quantize + matmul, shared by the primal and fwd rules. Returns
    ``(y, (xq, sx, wq, sw))`` — the int8 residuals are what the STE
    backward dequantizes (4× smaller than f32 residuals)."""
    k = x.shape[-1]
    n = w.shape[1]
    x2 = x.reshape(-1, k)
    sx = scale_of(jnp.max(jnp.abs(x2.astype(jnp.float32))))
    if per_channel:
        aw = jnp.max(jnp.abs(w.astype(jnp.float32)), axis=0)     # [N]
    else:
        aw = jnp.max(jnp.abs(w.astype(jnp.float32)))
    sw = jnp.broadcast_to(scale_of(aw), (n,))
    xq = quantize(x2, sx)
    wq = quantize(w, sw)
    y = _int8_matmul(xq, wq, sx, sw, impl=impl, interpret=interpret,
                     out_dtype=out_dtype)
    return y.reshape(x.shape[:-1] + (n,)), (xq, sx, wq, sw)


@functools.partial(jax.custom_vjp, nondiff_argnums=(2, 3, 4, 5))
def _qdot(x, w, per_channel, impl, interpret, out_dtype):
    return _qdot_impl(x, w, per_channel, impl, interpret, out_dtype)[0]


def _qdot_fwd(x, w, per_channel, impl, interpret, out_dtype):
    y, res = _qdot_impl(x, w, per_channel, impl, interpret, out_dtype)
    # Dtype sentinels: residuals must be jax types, and the cotangents
    # must come back in the PRIMAL dtypes (x may be bf16 while y/g are
    # f32 — the rescale owns the output precision).
    return y, (res, jnp.zeros((), x.dtype), jnp.zeros((), w.dtype))


def _qdot_bwd(per_channel, impl, interpret, out_dtype, residuals, g):
    # Straight-through estimator: quantize∘dequantize ≈ identity for the
    # gradient, so the backward is the plain matmul transpose pair over
    # the DEQUANTIZED (fake-quant) operands, run in f32 — standard QAT.
    # (The int8 residuals are 4× smaller than stashing the f32 primals.)
    (xq, sx, wq, sw), xsent, wsent = residuals
    g2 = g.reshape(-1, g.shape[-1]).astype(jnp.float32)
    xshape = g.shape[:-1] + (wq.shape[0],)
    dx = (g2 @ dequantize(wq, sw).T).reshape(xshape).astype(xsent.dtype)
    dw = (dequantize(xq, sx).T @ g2).astype(wsent.dtype)
    return dx, dw


_qdot.defvjp(_qdot_fwd, _qdot_bwd)


def quant_dot(x: jax.Array, w: jax.Array, *, per_channel: bool = True,
              impl: Optional[str] = None, interpret: bool = False,
              tag: Optional[str] = None,
              out_dtype: Any = jnp.float32) -> jax.Array:
    """Quantized ``x @ w``: symmetric int8 (per-tensor ``x``, per-channel
    ``w`` by default), int8×int8→int32 matmul, f32 rescale, straight-
    through gradients. ``x`` is ``[..., K]``, ``w`` is ``[K, N]``; the
    result is f32 (the f32 rescale IS the accumulation story) unless the
    caller names its storage dtype: ``out_dtype`` is the same f32 value
    cast where it is made, so a ``[32768, 9216]`` product is never held
    in f32 beside its bf16 copy."""
    if w.ndim != 2:
        raise ValueError(f"quant_dot expects a rank-2 rhs [K, N], got "
                         f"shape {w.shape}")
    if x.shape[-1] != w.shape[0]:
        raise ValueError(f"contraction mismatch: x[..., {x.shape[-1]}] "
                         f"@ w[{w.shape[0]}, ...]")
    m = int(np.prod(x.shape[:-1], dtype=np.int64))
    _record(tag or "dot", kind="dot", m=m, k=x.shape[-1], n=w.shape[1],
            impl=_resolve_impl(impl, interpret), per_channel=per_channel,
            int8_bytes=m * x.shape[-1] + x.shape[-1] * w.shape[1],
            bf16_bytes=2 * (m * x.shape[-1] + x.shape[-1] * w.shape[1]))
    return _qdot(x, w, per_channel, impl, interpret, jnp.dtype(out_dtype))


def quant_dot_general(lhs: jax.Array, rhs: jax.Array,
                      dimension_numbers: Any, **kw) -> jax.Array:
    """``lax.dot_general``-shaped entry over the quantized core: one
    contracting dim per side, no batch dims (the projection shapes the
    model lanes use). Anything else raises — the lane is explicit about
    what it owns."""
    (lc, rc), (lb, rb) = dimension_numbers
    if lb or rb or len(lc) != 1 or len(rc) != 1:
        raise NotImplementedError(
            "quant_dot_general supports a single contracting dim per "
            f"side and no batch dims, got {dimension_numbers}")
    lhs_t = jnp.moveaxis(lhs, lc[0], -1)
    rhs_t = jnp.moveaxis(rhs, rc[0], 0)
    rest = rhs_t.shape[1:]
    y = quant_dot(lhs_t, rhs_t.reshape(rhs_t.shape[0], -1), **kw)
    return y.reshape(lhs_t.shape[:-1] + rest)


class QuantDense(nn.Module):
    """``nn.Dense`` twin on the quantized lane: identical param tree
    paths (``kernel``/``bias``), kernel logical partitioning via
    ``kernel_init``, compute through :func:`quant_dot` with dynamic
    (current-tensor) scales. Embeddings and norms stay off this lane by
    policy — only matmul projections quantize."""

    features: int
    dtype: Any = jnp.float32
    param_dtype: Any = jnp.float32
    use_bias: bool = False
    per_channel: bool = True
    impl: Optional[str] = None
    interpret: bool = False
    kernel_init: Callable = nn.initializers.lecun_normal()
    bias_init: Callable = nn.initializers.zeros_init()

    @nn.compact
    def __call__(self, x):
        kernel = self.param("kernel", self.kernel_init,
                            (x.shape[-1], self.features), self.param_dtype)
        # Without a bias the f32 product is only cast: cast where it is made.
        y = quant_dot(x, kernel, per_channel=self.per_channel,
                      impl=self.impl, interpret=self.interpret,
                      tag=f"dense.{self.name}",
                      out_dtype=jnp.float32 if self.use_bias else self.dtype)
        if self.use_bias:
            bias = self.param("bias", self.bias_init, (self.features,),
                              self.param_dtype)
            y = y + bias
        return y.astype(self.dtype)


# ---------------------------------------------------------------------------
# Quantize-on-gather: delayed scaling over the GatherPlan buckets
# ---------------------------------------------------------------------------

@dataclass(frozen=True)
class QuantConfig:
    """The quantized-gather lane's knobs. ``window`` is the delayed-
    scaling amax-history length (scales react within ``window`` steps of
    a weight-magnitude shift; longer = smoother). ``bucket_bytes`` names
    the bucket plan geometry the per-bucket amax state was built for —
    it must agree with the accum step's plan (validated, like the
    FusedOptimizer's), and the ckpt codec re-derives the plan from it."""

    window: int = 8
    bucket_bytes: int = 4 << 20        # overlap.DEFAULT_BUCKET_BYTES

    def __post_init__(self):
        if self.window < 1:
            raise ValueError(f"window must be >= 1, got {self.window}")


class QuantTrainState(TrainState):
    """TrainState + the quantized-gather lane's state: ``quant_state`` is
    ``{"amax": [per-gather-bucket f32 [window] history]}`` (replicated —
    scales must be identical on every shard for the int8 gather to be
    exact), ``qconfig`` the static :class:`QuantConfig`. Master params
    and the ZeRO-3 scatter buckets are untouched — quantization lives
    only on the forward-gather wire."""

    qconfig: Any = struct.field(pytree_node=False, default=None)
    quant_state: Any = None


def push_amax(hist: jax.Array, amax: jax.Array) -> jax.Array:
    """Roll one fresh amax into a [window] history (oldest falls out)."""
    return jnp.roll(hist, -1).at[-1].set(amax.astype(jnp.float32))


def hist_scale(hist: jax.Array) -> jax.Array:
    """Delayed scale from a history: ``max(hist) / 127``."""
    return scale_of(jnp.max(hist))


def bucket_amax(leaves: Sequence[jax.Array]) -> jax.Array:
    """Current amax of one bucket = max over its member leaves' |max|
    (identical to the packed buffer's amax — max commutes with concat,
    so no buffer is ever built for the statistic)."""
    return functools.reduce(
        jnp.maximum,
        [jnp.max(jnp.abs(l.astype(jnp.float32))) for l in leaves])


def is_quant_state(state: Any) -> bool:
    """A TrainState riding the quantized-gather lane."""
    return getattr(state, "quant_state", None) is not None \
        and getattr(state, "qconfig", None) is not None


def check_quant_amax(gplan: Any, amax: Sequence[jax.Array]) -> None:
    """The amax state must match THIS gather plan's bucket geometry —
    a mismatch means it was built for a different bucket_bytes or fsdp
    topology (rebuild via :func:`with_gather_quant` or elastic-restore
    through the portable leaf-major form). The accum engine calls this
    before every quantized trace."""
    if len(amax) != gplan.n_gather_buckets:
        raise ValueError(
            f"quant_amax carries {len(amax)} histories but the gather "
            f"plan has {gplan.n_gather_buckets} buckets — the state was "
            f"built for a different bucket_bytes or fsdp topology; "
            f"rebuild it (with_gather_quant) or restore through the "
            f"portable leaf-major form")
    for k, h in enumerate(amax):
        shape = tuple(getattr(h, "shape", ()))
        if len(shape) != 1 or shape[0] < 1 or (
                k and shape != tuple(amax[0].shape)):
            raise ValueError(
                f"amax history {k} has shape {shape} — every history "
                f"must be one non-empty [window] f32 vector (bucket 0's "
                f"is {tuple(amax[0].shape)})")


def _plans_of(params: Any, mesh: Optional[Mesh], bucket_bytes: int):
    """(plan, gplan) for the quantized-gather lane, the same derivation
    the accum step uses (overlap.step_plans) — state init, the stepper,
    and the ckpt codec must all see identical bucket geometry."""
    from tony_tpu.parallel import overlap

    if mesh is None:
        raise ValueError(
            "quantize-on-gather needs a ZeRO-3 (fsdp-sharded) layout on "
            "a mesh — no mesh found on the params")
    specs = overlap.fsdp_param_specs(params, mesh)
    if specs is None:
        raise ValueError(
            "quantize-on-gather needs fsdp-sharded params (the lane "
            "quantizes the forward param gathers; a replicated layout "
            "has none)")
    return overlap.step_plans(params, mesh, bucket_bytes=bucket_bytes,
                              param_specs=specs)


def with_gather_quant(state: Any, mesh: Mesh, *,
                      window: int = 8,
                      bucket_bytes: Optional[int] = None
                      ) -> QuantTrainState:
    """Attach the quantized-gather lane to a TrainState: derive the
    gather plan from the params' committed shardings and seed every
    bucket's [window] amax history from the CURRENT param magnitudes (so
    step 1's delayed scale is already calibrated). ``bucket_bytes``
    defaults from a FusedOptimizer tx when present (the tx's plan sized
    everything else bucket-shaped)."""
    if bucket_bytes is None:
        bucket_bytes = getattr(state.tx, "bucket_bytes", None)
        if bucket_bytes is None:
            from tony_tpu.parallel.overlap import DEFAULT_BUCKET_BYTES
            bucket_bytes = DEFAULT_BUCKET_BYTES
    qcfg = QuantConfig(window=window, bucket_bytes=bucket_bytes)
    plan, gplan = _plans_of(state.params, mesh, bucket_bytes)
    leaves = jax.tree.leaves(state.params)
    rep = NamedSharding(mesh, P())
    amax = []
    for b in gplan.gather_buckets:
        m = bucket_amax([leaves[i] for i in plan.buckets[b]])
        amax.append(jax.device_put(jnp.full((window,), m, jnp.float32),
                                   rep))
    _record("attach", n_buckets=gplan.n_gather_buckets, window=window,
            bucket_bytes=bucket_bytes,
            raw_nbytes=list(gplan.gather_nbytes),
            int8_nbytes=[plan.bucket_numel[b]
                         for b in gplan.gather_buckets])
    return QuantTrainState(
        step=state.step, apply_fn=state.apply_fn, params=state.params,
        tx=state.tx, opt_state=state.opt_state, qconfig=qcfg,
        quant_state={"amax": amax})


def gather_roundtrip_exact(params: Any, mesh: Mesh,
                           bucket_bytes: int) -> bool:
    """The quantize-on-gather bit-exactness pin, as a callable check the
    tests and the bench leg share: gathering int8 then dequantizing must
    equal quantize∘dequantize of the UNQUANTIZED gather, leaf for leaf,
    bit for bit (shared scales commute with the collective)."""
    from tony_tpu import compat
    from tony_tpu.parallel import overlap

    specs = overlap.fsdp_param_specs(params, mesh)
    plan, gplan = overlap.step_plans(params, mesh,
                                     bucket_bytes=bucket_bytes,
                                     param_specs=specs)
    p_specs, _ = overlap.region_param_specs(plan, specs)
    from tony_tpu.parallel import FSDP

    def spmd(p):
        lv = jax.tree.leaves(p)
        # The shared per-bucket scale, computed exactly like the accum
        # engine does: local bucket amax, pmax over fsdp — identical on
        # every shard, which is WHY quantize commutes with the gather.
        scales = [scale_of(jax.lax.pmax(
            bucket_amax([lv[i] for i in plan.buckets[b]]), FSDP))
            for b in gplan.gather_buckets]
        leaf_scale: Dict[int, jax.Array] = {}
        for k, b in enumerate(gplan.gather_buckets):
            for i in plan.buckets[b]:
                leaf_scale[i] = scales[k]
        q_full = gplan.gather(list(lv), scales=scales)
        full = gplan.gather(list(lv))
        ref = [dequantize(quantize(full[i], leaf_scale[i]),
                          leaf_scale[i], full[i].dtype)
               if i in leaf_scale else full[i]
               for i in range(len(full))]
        ok = jnp.bool_(True)
        for a, b in zip(q_full, ref):
            ok = jnp.logical_and(ok, jnp.all(a == b))
        return ok

    flat_specs = jax.tree.leaves(p_specs,
                                 is_leaf=lambda x: isinstance(x, P))
    out = jax.jit(compat.shard_map(
        lambda *lv: spmd(jax.tree.unflatten(plan.treedef, list(lv))),
        mesh, in_specs=tuple(flat_specs), out_specs=P()))(
            *jax.tree.leaves(params))
    return bool(jax.device_get(out))


# ---------------------------------------------------------------------------
# Ckpt portability codec: per-bucket amax ⇄ per-leaf amax
# ---------------------------------------------------------------------------

def _mesh_of(params: Any) -> Optional[Mesh]:
    for leaf in jax.tree.leaves(params):
        mesh = getattr(getattr(leaf, "sharding", None), "mesh", None)
        if mesh is not None and getattr(mesh, "axis_names", None):
            return mesh
    return None


def amax_to_leaf_major(plan: Any, gplan: Any,
                       amax: Sequence[jax.Array]) -> Any:
    """Per-bucket histories → a param-shaped pytree of [window] f32
    arrays (host numpy): every member leaf carries its bucket's history,
    non-gathered leaves carry zeros. Leaf paths are topology-independent
    — the portable form the manifest records."""
    window = int(amax[0].shape[0]) if amax else 1
    leaves: List[Any] = [np.zeros((window,), np.float32)
                         for _ in plan.shapes]
    for k, b in enumerate(gplan.gather_buckets):
        h = np.asarray(jax.device_get(amax[k]), np.float32)
        for i in plan.buckets[b]:
            leaves[i] = h
    return jax.tree.unflatten(plan.treedef, leaves)


def leaf_major_to_amax(plan: Any, gplan: Any, tree: Any,
                       mesh: Optional[Mesh]) -> List[jax.Array]:
    """Inverse of :func:`amax_to_leaf_major` onto THIS plan's buckets:
    bucket history = elementwise max over member leaves' histories (the
    conservative merge when the bucket partition changed across an
    elastic restore — a too-large scale quantizes coarser for ``window``
    steps, never clips). A bucket whose members ALL carry zero histories
    (gatherable only on this topology) merges to zeros — the decode path
    re-seeds those from the live params, because a floored scale would
    clip, not coarsen."""
    leaves = [np.asarray(jax.device_get(l), np.float32)
              for l in jax.tree.leaves(tree)]
    out: List[jax.Array] = []
    rep = NamedSharding(mesh, P()) if mesh is not None else None
    for b in gplan.gather_buckets:
        h = functools.reduce(np.maximum,
                             [leaves[i] for i in plan.buckets[b]])
        buf = jnp.asarray(h, jnp.float32)
        if rep is not None:
            buf = jax.device_put(buf, rep)
        out.append(buf)
    return out


def encode_state(state: Any) -> Any:
    """Ckpt codec, encode half: per-bucket amax → portable per-leaf form
    (and the fused optimizer's slots through ITS codec — the quant codec
    composes so a fused+quant state round-trips whole)."""
    from tony_tpu.ops import fused_optim

    if not is_quant_state(state):
        return fused_optim.encode_state(state)
    inner = fused_optim.encode_state(state)
    if "amax" not in state.quant_state:
        return inner
    plan, gplan = _plans_of(state.params, _mesh_of(state.params),
                            state.qconfig.bucket_bytes)
    return inner.replace(quant_state={
        "amax_leaf": amax_to_leaf_major(plan, gplan,
                                        state.quant_state["amax"])})


def decode_state(state: Any, mesh: Optional[Mesh] = None) -> Any:
    """Ckpt codec, decode half: portable per-leaf amax → per-bucket
    histories re-planned for THE CURRENT topology."""
    from tony_tpu.ops import fused_optim

    if not is_quant_state(state):
        return fused_optim.decode_state(state, mesh)
    inner = fused_optim.decode_state(state, mesh)
    if "amax_leaf" not in state.quant_state:
        return inner
    if mesh is None:
        mesh = _mesh_of(state.params)
    plan, gplan = _plans_of(state.params, mesh,
                            state.qconfig.bucket_bytes)
    # Restored scalars (step, an optax count, ...) may come back
    # committed to a single device when the restore template's own
    # scalar was single-device; the step jit then refuses the mixed
    # device sets. Re-place every opt_state/step SCALAR replicated —
    # the same fix the fused codec applies to its count, generalized so
    # a quant state restores jit-consistent under any tx.
    rep = NamedSharding(mesh, P())

    def _respread(leaf):
        if getattr(leaf, "ndim", None) == 0:
            return jax.device_put(jnp.asarray(jax.device_get(leaf)), rep)
        return leaf

    step = inner.step
    if getattr(step, "ndim", None) == 0:
        step = jax.device_put(jnp.asarray(jax.device_get(step)), rep)
    amax = leaf_major_to_amax(plan, gplan,
                              state.quant_state["amax_leaf"], mesh)
    # A bucket that became gatherable only on THIS topology (e.g. a leaf
    # that was uneven at the saving fsdp degree and is even now) merges
    # an all-zero portable history — and a zero history floors the scale
    # at AMAX_FLOOR/127, which would CLIP that bucket's params to ~0 on
    # the first step. Re-seed such buckets from the current param
    # magnitudes, exactly like with_gather_quant does at attach time.
    leaves = jax.tree.leaves(inner.params)
    window = state.qconfig.window
    for k, b in enumerate(gplan.gather_buckets):
        if float(jnp.max(amax[k])) == 0.0:
            m = bucket_amax([leaves[i] for i in plan.buckets[b]])
            amax[k] = jax.device_put(
                jnp.full((window,), m, jnp.float32), rep)
    return inner.replace(
        step=step,
        opt_state=jax.tree.map(_respread, inner.opt_state),
        quant_state={"amax": amax})


def _register_codec() -> None:
    from tony_tpu import ckpt

    # Prepend: a fused+quant state matches the fused codec's predicate
    # too, but only this codec handles BOTH planes (it delegates the
    # slots to fused_optim's) — first match wins in the registry.
    ckpt.register_portable_codec(
        "quant_gather", is_quant_state, encode_state, decode_state,
        prepend=True)


_register_codec()
