"""Bucket-major fused optimizer plane: one update kernel per ZeRO-3 bucket.

After PR 5 the backward half of a ZeRO-3 step is bucket-major end to end:
``psum_scatter`` lands each microbatch's gradients as flat, shard-major,
per-dtype bucket buffers (:class:`tony_tpu.parallel.overlap.GradBuckets`).
The optimizer update then *threw that away* — it unpacked the buffers back
into the leaf pytree and ran optax's per-leaf op soup: hundreds of tiny
multiply/adds, dispatch-bound and re-fragmenting exactly the tensors the
planner spent a PR coalescing (Horovod's lesson, arXiv:1802.05799: bucket
wins are lost if any stage re-fragments; T3, arXiv:2401.16677, makes the
same fused-granularity argument for the compute side of a collective's
producer/consumer chain). This module keeps the step bucket-major through
the update:

* :func:`fused_bucket_update` — ONE kernel launch per bucket: a pallas TPU
  kernel (``interpret=True`` for CPU tests, like ``ops/attention.py``) or a
  bit-identical pure-XLA ``jnp`` fallback, applying AdamW / SGD-momentum /
  Adafactor-style updates elementwise over the concatenated 1-D buffers —
  grads, params, and moment slots all in the bucket layout. The per-element
  math is a handful of flops over 4R+3W f32 bytes: bytes-bound (see the
  ROOFLINE.md entry), so the win is launch-count and layout, not flops.
* :class:`FusedOptimizer` — the rule + hyperparameters + bucket plan
  policy. ``init_state`` builds **bucket-resident** optimizer state: per-
  bucket f32 moment buffers stored in the scatter layout (sharded
  ``P(fsdp)`` for scatter buckets), so the ZeRO-3 step performs
  reduce → update entirely in the shard domain. The AdamW and SGD-momentum
  rules replicate optax's op order exactly — pinned BIT-exact in f32
  against ``optax.adamw`` / ``optax.sgd`` (bf16 params carry a documented
  tolerance: optax keeps bf16 moments, this plane keeps f32 slots). The
  ``adafactor`` rule is Adafactor-STYLE — second-moment-only, elementwise,
  non-factored (the factored row/col statistics need leaf geometry a flat
  bucket erases) — and is pinned against its own leaf-major reference.
* :func:`region_apply` (method) — the in-region core the accum engine
  calls (:func:`tony_tpu.parallel.overlap.microbatch_grads` with
  ``fused=``): bucket-major global grad norm (one fused reduction per
  buffer, ``psum`` over fsdp for scatter chunks), optional global-norm
  clipping, then the per-bucket update. Padded uneven-shard buckets stay
  inert in their pad rows: the pads are zero in grads (sums of the
  planner's zero padding), params (zero-padded at pack), and slots (init
  zero), and every rule maps (0, 0, 0) → (0, 0), weight decay included.
* leaf-major ⇄ bucket-major converters + a ckpt codec
  (:func:`encode_state` / :func:`decode_state`, registered with
  :mod:`tony_tpu.ckpt`): checkpoints carry the moments in the portable
  leaf-major form — leaf paths and shapes identical to the params — so
  existing manifests keep restoring and a fused state written on one
  fsdp/slice topology elastic-restores onto another, re-planned into that
  topology's buckets.
"""

from __future__ import annotations

import functools
from dataclasses import dataclass
from typing import Any, Callable, Dict, List, Optional, Sequence, Tuple, Union

import jax
import jax.numpy as jnp
import numpy as np
from jax.experimental import pallas as pl
from jax.experimental.pallas import tpu as pltpu
from jax.sharding import Mesh, NamedSharding, PartitionSpec as P

from tony_tpu import profiler
from tony_tpu.parallel import FSDP
from tony_tpu.parallel.overlap import DEFAULT_BUCKET_BYTES, GradBuckets

# Trace-time side channel into the profiler's plan registry.
_record = functools.partial(profiler.record, "update")

RULES: Tuple[str, ...] = ("adamw", "sgd", "adafactor")

# Moment slots per rule, in kernel-operand order.
_SLOTS: Dict[str, Tuple[str, ...]] = {
    "adamw": ("mu", "nu"),
    "sgd": ("trace",),
    "adafactor": ("nu",),
}

# Scalar operand layout (one tiny f32 vector per step, shared by every
# bucket's launch): [-lr, adam bias-correction 1, bias-correction 2, pad].
_N_SCAL = 4


def _rule_math(rule: str, g, p, slots, neg_lr, bc1, bc2, *, b1: float,
               b2: float, eps: float, weight_decay: float, momentum: float):
    """The per-element update, shared VERBATIM by the pallas kernel body
    and the XLA fallback (one math definition — the two paths are
    bit-identical by construction). ``g``/``p``/``slots`` are f32; the op
    order replicates optax exactly (``(1-b)*g + b*m``, bias-correct by
    division, ``sqrt(v̂)+eps``, decayed weights added to the update, scale
    by ``-lr`` last) so the f32 pin against optax is bit-exact."""
    if rule == "adamw":
        mu, nu = slots
        mu = (1 - b1) * g + b1 * mu
        nu = (1 - b2) * (g * g) + b2 * nu
        u = (mu / bc1) / (jnp.sqrt(nu / bc2) + eps)
        if weight_decay:
            u = u + weight_decay * p
        return p + neg_lr * u, (mu, nu)
    if rule == "sgd":
        (tr,) = slots
        tr = g + momentum * tr            # optax trace: g + decay * t
        u = tr
        if weight_decay:
            u = u + weight_decay * p
        return p + neg_lr * u, (tr,)
    if rule == "adafactor":
        # Adafactor-STYLE: second-moment-only, elementwise, no factoring
        # and no bias correction — deliberately free of any buffer-wide
        # statistic (an RMS clip over the buffer would count pad rows and
        # break uneven-shard inertness).
        (nu,) = slots
        nu = (1 - b2) * (g * g) + b2 * nu
        u = g / (jnp.sqrt(nu) + eps)
        if weight_decay:
            u = u + weight_decay * p
        return p + neg_lr * u, (nu,)
    raise ValueError(f"unknown fused optimizer rule {rule!r} "
                     f"(one of {RULES})")


def _update_kernel(nslots: int, rule: str, hyper: Dict[str, float]):
    """Kernel factory: ``(scal, g, p, *slots) -> (new_p, *new_slots)`` over
    one ``(block_rows, 128)`` tile. Scalars ride SMEM; everything else is a
    VMEM block of the padded-2D view of the 1-D bucket buffer."""

    def kernel(scal_ref, g_ref, p_ref, *refs):
        slot_refs = refs[:nslots]
        new_p_ref = refs[nslots]
        new_slot_refs = refs[nslots + 1:]
        neg_lr = scal_ref[0]
        bc1 = scal_ref[1]
        bc2 = scal_ref[2]
        g = g_ref[:].astype(jnp.float32)
        p = p_ref[:]
        p_new, new_slots = _rule_math(
            rule, g, p.astype(jnp.float32),
            tuple(r[:] for r in slot_refs), neg_lr, bc1, bc2, **hyper)
        new_p_ref[:] = p_new.astype(new_p_ref.dtype)
        for r, v in zip(new_slot_refs, new_slots):
            r[:] = v

    return kernel


def _round_up(n: int, m: int) -> int:
    return n + ((-n) % m)


def _resolve_impl(impl: Optional[str], interpret: bool) -> str:
    """THE impl-dispatch policy (one definition: the kernel entry and the
    profiler record must never disagree): explicit wins; else pallas on
    TPU or under the interpreter, the XLA fallback elsewhere."""
    if impl is not None:
        return impl
    return "pallas" if (interpret
                        or jax.default_backend() == "tpu") else "xla"


# Per-operand VMEM block: 1024 rows x 128 lanes x 4 B = 512 KiB; with the
# ~7 live operands of an adamw launch that is ~3.5 MiB — comfortable
# against the 16 MiB/core budget while big enough to amortize grid steps.
_BLOCK_ROWS = 1024


def fused_bucket_update(g: jax.Array, p: jax.Array,
                        slots: Sequence[jax.Array], scal: jax.Array, *,
                        rule: str, hyper: Dict[str, float],
                        impl: Optional[str] = None,
                        interpret: bool = False
                        ) -> Tuple[jax.Array, Tuple[jax.Array, ...]]:
    """ONE optimizer-update launch over one bucket's 1-D buffers.

    ``g``/``p`` are the bucket's gradient and parameter buffers (the
    bucket's storage dtype); ``slots`` are its f32 moment buffers (count
    and order per ``_SLOTS[rule]``); ``scal`` is the ``_N_SCAL``-vector
    from :meth:`FusedOptimizer.scalars`. Returns ``(new_p, new_slots)``
    with dtypes preserved.

    Dispatch mirrors ``ops/attention.py``: the pallas kernel on TPU (or
    under ``interpret=True`` — how CPU tests cover the kernel), the pure-
    XLA fallback elsewhere (``impl="xla"``); both run the SAME
    ``_rule_math`` and are bit-identical. The 1-D buffer is viewed as a
    zero-padded ``(rows, 128)`` f32-tile-legal 2-D array for the kernel;
    the edge pad is sliced back off (interior uneven-shard pads are the
    planner's and stay in place — zeros in, zeros out).
    """
    if rule not in RULES:
        raise ValueError(f"unknown fused optimizer rule {rule!r} "
                         f"(one of {RULES})")
    nslots = len(_SLOTS[rule])
    if len(slots) != nslots:
        raise ValueError(f"rule {rule!r} expects {nslots} slot buffer(s) "
                         f"({_SLOTS[rule]}), got {len(slots)}")
    impl = _resolve_impl(impl, interpret)
    if impl == "xla":
        p_new, new_slots = _rule_math(
            rule, g.astype(jnp.float32), p.astype(jnp.float32),
            tuple(slots), scal[0], scal[1], scal[2], **hyper)
        return p_new.astype(p.dtype), new_slots
    if impl != "pallas":
        raise ValueError(f"unknown impl {impl!r} (pallas|xla)")

    n = g.shape[0]
    rows = max(1, -(-n // 128))
    block_rows = min(_BLOCK_ROWS, _round_up(rows, 8))
    rows_p = _round_up(rows, block_rows)
    pad = rows_p * 128 - n

    def to2(x):
        if pad:
            x = jnp.concatenate([x, jnp.zeros((pad,), x.dtype)])
        return x.reshape(rows_p, 128)

    blk = pl.BlockSpec((block_rows, 128), lambda i: (i, 0))
    out_shapes = [jax.ShapeDtypeStruct((rows_p, 128), p.dtype)] + [
        jax.ShapeDtypeStruct((rows_p, 128), jnp.float32)] * nslots
    outs = pl.pallas_call(
        _update_kernel(nslots, rule, hyper),
        grid=(rows_p // block_rows,),
        in_specs=[pl.BlockSpec(memory_space=pltpu.SMEM)]
        + [blk] * (2 + nslots),
        out_specs=tuple([blk] * (1 + nslots)),
        out_shape=tuple(out_shapes),
        interpret=interpret,
        cost_estimate=pl.CostEstimate(
            flops=12 * n,
            bytes_accessed=(g.size * g.dtype.itemsize
                            + 2 * p.size * p.dtype.itemsize
                            + 8 * nslots * n),
            transcendentals=n),
    )(scal, to2(g), to2(p), *[to2(s) for s in slots])
    p_new = outs[0].reshape(-1)[:n]
    new_slots = tuple(o.reshape(-1)[:n] for o in outs[1:])
    return p_new, new_slots


@dataclass(frozen=True)
class FusedOptimizer:
    """Rule + hyperparameters + bucket policy of the fused optimizer plane.

    Passed as the ``tx`` of a :class:`~flax.training.train_state.TrainState`
    (``train.create_train_state`` detects it and builds bucket-resident
    state); ``train.make_accum_train_step(update="fused_bucket")`` drives
    the in-region update. ``lr`` may be a python float or a callable
    ``count -> lr`` (schedule, resolved per step at trace time).

    AdamW and SGD-momentum replicate optax bit-exact in f32
    (``optax.adamw(lr, b1, b2, eps, weight_decay=...)`` with ``mask=None``;
    ``optax.sgd(lr, momentum)`` — for the exact sgd pin keep
    ``weight_decay=0``, optax's sgd has none). ``clip_norm`` applies
    global-norm clipping from the bucket-major norm before the update
    (optax's ``clip_by_global_norm`` formula; the norm itself differs from
    the per-leaf reduction only by fp reassociation).
    """

    rule: str = "adamw"
    lr: Union[float, Callable[[jax.Array], Any]] = 1e-3
    b1: float = 0.9
    b2: float = 0.999
    eps: float = 1e-8
    weight_decay: float = 0.0
    momentum: float = 0.9
    clip_norm: Optional[float] = None
    bucket_bytes: int = DEFAULT_BUCKET_BYTES
    impl: Optional[str] = None      # None = auto: pallas on TPU, xla else
    interpret: bool = False         # force the pallas interpreter (tests)

    def __post_init__(self):
        if self.rule not in RULES:
            raise ValueError(f"unknown fused optimizer rule {self.rule!r} "
                             f"(one of {RULES})")

    @property
    def slot_names(self) -> Tuple[str, ...]:
        return _SLOTS[self.rule]

    @property
    def hyper(self) -> Dict[str, float]:
        return {"b1": self.b1, "b2": self.b2, "eps": self.eps,
                "weight_decay": self.weight_decay,
                "momentum": self.momentum}

    def resolved_impl(self) -> str:
        return _resolve_impl(self.impl, self.interpret)

    def scalars(self, count: jax.Array) -> jax.Array:
        """The per-step scalar vector (one per step, shared by every
        bucket launch): ``[-lr, 1-b1^t, 1-b2^t, 0]``. The bias-correction
        expressions mirror optax's (python-float base ** int32 count) so
        the f32 pin stays bit-exact."""
        if self.rule == "adamw":
            bc1 = 1 - self.b1 ** count
            bc2 = 1 - self.b2 ** count
        else:
            bc1 = bc2 = jnp.float32(1.0)
        lr = self.lr(count) if callable(self.lr) else self.lr
        return jnp.stack([jnp.asarray(-lr, jnp.float32),
                          jnp.asarray(bc1, jnp.float32),
                          jnp.asarray(bc2, jnp.float32),
                          jnp.float32(0.0)])

    # -- planning / state ---------------------------------------------------

    def plan_for(self, params: Any, mesh: Optional[Mesh]) -> GradBuckets:
        """The deterministic bucket plan for THIS (params, topology): the
        same derivation everywhere (state init, train step, elastic
        restore), so bucket-resident buffers always line up."""
        from tony_tpu.parallel import overlap

        specs = overlap.fsdp_param_specs(params, mesh) \
            if mesh is not None else None
        if specs is None:
            return GradBuckets.plan(params, self.bucket_bytes)
        return GradBuckets.plan_sharded(
            params, specs, shard_size=mesh.shape[FSDP],
            bucket_bytes=self.bucket_bytes)

    def bucket_specs(self, plan: GradBuckets) -> List[P]:
        """Per-bucket shard_map/NamedSharding specs of the bucket-domain
        buffers: scatter buckets live in the scatter layout (``P(fsdp)``),
        the rest replicated."""
        return [P(FSDP) if plan._is_scatter(b) else P()
                for b in range(plan.n_buckets)]

    def init_state(self, params: Any, mesh: Optional[Mesh] = None,
                   plan: Optional[GradBuckets] = None) -> Dict[str, Any]:
        """Bucket-resident zero state: ``{"count": int32 0, "slots":
        {name: [per-bucket f32 buffer]}}`` with scatter buckets' buffers
        sharded ``P(fsdp)`` on ``mesh`` — the layout the in-region update
        consumes directly, no resharding on the step path."""
        plan = self.plan_for(params, mesh) if plan is None else plan
        specs = self.bucket_specs(plan)
        slots: Dict[str, List[jax.Array]] = {}
        for name in self.slot_names:
            bufs = []
            for b in range(plan.n_buckets):
                buf = jnp.zeros((plan.bucket_numel[b],), jnp.float32)
                if mesh is not None:
                    buf = jax.device_put(
                        buf, NamedSharding(mesh, specs[b]))
                bufs.append(buf)
            slots[name] = bufs
        count = jnp.zeros((), jnp.int32)
        if mesh is not None:
            count = jax.device_put(count, NamedSharding(mesh, P()))
        return {"count": count, "slots": slots}

    def check_slots(self, plan: GradBuckets, slots: Dict[str, Any]) -> None:
        names = tuple(slots)
        if set(names) != set(self.slot_names):
            raise ValueError(
                f"fused opt state carries slots {sorted(names)} but rule "
                f"{self.rule!r} needs {sorted(self.slot_names)}")
        for name in names:
            if len(slots[name]) != plan.n_buckets:
                raise ValueError(
                    f"fused opt state slot {name!r} has "
                    f"{len(slots[name])} bucket buffers but the plan has "
                    f"{plan.n_buckets} — the state was initialized for a "
                    f"different bucket_bytes or fsdp topology; rebuild it "
                    f"(create_train_state) or elastic-restore through the "
                    f"leaf-major portable form")

    # -- the in-region core -------------------------------------------------

    def local_pack(self, plan: GradBuckets, leaves: Sequence[Any], b: int,
                   f_idx, *, axis: str = FSDP, sharded: bool = True):
        """Region-LOCAL bucket packing: build bucket ``b``'s buffer from
        this device's view of the leaves — even scatter leaves are their
        local shard already, padded leaves are zero-padded and sliced to
        shard ``f_idx``, everything else concatenates whole. This is the
        only packing the fused plane ever does on sharded data: global
        ``pack()`` would route the concat through GSPMD (and the jax-0.4
        partitioner mis-reshards concatenated slice chunks on multi-axis
        meshes — measured, not hypothetical), while local packs are plain
        per-device data movement."""
        idxs = plan.buckets[b]
        if plan._is_scatter(b) and sharded and plan._is_padded(b):
            parts = []
            for i in idxs:
                d = plan.shard_dims[i]
                leaf = leaves[i]
                widths = [(0, plan._pad(i) if k == d else 0)
                          for k in range(len(plan.shapes[i]))]
                leaf = jnp.pad(leaf, widths)
                nrows = plan.padded_shape(i)[d] // plan.shard_size
                parts.append(jnp.ravel(jax.lax.dynamic_slice_in_dim(
                    leaf, f_idx * nrows, nrows, axis=d)))
        else:
            parts = [jnp.ravel(leaves[i]) for i in idxs]
        return parts[0] if len(parts) == 1 else jnp.concatenate(parts)

    def region_apply(self, plan: GradBuckets, param_leaves: Sequence[Any],
                     grad_bufs: Sequence[jax.Array], slots: Dict[str, Any],
                     scal: jax.Array, *, axis: str = FSDP,
                     sharded: Optional[bool] = None):
        """Bucket-major update core. Called INSIDE a manually-sharded
        region over ``axis`` when the plan has scatter buckets (the accum
        engine's region, or :func:`fused_update_step`'s wrapper); callable
        outside any region for shard-free plans.

        ``param_leaves`` are the region-local leaves (scatter leaves in
        shard shape, uneven/replicated leaves whole); ``grad_bufs`` the
        per-bucket gradient buffers in the same local layout the scan
        accumulators have (scatter chunk / full). Returns
        ``(new_param_leaves, new_slots, grad_norm)`` where the norm is the
        bucket-major global grad norm (one fused reduction per buffer,
        ``psum`` over ``axis`` for the disjoint scatter chunks) and the
        update saw ``clip_norm`` applied when configured.
        """
        self.check_slots(plan, slots)
        shard = plan.shard_size > 1
        if sharded is None:
            sharded = shard

        # Bucket-major global grad norm: one sum-of-squares per buffer.
        sq = jnp.float32(0.0)
        for b, gb in enumerate(grad_bufs):
            s = jnp.sum(jnp.square(gb.astype(jnp.float32)))
            if plan._is_scatter(b) and sharded:
                s = jax.lax.psum(s, axis)
            sq = sq + s
        gnorm = jnp.sqrt(sq)
        if self.clip_norm is not None:
            # optax.clip_by_global_norm's trim ratio, from the bucket norm.
            trim = self.clip_norm / jnp.maximum(gnorm, self.clip_norm)
            grad_bufs = [gb * trim.astype(gb.dtype) for gb in grad_bufs]

        new_leaves: List[Any] = list(param_leaves)
        new_slots: Dict[str, List[Any]] = {n: [None] * plan.n_buckets
                                           for n in self.slot_names}
        needs_f = sharded and any(
            plan._is_scatter(b) and plan._is_padded(b)
            for b in range(plan.n_buckets))
        f_idx = jax.lax.axis_index(axis) if needs_f else None
        for b, idxs in enumerate(plan.buckets):
            scatter = plan._is_scatter(b) and sharded
            padded = plan._is_padded(b)
            # Even scatter buckets: the local leaves ARE shard f, so the
            # local pack is pack()'s chunk f. Padded buckets: leaves
            # crossed the region replicated; local_pack zero-pads and
            # slices THIS device's shard so the buffer matches the grad
            # chunk's layout (pad rows zeros — inert through every rule).
            p_buf = self.local_pack(plan, param_leaves, b, f_idx,
                                    axis=axis, sharded=sharded)
            slot_bufs = tuple(slots[n][b] for n in self.slot_names)
            p_new, s_new = fused_bucket_update(
                grad_bufs[b], p_buf, slot_bufs, scal, rule=self.rule,
                hyper=self.hyper, impl=self.impl, interpret=self.interpret)
            for n, v in zip(self.slot_names, s_new):
                new_slots[n][b] = v
            if scatter and not padded:
                parts = plan.leaf_buffers(b, p_new, layout="shard")
            elif scatter:
                full = jax.lax.all_gather(p_new, axis, tiled=True)
                parts = plan.leaf_buffers(b, full, layout="gathered")
            else:
                parts = plan.leaf_buffers(b, p_new, layout="full")
            for i, v in parts.items():
                new_leaves[i] = v
        return new_leaves, new_slots, gnorm

    def region_collectives(self, plan: GradBuckets, *,
                           sharded: bool = True,
                           axis: str = FSDP
                           ) -> List[Tuple[str, Tuple[str, ...], int, str]]:
        """The collectives :meth:`region_apply` itself issues, as
        ``(kind, axes, nbytes, note)`` tuples — the fused plane's
        contribution to the static analyzer's planned set (the scalar
        grad-norm psums are below any audit threshold and deliberately
        omitted): one param ``all_gather`` per PADDED scatter bucket
        (uneven leaves exit the region whole, so their updated params
        re-gather once)."""
        out: List[Tuple[str, Tuple[str, ...], int, str]] = []
        if not sharded:
            return out
        for b in range(plan.n_buckets):
            if plan._is_scatter(b) and plan._is_padded(b):
                out.append(("all_gather", (axis,), plan.bucket_nbytes[b],
                            f"bucket {b} padded param re-gather"))
        return out

    def record(self, tag: str, plan: GradBuckets, **extra) -> None:
        """Bank the update schedule into ``profiler.report("update")``."""
        _record(tag, rule=self.rule, impl=self.resolved_impl(),
                n_buckets=plan.n_buckets,
                n_scatter_buckets=plan.n_scatter_buckets,
                bucket_nbytes=list(plan.bucket_nbytes),
                slot_names=list(self.slot_names),
                slot_bytes=4 * sum(plan.bucket_numel)
                * len(self.slot_names),
                clip_norm=self.clip_norm,
                weight_decay=self.weight_decay, **extra)


def fused_update_step(fused: FusedOptimizer, params: Any, grads: Any,
                      opt_state: Dict[str, Any],
                      mesh: Optional[Mesh] = None, *,
                      plan: Optional[GradBuckets] = None,
                      param_specs: Optional[Any] = None
                      ) -> Tuple[Any, Dict[str, Any], jax.Array]:
    """Standalone leaf-major entry: pack ``grads`` into the plan's bucket
    buffers and run the fused update — the surface the optax pins test
    (``make_accum_train_step(update="fused_bucket")`` fuses the same
    :meth:`~FusedOptimizer.region_apply` into its accum region so the
    grads never leave the bucket domain at all).

    Returns ``(new_params, new_opt_state, grad_norm)``. Under ``jit`` the
    plan (and, for sharded plans, ``param_specs``) must be passed in —
    they are derived from committed shardings, which tracers don't carry.
    Grads enter the region LEAF-major (same boundary layout as the
    params) and are packed per device inside it — bucket buffers are
    never materialized in the global GSPMD domain.
    """
    from tony_tpu import compat
    from tony_tpu.parallel import overlap

    if plan is None:
        plan = fused.plan_for(params, mesh)
    fused.check_slots(plan, opt_state["slots"])
    count_inc = opt_state["count"] + 1
    scal = fused.scalars(count_inc)
    fused.record("fused_update", plan)
    sharded = plan.shard_size > 1 and mesh is not None

    def apply_local(p_leaves, g_leaves, sl, sc, f_idx_needed: bool):
        g_bufs = [fused.local_pack(plan, g_leaves, b,
                                   jax.lax.axis_index(FSDP)
                                   if (f_idx_needed and plan._is_scatter(b)
                                       and plan._is_padded(b)) else None,
                                   sharded=sharded)
                  for b in range(plan.n_buckets)]
        return fused.region_apply(plan, p_leaves, g_bufs, sl, sc,
                                  sharded=sharded)

    if not sharded:
        new_leaves, new_slots, gnorm = apply_local(
            jax.tree.leaves(params), jax.tree.leaves(grads),
            opt_state["slots"], scal, False)
        new_params = jax.tree.unflatten(plan.treedef, new_leaves)
        return new_params, {"count": count_inc, "slots": new_slots}, gnorm

    if param_specs is None:
        param_specs = overlap.fsdp_param_specs(params, mesh)
    if param_specs is None:
        raise ValueError(
            "fused_update_step: the plan has scatter buckets but no fsdp "
            "layout was detected on the params — pass param_specs")
    p_specs, _ = overlap.region_param_specs(plan, param_specs)
    b_specs = fused.bucket_specs(plan)
    slot_specs = {n: list(b_specs) for n in fused.slot_names}

    def spmd(p, g, sl, sc):
        new_leaves, new_slots, gnorm = apply_local(
            jax.tree.leaves(p), jax.tree.leaves(g), sl, sc, True)
        return (jax.tree.unflatten(plan.treedef, new_leaves), new_slots,
                gnorm)

    new_params, new_slots, gnorm = compat.shard_map(
        spmd, mesh, in_specs=(p_specs, p_specs, slot_specs, P()),
        out_specs=(p_specs, slot_specs, P()))(
            params, grads, opt_state["slots"], scal)
    return new_params, {"count": count_inc, "slots": new_slots}, gnorm


# ---------------------------------------------------------------------------
# Leaf-major ⇄ bucket-major converters + the ckpt portability codec
# ---------------------------------------------------------------------------

def _host(x) -> np.ndarray:
    return np.asarray(jax.device_get(x))


def _np_unpack_bucket(plan: GradBuckets, b: int,
                      buf: np.ndarray) -> Dict[int, np.ndarray]:
    """Host-numpy twin of ``leaf_buffers`` (scatter buckets in the
    "gathered" layout, others "full"): whole unpadded leaves from one
    shard-major buffer, zero jax involvement."""
    idxs = plan.buckets[b]
    out: Dict[int, np.ndarray] = {}
    off = 0
    if plan._is_scatter(b):
        chunk = plan.bucket_numel[b] // plan.shard_size
        for i in idxs:
            shp = plan.shard_shape(i)
            n = int(np.prod(shp, dtype=np.int64))
            d = plan.shard_dims[i]
            full = np.concatenate(
                [buf[f * chunk + off:f * chunk + off + n].reshape(shp)
                 for f in range(plan.shard_size)], axis=d)
            if plan._pad(i):
                sl = [slice(None)] * full.ndim
                sl[d] = slice(0, plan.shapes[i][d])
                full = full[tuple(sl)]
            out[i] = full
            off += n
        return out
    for i in idxs:
        shp = plan.shapes[i]
        n = int(np.prod(shp, dtype=np.int64))
        out[i] = buf[off:off + n].reshape(shp)
        off += n
    return out


def _np_pack_bucket(plan: GradBuckets, b: int,
                    leaves: Sequence[np.ndarray]) -> np.ndarray:
    """Host-numpy twin of ``pack`` for one bucket: shard-major with
    zero-padded uneven leaves."""
    idxs = plan.buckets[b]
    if not plan._is_scatter(b):
        return np.concatenate(
            [np.asarray(leaves[i]).reshape(-1) for i in idxs])
    src = {}
    for i in idxs:
        a = np.asarray(leaves[i])
        if plan._pad(i):
            d = plan.shard_dims[i]
            widths = [(0, plan._pad(i) if k == d else 0)
                      for k in range(a.ndim)]
            a = np.pad(a, widths)
        src[i] = a
    parts = []
    for f in range(plan.shard_size):
        for i in idxs:
            d = plan.shard_dims[i]
            n = plan.padded_shape(i)[d] // plan.shard_size
            sl = [slice(None)] * src[i].ndim
            sl[d] = slice(f * n, (f + 1) * n)
            parts.append(src[i][tuple(sl)].reshape(-1))
    return np.concatenate(parts)


def slots_to_leaf_major(plan: GradBuckets,
                        slots: Dict[str, Sequence[jax.Array]]
                        ) -> Dict[str, Any]:
    """Bucket-resident slot buffers → per-slot pytrees shaped like the
    params (f32 moments as HOST numpy, leaf paths identical to the param
    tree) — the portable form the ckpt manifests carry. Conversion is
    pure host numpy over ``device_get`` copies: a ``P(fsdp)``-sharded
    scatter buffer is the full shard-major buffer globally, and slicing
    it apart host-side (a) keeps the jax-0.4 GSPMD partitioner out of
    the repack entirely (its resharding of concatenated slice chunks on
    multi-axis meshes is wrong — the same reason the step path only
    packs region-locally) and (b) never materializes the unsharded slots
    in device memory. Ckpt-boundary only; the step path never calls
    this. The encode still pays the slots' device→host pull on the
    saving thread — folding it into the async snapshot writer is a named
    follow-on."""
    out: Dict[str, Any] = {}
    for name, bufs in slots.items():
        leaves: List[Any] = [None] * len(plan.shapes)
        for b in range(plan.n_buckets):
            for i, v in _np_unpack_bucket(plan, b,
                                          _host(bufs[b])).items():
                leaves[i] = v
        out[name] = jax.tree.unflatten(plan.treedef, leaves)
    return out


def leaf_major_to_slots(plan: GradBuckets, trees: Dict[str, Any],
                        mesh: Optional[Mesh] = None
                        ) -> Dict[str, List[jax.Array]]:
    """Inverse of :func:`slots_to_leaf_major` onto THIS plan's buckets:
    host-numpy re-pack (re-zero-padding uneven leaves) shard-major, then
    each scatter buffer is placed DIRECTLY into the scatter layout on
    ``mesh`` — devices receive only their chunk, the full buffer exists
    on host alone. The plan may belong to a different topology than the
    one that wrote the leaf-major form — that is the elastic-restore
    path."""
    out: Dict[str, List[jax.Array]] = {}
    for name, tree in trees.items():
        host_leaves = [_host(l) for l in jax.tree.leaves(tree)]
        bufs: List[Any] = []
        for b in range(plan.n_buckets):
            buf = _np_pack_bucket(plan, b, host_leaves)
            if mesh is not None:
                buf = jax.device_put(buf, NamedSharding(
                    mesh, P(FSDP) if plan._is_scatter(b) else P()))
            else:
                buf = jnp.asarray(buf)
            bufs.append(buf)
        out[name] = bufs
    return out


def is_fused_state(state: Any) -> bool:
    """A TrainState driven by this plane: ``tx`` is a FusedOptimizer and
    the opt state is a count+slots (or count+leaf portable) dict."""
    return isinstance(getattr(state, "tx", None), FusedOptimizer) \
        and isinstance(getattr(state, "opt_state", None), dict) \
        and "count" in state.opt_state


def _mesh_of(params: Any) -> Optional[Mesh]:
    for leaf in jax.tree.leaves(params):
        mesh = getattr(getattr(leaf, "sharding", None), "mesh", None)
        if mesh is not None and getattr(mesh, "axis_names", None):
            return mesh
    return None


def encode_state(state: Any) -> Any:
    """Ckpt codec, encode half: bucket-resident → portable leaf-major
    (``{"count", "leaf": {slot: param-shaped tree}}``). The manifest then
    records topology-independent leaf paths/shapes/specs, so the existing
    elastic-restore machinery handles fused states unchanged."""
    if not is_fused_state(state) or "slots" not in state.opt_state:
        return state
    plan = state.tx.plan_for(state.params, _mesh_of(state.params))
    state.tx.check_slots(plan, state.opt_state["slots"])
    return state.replace(opt_state={
        "count": state.opt_state["count"],
        "leaf": slots_to_leaf_major(plan, state.opt_state["slots"])})


def decode_state(state: Any, mesh: Optional[Mesh] = None) -> Any:
    """Ckpt codec, decode half: portable leaf-major → bucket-resident,
    re-planned for THE CURRENT topology (``mesh``, defaulting to the
    params' committed mesh) — a state written at fsdp=4 restores onto
    fsdp=2 with its moments re-bucketed into the new scatter layout."""
    if not is_fused_state(state) or "leaf" not in state.opt_state:
        return state
    if mesh is None:
        mesh = _mesh_of(state.params)
    plan = state.tx.plan_for(state.params, mesh)
    count = state.opt_state["count"]
    if mesh is not None:
        # The restored scalar may sit on a single device; the step jit
        # needs every state leaf on one device set.
        count = jax.device_put(jnp.asarray(_host(count), jnp.int32),
                               NamedSharding(mesh, P()))
    return state.replace(opt_state={
        "count": count,
        "slots": leaf_major_to_slots(plan, state.opt_state["leaf"], mesh)})


def _register_codec() -> None:
    from tony_tpu import ckpt

    ckpt.register_portable_codec(
        "fused_optim",
        lambda tree: is_fused_state(tree),
        encode_state, decode_state)


_register_codec()
