"""Comm/compute overlap engine: bucketed gradient sync under microbatched
accumulation, plus the XLA scheduler knobs that make the overlap real.

The seed's train step reduces gradients in one monolithic GSPMD ``psum``
issued after the full backward — zero overlap structure, the exact thing
Horovod's bucketed allreduce (arXiv:1802.05799) fixed for GPU rings and T3
(arXiv:2401.16677) shows is where modern MFU headroom lives. This module
builds that layer natively:

* :class:`GradBuckets` — a Horovod-style byte-threshold bucketing plan over
  the flattened grad pytree. Each bucket concatenates same-dtype leaves up
  to ``bucket_bytes`` and is reduced as ONE collective, so small tensors
  amortize launch latency and big ones don't serialize the whole sync.
  :meth:`GradBuckets.plan_sharded` is the ZeRO-3 planner: leaves with an
  fsdp-sharded dim are packed *shard-major*, so one ``psum_scatter`` over
  the fsdp axis lands each microbatch's grads straight in the shard layout
  — no gather, no replicated-grad materialization.
* :func:`microbatch_grads` — the accumulation step core: the local batch is
  split into K microbatches inside one ``lax.scan``; each microbatch's
  grads are packed and reduced per bucket *inside* the scan body, so under
  XLA's latency-hiding scheduler the reduction of microbatch *i*'s buckets
  overlaps the backward compute of microbatch *i+1*. On a multi-slice mesh
  the reduce is two-level: ``psum_scatter`` intra-slice over ICI per
  bucket, then a per-bucket allreduce over the DCN ``slice`` axis issued
  inside the scan — the slow cross-slice hop rides under both the next
  microbatch's backward and the next bucket's ICI phase.
  :func:`tony_tpu.train.make_accum_train_step` wraps this into a drop-in
  train step and auto-detects the ZeRO-3 layout from the state's
  shardings.
* the latency-hiding-scheduler / async-collective TPU compiler flags that
  make the overlap real live with their only caller,
  :func:`tony_tpu.runtime.jax_runtime.overlap_xla_flags` (the executor
  builds a task's env and must not import jax to do it).
"""

from __future__ import annotations

import functools
import logging
from dataclasses import dataclass
from typing import Any, Callable, Dict, List, Optional, Sequence, Tuple

import jax
import jax.numpy as jnp
import numpy as np
from jax.sharding import Mesh, PartitionSpec as P

from tony_tpu import compat, profiler
from tony_tpu.parallel import DATA, FSDP, SLICE

_log = logging.getLogger(__name__)

# Horovod's fusion buffer defaults to 64 MiB for NCCL rings; ICI collectives
# saturate earlier, and smaller buckets mean the first reduction launches
# sooner after the first grads materialize. 4 MiB is the planner default;
# callers tune per model via ``bucket_bytes``.
DEFAULT_BUCKET_BYTES = 4 << 20

def sync_axes(mesh: Mesh) -> Tuple[str, ...]:
    """The gradient-sync mesh axes: the DCN slice axis plus both DP axes,
    in mesh order — matches :func:`tony_tpu.parallel.batch_sharding`'s
    batch placement."""
    return tuple(a for a in (SLICE, DATA, FSDP) if a in mesh.axis_names)


def sync_size(mesh: Mesh) -> int:
    """Device count of the gradient-sync group (product of the slice and DP
    axes) — the denominator shared by the accum step and the pipeline
    schedules."""
    size = 1
    for a in sync_axes(mesh):
        size *= mesh.shape[a]
    return size


def ici_axes(mesh: Mesh) -> Tuple[str, ...]:
    """The intra-slice (ICI) gradient-sync axes: :func:`sync_axes` minus
    the DCN slice axis."""
    return tuple(a for a in (DATA, FSDP) if a in mesh.axis_names)


def dcn_axis(mesh: Mesh) -> Optional[str]:
    """The cross-slice (DCN) sync axis, or None on a single-slice mesh —
    hierarchical reduction only exists when this is set."""
    if SLICE in mesh.axis_names and mesh.shape[SLICE] > 1:
        return SLICE
    return None


def fsdp_param_specs(params: Any, mesh: Mesh) -> Optional[Any]:
    """Detect a ZeRO-3 (fsdp-sharded) parameter layout from the arrays'
    committed shardings: a pytree of :class:`PartitionSpec` (one per leaf,
    ``P()`` for replicated leaves) when at least one leaf is sharded over
    the fsdp axis of a mesh with ``fsdp > 1``, else ``None``.

    This is how ``train.make_accum_train_step`` decides between the
    replicated-param and sharded-param accumulation paths without a flag:
    the layout the state was created with IS the contract.
    """
    if FSDP not in mesh.axis_names or mesh.shape[FSDP] <= 1:
        return None
    leaves, treedef = jax.tree.flatten(params)
    specs: List[P] = []
    found = False
    for leaf in leaves:
        spec = getattr(getattr(leaf, "sharding", None), "spec", None)
        if spec is None:
            spec = P()
        # Strip size-1 mesh axes (a spec naming "model" on a model=1 mesh
        # is replicated in fact): the engine plans off REAL sharding.
        entries = []
        for entry in tuple(spec):
            names = entry if isinstance(entry, tuple) else (
                (entry,) if entry is not None else ())
            kept = tuple(a for a in names
                         if a in mesh.axis_names and mesh.shape[a] > 1)
            if FSDP in kept:
                found = True
            entries.append(kept if len(kept) > 1
                           else (kept[0] if kept else None))
        specs.append(P(*entries))
    if not found:
        return None
    return jax.tree.unflatten(treedef, specs)


def _shard_dim(spec: Any, shape: Tuple[int, ...], shard_axis: str,
               shard_size: int) -> Optional[int]:
    """The leaf dim sharded over ``shard_axis`` per ``spec`` (None when
    replicated). Raises on layouts the accum engine cannot own: sharding
    over any other mesh axis, or fsdp combined with another axis on one
    dim. A sharded dim NOT divisible by the shard count is legal — the
    planner pads it into its scatter bucket (see ``shard_pads``)."""
    dim: Optional[int] = None
    for d, entry in enumerate(tuple(spec)):
        if entry is None:
            continue
        names = entry if isinstance(entry, tuple) else (entry,)
        if shard_axis in names:
            if len(names) > 1:
                raise ValueError(
                    f"param dim {d} sharded over {names}: the accum engine "
                    f"supports {shard_axis!r} alone on a dim")
            if dim is not None:
                raise ValueError(
                    f"param sharded over {shard_axis!r} on two dims "
                    f"({dim} and {d}) — not a ZeRO-3 layout")
            dim = d
        else:
            raise ValueError(
                f"param dim {d} sharded over {names}: only {shard_axis!r} "
                f"is supported inside the accum engine (model/pipe/seq "
                f"axes belong to GSPMD, not the manual region)")
    return dim


@dataclass(frozen=True)
class GradBuckets:
    """A size-targeted partition of a grad pytree's leaves into reduction
    buckets: every leaf lands in exactly one bucket; leaves of one dtype
    pack together (a bucket is one concatenated 1-D buffer) in flatten
    order until adding the next leaf would cross ``threshold`` bytes; a
    single leaf bigger than the threshold gets a bucket of its own.

    A plan from :meth:`plan_sharded` additionally carries the ZeRO-3 shard
    layout: ``shard_dims[i]`` is leaf *i*'s fsdp-sharded dim (None for
    replicated leaves), and scatter buckets (``bucket_scatter``) hold only
    sharded leaves, packed shard-major — chunk *f* of the buffer is the
    concatenation of every member leaf's shard *f* — so ``psum_scatter``
    over the fsdp axis yields exactly the local shard of the summed grads.

    Leaves whose sharded dim does NOT divide the fsdp axis (the uneven
    ZeRO-3 follow-on) are padded into dedicated scatter buckets
    (``shard_pads[i]`` rows of zeros on the shard dim, ``bucket_padded``
    marks the buckets): the in-scan ``psum_scatter`` is identical, and the
    consumer re-gathers + unpads them after the scan (their grads come
    back whole — the uneven leaf can't live in the shard layout).
    """

    treedef: Any
    shapes: Tuple[Tuple[int, ...], ...]
    dtypes: Tuple[Any, ...]
    buckets: Tuple[Tuple[int, ...], ...]   # leaf indices per bucket
    bucket_nbytes: Tuple[int, ...]         # payload bytes per bucket
    bucket_numel: Tuple[int, ...]          # payload elements per bucket
    threshold: int
    shard_size: int = 1                    # fsdp axis size (1 = replicated)
    shard_dims: Tuple[Optional[int], ...] = ()    # per-leaf sharded dim
    bucket_scatter: Tuple[bool, ...] = ()         # per-bucket scatter flag
    shard_pads: Tuple[int, ...] = ()       # per-leaf pad rows on shard dim
    bucket_padded: Tuple[bool, ...] = ()   # per-bucket uneven-leaf flag

    @classmethod
    def plan(cls, tree: Any,
             bucket_bytes: int = DEFAULT_BUCKET_BYTES) -> "GradBuckets":
        """Plan from any pytree of arrays / ShapeDtypeStructs / tracers
        (only ``.shape``/``.dtype`` are read — works under ``eval_shape``
        and inside a jit trace)."""
        return cls._plan(tree, bucket_bytes, shard_dims=None, shard_size=1)

    @classmethod
    def plan_sharded(cls, tree: Any, specs: Any, *, shard_size: int,
                     bucket_bytes: int = DEFAULT_BUCKET_BYTES
                     ) -> "GradBuckets":
        """ZeRO-3 plan: ``specs`` is a pytree of :class:`PartitionSpec`
        matching ``tree`` (``P()`` = replicated leaf); leaves with an
        fsdp-sharded dim land in scatter buckets (uneven dims padded into
        their own buckets), the rest in ordinary allreduce buckets.
        ``shard_size`` is the fsdp axis size."""
        leaves = jax.tree.leaves(tree)
        spec_leaves = jax.tree.leaves(
            specs, is_leaf=lambda x: isinstance(x, P))
        if len(spec_leaves) != len(leaves):
            raise ValueError(
                f"param/spec trees disagree: {len(leaves)} leaves vs "
                f"{len(spec_leaves)} specs")
        shard_dims = tuple(
            _shard_dim(s, tuple(l.shape), FSDP, shard_size)
            for l, s in zip(leaves, spec_leaves))
        return cls._plan(tree, bucket_bytes, shard_dims=shard_dims,
                         shard_size=shard_size)

    @classmethod
    def _plan(cls, tree, bucket_bytes, *, shard_dims, shard_size):
        if bucket_bytes <= 0:
            raise ValueError(f"bucket_bytes must be positive, got "
                             f"{bucket_bytes}")
        leaves, treedef = jax.tree.flatten(tree)
        if not leaves:
            raise ValueError(
                "GradBuckets.plan: empty gradient pytree — nothing to "
                "bucket (did the loss close over its params instead of "
                "taking them as an argument?)")
        shapes = tuple(tuple(l.shape) for l in leaves)
        dtypes = tuple(np.dtype(l.dtype) for l in leaves)
        if shard_dims is None:
            shard_dims = (None,) * len(leaves)
        pads = tuple(
            (-shapes[i][d]) % shard_size if (d := shard_dims[i]) is not None
            and shard_size > 1 else 0
            for i in range(len(leaves)))
        # Payload size: scatter leaves count their PADDED extent — the pad
        # rows ride the collective, so the planner must budget them.
        sizes = []
        for i, (s, d) in enumerate(zip(shapes, dtypes)):
            numel = int(np.prod(s, dtype=np.int64))
            if pads[i] and s[shard_dims[i]]:
                numel = numel // s[shard_dims[i]] * (s[shard_dims[i]]
                                                    + pads[i])
            sizes.append(numel * d.itemsize)
        # Group key: (dtype, scatterable, padded) — a bucket is one
        # collective; a psum_scatter bucket cannot host replicated leaves
        # (their grads must come back whole, not as a shard), and padded
        # (uneven) leaves get their own buckets because theirs are
        # re-gathered after the scan while even leaves stay sharded.
        groups: Dict[Tuple[Any, bool, bool], list] = {}
        for i, d in enumerate(dtypes):
            sc = shard_dims[i] is not None and shard_size > 1
            groups.setdefault((d, sc, sc and pads[i] > 0), []).append(i)
        buckets, nbytes, numel, scatter, padded = [], [], [], [], []

        def close(cur, cur_b, d, sc, pd):
            buckets.append(tuple(cur))
            nbytes.append(cur_b)
            numel.append(cur_b // d.itemsize)
            scatter.append(sc)
            padded.append(pd)

        for (d, sc, pd), idxs in groups.items():
            cur: list = []
            cur_b = 0
            for i in idxs:
                if cur and cur_b + sizes[i] > bucket_bytes:
                    close(cur, cur_b, d, sc, pd)
                    cur, cur_b = [], 0
                cur.append(i)
                cur_b += sizes[i]
            if cur:
                close(cur, cur_b, d, sc, pd)
        return cls(treedef, shapes, dtypes, tuple(buckets), tuple(nbytes),
                   tuple(numel), bucket_bytes, shard_size, shard_dims,
                   tuple(scatter), pads, tuple(padded))

    @property
    def n_buckets(self) -> int:
        return len(self.buckets)

    @property
    def n_scatter_buckets(self) -> int:
        return sum(1 for s in self.bucket_scatter if s)

    def _is_scatter(self, b: int) -> bool:
        return bool(self.bucket_scatter) and self.bucket_scatter[b]

    def _is_padded(self, b: int) -> bool:
        return bool(self.bucket_padded) and self.bucket_padded[b]

    def _pad(self, i: int) -> int:
        return self.shard_pads[i] if self.shard_pads else 0

    def padded_shape(self, i: int) -> Tuple[int, ...]:
        """Leaf *i*'s shape with the uneven-shard pad applied."""
        pad = self._pad(i)
        if not pad:
            return self.shapes[i]
        s = list(self.shapes[i])
        s[self.shard_dims[i]] += pad
        return tuple(s)

    def shard_shape(self, i: int) -> Tuple[int, ...]:
        """Leaf *i*'s local-shard shape under the plan's fsdp layout
        (padded extent for uneven leaves — their shard IS padded)."""
        d = self.shard_dims[i] if self.shard_dims else None
        if d is None or self.shard_size == 1:
            return self.shapes[i]
        s = list(self.padded_shape(i))
        s[d] //= self.shard_size
        return tuple(s)

    def pack(self, tree: Any) -> list:
        """Pytree → per-bucket 1-D concatenated buffers. Scatter buckets
        are packed shard-major (chunk f = every member leaf's shard f), so
        a ``psum_scatter`` over the fsdp axis returns the local shard;
        uneven leaves are zero-padded on the shard dim first."""
        leaves = jax.tree.leaves(tree)
        out = []
        for b, idxs in enumerate(self.buckets):
            if self._is_scatter(b):
                src = {}
                for i in idxs:
                    pad = self._pad(i)
                    if pad:
                        d = self.shard_dims[i]
                        widths = [(0, pad if k == d else 0)
                                  for k in range(len(self.shapes[i]))]
                        src[i] = jnp.pad(leaves[i], widths)
                    else:
                        src[i] = leaves[i]
                parts = []
                for f in range(self.shard_size):
                    for i in idxs:
                        d = self.shard_dims[i]
                        n = self.padded_shape(i)[d] // self.shard_size
                        parts.append(jax.lax.slice_in_dim(
                            src[i], f * n, (f + 1) * n,
                            axis=d).reshape(-1))
                out.append(jnp.concatenate(parts))
            elif len(idxs) > 1:
                out.append(jnp.concatenate(
                    [leaves[i].reshape(-1) for i in idxs]))
            else:
                out.append(leaves[idxs[0]].reshape(-1))
        return out

    def leaf_buffers(self, b: int, buf: jax.Array, *,
                     layout: str) -> Dict[int, jax.Array]:
        """Bucket *b*'s buffer → ``{leaf_index: array}``.

        ``layout="full"``: linear packing of whole leaves (allreduce / re-
        gathered rs buckets). ``layout="shard"``: a scatter bucket's local
        ``psum_scatter`` chunk → shard-shaped leaves. ``layout="gathered"``:
        a scatter bucket's buffer re-gathered over the fsdp axis (shard-
        major, padded) → whole UNPADDED leaves — the uneven-leaf exit path.
        """
        idxs = self.buckets[b]
        out: Dict[int, jax.Array] = {}
        if layout == "gathered":
            chunk = self.bucket_numel[b] // self.shard_size
            off = 0
            for i in idxs:
                shp = self.shard_shape(i)
                n = int(np.prod(shp, dtype=np.int64))
                d = self.shard_dims[i]
                full = jnp.concatenate(
                    [jax.lax.dynamic_slice_in_dim(
                        buf, f * chunk + off, n).reshape(shp)
                     for f in range(self.shard_size)], axis=d)
                if self._pad(i):
                    full = jax.lax.slice_in_dim(
                        full, 0, self.shapes[i][d], axis=d)
                out[i] = full
                off += n
            return out
        if layout not in ("full", "shard"):
            raise ValueError(f"unknown layout {layout!r}")
        off = 0
        for i in idxs:
            shp = self.shard_shape(i) if layout == "shard" \
                else self.shapes[i]
            n = int(np.prod(shp, dtype=np.int64))
            out[i] = jax.lax.dynamic_slice_in_dim(
                buf, off, n).reshape(shp)
            off += n
        return out

    def unpack(self, bufs: Sequence[jax.Array]) -> Any:
        """Per-bucket FULL buffers → pytree (inverse of :meth:`pack` for
        non-scatter plans / gathered buffers)."""
        leaves: list = [None] * len(self.shapes)
        for b in range(len(self.buckets)):
            for i, v in self.leaf_buffers(b, bufs[b], layout="full").items():
                leaves[i] = v
        return jax.tree.unflatten(self.treedef, leaves)

    def unpack_shards(self, bufs: Sequence[jax.Array]) -> Any:
        """Per-bucket buffers → pytree in the SHARD layout: scatter
        buckets' buffers are the local ``psum_scatter`` chunk and unpack to
        shard-shaped leaves; other buffers unpack whole."""
        leaves: list = [None] * len(self.shapes)
        for b in range(len(self.buckets)):
            layout = "shard" if self._is_scatter(b) else "full"
            for i, v in self.leaf_buffers(b, bufs[b],
                                          layout=layout).items():
                leaves[i] = v
        return jax.tree.unflatten(self.treedef, leaves)

    def reduce(self, tree: Any, axis_names: Tuple[str, ...], *,
               op: str = "all_reduce", group_size: int = 1) -> Any:
        """Explicit per-bucket cross-replica sum of ``tree`` (must be
        called inside a manually-sharded region over ``axis_names``).

        ``op="all_reduce"``: one ``psum`` per bucket.
        ``op="reduce_scatter"``: ``psum_scatter`` per (padded) bucket +
        one tail ``all_gather`` — the bandwidth-optimal RS+AG split of an
        allreduce; ``group_size`` must be the product of the axis sizes.
        """
        if self.n_scatter_buckets:
            raise ValueError(
                "reduce() is the replicated-plan primitive; ZeRO-3 "
                "scatter plans are driven by microbatch_grads (shard-"
                "major buffers unpack to the SHARD layout, not whole "
                "leaves)")
        bufs = self.pack(tree)
        if op == "all_reduce":
            return self.unpack([jax.lax.psum(b, axis_names) for b in bufs])
        if op != "reduce_scatter":
            raise ValueError(f"unknown reduce op {op!r} "
                             "(all_reduce|reduce_scatter)")
        out = []
        for b in bufs:
            n = b.shape[0]
            pad = (-n) % group_size
            if pad:
                b = jnp.concatenate([b, jnp.zeros((pad,), b.dtype)])
            shard = jax.lax.psum_scatter(b, axis_names, tiled=True)
            full = jax.lax.all_gather(shard, axis_names, tiled=True)
            out.append(full[:n] if pad else full)
        return self.unpack(out)


# Trace-time side channel into the profiler's plan registry.
_record = functools.partial(profiler.record, "overlap")


def reduce_schedule(plan: "GradBuckets", mesh: Mesh, *,
                    reduce_op: str = "all_reduce",
                    hierarchy: str = "auto"
                    ) -> Tuple[List[Tuple[str, list]], Tuple[str, ...],
                               int, bool]:
    """THE per-bucket reduce schedule — one derivation shared by the accum
    engine (which executes it) and the static analyzer (which audits the
    traced program against it; if they ever derived it separately the
    audit would drift from the code it checks).

    Each bucket gets ``(mode, post_groups)``: mode fixes the in-scan
    collective + accumulator shape; post_groups are the psum axis groups
    issued after the scatter — hierarchical keeps the DCN hop its OWN
    collective so the scheduler can slide it independently of the ICI
    phase.

    * ``"scatter"``: psum_scatter over fsdp into the ZeRO-3 shard layout
    * ``"rs"``:      psum_scatter over the (padded) reduce group + tail AG
    * ``"ar"``:      plain psum

    Returns ``(sched, rs_axes, rs_group, hier)`` where ``rs_axes``/
    ``rs_group`` are the psum_scatter group of the ``"rs"`` buckets and
    ``hier`` says whether the DCN level exists.
    """
    if reduce_op not in ("all_reduce", "reduce_scatter"):
        raise ValueError(f"unknown reduce op {reduce_op!r} "
                         "(all_reduce|reduce_scatter)")
    if hierarchy not in ("auto", "flat", "hierarchical"):
        raise ValueError(f"unknown hierarchy {hierarchy!r} "
                         "(auto|flat|hierarchical)")
    axes = sync_axes(mesh)
    ici = ici_axes(mesh)
    dcn = dcn_axis(mesh)
    if hierarchy == "hierarchical" and dcn is None:
        raise ValueError(
            "hierarchy='hierarchical' needs a multi-slice mesh (slice "
            "axis > 1); build one with MeshSpec(slices=...)")
    hier = dcn is not None and hierarchy != "flat"
    ici_group = 1
    for a in ici:
        ici_group *= mesh.shape[a]
    group = sync_size(mesh)
    sched: List[Tuple[str, list]] = []
    for b in range(plan.n_buckets):
        if plan._is_scatter(b):
            if hier:
                post = [_present(mesh, tuple(a for a in ici if a != FSDP)),
                        (dcn,)]
            else:
                post = [_present(mesh,
                                 tuple(a for a in axes if a != FSDP))]
            sched.append(("scatter", [g for g in post if g]))
        elif hier:
            sched.append(("rs", [(dcn,)]))
        elif reduce_op == "reduce_scatter":
            sched.append(("rs", []))
        else:
            sched.append(("ar", []))
    rs_axes = ici if hier else axes
    rs_group = ici_group if hier else group
    return sched, rs_axes, rs_group, hier


def step_plans(params: Any, mesh: Mesh, *,
               bucket_bytes: int = DEFAULT_BUCKET_BYTES,
               param_specs: Optional[Any] = None,
               prefetch: int = 1):
    """``(plan, gather_plan)`` exactly as :func:`microbatch_grads` derives
    them for a step over ``params`` — the one planning entry the engine,
    the stepper's ``inspect`` hook, and the static analyzer all share.
    ``gather_plan`` is ``None`` for replicated (non-ZeRO-3) layouts."""
    from tony_tpu.parallel import sched as sched_mod  # lazy: no cycle

    if param_specs is None:
        return GradBuckets.plan(params, bucket_bytes), None
    fsdp_size = mesh.shape[FSDP] if FSDP in mesh.axis_names else 1
    plan = GradBuckets.plan_sharded(params, param_specs,
                                    shard_size=fsdp_size,
                                    bucket_bytes=bucket_bytes)
    return plan, sched_mod.GatherPlan.from_buckets(plan, prefetch=prefetch)


def region_param_specs(plan: "GradBuckets", param_specs: Any
                       ) -> Tuple[Any, List[Tuple[int, ...]]]:
    """Full-rank shard_map entry specs for a ZeRO-3 plan (shard_map wants
    one entry per dim). UNEVEN leaves — shard dim not divisible by fsdp,
    ``plan.shard_pads > 0`` — cross the region boundary REPLICATED:
    shard_map can't split an indivisible dim, so jax reshards them at
    entry and their grads exit whole (the scatter bucket still pads and
    reduces them bandwidth-optimally inside). Returns ``(p_specs,
    uneven_shapes)`` — shared by the accum engine and the fused-optimizer
    standalone step so both regions see the identical boundary layout."""
    spec_leaves = []
    uneven: List[Tuple[int, ...]] = []
    for i, s in enumerate(jax.tree.leaves(
            param_specs, is_leaf=lambda x: isinstance(x, P))):
        entries = list(tuple(s)) + [None] * (len(plan.shapes[i])
                                             - len(tuple(s)))
        if plan._pad(i):
            entries[plan.shard_dims[i]] = None
            uneven.append(plan.shapes[i])
        spec_leaves.append(P(*entries))
    return jax.tree.unflatten(plan.treedef, spec_leaves), uneven


def _present(mesh: Mesh, axes: Sequence[str]) -> Tuple[str, ...]:
    """Drop size-1 axes: a psum over them is a no-op the latency-hiding
    scheduler still has to place."""
    return tuple(a for a in axes if mesh.shape[a] > 1)


def microbatch_grads(loss_fn: Callable[[Any, Any], Any], params: Any,
                     batch: Any, mesh: Mesh, *, microbatches: int,
                     buckets: Optional[GradBuckets] = None,
                     bucket_bytes: int = DEFAULT_BUCKET_BYTES,
                     reduce_op: str = "all_reduce",
                     has_aux: bool = False,
                     param_specs: Optional[Any] = None,
                     hierarchy: str = "auto",
                     gather: str = "bucketed",
                     prefetch: int = 1,
                     fused: Optional[Any] = None,
                     opt_slots: Optional[Any] = None,
                     opt_scal: Optional[jax.Array] = None,
                     quant_amax: Optional[Sequence[jax.Array]] = None):
    """Gradient accumulation over ``microbatches`` with per-bucket sync.

    ``loss_fn(params, microbatch) -> loss`` (or ``(loss, aux)`` with
    ``has_aux``) is the per-shard loss — a *mean* over its microbatch
    slice, collective-free (the engine owns all cross-device traffic, like
    ``gpipe``'s ``stage_fn`` contract). The batch's leading dim is split
    over the sync axes (slice × data × fsdp). Returns ``(loss, grads)``
    (or ``(loss, aux, grads)``): the global-mean loss and grads —
    numerically the monolithic full-batch step up to fp reassociation.

    **Replicated mode** (``param_specs=None``): params are replicated
    across the sync axes inside the region; grads come back replicated.

    **ZeRO-3 mode** (``param_specs`` = pytree of ``PartitionSpec``): params
    enter the region in their fsdp-shard layout; each microbatch gathers
    them for compute, but the grads are ``psum_scatter``-ed straight into
    the shard layout per shard-major bucket and never materialize
    replicated — the returned grads carry exactly ``param_specs``, ready
    for ``apply_gradients`` on a sharded optimizer state. EXCEPTION —
    uneven leaves (sharded dim not divisible by the fsdp size, which used
    to raise): their reduction still rides a zero-padded scatter bucket,
    but the leaf itself crosses the region boundary REPLICATED (shard_map
    cannot split an indivisible dim) and its grad comes back whole, so
    the per-leaf memory saving does not apply to it. Logged (WARNING,
    once per plan) so a large uneven leaf — e.g. a vocab embedding whose
    dim doesn't divide fsdp — can't silently eat the ZeRO-3 budget.

    **Forward gathers** (ZeRO-3 only; ``gather`` = ``"bucketed"`` |
    ``"per_leaf"``): each microbatch re-gathers the sharded params for
    compute. The default coalesces the per-leaf ``all_gather``s into the
    SAME shard-major buckets the scatter plan uses (one collective per
    bucket — bit-exact vs per-leaf, it is pure data movement) and chains
    bucket *k*'s gather on bucket *k−prefetch*'s completion
    (:class:`tony_tpu.parallel.sched.GatherPlan`), so the next bucket's
    gather rides under this bucket's layer compute while replicated
    params never materialize outside the live bucket window.
    ``"per_leaf"`` is the pre-scheduler path, kept as the numerics pin.

    **Hierarchy** (``"auto"`` | ``"flat"`` | ``"hierarchical"``): on a
    multi-slice mesh (``slice`` axis > 1) the auto/hierarchical reduce is
    two-level — ``psum_scatter`` over the intra-slice ICI axes per bucket,
    then a small per-bucket allreduce over the DCN ``slice`` axis, both
    issued inside the scan so the DCN hop hides under the next
    microbatch's backward and the next bucket's ICI phase; the shards are
    re-gathered over ICI once, after the scan. ``"flat"`` forces the
    single-level reduce over the whole sync group (the numerics pin for
    the hierarchical path).

    Inside the scan body each microbatch's grads are reduced bucket by
    bucket, so the collective for microbatch *i* is in flight while
    microbatch *i+1*'s forward/backward computes (the Horovod overlap,
    expressed for XLA's latency-hiding scheduler — see
    :func:`tony_tpu.runtime.jax_runtime.overlap_xla_flags`).

    **Fused optimizer update** (``fused`` =
    :class:`tony_tpu.ops.fused_optim.FusedOptimizer`, with ``opt_slots``
    its bucket-resident slot buffers and ``opt_scal`` the per-step scalar
    vector): instead of unpacking the reduced bucket buffers into leaf
    grads, the optimizer update runs IN the region, bucket by bucket, on
    the very accumulators the scan produced — reduce → update never
    leaves the bucket domain, and scatter buckets stay in the shard
    layout throughout. The return changes to ``(loss[, aux], new_params,
    new_slots, grad_norm)`` where the norm is the bucket-major global
    grad norm (post-reduce, pre-clip).

    **Quantized gathers** (``quant_amax`` = per-gather-bucket f32
    ``[window]`` amax histories, replicated — see
    :mod:`tony_tpu.ops.quant`): the bucketed forward gathers ship int8.
    Scales are DELAYED — derived from the history the state carries, so
    every shard quantizes with the identical scale and the int8 wire
    format is bit-exact against quantize-after-gather. The region
    measures the current bucket amax once at entry (local max + ``pmax``
    over fsdp — the params don't change inside the scan) and rolls it
    into the history; the updated histories append to the return
    (``..., new_amax``). ZeRO-3 + ``gather="bucketed"`` only.
    """
    from tony_tpu.parallel import sched as sched_mod  # lazy: no cycle

    axes = sync_axes(mesh)
    group = sync_size(mesh)
    dcn = dcn_axis(mesh)
    if gather not in ("bucketed", "per_leaf"):
        raise ValueError(f"unknown gather mode {gather!r} "
                         "(bucketed|per_leaf)")
    lead = jax.tree.leaves(batch)[0].shape[0]
    if lead % (group * microbatches):
        raise ValueError(
            f"global batch {lead} not divisible by sync group {group} x "
            f"microbatches {microbatches} (= {group * microbatches})")

    zero3 = param_specs is not None
    gplan = None
    if zero3:
        # The forward-gather schedule is resolved HERE, once per plan —
        # which leaves gather, on which dim, in which bucket. The scan
        # body below just drives the static lists (the spec probing that
        # used to run per gather_params call is gone from the traced
        # path).
        if buckets is not None:
            plan = buckets
            gplan = sched_mod.GatherPlan.from_buckets(plan,
                                                      prefetch=prefetch)
        else:
            plan, gplan = step_plans(params, mesh,
                                     bucket_bytes=bucket_bytes,
                                     param_specs=param_specs,
                                     prefetch=prefetch)
        p_specs, uneven = region_param_specs(plan, param_specs)
        if uneven:
            # Loud on purpose: these leaves lose the ZeRO-3 per-leaf
            # memory saving (replicated at the boundary, whole grads) —
            # a big uneven leaf deserves a reshape, not a silent OOM.
            _log.warning(
                "ZeRO-3 plan: %d leaf(s) with fsdp-indivisible sharded "
                "dims (shapes %s) are replicated at the accum-region "
                "boundary; their grads reduce via padded scatter buckets "
                "but return whole", len(uneven), uneven[:4])
    else:
        plan = buckets if buckets is not None else GradBuckets.plan(
            params, bucket_bytes)
        p_specs = jax.tree.map(lambda _: P(), params)
    quant = quant_amax is not None
    if quant:
        if not zero3 or gather != "bucketed":
            raise ValueError(
                "quantize-on-gather (quant_amax=) needs the ZeRO-3 "
                "bucketed gather path (fsdp-sharded params, "
                "gather='bucketed') — the int8 lane lives on the "
                "GatherPlan bucket boundary")
        from tony_tpu.ops import quant as _quant_mod

        _quant_mod.check_quant_amax(gplan, quant_amax)
    b_specs = jax.tree.map(lambda _: P(axes), batch)
    grad_fn = jax.value_and_grad(loss_fn, has_aux=has_aux)

    # Per-bucket reduce schedule, resolved at trace time — ONE derivation
    # shared with the static analyzer (see :func:`reduce_schedule`).
    sched, rs_axes, rs_group, hier = reduce_schedule(
        plan, mesh, reduce_op=reduce_op, hierarchy=hierarchy)

    levels: List[Dict[str, object]] = []
    if zero3 and plan.n_scatter_buckets:
        levels.append({
            "level": "ici", "op": "psum_scatter", "axes": [FSDP],
            "bucket_nbytes": [n if plan._is_scatter(b) else 0
                              for b, n in enumerate(plan.bucket_nbytes)]})
    # A flat reduce on a multi-slice mesh spans BOTH transports in one
    # collective — label it so, or the report would claim the cross-slice
    # hop rides ICI.
    flat_level = "ici" if dcn is None or hier else "ici+dcn"
    if any(m == "rs" for m, _ in sched):
        levels.append({
            "level": "ici" if hier else flat_level, "op": "psum_scatter",
            "axes": list(rs_axes),
            "bucket_nbytes": [n if m == "rs" else 0 for (m, _), n in
                              zip(sched, plan.bucket_nbytes)]})
    if any(m == "ar" for m, _ in sched):
        levels.append({
            "level": flat_level, "op": "all_reduce", "axes": list(axes),
            "bucket_nbytes": [n if m == "ar" else 0 for (m, _), n in
                              zip(sched, plan.bucket_nbytes)]})
    if hier:
        # The DCN hop moves one scattered chunk per bucket.
        def _chunk(b):
            numel, item = plan.bucket_numel[b], \
                plan.dtypes[plan.buckets[b][0]].itemsize
            if sched[b][0] == "scatter":
                return (numel // plan.shard_size) * item
            padded = numel + ((-numel) % rs_group)
            return (padded // rs_group) * item
        levels.append({
            "level": "dcn", "op": "all_reduce", "axes": [dcn],
            "bucket_nbytes": [_chunk(b) for b in range(plan.n_buckets)]})
    _record("accum_step", n_buckets=plan.n_buckets,
            bucket_nbytes=list(plan.bucket_nbytes),
            threshold=plan.threshold, microbatches=microbatches,
            reduce_op=reduce_op, sync_group=group,
            hierarchy="hierarchical" if hier else "flat",
            zero3=zero3, n_scatter_buckets=plan.n_scatter_buckets,
            n_padded_buckets=sum(1 for b in range(plan.n_buckets)
                                 if plan._is_padded(b)),
            levels=levels)
    # Mirror the whole schedule into the unified collective registry: the
    # reduce levels plus (ZeRO-3) the forward gathers, so every transfer
    # in the step shows up in profiler.report("collective").
    sched_mod.record_reduce_levels("accum", levels)
    if zero3 and gplan.gather_leaves:
        if gather == "bucketed":
            # The quantized lane ships int8 on the wire: 1 B/element
            # instead of the bucket dtype's itemsize.
            nbytes = [plan.bucket_numel[b] for b in gplan.gather_buckets] \
                if quant else list(gplan.gather_nbytes)
        else:
            nbytes = [
                int(np.prod(plan.shapes[i], dtype=np.int64))
                * plan.dtypes[i].itemsize for i, _ in gplan.gather_leaves]
        sched_mod.record_collective(
            "accum.fwd_gather", kind="all_gather", plane="fwd_gather",
            axes=[FSDP], nbytes=nbytes, gather=gather,
            quant="int8" if quant else None,
            prefetch=gplan.prefetch if gather == "bucketed" else None,
            per_microbatch=microbatches)
    if quant:
        raw = list(gplan.gather_nbytes)
        q_nb = [plan.bucket_numel[b] for b in gplan.gather_buckets]
        profiler.record(
            "quant", "accum_gather", n_buckets=gplan.n_gather_buckets,
            window=int(quant_amax[0].shape[0]) if quant_amax else 0,
            raw_nbytes=raw, int8_nbytes=q_nb,
            bytes_saved=sum(raw) - sum(q_nb),
            per_microbatch=microbatches)

    def gather_params(p, scales=None):
        if not zero3:
            return p
        leaves = list(jax.tree.leaves(p))
        if gather == "bucketed":
            return jax.tree.unflatten(plan.treedef,
                                      gplan.gather(leaves, scales=scales))
        # Per-leaf pin path: replicated/scalar/uneven leaves entered the
        # region whole and are not in the (static) drive list.
        for i, d in gplan.gather_leaves:
            leaves[i] = jax.lax.all_gather(leaves[i], FSDP, axis=d,
                                           tiled=True)
        return jax.tree.unflatten(plan.treedef, leaves)

    def spmd(params, local, slots=None, scal=None, qamax=None):
        scales = None
        new_amax: List[jax.Array] = []
        if quant:
            from tony_tpu.ops import quant as quant_mod

            # Delayed scaling: THIS step quantizes with the scale the
            # state carried in (identical on every shard — the int8
            # gather's exactness rests on that); the CURRENT amax is
            # measured once at region entry (params are loop-invariant
            # inside the scan) and rolled into the history for the next
            # step, the same in-region cadence as PR 7's opt slots.
            leaves0 = jax.tree.leaves(params)
            scales = [quant_mod.hist_scale(h) for h in qamax]
            for k, b in enumerate(gplan.gather_buckets):
                m = jax.lax.pmax(quant_mod.bucket_amax(
                    [leaves0[i] for i in plan.buckets[b]]), gplan.axis)
                new_amax.append(quant_mod.push_amax(qamax[k], m))
        mbs = jax.tree.map(
            lambda x: x.reshape((microbatches, x.shape[0] // microbatches)
                                + x.shape[1:]), local)
        acc0 = []
        for b, (idxs, n) in enumerate(zip(plan.buckets, plan.bucket_numel)):
            dt = plan.dtypes[idxs[0]]
            mode, _ = sched[b]
            if mode == "scatter":
                n = n // plan.shard_size
            elif mode == "rs":
                n = (n + ((-n) % rs_group)) // rs_group   # padded shard
            acc0.append(jnp.zeros((n,), dt))

        def body(carry, mb):
            loss_acc, aux_acc, acc = carry
            out, grads = grad_fn(gather_params(params, scales), mb)
            loss, aux = out if has_aux else (out, jnp.float32(0.0))
            bufs = plan.pack(grads)
            nxt = []
            for b, (a, buf) in enumerate(zip(acc, bufs)):
                mode, post = sched[b]
                if mode == "scatter":
                    s = jax.lax.psum_scatter(buf, FSDP, tiled=True)
                elif mode == "rs":
                    pad = (-buf.shape[0]) % rs_group
                    if pad:
                        buf = jnp.concatenate(
                            [buf, jnp.zeros((pad,), buf.dtype)])
                    s = jax.lax.psum_scatter(buf, rs_axes, tiled=True)
                else:
                    s = jax.lax.psum(buf, axes)
                for g in post:
                    s = jax.lax.psum(s, g)
                nxt.append(a + s)
            return (loss_acc + loss, aux_acc + aux, nxt), None

        (loss, aux, acc), _ = jax.lax.scan(
            body, (jnp.float32(0.0), jnp.float32(0.0), acc0), mbs)
        denom = microbatches * group
        if fused is not None:
            # Fused-optimizer tail: mean-scale the bucket accumulators
            # ("rs" buckets re-gather once first — their leaves live
            # replicated) and hand them STRAIGHT to the in-region update;
            # the leaf-grad pytree never materializes.
            g_bufs = []
            for b, (a, n) in enumerate(zip(acc, plan.bucket_numel)):
                if sched[b][0] == "rs":
                    a = jax.lax.all_gather(a, rs_axes, tiled=True)[:n]
                g_bufs.append(a / denom)
            with jax.named_scope("optimizer"):
                new_leaves, new_slots, gnorm = fused.region_apply(
                    plan, jax.tree.leaves(params), g_bufs, slots, scal,
                    sharded=zero3 and plan.shard_size > 1)
            loss = jax.lax.psum(loss, axes) / denom
            aux = jax.lax.psum(aux, axes) / denom
            return (loss, aux,
                    jax.tree.unflatten(plan.treedef, new_leaves),
                    new_slots, gnorm) + ((new_amax,) if quant else ())
        # Tail: "rs" buckets re-gather ONCE over their scatter group;
        # even scatter buckets stay in the shard layout (that IS the
        # output); PADDED scatter buckets re-gather over fsdp and unpad —
        # their leaves exit the region whole.
        leaf_out: list = [None] * len(plan.shapes)
        for b, (a, n) in enumerate(zip(acc, plan.bucket_numel)):
            mode = sched[b][0]
            if mode == "rs":
                buf = jax.lax.all_gather(a, rs_axes, tiled=True)[:n]
                parts = plan.leaf_buffers(b, buf, layout="full")
            elif mode == "scatter" and plan._is_padded(b):
                buf = jax.lax.all_gather(a, FSDP, tiled=True)
                parts = plan.leaf_buffers(b, buf, layout="gathered")
            elif mode == "scatter":
                parts = plan.leaf_buffers(b, a, layout="shard")
            else:
                parts = plan.leaf_buffers(b, a, layout="full")
            for i, v in parts.items():
                leaf_out[i] = v
        tree = jax.tree.unflatten(plan.treedef, leaf_out)
        grads = jax.tree.map(lambda b: b / denom, tree)
        loss = jax.lax.psum(loss, axes) / denom
        aux = jax.lax.psum(aux, axes) / denom
        return (loss, aux, grads) + ((new_amax,) if quant else ())

    amax_specs = [P()] * len(quant_amax) if quant else None
    if fused is not None:
        if opt_slots is None or opt_scal is None:
            raise ValueError(
                "microbatch_grads(fused=...) needs opt_slots (the bucket-"
                "resident slot buffers) and opt_scal (FusedOptimizer"
                ".scalars(count))")
        fused.check_slots(plan, opt_slots)
        bspecs_f = fused.bucket_specs(plan)
        slot_specs = {n: list(bspecs_f) for n in fused.slot_names}
        fused.record("accum_update", plan, microbatches=microbatches)
        in_specs = (p_specs, b_specs, slot_specs, P())
        out_specs = (P(), P(), p_specs, slot_specs, P())
        args = (params, batch, opt_slots, opt_scal)
        if quant:
            in_specs += (amax_specs,)
            out_specs += (amax_specs,)
            args += (list(quant_amax),)
        outs = compat.shard_map(spmd, mesh, in_specs=in_specs,
                                out_specs=out_specs)(*args)
        loss, aux, new_params, new_slots, gnorm = outs[:5]
        tail = (outs[5],) if quant else ()
        if has_aux:
            return (loss, aux, new_params, new_slots, gnorm) + tail
        return (loss, new_params, new_slots, gnorm) + tail
    if quant:
        loss, aux, grads, new_hist = compat.shard_map(
            lambda p, l, qa: spmd(p, l, qamax=qa), mesh,
            in_specs=(p_specs, b_specs, amax_specs),
            out_specs=(P(), P(), p_specs, amax_specs))(
                params, batch, list(quant_amax))
        if has_aux:
            return loss, aux, grads, new_hist
        return loss, grads, new_hist
    loss, aux, grads = compat.shard_map(
        spmd, mesh, in_specs=(p_specs, b_specs),
        out_specs=(P(), P(), p_specs))(params, batch)
    if has_aux:
        return loss, aux, grads
    return loss, grads
