"""Unified collective scheduler: one tracking-and-triggering layer over
every inter-chip transfer in a train step.

T3 (arXiv:2401.16677) argues that fine-grained compute/collective overlap
needs ONE layer that owns all transfers, not a per-collective hack — the
same consolidation Horovod (arXiv:1802.05799) made for GPU reductions.
After PRs 1–2 this repo had the backward half: :class:`~tony_tpu.parallel
.overlap.GradBuckets` schedules the gradient reduce. This module promotes
that planner into the general scheduler the ROADMAP names:

* :class:`GatherPlan` — the forward-path twin of the backward scatter.
  ZeRO-3 param ``all_gather``s used to run per leaf and unbucketed; here
  they are coalesced into the SAME shard-major byte-threshold buckets the
  scatter plan uses (one ``all_gather`` per bucket returns the buffer in
  exactly the layout ``GradBuckets.pack`` writes, so
  ``leaf_buffers(layout="gathered")`` unpacks whole leaves — pure data
  movement, bit-exact vs per-leaf gathers). A ``prefetch`` depth chains
  bucket *k*'s gather on bucket *k−prefetch*'s completion via
  ``lax.optimization_barrier``: XLA's latency-hiding scheduler slides
  bucket *k+1*'s gather under bucket *k*'s layer compute, but can never
  hoist EVERY gather to step start — so replicated params only
  materialize for the live window of buckets, preserving the ZeRO-3
  memory contract.
* :func:`moe_dispatch_ffn_combine` — MoE expert dispatch/combine with the
  EP ``all_to_all`` issued EXPLICITLY per capacity chunk inside the layer
  (instead of whatever GSPMD picks for the dispatch einsum): chunk *c+1*'s
  dispatch a2a is dataflow-independent of chunk *c*'s expert FFN, so the
  a2a rides under FFN compute. Math mirrors
  :class:`tony_tpu.models.moe.MoEMLP`'s GSPMD path (same einsums, same
  dtype casts) up to the fp reassociation of the per-chunk combine sum.
* :func:`record_pipeline_edges` — registers ``gpipe``/``gpipe_1f1b``'s
  ``ppermute`` ring edges with the scheduler so pipeline traffic shares
  the same profiler record schema as everything else.
* :func:`record_collective` / ``profiler.report("collective")`` — the one
  record schema (kind, plane, axes, per-issue nbytes + freeform extras):
  every collective in a ZeRO-3 + MoE + pipeline step is either hidden or
  accounted for, inspectable from one report.
"""

from __future__ import annotations

import functools
from dataclasses import dataclass
from typing import Any, List, Optional, Sequence, Tuple

import jax
import jax.numpy as jnp
import numpy as np
from jax.sharding import Mesh, PartitionSpec as P

from tony_tpu import compat, profiler
from tony_tpu.parallel import DATA, EXPERT, FSDP, MODEL, PIPE, SEQ, SLICE
from tony_tpu.parallel.overlap import GradBuckets

# Forward-gather prefetch depth: how many bucket gathers may be in flight
# ahead of the one compute is consuming. 1 = classic double buffering (the
# next bucket gathers while this one computes); 0 disables the chain (all
# gathers issue eagerly — max overlap, max transient replicated memory).
DEFAULT_PREFETCH = 1

# Trace-time side channel into the profiler's plan registry, kind
# "collective": one schema for every inter-chip transfer the step issues.
record_collective = functools.partial(profiler.record, "collective")


@dataclass(frozen=True)
class GatherPlan:
    """Bucketed + prefetched forward ``all_gather`` schedule over a ZeRO-3
    :class:`GradBuckets` plan.

    Everything here is resolved at BUILD time, outside any trace (the
    per-call spec probing that used to live in ``gather_params`` is
    hoisted into :meth:`from_buckets`):

    * ``gather_buckets`` — the plan's even (unpadded) scatter buckets in
      leaf-consumption order: these hold exactly the leaves that cross
      the manual region in the shard layout and need gathering.
    * ``gather_leaves`` — ``(leaf_index, shard_dim)`` pairs for the same
      leaves, the static drive list of the per-leaf fallback path.
    * ``passthrough`` — leaf indices NOT gathered: replicated leaves,
      scalars, and uneven (padded) leaves, which enter the region whole.
    """

    plan: GradBuckets
    prefetch: int = DEFAULT_PREFETCH
    axis: str = FSDP
    gather_buckets: Tuple[int, ...] = ()
    gather_leaves: Tuple[Tuple[int, int], ...] = ()
    passthrough: Tuple[int, ...] = ()

    @classmethod
    def from_buckets(cls, plan: GradBuckets, *,
                     prefetch: int = DEFAULT_PREFETCH,
                     axis: str = FSDP) -> "GatherPlan":
        if prefetch < 0:
            raise ValueError(f"prefetch must be >= 0, got {prefetch}")
        gatherable = set()
        buckets = []
        for b in range(plan.n_buckets):
            if plan._is_scatter(b) and not plan._is_padded(b):
                buckets.append(b)
                gatherable.update(plan.buckets[b])
        # Consumption order: leaves flatten in model order, so the bucket
        # holding the earliest leaf is the one compute touches first —
        # gather in that order or the prefetch chain fights the consumer.
        buckets.sort(key=lambda b: min(plan.buckets[b]))
        leaves = tuple(
            (i, plan.shard_dims[i]) for i in range(len(plan.shapes))
            if i in gatherable)
        passthrough = tuple(i for i in range(len(plan.shapes))
                            if i not in gatherable)
        return cls(plan, prefetch, axis, tuple(buckets), leaves,
                   passthrough)

    @property
    def n_gather_buckets(self) -> int:
        return len(self.gather_buckets)

    @property
    def gather_nbytes(self) -> Tuple[int, ...]:
        """Per-gather payload bytes (the FULL gathered buffer — what the
        collective materializes, shard_size × what each chip sends)."""
        return tuple(self.plan.bucket_nbytes[b] for b in self.gather_buckets)

    def window_nbytes(self) -> int:
        """The prefetch-window memory promise: the most replicated bytes
        the chained gathers may have in flight at once — bucket *k* can't
        issue before bucket *k − prefetch* exists, so at most
        ``prefetch + 1`` consecutive gathered buffers coexist as fresh
        gathers (``prefetch = 0`` disables the chain: everything may
        issue eagerly). This is the bound the analyzer's replication-leak
        rule audits the traced step against."""
        sizes = self.gather_nbytes
        if not sizes:
            return 0
        if not self.prefetch:
            return sum(sizes)
        width = min(len(sizes), self.prefetch + 1)
        return max(sum(sizes[k:k + width])
                   for k in range(len(sizes) - width + 1))

    def gather(self, leaves: Sequence[jax.Array],
               scales: Optional[Sequence[jax.Array]] = None
               ) -> List[jax.Array]:
        """Region-local leaves (shard layout) → full leaves, one
        ``all_gather`` per bucket, prefetch-chained. Must be called inside
        a manually-sharded region over ``self.axis``.

        ``scales`` (one f32 scalar per gather bucket, IDENTICAL on every
        shard — the quantized lane's delayed scales) switches the wire
        format to int8: each chunk is symmetric-quantized before the
        collective and dequantized on arrival, so the gather ships
        ``itemsize×`` fewer bytes (4× for f32 params). Because the scale
        is shared, quantize∘gather ≡ gather∘quantize bit-exact — see
        :mod:`tony_tpu.ops.quant`."""
        plan = self.plan
        out = list(leaves)
        done: List[jax.Array] = []
        for k, b in enumerate(self.gather_buckets):
            idxs = plan.buckets[b]
            parts = [leaves[i].reshape(-1) for i in idxs]
            # packsite: region-local — inside the shard_map region these
            # are per-device shard buffers, never GSPMD-sharded arrays.
            chunk = parts[0] if len(parts) == 1 else jnp.concatenate(parts)
            if self.prefetch and k >= self.prefetch:
                # Bucket k may not start gathering before bucket
                # k-prefetch's buffer exists: bounds in-flight replicated
                # bytes without serializing gather k behind its consumer.
                dep = done[k - self.prefetch].reshape(-1)[0]
                chunk, _ = jax.lax.optimization_barrier((chunk, dep))
            if scales is not None:
                from tony_tpu.ops.quant import dequantize, quantize

                q = jax.lax.all_gather(quantize(chunk, scales[k]),
                                       self.axis, tiled=True)
                full = dequantize(q, scales[k], chunk.dtype)
            else:
                full = jax.lax.all_gather(chunk, self.axis, tiled=True)
            done.append(full)
            # The gathered buffer is shard-major — exactly pack()'s scatter
            # layout — so the uneven-leaf exit path's "gathered" unpacking
            # is the inverse for free (no pads here: padded buckets are
            # passthrough).
            for i, v in plan.leaf_buffers(b, full, layout="gathered").items():
                out[i] = v
        return out


def record_reduce_levels(tag: str, levels: Sequence[dict]) -> None:
    """Mirror an accum plan's per-level reduce schedule into the unified
    collective registry: one record per (level, op) with the per-bucket
    bytes that actually move at that level."""
    for lv in levels:
        nbytes = [n for n in lv.get("bucket_nbytes", []) if n]
        record_collective(
            f"{tag}.grad.{lv['level']}.{lv['op']}", kind=lv["op"],
            plane="grad_reduce", axes=list(lv["axes"]), nbytes=nbytes)


def record_pipeline_edges(tag: str, *, stages: int, microbatches: int,
                          mb_nbytes: int, reverse: bool = False) -> None:
    """Register a pipeline schedule's ``ppermute`` ring edges: one
    microbatch buffer crosses a stage edge per tick (forward fill/drain;
    the 1F1B backward runs the mirrored reverse ring too)."""
    ticks = microbatches + stages - 1
    directions = 2 if reverse else 1
    record_collective(
        f"{tag}.ppermute", kind="ppermute", plane="pipeline", axes=[PIPE],
        nbytes=[mb_nbytes] * (ticks * directions), stages=stages,
        microbatches=microbatches, ticks_per_direction=ticks,
        directions=directions)


def moe_dispatch_ffn_combine(x: jax.Array, dispatch: jax.Array,
                             combine: jax.Array,
                             weights: Tuple[jax.Array, jax.Array, jax.Array],
                             mesh: Mesh, *, chunks: int = 2,
                             dtype: Any = jnp.bfloat16,
                             axis: str = EXPERT) -> jax.Array:
    """Expert-parallel SwiGLU dispatch → FFN → combine with the EP
    ``all_to_all`` issued explicitly per capacity chunk.

    Args:
      x: [B, T, D] tokens, batch dim sharded over the DP axes as usual.
      dispatch/combine: [B, T, E, C] routing tensors from
        :func:`tony_tpu.models.moe.router_assignment` (computed locally —
        no cross-device traffic).
      weights: stacked ``(w_gate, w_up, w_down)`` with leading expert dim
        E, sharded over ``axis``.
      chunks: capacity-chunk count — the capacity dim C splits into this
        many a2a+FFN waves so chunk *c+1*'s dispatch ``all_to_all`` rides
        under chunk *c*'s expert FFN compute (clamped to C).

    The math is the GSPMD dispatch-einsum path of
    :class:`~tony_tpu.models.moe.MoEMLP` with the same dtype casts; the
    only numerical difference is the per-chunk combine sum's fp
    reassociation. Owns ONLY the expert axis: model/seq/pipe mesh axes
    must be 1 (those belong to GSPMD, outside this region), and this must
    not be called inside another manual region (e.g. the accum engine's).
    """
    w_gate, w_up, w_down = weights
    if axis not in mesh.axis_names:
        raise ValueError(f"mesh has no {axis!r} axis: {mesh.axis_names}")
    ep = mesh.shape[axis]
    e = w_gate.shape[0]
    if e % ep:
        raise ValueError(
            f"n_experts={e} not divisible by the {ep}-way {axis!r} mesh "
            f"axis — every chip must own the same number of experts")
    for a in (MODEL, SEQ, PIPE):
        if a in mesh.axis_names and mesh.shape[a] > 1:
            raise ValueError(
                f"explicit a2a owns only the {axis!r} axis; mesh axis "
                f"{a!r} has size {mesh.shape[a]} — tensor/seq/pipe "
                f"sharding belongs to GSPMD (use the einsum path)")
    batch_axes = tuple(a for a in (SLICE, DATA, FSDP)
                       if a in mesh.axis_names and mesh.shape[a] > 1)
    dp = 1
    for a in batch_axes:
        dp *= mesh.shape[a]
    c = dispatch.shape[-1]
    n_chunks = max(1, min(chunks, c))
    bounds = np.cumsum([0] + [len(s) for s in
                              np.array_split(np.arange(c), n_chunks)])
    itemsize = np.dtype(dtype).itemsize
    # Per-issue PER-CHIP payload (the [E, B_local, Cc, D] tensor each
    # chip exchanges) — same semantics as the pipeline-edge records, so
    # report("collective") byte columns compare across planes.
    chunk_nbytes = [
        e * (x.shape[0] // dp) * int(bounds[j + 1] - bounds[j])
        * x.shape[-1] * itemsize for j in range(n_chunks)]
    record_collective("moe.dispatch", kind="all_to_all", plane="moe",
                      axes=[axis], nbytes=chunk_nbytes, chunks=n_chunks,
                      capacity=c, experts=e)
    record_collective("moe.combine", kind="all_to_all", plane="moe",
                      axes=[axis], nbytes=chunk_nbytes, chunks=n_chunks,
                      capacity=c, experts=e)

    x_spec = P(batch_axes or None)
    w_spec = P(axis)

    def spmd(x_l, disp_l, comb_l, wg_l, wu_l, wd_l):
        wg = wg_l.astype(dtype)
        wu = wu_l.astype(dtype)
        wd = wd_l.astype(dtype)
        y = jnp.zeros(x_l.shape[:2] + (x_l.shape[-1],), dtype)
        for j in range(n_chunks):
            c0, c1 = int(bounds[j]), int(bounds[j + 1])
            # Dispatch: local tokens → [E, B_l, Cc, D], then a2a exchanges
            # the expert dim for the group dim: each chip keeps its OWN
            # experts' slots from every peer's groups.
            xin = jnp.einsum("gsec,gsd->egcd",
                             disp_l[..., c0:c1].astype(dtype), x_l,
                             precision=jax.lax.Precision.DEFAULT)
            xin = jax.lax.all_to_all(xin, axis, split_axis=0,
                                     concat_axis=1, tiled=True)
            h = jax.nn.silu(jnp.einsum("egcd,edf->egcf", xin, wg))
            h = h * jnp.einsum("egcd,edf->egcf", xin, wu)
            out = jnp.einsum("egcf,efd->egcd", h, wd)
            # Combine a2a: the inverse exchange, back to token order.
            out = jax.lax.all_to_all(out, axis, split_axis=1,
                                     concat_axis=0, tiled=True)
            y = y + jnp.einsum("gsec,egcd->gsd",
                               comb_l[..., c0:c1].astype(dtype), out)
        return y

    return compat.shard_map(
        spmd, mesh,
        in_specs=(x_spec, x_spec, x_spec, w_spec, w_spec, w_spec),
        out_specs=x_spec)(x, dispatch, combine, w_gate, w_up, w_down)
