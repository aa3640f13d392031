"""Parallelism layer: device meshes, sharding rules, and collectives.

The reference delegates ALL parallelism to the launched frameworks (SURVEY.md
§2.3: PS via ``TF_CONFIG``, ring-allreduce via Horovod/NCCL, DDP via c10d) —
TonY itself owns no tensor code. This package is the TPU-native replacement
for that delegated layer, built the way JAX programs scale (SURVEY.md §2.3
"TPU-build equivalent" column):

* one :class:`MeshSpec` describes the whole parallelism layout
  (dp/fsdp/pp/ep/sp/tp) and builds a :class:`jax.sharding.Mesh`;
* parameters and activations carry *logical* axis names; :data:`RULES` maps
  them onto mesh axes (GSPMD then inserts the collectives — ``psum`` for DP
  grads over ICI replaces NCCL allreduce, ``all_gather``/``reduce_scatter``
  for FSDP, ``ppermute`` rings for sequence parallelism);
* :mod:`tony_tpu.parallel.ring_attention` provides ring attention over the
  ``seq`` mesh axis for long-context training (SURVEY.md §5.7).

No NCCL, no MPI, no parameter server: the data plane is XLA collectives over
ICI intra-slice / DCN across slices.
"""

from __future__ import annotations

from dataclasses import dataclass
from typing import Optional, Sequence, Tuple

from tony_tpu import profiler

with profiler.importing("jax"):         # set-up span tony:import
    import jax
import numpy as np
from jax.sharding import Mesh, NamedSharding, PartitionSpec as P

# Mesh axis names, outermost (most DCN-friendly) to innermost (most
# ICI-bandwidth-hungry). The slice axis IS the DCN boundary: collectives
# over it cross slices, everything else stays on ICI. Data-parallel axes
# next so cross-slice traffic is the cheap gradient allreduce;
# tensor-parallel innermost so its per-layer collectives ride the fastest
# ICI links.
SLICE = "slice"     # DCN data parallel: one index per TPU slice
DATA = "data"       # pure data parallel (replicated params)
FSDP = "fsdp"       # data parallel with sharded params/optimizer (ZeRO-3)
PIPE = "pipe"       # pipeline parallelism (GPipe over ppermute)
EXPERT = "expert"   # MoE expert parallelism
SEQ = "seq"         # sequence/context parallelism (ring attention)
MODEL = "model"     # tensor parallelism (megatron-style)

AXES: Tuple[str, ...] = (SLICE, DATA, FSDP, PIPE, EXPERT, SEQ, MODEL)

# Logical-axis → mesh-axis rules (flax linen logical partitioning format).
# Parameters: weights shard over fsdp on their "embed"-like dim and over
# model on their "heads/ffn/vocab"-like dim. Activations: batch over both
# data axes, sequence over the ring axis.
RULES: Tuple[Tuple[str, object], ...] = (
    ("batch", (SLICE, DATA, FSDP)),
    ("act_seq", SEQ),
    ("act_embed", None),   # activations' feature dim (params' "embed" is
                           # fsdp-sharded; mixing both in one array would
                           # double-map the fsdp axis)
    ("act_heads", MODEL),
    ("embed", FSDP),
    ("heads", MODEL),
    ("kv_heads", MODEL),
    ("ffn", MODEL),
    ("vocab", MODEL),
    ("expert", EXPERT),
    ("expert_dim", None),  # router logits' expert dim (tiny, replicated)
    ("stage", None),       # pipeline stages: scan-over-layers axis, unsharded
    ("norm", None),
)


@dataclass(frozen=True)
class MeshSpec:
    """One parallelism layout: how many ways along each axis.

    The product must equal the device count. ``dp`` is accumulated
    automatically when left at 0: remaining devices go to data parallelism —
    the common "fill the pod with DP" default. ``slices`` is the DCN-level
    data-parallel degree (one index per TPU slice; 1 = single-slice job);
    the overlap engine reduces over it separately from the ICI axes.
    """
    dp: int = 0
    fsdp: int = 1
    pp: int = 1
    ep: int = 1
    sp: int = 1
    tp: int = 1
    slices: int = 1

    def resolved_dp(self, n_devices: int) -> int:
        rest = (self.slices * self.fsdp * self.pp * self.ep * self.sp
                * self.tp)
        if self.dp:
            return self.dp
        if n_devices % rest:
            raise ValueError(f"{n_devices} devices not divisible by "
                             f"slices*fsdp*pp*ep*sp*tp={rest}")
        return n_devices // rest

    def build(self, devices: Optional[Sequence[jax.Device]] = None) -> Mesh:
        devices = list(devices if devices is not None
                       else profiler.backend_devices())
        dp = self.resolved_dp(len(devices))
        shape = (self.slices, dp, self.fsdp, self.pp, self.ep, self.sp,
                 self.tp)
        if int(np.prod(shape)) != len(devices):
            raise ValueError(
                f"mesh shape {dict(zip(AXES, shape))} needs "
                f"{int(np.prod(shape))} devices, have {len(devices)}")
        arr = np.asarray(devices).reshape(shape)
        return Mesh(arr, AXES)


def make_mesh(n_devices: Optional[int] = None, **spec_kw) -> Mesh:
    """Convenience: ``make_mesh(tp=2, sp=4)`` over all (or the first N)
    local devices."""
    devices = jax.devices()
    if n_devices is not None:
        devices = devices[:n_devices]
    return MeshSpec(**spec_kw).build(devices)


def batch_sharding(mesh: Mesh, *, seq_axis: bool = False) -> NamedSharding:
    """Input-batch sharding: batch dim over the slice axis and both DP axes;
    optionally the sequence dim over the ring axis (long-context inputs)."""
    if seq_axis:
        return NamedSharding(mesh, P((SLICE, DATA, FSDP), SEQ))
    return NamedSharding(mesh, P((SLICE, DATA, FSDP)))


def replicated(mesh: Mesh) -> NamedSharding:
    return NamedSharding(mesh, P())


def logical_sharding(mesh: Mesh, *logical_axes: Optional[str],
                     allow_unknown: bool = False) -> NamedSharding:
    """NamedSharding for an array whose dims carry the given logical axis
    names (None = unsharded dim), resolved through :data:`RULES`.

    Unknown names raise: a typo'd axis used to fall through ``get`` to
    ``None`` and silently replicate the dim — the worst failure mode for a
    sharding bug (correct numbers, wrong memory/traffic). Pass
    ``allow_unknown=True`` to deliberately leave unlisted names unsharded
    (e.g. model code carrying axes for a rule set layered elsewhere).
    """
    table = dict(RULES)
    spec = []
    for ax in logical_axes:
        if ax is None:
            spec.append(None)
        elif ax in table:
            spec.append(table[ax])
        elif allow_unknown:
            spec.append(None)
        else:
            raise ValueError(
                f"unknown logical axis {ax!r}: not in RULES "
                f"({sorted(table)}); pass allow_unknown=True to leave it "
                f"unsharded deliberately")
    return NamedSharding(mesh, P(*spec))


def shard_logical(mesh: Mesh, x: jax.Array, *logical_axes: Optional[str],
                  allow_unknown: bool = False) -> jax.Array:
    """Device-put ``x`` with :func:`logical_sharding`."""
    return jax.device_put(
        x, logical_sharding(mesh, *logical_axes,
                            allow_unknown=allow_unknown))


def constraint(x: jax.Array, mesh: Mesh, *logical_axes: Optional[str],
               allow_unknown: bool = False) -> jax.Array:
    """``with_sharding_constraint`` through the logical-axis rules — the
    in-jit annotation that steers GSPMD."""
    return jax.lax.with_sharding_constraint(
        x, logical_sharding(mesh, *logical_axes,
                            allow_unknown=allow_unknown))


from tony_tpu.parallel.ring_attention import (  # noqa: E402  (re-export)
    ring_attention, ring_attention_sharded)
from tony_tpu.parallel.pipeline import (  # noqa: E402  (re-export)
    gpipe, gpipe_1f1b, pipelined_lm_logits, stage_split)
from tony_tpu.parallel.overlap import (  # noqa: E402  (re-export)
    GradBuckets, fsdp_param_specs, microbatch_grads)
from tony_tpu.parallel.sched import (  # noqa: E402  (re-export)
    GatherPlan, moe_dispatch_ffn_combine)

__all__ = [
    "AXES", "SLICE", "DATA", "FSDP", "PIPE", "EXPERT", "SEQ", "MODEL",
    "RULES",
    "MeshSpec", "make_mesh", "batch_sharding", "replicated",
    "logical_sharding", "shard_logical", "constraint",
    "ring_attention", "ring_attention_sharded", "gpipe", "gpipe_1f1b",
    "pipelined_lm_logits", "stage_split",
    "GradBuckets", "fsdp_param_specs", "microbatch_grads",
    "GatherPlan", "moe_dispatch_ffn_combine",
]
