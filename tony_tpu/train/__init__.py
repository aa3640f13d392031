"""Training harness: sharded state, train steps, multi-host data feeding.

The reference has no training loop of its own — user scripts train inside
whatever framework TonY launched (SURVEY.md §1 L7). The TPU rebuild makes the
loop a library so examples and benchmarks share one GSPMD path:

* :func:`create_train_state` — init params under ``jit`` with shardings
  resolved from the model's flax logical axis names through
  :data:`tony_tpu.parallel.RULES` (optimizer state inherits by propagation);
* :func:`make_train_step` — one jitted step: loss → grad → update, batch
  sharded over the DP axes; XLA inserts the gradient ``psum`` over ICI
  (this IS the Horovod-allreduce/DDP replacement, SURVEY.md §2.3–2.4);
* :func:`global_batch` — multi-host feeding: each process contributes its
  local shard of the global batch (``jax.make_array_from_process_local_data``),
  the executor-side analogue of per-worker data sharding.
"""

from __future__ import annotations

import contextlib
import functools
import logging
import os
import time
import weakref
from typing import Any, Callable, Dict, Iterable, Optional, Tuple

from tony_tpu import profiler

# Each the set-up span tony:import where this module is the first of the
# process to import it (PERF.md §5 has what each costs on the chip's host).
with profiler.importing("jax"):
    import jax
    import jax.numpy as jnp
with profiler.importing("flax.linen"):
    import flax.linen as nn
    from flax.training.train_state import TrainState
with profiler.importing("optax"):
    import optax
import numpy as np
from jax.sharding import Mesh, NamedSharding, PartitionSpec as P

from tony_tpu import chaos, constants, remat
from tony_tpu import parallel as par
from tony_tpu.parallel import overlap

_log = logging.getLogger(__name__)


def cross_entropy_loss(logits: jax.Array, labels: jax.Array) -> jax.Array:
    """Mean softmax cross entropy; labels are integer classes (any rank —
    tokens or images)."""
    return optax.softmax_cross_entropy_with_integer_labels(
        logits.astype(jnp.float32), labels).mean()


def next_token_loss(logits: jax.Array, tokens: jax.Array) -> jax.Array:
    """Causal-LM loss: predict token t+1 from position t (device scope
    ``loss``)."""
    with jax.named_scope("loss"):
        return cross_entropy_loss(logits[:, :-1], tokens[:, 1:])


def _recomputed_xent(rows, wb, labels, weights, r):
    """Sum of the weighted row losses over ``r``; ``rows`` [n, chunk, d],
    ``wb`` [d, V], ``labels`` and ``weights`` [n, chunk]. Differentiated by
    autodiff, the checkpointed body multiplies a chunk's logits a second
    time in the backward: four products a chunk."""
    @jax.checkpoint
    def body(acc, xs):
        hc, lc, mc = xs
        with jax.named_scope("lm_head"):
            logits = (hc @ wb).astype(jnp.float32)      # [chunk, V]
        with jax.named_scope("loss"):
            lse = jax.nn.logsumexp(logits, axis=-1)
            lab = jnp.take_along_axis(logits, lc[:, None], axis=-1)[:, 0]
            return acc + ((lse - lab) * mc).sum(), None

    total, _ = jax.lax.scan(body, jnp.float32(0.0), (rows, labels, weights))
    return total / r


@functools.partial(jax.custom_vjp, nondiff_argnums=(4,))
def _grad_in_forward_xent(rows, wb, labels, weights, r):
    """:func:`_recomputed_xent` whose differentiated pass takes both
    gradients while a chunk's logits are in hand: three products a chunk.
    Undifferentiated (evaluation, ``eval_shape``) no gradient is formed."""
    return _recomputed_xent(rows, wb, labels, weights, r)


def _grad_in_forward_xent_fwd(rows, wb, labels, weights, r):
    profiler.count_once("head:grad_in_forward", 1)

    def body(carry, xs):
        acc, dw = carry
        hc, lc, mc = xs
        with jax.named_scope("lm_head"):
            low, products = jax.vjp(jnp.matmul, hc, wb)     # [chunk, V]
        with jax.named_scope("loss"):
            logits = low.astype(jnp.float32)
            # jax.nn.logsumexp with its parts kept, for d loss / d logits
            # below to be autodiff's of _recomputed_xent's body operation
            # for operation: the same float32 numbers, one cast to the
            # products' dtype.
            top = jnp.max(logits, axis=-1)
            top = jnp.where(jnp.isfinite(top), top, 0.0)
            e = jnp.exp(logits - top[:, None])
            s = e.sum(axis=-1)
            lse = jnp.log(s) + top
            # The label logit is picked before the cast (the same number):
            # a gather over the float32 logits makes XLA write them out for
            # it alone, half a GiB a chunk at 1024 x 131,136.
            lab = jnp.take_along_axis(low, lc[:, None], axis=-1)[:, 0]
            term = ((lse - lab.astype(jnp.float32)) * mc).sum()
            ct = mc / r                         # a row's cotangent
            soft = e * (ct / s)[:, None]
            hit = jax.lax.broadcasted_iota(
                jnp.int32, logits.shape, 1) == lc[:, None]
            dl = jnp.where(hit, soft - ct[:, None], soft).astype(low.dtype)
        with jax.named_scope("lm_head"):
            dh, dwc = products(dl)              # [chunk, d], [d, V]
        return (acc + term, dw + dwc), dh

    # Last chunk first, as autodiff's backward of the scan runs: the table's
    # gradient is summed in ``dtype``, and the order is part of the sum.
    (total, dw), dh = jax.lax.scan(
        body, (jnp.float32(0.0), jnp.zeros_like(wb)),
        (rows, labels, weights), reverse=True)
    return total / r, (dh, dw)


def _grad_in_forward_xent_bwd(r, res, g):
    scale = lambda a: (g * a.astype(jnp.float32)).astype(a.dtype)
    return (*map(scale, res), None, None)


_grad_in_forward_xent.defvjp(_grad_in_forward_xent_fwd,
                             _grad_in_forward_xent_bwd)


def chunked_next_token_xent(hidden: jax.Array, lm_head: jax.Array,
                            tokens: jax.Array, chunk: int,
                            dtype=jnp.bfloat16, shift: int = 1) -> jax.Array:
    """Fused LM-head + causal cross entropy that never holds the [B, T, V]
    logits (float32: 16 GiB at 1 x 32768 over 131,136 columns). Rows go
    through a ``lax.scan`` in ``chunk``-sized steps: a chunk's logits in
    ``dtype`` on the MXU, float32 log-sum-exp minus the label logit, summed
    into a carry. Row ``i`` is scored against token ``i + shift`` (2: the
    second-next token a multi-token-prediction module predicts), a mean
    over the ``T - shift`` rows that have one.

    Differentiated, the same pass takes the gradient while the chunk's
    logits are in hand: ``(softmax - onehot) * weight / r`` as autodiff
    forms it, cast to ``dtype``, times the head for the rows' gradient (the
    scan's stacked output), times the rows for the head's (summed over
    chunks in the carry, last chunk first as autodiff sums them). Those two
    arrays are the only residuals and the backward scales them by the
    cotangent: three products a chunk. At a cotangent of 1 (or any power of
    two) they are the gradients autodiff takes of the checkpointed scan bit
    for bit; another cotangent (0.3, a second loss through the same head)
    scales what is already rounded to ``dtype`` and rounds again, where
    autodiff scales ``dlogits`` before its one rounding: the two differ by
    roundings of ``dtype``, no more — read at 0.3 in bfloat16, 2 ulp at
    the scale of a row of the rows' gradient and, the head's being a sum
    over the chunks that either path rounds once a chunk, 5 ulp at the
    scale of one of its columns over 8 chunks (an element that is a small
    difference of large terms is off by the terms' ulp, as in any sum).
    The cast of ``lm_head``, the slice, the padding and a tied table's
    transpose stay outside, for autodiff to transpose.

    In a step whose backward keeps named residuals
    (:func:`tony_tpu.remat.kept`) autodiff differentiates the checkpointed
    scan instead, which rebuilds a chunk's logits in the backward (four
    products). There the ladder's reading of the step decides what is
    kept, and it reads the gradients held here at twice what the program
    grows by (it counts the heap's holes a second time: PERF.md section
    7), which costs the Keye cell's step its ``sel``; at the floor there
    is nothing to lose. The body goes when the ladder reads what the
    program holds (ROADMAP G22).

    Trace-time counters: ``head:chunks`` (the scan's length),
    ``head:grad_in_forward`` (1 once the three-product pass was traced).
    """
    d = hidden.shape[-1]
    rows = hidden[:, :-shift].reshape(-1, d)
    labels = tokens[:, shift:].reshape(-1)
    r = rows.shape[0]
    n = -(-r // chunk)   # ceil: minimal whole-chunk cover
    pad = n * chunk - r
    if pad:
        # Pad to a whole number of chunks; padded rows get weight 0.
        rows = jnp.pad(rows, ((0, pad), (0, 0)))
        labels = jnp.pad(labels, (0, pad))
        weights = jnp.pad(jnp.ones((r,), jnp.float32), (0, pad))
    else:
        weights = jnp.ones((r,), jnp.float32)
    wb = lm_head.astype(dtype)
    profiler.count_once("head:chunks", n)
    xent = _recomputed_xent if remat.kept() else _grad_in_forward_xent
    return xent(rows.reshape(n, chunk, d), wb, labels.reshape(n, chunk),
                weights.reshape(n, chunk), r)


def param_shardings(model: nn.Module, sample_input: jax.Array, mesh: Mesh,
                    rng: Optional[jax.Array] = None,
                    rules=par.RULES) -> Tuple[Any, Any]:
    """(abstract params, NamedSharding tree) from the model's logical axis
    metadata — no real initialization happens (eval_shape only)."""
    rng = jax.random.PRNGKey(0) if rng is None else rng
    with nn.logical_axis_rules(rules):
        abstract = jax.eval_shape(model.init, rng, sample_input)
    logical = nn.get_partition_spec(abstract)
    shardings = nn.logical_to_mesh_sharding(logical, mesh, list(rules))
    return abstract["params"], shardings["params"]


@profiler.span("tony:create_train_state")
def create_train_state(model: nn.Module, tx: Any,
                       sample_input: jax.Array, rng: jax.Array,
                       mesh: Optional[Mesh] = None,
                       rules=par.RULES) -> TrainState:
    """Initialize a TrainState; with a mesh, params are created already
    sharded (jit + constraints — no host-memory detour) and every
    param-shaped optimizer leaf is pinned to its param's layout.

    ``tx`` may be an optax ``GradientTransformation`` (leaf-major state,
    the default path) or a :class:`tony_tpu.ops.fused_optim
    .FusedOptimizer` — then the optimizer state is **bucket-resident**:
    per-bucket f32 moment buffers in the ZeRO-3 scatter layout, planned
    from the params' committed shardings, consumed in place by
    ``make_accum_train_step(update="fused_bucket")``."""
    from tony_tpu.ops import fused_optim

    fused = isinstance(tx, fused_optim.FusedOptimizer)
    if mesh is None:
        # One program, and only the initialisers' part of it: under jit the
        # forward pass ``model.init`` traces is dead code (eagerly it runs
        # — a whole 16k-token forward of dozens of programs, the scanned
        # layer stack compiled for nothing else).
        params = jax.jit(lambda rng, x: nn.unbox(
            model.init(rng, x))["params"])(rng, sample_input)
        if fused:
            return TrainState(step=0, apply_fn=model.apply, params=params,
                              tx=tx, opt_state=tx.init_state(params))
        return TrainState.create(apply_fn=model.apply, params=params, tx=tx)
    _, shardings = param_shardings(model, sample_input, mesh, rng, rules)

    def make(rng):
        with nn.logical_axis_rules(rules):
            params = nn.unbox(model.init(rng, sample_input))["params"]
        params = jax.tree.map(jax.lax.with_sharding_constraint,
                              params, shardings)
        if fused:
            return params
        state = TrainState.create(apply_fn=model.apply, params=params,
                                  tx=tx)
        # The moments are zeros with no data dependence on the params,
        # so GSPMD propagates nothing to them: left alone they come out
        # REPLICATED (a full copy per device, and a second compile of
        # the step once its outputs shard them). Pin every param-shaped
        # optimizer leaf to its param's layout.
        opt_state = optax.tree_utils.tree_map_params(
            tx, jax.lax.with_sharding_constraint, state.opt_state,
            shardings)
        return state.replace(opt_state=opt_state)

    with jax.set_mesh(mesh):
        out = jax.jit(make)(rng)
    if not fused:
        return out
    # Bucket planning reads COMMITTED shardings, so the opt state is
    # built eagerly from the real (already-sharded) params.
    return TrainState(step=0, apply_fn=model.apply, params=out, tx=tx,
                      opt_state=tx.init_state(out, mesh))


def fsdp_shard_state(state: TrainState, mesh: Mesh) -> TrainState:
    """Re-create a TrainState with params (and fresh optimizer state) in
    the ZeRO-3 layout: each param's first fsdp-divisible dim is sharded
    over the fsdp axis, the rest stay replicated — the manual analogue of
    what ``create_train_state`` produces for models carrying "embed"
    logical axes."""
    from tony_tpu.ops import fused_optim

    F = mesh.shape["fsdp"]

    def spec_of(p):
        for d, n in enumerate(p.shape):
            if n % F == 0:
                return P(*([None] * d + ["fsdp"]
                           + [None] * (p.ndim - d - 1)))
        return P()

    shardings = jax.tree.map(
        lambda p: NamedSharding(mesh, spec_of(p)), state.params)
    params = jax.device_put(state.params, shardings)
    if isinstance(state.tx, fused_optim.FusedOptimizer):
        # Bucket-resident state is planned off committed shardings, so it
        # must be rebuilt AFTER the reshard, not GSPMD-propagated.
        return TrainState(step=0, apply_fn=state.apply_fn, params=params,
                          tx=state.tx,
                          opt_state=state.tx.init_state(params, mesh))
    return TrainState.create(apply_fn=state.apply_fn, params=params,
                             tx=state.tx)


def make_train_step(loss_of: Callable[[jax.Array, Dict[str, jax.Array]],
                                      jax.Array] = None,
                    mesh: Optional[Mesh] = None,
                    rules=par.RULES,
                    donate: bool = True,
                    seq_axis: bool = False,
                    apply_kwargs_of: Optional[Callable[
                        [Dict[str, jax.Array]], Dict[str, Any]]] = None):
    """Build the jitted train step ``(state, batch) -> (state, metrics)``.

    ``loss_of(logits, batch)`` defaults to classification cross entropy on
    ``batch={'x', 'y'}``. With a mesh, the batch is constrained onto the DP
    axes so GSPMD shards compute and allreduces grads over ICI;
    ``seq_axis=True`` additionally keeps the sequence dim on the ring axis
    — long-context batches fed via ``global_batch(..., seq_axis=True)``
    were being re-constrained OFF the ring axis inside the step before
    this kwarg existed. ``apply_kwargs_of(batch)`` feeds extra kwargs to
    the model (e.g. ``targets`` for a model with a fused head+loss —
    ``loss_of`` then receives the model's scalar loss as its first
    argument).

    What the backward keeps of a remat'd block instead of recomputing it
    is settled at the first call, from the compiled step's memory against
    the device's (:class:`tony_tpu.remat.ChosenStep`, which is what comes
    back: call it, ``lower`` it or ``trace`` it like the jitted step). On
    a backend that reports no memory limit (the CPU) nothing is kept.
    """
    if loss_of is None:
        loss_of = lambda logits, batch: cross_entropy_loss(logits, batch["y"])

    def build(saved: remat.Saved):
        def step(state: TrainState, batch: Dict[str, jax.Array]):
            if mesh is not None:
                batch = jax.tree.map(
                    lambda x: jax.lax.with_sharding_constraint(
                        # The (batch, seq) spec is rank-2: rank-1 leaves
                        # (labels, weights) take the plain batch sharding.
                        x, par.batch_sharding(
                            mesh, seq_axis=seq_axis and x.ndim >= 2)), batch)

            def loss_fn(params):
                extra = apply_kwargs_of(batch) if apply_kwargs_of else {}
                with nn.logical_axis_rules(rules), saved:
                    # mutable="losses": models that sow auxiliary objectives
                    # (e.g. the MoE load-balancing loss) contribute them here;
                    # dense models return an empty collection.
                    # ``stats``: what a model sows to be read, not
                    # trained on (a dropless expert layer's row counts);
                    # it comes back as ``metrics["stats"]``.
                    logits, sown = state.apply_fn(
                        {"params": params}, batch["x"],
                        mutable=["losses", "stats"], **extra)
                aux = sum((leaf.sum() for leaf in
                           jax.tree.leaves(sown.get("losses", {}))),
                          start=jnp.float32(0.0))
                return loss_of(logits, batch) + aux, (
                    aux, sown.get("stats", {}))

            (loss, (aux, stats)), grads = jax.value_and_grad(
                loss_fn, has_aux=True)(state.params)
            with jax.named_scope("optimizer"):
                new_state = state.apply_gradients(grads=grads)
                gnorm = optax.global_norm(grads)
            metrics = {"loss": loss, "grad_norm": gnorm, "aux_loss": aux}
            if jax.tree.leaves(stats):
                metrics["stats"] = stats
            return new_state, metrics

        return jax.jit(step, donate_argnums=(0,) if donate else ())

    return remat.ChosenStep(build, mesh, memo_extra={
        "donate": donate, "seq_axis": seq_axis})


def make_accum_train_step(loss_of: Callable[[jax.Array,
                                             Dict[str, jax.Array]],
                                            jax.Array] = None,
                          mesh: Mesh = None,
                          *,
                          microbatches: int,
                          bucket_bytes: int = overlap.DEFAULT_BUCKET_BYTES,
                          reduce_op: str = "all_reduce",
                          hierarchy: str = "auto",
                          gather: str = "bucketed",
                          prefetch: int = 1,
                          update: str = "optax",
                          quant: bool = False,
                          donate: bool = True,
                          apply_kwargs_of: Optional[Callable[
                              [Dict[str, jax.Array]],
                              Dict[str, Any]]] = None,
                          aot_cache: Optional[Any] = None):
    """Microbatched-accumulation train step with bucketed gradient sync —
    the comm/compute-overlap counterpart of :func:`make_train_step`.

    Same ``(state, batch) -> (state, metrics)`` contract and numerics
    (loss/grads match the monolithic step to fp reassociation), but the
    local batch is split into ``microbatches`` inside one ``lax.scan`` and
    the gradient reduction is issued per size-targeted bucket as each
    microbatch's backward finishes —
    :func:`tony_tpu.parallel.overlap.microbatch_grads` is the engine;
    :func:`~tony_tpu.runtime.jax_runtime.overlap_xla_flags` supplies the XLA
    knobs that turn the structure into actual overlap on TPU.

    The parameter layout is detected from the state's committed shardings
    per call (:func:`~tony_tpu.parallel.overlap.fsdp_param_specs`):

    * replicated params → the pure-DP path (grads replicated);
    * fsdp-sharded params (ZeRO-3, e.g. from ``create_train_state`` on an
      ``fsdp > 1`` mesh) → grads are ``psum_scatter``-ed straight into the
      shard layout and ``apply_gradients``/``global_norm`` run on sharded
      grads — replicated gradients never materialize. The forward param
      ``all_gather``s are bucketed + prefetched by the collective
      scheduler by default (``gather="bucketed"``, ``prefetch=k`` — see
      :class:`tony_tpu.parallel.sched.GatherPlan`); ``gather="per_leaf"``
      keeps the pre-scheduler path as the bit-exact numerics pin.

    On a multi-slice mesh (``MeshSpec(slices=...)``) the reduce is
    hierarchical by default: per-bucket ``psum_scatter`` over ICI, then a
    per-bucket DCN allreduce inside the scan (``hierarchy="flat"`` forces
    the single-level reduce — the numerics pin). The model must be
    collective-free inside (same contract as ``gpipe``'s ``stage_fn``).

    ``update`` selects the optimizer path: ``"optax"`` (default — the
    reduced grads unpack to leaves and ``state.apply_gradients`` runs
    optax's per-leaf update) or ``"fused_bucket"`` — the state's ``tx``
    must be a :class:`tony_tpu.ops.fused_optim.FusedOptimizer` and its
    opt state bucket-resident (``create_train_state`` builds it): the
    update then runs INSIDE the accum region as one fused kernel per
    bucket buffer, straight off the scan's reduce accumulators — grads
    never re-materialize as a leaf pytree, scatter buckets never leave
    the shard layout, and the reported ``grad_norm`` is the bucket-major
    fused reduction (per-leaf value up to fp reassociation). The bucket
    plan is the tx's (``bucket_bytes`` on the FusedOptimizer — the
    ``bucket_bytes`` argument here must agree, it sized the opt state).

    ``quant=True`` switches the ZeRO-3 forward param gathers to the
    quantized int8 wire format (:mod:`tony_tpu.ops.quant`): the state
    must be a :class:`~tony_tpu.ops.quant.QuantTrainState` (attach with
    ``quant.with_gather_quant``) whose delayed-scaling amax histories
    ride the step — f32 master params and the scatter-bucket gradient
    reduce are untouched; only the forward gather bytes shrink (4× for
    f32 params). Requires ``gather="bucketed"``; composes with both
    ``update`` modes. The loss-pin gate in ``tests/test_quant.py`` is
    the numerics contract for this knob.
    """
    if mesh is None:
        raise ValueError("make_accum_train_step requires a mesh: the "
                         "bucketed reduction IS the cross-device sync")
    if update not in ("optax", "fused_bucket"):
        raise ValueError(f"unknown update mode {update!r} "
                         "(optax|fused_bucket)")
    if quant and gather != "bucketed":
        raise ValueError(
            "quant=True quantizes the BUCKETED gather wire format; "
            f"gather={gather!r} has no bucket boundary to quantize at")
    if loss_of is None:
        loss_of = lambda logits, batch: cross_entropy_loss(logits, batch["y"])

    def build(param_specs):
        def step(state: TrainState, batch: Dict[str, jax.Array]):
            def loss_fn(params, mb):
                extra = apply_kwargs_of(mb) if apply_kwargs_of else {}
                # No logical_axis_rules scope: inside the manually-sharded
                # region GSPMD constraints don't apply (with no rules
                # active, flax's with_logical_constraint is a no-op).
                logits, sown = state.apply_fn(
                    {"params": params}, mb["x"], mutable="losses", **extra)
                aux = sum((leaf.sum() for leaf in
                           jax.tree.leaves(sown.get("losses", {}))),
                          start=jnp.float32(0.0))
                return loss_of(logits, mb) + aux, aux

            qamax = state.quant_state["amax"] if quant else None
            if update == "fused_bucket":
                # Bucket-major end to end: the optimizer update runs in
                # the accum region on the scan's reduce accumulators —
                # one fused kernel per bucket, grad norm included.
                count_inc = state.opt_state["count"] + 1
                scal = state.tx.scalars(count_inc)
                outs = overlap.microbatch_grads(
                    loss_fn, state.params, batch, mesh,
                    microbatches=microbatches,
                    bucket_bytes=state.tx.bucket_bytes,
                    reduce_op=reduce_op, has_aux=True,
                    param_specs=param_specs, hierarchy=hierarchy,
                    gather=gather, prefetch=prefetch,
                    fused=state.tx,
                    opt_slots=state.opt_state["slots"],
                    opt_scal=scal, quant_amax=qamax)
                loss, aux, new_params, new_slots, gnorm = outs[:5]
                new_state = state.replace(
                    step=state.step + 1, params=new_params,
                    opt_state={"count": count_inc, "slots": new_slots})
                if quant:
                    new_state = new_state.replace(
                        quant_state={"amax": outs[5]})
                return new_state, {"loss": loss, "grad_norm": gnorm,
                                   "aux_loss": aux}

            outs = overlap.microbatch_grads(
                loss_fn, state.params, batch, mesh,
                microbatches=microbatches, bucket_bytes=bucket_bytes,
                reduce_op=reduce_op, has_aux=True,
                param_specs=param_specs, hierarchy=hierarchy,
                gather=gather, prefetch=prefetch, quant_amax=qamax)
            loss, aux, grads = outs[:3]
            # ZeRO-3: grads carry the fsdp shard layout here, so the
            # optimizer update and the norm reduction below run shard-
            # local with GSPMD inserting only the tiny norm psum.
            with jax.named_scope("optimizer"):
                new_state = state.apply_gradients(grads=grads)
                gnorm = optax.global_norm(grads)
            if quant:
                new_state = new_state.replace(
                    quant_state={"amax": outs[3]})
            return new_state, {"loss": loss, "grad_norm": gnorm,
                               "aux_loss": aux}

        return jax.jit(step, donate_argnums=(0,) if donate else ())

    # Layout detection memoized on the params' (treedef, shardings): one
    # flatten + hash per step on the hit path — fsdp_param_specs' spec
    # normalization and the jit-key build run only when the layout
    # actually changes (in practice, once).
    jitted: Dict[Any, Any] = {}

    def _jitted_for(state):
        leaves, treedef = jax.tree.flatten(state.params)
        key = (treedef,
               tuple(getattr(l, "sharding", None) for l in leaves))
        if key not in jitted:
            jitted[key] = build(overlap.fsdp_param_specs(
                state.params, mesh))
        return jitted[key]

    # Cold-start plane (tony_tpu.ckpt.aot): the persisted-executable
    # memo parallel to `jitted` — the raw jit stays what `inspect`
    # hands the analysis plane, the compiled executable is what the
    # hot loop calls. Keyed by (layout key, batch aval key); the CACHE
    # key is the digest of the LOWERED module: the training step closes
    # over an arbitrary user loss_of, which no config fingerprint can
    # soundly capture — so this path traces always (cheap, and what a
    # gang restart pays anyway) and skips only XLA compilation (the
    # dominant cost). A changed loss body, flag, or topology changes
    # the lowered text and misses cleanly.
    compiled: Dict[Any, Any] = {}

    def _compiled_for(state, batch):
        import hashlib

        from tony_tpu.ckpt import aot

        fn = _jitted_for(state)
        pleaves, ptreedef = jax.tree.flatten(state.params)
        bleaves, btreedef = jax.tree.flatten(batch)
        # The memo must key on EVERY state leaf's sharding, not just
        # the params': step 1's output re-shards the optimizer state
        # (replicated init -> the step's out_shardings), and a stale
        # Compiled hard-fails on the mismatch where raw jit would
        # silently re-trace. The wider key re-lowers, the lowered-HLO
        # digest shifts, and the cache misses cleanly into a compile.
        key = ((ptreedef,
                tuple(getattr(l, "sharding", None)
                      for l in jax.tree.leaves(state))),
               (btreedef,
                tuple((tuple(l.shape), str(l.dtype),
                       str(getattr(l, "sharding", None)))
                      for l in bleaves)))
        if key in compiled:
            return compiled[key]
        low = fn.lower(state, batch)
        fp = aot.make_fingerprint(
            "train_step", mesh=mesh,
            geometry={"microbatches": int(microbatches),
                      "bucket_bytes": int(bucket_bytes),
                      "reduce_op": reduce_op, "hierarchy": hierarchy,
                      "gather": gather, "prefetch": int(prefetch),
                      "update": update, "quant": bool(quant),
                      "donate": bool(donate)},
            tree=state, batch=batch,
            extra={"hlo": hashlib.sha256(
                low.as_text().encode()).hexdigest()})
        # The state treedef's static aux (the optax tx) doesn't pickle,
        # so the entry stores no call trees; both sides of the call are
        # re-derived here, from THIS process's args and lowering.
        ex = aot_cache.get(
            fp,
            in_tree=jax.tree_util.tree_structure(((state, batch), {})),
            out_tree=jax.tree_util.tree_structure(low.out_info))
        if ex is None:
            ex = low.compile()
            aot_cache.put(fp, ex)
        compiled[key] = ex
        return ex

    def stepper(state, batch):
        if update == "fused_bucket":
            from tony_tpu.ops import fused_optim

            if not isinstance(state.tx, fused_optim.FusedOptimizer):
                raise ValueError(
                    "update='fused_bucket' needs a state whose tx is a "
                    "tony_tpu.ops.fused_optim.FusedOptimizer (build it "
                    f"with create_train_state), got {type(state.tx)}")
            if bucket_bytes != overlap.DEFAULT_BUCKET_BYTES \
                    and bucket_bytes != state.tx.bucket_bytes:
                raise ValueError(
                    f"update='fused_bucket': bucket_bytes={bucket_bytes} "
                    f"disagrees with the FusedOptimizer's "
                    f"{state.tx.bucket_bytes} — the tx's value sized the "
                    f"bucket-resident opt state and wins; set it there")
        if quant:
            from tony_tpu.ops import quant as quant_mod

            if not quant_mod.is_quant_state(state):
                raise ValueError(
                    "quant=True needs a QuantTrainState carrying the "
                    "delayed-scaling amax state — attach it with "
                    "tony_tpu.ops.quant.with_gather_quant(state, mesh)")
            bb = state.tx.bucket_bytes if update == "fused_bucket" \
                else bucket_bytes
            if state.qconfig.bucket_bytes != bb:
                raise ValueError(
                    f"quant=True: the state's QuantConfig.bucket_bytes="
                    f"{state.qconfig.bucket_bytes} disagrees with the "
                    f"step's {bb} — the amax histories were sized for a "
                    f"different bucket plan; rebuild with "
                    f"with_gather_quant(bucket_bytes={bb})")
        with jax.set_mesh(mesh):
            if aot_cache is not None:
                return _compiled_for(state, batch)(state, batch)
            return _jitted_for(state)(state, batch)

    def inspect(state):
        """Static-analysis hook: the jitted step this stepper would run
        for ``state``'s layout, plus the planner artifacts and config
        knobs it was built from — everything
        :func:`tony_tpu.analysis.analyze_accum_step` needs to audit the
        traced program against the plan it claims to execute. Plans come
        from :func:`~tony_tpu.parallel.overlap.step_plans`, the SAME
        derivation ``microbatch_grads`` uses, so the audit target can
        never drift from the step."""
        param_specs = overlap.fsdp_param_specs(state.params, mesh)
        bb = state.tx.bucket_bytes if update == "fused_bucket" \
            else bucket_bytes
        plan, gplan = overlap.step_plans(
            state.params, mesh, bucket_bytes=bb, param_specs=param_specs,
            prefetch=prefetch)
        return {"jitted": _jitted_for(state), "plan": plan,
                "gplan": gplan, "mesh": mesh, "update": update,
                "gather": gather, "reduce_op": reduce_op,
                "hierarchy": hierarchy, "donate": donate,
                "microbatches": microbatches, "bucket_bytes": bb,
                "param_specs": param_specs, "quant": quant,
                "fused": state.tx if update == "fused_bucket" else None}

    stepper.inspect = inspect
    return stepper


def shared_aot_cache(path: Optional[str] = None):
    """The gang-shared train AOT cache for ``make_accum_train_step
    (aot_cache=...)``, or ``None`` when the plane is unarmed — ``path``
    defaults from the ``TONY_TRAIN_AOT_CACHE`` env ``JAXRuntime``
    exports (``tony.train.aot-cache``), so a tony-submitted script arms
    it with one kwarg and runs unchanged everywhere else. Every worker
    opens the SAME durable directory: the first to lower a (mesh,
    geometry, lowered-HLO) fingerprint compiles and populates (put is
    stage-then-rename, first writer wins — concurrent gang mates race
    safely), the rest deserialize in milliseconds, and an elastic
    resize's re-gang stops paying a full recompile per topology change
    (the fingerprint keys the mesh, so each topology caches its own
    entry once)."""
    path = path or os.environ.get(constants.ENV_TRAIN_AOT_CACHE) or None
    if not path:
        return None
    from tony_tpu.ckpt.aot import AOTCache

    return AOTCache(path)


@contextlib.contextmanager
def _first_step():
    """The set-up span ``tony:first_step`` around the loop's first
    ``step_fn`` call, from the call to its return (no fence: what the
    device still has to do is the second step's wait). Its attrs are
    what the process built under it, by the counters' change — programs
    compiled or loaded and the seconds of each kind — and whether the
    step's residuals came from the memo (``from_memo``: absent where
    no ladder was walked or read). A cold start's rungs are its
    ``tony:remat_rung`` children."""
    before = profiler.counters()
    with profiler.span("tony:first_step") as sp:
        try:
            yield
        finally:
            after = profiler.counters()
            grew = lambda k: after.get(k, 0) - before.get(k, 0)
            sp.attrs.update(
                programs=int(grew("programs_compiled")
                             + grew("programs_loaded")),
                **{k: round(grew(k), 3) for k in
                   ("trace_s", "lower_s", "compile_s", "load_s")})
            if "remat:from_memo" in after:
                sp.attrs.update(from_memo=bool(after["remat:from_memo"]))


def train_loop(state: TrainState, step_fn: Callable[[TrainState, Any],
                                                    Tuple[TrainState, Any]],
               batches: Optional[Iterable[Any]] = None, *,
               data: Optional[Any] = None,
               ckpt_dir: Optional[str] = None,
               save_every: Optional[int] = None,
               keep: Optional[int] = None,
               restore_on_start: bool = True,
               mesh: Optional[Mesh] = None,
               save_final: bool = True,
               on_step: Optional[Callable[[int, Dict[str, Any]],
                                          None]] = None,
               drain_file: Optional[str] = None,
               publish_every: Optional[int] = None):
    """Drive ``step_fn`` over ``batches`` with integrated elastic
    checkpointing — the control-plane hook the gang-restart contract needs
    (``tony.am.retry-count``): attempt N+1 calls this exactly like attempt
    N did and resumes from the newest committed step automatically.

    ``ckpt_dir``/``save_every``/``keep`` default from the ``TONY_CKPT_*``
    env the JAXRuntime injects (``tony.ckpt.dir/every/keep``), so a
    tony-submitted job gets durable resume without touching its script;
    with no directory configured this is a plain fold over the batches.

    * ``restore_on_start``: restore the newest committed checkpoint into
      ``state`` before the first step (elastic: ``mesh`` maps the saved
      PartitionSpecs onto THIS attempt's topology when the state carries
      no committed shardings of its own); a no-op on the first attempt.
    * ``save_every=k``: async save (:class:`tony_tpu.ckpt
      .AsyncCheckpointer`) after every k-th step — the loop stalls only
      for the device→host snapshot, the commit overlaps later steps.
    * the executor reads the same directory and reports the last COMMITTED
      step to the AM over the heartbeat RPC, so the attempt log shows what
      a restart will resume from.

    ``data=`` attaches a framework-owned input iterator
    (:class:`tony_tpu.data.DeviceIterator` / ``PipelineIterator`` — any
    iterable with ``state()``/``restore()``) instead of ``batches``: the
    pipeline cursor is then saved INSIDE the same committed step as the
    train state (one atomic commit for both — see
    :mod:`tony_tpu.data.ckptio`) and restored with it, so a resumed run's
    example stream is element-identical to an uninterrupted one, even
    when the gang restarts with a different host count (the cursor is
    global; the new ShardSpecs re-slice it). A bare pre-data checkpoint
    restores the model alone and the stream starts from the iterator's
    current position.

    ``drain_file`` (default: the ``TONY_DRAIN_FILE`` env the executor
    injects) is the elastic-resize drain flag: the loop polls for it
    between steps, and when it appears commits model + data cursor
    SYNCHRONOUSLY (the resize controller may only re-gang against a
    durable manifest) and exits with ``SystemExit(EXIT_DRAINED)`` — the
    executor reports that code and the AM records the worker DRAINED,
    not failed.

    ``publish_every=n`` (default: the ``TONY_PUBLISH_EVERY`` env from
    ``tony.publish.every``) is the continuous-publication knob
    (:mod:`tony_tpu.publish`): after every n-th periodic save — and the
    final save — process 0 waits out the async COMMIT (the pointer may
    only ever name a manifest a restore can land) and advances the ckpt
    root's versioned ``published.json`` pointer through stage-and-
    rename. The executor announces the pointer on its heartbeat and the
    AM's follow mode rolls the serving fleet onto it, so a training
    gang continuously feeds the replicas it shares a control plane
    with — no manual checkpoint copying.

    On a profiler trace the loop shows as ``train_step`` (a
    ``StepTraceAnnotation`` around each ``step_fn`` call),
    ``train:next_batch``, ``train:on_step``, ``train:save`` and, with a
    drain file, ``train:drain_poll``; the restore is the set-up span
    ``tony:restore``, the first ``step_fn`` call — the one that traces,
    lowers and compiles or loads the step — the set-up span
    ``tony:first_step`` with what it built as attrs, and the snapshot
    stalls add up in the counters ``saves`` / ``save_stall_s``
    (:mod:`tony_tpu.profiler`).

    Returns ``(state, last_metrics)``.
    """
    from tony_tpu import ckpt as ckpt_mod

    if (batches is None) == (data is None):
        raise ValueError("train_loop needs exactly one of batches= or "
                         "data=")
    if data is not None:
        batches = data
    stateful_data = (data is not None and hasattr(data, "state")
                     and hasattr(data, "restore"))
    if ckpt_dir is None:
        ckpt_dir = os.environ.get(constants.ENV_CKPT_DIR) or None
    if save_every is None:
        save_every = int(os.environ.get(constants.ENV_CKPT_EVERY, "0")
                         or 0)
    if keep is None:
        keep = int(os.environ.get(constants.ENV_CKPT_KEEP, "3") or 3)
    if drain_file is None:
        drain_file = os.environ.get(constants.ENV_DRAIN_FILE) or None
    if publish_every is None:
        publish_every = int(os.environ.get(constants.ENV_PUBLISH_EVERY,
                                           "0") or 0)
    mgr = None
    if ckpt_dir:
        from tony_tpu.data import ckptio

        mgr = ckpt_mod.AsyncCheckpointer(ckpt_dir, keep=keep)
        if restore_on_start:
            with profiler.span("tony:restore") as sp:
                latest = ckpt_mod.latest_step(ckpt_dir)
                if latest is not None and ckptio.has_iter_state(ckpt_dir,
                                                               latest):
                    # Wrapped {model, data_iter} checkpoint: unwrap keyed
                    # on what the manifest CONTAINS, not on what this
                    # caller passed — a batches= run restoring a data=
                    # run's save must still get the model (the strict-mode
                    # tree-mismatch KeyError it would otherwise hit reads
                    # like a wrong model, not a wrapped checkpoint).
                    # encode/decode_portable: planes with topology-bound
                    # live state (the fused optimizer's bucket-resident
                    # moments) restore through their portable leaf-major
                    # form and are re-bound to THIS attempt's topology;
                    # identity for everything else.
                    state = ckpt_mod.decode_portable(
                        ckpt_mod.restore_pytree(
                            ckpt_dir,
                            {ckptio.MODEL_KEY:
                             ckpt_mod.encode_portable(state)},
                            step=latest, mesh=mesh)[ckptio.MODEL_KEY], mesh)
                    if stateful_data:
                        data.restore(
                            ckptio.load_iter_state(ckpt_dir, latest))
                    else:
                        _log.warning(
                            "checkpoint step %d carries data-iterator "
                            "state but this train_loop has no stateful "
                            "data=; the model resumes, the input stream "
                            "starts from the beginning", latest)
                else:
                    state = ckpt_mod.decode_portable(
                        ckpt_mod.restore_latest(
                            ckpt_dir, ckpt_mod.encode_portable(state),
                            mesh=mesh), mesh)
                # step None: looked, found nothing to resume from.
                sp.attrs.update(step=latest, bytes=0 if latest is None
                                else sum(getattr(x, "nbytes", 0) for x in
                                         jax.tree.leaves(state)))

    def payload():
        # Saves go through the same portable codec: manifests carry the
        # topology-independent form (fused opt state leaf-major), so any
        # future attempt's topology can restore them.
        st = ckpt_mod.encode_portable(state)
        if stateful_data:
            return ckptio.wrap_for_save(st, data.state())
        return st

    metrics: Dict[str, Any] = {}
    done = 0
    saved_at: Optional[int] = None
    saves = 0
    published_step: Optional[int] = None

    def maybe_publish(step: int) -> None:
        # Continuous publication: the pointer may only advance over a
        # COMMITTED manifest, so the async save queue drains first
        # (wait() also re-raises any pending writer failure — a broken
        # commit must never be published). One writer per gang: only
        # process 0 advances the pointer, after every process's shards
        # are inside the commit by the wait barrier.
        nonlocal published_step
        if not publish_every or mgr is None or step == published_step:
            return
        from tony_tpu import publish as publish_mod

        mgr.wait()
        if jax.process_index() == 0:
            publish_mod.publish_step(ckpt_dir, step)
        published_step = step

    def save(step: int) -> None:
        # What the loop pays for a save is the snapshot stall (slot wait +
        # device->host extract); the commit overlaps later steps.
        t0 = time.perf_counter()
        with profiler.span("train:save", step=step):
            mgr.save(payload(), step=step)
        profiler.count("save_stall_s", time.perf_counter() - t0)
        profiler.count("saves")

    end = object()
    feed = iter(batches)
    try:
        while True:
            with profiler.span("train:next_batch"):
                batch = next(feed, end)
            if batch is end:
                break
            # One call site for every step: the call stack above a Pallas
            # kernel is part of its compile-cache key.
            with jax.profiler.StepTraceAnnotation("train_step",
                                                  step_num=done), \
                    (_first_step() if done == 0
                     else contextlib.nullcontext()):
                state, metrics = step_fn(state, batch)
            done += 1
            if done == 1:
                # End of set-up: the first step is traced, lowered and
                # compiled (or loaded) by now, and every build is on the
                # timeline.
                profiler.write_timeline()
            chaos.kill_point(done)
            if on_step is not None:
                with profiler.span("train:on_step"):
                    on_step(done, metrics)
            if mgr is not None and save_every and done % save_every == 0:
                saved_at = int(jax.device_get(state.step)) \
                    if hasattr(state, "step") else done
                save(saved_at)
                saves += 1
                if publish_every and saves % publish_every == 0:
                    maybe_publish(saved_at)
            if drain_file is not None:
                with profiler.span("train:drain_poll"):
                    draining = os.path.exists(drain_file)
                if not draining:
                    continue
                # Drain directive (elastic resize): commit model + cursor
                # SYNCHRONOUSLY — wait() both drains the async queue and
                # re-raises any pending writer failure, so EXIT_DRAINED
                # is only ever reported over a durable manifest.
                if mgr is not None:
                    here = int(jax.device_get(state.step)) \
                        if hasattr(state, "step") else done
                    if here != saved_at:
                        save(here)
                    mgr.wait()
                raise SystemExit(constants.EXIT_DRAINED)
        if mgr is not None and save_final and done:
            final = int(jax.device_get(state.step)) \
                if hasattr(state, "step") else done
            if final != saved_at:
                save(final)
            maybe_publish(final)
        if mgr is not None:
            mgr.wait()
    finally:
        if mgr is not None:
            mgr.close()
        # The loop owns the iteration: release the prefetch thread and
        # its staged device batches even when step_fn raises (close() is
        # idempotent and state() still reads the delivered cursor after).
        if data is not None and hasattr(data, "close"):
            data.close()
        profiler.write_timeline()
    return state, metrics


def train_stats_writer(path: Optional[str] = None, *,
                       flops_per_step: float = 0.0,
                       peak_flops: float = 0.0
                       ) -> Callable[[int, Dict[str, Any]], None]:
    """An ``on_step`` callback for :func:`train_loop` that publishes
    per-step cost telemetry — wall time, collective bytes (summed from
    ``tony_tpu.profiler.report("collective")``'s planned per-issue
    payloads), and an MFU estimate (``flops_per_step / (step_time *
    peak_flops)`` when both are given) — to the executor's stats file
    through the atomic stage-and-rename idiom (tmp + ``os.replace``,
    the serve engine's ``write_stats`` contract). The executor's
    heartbeat loop piggybacks the file to the AM unchanged, where the
    history plane logs each window as a TRAIN_STEP event: one writer,
    one schema, no second bookkeeping path.

    ``path`` defaults to the ``TONY_SERVE_STATS`` env the executor
    injects into every task; outside a tony-run task (no env, no
    explicit path) the callback is a no-op so scripts run unchanged."""
    import json as json_mod
    import time as time_mod

    target = path or os.environ.get(constants.ENV_SERVE_STATS)
    last = {"t": time_mod.monotonic()}

    def on_step(step: int, metrics: Dict[str, Any]) -> None:
        now = time_mod.monotonic()
        dt = now - last["t"]
        last["t"] = now
        if not target:
            return
        nbytes = 0.0
        try:
            for rec in profiler.report("collective").values():
                nbytes += float(sum(rec.get("nbytes") or ()))
        except Exception:
            pass                       # telemetry is advisory
        mfu = (flops_per_step / (dt * peak_flops)
               if flops_per_step > 0 and peak_flops > 0 and dt > 0
               else 0.0)
        payload = {"step": float(step), "step_time_s": float(dt),
                   "collective_bytes": nbytes, "mfu": float(mfu)}
        loss = metrics.get("loss") if isinstance(metrics, dict) else None
        if loss is not None:
            try:
                payload["loss"] = float(jax.device_get(loss))
            except (TypeError, ValueError):
                pass
        tmp = f"{target}.tmp.{os.getpid()}"
        try:
            with open(tmp, "w") as fh:
                json_mod.dump(payload, fh)
            os.replace(tmp, target)
        except OSError:
            pass                       # advisory: never fail the step

    return on_step


def _validate_local_batch(mesh: Mesh, local_batch: Dict[str, Any],
                          seq_axis: bool = False) -> None:
    """Pre-flight the ``make_array_from_process_local_data`` contract and
    raise a ``ValueError`` NAMING the offending leaf — the raw failure is
    an opaque shape-assembly error deep inside jax. Checks (local-side
    proxies for "every process contributes the same local batch shape"):

    * every leaf is array-like with a batch dim, and all leaves agree on
      it (a per-process collective compare is impossible pre-assembly, but
      since every process runs this same check on the same contract, a
      divergent process fails by itself, by name);
    * the assembled global batch dim divides the mesh's batch sharding,
      and the local dim divides this process's share of it;
    * with ``seq_axis``, the (process-replicated) sequence dim divides the
      ring axis.
    """
    flat = jax.tree_util.tree_flatten_with_path(local_batch)[0]
    if not flat:
        return
    nproc = jax.process_count()
    spec0 = par.batch_sharding(mesh).spec[0]
    names = spec0 if isinstance(spec0, tuple) else (spec0,)
    n_shards = 1
    for a in names:
        n_shards *= mesh.shape[a]
    ref_path = ref_dim = None
    for path, leaf in flat:
        name = jax.tree_util.keystr(path)
        if not hasattr(leaf, "shape") or np.ndim(leaf) == 0:
            raise ValueError(
                f"global_batch leaf {name}: expected an array with a "
                f"leading batch dim, got {type(leaf).__name__} of rank "
                f"{np.ndim(leaf)}")
        dim = int(np.shape(leaf)[0])
        if ref_dim is None:
            ref_path, ref_dim = name, dim
        elif dim != ref_dim:
            raise ValueError(
                f"global_batch leaf {name}: local batch dim {dim} != "
                f"{ref_dim} (leaf {ref_path}) — every leaf of every "
                f"process must contribute the same local batch count")
        if seq_axis and np.ndim(leaf) >= 2:
            seq = int(np.shape(leaf)[1])
            seq_shards = mesh.shape[par.SEQ]
            if seq % seq_shards:
                raise ValueError(
                    f"global_batch leaf {name}: sequence dim {seq} not "
                    f"divisible by the {seq_shards}-way ring axis "
                    f"({par.SEQ!r}) of the mesh")
    global_dim = ref_dim * nproc
    if global_dim % n_shards:
        raise ValueError(
            f"global_batch leaf {ref_path}: local batch dim {ref_dim} x "
            f"{nproc} process(es) = global {global_dim}, not divisible by "
            f"the {n_shards}-way batch sharding {tuple(names)} of the "
            f"mesh — pad or resize the per-process batch")
    if n_shards % nproc == 0:
        per_proc = n_shards // nproc
        if per_proc and ref_dim % per_proc:
            raise ValueError(
                f"global_batch leaf {ref_path}: local batch dim {ref_dim} "
                f"not divisible by this process's {per_proc} addressable "
                f"batch shard(s) ({n_shards}-way sharding over {nproc} "
                f"process(es))")


# Contracts already validated, mesh → {(seq_axis, treedef, leaf shapes)}:
# the shape contract is invariant per pipeline, so per-step callers pay
# the full pre-flight once, not every step. Only successes are cached —
# a bad contract re-raises on every call. Weakly keyed so cached meshes
# are released with their last outside reference; per-mesh bound as a
# backstop against pathological ever-changing shapes (when full,
# validation just runs).
_VALIDATED_CONTRACTS: "weakref.WeakKeyDictionary" = weakref.WeakKeyDictionary()
_VALIDATED_CONTRACTS_MAX = 256


def global_batch(mesh: Mesh, local_batch: Dict[str, Any],
                 seq_axis: bool = False,
                 check: bool = True) -> Dict[str, jax.Array]:
    """Assemble the logically-global batch from this process's local shard —
    every process calls this with its own slice (multi-host feeding).
    ``check`` pre-flights the shape contract with a leaf-naming
    ``ValueError`` instead of jax's opaque assembly failure (memoized per
    (mesh, treedef, leaf-shape) contract, so the per-step cost is one
    flatten + set lookup)."""
    if check:
        leaves, treedef = jax.tree_util.tree_flatten(local_batch)
        key = (seq_axis, treedef, tuple(np.shape(l) for l in leaves))
        seen = _VALIDATED_CONTRACTS.setdefault(mesh, set())
        if key not in seen:
            _validate_local_batch(mesh, local_batch, seq_axis=seq_axis)
            if len(seen) < _VALIDATED_CONTRACTS_MAX:
                seen.add(key)

    def put(x):
        # Rank-1 leaves (labels, weights) can't carry the seq dim.
        sharding = par.batch_sharding(
            mesh, seq_axis=seq_axis and x.ndim >= 2)
        return jax.make_array_from_process_local_data(sharding, x)
    return jax.tree.map(put, local_batch)
