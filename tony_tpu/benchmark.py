"""Shared ResNet-50 benchmark core: the exact step, measurement protocol,
and MFU accounting used by ``bench.py`` — importable so the same number can
be produced INSIDE a ``tony submit`` job (BASELINE.md measures the north
star "via tony-submit", not via a bare script; see
``examples/resnet_bench_job``).

Protocol (ROOFLINE.md): the timed window is ONE jitted ``lax.scan`` over
``steps`` train steps (one dispatch per window, not per step); each window
ends in a device→host readback of the loss AND a param leaf, which waits
for the device like ``block_until_ready`` does; best window of N wins.
"""

from __future__ import annotations

import functools
import os
import time

import jax
import jax.numpy as jnp

# Peak bf16 matmul FLOP/s per chip by generation (Google Cloud TPU
# documentation, per-generation system-architecture pages).
PEAK_BF16 = {
    "v4": 275e12,
    "v5e": 197e12,
    "v5p": 459e12,
    "v6e": 918e12,
}

# jax's ``device_kind`` → generation. "TPU v5 lite" is what the v5e
# machine reports (jax 0.9.0 / libtpu 0.0.34); the rest are the names
# jax's own Pallas TPU backend matches on.
GENERATION_BY_DEVICE_KIND = {
    "TPU v4": "v4",
    "TPU v5 lite": "v5e",
    "TPU v5e": "v5e",
    "TPU v5": "v5p",
    "TPU v5p": "v5p",
    "TPU v6 lite": "v6e",
    "TPU v6e": "v6e",
}


def chip_generation() -> str:
    """Generation of the chip jax is attached to. No chip, or a chip that
    is not in the table, is an error — never a default."""
    dev = jax.devices()[0]
    if dev.platform != "tpu":
        raise RuntimeError(
            f"no TPU attached (jax reports platform {dev.platform!r}): "
            f"device metrics are only measured on a chip")
    try:
        return GENERATION_BY_DEVICE_KIND[dev.device_kind]
    except KeyError:
        raise RuntimeError(
            f"device_kind {dev.device_kind!r} is not in "
            f"benchmark.GENERATION_BY_DEVICE_KIND; add it with its "
            f"published peak before reporting a utilization") from None


def best_window_time(window, carry, params_of, default_windows=4):
    """Run ``window(carry) -> (carry, loss)`` twice as warmup (compile +
    steady state), then best-of-N timed runs, each device→host fenced.
    Returns ``(best_seconds, carry, loss)``."""
    carry, loss = window(carry)
    float(loss)
    carry, loss = window(carry)
    float(loss)
    best = float("inf")
    for _ in range(int(os.environ.get("BENCH_WINDOWS",
                                      str(default_windows)))):
        t0 = time.perf_counter()
        carry, loss = window(carry)
        float(loss)
        float(jax.tree_util.tree_leaves(params_of(carry))[0].ravel()[0])
        best = min(best, time.perf_counter() - t0)
    return best, carry, loss


def resnet_window(batch: int, image: int, steps: int, *,
                  s2d: bool = True, fused_bn: bool = False):
    """(window, carry): the full ResNet-50 train step (fwd + bwd + SGD +
    BatchNorm stats) on synthetic ImageNet-shaped bf16 data, scanned
    ``steps`` times per dispatch."""
    import optax

    from tony_tpu import train as tr
    from tony_tpu.models import get_model

    model = get_model("resnet50", fused_bn=fused_bn, s2d_stem=s2d)
    kx, ky, kinit = jax.random.split(jax.random.PRNGKey(0), 3)
    x = jax.random.normal(kx, (batch, image, image, 3), jnp.bfloat16)
    y = jax.random.randint(ky, (batch,), 0, 1000)
    variables = jax.jit(lambda: model.init(kinit, x, train=False))()
    params, batch_stats = variables["params"], variables["batch_stats"]
    tx = optax.sgd(0.1, momentum=0.9)
    opt_state = jax.jit(tx.init)(params)

    def step(carry, _):
        params, opt_state, batch_stats = carry

        def loss_fn(p):
            logits, updates = model.apply(
                {"params": p, "batch_stats": batch_stats}, x, train=True,
                mutable=["batch_stats"])
            return tr.cross_entropy_loss(logits, y), updates["batch_stats"]

        (loss, new_stats), grads = jax.value_and_grad(
            loss_fn, has_aux=True)(params)
        updates, opt_state = tx.update(grads, opt_state, params)
        params = optax.apply_updates(params, updates)
        return (params, opt_state, new_stats), loss

    @functools.partial(jax.jit, donate_argnums=(0,))
    def window(carry):
        carry, losses = jax.lax.scan(step, carry, None, length=steps)
        return carry, losses[-1]

    return window, (params, opt_state, batch_stats)


def fsdp_shard_state(state, mesh):
    """Re-create a TrainState with params (and fresh optimizer state) in
    the ZeRO-3 layout: each param's first fsdp-divisible dim is sharded
    over the fsdp axis, the rest stay replicated — the manual analogue of
    what ``create_train_state`` produces for models carrying "embed"
    logical axes."""
    from flax.training.train_state import TrainState
    from jax.sharding import NamedSharding, PartitionSpec as P

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
    from tony_tpu.ops import fused_optim

    if isinstance(state.tx, fused_optim.FusedOptimizer):
        # Bucket-resident state is planned off committed shardings, so it
        # must be rebuilt AFTER the reshard, not GSPMD-propagated.
        return TrainState(step=0, apply_fn=state.apply_fn, params=params,
                          tx=state.tx,
                          opt_state=state.tx.init_state(params, mesh))
    return TrainState.create(apply_fn=state.apply_fn, params=params,
                             tx=state.tx)


def run_overlap_bench(*, batch: int | None = None, hidden: int = 512,
                      steps: int | None = None, microbatches: int = 4,
                      bucket_bytes: int = 1 << 20,
                      reduce_op: str = "all_reduce",
                      slices: int = 1, fsdp: int = 1,
                      zero3: bool = False, hierarchy: str = "auto",
                      on_tpu: bool | None = None) -> dict:
    """Overlap-engine leg: monolithic GSPMD step vs bucketed-accumulation
    step (``make_accum_train_step``) on a DP mesh over all local devices,
    same model / optimizer / data.

    ``slices=2`` builds a (host-simulated) multi-slice mesh and exercises
    the hierarchical ICI/DCN reduce; ``zero3=True`` (with ``fsdp>1``)
    shards the params so the accum step runs the psum_scatter-into-shard
    path. Reports both step times, the speedup, the bucket plan (count and
    per-bucket bytes — the numbers the latency-hiding scheduler pipelines,
    plus the per-level plan for hierarchical/ZeRO-3 runs), and the
    numerics deltas between the two paths: the bucketed step must match
    the monolithic step's loss and grad-norm within 1e-5 or the comparison
    is void (``numerics_ok`` gates the headline).
    """
    import optax

    from tony_tpu import parallel as par
    from tony_tpu import profiler
    from tony_tpu import train as tr
    from tony_tpu.models import get_model
    from tony_tpu.parallel import overlap

    if on_tpu is None:
        on_tpu = jax.default_backend() not in ("cpu",)
    if steps is None:
        steps = 20 if on_tpu else 4
    mesh = par.make_mesh(slices=slices, fsdp=fsdp)   # rest of devices: data
    dp = overlap.sync_size(mesh)
    if batch is None:
        batch = dp * microbatches * (16 if on_tpu else 4)
    model = get_model("mnist-mlp", hidden=hidden)
    kx, ky, kr = jax.random.split(jax.random.PRNGKey(0), 3)
    x = jax.random.normal(kx, (batch, 784), jnp.float32)
    y = jax.random.randint(ky, (batch,), 0, 10)
    data = {"x": x, "y": y}
    state = tr.create_train_state(model, optax.sgd(0.1, momentum=0.9),
                                  x, kr)
    if zero3:
        if fsdp <= 1:
            raise ValueError("zero3=True needs fsdp > 1")
        state = fsdp_shard_state(state, mesh)
        specs = overlap.fsdp_param_specs(state.params, mesh)
        plan = overlap.GradBuckets.plan_sharded(
            state.params, specs, shard_size=fsdp, bucket_bytes=bucket_bytes)
    else:
        plan = overlap.GradBuckets.plan(state.params, bucket_bytes)

    profiler.reset_overlap_records()
    mono = tr.make_train_step(mesh=mesh, donate=False)
    accum = tr.make_accum_train_step(
        mesh=mesh, microbatches=microbatches, bucket_bytes=bucket_bytes,
        reduce_op=reduce_op, hierarchy=hierarchy, donate=False)
    # Numerics pin first, from the identical initial state.
    _, m_mono = mono(state, data)
    _, m_accum = accum(state, data)
    loss_delta = abs(float(m_mono["loss"]) - float(m_accum["loss"]))
    gnorm_delta = abs(float(m_mono["grad_norm"])
                      - float(m_accum["grad_norm"]))

    def timed(step_fn):
        def window(st):
            metrics = None
            for _ in range(steps):
                st, metrics = step_fn(st, data)
            return st, metrics["loss"]
        best, _, _ = best_window_time(window, state,
                                      params_of=lambda s: s.params)
        return best / steps

    mono_s = timed(mono)
    accum_s = timed(accum)
    records = profiler.overlap_report()
    return {
        "metric": "overlap_bench",
        "mono_step_s": round(mono_s, 6),
        "accum_step_s": round(accum_s, 6),
        "speedup": round(mono_s / accum_s, 4) if accum_s else None,
        "microbatches": microbatches,
        "reduce_op": reduce_op,
        "slices": slices,
        "fsdp": fsdp,
        "zero3": zero3,
        "hierarchy": records.get("accum_step", {}).get("hierarchy",
                                                       hierarchy),
        "n_buckets": plan.n_buckets,
        "n_scatter_buckets": plan.n_scatter_buckets,
        "bucket_nbytes": list(plan.bucket_nbytes),
        "bucket_threshold": plan.threshold,
        "loss_delta": loss_delta,
        "grad_norm_delta": gnorm_delta,
        "numerics_ok": bool(loss_delta < 1e-5 and gnorm_delta < 1e-5),
        "overlap_records": records,
        "batch": batch,
        "dp": dp,
        "backend": jax.default_backend(),
    }


def run_overlap_sweep(bucket_bytes_list=(64 << 10, 256 << 10, 1 << 20,
                                         4 << 20),
                      **kw) -> dict:
    """Bucket-bytes sweep over :func:`run_overlap_bench` — the tuning
    curve for the planner threshold (ROADMAP: record in BENCH). Returns
    the per-threshold legs trimmed to the numbers that move."""
    legs = []
    for bb in bucket_bytes_list:
        r = run_overlap_bench(bucket_bytes=bb, **kw)
        legs.append({k: r[k] for k in (
            "bucket_threshold", "n_buckets", "n_scatter_buckets",
            "mono_step_s", "accum_step_s", "speedup", "numerics_ok")})
    return {
        "metric": "overlap_bucket_sweep",
        "slices": kw.get("slices", 1),
        "fsdp": kw.get("fsdp", 1),
        "zero3": kw.get("zero3", False),
        "backend": jax.default_backend(),
        "legs": legs,
    }


def run_sched_bench(*, leaves: int = 96, leaf_rows: int = 16,
                    leaf_cols: int = 64, fsdp: int | None = None,
                    bucket_bytes: int = 256 << 10, prefetch: int = 1,
                    microbatches: int = 4, a2a_chunks: int = 2,
                    steps: int | None = None,
                    on_tpu: bool | None = None) -> dict:
    """Collective-scheduler leg (tony_tpu.parallel.sched), three probes:

    1. **Forward gathers** — a ``leaves``-leaf fsdp-sharded param tree
       gathered per leaf (the pre-scheduler path) vs coalesced into
       shard-major byte-threshold buckets with prefetch chaining
       (:class:`~tony_tpu.parallel.sched.GatherPlan`). The gather-only
       step has nothing to hide under, so its wall time IS the exposed
       gather time; ``gather_2x_ok`` (bucketed ≥ 2× faster) gates the
       headline, and the gathered values are pinned bit-exact.
    2. **ZeRO-3 step numerics** — ``microbatch_grads`` with
       ``gather="bucketed"`` vs ``gather="per_leaf"`` on the same state:
       loss and every grad leaf must match BIT-exact (bucketing is pure
       data movement), plus both full accum-step times.
    3. **MoE a2a** — the GSPMD dispatch-einsum path vs the scheduler's
       explicit per-capacity-chunk ``all_to_all``
       (:func:`~tony_tpu.parallel.sched.moe_dispatch_ffn_combine`) on an
       ``ep`` mesh, output delta + step times. On the host-simulated mesh
       the a2a timing is directional; the numerics and the record schema
       are the CPU-verifiable part.

    The unified ``profiler.collective_report()`` snapshot rides along so
    the bench JSON shows every collective the step issued.
    """
    import flax.linen as nn
    import numpy as np
    import optax
    from jax.sharding import NamedSharding, PartitionSpec as P

    from tony_tpu import parallel as par
    from tony_tpu import profiler
    from tony_tpu import train as tr
    from tony_tpu.compat import shard_map
    from tony_tpu.models import get_model
    from tony_tpu.models.moe import MoEMLP
    from tony_tpu.parallel import overlap, sched

    if on_tpu is None:
        on_tpu = jax.default_backend() not in ("cpu",)
    if steps is None:
        steps = 20 if on_tpu else 8
    n_dev = len(jax.devices())
    if fsdp is None:
        fsdp = 4 if n_dev % 4 == 0 else (2 if n_dev % 2 == 0 else 1)
    windows = int(os.environ.get("BENCH_WINDOWS", "3"))
    profiler.reset_collective_records()

    # --- leg 1: per-leaf vs bucketed+prefetched forward gathers --------
    mesh = par.make_mesh(fsdp=fsdp)
    keys = jax.random.split(jax.random.PRNGKey(0), leaves)
    params = {f"w{i:03d}": jax.random.normal(
        keys[i], (leaf_rows, leaf_cols), jnp.float32)
        for i in range(leaves)}
    specs = jax.tree.map(lambda _: P("fsdp"), params)
    params = jax.device_put(params, jax.tree.map(
        lambda _: NamedSharding(mesh, P("fsdp")), params))
    plan = overlap.GradBuckets.plan_sharded(
        params, specs, shard_size=fsdp, bucket_bytes=bucket_bytes)
    gplan = sched.GatherPlan.from_buckets(plan, prefetch=prefetch)

    def consume(leaves_full):
        # Touch every gathered element so no gather can be elided.
        return sum(l.sum() for l in leaves_full)

    def per_leaf_fn(p):
        def spmd(p):
            return consume([jax.lax.all_gather(l, "fsdp", axis=0,
                                               tiled=True)
                            for l in jax.tree.leaves(p)])
        return shard_map(spmd, mesh, in_specs=(specs,),
                         out_specs=P())(p)

    def bucketed_fn(p):
        def spmd(p):
            return consume(gplan.gather(jax.tree.leaves(p)))
        return shard_map(spmd, mesh, in_specs=(specs,),
                         out_specs=P())(p)

    def timed(fn, arg, jit=True):
        # One timing methodology per file: the shared best-of-N fenced
        # window harness (warmup + loss AND param-leaf readback fences).
        # jit=False for callables that are already jitted inside (the
        # accum stepper: its layout detection reads committed shardings
        # off the REAL leaves and must not be traced).
        f = jax.jit(fn) if jit else fn

        def window(carry):
            out = None
            for _ in range(steps):
                out = f(carry)
            return carry, out

        def first_array(c):
            # Fence on a device leaf (TrainState.step is a plain int).
            return next(l for l in jax.tree_util.tree_leaves(c)
                        if hasattr(l, "ravel"))

        best, _, _ = best_window_time(window, arg, params_of=first_array,
                                      default_windows=windows)
        return best / steps

    per_leaf_s = timed(per_leaf_fn, params)
    bucketed_s = timed(bucketed_fn, params)

    # Bit-exact pin on the gathered VALUES (bucketing is data movement).
    def gathered_values(use_plan):
        def spmd(p):
            ls = jax.tree.leaves(p)
            if use_plan:
                return gplan.gather(ls)
            return [jax.lax.all_gather(l, "fsdp", axis=0, tiled=True)
                    for l in ls]
        return jax.jit(shard_map(spmd, mesh, in_specs=(specs,),
                                 out_specs=[P()] * leaves))(params)

    gather_exact = all(
        np.array_equal(np.asarray(jax.device_get(a)),
                       np.asarray(jax.device_get(b)))
        for a, b in zip(gathered_values(True), gathered_values(False)))

    # --- leg 2: ZeRO-3 accum step, bucketed vs per-leaf gathers --------
    model = get_model("mnist-mlp", hidden=512)
    kx, ky, kr = jax.random.split(jax.random.PRNGKey(1), 3)
    dp = overlap.sync_size(mesh)
    batch_n = dp * microbatches * (16 if on_tpu else 4)
    x = jax.random.normal(kx, (batch_n, 784), jnp.float32)
    yb = jax.random.randint(ky, (batch_n,), 0, 10)
    data = {"x": x, "y": yb}
    state = fsdp_shard_state(
        tr.create_train_state(model, optax.sgd(0.1, momentum=0.9), x, kr),
        mesh)
    z_specs = overlap.fsdp_param_specs(state.params, mesh)

    def loss_fn(p, mb):
        logits = state.apply_fn({"params": p}, mb["x"])
        return tr.cross_entropy_loss(logits, mb["y"])

    grads_by_mode = {}
    for mode in ("bucketed", "per_leaf"):
        grads_by_mode[mode] = jax.jit(lambda p, b, m=mode: overlap.
                                      microbatch_grads(
                                          loss_fn, p, b, mesh,
                                          microbatches=microbatches,
                                          bucket_bytes=bucket_bytes,
                                          param_specs=z_specs, gather=m,
                                          prefetch=prefetch))(state.params,
                                                             data)
    (l_b, g_b), (l_p, g_p) = (grads_by_mode["bucketed"],
                              grads_by_mode["per_leaf"])
    zero3_exact = bool(float(l_b) == float(l_p)) and all(
        np.array_equal(np.asarray(jax.device_get(a)),
                       np.asarray(jax.device_get(b)))
        for a, b in zip(jax.tree.leaves(g_b), jax.tree.leaves(g_p)))

    step_s = {}
    for mode in ("bucketed", "per_leaf"):
        step_fn = tr.make_accum_train_step(
            mesh=mesh, microbatches=microbatches,
            bucket_bytes=bucket_bytes, gather=mode, prefetch=prefetch,
            donate=False)
        step_s[mode] = timed(
            lambda st, f=step_fn: f(st, data)[1]["loss"], state,
            jit=False)

    # --- leg 3: MoE a2a under the scheduler vs GSPMD default -----------
    moe = {}
    ep = 2 if n_dev % 2 == 0 else 1
    if ep > 1:
        mesh_e = par.make_mesh(ep=ep)
        b, t, d, f, e = (16 if on_tpu else 8), 16, 64, 128, 2 * ep
        xk = jax.random.normal(jax.random.PRNGKey(2), (b, t, d),
                               jnp.float32)
        layer = MoEMLP(dim=d, ffn_hidden=f, n_experts=e, top_k=2,
                       dtype=jnp.float32)
        variables = {"params": nn.unbox(
            layer.init(jax.random.PRNGKey(3), xk))["params"]}
        w_shard = {"params": {
            k: NamedSharding(mesh_e, P("expert"))
            if k.startswith("w_") and k != "w_router"
            else NamedSharding(mesh_e, P())
            for k in variables["params"]}}
        v_sh = jax.device_put(variables, w_shard)
        x_sh = jax.device_put(xk, par.batch_sharding(mesh_e))

        def gspmd_fn(v, xx):
            with nn.logical_axis_rules(par.RULES):
                return layer.apply(v, xx)

        layer_s = MoEMLP(dim=d, ffn_hidden=f, n_experts=e, top_k=2,
                         dtype=jnp.float32, explicit_a2a=True,
                         mesh=mesh_e, a2a_chunks=a2a_chunks)
        sched_fn = lambda v, xx: layer_s.apply(v, xx)
        y_g = jax.jit(gspmd_fn)(v_sh, x_sh)
        y_s = jax.jit(sched_fn)(v_sh, x_sh)
        moe = {
            "moe_gspmd_s": round(timed(lambda v: gspmd_fn(v, x_sh).sum(),
                                       v_sh), 6),
            "moe_sched_s": round(timed(lambda v: sched_fn(v, x_sh).sum(),
                                       v_sh), 6),
            "moe_a2a_chunks": a2a_chunks,
            "moe_delta": float(jnp.max(jnp.abs(
                jax.device_get(y_g) - jax.device_get(y_s)))),
        }
        moe["moe_numerics_ok"] = bool(moe["moe_delta"] < 1e-5)

    out = {
        "metric": "sched_bench",
        "gather_per_leaf_s": round(per_leaf_s, 6),
        "gather_bucketed_s": round(bucketed_s, 6),
        "gather_speedup": round(per_leaf_s / bucketed_s, 4)
        if bucketed_s else None,
        "gather_2x_ok": bool(bucketed_s and per_leaf_s >= 2 * bucketed_s),
        "gather_bitexact": bool(gather_exact),
        "n_leaves": leaves,
        "n_gather_buckets": gplan.n_gather_buckets,
        "gather_nbytes": list(gplan.gather_nbytes),
        "prefetch": prefetch,
        "zero3_step_bucketed_s": round(step_s["bucketed"], 6),
        "zero3_step_per_leaf_s": round(step_s["per_leaf"], 6),
        "zero3_bitexact": bool(zero3_exact),
        "fsdp": fsdp,
        "microbatches": microbatches,
        "bucket_threshold": bucket_bytes,
        "backend": jax.default_backend(),
        **moe,
        "collective_records": profiler.collective_report(),
    }
    return out


def _count_eqns(jaxpr) -> int:
    """Total jaxpr equation count, sub-jaxprs included — the dispatch-
    granularity proxy the optimizer legs report (per-leaf optax updates
    scale O(n_leaves), the fused plane O(n_buckets))."""
    n = len(jaxpr.eqns)
    for eq in jaxpr.eqns:
        for v in eq.params.values():
            vs = v if isinstance(v, (list, tuple)) else (v,)
            for x in vs:
                inner = getattr(x, "jaxpr", x)
                if hasattr(inner, "eqns"):
                    n += _count_eqns(inner)
    return n


def run_optim_bench(*, leaves: int = 192, leaf_rows: int = 16,
                    leaf_cols: int = 64, fsdp: int | None = None,
                    bucket_bytes: int = 256 << 10, rule: str = "adamw",
                    steps: int | None = None,
                    on_tpu: bool | None = None) -> dict:
    """Fused-optimizer leg (tony_tpu.ops.fused_optim): per-leaf optax
    updates vs the bucket-major fused update on a ``leaves``-leaf
    fsdp-sharded tree (the many-small-leaves regime where the per-leaf op
    soup is dispatch-bound — every leaf costs its own multiply/add chain
    while the fused plane issues one update per bucket buffer).

    Three numbers gate the headline: wall time per update (both paths
    jitted, donated, fenced best-of-N), the jaxpr equation counts (the
    O(n_leaves) vs O(n_buckets) claim, compiler-visible), and the f32
    numerics pin (the fused params must match optax BIT-exact — the same
    pin ``tests/test_fused_optim.py`` holds; ``numerics_ok`` gates the
    timing claim like every other leg).
    """
    import numpy as np
    import optax

    from tony_tpu import parallel as par
    from tony_tpu import profiler
    from tony_tpu.ops import fused_optim
    from tony_tpu.parallel import overlap

    if on_tpu is None:
        on_tpu = jax.default_backend() not in ("cpu",)
    if steps is None:
        steps = 20 if on_tpu else 10
    n_dev = len(jax.devices())
    if fsdp is None:
        fsdp = 4 if n_dev % 4 == 0 else (2 if n_dev % 2 == 0 else 1)
    windows = int(os.environ.get("BENCH_WINDOWS", "3"))
    mesh = par.make_mesh(fsdp=fsdp)
    from jax.sharding import NamedSharding, PartitionSpec as P

    keys = jax.random.split(jax.random.PRNGKey(0), 2 * leaves)
    params = {f"w{i:03d}": jax.random.normal(
        keys[i], (leaf_rows, leaf_cols), jnp.float32)
        for i in range(leaves)}
    shardings = jax.tree.map(
        lambda _: NamedSharding(mesh, P("fsdp")), params)
    params = jax.device_put(params, shardings)
    grads = jax.device_put(
        {k: jax.random.normal(keys[leaves + i],
                              (leaf_rows, leaf_cols), jnp.float32) * 1e-2
         for i, k in enumerate(params)}, shardings)
    specs = overlap.fsdp_param_specs(params, mesh)

    fused = fused_optim.FusedOptimizer(
        rule=rule, lr=1e-3, weight_decay=1e-2, bucket_bytes=bucket_bytes)
    plan = fused.plan_for(params, mesh)
    profiler.reset_update_records()
    opt0 = fused.init_state(params, mesh, plan=plan)

    tx = optax.adamw(1e-3, weight_decay=1e-2) if rule == "adamw" \
        else optax.sgd(1e-3, momentum=0.9)
    # Leaf-major optax state in the params' layout (GSPMD-propagated, as
    # apply_gradients would hold it).
    oopt0 = jax.jit(tx.init)(params)

    def fused_fn(p, s):
        new_p, new_s, _ = fused_optim.fused_update_step(
            fused, p, grads, s, mesh, plan=plan, param_specs=specs)
        return new_p, new_s

    def optax_fn(p, s):
        u, s2 = tx.update(grads, s, p)
        return optax.apply_updates(p, u), s2

    fused_jit = jax.jit(fused_fn, donate_argnums=(0, 1))
    optax_jit = jax.jit(optax_fn, donate_argnums=(0, 1))

    # Numerics pin before the timed (donating) runs.
    fp, _ = jax.jit(fused_fn)(params, opt0)
    op, _ = jax.jit(optax_fn)(params, oopt0)
    exact = all(np.array_equal(np.asarray(jax.device_get(a)),
                               np.asarray(jax.device_get(b)))
                for a, b in zip(jax.tree.leaves(fp), jax.tree.leaves(op)))

    eqns = {
        "fused": _count_eqns(jax.make_jaxpr(fused_fn)(params, opt0).jaxpr),
        "optax": _count_eqns(jax.make_jaxpr(optax_fn)(params, oopt0).jaxpr),
    }

    def timed(step_jit, p, s):
        def window(carry):
            p, s = carry
            for _ in range(steps):
                p, s = step_jit(p, s)
            return (p, s), jax.tree.leaves(p)[0].ravel()[0]

        best, _, _ = best_window_time(
            window, (p, s),
            params_of=lambda c: jax.tree.leaves(c[0])[0],
            default_windows=windows)
        return best / steps

    # Fresh device trees per timed leg: the jitted steps donate their
    # inputs, so the originals are dead after the first call.
    host_p = jax.device_get(params)
    p_f = jax.device_put(host_p, shardings)
    fused_s = timed(fused_jit, p_f, fused.init_state(p_f, mesh, plan=plan))
    p_o = jax.device_put(host_p, shardings)
    optax_s = timed(optax_jit, p_o, jax.jit(tx.init)(p_o))
    return {
        "metric": "optim_bench",
        "rule": rule,
        "optax_update_s": round(optax_s, 6),
        "fused_update_s": round(fused_s, 6),
        "speedup": round(optax_s / fused_s, 4) if fused_s else None,
        "n_leaves": leaves,
        "n_buckets": plan.n_buckets,
        "n_scatter_buckets": plan.n_scatter_buckets,
        "bucket_nbytes": list(plan.bucket_nbytes),
        "bucket_threshold": bucket_bytes,
        "optax_jaxpr_eqns": eqns["optax"],
        "fused_jaxpr_eqns": eqns["fused"],
        "numerics_ok": bool(exact),
        "fsdp": fsdp,
        "update_records": profiler.update_report(),
        "backend": jax.default_backend(),
    }


def run_ckpt_bench(*, hidden: int = 2048, steps: int = 4, saves: int = 3,
                   fsdp: int = 1, directory: str | None = None) -> dict:
    """Checkpoint-plane leg: blocking save wall time vs the stall an async
    save actually charges the train loop (slot wait + device→host extract;
    the serialize/fsync/commit overlaps subsequent steps on the writer
    thread). Same state, same directory tree, best-of-``saves`` each.

    ``fsdp > 1`` shards the state first so the saves exercise the shard-
    local write path (each process writes only its replica-0 chunks).
    The restore leg re-reads the last committed step and pins it bit-exact
    against the live state — a save that stalls less but restores wrong
    is not a checkpoint. ``overlap_ok`` (async stall < blocking save)
    gates the headline, mirroring ``numerics_ok`` in the overlap bench.
    """
    import shutil
    import tempfile
    from pathlib import Path

    import numpy as np
    import optax

    from tony_tpu import ckpt as ckpt_mod
    from tony_tpu import parallel as par
    from tony_tpu import profiler
    from tony_tpu import train as tr
    from tony_tpu.models import get_model

    mesh = par.make_mesh(fsdp=fsdp)
    dp = 1
    for a in mesh.axis_names:
        dp *= mesh.shape[a]
    batch = dp * 4
    model = get_model("mnist-mlp", hidden=hidden)
    kx, ky, kr = jax.random.split(jax.random.PRNGKey(0), 3)
    x = jax.random.normal(kx, (batch, 784), jnp.float32)
    y = jax.random.randint(ky, (batch,), 0, 10)
    data = {"x": x, "y": y}
    state = tr.create_train_state(model, optax.sgd(0.1, momentum=0.9),
                                  x, kr)
    if fsdp > 1:
        state = fsdp_shard_state(state, mesh)
    step = tr.make_train_step(mesh=mesh, donate=False)
    state, _ = step(state, data)            # warm the compile
    root = Path(directory) if directory else Path(tempfile.mkdtemp(
        prefix="tony-ckpt-bench-"))
    profiler.reset_ckpt_records()
    try:
        blocking = ckpt_mod.AsyncCheckpointer(root / "blocking", keep=2)
        blocking_s = []
        for i in range(saves):
            t0 = time.perf_counter()
            blocking.save(state, step=i + 1, block=True)
            blocking_s.append(time.perf_counter() - t0)
        blocking.close()
        profiler.record_ckpt("blocking_save", save_s=min(blocking_s),
                             nbytes=blocking.stats["nbytes"])

        async_c = ckpt_mod.AsyncCheckpointer(root / "async", keep=2)
        overlap_step_s = []
        for i in range(saves):
            async_c.save(state, step=i + 1)      # stall recorded inside
            t0 = time.perf_counter()             # steps riding the write
            for _ in range(steps):
                state, _ = step(state, data)
            jax.block_until_ready(state.params)
            overlap_step_s.append((time.perf_counter() - t0) / steps)
        async_c.wait()
        stall_s = min(async_c.stats["stall_s"])
        write_s = min(async_c.stats["write_s"])
        nbytes = async_c.stats["nbytes"]

        # Restore pin: save the CURRENT state once more (the earlier async
        # saves snapshotted older states) and require the committed step
        # to round-trip bit-exact through the elastic path (mesh-mapped
        # specs, no target shardings) — a save that stalls less but
        # restores wrong is not a checkpoint.
        async_c.save(state, step=saves + 1, block=True)
        abstract = jax.tree.map(
            lambda a: np.zeros(a.shape, a.dtype)
            if hasattr(a, "shape") else a, jax.device_get(state))
        restored = ckpt_mod.restore_pytree(root / "async", abstract,
                                           mesh=mesh)
        exact = all(
            np.array_equal(np.asarray(jax.device_get(a)),
                           np.asarray(jax.device_get(b)))
            for a, b in zip(jax.tree.leaves(restored),
                            jax.tree.leaves(state))
            if hasattr(b, "shape"))
        async_c.close()
    finally:
        if not directory:
            shutil.rmtree(root, ignore_errors=True)
    return {
        "metric": "ckpt_bench",
        "state_mb": round(nbytes / (1024 * 1024), 3),
        "blocking_save_s": round(min(blocking_s), 6),
        "async_stall_s": round(stall_s, 6),
        "async_write_s": round(write_s, 6),
        "stall_vs_blocking": round(stall_s / min(blocking_s), 4)
        if min(blocking_s) else None,
        "overlap_ok": bool(stall_s < min(blocking_s)),
        "restore_exact": bool(exact),
        "overlapped_step_s": round(min(overlap_step_s), 6),
        "saves": saves,
        "fsdp": fsdp,
        "ckpt_records": profiler.ckpt_report(),
        "backend": jax.default_backend(),
    }


def run_input_bench(*, steps: int = 24, global_batch: int = 32,
                    hidden: int = 512, examples: int = 512,
                    feed_latency_ms: float = 3.0,
                    depths: tuple = (0, 1, 2)) -> dict:
    """Input-plane leg (tony_tpu.data): per-step wait-on-data at prefetch
    depth 0/1/2 over the SAME deterministic pipeline and train step.

    The pipeline's map stage sleeps ``feed_latency_ms`` per batch —
    simulated feed LATENCY (disk seek / decode wait / remote read), the
    component prefetch can hide on any backend (a CPU-bound map would
    contend with the XLA step on CPU and say nothing about TPU). Depth 0
    pays the latency inside every ``next()``; depth >= 1 stages batches
    from the background thread while the device steps, so the measured
    wait collapses to the queue pop. ``stall_hidden`` (depth-1 wait under
    half the depth-0 wait) gates the headline, mirroring ``overlap_ok``
    in the ckpt bench.
    """
    import numpy as np
    import optax

    from tony_tpu import data as data_mod
    from tony_tpu import parallel as par
    from tony_tpu import profiler
    from tony_tpu import train as tr
    from tony_tpu.models import get_model

    mesh = par.make_mesh()
    model = get_model("mnist-mlp", hidden=hidden)
    kx, ky, kr = jax.random.split(jax.random.PRNGKey(0), 3)
    x0 = jax.random.normal(kx, (global_batch, 784), jnp.float32)
    state0 = tr.create_train_state(model, optax.sgd(0.1, momentum=0.9),
                                   x0, kr)
    step = tr.make_train_step(mesh=mesh, donate=False)
    xs = np.asarray(jax.random.normal(kx, (examples, 784), jnp.float32))
    ys = np.asarray(jax.random.randint(ky, (examples,), 0, 10))

    def slow_map(batch):
        time.sleep(feed_latency_ms / 1e3)
        return batch

    def make_iter(depth):
        ds = (data_mod.Dataset.from_arrays({"x": xs, "y": ys}, seed=0)
              .shuffle().repeat().batch(global_batch).map(slow_map))
        return data_mod.DeviceIterator(
            ds.iterator(data_mod.ShardSpec(0, 1)), mesh, depth=depth,
            tag=f"input_d{depth}")

    profiler.reset_input_records()
    out: dict = {"metric": "input_bench", "global_batch": global_batch,
                 "steps": steps, "feed_latency_ms": feed_latency_ms,
                 "backend": jax.default_backend()}
    per_depth = {}
    for depth in depths:
        it = make_iter(depth)
        state = state0
        try:
            # Warm: compile the step and (depth >= 1) fill the staging
            # queue before the timed window.
            state, _ = step(state, next(it))
            jax.block_until_ready(state.params)
            n_warm = it.stats["steps"]
            warm_wait_s = it.stats["wait_s_total"]
            t0 = time.perf_counter()
            for _ in range(steps):
                state, _ = step(state, next(it))
            jax.block_until_ready(state.params)
            wall = time.perf_counter() - t0
            n_timed = it.stats["steps"] - n_warm
            timed_wait_s = it.stats["wait_s_total"] - warm_wait_s
            per_depth[depth] = {
                "step_ms": round(1e3 * wall / steps, 3),
                "input_wait_ms": round(1e3 * timed_wait_s / n_timed, 3),
            }
        finally:
            it.close()
    out["per_depth"] = {str(k): v for k, v in per_depth.items()}
    d0 = per_depth.get(0, {}).get("input_wait_ms")
    d1 = per_depth.get(1, {}).get("input_wait_ms")
    out["input_stall_ms_depth0"] = d0
    out["input_stall_ms_depth1"] = d1
    out["input_stall_ms_depth2"] = \
        per_depth.get(2, {}).get("input_wait_ms")
    out["stall_hidden"] = bool(d0 is not None and d1 is not None
                               and d1 < 0.5 * d0)
    out["input_records"] = profiler.input_report()
    return out


def peak_flops() -> float:
    """THE peak-FLOPs rule for MFU accounting (single definition — every
    bench leg divides by this): the attached chip's bf16 peak. Raises
    without a chip or on an unknown one (:func:`chip_generation`)."""
    return PEAK_BF16[chip_generation()]


def run_resnet_bench(batch: int, image: int, steps: int, *,
                     s2d: bool = True, fused_bn: bool = False) -> dict:
    """Measure and return the headline dict (metric/value/vs_baseline…);
    every caller (bench.py AND the tony-submitted job) accounts MFU
    identically. Needs a chip: raises before measuring without one."""
    from tony_tpu.models.resnet import resnet50_flops

    gen = chip_generation()       # first: no chip, no measurement
    peak = PEAK_BF16[gen]
    window, carry = resnet_window(batch, image, steps, s2d=s2d,
                                  fused_bn=fused_bn)
    elapsed, carry, loss = best_window_time(window, carry,
                                            params_of=lambda c: c[0])
    images_per_sec = batch * steps / elapsed
    train_flops_per_step = 3 * resnet50_flops(batch, image)
    mfu = train_flops_per_step * steps / elapsed / peak
    return {
        "metric": "resnet50_mfu",
        "value": round(mfu, 4),
        "unit": "fraction_of_bf16_peak",
        "vs_baseline": round(mfu / 0.55, 4),
        "images_per_sec_per_chip": round(images_per_sec, 1),
        "batch": batch,
        "image": image,
        "backend": jax.default_backend(),
        "chip": gen,
        "fused_bn": fused_bn,
        "s2d_stem": s2d,
        "loss": float(loss),
    }


def run_quant_bench(*, m: int = 512, k: int = 1024, n: int = 1024,
                    steps: int | None = None,
                    on_tpu: bool | None = None) -> dict:
    """Quantized-lane leg (tony_tpu.ops.quant): three gated numbers.

    1. **Matmul wall time** — the int8×int8→int32+f32-rescale path vs the
       bf16 matmul at a projection-sized shape, both jitted and fenced
       best-of-N. On TPU metal the int8 MXU runs 2× bf16 peak
       (ROOFLINE.md §7); on the CPU simulation XLA has no int8 fast path,
       so the CPU number documents the dispatch overhead, not the win —
       ``quant_matmul_sim_note`` says so explicitly and the metal
       measurement rides the real-hardware debt list.
    2. **Quantize-on-gather bytes** — raw vs int8 wire bytes of the
       ZeRO-3 forward gathers from the live GatherPlan (the ≥2×-fewer-
       gather-bytes claim vs BENCH_r09's bucketed path; 4× for f32
       params), plus the bit-exactness pin (dequantized int8 gather ==
       quantize∘dequantize of the unquantized gather).
    3. **Loss pin** — a short quantized-gather accum training vs the
       unquantized one; the relative final-loss disagreement gates the
       byte claim the way ``numerics_ok`` gates every other leg.
    """
    import numpy as np
    import optax

    from tony_tpu import parallel as par
    from tony_tpu import profiler
    from tony_tpu import train as tr
    from tony_tpu.models import get_model
    from tony_tpu.ops import quant as q
    from tony_tpu.parallel import overlap

    if on_tpu is None:
        on_tpu = jax.default_backend() not in ("cpu",)
    if steps is None:
        steps = 20 if on_tpu else 8
    windows = int(os.environ.get("BENCH_WINDOWS", "3"))
    ks = jax.random.split(jax.random.PRNGKey(0), 2)
    x16 = jax.random.normal(ks[0], (m, k), jnp.bfloat16)
    w16 = jax.random.normal(ks[1], (k, n), jnp.bfloat16) * 0.2

    bf16_jit = jax.jit(lambda a, b: a @ b)
    quant_jit = jax.jit(functools.partial(q.quant_dot, impl=None))

    def timed(fn, *args):
        fn(*args).block_until_ready()          # compile
        fn(*args).block_until_ready()          # steady state
        best = float("inf")
        for _ in range(windows):
            t0 = time.perf_counter()
            for _ in range(steps):
                out = fn(*args)
            out.block_until_ready()
            best = min(best, time.perf_counter() - t0)
        return best / steps

    bf16_s = timed(bf16_jit, x16, w16)
    quant_s = timed(quant_jit, x16, w16)
    # Kernel-vs-fallback pin at a small shape (interpret mode compiles
    # the whole padded grid on CPU — keep it cheap).
    xs = jax.random.normal(ks[0], (33, 70), jnp.float32)
    ws = jax.random.normal(ks[1], (70, 130), jnp.float32)
    kernel_bitexact = bool(np.array_equal(
        np.asarray(q.quant_dot(xs, ws, impl="xla")),
        np.asarray(q.quant_dot(xs, ws, interpret=True))))

    # --- quantize-on-gather: bytes + exactness + loss pin -------------
    n_dev = len(jax.devices())
    fsdp = 4 if n_dev % 4 == 0 else (2 if n_dev % 2 == 0 else 1)
    out: dict = {
        "metric": "quant_bench",
        "matmul_m_k_n": [m, k, n],
        "bf16_matmul_s": round(bf16_s, 6),
        "quant_matmul_s": round(quant_s, 6),
        "quant_matmul_speedup": round(bf16_s / quant_s, 4)
        if quant_s else None,
        "quant_kernel_bitexact": kernel_bitexact,
        "backend": jax.default_backend(),
    }
    if not on_tpu:
        out["quant_matmul_sim_note"] = (
            "CPU simulation: XLA has no int8 matmul fast path, so the "
            "wall-time ratio here measures quantize/rescale overhead, "
            "not the MXU win — int8 doubles MXU peak on metal "
            "(ROOFLINE.md §7); measurement rides the real-hardware "
            "debt list (ROADMAP)")
    if fsdp < 2:
        return out

    mesh = par.make_mesh(fsdp=fsdp)
    model = get_model("mnist-mlp", hidden=64)
    kx, ky = jax.random.split(jax.random.PRNGKey(1))
    data = {"x": jax.random.normal(kx, (64, 784), jnp.float32),
            "y": jax.random.randint(ky, (64,), 0, 10)}
    bb = 1 << 15

    def fresh():
        return fsdp_shard_state(tr.create_train_state(
            model, optax.adamw(1e-3), data["x"], jax.random.PRNGKey(2)),
            mesh)

    profiler.reset_quant_records()
    sp = fresh()
    sq = q.with_gather_quant(fresh(), mesh, window=4, bucket_bytes=bb)
    specs = overlap.fsdp_param_specs(sq.params, mesh)
    plan, gplan = overlap.step_plans(sq.params, mesh, bucket_bytes=bb,
                                     param_specs=specs)
    raw = sum(gplan.gather_nbytes)
    int8 = sum(plan.bucket_numel[b] for b in gplan.gather_buckets)
    step_p = tr.make_accum_train_step(mesh=mesh, microbatches=4,
                                      bucket_bytes=bb, donate=False)
    step_q = tr.make_accum_train_step(mesh=mesh, microbatches=4,
                                      bucket_bytes=bb, quant=True,
                                      donate=False)
    for _ in range(steps):
        sp, mp = step_p(sp, data)
        sq, mq = step_q(sq, data)
    lp, lq = float(mp["loss"]), float(mq["loss"])
    out.update({
        "gather_raw_nbytes": raw,
        "gather_int8_nbytes": int8,
        "gather_bytes_ratio": round(raw / int8, 2) if int8 else None,
        "gather_2x_fewer_ok": bool(int8 and raw / int8 >= 2.0),
        "gather_roundtrip_bitexact": q.gather_roundtrip_exact(
            sq.params, mesh, bb),
        "losspin_steps": steps,
        "losspin_plain": round(lp, 6),
        "losspin_quant": round(lq, 6),
        "losspin_rel": round(abs(lq - lp) / lp, 6) if lp else None,
        "losspin_ok": bool(lp and abs(lq - lp) / lp < 0.02),
        "fsdp": fsdp,
        "quant_records": profiler.quant_report(),
    })
    return out


def _drive_serve_trace(eng, prompts, new_tokens, arrivals,
                       warm_prompts=None, tenants=None) -> dict:
    """The shared arrival-driven measurement loop of the serve, spec,
    and route bench legs — ONE implementation so the legs can claim
    "the same Poisson trace" structurally, not by parallel maintenance.
    Warms every jit shape the trace will hit (max_new_tokens=2 — the
    measured window times steady-state engine behavior, not compiles),
    snapshots every counter the caller reads (forwards, draft forwards,
    the speculation counters — the warm pass runs at forced depth
    min(k, remaining)=1 and must not dilute the per-depth numbers —
    and the route leg's prefill/prefix counters), then replays
    ``arrivals`` in wall time and reports tokens, latencies, and
    warm-excluded counter deltas. ``warm_prompts`` overrides the warm
    pass's prompts (the route leg warms with length-matched but
    token-scrambled prompts so the prefix cache's measured hit rate
    comes from the trace's OWN sharing, not from the warm pass having
    pre-published the very prompts under test). ``tenants`` tags each
    request's QoS class for the qos leg (the warm pass stays untagged —
    untagged requests bypass budgets, so warming never defers)."""
    import numpy as np

    from tony_tpu.serve import Request

    for i, p in enumerate(warm_prompts if warm_prompts is not None
                          else prompts):
        eng.submit(Request(rid=f"warm-{i}", tokens=p, max_new_tokens=2))
    eng.run()
    warm_forwards = eng.forwards
    warm_draft = getattr(getattr(eng, "draft", None), "forwards", 0)
    warm_spec = {k: getattr(eng, k, 0) for k in
                 ("spec_proposed", "spec_accepted", "spec_rounds",
                  "spec_tokens_out")}
    warm_route = {k: getattr(eng, k, 0) for k in
                  ("prefill_launches", "prefill_rows", "prefill_chunks",
                   "prefix_hit_blocks", "prefix_lookup_blocks")}
    warm_steps = eng._steps
    done: dict = {}
    i = 0
    t0 = time.perf_counter()
    while i < len(prompts) or eng.queue_depth or eng.running:
        now = time.perf_counter() - t0
        while i < len(prompts) and now >= arrivals[i]:
            eng.submit(Request(rid=f"r{i}", tokens=prompts[i],
                               max_new_tokens=new_tokens[i],
                               tenant=(None if tenants is None
                                       else tenants[i])))
            i += 1
        if not (eng.queue_depth or eng.running):
            time.sleep(max(0.0, arrivals[i] - now))
            continue
        for c in eng.step():
            done[c.rid] = c
    wall = time.perf_counter() - t0
    lats = sorted(c.latency_s for c in done.values())

    def pct(p):
        return lats[min(len(lats) - 1, int(p * (len(lats) - 1) + 0.5))]

    n_tokens = sum(len(c.tokens) for c in done.values())
    forwards = eng.forwards - warm_forwards
    out = {
        "tokens": {rid: c.tokens for rid, c in done.items()},
        "wall_s": wall,
        "tokens_per_s": n_tokens / wall,
        "p50_ms": 1e3 * pct(0.50),
        "p99_ms": 1e3 * pct(0.99),
        # Per-request latency map: the disagg leg slices the decode
        # floor out of a mixed floor+burst trace.
        "latency_ms": {rid: 1e3 * c.latency_s
                       for rid, c in done.items()},
        "forwards": forwards,
        "steps": eng._steps - warm_steps,
        "tokens_per_forward": n_tokens / forwards,
    }
    route = {k: getattr(eng, k, 0) - warm_route[k] for k in warm_route}
    out["prefill_launches"] = route["prefill_launches"]
    out["prefill_rows"] = route["prefill_rows"]
    out["prefill_chunks"] = route["prefill_chunks"]
    out["prefix_hit_rate"] = (
        route["prefix_hit_blocks"] / route["prefix_lookup_blocks"]
        if route["prefix_lookup_blocks"] else 0.0)
    if hasattr(eng, "spec_proposed"):
        proposed = eng.spec_proposed - warm_spec["spec_proposed"]
        accepted = eng.spec_accepted - warm_spec["spec_accepted"]
        rounds = eng.spec_rounds - warm_spec["spec_rounds"]
        spec_tokens = eng.spec_tokens_out - warm_spec["spec_tokens_out"]
        out["draft_forwards"] = (
            getattr(eng.draft, "forwards", 0) - warm_draft)
        out["acceptance_rate"] = (accepted / proposed
                                  if proposed else 0.0)
        out["tokens_per_seq_round"] = (spec_tokens / rounds
                                       if rounds else 0.0)
    return out


def run_serve_bench(*, n_requests: int | None = None,
                    max_new: int | None = None, seed: int = 0,
                    on_tpu: bool | None = None) -> dict:
    """Serving-plane leg (tony_tpu.serve): continuous vs static batching
    under one Poisson arrival trace on the simulated mesh.

    Both policies run the SAME engine, model, params, and arrival
    schedule; the only difference is the join rule — continuous admits a
    request the iteration blocks free up, static waits for the running
    batch to drain (the classic serve-a-batch-at-a-time baseline every
    user would rebuild). Three gated numbers:

    * **tokens/s** per policy and the continuous/static throughput
      ratio;
    * **p50/p99 request latency** per policy (arrival→completion wall
      time — the number the heartbeat autoscaler acts on);
    * **numerics gate** — both policies must emit IDENTICAL token
      streams per request (continuous batching is bit-transparent; the
      serve test suite pins the logits, this leg gates the tokens).

    CPU-simulated wall times measure engine/dispatch behavior, not TPU
    decode throughput — ``serve_sim_note`` says so; metal numbers ride
    the real-hardware debt list.
    """
    import numpy as np

    import flax.linen as nn

    from tony_tpu.models import get_model
    from tony_tpu.serve import Request, ServeEngine

    if on_tpu is None:
        on_tpu = jax.default_backend() not in ("cpu",)
    if n_requests is None:
        n_requests = 24
    rng = np.random.RandomState(seed)
    model = get_model("llama-tiny", n_layers=2)
    toks0 = jnp.zeros((1, 16), jnp.int32)
    params = nn.unbox(model.init(jax.random.PRNGKey(seed), toks0))["params"]
    params = jax.tree.map(
        lambda a: a.astype(jnp.bfloat16) if a.dtype == jnp.float32 else a,
        params)
    prompts = [list(rng.randint(0, model.cfg.vocab, rng.randint(4, 24)))
               for _ in range(n_requests)]
    # Heterogeneous generation lengths: the head-of-line blocking that
    # batch-boundary ("static") serving suffers — a short request stuck
    # behind a long batch — is the regime iteration-level join/evict
    # exists for.
    new_tokens = [int(rng.randint(2, 25)) if max_new is None else max_new
                  for _ in range(n_requests)]

    def drive(policy: str, gap_s: float) -> dict:
        eng = ServeEngine(model, params, ctx_max=64, block_size=8,
                          q_block=16, decode_buckets=(8,), max_running=8,
                          join_policy=policy, tag=f"serve_bench_{policy}")
        # Poisson arrivals in WALL time (mean gap scaled off a measured
        # decode step, so requests land while earlier ones still decode
        # — the regime continuous batching exists for, on any backend),
        # drawn per policy off the shared rng exactly as before the
        # drive loop moved into _drive_serve_trace.
        arrivals = np.cumsum(rng.exponential(gap_s, n_requests))
        return _drive_serve_trace(eng, prompts, new_tokens, arrivals)

    # Calibrate the arrival rate off a measured decode step so the trace
    # overlaps generations on fast and slow backends alike: one request
    # occupies the engine for ~(1 prefill + max_new-1 decodes); a mean
    # gap of ~1.5 decode steps keeps several generations in flight.
    probe = ServeEngine(model, params, ctx_max=64, block_size=8,
                        q_block=16, decode_buckets=(8,), max_running=8,
                        tag="serve_bench_probe")
    probe.submit(Request(rid="probe", tokens=prompts[0],
                         max_new_tokens=4))
    probe.run()
    t0 = time.perf_counter()
    probe.submit(Request(rid="probe2", tokens=prompts[0],
                         max_new_tokens=4))
    steps0 = probe._steps
    probe.run()
    step_s = (time.perf_counter() - t0) / max(1, probe._steps - steps0)
    gap_s = 1.5 * step_s
    cont = drive("continuous", gap_s)
    stat = drive("static", gap_s)
    out = {
        "serve_requests": n_requests,
        "serve_max_new_tokens": (max_new if max_new is not None
                                 else [min(new_tokens), max(new_tokens)]),
        "serve_continuous_tokens_per_s": round(cont["tokens_per_s"], 2),
        "serve_static_tokens_per_s": round(stat["tokens_per_s"], 2),
        "serve_throughput_ratio": round(
            cont["tokens_per_s"] / stat["tokens_per_s"], 3)
        if stat["tokens_per_s"] else None,
        "serve_continuous_forwards": cont["forwards"],
        "serve_static_forwards": stat["forwards"],
        "serve_forwards_ratio": round(
            stat["forwards"] / cont["forwards"], 3)
        if cont["forwards"] else None,
        "serve_continuous_p50_ms": round(cont["p50_ms"], 2),
        "serve_continuous_p99_ms": round(cont["p99_ms"], 2),
        "serve_static_p50_ms": round(stat["p50_ms"], 2),
        "serve_static_p99_ms": round(stat["p99_ms"], 2),
        "serve_numerics_ok": cont["tokens"] == stat["tokens"],
        "backend": jax.default_backend(),
    }
    if not on_tpu:
        out["serve_sim_note"] = (
            "CPU simulation: wall times are noisy and biased against "
            "the continuous policy (alternating prefill/decode "
            "executables run ~2x slower per launch on XLA CPU than a "
            "same-executable streak — a host artifact; on TPU the "
            "forward dominates and launch cost is shape-stable). The "
            "machine-independent claim is serve_forwards_ratio: fewer "
            "forward launches for the SAME tokens under the same trace. "
            "Metal wall numbers ride the real-hardware debt list "
            "(ROADMAP)")
    return out


def run_spec_bench(*, n_requests: int | None = None,
                   depths: tuple = (2, 4, 8), seed: int = 0,
                   on_tpu: bool | None = None) -> dict:
    """Speculative-decoding leg (tony_tpu.serve.spec): the draft-and-
    verify engine vs the plain continuous-batching engine on the SAME
    Poisson arrival trace as BENCH_r12 (same seed, same prompts, same
    generation lengths, same calibration protocol). Gated numbers:

    * **tokens per target forward** — the headline: speculation must
      multiply what one target launch buys. Two views: the global
      ``tokens_per_forward`` (prefills included) against the baseline's,
      and the per-sequence ``tokens_per_seq_round`` (= 1 + mean accepted
      run — what ONE verify launch earns for ONE sequence, batching
      excluded; > 1 whenever anything is accepted);
    * **acceptance rate by draft depth k** — the self-drafting n-gram
      lane at each k (no second model needed; greedy tails of the tiny
      model repeat, which is exactly what prompt lookup predicts), plus
      the draft==target model lane as the perfect-acceptance upper
      bound with its draft forwards accounted;
    * **the bitwise gate** — every configuration must emit token streams
      IDENTICAL to the plain engine's (greedy accept/reject is
      deterministic; tests/test_spec.py pins the logits too).

    CPU-simulated wall times measure engine scheduling, not TPU decode —
    ``spec_sim_note`` says so; metal rides the real-hardware debt list.
    """
    import numpy as np

    import flax.linen as nn

    from tony_tpu.models import get_model
    from tony_tpu.serve import Request, ServeEngine, SpecEngine

    if on_tpu is None:
        on_tpu = jax.default_backend() not in ("cpu",)
    if n_requests is None:
        n_requests = 24
    rng = np.random.RandomState(seed)
    model = get_model("llama-tiny", n_layers=2)
    toks0 = jnp.zeros((1, 16), jnp.int32)
    params = nn.unbox(model.init(jax.random.PRNGKey(seed), toks0))["params"]
    params = jax.tree.map(
        lambda a: a.astype(jnp.bfloat16) if a.dtype == jnp.float32 else a,
        params)
    # The BENCH_r12 trace, reproduced: same RandomState consumption order.
    prompts = [list(rng.randint(0, model.cfg.vocab, rng.randint(4, 24)))
               for _ in range(n_requests)]
    new_tokens = [int(rng.randint(2, 25)) for _ in range(n_requests)]

    def build(kind: str, k: int = 0):
        kw = dict(ctx_max=64, block_size=8, q_block=16,
                  decode_buckets=(8,), max_running=8,
                  tag=f"spec_bench_{kind}{k or ''}")
        if kind == "plain":
            return ServeEngine(model, params, **kw)
        if kind == "ngram":
            return SpecEngine(model, params, spec_k=k, **kw)
        return SpecEngine(model, params, spec_k=k, draft_model=model,
                          draft_params=params, **kw)

    # The BENCH_r12 calibration protocol: mean arrival gap ~1.5 measured
    # engine steps, so generations overlap on fast and slow backends.
    probe = build("plain")
    probe.tag = "spec_bench_probe"
    probe.submit(Request(rid="probe", tokens=prompts[0],
                         max_new_tokens=4))
    probe.run()
    t0 = time.perf_counter()
    probe.submit(Request(rid="probe2", tokens=prompts[0],
                         max_new_tokens=4))
    steps0 = probe._steps
    probe.run()
    step_s = (time.perf_counter() - t0) / max(1, probe._steps - steps0)
    gap_s = 1.5 * step_s

    # ONE arrival schedule, shared by every engine — forward counts
    # compare speculation against the baseline on the identical trace,
    # not against Poisson draw noise (wall-clock join timing still
    # jitters batch composition, but greedy token streams are
    # arrival-independent, which is what the bitwise gate checks).
    arrivals = np.cumsum(rng.exponential(gap_s, n_requests))
    base = _drive_serve_trace(build("plain"), prompts, new_tokens,
                              arrivals)
    out = {
        "metric": "spec_bench",
        "spec_requests": n_requests,
        "spec_baseline_forwards": base["forwards"],
        "spec_baseline_tokens_per_forward": round(
            base["tokens_per_forward"], 3),
        "spec_baseline_p50_ms": round(base["p50_ms"], 2),
        "spec_baseline_p99_ms": round(base["p99_ms"], 2),
        "spec_baseline_tokens_per_s": round(base["tokens_per_s"], 2),
        "backend": jax.default_backend(),
    }
    all_identical = True
    for k in depths:
        r = _drive_serve_trace(build("ngram", k), prompts,
                               new_tokens, arrivals)
        ident = r["tokens"] == base["tokens"]
        all_identical = all_identical and ident
        out[f"spec_k{k}_forwards"] = r["forwards"]
        out[f"spec_k{k}_forwards_ratio"] = round(
            base["forwards"] / r["forwards"], 3)
        out[f"spec_k{k}_tokens_per_forward"] = round(
            r["tokens_per_forward"], 3)
        out[f"spec_k{k}_tokens_per_seq_round"] = round(
            r["tokens_per_seq_round"], 3)
        out[f"spec_k{k}_acceptance_rate"] = round(
            r["acceptance_rate"], 3)
        out[f"spec_k{k}_p50_ms"] = round(r["p50_ms"], 2)
        out[f"spec_k{k}_p99_ms"] = round(r["p99_ms"], 2)
        out[f"spec_k{k}_tokens_identical"] = ident
    # Perfect-draft upper bound: draft == target, total acceptance —
    # what a well-trained small draft buys at this depth (its launches
    # are a same-size model here; a real draft is k× smaller, which is
    # the point — see spec_sim_note).
    ub = _drive_serve_trace(build("model", 4), prompts,
                            new_tokens, arrivals)
    out["spec_selfdraft_forwards"] = ub["forwards"]
    out["spec_selfdraft_draft_forwards"] = ub["draft_forwards"]
    out["spec_selfdraft_forwards_ratio"] = round(
        base["forwards"] / ub["forwards"], 3)
    out["spec_selfdraft_acceptance_rate"] = round(
        ub["acceptance_rate"], 3)
    out["spec_selfdraft_tokens_per_seq_round"] = round(
        ub["tokens_per_seq_round"], 3)
    out["spec_selfdraft_tokens_identical"] = \
        ub["tokens"] == base["tokens"]
    out["spec_numerics_ok"] = all_identical and \
        out["spec_selfdraft_tokens_identical"]
    if not on_tpu:
        out["spec_sim_note"] = (
            "CPU simulation: wall clock measures engine scheduling, not "
            "TPU decode. The machine-independent claims are the forward "
            "counts: spec_k*_forwards_ratio (fewer target launches for "
            "the SAME tokens on the same trace) and tokens_per_seq_round "
            "(= 1 + mean accepted run, what one verify launch earns one "
            "sequence). The n-gram lane costs zero extra launches; the "
            "selfdraft lane's draft launches are a SAME-size model here "
            "(upper-bound acceptance demo) — a production draft is "
            "several times smaller, so its launches cost a fraction of "
            "a target forward. Metal wall numbers ride the "
            "real-hardware debt list (ROADMAP)")
    return out


def _drive_routed_trace(router, prompts, new_tokens, arrivals,
                        sessions=None, refresh=None) -> dict:
    """Arrival-driven drive through a :class:`tony_tpu.serve.router.
    RequestRouter`: one thread per request sleeps until its arrival and
    dispatches; the in-process EngineFront transports interleave the
    concurrent callers onto each replica's continuous batch — the same
    drive discipline a replica's RPC front runs. ``refresh`` (called
    before each dispatch) stands in for the heartbeat tick: it pushes
    each replica's live queue/p99/digest into the router, so the
    scoring sees the fleet as the AM would."""
    import threading

    results: dict = {}
    walls: dict = {}
    lock = threading.Lock()
    t0 = time.perf_counter()

    def worker(i: int) -> None:
        delay = arrivals[i] - (time.perf_counter() - t0)
        if delay > 0:
            time.sleep(delay)
        if refresh is not None:
            with lock:
                refresh()
        t_req = time.perf_counter()
        out = router.dispatch(
            prompts[i], new_tokens[i], rid=f"r{i}",
            session_id=None if sessions is None else sessions[i])
        with lock:
            results[f"r{i}"] = out
            # Caller-side wall latency: arrival -> completion INCLUDING
            # routing and (for a disaggregated fleet) the KV handoff —
            # the replica-reported latency_ms covers only its own
            # engine's window.
            walls[f"r{i}"] = 1e3 * (time.perf_counter() - t_req)

    threads = [threading.Thread(target=worker, args=(i,), daemon=True)
               for i in range(len(prompts))]
    for t in threads:
        t.start()
    for t in threads:
        t.join()
    wall = time.perf_counter() - t0
    lats = sorted(r["latency_ms"] for r in results.values())

    def pct(p):
        return lats[min(len(lats) - 1, int(p * (len(lats) - 1) + 0.5))]

    n_tokens = sum(len(r["tokens"]) for r in results.values())
    by_replica: dict = {}
    for r in results.values():
        by_replica[r["replica"]] = by_replica.get(r["replica"], 0) + 1
    return {
        "tokens": {rid: r["tokens"] for rid, r in results.items()},
        "wall_s": wall,
        "tokens_per_s": n_tokens / wall,
        "p50_ms": pct(0.50),
        "p99_ms": pct(0.99),
        "wall_latency_ms": dict(walls),
        "by_replica": by_replica,
    }


def run_route_bench(*, n_requests: int | None = None, seed: int = 0,
                    on_tpu: bool | None = None) -> dict:
    """Routed-serving leg (tony_tpu.serve PR 13) on a shared-prefix
    workload mix: chat-style traffic where most prompts extend one of a
    few long system-prompt stems — the regime where prefill compute is
    mostly redundant re-processing of shared prefixes. Four engine
    configurations run the SAME requests (prefix caching and chunked
    prefill are bit-transparent, so the token-identity gate holds
    across all of them), then the same trace runs ROUTED over a
    2-replica fleet:

    * **prefill-launch/row reduction + cache hit rate** (the
      machine-independent claims): with the prefix cache on, admissions
      adopt the published stem blocks and the corresponding prefill
      work is never issued;
    * **p50/p99 with chunked prefill on vs off** under long-prompt
      admissions landing mid-decode;
    * **2-replica routed vs 1-replica throughput** with sticky
      sessions and digest-driven cache affinity;
    * **the numerics gate** — every configuration (and the routed
      fleet) must emit IDENTICAL token streams per request.

    CPU wall numbers measure engine scheduling (``route_sim_note``);
    the launch/row counts and hit rates are the claims that transfer.
    """
    import numpy as np

    import flax.linen as nn

    from tony_tpu.models import get_model
    from tony_tpu.serve import EngineFront, Request, ServeEngine
    from tony_tpu.serve.router import RequestRouter

    if on_tpu is None:
        on_tpu = jax.default_backend() not in ("cpu",)
    if n_requests is None:
        n_requests = 24
    rng = np.random.RandomState(seed)
    model = get_model("llama-tiny", n_layers=2)
    toks0 = jnp.zeros((1, 16), jnp.int32)
    params = nn.unbox(model.init(jax.random.PRNGKey(seed), toks0))["params"]
    params = jax.tree.map(
        lambda a: a.astype(jnp.bfloat16) if a.dtype == jnp.float32 else a,
        params)
    # The shared-prefix mix: 3 "system prompt" stems of 32 tokens (4 KV
    # blocks of 8), each request = a stem + a unique 1..16-token tail;
    # sessions group requests per stem so sticky routing keeps a
    # conversation's blocks on one replica.
    stems = [list(rng.randint(0, model.cfg.vocab, 32)) for _ in range(3)]
    stem_of = [int(rng.randint(3)) for _ in range(n_requests)]
    prompts = [stems[s] + list(rng.randint(0, model.cfg.vocab,
                                           1 + int(rng.randint(16))))
               for s in stem_of]
    sessions = [f"sess-{s}" for s in stem_of]
    new_tokens = [int(rng.randint(2, 17)) for _ in range(n_requests)]
    # Length-matched scrambled warm prompts: compile every shape the
    # trace hits WITHOUT pre-publishing the measured prompts' blocks —
    # the reported hit rate is the trace's own sharing.
    warm_prompts = [list(rng.randint(0, model.cfg.vocab, len(p)))
                    for p in prompts]

    def build(tag: str, **kw) -> ServeEngine:
        return ServeEngine(model, params, ctx_max=64, block_size=8,
                           q_block=16, decode_buckets=(8,), max_running=8,
                           tag=f"route_bench_{tag}", **kw)

    # BENCH_r12/r13 calibration protocol: mean arrival gap ~1.5 measured
    # engine steps so generations overlap on any backend.
    probe = build("probe")
    probe.submit(Request(rid="probe", tokens=prompts[0],
                         max_new_tokens=4))
    probe.run()
    t0 = time.perf_counter()
    probe.submit(Request(rid="probe2", tokens=prompts[0],
                         max_new_tokens=4))
    steps0 = probe._steps
    probe.run()
    step_s = (time.perf_counter() - t0) / max(1, probe._steps - steps0)
    arrivals = np.cumsum(rng.exponential(1.5 * step_s, n_requests))

    configs = {
        "base": {},
        "prefix": {"prefix_cache": True},
        "chunk": {"prefill_chunk": 32},
        "prefix_chunk": {"prefix_cache": True, "prefill_chunk": 32},
    }
    runs = {name: _drive_serve_trace(build(name, **kw), prompts,
                                     new_tokens, arrivals,
                                     warm_prompts=warm_prompts)
            for name, kw in configs.items()}
    base = runs["base"]
    out = {
        "metric": "route_bench",
        "route_requests": n_requests,
        "route_stems": len(stems),
        "route_stem_tokens": len(stems[0]),
        "backend": jax.default_backend(),
    }
    identical = True
    for name, r in runs.items():
        identical = identical and r["tokens"] == base["tokens"]
        out[f"route_{name}_prefill_launches"] = r["prefill_launches"]
        out[f"route_{name}_prefill_rows"] = r["prefill_rows"]
        out[f"route_{name}_p50_ms"] = round(r["p50_ms"], 2)
        out[f"route_{name}_p99_ms"] = round(r["p99_ms"], 2)
        out[f"route_{name}_tokens_per_s"] = round(r["tokens_per_s"], 2)
    out["route_prefix_hit_rate"] = round(runs["prefix"]["prefix_hit_rate"],
                                         3)
    out["route_prefix_chunk_hit_rate"] = round(
        runs["prefix_chunk"]["prefix_hit_rate"], 3)
    # The prefill-forward-launch reduction: measured on the chunked
    # pair, where a launch is a fixed chunk of work — adopting a stem's
    # blocks skips whole chunk launches. (Monolithic prefill always
    # costs one launch per admission; there the saving shows in ROWS.)
    out["route_prefix_launch_reduction"] = round(
        runs["chunk"]["prefill_launches"]
        / runs["prefix_chunk"]["prefill_launches"], 3) \
        if runs["prefix_chunk"]["prefill_launches"] else None
    out["route_prefix_row_reduction"] = round(
        base["prefill_rows"] / runs["prefix"]["prefill_rows"], 3) \
        if runs["prefix"]["prefill_rows"] else None

    # -- the 2-replica routed fleet vs the 1-replica baseline ------------
    def routed(n_replicas: int) -> dict:
        router = RequestRouter(block_size=8)
        engines = []
        for i in range(n_replicas):
            eng = build(f"fleet{n_replicas}_{i}", prefix_cache=True,
                        prefill_chunk=32)
            # Warm each replica's shapes outside the measured window.
            front = EngineFront(eng)
            for w in (warm_prompts[0], warm_prompts[1]):
                front.generate(w, 2)
            engines.append(eng)
            router.upsert_replica(f"r{i}", client=front,
                                  stats=eng.stats())

        def refresh() -> None:
            # The heartbeat tick, inlined: live queue depth + digest.
            for i, e in enumerate(engines):
                router.upsert_replica(f"r{i}", stats={
                    **e.stats(), "prefix_digest": e.prefix_digest()})

        run = _drive_routed_trace(router, prompts, new_tokens, arrivals,
                                  sessions=sessions, refresh=refresh)
        run["router_stats"] = router.stats()
        run["forwards"] = sum(e.forwards for e in engines)
        return run

    one = routed(1)
    two = routed(2)
    out["route_1rep_tokens_per_s"] = round(one["tokens_per_s"], 2)
    out["route_2rep_tokens_per_s"] = round(two["tokens_per_s"], 2)
    out["route_2rep_speedup"] = round(
        two["tokens_per_s"] / one["tokens_per_s"], 3) \
        if one["tokens_per_s"] else None
    out["route_2rep_p50_ms"] = round(two["p50_ms"], 2)
    out["route_2rep_p99_ms"] = round(two["p99_ms"], 2)
    out["route_2rep_by_replica"] = two["by_replica"]
    out["route_2rep_affinity_hits"] = two["router_stats"]["affinity_hits"]
    out["route_2rep_cache_routed"] = two["router_stats"]["cache_routed"]
    identical = identical and one["tokens"] == base["tokens"] \
        and two["tokens"] == base["tokens"]
    out["route_numerics_ok"] = identical
    if not on_tpu:
        out["route_sim_note"] = (
            "CPU simulation: wall times measure engine scheduling on a "
            "shared host CPU (two 'replicas' contend for the same "
            "cores, so route_2rep_speedup understates a real fleet "
            "where each replica owns its chips; the monolithic+prefix "
            "config's wall numbers also suffer BENCH_r12's XLA-CPU "
            "executable-alternation artifact — prefix hits shrink each "
            "prefill to a different small shape, and alternating "
            "executables run ~2x slower per launch on CPU, which is "
            "why the chunked+prefix config, whose launches stay "
            "shape-stable, is the fast one). The machine-"
            "independent claims are route_prefix_launch_reduction / "
            "route_prefix_row_reduction (prefill work never issued for "
            "adopted blocks), route_prefix_hit_rate, and "
            "route_numerics_ok (identical token streams in every "
            "configuration, routed fleet included). Metal wall numbers "
            "ride the real-hardware debt list (ROADMAP)")
    return out


def run_disagg_bench(*, n_floor: int | None = None,
                     n_burst: int | None = None, seed: int = 0,
                     on_tpu: bool | None = None) -> dict:
    """Disaggregated prefill/decode leg (tony_tpu.serve.disagg, PR 15)
    on the shared Poisson protocol with a PREFILL-BURST phase: a steady
    decode floor (short prompts, long generations) absorbs a cluster of
    long-prompt admissions mid-trace — the regime where prefill and
    decode contend for the same chips. Two configurations run the SAME
    requests and arrival schedule:

    * **colocated chunked** — the BENCH_r14 mitigation: one engine,
      chunked prefill interleaved with decode (the decode floor pays
      one chunk launch per iteration while the burst drains);
    * **split gang** — a prefill replica and a decode replica behind
      the role-aware router: the burst's chunk launches run on the
      prefill replica, KV blocks ship over the handoff wire, and the
      decode replica's loop issues ZERO prefill work.

    The headline is decode-floor p99 isolation under the burst; the
    machine-independent claims are the decode side's prefill-launch
    count (exactly zero) and the forward-launch split; token identity
    is gated in both configurations (the handoff is bitwise
    transparent). CPU wall numbers measure scheduling on a shared host
    (``disagg_sim_note``)."""
    import numpy as np

    import flax.linen as nn

    from tony_tpu.models import get_model
    from tony_tpu.serve import EngineFront, Request, ServeEngine
    from tony_tpu.serve.disagg import DecodeFront, PrefillFront
    from tony_tpu.serve.router import RequestRouter

    if on_tpu is None:
        on_tpu = jax.default_backend() not in ("cpu",)
    if n_floor is None:
        n_floor = 16
    if n_burst is None:
        n_burst = 8
    burst_len = 96                      # 3 chunk launches per admission
    rng = np.random.RandomState(seed)
    model = get_model("llama-tiny", n_layers=2)
    toks0 = jnp.zeros((1, 16), jnp.int32)
    params = nn.unbox(model.init(jax.random.PRNGKey(seed), toks0))["params"]
    params = jax.tree.map(
        lambda a: a.astype(jnp.bfloat16) if a.dtype == jnp.float32 else a,
        params)

    def build(tag: str, **kw) -> ServeEngine:
        return ServeEngine(model, params, ctx_max=128, block_size=8,
                           q_block=16, decode_buckets=(8,), max_running=8,
                           tag=f"disagg_bench_{tag}", **kw)

    # The workload: a decode floor of short prompts with real
    # generation lengths (the BENCH_r12/r13/r14 protocol), plus a burst
    # of long prompts — one chunk-launch apiece per 32 rows — landing
    # in a tight cluster one third into the trace: the regime where a
    # colocated engine interleaves the burst's chunk launches into
    # every decode iteration of the floor, and the split gang runs them
    # on the prefill replica instead.
    floor_prompts = [list(rng.randint(0, model.cfg.vocab,
                                      4 + int(rng.randint(9))))
                     for _ in range(n_floor)]
    floor_new = [int(rng.randint(10, 17)) for _ in range(n_floor)]
    burst_prompts = [list(rng.randint(0, model.cfg.vocab, burst_len))
                     for _ in range(n_burst)]
    burst_new = [int(rng.randint(2, 4)) for _ in range(n_burst)]

    # BENCH_r12/r13/r14 calibration protocol: arrival gaps scaled off a
    # measured engine step so the floor overlaps itself on any backend.
    probe = build("probe", prefill_chunk=32)
    probe.submit(Request(rid="probe", tokens=floor_prompts[0],
                         max_new_tokens=4))
    probe.run()
    t0 = time.perf_counter()
    probe.submit(Request(rid="probe2", tokens=floor_prompts[0],
                         max_new_tokens=4))
    steps0 = probe._steps
    probe.run()
    step_s = (time.perf_counter() - t0) / max(1, probe._steps - steps0)
    floor_arrivals = np.cumsum(rng.exponential(1.5 * step_s, n_floor))
    t_burst = float(floor_arrivals[n_floor // 3])
    burst_arrivals = t_burst + 0.1 * step_s * np.arange(n_burst)

    # One merged trace, sorted by arrival, floor membership remembered
    # by rid so the percentile split survives the sort.
    merged = sorted(
        [(a, p, n, True) for a, p, n in zip(floor_arrivals,
                                            floor_prompts, floor_new)]
        + [(a, p, n, False) for a, p, n in zip(burst_arrivals,
                                               burst_prompts, burst_new)],
        key=lambda t: t[0])
    arrivals = [t[0] for t in merged]
    prompts = [t[1] for t in merged]
    new_tokens = [t[2] for t in merged]
    floor_rids = [f"r{i}" for i, t in enumerate(merged) if t[3]]
    burst_rids = [f"r{i}" for i, t in enumerate(merged) if not t[3]]
    warm_prompts = [list(rng.randint(0, model.cfg.vocab, len(p)))
                    for p in prompts]

    def pctl(vals, p):
        vals = sorted(vals)
        return vals[min(len(vals) - 1, int(p * (len(vals) - 1) + 0.5))]

    # -- colocated chunked (the PR 13 mitigation) ------------------------
    col_eng = build("colocated", prefill_chunk=32)
    col = _drive_serve_trace(col_eng, prompts, new_tokens, arrivals,
                             warm_prompts=warm_prompts)

    # -- the split gang --------------------------------------------------
    pf_eng = build("prefill", role="prefill", prefill_chunk=32)
    dc_eng = build("decode", role="decode")
    pf_front, dc_front = EngineFront(pf_eng), EngineFront(dc_eng)
    pf_client = PrefillFront(pf_front)
    dc_client = DecodeFront(dc_front)
    # Warm every shape the trace hits THROUGH the handoff path (the
    # measured window times steady state, not compiles): one floor-
    # and one burst-shaped prompt.
    for wp in (warm_prompts[0],
               next(w for w, t in zip(warm_prompts, merged) if not t[3])):
        pf_client.prefill_handoff(wp, 2, decode=dc_client)
    warm = {"pf_forwards": pf_eng.forwards, "dc_forwards": dc_eng.forwards,
            "pf_chunks": pf_eng.prefill_chunks,
            "dc_prefill": dc_eng.prefill_launches,
            "dc_steps": dc_eng._steps,
            "shipped": pf_eng.blocks_shipped,
            "handoffs_out": pf_eng.handoffs_out,
            "handoff_ms": pf_eng.handoff_ms + dc_eng.handoff_ms}
    router = RequestRouter(block_size=8)
    router.upsert_replica("prefill:0", client=pf_client,
                          stats=pf_eng.stats())
    router.upsert_replica("decode:0", client=dc_client,
                          stats=dc_eng.stats())

    def refresh() -> None:
        router.upsert_replica("prefill:0", client=pf_client,
                              stats=pf_eng.stats())
        router.upsert_replica("decode:0", client=dc_client,
                              stats=dc_eng.stats())

    dis = _drive_routed_trace(router, prompts, new_tokens, arrivals,
                              refresh=refresh)

    col_floor = [col["latency_ms"][r] for r in floor_rids]
    dis_floor = [dis["wall_latency_ms"][r] for r in floor_rids]
    dc_steps = dc_eng._steps - warm["dc_steps"]
    out = {
        "metric": "disagg_bench",
        "disagg_floor_requests": n_floor,
        "disagg_burst_requests": n_burst,
        "disagg_burst_prompt_tokens": burst_len,
        "backend": jax.default_backend(),
        # THE isolation claim, in the machine-independent currency
        # (launches on the decode critical path): the colocated engine
        # interleaves one burst-chunk launch into a large fraction of
        # the floor's decode iterations; the split decode replica's
        # loop carries ZERO prefill launches — isolation by
        # construction, not a mitigation. On metal a 32x256-row chunk
        # launch is compute-bound and costs at least a (bytes-bound)
        # decode launch, so the interleave fraction IS the decode
        # latency tax (ROOFLINE §11); on XLA-CPU the same chunk launch
        # is artificially cheap next to a batched decode step, which is
        # why the wall numbers below understate the split.
        "disagg_colocated_prefill_chunks": col["prefill_chunks"],
        "disagg_colocated_steps": col["steps"],
        "disagg_colocated_iteration_prefill_fraction": round(
            col["prefill_chunks"] / col["steps"], 3) if col["steps"]
        else None,
        "disagg_decode_prefill_launches":
            dc_eng.prefill_launches - warm["dc_prefill"],
        "disagg_decode_steps": dc_steps,
        # Measured, not asserted: 0.0 whenever no handoff fell back to
        # colocated prefill on the decode replica (the HandoffError
        # path) — a run where fallbacks fired reports the real fraction
        # next to the launch count above instead of a constant.
        "disagg_decode_iteration_prefill_fraction": round(
            (dc_eng.prefill_launches - warm["dc_prefill"]) / dc_steps, 3)
        if dc_steps else None,
        "disagg_prefill_gang_chunks":
            pf_eng.prefill_chunks - warm["pf_chunks"],
        "disagg_decode_forwards": dc_eng.forwards - warm["dc_forwards"],
        # The handoff ledger: what moving the KV actually cost.
        "disagg_blocks_shipped": pf_eng.blocks_shipped - warm["shipped"],
        "disagg_handoffs": pf_eng.handoffs_out - warm["handoffs_out"],
        "disagg_handoff_ms_total": round(
            pf_eng.handoff_ms + dc_eng.handoff_ms - warm["handoff_ms"],
            2),
        # Wall latencies as measured on this backend (see sim note).
        "disagg_colocated_floor_p50_ms": round(pctl(col_floor, 0.50), 2),
        "disagg_colocated_floor_p99_ms": round(pctl(col_floor, 0.99), 2),
        "disagg_split_floor_p50_ms": round(pctl(dis_floor, 0.50), 2),
        "disagg_split_floor_p99_ms": round(pctl(dis_floor, 0.99), 2),
        "disagg_floor_p99_isolation_wall": round(
            pctl(col_floor, 0.99) / pctl(dis_floor, 0.99), 3)
        if pctl(dis_floor, 0.99) else None,
        "disagg_burst_p99_ms": round(
            pctl([dis["wall_latency_ms"][r] for r in burst_rids], 0.99),
            2),
        "disagg_numerics_ok": dis["tokens"] == col["tokens"],
    }
    if not on_tpu:
        out["disagg_sim_note"] = (
            "CPU simulation with INVERTED launch economics: on this "
            "backend a (1,32) chunk launch is compute-bound and cheap "
            "next to a batched (8,16) decode step, so the colocated "
            "engine's interleave tax — the thing disaggregation removes "
            "— barely registers in wall time, while the split gang "
            "pays real costs metal does not charge (two 'replicas' "
            "contending for one host CPU, a per-request dispatch "
            "thread, and host-RAM device round trips per handoff). "
            "disagg_floor_p99_isolation_wall on this host is therefore "
            "BELOW 1 and is explicitly NOT the claim. The claims that "
            "transfer: disagg_decode_prefill_launches == 0 vs the "
            "colocated engine's interleave fraction "
            "(disagg_colocated_iteration_prefill_fraction of decode "
            "iterations carry a chunk launch — on metal each costs >= "
            "a decode launch, ROOFLINE §11, so that fraction is the "
            "floor's latency tax), the launch split across the gangs, "
            "disagg_blocks_shipped with the handoff byte math, and "
            "disagg_numerics_ok (identical token streams, handoff "
            "included). Metal wall p99 rides the real-hardware debt "
            "list (ROADMAP)")
    return out


def run_kvtier_bench(*, n_conversations: int | None = None,
                     n_turns: int | None = None, seed: int = 0,
                     on_tpu: bool | None = None) -> dict:
    """KV-memory-hierarchy leg (tony_tpu.serve PR 16): multi-turn
    conversations against an engine with the host-offload tier armed
    (idle conversations PARK — their KV demotes to host RAM between
    turns and resumes through the atomic import path) vs the identical
    engine that recomputes every turn's history from scratch. Both
    engines see the SAME conversations: rounds of turn-requests, every
    conversation's turn-t prompt being its full accumulated history
    plus fresh user tokens (the chat-completion wire shape).

    The headline is turn-resume latency; the machine-independent claims
    are the prefill-ROW ledger — a resumed turn issues prefill rows
    ONLY for the uncovered suffix (``kvtier_covered_extent_prefill_rows
    == 0``: not one row recomputes history the parked record already
    holds), the park hit rate, and the demote/promote ledger. Token
    identity is gated: the parked engine's streams are bitwise the
    recompute engine's (the parity the kvtier tests pin row-by-row on
    logits). CPU wall numbers measure scheduling plus genuinely saved
    prefill compute (``kvtier_sim_note``)."""
    import numpy as np

    import flax.linen as nn

    from tony_tpu.models import get_model
    from tony_tpu.serve import Request, ServeEngine

    if on_tpu is None:
        on_tpu = jax.default_backend() not in ("cpu",)
    if n_conversations is None:
        n_conversations = 8
    if n_turns is None:
        n_turns = 3
    turn_tokens, max_new = 12, 6
    rng = np.random.RandomState(seed)
    model = get_model("llama-tiny", n_layers=2)
    toks0 = jnp.zeros((1, 16), jnp.int32)
    params = nn.unbox(model.init(jax.random.PRNGKey(seed), toks0))["params"]
    params = jax.tree.map(
        lambda a: a.astype(jnp.bfloat16) if a.dtype == jnp.float32 else a,
        params)

    def build(tag: str, **kw) -> ServeEngine:
        return ServeEngine(model, params, ctx_max=128, block_size=8,
                           q_block=16, decode_buckets=(8,),
                           max_running=n_conversations,
                           tag=f"kvtier_bench_{tag}", **kw)

    parked = build("parked", host_blocks=512)
    plain = build("recompute")

    # Resume-start ledger: record where each resumed admission begins
    # its prefill so the covered-extent row count is computed EXACTLY
    # (measured rows minus the padded uncovered suffix == 0), not
    # inferred from a ratio.
    starts: dict = {}
    orig_resume = parked._try_resume

    def _spy(req, total):
        res = orig_resume(req, total)
        if res is not None:
            starts[req.rid] = res[0]
        return res

    parked._try_resume = _spy

    # Fixed per-turn geometry (turn_tokens user tokens, max_new
    # generated) keeps the jit-shape family identical across
    # conversations and rounds: ONE warm conversation driven through
    # all n_turns hits every prefill pad and decode bucket the
    # measured trace will, for both engines.
    def drive_round(eng, histories, fresh, conv_tags, t):
        reqs = []
        for i, hist in enumerate(histories):
            prompt = list(hist) + [int(x) for x in fresh[i]]
            kw = {}
            if conv_tags is not None:
                kw["conv"] = conv_tags[i]
            reqs.append((f"t{t}c{i}", prompt))
            eng.submit(Request(rid=f"t{t}c{i}", tokens=prompt,
                               max_new_tokens=max_new, **kw))
        t0 = time.perf_counter()
        done = {c.rid: c for c in eng.run()}
        wall = time.perf_counter() - t0
        out_hist = []
        for i, (rid, prompt) in enumerate(reqs):
            out_hist.append(prompt + list(done[rid].tokens))
        lats = [done[rid].latency_s * 1e3 for rid, _ in reqs]
        toks = {rid: list(done[rid].tokens) for rid, _ in reqs}
        return out_hist, lats, toks, wall

    def warm(eng, tag):
        hist = []
        w = np.random.RandomState(seed + 999)
        for t in range(n_turns):
            hists, _, _, _ = drive_round(
                eng, [hist], [w.randint(0, model.cfg.vocab, turn_tokens)],
                [f"warm-{tag}"] if tag == "parked" else None, f"w{t}")
            hist = hists[0]

    warm(parked, "parked")
    warm(plain, "plain")
    starts.clear()
    snap = {e: {"rows": e.prefill_rows, "launches": e.prefill_launches,
                "hits": e.park_hits, "lookups": e.park_lookups,
                "demoted": e.cache.demoted_total,
                "promoted": e.cache.promoted_total}
            for e in (parked, plain)}

    fresh = [[rng.randint(0, model.cfg.vocab, turn_tokens)
              for _ in range(n_conversations)] for _ in range(n_turns)]
    p_hist = [[] for _ in range(n_conversations)]
    r_hist = [[] for _ in range(n_conversations)]
    convs = [f"c{i}" for i in range(n_conversations)]
    rows_at_round, lat_parked, lat_plain = {}, [], []
    numerics_ok = True
    for t in range(n_turns):
        rows_at_round[t] = (parked.prefill_rows, plain.prefill_rows)
        p_hist, pl, ptoks, _ = drive_round(parked, p_hist, fresh[t],
                                           convs, t)
        r_hist, rl, rtoks, _ = drive_round(plain, r_hist, fresh[t],
                                           None, t)
        numerics_ok = numerics_ok and ptoks == rtoks
        if t > 0:                       # resume turns only
            lat_parked.extend(pl)
            lat_plain.extend(rl)

    def pctl(vals, p):
        vals = sorted(vals)
        return vals[min(len(vals) - 1, int(p * (len(vals) - 1) + 0.5))]

    # The covered-extent ledger: every resumed turn's measured rows
    # must equal the q_block-padded UNCOVERED suffix exactly.
    resumed = {rid: s for rid, s in starts.items()
               if not rid.startswith("t0")}
    expected_suffix_rows = 0
    for t in range(1, n_turns):
        for i in range(n_conversations):
            rid = f"t{t}c{i}"
            if rid not in resumed:
                continue
            prompt_len = len(p_hist[i]) - (n_turns - t) * (
                turn_tokens + max_new)
            t_real = prompt_len - resumed[rid]
            expected_suffix_rows += -(-t_real // parked.q_block) \
                * parked.q_block
    parked_resume_rows = parked.prefill_rows - rows_at_round[1][0]
    plain_resume_rows = plain.prefill_rows - rows_at_round[1][1]
    stats = parked.stats()
    out = {
        "metric": "kvtier_bench",
        "kvtier_conversations": n_conversations,
        "kvtier_turns": n_turns,
        "kvtier_turn_user_tokens": turn_tokens,
        "kvtier_turn_new_tokens": max_new,
        "backend": jax.default_backend(),
        # THE resume claim, in the machine-independent currency: a
        # resumed turn prefills the uncovered suffix ONLY — zero rows
        # recompute history the parked record covers. On metal each
        # elided row is prefill compute bought back at host<->device
        # copy prices (ROOFLINE §12); here the ledger is exact.
        "kvtier_park_hits": parked.park_hits - snap[parked]["hits"],
        "kvtier_park_lookups":
            parked.park_lookups - snap[parked]["lookups"],
        "kvtier_park_hit_rate": round(stats["park_hit_rate"], 3),
        "kvtier_resume_prefill_rows": parked_resume_rows,
        "kvtier_recompute_prefill_rows": plain_resume_rows,
        "kvtier_covered_extent_prefill_rows":
            parked_resume_rows - expected_suffix_rows,
        "kvtier_resume_row_fraction": round(
            parked_resume_rows / plain_resume_rows, 3)
        if plain_resume_rows else None,
        "kvtier_demotions":
            parked.cache.demoted_total - snap[parked]["demoted"],
        "kvtier_promotions":
            parked.cache.promoted_total - snap[parked]["promoted"],
        "kvtier_host_blocks_used": int(stats["host_blocks"]),
        "kvtier_parked_seqs": int(stats["parked_seqs"]),
        # Wall latencies over the resume turns (t >= 2), as measured.
        "kvtier_resume_p50_ms": round(pctl(lat_parked, 0.50), 2),
        "kvtier_resume_p99_ms": round(pctl(lat_parked, 0.99), 2),
        "kvtier_recompute_p50_ms": round(pctl(lat_plain, 0.50), 2),
        "kvtier_recompute_p99_ms": round(pctl(lat_plain, 0.99), 2),
        "kvtier_resume_speedup_p50_wall": round(
            pctl(lat_plain, 0.50) / pctl(lat_parked, 0.50), 3)
        if pctl(lat_parked, 0.50) else None,
        "kvtier_numerics_ok": numerics_ok,
    }
    parked.cache.close()
    if not on_tpu:
        out["kvtier_sim_note"] = (
            "CPU simulation: the wall speedup mixes genuinely saved "
            "prefill compute (XLA-CPU really does run the elided rows' "
            "flops) with scheduling noise, and the host tier's "
            "demote/promote 'copies' are host-RAM memcpys rather than "
            "PCIe/ICI transfers — so the wall numbers neither price "
            "the copy nor the HBM it frees. The claims that transfer: "
            "kvtier_covered_extent_prefill_rows == 0 (a resumed turn "
            "recomputes NOTHING the parked record covers), the "
            "resume-vs-recompute row ledger with the ROOFLINE §12 "
            "bytes-per-elided-flop math, the park hit rate, and "
            "kvtier_numerics_ok (bitwise identical streams). Metal "
            "wall latency rides the real-hardware debt list (ROADMAP)")
    return out


def run_coldstart_bench(*, seed: int = 0,
                        on_tpu: bool | None = None) -> dict:
    """Replica cold-start leg (tony_tpu.ckpt.aot PR 17): grant→first-
    token for three replica starts against the SAME workload — a COLD
    replica (empty AOT cache: every step program traces and compiles at
    warm time, populating the cache), a CACHE-HIT replica (same
    fingerprints: warm() deserializes persisted executables in
    milliseconds and the start executes ZERO fresh traces or compiles —
    counter-pinned), and a WARM-STANDBY replica (compiled ahead of the
    clock; its grant cost is one promote() RPC plus the first request).

    The wall split is broken out per start: engine build, warm (further
    split by the engine's own compile_ms vs deserialize_ms ledgers),
    and first-token. The machine-independent claims are the cache
    counters (hit start: ``fresh_compiles == 0`` AND the raw-jit memo
    stays EMPTY — nothing traced) and token identity: all three starts'
    streams are bitwise equal, logits included. XLA-CPU compile walls
    stand in for TPU compile walls (``coldstart_sim_note``)."""
    import shutil
    import tempfile

    import numpy as np

    import flax.linen as nn

    from tony_tpu.ckpt.aot import AOTCache
    from tony_tpu.models import get_model
    from tony_tpu.serve import Request, ServeEngine

    if on_tpu is None:
        on_tpu = jax.default_backend() not in ("cpu",)
    model = get_model("llama-tiny", n_layers=2)
    toks0 = jnp.zeros((1, 16), jnp.int32)
    params = nn.unbox(model.init(jax.random.PRNGKey(seed), toks0))["params"]
    params = jax.tree.map(
        lambda a: a.astype(jnp.bfloat16) if a.dtype == jnp.float32 else a,
        params)
    rng = np.random.RandomState(seed)
    prompts = [rng.randint(1, 200, size=n).tolist() for n in (5, 3, 9)]
    max_new = 6
    root = tempfile.mkdtemp(prefix="tony_coldstart_bench_")

    def build(tag: str, **kw) -> ServeEngine:
        # One decode bucket + prompts under one q_block: the FULL step
        # family is two programs — (4, 16) decode/verify and (1, 16)
        # monolithic prefill — so warm(prefill_pads=(16,)) provably
        # covers every shape the drive launches.
        return ServeEngine(model, params, ctx_max=128, block_size=8,
                           q_block=16, decode_buckets=(4,),
                           max_running=4, keep_logits=True,
                           aot_cache=AOTCache(root),
                           tag=f"coldstart_bench_{tag}", **kw)

    def first_token_ms(eng) -> float:
        t0 = time.perf_counter()
        eng.submit(Request(rid="probe", tokens=list(prompts[0]),
                           max_new_tokens=1))
        done = list(eng.run())
        assert len(done) == 1 and len(done[0].tokens) == 1
        return 1e3 * (time.perf_counter() - t0)

    def drive(eng) -> dict:
        for i, p in enumerate(prompts):
            eng.submit(Request(rid=i, tokens=list(p),
                               max_new_tokens=max_new))
        return {c.rid: c for c in eng.run()}

    def start(tag: str, **kw) -> tuple:
        """One replica start: build + warm + first token, timed."""
        t0 = time.perf_counter()
        eng = build(tag, **kw)
        build_ms = 1e3 * (time.perf_counter() - t0)
        t0 = time.perf_counter()
        warmed = eng.warm(prefill_pads=(16,))
        warm_ms = 1e3 * (time.perf_counter() - t0)
        ft_ms = first_token_ms(eng)
        return eng, {
            f"coldstart_{tag}_build_ms": round(build_ms, 2),
            f"coldstart_{tag}_warm_ms": round(warm_ms, 2),
            f"coldstart_{tag}_warm_programs": warmed,
            f"coldstart_{tag}_compile_ms": round(eng.compile_ms, 2),
            f"coldstart_{tag}_deserialize_ms":
                round(eng.deserialize_ms, 2),
            f"coldstart_{tag}_first_token_ms": round(ft_ms, 2),
            f"coldstart_{tag}_grant_to_first_token_ms":
                round(build_ms + warm_ms + ft_ms, 2),
            f"coldstart_{tag}_fresh_compiles": eng.fresh_compiles,
            f"coldstart_{tag}_aot_hits": eng.aot_hits,
            f"coldstart_{tag}_aot_misses": eng.aot_misses,
        }

    out = {"metric": "coldstart_bench",
           "backend": jax.default_backend(),
           "coldstart_max_new_tokens": max_new}

    # Leg 1 — COLD: empty cache, warm pays the full trace+compile wall
    # AND persists every executable for the fleet.
    cold, row = start("cold")
    out.update(row)
    ref = drive(cold)

    # Leg 2 — CACHE-HIT: a fresh replica on the populated cache. The
    # acceptance pin: zero fresh traces or compiles across the ENTIRE
    # start-and-serve — and the raw-jit memo must stay empty (had
    # anything traced, it would live there).
    hit, row = start("hit")
    out.update(row)
    got_hit = drive(hit)
    out["coldstart_hit_zero_fresh_compiles"] = (
        hit.fresh_compiles == 0 and len(hit._fns) == 0)

    # Leg 3 — WARM-STANDBY: compiled ahead of the clock (untimed); the
    # grant is one promote() flip plus the first request.
    standby = build("standby", warm_standby=True)
    standby.warm(prefill_pads=(16,))
    t0 = time.perf_counter()
    assert standby.promote()
    promote_ms = 1e3 * (time.perf_counter() - t0)
    ft_ms = first_token_ms(standby)
    out["coldstart_standby_promote_ms"] = round(promote_ms, 4)
    out["coldstart_standby_first_token_ms"] = round(ft_ms, 2)
    out["coldstart_standby_grant_to_first_token_ms"] = round(
        promote_ms + ft_ms, 2)
    out["coldstart_standby_fresh_compiles"] = standby.fresh_compiles
    got_standby = drive(standby)

    # Token identity across all three starts — the cache may cost a
    # compile, never a wrong program.
    numerics_ok = True
    for got in (got_hit, got_standby):
        numerics_ok = numerics_ok and sorted(got) == sorted(ref)
        for rid in ref:
            numerics_ok = (numerics_ok
                           and got[rid].tokens == ref[rid].tokens
                           and all(np.array_equal(a, b) for a, b in
                                   zip(got[rid].logits, ref[rid].logits)))
    out["coldstart_numerics_ok"] = numerics_ok
    cold_wall = out["coldstart_cold_grant_to_first_token_ms"]
    hit_wall = out["coldstart_hit_grant_to_first_token_ms"]
    sb_wall = out["coldstart_standby_grant_to_first_token_ms"]
    out["coldstart_hit_speedup_wall"] = (
        round(cold_wall / hit_wall, 2) if hit_wall else None)
    out["coldstart_standby_speedup_wall"] = (
        round(cold_wall / sb_wall, 2) if sb_wall else None)
    shutil.rmtree(root, ignore_errors=True)
    if not on_tpu:
        out["coldstart_sim_note"] = (
            "CPU simulation: XLA-CPU compiles the tiny 2-layer step in "
            "tens of milliseconds where XLA-TPU spends seconds-to-"
            "minutes on a real model, so the wall split UNDERSTATES "
            "the cold-start win; params are handed over in memory, so "
            "the checkpoint-restore segment of a real grant (priced by "
            "the ckpt bench, ROOFLINE §7) is absent from every leg. "
            "The claims that transfer: the cache state machine (cold "
            "populates, hit deserializes), "
            "coldstart_hit_zero_fresh_compiles (a cache-hit start "
            "traces and compiles NOTHING — the counter pin), the "
            "standby grant collapsing to promote + first request, and "
            "coldstart_numerics_ok (bitwise identical streams, logits "
            "included). ROOFLINE §13 prices the metal version")
    return out


def run_resize_bench(*, hidden: int = 1024, steps: int = 24,
                     resize_at: int = 12,
                     directory: str | None = None,
                     on_tpu: bool | None = None) -> dict:
    """Elastic-resize leg (tony_tpu.am.resize, PR 19): what a drain →
    commit → re-gang → restore cycle costs the training timeline, and
    whether it costs the MODEL anything. Two runs over the same batch
    schedule:

    * **undisturbed reference** — ``steps`` optimizer steps straight
      through;
    * **elastic run** — the same schedule interrupted at ``resize_at``
      by the resize lifecycle's data plane: a synchronous drain-commit
      (the train loop's EXIT_DRAINED contract — save + wait so the
      manifest is durable before the worker reports drained), then an
      elastic restore of the committed step (the re-gang survivor's
      first act on the new topology), then the remaining steps from the
      restored state.

    The headline is ``resize_overhead_s`` (elastic wall − undisturbed
    wall) decomposed into ``drain_commit_s`` + ``restore_s``; ROOFLINE
    §15 prices the same walls against checkpoint size and host I/O. The
    machine-independent claim is ``resize_numerics_ok``: the elastic
    run's final state is BITWISE the undisturbed run's — a resize that
    moves the loss curve is a restart, not a resize (tests/
    test_elastic.py pins the example-id stream and multi-preemption
    composition on top)."""
    import shutil
    import tempfile
    from pathlib import Path

    import numpy as np
    import optax

    from tony_tpu import ckpt as ckpt_mod
    from tony_tpu import parallel as par
    from tony_tpu import train as tr
    from tony_tpu.models import get_model

    if on_tpu is None:
        on_tpu = jax.default_backend() not in ("cpu",)
    mesh = par.make_mesh(fsdp=1)
    batch = 8
    model = get_model("mnist-mlp", hidden=hidden)
    kx, ky, kr = jax.random.split(jax.random.PRNGKey(0), 3)
    xs = jax.random.normal(kx, (steps, batch, 784), jnp.float32)
    ys = jax.random.randint(ky, (steps, batch), 0, 10)
    state0 = tr.create_train_state(model, optax.sgd(0.1, momentum=0.9),
                                   xs[0], kr)
    step = tr.make_train_step(mesh=mesh, donate=False)
    _ = step(state0, {"x": xs[0], "y": ys[0]})      # warm the compile

    def run_steps(state, lo: int, hi: int):
        for i in range(lo, hi):
            state, _ = step(state, {"x": xs[i], "y": ys[i]})
        jax.block_until_ready(state.params)
        return state

    root = Path(directory) if directory else Path(tempfile.mkdtemp(
        prefix="tony-resize-bench-"))
    try:
        t0 = time.perf_counter()
        ref = run_steps(state0, 0, steps)
        undisturbed_s = time.perf_counter() - t0

        ck = ckpt_mod.AsyncCheckpointer(root / "resize", keep=2)
        t0 = time.perf_counter()
        state = run_steps(state0, 0, resize_at)
        t1 = time.perf_counter()
        ck.save(state, step=resize_at, block=True)  # the drain commit
        t2 = time.perf_counter()
        abstract = jax.tree.map(
            lambda a: np.zeros(a.shape, a.dtype)
            if hasattr(a, "shape") else a, jax.device_get(state))
        restored = ckpt_mod.restore_pytree(root / "resize", abstract,
                                           mesh=mesh)
        t3 = time.perf_counter()
        final = run_steps(restored, resize_at, steps)
        elastic_s = time.perf_counter() - t0
        nbytes = ck.stats["nbytes"]
        ck.close()

        exact = all(
            np.array_equal(np.asarray(jax.device_get(a)),
                           np.asarray(jax.device_get(b)))
            for a, b in zip(jax.tree.leaves(final), jax.tree.leaves(ref))
            if hasattr(b, "shape"))
    finally:
        if not directory:
            shutil.rmtree(root, ignore_errors=True)
    out = {
        "metric": "resize_bench",
        "resize_steps": steps,
        "resize_at": resize_at,
        "resize_state_mb": round(nbytes / (1024 * 1024), 3),
        "resize_undisturbed_s": round(undisturbed_s, 6),
        "resize_elastic_s": round(elastic_s, 6),
        "resize_overhead_s": round(elastic_s - undisturbed_s, 6),
        "resize_drain_commit_s": round(t2 - t1, 6),
        "resize_restore_s": round(t3 - t2, 6),
        "resize_numerics_ok": bool(exact),
        "backend": jax.default_backend(),
    }
    if not on_tpu:
        out["resize_sim_note"] = (
            "CPU simulation: the walls price the lifecycle's DATA plane "
            "(drain-commit + elastic restore) in one process — the "
            "container re-grant and gang re-negotiation between them "
            "are scheduler walls the MiniPod e2e measures, and tmpfs "
            "I/O understates a real host's commit/restore cost "
            "(ROOFLINE §15 prices both). The claim that transfers: "
            "resize_numerics_ok — the interrupted run's final state is "
            "bitwise the undisturbed run's")
    return out


def run_qos_bench(*, n_victim: int | None = None,
                  n_aggressor: int | None = None, seed: int = 0,
                  on_tpu: bool | None = None) -> dict:
    """Multi-tenant QoS leg (tony_tpu.serve.qos, PR 18) on the shared
    Poisson protocol with an AGGRESSOR-BURST phase: a victim tenant's
    steady decode floor (short prompts, real generation lengths — the
    BENCH_r12 workload) absorbs a tight cluster of long-prompt
    admissions from an aggressor tenant one third into the trace — the
    noisy-neighbor regime weighted-fair budgets exist for. Three
    configurations run the victim's requests on the SAME arrival
    schedule:

    * **unloaded reference** — the victim floor alone on a plain
      engine: the bitwise baseline for the victim's token streams;
    * **budgets off** (``qos=None``) — tenant tags ride the requests
      but nothing enforces them: the burst's admissions take running
      slots and pool blocks first-come-first-served and the victim
      queues behind them;
    * **budgets on** — ``QosPolicy(victim:3, aggressor:1)`` over the
      same pool: the admission scan DEFERS aggressor requests past
      their weighted-fair block share (skip-over; per-tenant FIFO
      preserved) and the victim's requests admit past them.

    The headline is victim p99 with vs without budgets under the same
    burst. The machine-independent claims: the deferral ledger
    (``qos_deferrals`` > 0 budgeted, == 0 unbudgeted, rejections 0 in
    both — deferral is back-pressure on the aggressor, never a drop or
    a victim penalty) and ``qos_numerics_ok`` (the victim's token
    streams in BOTH loaded configurations bitwise-match the unloaded
    reference, and the full trace matches across budgets on/off — QoS
    moves WHEN work admits, never WHAT it computes; tests/test_qos.py
    pins the per-token logits too). CPU wall numbers measure
    scheduling on a shared host (``qos_sim_note``)."""
    import numpy as np

    import flax.linen as nn

    from tony_tpu.models import get_model
    from tony_tpu.serve import Request, ServeEngine
    from tony_tpu.serve.qos import QosPolicy

    if on_tpu is None:
        on_tpu = jax.default_backend() not in ("cpu",)
    if n_victim is None:
        n_victim = 16
    if n_aggressor is None:
        n_aggressor = 8
    burst_len = 48                      # 6 pool blocks per admission
    rng = np.random.RandomState(seed)
    model = get_model("llama-tiny", n_layers=2)
    toks0 = jnp.zeros((1, 16), jnp.int32)
    params = nn.unbox(model.init(jax.random.PRNGKey(seed), toks0))["params"]
    params = jax.tree.map(
        lambda a: a.astype(jnp.bfloat16) if a.dtype == jnp.float32 else a,
        params)

    def build(tag: str, **kw) -> ServeEngine:
        # 64-block pool (ctx 64 / block 8 x 8 running): the burst's 6
        # blocks per admission make the aggressor's 1/4 fair share (16
        # blocks) genuinely binding mid-trace.
        return ServeEngine(model, params, ctx_max=64, block_size=8,
                           q_block=16, decode_buckets=(8,), max_running=8,
                           tag=f"qos_bench_{tag}", **kw)

    # The workload: the BENCH_r12/r15 floor (short prompts, real
    # generation lengths) tagged "victim", plus a burst of long prompts
    # tagged "aggressor" landing in a tight cluster one third in.
    victim_prompts = [list(rng.randint(0, model.cfg.vocab,
                                       4 + int(rng.randint(9))))
                      for _ in range(n_victim)]
    victim_new = [int(rng.randint(10, 17)) for _ in range(n_victim)]
    agg_prompts = [list(rng.randint(0, model.cfg.vocab, burst_len))
                   for _ in range(n_aggressor)]
    # Long generations too: each burst admission HOLDS its 6+ blocks
    # for many decode steps, so later aggressor admissions genuinely
    # exceed the fair share mid-trace instead of draining before the
    # budget binds.
    agg_new = [int(rng.randint(8, 13)) for _ in range(n_aggressor)]

    # BENCH_r12..r17 calibration protocol: arrival gaps scaled off a
    # measured engine step so the floor overlaps itself on any backend.
    probe = build("probe")
    probe.submit(Request(rid="probe", tokens=victim_prompts[0],
                         max_new_tokens=4))
    probe.run()
    t0 = time.perf_counter()
    probe.submit(Request(rid="probe2", tokens=victim_prompts[0],
                         max_new_tokens=4))
    steps0 = probe._steps
    probe.run()
    step_s = (time.perf_counter() - t0) / max(1, probe._steps - steps0)
    victim_arrivals = np.cumsum(rng.exponential(1.5 * step_s, n_victim))
    t_burst = float(victim_arrivals[n_victim // 3])
    agg_arrivals = t_burst + 0.1 * step_s * np.arange(n_aggressor)

    # One merged trace sorted by arrival; tenant membership remembered
    # by rid so the percentile split and the bitwise victim gate
    # survive the sort (victims keep their relative order, so victim j
    # of the merged trace IS request j of the unloaded reference).
    merged = sorted(
        [(a, p, n, "victim") for a, p, n in zip(victim_arrivals,
                                                victim_prompts,
                                                victim_new)]
        + [(a, p, n, "aggressor") for a, p, n in zip(agg_arrivals,
                                                     agg_prompts,
                                                     agg_new)],
        key=lambda t: t[0])
    arrivals = [t[0] for t in merged]
    prompts = [t[1] for t in merged]
    new_tokens = [t[2] for t in merged]
    tenants = [t[3] for t in merged]
    victim_rids = [f"r{i}" for i, t in enumerate(merged)
                   if t[3] == "victim"]
    agg_rids = [f"r{i}" for i, t in enumerate(merged)
                if t[3] == "aggressor"]

    def pctl(vals, p):
        vals = sorted(vals)
        return vals[min(len(vals) - 1, int(p * (len(vals) - 1) + 0.5))]

    # -- unloaded reference (victim floor alone) -------------------------
    ref_eng = build("reference")
    ref = _drive_serve_trace(ref_eng, victim_prompts, victim_new,
                             list(victim_arrivals))

    # -- budgets off: tags ride, nothing enforces ------------------------
    off_eng = build("budgets_off")
    off = _drive_serve_trace(off_eng, prompts, new_tokens, arrivals,
                             tenants=tenants)

    # -- budgets on: weighted-fair admission -----------------------------
    pol = QosPolicy(classes={"victim": 3.0, "aggressor": 1.0})
    on_eng = build("budgets_on", qos=pol)
    on = _drive_serve_trace(on_eng, prompts, new_tokens, arrivals,
                            tenants=tenants)
    on_stats = on_eng.stats()

    vict_ok = all(
        off["tokens"][rid] == ref["tokens"][f"r{j}"]
        and on["tokens"][rid] == ref["tokens"][f"r{j}"]
        for j, rid in enumerate(victim_rids))
    off_v = [off["latency_ms"][r] for r in victim_rids]
    on_v = [on["latency_ms"][r] for r in victim_rids]
    ref_v = [ref["latency_ms"][r] for r in ref["latency_ms"]]
    out = {
        "metric": "qos_bench",
        "qos_victim_requests": n_victim,
        "qos_aggressor_requests": n_aggressor,
        "qos_aggressor_prompt_tokens": burst_len,
        "qos_pool_blocks": on_eng.cache.n_blocks,
        "qos_weights": {"victim": 3.0, "aggressor": 1.0},
        # The fair-share math the admission scan enforces mid-burst
        # (both tenants active): weight/(sum of active weights) x pool.
        "qos_aggressor_budget_blocks": pol.budget(
            "aggressor", on_eng.cache.n_blocks, ("victim", "aggressor")),
        "qos_victim_budget_blocks": pol.budget(
            "victim", on_eng.cache.n_blocks, ("victim", "aggressor")),
        "backend": jax.default_backend(),
        # The deferral ledger — back-pressure lands on the aggressor
        # as waiting, never as a drop (rejections need a queue cap,
        # unset here) and never on the victim.
        "qos_deferrals_budgeted": on_eng.qos_deferrals,
        "qos_deferrals_unbudgeted": off_eng.qos_deferrals,
        "qos_rejections_budgeted": on_eng.admission_rejections,
        "qos_rejections_unbudgeted": off_eng.admission_rejections,
        # The heartbeat view of the budgeted run: per-tenant lifetime
        # completions from the SAME stats() payload the session and
        # the history plane consume.
        "qos_tenant_completed": {
            t: d["completed"] for t, d in on_stats["tenants"].items()},
        # Wall latencies as measured on this backend (see sim note).
        "qos_victim_p50_ms_unloaded": round(pctl(ref_v, 0.50), 2),
        "qos_victim_p99_ms_unloaded": round(pctl(ref_v, 0.99), 2),
        "qos_victim_p50_ms_unbudgeted": round(pctl(off_v, 0.50), 2),
        "qos_victim_p99_ms_unbudgeted": round(pctl(off_v, 0.99), 2),
        "qos_victim_p50_ms_budgeted": round(pctl(on_v, 0.50), 2),
        "qos_victim_p99_ms_budgeted": round(pctl(on_v, 0.99), 2),
        "qos_victim_p99_isolation_wall": round(
            pctl(off_v, 0.99) / pctl(on_v, 0.99), 3)
        if pctl(on_v, 0.99) else None,
        # What fairness costs the aggressor: its p99 under deferral vs
        # first-come-first-served (the flip side of the victim's win).
        "qos_aggressor_p99_ms_unbudgeted": round(
            pctl([off["latency_ms"][r] for r in agg_rids], 0.99), 2),
        "qos_aggressor_p99_ms_budgeted": round(
            pctl([on["latency_ms"][r] for r in agg_rids], 0.99), 2),
        "qos_numerics_ok": vict_ok and on["tokens"] == off["tokens"],
    }
    if not on_tpu:
        out["qos_sim_note"] = (
            "CPU simulation: wall latencies measure engine scheduling "
            "on a shared host, and the burst's 48-token prefill "
            "launches are artificially cheap next to batched decode "
            "steps on XLA-CPU (the BENCH_r12 executable-alternation "
            "artifact), so qos_victim_p99_isolation_wall understates "
            "what the same deferral buys on metal, where each "
            "aggressor admission costs compute-bound prefill launches "
            "on the victim's critical path (ROOFLINE §14 prices the "
            "fair-share math). The claims that transfer: the deferral "
            "ledger (budgets defer the aggressor, zero deferrals "
            "without budgets, zero drops in both), the per-tenant "
            "completion ledger from the heartbeat schema, and "
            "qos_numerics_ok (victim streams bitwise equal to the "
            "unloaded engine with budgets on or off). Metal wall p99 "
            "rides the real-hardware debt list (ROADMAP)")
    return out
