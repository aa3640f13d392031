"""The training job the benchmark submits for the ``glm-4.7-flash``
configuration (``tony submit --framework jax --executes "python
train_glm47flash_task.py"``): ``tasks/train_task.py`` with this
architecture's weights layout (``weights_glm47flash``) and reference
(``reference_glm47flash``) and the model's fused untied head + loss
(``targets`` go to the model, the step's ``loss_of`` passes the scalar
through; the multi-token-prediction module's ``0.3 L_MTP`` is sown by the
model and added by the step: the step's ``aux_loss``; ``L_LM`` is ``loss -
aux_loss``, ``L_MTP`` is ``aux_loss / 0.3``, and the two are compared with
the reference's apart), one worker on one chip. The path a user's script
takes — ``dist.initialize`` -> ``get_model`` -> ``create_train_state`` ->
``make_train_step`` -> ``train_loop`` — with the benchmark's seeded weights
put in place of the initialised ones, and the benchmark's clock around it.

One ``train_loop`` call drives everything, fed by one generator, so that
the step is traced from the same call site in every run (the persistent
compile cache's key holds the innermost frames of the call stack):

  steps 1..2   the check steps: both losses of each, the first gradient's
               norms (from the optimizer's first moment), the weights'
               change, and from step 1 the rows the held experts were
               sent, layer by layer (``moe:rows_held``,
               ``moe:rows_max_expert``, read back after the window, as is
               the last step's ``moe:rows_held_last``: what the window's
               training did to the routing; the reference counts the same
               rows, so the two say how many tokens' expert flipped into
               or out of the held range on rounding)
  warm-up      two more steps, fenced
  [--trace 1]  a few fenced steps under ``jax.profiler``; the rows their
               held experts were sent add up to ``moe:rows_held_traced``
               (what the grouped kernels in the trace multiplied)
  window       fresh batches until the deadline; nothing is read back and
               the host only ever waits for the step before last
  fence

Then the state is freed and the plain reference follows the same two
steps from its own copy of the seeded weights.

Sizes arrive in ``bench_task.json`` beside this file. Prints one
``BENCH {json}`` line per process.
"""

import json
import os
import sys
import time

T_PROCESS = time.time()
CFG = json.load(open("bench_task.json"))
sys.path.insert(0, CFG["root"])

import tony_tpu.distributed as dist  # noqa: E402

dist.initialize()

import jax  # noqa: E402
import jax.numpy as jnp  # noqa: E402
import numpy as np  # noqa: E402
import optax  # noqa: E402

from benchmark import reference, traceread  # noqa: E402
from benchmark import reference_glm47flash  # noqa: E402
from benchmark import weights_glm47flash as weights  # noqa: E402
from tony_tpu import profiler  # noqa: E402
from tony_tpu import train  # noqa: E402
from tony_tpu.models import get_model  # noqa: E402
from tony_tpu.util import enable_compile_cache  # noqa: E402

CHECK_STEPS, WARM_STEPS, TRACE_STEPS, RUN_AHEAD = 2, 2, 6, 2
ADAM_B1 = 0.9
# Every program this process builds or loads from the compile cache, however
# small (new files in the cache's directory would not tell of a program that
# is loaded from it, or is too quick to be kept): none may be in the window.
BUILT = []
jax.monitoring.register_event_duration_secs_listener(
    lambda event, secs, **_: BUILT.append(event)
    if event == "/jax/core/compile/backend_compile_duration" else None)


def progress(what: str) -> None:
    """A line on stderr the driver shows when the job dies or is killed:
    how far a run came (a cold start walks the residual ladder and builds
    the reference's gradient: minutes each)."""
    print(f"[{time.time() - T_PROCESS:7.1f}s] {what}", file=sys.stderr,
          flush=True)


def first_moment(opt_state):
    """The Adam first-moment tree inside an optax state."""
    found = [s.mu for s in jax.tree.leaves(
        opt_state, is_leaf=lambda s: hasattr(s, "mu")) if hasattr(s, "mu")]
    if len(found) != 1:
        raise RuntimeError(f"expected one Adam state, found {len(found)}")
    return found[0]


def leaf_gaps(prog: dict, ref: dict) -> dict:
    """|program norm - reference norm| of every leaf, each against the
    reference's norm of that leaf or of the median leaf, whichever is
    larger (some gradients are all but zero)."""
    floor = float(np.median(list(ref.values())))
    return {n: abs(prog[n] - ref[n]) / max(ref[n], floor) for n in ref}


def worst(gaps: dict, ref: dict) -> tuple:
    """(widest gap, the three widest leaves for the log)."""
    names = sorted(gaps, key=gaps.get, reverse=True)
    return gaps[names[0]], [f"{n} {gaps[n]:.5f} (ref {ref[n]:.3g})"
                            for n in names[:3]]


def compare(prog_g, ref_g, prog_d, ref_d) -> tuple:
    """Three numbers of the first gradient and the weights' change:
    ``grad_median_gap``, the median leaf's gap over every leaf (a precision
    lost anywhere moves every leaf upstream of it, and a median does not
    hear the few leaves that are noise on a given seed);
    ``grad_norm_gap`` and ``param_change_gap``, the widest leaf's, over
    every leaf but ``weights.NOISE_LEAVES`` (the reason is there): what
    one wrong kernel or a step that changes nothing would show."""
    g, d = leaf_gaps(prog_g, ref_g), leaf_gaps(prog_d, ref_d)
    sound = lambda gaps: {n: v for n, v in gaps.items() if n.rsplit(
        ".", 1)[-1] not in weights.NOISE_LEAVES}
    numbers = {"grad_median_gap": float(np.median(list(g.values())))}
    leaves = {}
    numbers["grad_norm_gap"], leaves["grad_norm_gap"] = worst(
        sound(g), ref_g)
    numbers["param_change_gap"], leaves["param_change_gap"] = worst(
        sound(d), ref_d)
    table = {n: [g[n], ref_g[n], d[n], ref_d[n]] for n in g}
    return numbers, leaves, table


def main() -> None:
    cache_dir = enable_compile_cache()
    mcfg, B, S = CFG["model_cfg"], CFG["batch"], CFG["seq"]
    lr, seconds, trace_dir = CFG["learning_rate"], CFG["seconds"], CFG["trace_dir"]
    devs = jax.devices()
    n_proc, pid = jax.process_count(), jax.process_index()
    model = get_model(CFG["program_model"], **CFG["program_kwargs"])
    state = train.create_train_state(
        model, optax.adamw(lr), jnp.zeros((B, S), jnp.int32),
        jax.random.PRNGKey(0))
    step = train.make_train_step(
        loss_of=lambda loss, b: loss,
        apply_kwargs_of=lambda b: {"targets": b["x"]})
    t_init = time.time()

    def host_batch(rng):
        # ids from the configuration's slice of the vocabulary
        return rng.integers(0, mcfg["vocab"], (B, S), dtype=np.int32)

    def device_batch(tokens):
        # Unplaced, as create_train_state leaves its weights: arrays
        # pinned to the device would make the first step a program of its
        # own.
        return {"x": jnp.asarray(tokens)}

    out = {"process": pid, "processes": n_proc, "platform": devs[0].platform,
           "kind": devs[0].device_kind, "count": len(devs),
           "local_count": jax.local_device_count(),
           "compile_cache_dir": cache_dir, "seeds": []}
    for seed in CFG["seeds"]:
        rec = {"seed": seed}
        w0 = weights.make_weights(mcfg, seed)
        # The optimizer state create_train_state made is all zeros and
        # stays; only the initialised weights give way to the seeded ones.
        state = state.replace(params=weights.to_program_tree(w0, mcfg))
        del w0
        progress(f"seed {seed}: weights made")
        rec["t_weights"] = time.time()
        rng = np.random.default_rng([seed, 1])
        check_batches = [host_batch(rng) for _ in range(CHECK_STEPS)]
        done = []            # metrics of every step, as device arrays
        marks = {}

        def counted(st, batch):
            with jax.profiler.TraceAnnotation("bench:train_step_dispatch"):
                st, metrics = step(st, batch)
            done.append((metrics["loss"], metrics["aux_loss"]))
            marks["stats_last"] = metrics["stats"]
            if len(done) == 1:
                marks["stats"] = metrics["stats"]
                rec["t_step1"] = time.time()    # traced, lowered, loaded
                progress("step 1 dispatched")
                rec["built_to_step1"] = len(BUILT)
                marks["mu"] = jax.jit(reference.leaf_norms)(
                    weights.from_program_tree(first_moment(st.opt_state), mcfg))
            if len(done) == CHECK_STEPS:
                # A second copy of the seeded weights beside the state:
                # it has to be gone before the next step's temporaries,
                # and this step's have to be gone before it comes (the
                # step leaves the chip no room for both).
                jax.block_until_ready(st.params)
                w_seed = weights.make_weights(mcfg, seed)
                marks["change"] = jax.block_until_ready(
                    jax.jit(reference.change_norms)(
                        weights.from_program_tree(st.params, mcfg), w_seed))
                del w_seed
            return st, metrics

        def fence():
            with jax.profiler.TraceAnnotation("bench:fence"):
                jax.block_until_ready(done[-1][0])

        def feed():
            for tokens in check_batches:
                yield device_batch(tokens)
            for _ in range(WARM_STEPS):
                yield device_batch(host_batch(rng))
            fence()
            if trace_dir:
                jax.profiler.start_trace(trace_dir)
                marks["trace_t0"] = time.time()
                walls, marks["stats_traced"] = [], []
                for _ in range(TRACE_STEPS):
                    t0 = time.time()
                    yield device_batch(host_batch(rng))
                    fence()
                    walls.append(time.time() - t0)
                    marks["stats_traced"].append(marks["stats_last"])
                marks["trace_window_s"] = time.time() - marks["trace_t0"]
                jax.profiler.stop_trace()
                marks["step_walls_s"] = walls
            progress("window")
            marks["n0"] = len(done)
            marks["built_before"] = len(BUILT)
            marks["t0"] = time.time()
            marks["stamps"] = [marks["t0"]]
            deadline = marks["t0"] + seconds
            while seconds and time.time() < deadline:
                with jax.profiler.TraceAnnotation("bench:make_batch"):
                    batch = device_batch(host_batch(rng))
                yield batch
                jax.block_until_ready(done[-RUN_AHEAD][0])
                marks["stamps"].append(time.time())
            fence()
            marks["t1"] = time.time()
            marks["built_in_window"] = len(BUILT) - marks["built_before"]

        state, _ = train.train_loop(state, counted, batches=feed(),
                                    save_final=False)
        steps = len(done) - marks["n0"]
        stats = jax.local_devices()[0].memory_stats() or {}
        total = [float(x) for x, _ in done]
        mtp = [float(x) / mcfg["mtp_weight"] for _, x in done]   # L_MTP
        losses = [t - mcfg["mtp_weight"] * m
                  for t, m in zip(total, mtp)]                   # L_LM

        def sown(stats, name):
            """The sown ``name`` of one step, layer by layer."""
            return [int(v) for path, leaf
                    in jax.tree_util.tree_leaves_with_path(stats)
                    if name in jax.tree_util.keystr(path)
                    for v in np.asarray(leaf).reshape(-1)]

        rows_layers = sown(marks["stats"], "moe_rows_held")
        rows_held = sum(rows_layers)
        rows_max = max(sown(marks["stats"], "moe_rows_max_expert"))
        rows_last = sum(sown(marks["stats_last"], "moe_rows_held"))
        profiler.count_once("moe:rows_held", rows_held)
        profiler.count_once("moe:rows_max_expert", rows_max)
        profiler.count_once("moe:rows_held_last", rows_last)
        if marks.get("stats_traced"):
            profiler.count_once("moe:rows_held_traced", sum(
                sum(sown(st, "moe_rows_held"))
                for st in marks["stats_traced"]))
        rec.update(
            moe_rows_held=rows_held, moe_rows_max_expert=rows_max,
            moe_rows_held_last=rows_last, moe_rows_held_layers=rows_layers,
            t_process=T_PROCESS, t_init=t_init, t_window=marks["t0"],
            window_s=marks["t1"] - marks["t0"], steps=steps,
            tokens=steps * B * S,
            compiled_in_window=marks["built_in_window"],
            built_to_window=marks["built_before"],
            memory_peak_bytes=stats.get("peak_bytes_in_use"),
            losses_check=losses[:CHECK_STEPS],
            mtp_losses_check=mtp[:CHECK_STEPS], loss_last=total[-1],
            losses_finite=bool(np.all(np.isfinite(total))),
            step_walls_s=marks.get("step_walls_s"),
            # The host's clock between two turns of the window's loop: a
            # stall shows as one long turn, a slower program as all of them.
            window_turns_s=sorted(round(b - a, 3) for a, b in zip(
                marks["stamps"], marks["stamps"][1:]))[-3:])
        prog_g = {n: float(x) / (1 - ADAM_B1) for n, x in marks["mu"].items()}
        prog_d = {n: float(x) for n, x in marks["change"].items()}
        state = state.replace(params=None, opt_state=None)   # free the chip
        done.clear()
        marks.clear()

        progress(f"window over, {steps} steps; the reference")
        t_ref = time.time()
        w_ref = weights.make_weights(mcfg, seed)
        ref_batches = [jnp.asarray(b) for b in check_batches]
        # The rows the reference sends the held experts at step 1: beside
        # the program's, the tokens whose expert flipped across the held
        # range's edge on rounding (a net count, layer by layer).
        ref_rows = [int(n) for n in jax.jit(
            lambda w, b: reference_glm47flash.rows_held(w, b, mcfg))(
                w_ref, ref_batches[0])]
        progress("reference rows counted")
        ref_pairs, ref_g, w_ref = reference_glm47flash.train_steps(
            w_ref, ref_batches, mcfg, lr)
        w_seed = weights.make_weights(mcfg, seed)
        ref_d = jax.jit(reference.change_norms)(w_ref, w_seed)
        ref_losses = [lm for lm, _ in ref_pairs]
        ref_mtp = [m for _, m in ref_pairs]
        ref_g = {n: float(x) for n, x in ref_g.items()}
        ref_d = {n: float(x) for n, x in ref_d.items()}
        del w_ref, w_seed
        numbers, leaves, rec["leaf_table"] = compare(
            prog_g, ref_g, prog_d, ref_d)
        rec.update(
            reference_s=time.time() - t_ref, reference_losses=ref_losses,
            reference_mtp_losses=ref_mtp,
            reference_rows_held_layers=ref_rows,
            compared={
                "loss_gap": max(abs(a - b) for a, b in
                                zip(rec["losses_check"], ref_losses)),
                "mtp_loss_gap": max(abs(a - b) for a, b in zip(
                    rec["mtp_losses_check"], ref_mtp)),
                **numbers},
            worst_leaves=leaves)
        out["seeds"].append(rec)
        progress(f"seed {seed} compared")
        # The state template for the next seed (limits runs only).
        if seed != CFG["seeds"][-1]:
            state = train.create_train_state(
                model, optax.adamw(lr), jnp.zeros((B, S), jnp.int32),
                jax.random.PRNGKey(0))
    if trace_dir and pid == 0:
        traceread.extract(trace_dir, os.path.join(trace_dir, "events.json"))
    print("BENCH " + json.dumps(out), flush=True)


main()
