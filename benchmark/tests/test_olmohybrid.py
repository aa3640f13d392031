"""What the ``olmo-hybrid-7b`` configuration brings to the benchmark: its
configuration file against the published keys, its parameter count against
the file's table, its roofline arithmetic and the cell's numbers by hand,
its plain reference against hand-written ``numpy`` on tiny cases (the
scalar-decay recurrence, the convolution, the q/k-norm's statistic), its
readers on made-up calls, the limits of ``correct`` against the chip's
committed readings, and a rehearsal of the cell on the CPU through the real
control flow."""

import importlib
import json
import time
from types import SimpleNamespace

import numpy as np
import pytest

from benchmark import manifest, modelcfg_olmohybrid as mc
from benchmark import roofline, roofline_olmohybrid as ro

CELL = "olmohybrid.train-16k"
NAME = "olmo-hybrid-7b"
CFG = mc.load(NAME)
V5E = {"platform": "tpu", "kind": "TPU v5 lite", "count": 1}

# The catalog's ``config`` of the architecture, as published.
PUBLISHED = {
    "model_type": "olmo_hybrid", "vocab_size": 100352, "hidden_size": 3840,
    "intermediate_size": 11008, "num_hidden_layers": 32,
    "num_attention_heads": 30, "num_key_value_heads": 30,
    "hidden_act": "silu", "max_position_embeddings": 65536,
    "attention_bias": False, "rms_norm_eps": 1e-06,
    "tie_word_embeddings": False,
    "layer_types": ["linear_attention"] * 3 + ["full_attention"],
    "linear_num_key_heads": 30, "linear_num_value_heads": 30,
    "linear_key_head_dim": 96, "linear_value_head_dim": 192,
    "linear_conv_kernel_dim": 4, "linear_allow_neg_eigval": True,
    "rope_parameters": {"rope_theta": None}}
NEW = ("gdn_ms.olmohybrid", "attn_ms.olmohybrid", "mlp_ms.olmohybrid",
       "mfu.olmohybrid", "gdn_chunk_roofline.olmohybrid",
       "flash_roofline.olmohybrid")


def metric(name, art):
    spec = manifest.metric_file(name)
    reader = importlib.import_module(f"benchmark.readers.{spec['reader']}")
    return reader.read(art, spec.get("args", {}))


# ------------------------------------------------------------- configuration

def test_configuration_file_holds_the_published_keys():
    raw = json.loads((manifest.HERE / "configs" / f"{NAME}.json").read_text())
    entry = next(c for c in manifest.load()["configs"] if c["name"] == NAME)
    assert raw["source"] == entry["source"]
    assert sorted(raw["reduced"]) == sorted(entry["reduced"]) == [
        "linear_num_key_heads", "linear_num_value_heads",
        "num_attention_heads", "num_hidden_layers", "num_key_value_heads",
        "vocab_size"]
    for key, value in PUBLISHED.items():
        if key == "layer_types":
            assert raw[key] == value * 8            # kept whole: 32 entries
        elif key not in raw["reduced"]:
            assert raw[key] == value, key
    # the floors: one whole period, an eighth of the vocabulary; half of
    # the heads, the published counts beside them
    assert raw["num_hidden_layers"] == 4
    assert CFG["kinds"] == ["gdn", "gdn", "gdn", "attn"]
    assert raw["vocab_size"] * 8 == PUBLISHED["vocab_size"]
    assert (CFG["heads"], CFG["kv_heads"], CFG["gdn_heads"]) == (15, 15, 15)
    assert (CFG["heads_total"], CFG["gdn_heads_total"]) == (30, 30)
    assert "two chips share each layer's mixers by heads" in raw["deployment"]
    # every width the cut may not touch
    assert (CFG["hidden"], CFG["ffn"], CFG["head_dim"], CFG["dk"], CFG["dv"],
            CFG["conv"], CFG["neg_eigval"], CFG["eps"]) == (
        3840, 11008, 128, 96, 192, 4, True, 1e-6)
    for part in ("layer", "gdn_projections", "gdn_decay", "gdn_recurrence",
                 "gdn_output", "attention", "training_recipe", "init"):
        assert {"value", "why"} <= set(raw["assumed"][part]), part


def test_parameter_count_is_the_files_table():
    count = mc.param_count(CFG)
    raw = json.loads((manifest.HERE / "configs" / f"{NAME}.json").read_text())
    for part, n in count.items():
        assert raw["parameters"][part] == n, part
    assert count["total"] == 766_241_946
    # by hand: q, k 3840 x 1440, v, z 3840 x 2880, o 2880 x 3840, a, b
    # 3840 x 15, the taps, A_log + dt_bias + the head norm
    assert count["gdn_mixer"] == 2 * 3840 * 1440 + 3 * 3840 * 2880 \
        + 2 * 3840 * 15 + 4 * (1440 + 1440 + 2880) + 15 + 15 + 192
    assert count["attn_mixer"] == 4 * 3840 * 1920 + 2 * 1920
    assert count["ffn"] == 3 * 3840 * 11008
    # 16 B a parameter: 12.26 GB, 77% of the chip
    assert 0.76 < count["total"] * 16 / 16e9 < 0.77


def test_weights_layout_is_the_programs_tree():
    import flax.linen as nn
    import jax
    import jax.numpy as jnp

    from benchmark import weights_olmohybrid as wo
    from tony_tpu.models import get_model

    cfg = mc.tiny(CFG)
    model = get_model(cfg["program"]["model"], **mc.program_kwargs(cfg))
    shapes = nn.meta.unbox(jax.eval_shape(lambda: model.init(
        jax.random.PRNGKey(0), jnp.zeros((1, 16), jnp.int32)))["params"])
    w = wo.make_weights(cfg, 3)
    tree = wo.to_program_tree(w, cfg)
    assert jax.tree.map(lambda a: a.shape, tree) == jax.tree.map(
        lambda a: a.shape, shapes)
    back = wo.from_program_tree(tree, cfg)
    assert set(back) == set(w) and all(back[n] is w[n] for n in w)
    # the same seed, the same leaves; a large seed (the driver's pass 2^31)
    again = wo.make_weights(cfg, 3)
    assert all(bool((again[n] == w[n]).all()) for n in w)
    big = wo.make_weights(cfg, 2 ** 31 + 5)
    assert not bool((big["embed"] == w["embed"]).all())
    # beta fills (0, 2) and the decay is a live one at unit-scale inputs
    a_log = np.asarray(w["L0.a_log"])
    assert (a_log >= 0).all() and (a_log <= np.log(16.0) + 1e-6).all()


# ----------------------------------------------------------------- roofline

def test_the_cells_numbers():
    """The cell's arithmetic by hand at 1 x 16384, 15 held heads."""
    d, t = 3840, 16384
    gdn = 2 * d * 1440 + 2 * d * 2880 + 2 * d * 15 + 2880 * d
    attn = 4 * d * 1920
    ffn = 3 * d * 11008
    params = 3 * gdn + attn + 4 * ffn + d * 12544
    assert ro.matmul_params(CFG) == params == 717_964_800
    mixing = 3 * 21 * 15 * 96 * 192 + 6 * 15 * 128 * (t + 1)
    assert ro.mixer_flops_per_token(CFG, t) == mixing
    flops = ro.train_flops_per_token(CFG, t)
    assert flops == 6 * params + mixing
    assert 73.9e12 < flops * t < 74.0e12            # a step
    # the FFN, whole on this chip beside half the heads, is 67% of a
    # token's FLOPs; the delta rule's own work 0.4%
    assert 0.67 < 6 * 4 * ffn / flops < 0.68
    assert 3 * 21 * 15 * 96 * 192 / flops < 0.005


def test_the_recurrences_work_knows_no_chunk_and_no_padded_lane():
    flops, nbytes = ro.gdn_recurrence(10, 2, 8, 16)
    assert flops == 10 * 2 * 7 * 8 * 16
    assert nbytes == 10 * 2 * ((2 * 8 + 2 * 16) * 2 + 8)
    bflops, bbytes = ro.gdn_recurrence(10, 2, 8, 16, backward=True)
    assert bflops == 2 * flops and bbytes == 10 * 2 * (2 * 104 + 32)
    # the cell's call: 15 heads of 96 x 192 over 16384 tokens, forward —
    # memory-bound: 285 MB at 819 GB/s = 0.35 ms against 0.16 ms of FLOPs
    flops, nbytes = ro.gdn_recurrence(16384, 15, 96, 192)
    assert (flops, nbytes) == (31_708_938_240, 285_081_600)
    least, bound = roofline.least_seconds(flops, nbytes,
                                          roofline.peaks("TPU v5 lite"))
    assert bound == "memory" and 0.34e-3 < least < 0.36e-3


# ---------------------------------------------------------------- reference

def test_reference_recurrence_against_numpy_by_hand():
    import jax.numpy as jnp

    from benchmark import reference_olmohybrid as ref
    rng = np.random.default_rng(0)
    t, h, dk, dv = 6, 2, 3, 5
    q, k = rng.normal(size=(2, t, h, dk))
    v = rng.normal(size=(t, h, dv))
    g = -rng.random((t, h))
    beta = 2 * rng.random((t, h))
    want = np.zeros((t, h, dv))
    for head in range(h):
        s = np.zeros((dk, dv))
        for i in range(t):
            kk = k[i, head][:, None]
            s = np.exp(g[i, head]) * (np.eye(dk) - beta[i, head] * kk @ kk.T) \
                @ s + beta[i, head] * kk @ v[i, head][None, :]
            want[i, head] = s.T @ q[i, head]
    got = ref.recurrence(*(jnp.asarray(a, jnp.float32)
                           for a in (q, k, v, g, beta)))
    np.testing.assert_allclose(got, want, rtol=2e-5, atol=2e-6)


def test_reference_conv_and_qk_norm_by_hand():
    import jax.numpy as jnp

    from benchmark import reference_olmohybrid as ref
    rng = np.random.default_rng(1)
    a, taps = rng.normal(size=(5, 3)), rng.normal(size=(4, 3))
    want = np.zeros_like(a)
    for i in range(5):
        for j in range(4):                      # j steps back
            if i - j >= 0:
                want[i] += taps[3 - j] * a[i - j]
    np.testing.assert_allclose(
        ref.conv(jnp.asarray(a, jnp.float32), jnp.asarray(taps, jnp.float32)),
        want, rtol=1e-5, atol=1e-6)
    # attention over one key is the value itself through W_o; the q/k-norm
    # over two groups takes each half's own mean square
    cfg = dict(mc.tiny(CFG), head_dim=2)
    d, e = 4, 8
    lw = {n: jnp.asarray(rng.normal(size=s), jnp.float32) for n, s in (
        ("wq", (d, e)), ("wk", (d, e)), ("wv", (d, e)), ("wo", (e, d)),
        ("q_norm", (e,)), ("k_norm", (e,)))}
    x = jnp.asarray(rng.normal(size=(1, d)), jnp.float32)
    np.testing.assert_allclose(ref.attn(x, lw, cfg), (x @ lw["wv"]) @ lw["wo"],
                               rtol=1e-5, atol=1e-5)
    x = jnp.asarray(rng.normal(size=(3, d)), jnp.float32)
    assert not np.allclose(ref.attn(x, lw, cfg), ref.attn(x, lw, cfg, 2))


# ------------------------------------------------------------------ manifest

def test_the_manifest_finds_every_new_file():
    bench = manifest.load()
    assert manifest.validate(bench) == []
    assert len(bench["workloads"]) >= 6
    cell = manifest.cell(bench, CELL)
    assert cell["chips"] == 1 and cell["config"] == NAME
    assert cell["traffic"] == "train-16k"
    wl = manifest.workload_file(CELL)
    assert wl["driver"] == "train_olmohybrid" and wl["job"]["seq"] == 16384
    assert wl["job"]["batch"] == 1
    importlib.import_module(f"benchmark.drivers.{wl['driver']}")
    assert (manifest.HERE / "tasks" / "train_olmohybrid_task.py").is_file()
    for module in ("reference_olmohybrid", "weights_olmohybrid",
                   "modelcfg_olmohybrid", "roofline_olmohybrid"):
        assert (manifest.HERE / f"{module}.py").is_file()
    mine = {m["name"] for m in manifest.cell_metrics(bench, cell,
                                                     "per_layer")}
    assert set(NEW) <= mine
    assert {"step_ms", "device_idle.train", "optimizer_ms.train",
            "head_loss_ms.train", "programs_built.train", "launch_s.train",
            "task_init_s", "state_init_s.train", "build_s.train",
            "loop_step_ms.train", "import_s.train", "backend_init_s.train",
            "first_step_s.train", "init_unspanned_s.train"} <= mine
    assert len(mine) == 14 + len(NEW)
    assert not {"mfu", "flash_roofline.train", "kda_ms.kimilinear"} & mine
    for m in bench["per_layer"]:
        if m["name"] in NEW:
            assert m["workloads"] == [CELL] and m["moves"] == "train_tok_s"
            manifest.metric_file(m["name"])
    assert {m["name"] for m in manifest.cell_metrics(
        bench, cell, "end_to_end")} == {"train_tok_s", "setup_s"}


@pytest.mark.parametrize("name", NEW)
def test_readers_find_nothing_where_the_program_has_nothing(name):
    """The parent commit, another configuration, a rehearsal or an
    untraced run: None, never an exception."""
    from benchmark import modelcfg
    base = {"kind": "train", "cell": "no-such-run", "chips": 1,
            "device": V5E, "job": {"seq": 16384, "batch": 1}, "tok_s": None,
            "trace": None, "trace_events": None,
            "task": {"step_walls_s": [1.0]}}
    assert metric(name, dict(base, model_cfg=CFG)) is None
    fusion = {"planes": [{"name": "/device:TPU:0", "lines": [{
        "name": "XLA Ops", "events": [["%fusion.1 = f32[8] fusion()", 0, 5]]}]}]}
    if name in ("mfu.olmohybrid", "gdn_chunk_roofline.olmohybrid"):
        dense = dict(base, model_cfg=modelcfg.load("mistral-7b-v0.3"),
                     tok_s=30000.0, trace=fusion)
        assert metric(name, dense) is None
    if "roofline" in name:
        # this configuration on a program without the kernels
        assert metric(name, dict(base, model_cfg=CFG, trace=fusion)) is None


def test_scope_metrics_read_the_new_scopes():
    from benchmark import scoperead
    from benchmark.readers import scope_ms
    table = {"gdn": 1e8, "gdn_proj": 4e8, "gdn_conv": 2e8, "gdn_gate": 1e8,
             "gdn_out": 2e8, "gdn_chunk_fwd": 4e8, "gdn_chunk_bwd": 6e8,
             "attn": 1e8, "attn_fwd": 4e8, "attn_bwd_dq": 2e8,
             "attn_bwd_dkv": 1e8, "mlp": 12e8, "optimizer": 1e8}
    for name, want in (("gdn_ms.olmohybrid", 500.0),
                       ("attn_ms.olmohybrid", 200.0),
                       ("mlp_ms.olmohybrid", 300.0)):
        spec = manifest.metric_file(name)["args"]
        art = {"task": {"step_walls_s": [1.0] * 4},
               "scope_self_ns:" + ",".join(spec["known"]): table}
        assert scope_ms.read(art, spec) == pytest.approx(want)
    known = tuple(manifest.metric_file("gdn_ms.olmohybrid")["args"]["known"])
    for other in ("attn_ms.olmohybrid", "mlp_ms.olmohybrid"):
        assert tuple(manifest.metric_file(other)["args"]["known"]) == known
    layer = "jit(step)/transpose(jvp(HybridDecoder))/layer_2/"
    for path, scope in (
            ("gdn/gdn_proj/wq/dot_general", "gdn_proj"),
            ("gdn/gdn_conv/ssm_conv/mul", "gdn_conv"),
            ("gdn/gdn_gate/wa/dot_general", "gdn_gate"),
            ("gdn/gdn_chunk_fwd/pallas_call", "gdn_chunk_fwd"),
            ("gdn/gdn_chunk_bwd/pallas_call", "gdn_chunk_bwd"),
            ("gdn/gdn_out/wo/dot_general", "gdn_out"),
            ("attn/wq/dot_general", "attn"),
            ("attn/attn_bwd_dkv/pallas_call", "attn_bwd_dkv"),
            ("mlp/w_gate/dot_general", "mlp"),
            ("norm1/mul", "")):
        assert scoperead.scope_of(layer + path, known) == scope


def _art(calls, dur=400_000, steps=2):
    return {"kind": "train", "cell": CELL, "chips": 1, "model_cfg": CFG,
            "device": V5E, "job": {"seq": 16384, "batch": 1},
            "task": {"step_walls_s": [1.0] * steps}, "trace": {"planes": [{
                "name": "/device:TPU:0", "lines": [{
                    "name": "XLA Ops",
                    "events": [[c, 1000 * i, dur]
                               for i, c in enumerate(calls)]}]}]}}


def test_gdn_roofline_is_the_recurrences_work_over_the_calls_time():
    """Each forward call is charged a layer's recurrence forward at the
    published 96 x 192 (not the kernel's 128 x 256 lanes), each backward
    call its backward: two forwards and a backward at 10 ms each."""
    peak = roofline.peaks("TPU v5 lite")
    fwd = roofline.least_seconds(*ro.gdn_recurrence(16384, 15, 96, 192),
                                 peak)[0]
    bwd = roofline.least_seconds(
        *ro.gdn_recurrence(16384, 15, 96, 192, True), peak)[0]
    calls = ["%gdn_chunk_fwd.1 = (bf16[1,16384,3840]) custom-call(bf16[1,16",
             "%gdn_chunk_fwd.2 = (bf16[1,16384,3840]) custom-call(bf16[1,16",
             "%gdn_chunk_bwd.1 = (bf16[1,16384,1920]) custom-call(bf16[1,16",
             "%fusion.3 = bf16[1,16384,3840] fusion(%gdn_chunk_fwd.1)"]
    got = metric("gdn_chunk_roofline.olmohybrid", _art(calls, 10_000_000))
    assert got == pytest.approx(100 * (2 * fwd + bwd) / 0.030, rel=1e-6)
    assert 0 < got < 100


def test_flash_roofline_reads_the_held_heads():
    """``flash_roofline.train``'s reader at this configuration's 15 held
    heads of 128: the forward's work on the forward call, the backward's
    on the dk/dv call."""
    peak = roofline.peaks("TPU v5 lite")
    dims = (1, 15, 15, 16384, 128)
    fwd = roofline.least_seconds(*roofline.flash_fwd(*dims), peak)[0]
    bwd = roofline.least_seconds(*roofline.flash_bwd(*dims), peak)[0]
    calls = ["%attn_fwd.1 = (bf16[1,16384,1920], f32[1,15,16384,1]) "
             "custom-call(bf16[1,16384,1920] %q)",
             "%attn_bwd_dq.1 = bf16[1,16384,1920] "
             "custom-call(bf16[1,16384,1920] %q)",
             "%attn_bwd_dkv.1 = (bf16[1,16384,1920], bf16[1,16384,1920]) "
             "custom-call(bf16[1,16384,1920] %q)"]
    got = metric("flash_roofline.olmohybrid", _art(calls, 20_000_000))
    assert got == pytest.approx(100 * (fwd + bwd) / 0.060, rel=1e-6)


def test_mfu_is_the_models_flops_at_the_rate():
    art = dict(_art([]), tok_s=20000.0)
    want = 100 * ro.train_flops_per_token(CFG, 16384) * 20000 / 197e12
    assert metric("mfu.olmohybrid", art) == pytest.approx(want)
    assert 45 < want < 47


# ---------------------------------------------------- the limits of correct

def _readings():
    wl = manifest.workload_file(CELL)
    return wl["limits"], wl["readings"]["sound"], wl["readings"]["int8"]


def test_every_sound_reading_is_under_every_limit_with_room():
    """The chip's readings are data beside the limits
    (``workloads/olmohybrid.train-16k.json`` ``readings``, each with its
    call): the harness's own rule, ``value <= limit``, holds on every sound
    seed with room (the driver draws fresh seeds). The first calls took the
    widest-leaf numbers over every leaf (``*_all_leaves``: what made wq and
    wk ``weights_olmohybrid.NOISE_LEAVES``); those seeds count for the
    other two numbers."""
    limits, sound, _ = _readings()
    for name, entry in limits.items():
        values = [seed[name] for seed in sound.values() if name in seed]
        assert len(values) >= 10, name
        assert 2 * max(values) <= entry["limit"], (name, max(values))
    # over every leaf the widest was a gdn layer's wq or wk, heavy-tailed
    wide = [seed["grad_norm_gap_all_leaves"] for seed in sound.values()
            if "grad_norm_gap_all_leaves" in seed]
    assert len(wide) >= 12 and max(wide) > 4 * limits["grad_norm_gap"]["limit"]


def test_the_int8_control_ends_incorrect_on_both_seeds():
    """``--control int8``: every control seed is over each of the three
    limits that hear a precision — the median leaf's and the widest sound
    leaf's gradient gap with 2.5x of room, the loss by less —;
    ``param_change_gap`` is a guard between the sound readings and 1."""
    limits, sound, int8 = _readings()
    assert len(int8) >= 4
    for name, room in (("loss_gap", 1.0), ("grad_median_gap", 2.5),
                       ("grad_norm_gap", 2.5)):
        values = [seed[name] for seed in int8.values() if name in seed]
        assert len(values) >= 2, name
        assert min(values) > room * limits[name]["limit"], (name, values)
    assert all(v["param_change_gap"] < limits["param_change_gap"]["limit"]
               < 1 for v in sound.values() if "param_change_gap" in v)


# ------------------------------------------------------------------- a run

TINY_LIMITS = {"loss_gap": 0.2, "grad_median_gap": 0.05,
               "grad_norm_gap": 0.9, "param_change_gap": 0.5}


def drive(**over):
    from benchmark.drivers import train_olmohybrid
    bench = manifest.load()
    args = SimpleNamespace(seed=7, seconds=2.0, trace=0, rehearse=True,
                           control=None, limit_seeds=None)
    vars(args).update(over)
    wl = manifest.workload_file(CELL)
    wl["limits"] = {k: {"limit": v} for k, v in TINY_LIMITS.items()}
    return train_olmohybrid.run(manifest.cell(bench, CELL), wl, args,
                                time.time())


@pytest.fixture(scope="module")
def sound_run():
    return drive()


def test_rehearsal_walks_the_whole_cell(sound_run):
    """`tony submit` of the task, the check steps, warm-up, the window and
    the reference, at a tiny size (limits of the tiny size's own: a
    64-wide model in bfloat16 reads far wider gaps than the cell)."""
    assert sound_run["correct"] is True
    assert sound_run["attempted"] > 0 and sound_run["failed"] == 0
    assert sound_run["end_to_end"]["train_tok_s"] > 0
    task = sound_run["artifacts"]["task"]
    assert task["compiled_in_window"] == 0
    assert set(task["compared"]) == set(TINY_LIMITS)


def test_the_cell_reports_its_metrics(sound_run):
    bench = manifest.load()
    got = manifest.read_layer_metrics(bench, manifest.cell(bench, CELL),
                                      sound_run["artifacts"])
    # no trace and no TPU in a rehearsal: the host-side metrics only, and
    # every device reader returns None instead of raising
    assert {"launch_s.train", "task_init_s"} <= set(got)
    assert not set(NEW) & set(got)


def test_int8_control_runs_the_other_lane(sound_run):
    control = drive(control="int8")
    gaps = lambda r: r["artifacts"]["task"]["compared"]
    assert set(gaps(control)) == set(TINY_LIMITS)
    assert all(gaps(control)[k] != gaps(sound_run)[k] for k in TINY_LIMITS)
