"""What the ``zaya1-8b`` configuration brings to the benchmark: its
configuration file against the published keys, its parameter count against
the initialised tree, its roofline arithmetic, its plain reference against
a hand-written ``numpy`` CCA on one tiny case, its readers on a recorded
excerpt of a chip run (PR 33's traced run of ``zaya1.train-32k``), and a
rehearsal of the cell on the CPU through the real control flow."""

import importlib
import json
import time
from types import SimpleNamespace

import numpy as np
import pytest

from benchmark import manifest, modelcfg_zaya1 as mc
from benchmark import roofline, roofline_zaya1 as rz

DATA = manifest.HERE / "tests" / "data"
CELL = "zaya1.train-32k"
NAME = "zaya1-8b"
CFG = mc.load(NAME)
V5E = {"platform": "tpu", "kind": "TPU v5 lite", "count": 1}

# The catalog's ``config`` of the architecture, as published.
PUBLISHED = {
    "attention_bias": False, "cca_time0": 2, "cca_time1": 2, "head_dim": 128,
    "hidden_act": "silu", "hidden_size": 2048,
    "layer_types": ["hybrid"] * 40, "lm_head_bias": False,
    "max_position_embeddings": 131072, "model_type": "zaya",
    "moe_intermediate_size": 2048, "num_attention_heads": 8,
    "num_experts": 16, "num_experts_per_tok": 1, "num_hidden_layers": 40,
    "num_key_value_heads": 2, "partial_rotary_factor": 0.5,
    "rms_norm_eps": 1e-05,
    "rope_parameters": {
        "hybrid": {"partial_rotary_factor": 0.5, "rope_theta": 5000000,
                   "rope_type": "default"},
        "hybrid_sliding": {"partial_rotary_factor": 0.5, "rope_theta": 10000,
                           "rope_type": "default"},
        "rope_type": "default"},
    "router_hidden_size": 256, "sliding_window": None,
    "tie_word_embeddings": True, "vocab_size": 262272}
NEW = ("attn_ms.zaya1", "cca_mix_ms.zaya1", "moe_ms.zaya1",
       "moe_router_ms.zaya1", "mfu.zaya1", "flash_roofline.zaya1",
       "moe_gmm_roofline.zaya1", "moe_rows_max.zaya1",
       "moe_rows_drift.zaya1")


# ------------------------------------------------------------- configuration

def test_configuration_file_holds_the_published_keys():
    raw = json.loads((manifest.HERE / "configs" / f"{NAME}.json").read_text())
    entry = next(c for c in manifest.load()["configs"] if c["name"] == NAME)
    assert raw["source"] == entry["source"]
    assert sorted(raw["reduced"]) == sorted(entry["reduced"]) == [
        "num_experts", "num_hidden_layers", "vocab_size"]
    for key, value in PUBLISHED.items():
        if key not in raw["reduced"]:
            assert raw[key] == value, key
    assert raw["num_hidden_layers"] == 4                 # the floor
    assert raw["num_experts"] == 8 and raw["held"]["router_outputs"] == 16
    assert raw["vocab_size"] * 2 == PUBLISHED["vocab_size"]
    assert "two chips share each layer" in raw["deployment"]
    left_out = [k for k, v in raw["assumed"].items()
                if str(v["value"]).startswith("left out")]
    assert sorted(left_out) == ["depth_skipping_expert", "residual_scales",
                                "router_balancing_biases"]


def test_parameter_count_is_the_files_and_the_trees():
    import jax
    import jax.numpy as jnp
    from tony_tpu.models import get_model

    count = mc.param_count(CFG)
    raw = json.loads((manifest.HERE / "configs" / f"{NAME}.json").read_text())
    stated = raw["parameters"]
    assert count["total"] == stated["total"] == 696_183_816
    assert count["layer"] == stated["per_layer"]
    assert count["experts_held"] == stated["per_layer_experts_held"]
    for part in ("attention", "cca_mix", "router", "norms"):
        assert count[part] == stated["per_layer_outside_experts"][part]
    # 16 B a parameter: 11.1 GB, 70% of the chip
    assert 0.69 < count["total"] * 16 / 16e9 < 0.70
    # the tree ``model.init`` would make, by shape alone
    model = get_model(CFG["program"]["model"],
                      **mc.program_kwargs(CFG, 32768))
    tree = jax.eval_shape(model.init, jax.random.PRNGKey(0),
                          jnp.zeros((1, 128), jnp.int32))["params"]
    assert sum(int(np.prod(a.shape))
               for a in jax.tree.leaves(tree)) == count["total"]
    assert "lm_head_kernel" not in tree                   # one tied table


def test_weights_layout_is_the_programs_tree():
    import jax
    import jax.numpy as jnp
    from benchmark import weights_zaya1 as wz
    from tony_tpu.models import get_model

    tiny = mc.tiny(CFG)
    model = get_model(tiny["program"]["model"], **mc.program_kwargs(tiny, 64))
    tree = jax.eval_shape(model.init, jax.random.PRNGKey(0),
                          jnp.zeros((1, 64), jnp.int32))["params"]
    mine = wz.to_program_tree(wz.make_weights(tiny, 3))
    import flax.linen as nn
    shapes = lambda t: jax.tree.map(lambda a: tuple(a.shape), t)
    assert shapes(nn.unbox(tree)) == shapes(mine)
    back = wz.from_program_tree(mine)
    assert sorted(back) == sorted(wz.leaf_specs(tiny))


def test_the_cells_numbers():
    """The workload file's arithmetic (GFLOP a trained token, forward +
    backward) and the cut's shares."""
    per = lambda params: 6 * CFG["layers"] * params / 1e9
    d, q, kv = 2048, 1024, 256
    assert per(d * (q + 2 * kv) + q * d) == pytest.approx(0.126, abs=1e-3)
    assert per(2 * 10 * 128 * 128) == pytest.approx(0.008, abs=1e-3)
    assert per(0.5 * 3 * d * 2048) == pytest.approx(0.151, abs=1e-3)
    assert per(d * 256 + 2 * 256 * 256 + 256 * 16) == pytest.approx(
        0.016, abs=1e-3)
    assert 6 * d * CFG["vocab"] / 1e9 == pytest.approx(1.61, abs=5e-3)
    attn = lambda seq: CFG["layers"] * rz.attention_flops_per_token(
        CFG, seq) / 1e9
    assert attn(32768) == pytest.approx(0.94, abs=5e-3)
    assert attn(8192) == pytest.approx(0.235, abs=5e-3)
    total = rz.train_flops_per_token(CFG, 32768) / 1e9
    assert total == pytest.approx(2.85, abs=5e-3)
    assert (total - 1.61) / total == pytest.approx(0.43, abs=0.01)
    short = rz.train_flops_per_token(CFG, 8192) / 1e9
    assert (short - 1.61) / short == pytest.approx(0.25, abs=0.01)
    assert 32768 // CFG["experts"] == 2048          # rows a held expert


# ----------------------------------------------------------- the reference

def test_reference_cca_against_numpy_by_hand():
    """One tiny case, float64 ``numpy`` loops written from the equations:
    the value head that reads the token before, both convolutions, the
    two means, the norms, the temperature and the half rotation."""
    import jax.numpy as jnp
    from benchmark import reference_zaya1 as ref

    t, dm, h, g, d = 5, 6, 4, 2, 4
    cfg = {"heads": h, "kv_heads": g, "head_dim": d, "eps": 1e-5,
           "rope_theta": 100.0, "rope_fraction": 0.5}
    rng = np.random.default_rng(0)
    lw = {"wq": rng.normal(size=(dm, h * d)), "wk": rng.normal(size=(dm, g * d)),
          "wv": rng.normal(size=(dm, g * d)),
          "conv0_w": rng.normal(size=(2, (h + g) * d)),
          "conv0_b": rng.normal(size=((h + g) * d,)),
          "conv1_w": rng.normal(size=(2, h + g, d, d)),
          "conv1_b": rng.normal(size=((h + g) * d,)),
          "tau": np.array([1.5, 0.5])}
    x = rng.normal(size=(t, dm))
    q, k, v = ref.qkv(jnp.asarray(x, jnp.float32),
                      {n: jnp.asarray(a, jnp.float32) for n, a in lw.items()},
                      cfg)

    qt = (x @ lw["wq"]).reshape(t, h, d)
    kt = (x @ lw["wk"]).reshape(t, g, d)
    u = np.concatenate([qt.reshape(t, -1), kt.reshape(t, -1)], -1)
    want_v = np.zeros((t, g, d))
    for s in range(t):
        want_v[s, 0] = x[s] @ lw["wv"][:, :d]                 # the token
        if s:
            want_v[s, 1] = x[s - 1] @ lw["wv"][:, d:]         # the one before
    np.testing.assert_allclose(v, want_v, rtol=1e-5, atol=1e-5)
    a0, a1 = lw["conv0_w"][1], lw["conv0_w"][0]
    c1 = np.array([a0 * u[s] + (a1 * u[s - 1] if s else 0) + lw["conv0_b"]
                   for s in range(t)]).reshape(t, h + g, d)
    big0, big1 = lw["conv1_w"][1], lw["conv1_w"][0]
    c2 = np.zeros((t, h + g, d))
    for s in range(t):
        for j in range(h + g):
            c2[s, j] = c1[s, j] @ big0[j] + lw["conv1_b"].reshape(-1, d)[j]
            if s:
                c2[s, j] += c1[s - 1, j] @ big1[j]
    want_q, want_k = np.zeros((t, h, d)), np.zeros((t, g, d))
    for s in range(t):
        for head in range(h):
            want_q[s, head] = c2[s, head] + (
                qt[s, head] + kt[s, head // 2]) / 2
        for grp in range(g):
            want_k[s, grp] = c2[s, h + grp] + (
                kt[s, grp] + qt[s, 2 * grp:2 * grp + 2].mean(0)) / 2
    unit = lambda a: a / np.sqrt((a * a).mean(-1, keepdims=True) + 1e-5)
    want_q, want_k = unit(want_q), unit(want_k) * lw["tau"][:, None]

    def turn(a):
        out = a.copy()
        for s in range(t):
            for i in range(1):                # 2 of 4 dims: one pair
                ang = s * 100.0 ** (-2 * i / 2)
                x1, x2 = a[s, :, 2 * i], a[s, :, 2 * i + 1]
                out[s, :, 2 * i] = x1 * np.cos(ang) - x2 * np.sin(ang)
                out[s, :, 2 * i + 1] = x1 * np.sin(ang) + x2 * np.cos(ang)
        return out
    np.testing.assert_allclose(q, turn(want_q), rtol=2e-4, atol=2e-5)
    np.testing.assert_allclose(k, turn(want_k), rtol=2e-4, atol=2e-5)
    # length sqrt(d) before the temperature; the unrotated half untouched
    np.testing.assert_allclose(np.sqrt((np.asarray(q) ** 2).sum(-1)),
                               np.sqrt(d), rtol=1e-3)
    np.testing.assert_allclose(np.asarray(q)[..., 2:], want_q[..., 2:],
                               rtol=2e-4, atol=2e-5)


def test_reference_router_gates_by_the_probability_itself():
    """One token by hand: the state of the layer before enters through
    gamma, the argmax expert's output is scaled by its own probability,
    and a share that does not hold it gives nothing."""
    import jax
    import jax.numpy as jnp
    from benchmark import reference_zaya1 as ref

    rng = np.random.default_rng(1)
    dm, r, e, f = 6, 4, 4, 3
    cfg = {"eps": 1e-5, "experts_held": e, "expert_offset": 0}
    lw = {"r_down": rng.normal(size=(dm, r)), "r_bdown": rng.normal(size=r),
          "r_gamma": rng.normal(size=r), "r_norm": 1 + 0.1 * rng.normal(size=r),
          "r_w1": rng.normal(size=(r, r)), "r_b1": rng.normal(size=r),
          "r_w2": rng.normal(size=(r, r)), "r_b2": rng.normal(size=r),
          "r_w3": 2 * rng.normal(size=(r, e)),
          "w_gate": rng.normal(size=(e, dm, f)),
          "w_up": rng.normal(size=(e, dm, f)),
          "w_down": rng.normal(size=(e, f, dm))}
    y, r_prev = rng.normal(size=(1, dm)), rng.normal(size=(1, r))
    jl = {n: jnp.asarray(a, jnp.float32) for n, a in lw.items()}
    p, state = ref.router(jnp.asarray(y, jnp.float32),
                          jnp.asarray(r_prev, jnp.float32), jl, cfg)

    want_r = y @ lw["r_down"] + lw["r_bdown"] + lw["r_gamma"] * r_prev
    z = want_r / np.sqrt((want_r ** 2).mean() + 1e-5) * lw["r_norm"]
    gelu = lambda a: np.asarray(jax.nn.gelu(jnp.asarray(a), approximate=False))
    z = gelu(gelu(z @ lw["r_w1"] + lw["r_b1"]) @ lw["r_w2"] + lw["r_b2"])
    logits = (z @ lw["r_w3"])[0]
    want_p = np.exp(logits - logits.max()) / np.exp(logits - logits.max()).sum()
    np.testing.assert_allclose(state, want_r, rtol=1e-5, atol=1e-5)
    np.testing.assert_allclose(p[0], want_p, rtol=1e-4, atol=1e-6)
    best = int(want_p.argmax())
    silu = lambda a: a / (1 + np.exp(-a))
    ffn = (silu(y @ lw["w_gate"][best]) * (y @ lw["w_up"][best])) \
        @ lw["w_down"][best]
    whole = ref.experts(jnp.asarray(y, jnp.float32), p, jl, cfg)
    np.testing.assert_allclose(whole, want_p[best] * ffn, rtol=1e-4,
                               atol=1e-5)
    assert want_p[best] < 1.0                    # not renormalised to 1
    other = (best + 1) % e
    share = {n: (a[other:other + 1] if n.startswith("w_") else a)
             for n, a in jl.items()}
    assert float(jnp.abs(ref.experts(jnp.asarray(y, jnp.float32), p, share,
                                     cfg, 1, other)).max()) == 0.0


# ------------------------------------------------------------------- readers

def metric(name, art):
    spec = manifest.metric_file(name)
    reader = importlib.import_module(f"benchmark.readers.{spec['reader']}")
    return reader.read(art, spec.get("args", {}))


def test_the_manifest_finds_every_new_file():
    bench = manifest.load()
    assert manifest.validate(bench) == []
    cell = manifest.cell(bench, CELL)
    assert cell["chips"] == 1 and cell["config"] == NAME
    wl = manifest.workload_file(CELL)
    assert wl["driver"] == "train_zaya1" and wl["job"]["seq"] == 32768
    assert wl["job"]["batch"] == 1
    importlib.import_module(f"benchmark.drivers.{wl['driver']}")
    assert (manifest.HERE / "tasks" / "train_zaya1_task.py").is_file()
    mine = {m["name"] for m in manifest.cell_metrics(bench, cell,
                                                     "per_layer")}
    assert set(NEW) <= mine
    assert {"step_ms", "device_idle.train", "optimizer_ms.train",
            "head_loss_ms.train", "programs_built.train", "launch_s.train",
            "task_init_s", "state_init_s.train", "build_s.train",
            "loop_step_ms.train"} <= mine
    assert not {"mfu", "flash_roofline.train", "mfu.keyevl2"} & mine
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
            "device": V5E, "job": {"seq": 32768}, "tok_s": None,
            "trace": None, "trace_events": None,
            "task": {"step_walls_s": [1.0]}}
    assert metric(name, dict(base, model_cfg=CFG)) is None
    if name == "flash_roofline.zaya1":
        return      # the shared flash reader reads any dense decoder's calls
    dense = dict(base, model_cfg=modelcfg.load("mistral-7b-v0.3"),
                 tok_s=30000.0, trace={"planes": [{
                     "name": "/device:TPU:0", "lines": [{
                         "name": "XLA Ops", "events": [
                             ["%fusion.1 = f32[8] fusion()", 0, 5]]}]}]})
    assert metric(name, dense) is None


def test_scope_metrics_read_the_new_scopes():
    from benchmark import scoperead
    from benchmark.readers import scope_ms
    table = {"attn": 4e8, "cca_proj": 2e8, "cca_mix": 1e8, "attn_fwd": 8e8,
             "attn_bwd_dq": 4e8, "attn_bwd_dkv": 5e8, "moe": 1e8,
             "moe_router": 0.5e8, "moe_experts": 2e8, "moe_gmm": 1e8,
             "moe_gmm_t": 1e8, "moe_tgmm": 1e8, "optimizer": 1e8}
    for name, want in (("attn_ms.zaya1", 600.0), ("cca_mix_ms.zaya1", 25.0),
                       ("moe_ms.zaya1", 162.5),
                       ("moe_router_ms.zaya1", 12.5)):
        spec = manifest.metric_file(name)["args"]
        art = {"task": {"step_walls_s": [1.0] * 4},
               "scope_self_ns:" + ",".join(spec["known"]): table}
        assert scope_ms.read(art, spec) == pytest.approx(want)
    # one table a run: the four scope metrics share the list they read by;
    # an operation belongs to the innermost scope on its path
    known = tuple(manifest.metric_file("attn_ms.zaya1")["args"]["known"])
    for other in ("cca_mix_ms.zaya1", "moe_ms.zaya1", "moe_router_ms.zaya1"):
        assert tuple(manifest.metric_file(other)["args"]["known"]) == known
    block = "jit(step)/transpose(jvp(Transformer))/layers/block/"
    for path, scope in (
            ("attn/attn._latent/cca_proj/wq/dot_general", "cca_proj"),
            ("attn/attn._latent/checkpoint/cca_mix/ssm_conv/mul", "cca_mix"),
            ("attn/attn._latent/attn_fwd/pallas_call", "attn_fwd"),
            ("attn/attn._latent/wo/dot_general", "attn"),
            ("moe_mlp/moe/moe_router/dot_general", "moe_router"),
            ("moe_mlp/moe/moe_experts/moe_gmm/pallas_call", "moe_gmm")):
        assert scoperead.scope_of(block + path, known) == scope


def test_grouped_matmul_roofline_counts_the_rows_that_ran(monkeypatch):
    """Work from the traced steps' own sown rows and the program's chunk
    count, not from a call's shape: 2 steps x 4 layers x 8 chunks sent
    131072 rows, so a call multiplied 2048 of its 4096-row buffer."""
    from benchmark.readers import timeline
    calls = [
        "%moe_gmm.7 = bf16[4096,2048] custom-call(s32[8] %a, s32[8] %b",
        "%moe_gmm_t.3 = bf16[4096,2048] custom-call(s32[8] %a, s32[8]",
        "%moe_tgmm.2 = bf16[8,2048,2048] custom-call(s32[8] %a, s32[8",
        "%fusion.9 = bf16[4096,2048] fusion(bf16[4096,2048] %moe_gmm.7)"]
    art = {"kind": "train", "cell": CELL, "chips": 1, "model_cfg": CFG,
           "device": V5E, "job": {"seq": 32768, "batch": 1},
           "task": {"step_walls_s": [1.0, 1.0]}, "trace": {"planes": [{
               "name": "/device:TPU:0", "lines": [{
                   "name": "XLA Ops",
                   "events": [[c, 1000 * i, 400_000]
                              for i, c in enumerate(calls)]}]}]}}
    counters = {"moe:chunks": 8}
    monkeypatch.setattr(timeline, "task_timeline",
                        lambda art: {"counters": counters})
    assert metric("moe_gmm_roofline.zaya1", art) is None     # no rows counted
    counters["moe:rows_held_traced"] = 131072
    flops, nbytes = rz.grouped_matmul(2048, 8, 8, 2048, 2048)
    assert flops == 2 * 2048 * 2048 * 2048
    assert nbytes == 2048 * 4096 * 2 + 8 * 2048 * 2048 * 2 / 8
    least, bound = roofline.least_seconds(
        flops, nbytes, roofline.peaks("TPU v5 lite"))
    assert bound == "compute"
    assert metric("moe_gmm_roofline.zaya1", art) == pytest.approx(
        100 * least / 400e-6, rel=1e-6)
    # twice the rows in the same calls: twice the share, no call's shape
    # having changed
    counters["moe:rows_held_traced"] = 262144
    assert metric("moe_gmm_roofline.zaya1", art) == pytest.approx(
        200 * least / 400e-6, rel=0.02)
    # a program without the kernels (the parent): nothing to read
    art["trace"]["planes"][0]["lines"][0]["events"] = [[calls[3], 0, 5]]
    assert metric("moe_gmm_roofline.zaya1", art) is None


def test_rows_max_is_the_fullest_expert_over_the_mean(monkeypatch):
    from benchmark.readers import timeline
    counters = {"moe:rows_held": 65536, "moe:rows_max_expert": 3072,
                "moe:experts_held": 8}
    monkeypatch.setattr(timeline, "task_timeline",
                        lambda art: {"counters": counters})
    got = metric("moe_rows_max.zaya1", {"model_cfg": CFG})
    assert got == pytest.approx(3072 / 2048)          # 65536 / (8 x 4)


def test_rows_drift_is_the_last_steps_rows_over_step_ones(monkeypatch):
    """What the window's training did to the routing: the absent experts
    add nothing, so the router learns the held half (1.91 in the recorded
    run: all but 5 of a step's 131072 rows)."""
    from benchmark.readers import timeline
    counters = {"moe:rows_held": 68544, "moe:rows_held_last": 131067}
    monkeypatch.setattr(timeline, "task_timeline",
                        lambda art: {"counters": counters})
    assert metric("moe_rows_drift.zaya1", {"model_cfg": CFG}) \
        == pytest.approx(131067 / 68544)


@pytest.fixture
def traced(monkeypatch):
    """Artifacts as ``drivers/train_zaya1.py`` hands them to the readers,
    with an excerpt of PR 33's traced run (seed 3300000101): every flash
    and grouped-matmul custom call of the first of its six fenced steps
    (names cut), one fusion, and the run's own counters."""
    from benchmark.readers import timeline
    rec = json.loads((DATA / "zaya1_trace_excerpt.json").read_text())
    monkeypatch.setattr(timeline, "task_timeline",
                        lambda art: {"counters": rec["counters"]})
    return {"kind": "train", "cell": CELL, "chips": 1, "model_cfg": CFG,
            "device": V5E, "job": {"seq": 32768, "batch": 1},
            "task": {"step_walls_s": [1.26] * 6}, "tok_s": 32768 / 1.257,
            "trace": {"planes": rec["planes"]}}


def test_readers_on_the_recorded_run(traced):
    """What the run itself printed over all six steps: 56.02 %, 57.41 %,
    1.661, 1.912, 37.89 %; the excerpt's one step reads the same to a
    tenth. No share near 100 %."""
    assert metric("flash_roofline.zaya1", traced) == pytest.approx(
        56.00, abs=0.01)
    assert metric("moe_gmm_roofline.zaya1", traced) == pytest.approx(
        57.50, abs=0.01)
    assert metric("moe_rows_max.zaya1", traced) == pytest.approx(
        1.661, abs=0.001)
    assert metric("moe_rows_drift.zaya1", traced) == pytest.approx(
        1.912, abs=0.001)
    assert metric("mfu.zaya1", traced) == pytest.approx(37.73, abs=0.01)


def test_the_recorded_calls_are_those_of_the_four_layers(traced):
    from benchmark import traceread
    ops = [name for plane in traceread.device_planes(traced["trace"])
           for name, _, _ in traceread.op_events(plane)]
    count = lambda prefix: sum(n.startswith(prefix + ".") for n in ops)
    # a step: the forward runs twice a layer (remat), dq and dk/dv once
    assert count("%attn_fwd") == 8
    assert count("%attn_bwd_dq") == count("%attn_bwd_dkv") == 4
    # a (layer, chunk): gate and up forward and again in the chunk's
    # recomputation, down in both, three transposed and three weight-
    # gradient calls: 4 layers x 8 chunks (``moe:chunks``) of each
    chunks = 4 * traced_counter(traced, "moe:chunks")
    assert count("%moe_gmm") == 6 * chunks
    assert count("%moe_gmm_t") == count("%moe_tgmm") == 3 * chunks


def traced_counter(art, name):
    from benchmark.readers import timeline
    return timeline.task_timeline(art)["counters"][name]


def test_mfu_is_the_models_flops_at_the_rate():
    art = {"kind": "train", "chips": 1, "device": V5E, "model_cfg": CFG,
           "job": {"seq": 32768}, "tok_s": 30000.0}
    assert metric("mfu.zaya1", art) == pytest.approx(
        100 * 2.8514e9 * 30000 / 197e12, rel=1e-3)


# ------------------------------------------------------------------- a run

TINY_LIMITS = {"loss_gap": 0.2, "grad_median_gap": 0.05,
               "grad_norm_gap": 0.5, "param_change_gap": 0.5}


def drive(**over):
    from benchmark.drivers import train_zaya1
    bench = manifest.load()
    args = SimpleNamespace(seed=7, seconds=2.0, trace=0, rehearse=True,
                           control=None, limit_seeds=None)
    vars(args).update(over)
    wl = manifest.workload_file(CELL)
    wl["limits"] = {k: {"limit": v} for k, v in TINY_LIMITS.items()}
    return train_zaya1.run(manifest.cell(bench, CELL), wl, args, time.time())


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
    # four of eight experts held, one a token: about half of each tiny
    # layer's 64 rows land here; the reference counts the same rows but
    # for the tokens whose expert flips on bfloat16 rounding
    assert len(task["moe_rows_held_layers"]) == 2
    assert 0 < task["moe_rows_held"] < 2 * 64
    assert sum(abs(a - b) for a, b in zip(
        task["moe_rows_held_layers"],
        task["reference_rows_held_layers"])) <= 8


def test_the_cell_reports_its_metrics(sound_run):
    bench = manifest.load()
    got = manifest.read_layer_metrics(bench, manifest.cell(bench, CELL),
                                      sound_run["artifacts"])
    # no trace and no TPU in a rehearsal: the host-side metrics only, and
    # every device reader returns None instead of raising
    assert {"launch_s.train", "task_init_s", "moe_rows_max.zaya1"} <= set(got)
    assert not {"mfu.zaya1", "flash_roofline.zaya1",
                "moe_gmm_roofline.zaya1"} & set(got)


def test_int8_control_runs_the_other_lane(sound_run):
    """The control walks the same flow on the int8 lane and reads other
    numbers. (How much wider is read on the chip: a 64-wide model in
    bfloat16 rounds as hard as the int8 grid does.)"""
    control = drive(control="int8")
    gaps = lambda r: r["artifacts"]["task"]["compared"]
    assert set(gaps(control)) == set(TINY_LIMITS)
    assert all(gaps(control)[k] != gaps(sound_run)[k] for k in TINY_LIMITS)
